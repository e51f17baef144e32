"""Mixture-of-Experts FFN with expert parallelism.

The reference has no MoE/expert parallelism anywhere (SURVEY §2.3: EP —
"No"); this is TPU-native new capability extending the encoder/decoder
blocks.  The design is the GShard/Switch dense-dispatch formulation, which
is the XLA-friendly one: token→expert routing becomes two einsums against
0/1 dispatch/combine tensors with fully static shapes, so GSPMD turns the
(tokens sharded on ``data``) × (experts sharded on ``expert``) contraction
into exactly the all_to_all pattern a hand-written MPI MoE would use — no
ragged transfers, no host control flow.

Routing: top-k gating (k=1 Switch, k=2 GShard default) with per-expert
capacity ``C = ceil(capacity_factor · k · N / E)``; overflow tokens fall
through the residual connection (their combine weights are zeroed).  The
load-balance auxiliary loss (Switch eq. 4) is sown into the ``losses``
collection; DLTrainer adds every sown loss to the objective.

This is the trainer's layer.  Serving uses
:mod:`synapseml_tpu.models.llm.experts`, which has no capacity, drops no
token and computes one chip's share of the experts exactly.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


class MoEFFN(nn.Module):
    """Drop-in FFN replacement: (B, S, D) → (B, S, D) through E experts."""
    num_experts: int
    d_ff: int
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        B, S, D = x.shape
        E, K = self.num_experts, self.top_k
        N = B * S
        C = max(1, int(self.capacity_factor * K * N / E + 0.999))
        tokens = x.reshape(N, D)

        # router (replicated small matmul, f32 for stable softmax)
        w_router = self.param(
            "router", nn.with_partitioning(
                nn.initializers.truncated_normal(0.02), ("embed", None)),
            (D, E), jnp.float32)
        probs = jax.nn.softmax(
            jnp.einsum("nd,de->ne", tokens.astype(jnp.float32), w_router),
            axis=-1)                                       # (N, E)

        gate_vals, gate_idx = lax.top_k(probs, K)          # (N, K)
        onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (N, K, E)

        # position of each (token, slot) inside its expert's capacity
        # buffer: slot-major cumulative count (slot-0 assignments of every
        # token beat all slot-1 assignments, the Switch priority rule)
        flat = onehot.transpose(1, 0, 2).reshape(K * N, E)
        pos = jnp.cumsum(flat, axis=0) - flat              # (K·N, E)
        pos_tok = jnp.sum(pos * flat, axis=-1).reshape(K, N).T.astype(jnp.int32)
        keep = (pos_tok < C).astype(jnp.float32)
        gates = gate_vals * keep                           # dropped → 0

        # (N, K, E, C) assignment → dense dispatch/combine tensors
        slot_oh = jax.nn.one_hot(pos_tok, C, dtype=jnp.float32) * keep[..., None]
        assign = onehot[:, :, :, None] * slot_oh[:, :, None, :]
        dispatch = assign.sum(1)                           # (N, E, C) ∈ {0,1}
        combine = (gates[:, :, None, None] * assign).sum(1)

        # expert-parallel compute: buffers sharded on the expert axis, the
        # dispatch einsum is the all_to_all boundary
        expert_in = jnp.einsum("nec,nd->ecd", dispatch, tokens.astype(jnp.float32))
        expert_in = nn.with_logical_constraint(
            expert_in.astype(self.dtype), ("expert", None, "embed"))

        w_up = self.param(
            "w_up", nn.with_partitioning(
                nn.initializers.truncated_normal(0.02),
                ("expert", "embed", "mlp")),
            (E, D, self.d_ff), jnp.float32)
        w_down = self.param(
            "w_down", nn.with_partitioning(
                nn.initializers.truncated_normal(0.02),
                ("expert", "mlp", "embed")),
            (E, self.d_ff, D), jnp.float32)

        h = jnp.einsum("ecd,edf->ecf", expert_in, w_up.astype(self.dtype))
        h = nn.gelu(h)
        expert_out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(self.dtype))
        expert_out = nn.with_logical_constraint(
            expert_out, ("expert", None, "embed"))

        out = jnp.einsum("nec,ecd->nd", combine.astype(self.dtype), expert_out)

        # Switch load-balance loss: E · Σ_e f_e · p_e (f = dispatch
        # fraction, p = mean router prob); scalar per layer, summed by the
        # trainer from the "losses" collection
        f_e = jnp.mean(onehot[:, 0, :], axis=0)
        p_e = jnp.mean(probs, axis=0)
        self.sow("losses", "moe_aux",
                 self.aux_loss_weight * E * jnp.sum(f_e * p_e))

        return out.reshape(B, S, D)
