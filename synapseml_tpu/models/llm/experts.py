"""The serving expert layer: one program's share of a mixture of experts.

A deployment divides a layer's routed experts over several chips; this
layer is told which ones it holds (``LlamaConfig.experts_first``,
``experts_held``) and computes their part of the result:

- the router runs over ALL ``num_experts`` in float32 and every token
  selects its ``num_experts_per_tok`` (sigmoid or softmax scores; with
  ``norm_topk_prob`` the selected scores are normalised over all the
  selected, held here or not; with ``expert_selection_bias`` the choice is
  by ``score + bias``, a learned float32 bias an expert, and the weights are
  the scores without it; with ``expert_groups`` the choice is among the
  experts of a token's ``expert_groups_kept`` best groups, a group scored by
  its two largest selection values; ``routed_scaling_factor`` multiplies
  the weights last);
- the (token, expert) pairs whose expert is held are grouped by expert and
  go through one grouped product, :func:`expert_ffn`, that reads an
  expert's weights only if it has a pair.  No capacity: a group is as long
  as its pairs, so the held experts' terms are exact whatever the
  imbalance.  The pairs of absent experts are left out, and nothing stands
  in for the exchange that would carry them to their chips: the partial
  sum is the layer's result, here and in the benchmark's reference alike;
- the shared experts take every token, are averaged, and counted once; they
  may have a width of their own (``shared_expert_d_ff``) and a sigmoid gate
  a token, ``sigmoid(x w_sg)`` (``shared_expert_gate``: Qwen3-Next's one
  shared expert);
- a token that is not real (a bucket's padding, an inactive slot's row)
  routes nowhere: it reaches no expert's weights and no counter.

The trainer's layer, with capacities and dropped overflow, is
:mod:`synapseml_tpu.models.dl.moe`; it shares nothing with this one.

``backend`` is the engine's ``attention_backend``: ``"paged"`` runs the
Pallas kernel below, ``"interpret"`` the same kernel through the Pallas
interpreter (CPU tests), ``"dense"`` the same rows through
``jax.lax.ragged_dot``.  The kernel is the TPU's path because the chip said
so: XLA lowers ``ragged_dot`` to a Mosaic kernel of its own that skips an
expert without rows too, but streams a weight in blocks of 512 x 512 and
takes rows 64 at a time: its decode products read 83% of their HBM
roofline where this kernel reads 91%, its prefill products take twice as
long, and the serving cell's tokens per second fell 7.0% (``PERF.md``
section 6).  ``ragged_dot`` stays for an engine that runs off the TPU,
where the only other way to run the kernel is the interpreter.

The layer sows three counts into the ``"stats"`` collection, which the
engine makes mutable and reads with a step's tokens (:data:`EXPERT_COUNTS`):
``expert_pairs_held`` (pairs computed here), ``experts_touched`` (held
experts with at least one pair: the weights a memory-bound step reads) and
``expert_tiles_active`` (the grouped product's tiles of
:func:`expert_row_tile` rows that hold a pair: the tiles it computed, pairs
and padding rows together).
"""

from __future__ import annotations

import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: tokens one grouped product takes: a longer pass goes through it in
#: chunks, so that the rows in flight (``k`` a token, padded to whole tiles
#: an expert) stay some hundreds of MB at 4,096 wide
_CHUNK_TOKENS = 1024

#: the router's product and scores: a choice among the experts turns on
#: small gaps, so float32 whatever the model's dtype
_ROUTER_DTYPE = jnp.float32

#: bytes of one weight block ``(tk, tn)`` the kernel streams a grid step
_BLOCK_BYTES = 4 * 1024 * 1024

#: what an expert layer sows, in the order :func:`stats_totals` returns them
EXPERT_COUNTS = ("expert_pairs_held", "experts_touched", "expert_tiles_active")


def _row_tile(pairs: int, held: int) -> int:
    """Rows of one tile of the grouped product: a tile belongs to one
    expert, so a small pass (decode: a pair or two an expert) takes the
    smallest tile the MXU's operand layout allows and a long one up to
    256, where a tile's products outweigh the weight block it reads."""
    t = 16
    while t < 256 and t * held < pairs:
        t *= 2
    return t


def expert_row_tile(cfg, tokens: int) -> int:
    """Rows of a tile of the grouped product in a pass of ``tokens`` tokens
    (a longer pass goes through it in chunks of :data:`_CHUNK_TOKENS`)."""
    return _row_tile(min(int(tokens), _CHUNK_TOKENS) * cfg.num_experts_per_tok,
                     cfg.experts_held_count)


def _divisor(n: int, cands) -> int:
    for c in cands:
        if n % c == 0:
            return c
    return n


def _grouped_kernel(n_k: int, gated: bool):
    """Grid ``(N / tn, active tiles, K / tk)``: tile ``i`` of the rows,
    all of one expert ``tile_expert[i]``, times that expert's ``(tk, tn)``
    weight block, accumulated over ``k`` in float32.  ``gated``: two
    weights, the result ``silu(x Wg) * (x Wu)``."""

    def kernel(tile_expert, n_active, x_ref, *refs):
        del tile_expert, n_active
        if gated:
            wg_ref, wu_ref, o_ref, acc_g, acc_u = refs
        else:
            wg_ref, o_ref, acc_g = refs
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _zero():
            acc_g[...] = jnp.zeros_like(acc_g)
            if gated:
                acc_u[...] = jnp.zeros_like(acc_u)

        x = x_ref[...]
        acc_g[...] += jnp.dot(x, wg_ref[...],
                              preferred_element_type=jnp.float32)
        if gated:
            acc_u[...] += jnp.dot(x, wu_ref[...],
                                  preferred_element_type=jnp.float32)

        @pl.when(k == n_k - 1)
        def _store():
            if gated:
                g = acc_g[...]
                o_ref[...] = (g * jax.nn.sigmoid(g) * acc_u[...]
                              ).astype(o_ref.dtype)
            else:
                o_ref[...] = acc_g[...].astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("tm", "out_dtype", "interpret"))
def expert_ffn(x_rows: jnp.ndarray,        # (tiles * tm, K)
               tile_expert: jnp.ndarray,   # (tiles,) int32
               n_active: jnp.ndarray,      # (1,) int32
               w: jnp.ndarray,             # (E, K, N)
               w_up: Optional[jnp.ndarray] = None,
               *, tm: int, out_dtype=None, interpret: bool = False):
    """The grouped product of the expert layer.  Rows come in tiles of
    ``tm``, each tile all of one expert (``tile_expert``); the first
    ``n_active`` tiles are computed and no other, so an expert without a
    pair costs no grid step and none of its weights is read.  With
    ``w_up`` the result is ``silu(x w[e]) * (x w_up[e])``, else
    ``x w[e]``.  Rows of tiles past ``n_active`` come back unwritten."""
    M, K = x_rows.shape
    E, _, N = w.shape
    gated = w_up is not None
    out_dtype = out_dtype or x_rows.dtype
    tn = _divisor(N, (512, 256, 128))
    per_row = tn * w.dtype.itemsize * (2 if gated else 1)
    tk = _divisor(K, [c for c in (4096, 2048, 1024, 512, 256, 128)
                      if c * per_row <= _BLOCK_BYTES])
    n_k = K // tk
    w_spec = pl.BlockSpec((None, tk, tn),
                          lambda n, i, k, te, na: (te[i], k, n))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // tn, n_active[0], n_k),
        in_specs=[pl.BlockSpec((tm, tk), lambda n, i, k, te, na: (i, k))]
        + [w_spec] * (2 if gated else 1),
        out_specs=pl.BlockSpec((tm, tn), lambda n, i, k, te, na: (i, n)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
        * (2 if gated else 1))
    return pl.pallas_call(
        _grouped_kernel(n_k, gated),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="expert_ffn",
    )(tile_expert, n_active, x_rows, *((w, w_up) if gated else (w,)))


def _routed(x, expert, weight, held, w_gate, w_up, w_down, backend: str):
    """The held experts' terms for one chunk of tokens.  ``x (T, d)``;
    ``expert (T, k)`` the selected experts as indices into the held ones;
    ``weight (T, k)`` float32; ``held (T, k)`` the pairs computed here.
    -> ``(terms, tiles)``: ``(T, d)`` float32 ``sum_j held weight_j
    E_{expert_j}(x)``, and the tiles computed, int32 ``()``."""
    T, k = expert.shape
    H = w_gate.shape[0]
    P = T * k
    tm = _row_tile(P, H)
    tiles = -(-P // tm) + H           # every group may end in a part tile
    e = jnp.where(held, expert, H).reshape(P)
    onehot = e[:, None] == jnp.arange(H)[None, :]                  # (P, H)
    counts = jnp.sum(onehot, 0, dtype=jnp.int32)                   # (H,)
    rank = jnp.sum(jnp.where(onehot, jnp.cumsum(onehot, 0, dtype=jnp.int32)
                             - 1, 0), -1)                          # (P,)
    group_tiles = -(-counts // tm)
    tile_end = jnp.cumsum(group_tiles)
    n_active = tile_end[-1:]
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(tiles), side="right"),
        H - 1).astype(jnp.int32)
    # row of every pair computed here: its group's first tile, then its rank
    row = jnp.where(e < H, (tile_end - group_tiles)[jnp.minimum(e, H - 1)]
                    * tm + rank, tiles * tm)                       # (P,)
    row_token = jnp.zeros(tiles * tm, jnp.int32).at[row].set(
        jnp.arange(P, dtype=jnp.int32) // k, mode="drop")
    x_rows = x[row_token]
    if backend == "dense":
        # the same rows through XLA's grouped product: a group is an
        # expert's whole tiles
        sizes = group_tiles * tm
        h = jax.nn.silu(lax.ragged_dot(x_rows, w_gate, sizes)) \
            * lax.ragged_dot(x_rows, w_up, sizes)
        y = lax.ragged_dot(h, w_down, sizes,
                           preferred_element_type=jnp.float32)
    else:
        kw = dict(tm=tm, interpret=backend == "interpret")
        h = expert_ffn(x_rows, tile_expert, n_active, w_gate, w_up, **kw)
        y = expert_ffn(h, tile_expert, n_active, w_down,
                       out_dtype=jnp.float32, **kw)
    # rows of tiles nobody computed hold whatever the buffer held
    pair = jnp.where(held.reshape(P, 1), y[jnp.minimum(row, tiles * tm - 1)],
                     0.0)
    return (jnp.sum(pair.reshape(T, k, -1) * weight[..., None], axis=1),
            n_active[0])


def _kept_groups(sel: jnp.ndarray, groups: int, kept: int) -> jnp.ndarray:
    """Group-limited selection (DeepSeek-V3's): the experts lie in
    ``groups`` equal groups by index, a group scores the sum of its two
    largest selection values, and the experts outside a token's ``kept``
    best groups are out of its choice.  ``sel (T, E)`` -> the same values
    there, the lowest float32 elsewhere."""
    T, E = sel.shape
    g = sel.reshape(T, groups, E // groups)
    _, best = lax.top_k(jnp.sum(lax.top_k(g, 2)[0], -1), kept)   # (T, kept)
    keep = jnp.any(best[:, :, None] == jnp.arange(groups), axis=1)
    return jnp.where(jnp.repeat(keep, E // groups, axis=1), sel,
                     jnp.finfo(jnp.float32).min)


def stats_totals(stats) -> jnp.ndarray:
    """:data:`EXPERT_COUNTS` summed over the layers of a pass's ``"stats"``
    collection: int32 ``(3,)``."""
    tot = dict.fromkeys(EXPERT_COUNTS, 0)
    for path, leaf in jax.tree_util.tree_leaves_with_path(stats):
        name = getattr(path[-1], "key", None)
        if name in tot:
            tot[name] = tot[name] + leaf
    return jnp.stack([jnp.asarray(tot[n], jnp.int32) for n in EXPERT_COUNTS])


class ExpertFFN(nn.Module):
    """``(B, S, d) -> (B, S, d)``: the held routed experts' weighted terms
    plus the average of the shared experts, gated where the configuration
    says so (module docstring).  ``valid (B, S)``: the tokens that are
    real."""
    cfg: "object"

    def _count(self, name: str, count) -> None:
        # the last pass's count, not a tuple that grows
        self.sow("stats", name, count, reduce_fn=lambda _, new: new,
                 init_fn=lambda: jnp.zeros((), jnp.int32))

    @nn.compact
    def __call__(self, h, valid, backend: str = "dense"):
        cfg = self.cfg
        B, S, d = h.shape
        E, k = cfg.num_experts, cfg.num_experts_per_tok
        first, H = cfg.experts_first, cfg.experts_held_count
        F = cfg.expert_d_ff or cfg.d_ff
        T = B * S
        x = h.reshape(T, d)
        init = nn.initializers.truncated_normal(0.02)

        def param(name, shape, axes):
            return self.param(name, nn.with_partitioning(init, axes), shape,
                              cfg.dtype)
        w_router = param("router", (d, E), ("embed", None))
        w_gate = param("experts_gate", (H, d, F), (None, "embed", "mlp"))
        w_up = param("experts_up", (H, d, F), (None, "embed", "mlp"))
        w_down = param("experts_down", (H, F, d), (None, "mlp", "embed"))

        # the router in float32: a choice among 128 turns on small gaps
        r = jnp.dot(x.astype(_ROUTER_DTYPE), w_router.astype(_ROUTER_DTYPE),
                    precision=lax.Precision.HIGHEST).astype(jnp.float32)
        score = jax.nn.sigmoid(r) if cfg.expert_selection == "sigmoid" \
            else jax.nn.softmax(r, axis=-1)
        sel = score
        if cfg.expert_selection_bias:
            # selected by score + bias, weighed by the score alone
            bias = self.param("router_bias", nn.initializers.zeros_init(),
                              (E,), jnp.float32)
            sel = score + bias
        if cfg.expert_groups > 1:
            sel = _kept_groups(sel, cfg.expert_groups, cfg.expert_groups_kept)
        if sel is score:
            top, idx = lax.top_k(score, k)                        # (T, k)
        else:
            _, idx = lax.top_k(sel, k)
            top = jnp.take_along_axis(score, idx, axis=-1)
        weight = top / jnp.sum(top, -1, keepdims=True) \
            if cfg.norm_topk_prob else top
        if cfg.routed_scaling_factor != 1.0:
            weight = weight * cfg.routed_scaling_factor
        held = (idx >= first) & (idx < first + H) & valid.reshape(T, 1)
        self._count("expert_pairs_held", jnp.sum(held, dtype=jnp.int32))
        self._count("experts_touched", jnp.sum(jnp.any(
            held[..., None] & (idx[..., None] - first == jnp.arange(H)),
            axis=(0, 1)), dtype=jnp.int32))

        expert = idx - first
        if T <= _CHUNK_TOKENS:
            routed, tiles = _routed(x, expert, weight, held, w_gate, w_up,
                                    w_down, backend)
        else:
            n = -(-T // _CHUNK_TOKENS)
            pad = n * _CHUNK_TOKENS - T

            def chunks(a):
                a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                return a.reshape((n, _CHUNK_TOKENS) + a.shape[1:])
            routed, tiles = lax.map(
                lambda c: _routed(*c, w_gate, w_up, w_down, backend),
                (chunks(x), chunks(expert), chunks(weight), chunks(held)))
            routed = routed.reshape(n * _CHUNK_TOKENS, d)[:T]
            tiles = jnp.sum(tiles)
        out = routed.astype(cfg.dtype).reshape(B, S, d)
        self._count("expert_tiles_active", tiles)

        ns = cfg.num_shared_experts
        if ns:
            # the shared experts side by side are one SwiGLU of width
            # ns * F (or their own width) whose down-projection sums them
            def dense(n, axes, name):
                return nn.Dense(n, use_bias=False, dtype=cfg.dtype,
                                name=name, kernel_init=nn.with_partitioning(
                                    init, axes))
            Fs = cfg.shared_expert_d_ff or ns * F
            g = dense(Fs, ("embed", "mlp"), "shared_gate")(h)
            u = dense(Fs, ("embed", "mlp"), "shared_up")(h)
            shared = dense(d, ("mlp", "embed"), "shared_down")(
                nn.silu(g) * u)
            if cfg.shared_expert_gate:
                # sigmoid(x w_sg) a token, in float32
                w_sg = param("shared_expert_gate", (d, 1), ("embed", None))
                gate = jax.nn.sigmoid(jnp.dot(
                    h.astype(jnp.float32), w_sg.astype(jnp.float32)))
                shared = (shared.astype(jnp.float32) * gate).astype(cfg.dtype)
            out = out + shared * jnp.asarray(1.0 / ns, cfg.dtype)
        return out
