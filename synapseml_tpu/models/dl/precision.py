"""Mixed-precision + rematerialization policies for DL training.

A bandwidth-bound fine-tune step has two byte-diet levers, each with an
explicit, testable contract:

- :class:`PrecisionPolicy` — which dtype the forward/backward compute
  runs in (``compute_dtype``), which dtype gradient leaves carry across
  the sync/update boundary (``grad_dtype``), and the master dtype of
  params / optimizer moments / batch statistics (``param_dtype``,
  always float32 here: the Micikevicius et al. mixed-precision recipe,
  arXiv:1710.03740 — bf16 activations *and* gradients end-to-end, f32
  master weights so tiny updates don't round to zero).
- :func:`remat_policy` — the ``rematPolicy`` estimator knob mapped to a
  ``jax.checkpoint`` policy (Chen et al., sublinear-memory training,
  arXiv:1604.06174): recompute block activations in the backward pass
  instead of round-tripping them through HBM.

Contracts (pinned in tests/test_perf_roofline.py):

- ``"bf16"`` (the default) is byte-identical to the historical step —
  the models already compute in bf16 with f32 params; the policy only
  names that contract.
- ``"bf16_grad"`` additionally rounds gradient leaves to bf16 at the
  sync boundary.  NOT bit-exact vs f32 grads — holdout-loss parity is
  the pin.  Composes with the PR-6 compressed collectives and the
  sharded update: the rounding happens BEFORE the wire codec (which
  still owns the wire dtype) and the error-feedback residual stream
  stays f32 — EF carries the CODEC's sub-quantum error at full f32
  resolution; the bf16 rounding of the raw gradient is part of the
  gradient numerics itself (like any other backward-pass rounding),
  not something the residual stream recovers.
- rematerialization is bit-exact by construction: the backward pass
  re-runs the SAME ops on the SAME values, so loss trajectories match
  the no-remat step bitwise (pinned tier-1).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

#: accepted ``rematPolicy`` values (estimator param + model configs)
REMAT_POLICIES = ("none", "dots_saveable", "full", "blocks")

#: accepted ``precision`` values
PRECISION_PRESETS = ("bf16", "f32", "bf16_grad")


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Dtype contract of one train step.  ``param_dtype`` is the master
    dtype: params, optimizer moments, batch statistics and the EF
    residual stream never leave it."""
    name: str = "bf16"
    compute_dtype: Any = jnp.bfloat16
    grad_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def casts_grads(self) -> bool:
        return self.grad_dtype != self.param_dtype


_POLICIES = {
    "bf16": PrecisionPolicy("bf16", jnp.bfloat16, jnp.float32),
    "f32": PrecisionPolicy("f32", jnp.float32, jnp.float32),
    "bf16_grad": PrecisionPolicy("bf16_grad", jnp.bfloat16, jnp.bfloat16),
}

#: checkpoint config-guard code per policy (the DL _CheckpointLoop
#: compares floats; a precision switch mid-run changes the numerics the
#: resumed batches would train under)
PRECISION_CODE = {"bf16": 0.0, "f32": 1.0, "bf16_grad": 2.0}


def resolve_precision(spec) -> PrecisionPolicy:
    """``None``/name/:class:`PrecisionPolicy` → policy (default bf16)."""
    if spec is None:
        return _POLICIES["bf16"]
    if isinstance(spec, PrecisionPolicy):
        return spec
    if isinstance(spec, str):
        if spec not in _POLICIES:
            raise ValueError(f"precision={spec!r}: expected one of "
                             f"{sorted(_POLICIES)}")
        return _POLICIES[spec]
    raise ValueError(f"precision must be a name or PrecisionPolicy, got "
                     f"{type(spec).__name__}")


def cast_floating(tree, dtype):
    """Cast every inexact leaf of ``tree`` to ``dtype`` (ints/bools pass
    through) — the one cast helper the step, the manual-DP sync and the
    tests share."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact) else x, tree)


def round_to(tree, dtype):
    """Round float leaves THROUGH ``dtype`` but keep f32 containers —
    the manual data-parallel path's grad rounding: the wire codec (which
    owns the wire dtype) and the f32 EF residual math downstream are
    unchanged, they just see bf16-rounded values."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype).astype(jnp.float32)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x, tree)


def remat_policy(name: Optional[str]):
    """``rematPolicy`` knob → ``(enabled, jax.checkpoint policy)``.

    - ``"none"``/None/False: no rematerialization.
    - ``"dots_saveable"``: remat each block, saving matmul/contraction
      results (``jax.checkpoint_policies.dots_saveable``) — cheap
      elementwise/norm chains recompute, the expensive contractions
      don't.
    - ``"full"`` / ``"blocks"`` (alias, and what ``True`` maps to):
      remat each block saving only its inputs — O(1)-block activation
      memory for ~1/3 more FLOPs, the Chen et al. schedule applied at
      block granularity.
    """
    if name in (None, False, "none"):
        return False, None
    if name is True:
        name = "full"
    if name not in REMAT_POLICIES:
        raise ValueError(f"rematPolicy={name!r}: expected one of "
                         f"{REMAT_POLICIES}")
    if name == "dots_saveable":
        return True, jax.checkpoint_policies.dots_saveable
    return True, None          # full/blocks: jax.checkpoint's default
