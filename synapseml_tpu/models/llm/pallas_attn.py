"""Pallas TPU paged decode attention — the serving-side hot-loop kernel.

The dense decode path (:class:`~synapseml_tpu.models.llm.model
.CausalAttention`, vector ``cache_index`` branch) attends every step over
the ENTIRE ``(n_slots, max_len)`` KV cache with a mask, so decode
attention bytes scale with cache *capacity* instead of *live tokens* —
the read-side twin of the write-side waste the PR-8 ``.at[].set``
scatter eliminated.  This kernel is the vLLM paged-KV read pattern
(Kwon et al., PagedAttention) adapted to XLA static shapes, held to the
Flash-style online-softmax contract (Dao et al., FlashAttention):

- **grid** ``(n_slots, num_tiles)`` with the tile dimension fastest; the
  per-slot live span (``spans[slot]`` tokens) is covered by
  ``ceil(span / tile)`` sublane-aligned K/V tiles.  Tiles past a slot's
  live span CLAMP their block index to the slot's last live tile
  (scalar-prefetched ``spans`` drives the index map), so Pallas's
  revisited-block elision skips their DMA entirely and a ``pl.when``
  gate skips their compute — a short sequence's dead tiles cost neither
  bytes nor flops.
- **span bucketing** — ``num_tiles`` is the bucketed (next power of two)
  tile count of the LONGEST live span in the batch, so a batch of short
  sequences does not even iterate a long cache's grid; one compiled
  program per bucket, O(log(max_len / tile)) programs total (the
  prefill-bucket idiom of :mod:`~synapseml_tpu.models.llm.slots`).
- **online softmax** — f32 running (max, sum, accumulator) in VMEM
  scratch across tiles; masking uses ``finfo(f32).min`` exactly like the
  dense path, so a masked key underflows to probability 0.0 in both.
- **GQA head grouping** — queries reshape ``(kv_heads, group, d_head)``
  and each kv head's ``(group, d_head) x (d_head, tile)`` contraction
  rides the MXU with the group dimension batched, reading each K/V tile
  once per kv head (not per query head).

Correctness runs the kernel in INTERPRET mode on CPU (the
``pallas_hist`` pattern): greedy decode through
:class:`~synapseml_tpu.models.llm.slots.SlotEngine` is pinned
token-exact vs the dense path, and kernel-vs-dense logits parity is
pinned ulp-tolerant across span buckets (tests/test_llm_paged.py).
Speed is measured where the hardware is; the byte ledger below
(:func:`paged_read_bytes` / :func:`dense_read_bytes`) is the kernel's
exact DMA accounting by construction — it feeds the
``llm_decode_bytes_per_token`` gauge and bench.py's paired
``llmserve_decode_roofline_before/after`` blocks.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM budget for the kernel working set (~16 MB/core minus block
#: slack — same bar as models/gbdt/pallas_hist._VMEM_BUDGET)
_VMEM_BUDGET = 13 * 1024 * 1024

#: key-tile candidates, largest first: 128-256 keeps the logits lane
#: dimension MXU-wide on real caches; the small tail exists for test
#: geometries (every candidate is sublane-aligned for f32)
_TILE_CANDIDATES = (256, 128, 64, 32, 16, 8)

#: the attention_backend switch values (the booster.py use_pallas
#: idiom: 'auto' gates on backend + geometry, 'interpret' is the CPU
#: correctness mode)
ATTENTION_BACKENDS = ("auto", "dense", "paged", "interpret")


def _sublane(dtype) -> int:
    """Minimum sublane multiple for ``dtype`` (f32 8, bf16 16, int8 32)."""
    return max(8, 32 // np.dtype(dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class PagedGeometry:
    """Resolved kernel geometry for one cache shape: the K/V key tile,
    the total tile count (``max_len // tile`` — the tile always divides
    ``max_len``), and the VMEM working-set estimate the gate admitted."""
    tile: int
    total_tiles: int
    vmem_bytes: int


def paged_geometry(max_len: int, num_heads: int, num_kv_heads: int,
                   d_head: int, dtype: Any = jnp.bfloat16,
                   max_query_span: int = 1,
                   tile: Optional[int] = None) -> Optional[PagedGeometry]:
    """The VMEM gate: pick the key-tile length for a
    ``(max_len, num_kv_heads, d_head)`` cache row, or None when no
    geometry fits (the 'auto' backend then stays dense — the
    ``fused_geometry`` idiom of the GBDT kernel).

    The tile must divide ``max_len`` (blocks never run past the cache
    row), be a sublane multiple for the cache dtype, and leave at least
    two tiles of span granularity (``tile <= max_len // 2``) — a
    one-tile "paged" read would just be the dense row with extra
    steps.  Working set: double-buffered K and V tiles plus the q/out
    blocks and the f32 online-softmax scratch — the latter three all
    scale with ``max_query_span`` (the speculative verify step's S:
    its q/out blocks are ``(1, S, H, D)`` and its scratch rows
    ``S*H``), so a spec-enabled engine must gate at the WIDEST verify
    it can launch, not at S=1.  Bytes are counted as Mosaic lays them
    out: the last two dims of every block pad to the dtype's
    (sublane, 128) tile, so a ``(KV=8, D=64)`` bf16 K/V row occupies a
    ``(16, 128)`` tile — four times its logical bytes.

    ``tile`` pins a single candidate instead of the ladder — the tuned
    override path.  It passes through the SAME divisibility/VMEM gate:
    a tuning-table winner that stopped fitting (config drift since it
    was measured) resolves to None, and the caller keeps the default
    geometry — tables can suggest, only the gate admits."""
    itemsize = np.dtype(dtype).itemsize
    sub = _sublane(dtype)
    s = max(1, int(max_query_span))

    def pad(n, m):
        return -(-n // m) * m
    d_pad = pad(d_head, 128)
    rows = pad(s * num_heads, 8)                  # f32 scratch sublanes
    candidates = _TILE_CANDIDATES if tile is None else (int(tile),)
    for cand in candidates:
        if cand <= 0 or cand % sub or max_len % cand \
                or cand > max_len // 2:
            continue
        need = (2 * 2 * cand * pad(num_kv_heads, sub) * d_pad
                * itemsize                                       # K+V x2 buf
                + 2 * 2 * s * pad(num_heads, sub) * d_pad
                * itemsize                                       # q+out x2 buf
                + rows * d_pad * 4                               # f32 acc
                + 2 * rows * 128 * 4)                            # m + l
        if need <= _VMEM_BUDGET:
            return PagedGeometry(cand, max_len // cand, need)
    return None


def paged_geometry_key(max_len: int, num_kv_heads: int, d_head: int,
                       dtype: Any, max_query_span: int = 1) -> str:
    """The tuning-table geometry key for a paged cache shape — the
    ``paged_attn_tile`` space records under it and ``SlotEngine``
    consults with it; one builder so the two can never drift."""
    from ...telemetry.tunetable import geometry_key
    return geometry_key(max_len=int(max_len), kv_heads=int(num_kv_heads),
                        d_head=int(d_head), dtype=np.dtype(dtype).name,
                        span=max(1, int(max_query_span)))


def resolve_attention_backend(backend: str, *, max_len: int,
                              num_heads: int, num_kv_heads: int,
                              d_head: int, dtype: Any = jnp.bfloat16,
                              max_query_span: int = 1) -> str:
    """The one parser for ``attention_backend`` (SlotEngine /
    LLMServer / bench) — returns the RESOLVED backend
    (``'dense'`` | ``'paged'`` | ``'interpret'``) or fails fast with an
    actionable message (the ``resolve_collective_config`` validation
    idiom):

    - ``'auto'`` — paged on a TPU backend when :func:`paged_geometry`
      fits VMEM, dense otherwise (never raises);
    - ``'dense'`` — always the XLA full-row path;
    - ``'paged'`` — the compiled Pallas kernel; raises off-TPU (Mosaic
      cannot compile for this backend) and when no geometry fits;
    - ``'interpret'`` — the kernel through the Pallas interpreter on
      any backend (the CPU correctness mode; orders of magnitude slower
      than dense — tests and parity audits only)."""
    if backend not in ATTENTION_BACKENDS:
        raise ValueError(
            f"attention_backend={backend!r}: must be one of "
            f"{ATTENTION_BACKENDS}")
    if backend == "dense":
        return "dense"
    geo = paged_geometry(max_len, num_heads, num_kv_heads, d_head, dtype,
                         max_query_span=max_query_span)
    on_tpu = jax.default_backend() == "tpu"
    if backend == "auto":
        return "paged" if (on_tpu and geo is not None) else "dense"
    if geo is None:
        raise ValueError(
            f"attention_backend={backend!r}: no paged geometry fits "
            f"(max_len={max_len}, kv_heads={num_kv_heads}, "
            f"d_head={d_head}, dtype={np.dtype(dtype).name}) — max_len "
            f"must be divisible by a sublane-aligned tile <= max_len//2 "
            f"and the tile working set must fit VMEM; use "
            f"attention_backend='dense' (or 'auto', which falls back)")
    if backend == "paged" and not on_tpu:
        raise ValueError(
            "attention_backend='paged' compiles a Mosaic TPU kernel but "
            f"this process is running on the "
            f"{jax.default_backend()!r} backend; use 'auto' (falls back "
            "to dense off-TPU), 'dense', or 'interpret' (runs the "
            "kernel in the Pallas interpreter for correctness work — "
            "far slower than dense)")
    return backend


def span_bucket_tiles(max_span: int, geo: PagedGeometry) -> int:
    """Bucketed grid length for the step: the next power of two >= the
    longest live span's tile count, clamped to the cache's total tiles
    — O(log) compiled programs, and a batch of short sequences never
    iterates a long cache's grid."""
    nt = -(-max(1, int(max_span)) // geo.tile)
    b = 1
    while b < nt:
        b *= 2
    return min(b, geo.total_tiles)


# ---------------------------------------------------------------------------
# the byte ledger (exact DMA accounting, shared by telemetry and bench)
# ---------------------------------------------------------------------------

def paged_read_bytes(spans, tile: int, num_kv_heads: int, d_head: int,
                     itemsize: int, num_layers: int = 1) -> int:
    """K/V bytes ONE paged decode step DMAs for ``spans``: each slot
    reads ``ceil(span / tile)`` tiles of K and of V per layer — dead
    tiles are elided by the clamped index map, so this is exact by
    construction of the grid, not an estimate.

    ``spans`` must cover EVERY slot in the launch, not just the active
    ones: the grid iterates all ``n_slots`` rows and block elision only
    skips revisits WITHIN a slot, so an inactive slot (span 1) still
    DMAs one K and one V tile per layer when the grid crosses into it."""
    tiles = np.ceil(np.maximum(np.asarray(spans, np.float64), 1.0)
                    / tile).astype(np.int64)
    return int(num_layers * 2 * tiles.sum() * tile
               * num_kv_heads * d_head * itemsize)


def dense_read_bytes(n_slots: int, max_len: int, num_kv_heads: int,
                     d_head: int, itemsize: int,
                     num_layers: int = 1) -> int:
    """K/V bytes the DENSE decode attention reads per step: the full
    ``(n_slots, max_len)`` K and V rows per layer, regardless of live
    spans — the capacity-scaled read the paged kernel replaces."""
    return int(num_layers * 2 * n_slots * max_len
               * num_kv_heads * d_head * itemsize)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _make_decode_kernel(kv_heads: int, group: int, tile: int, d_head: int,
                        s_len: int):
    neg = float(np.finfo(np.float32).min)

    def kernel(spans_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
               l_ref):
        """Grid ``(n_slots, num_tiles)``, tile fastest.  q/out blocks
        ``(1, S, H, D)`` constant per slot (S == 1 is the plain decode
        step; S > 1 the speculative-verify span, whose S query
        positions amortize ONE span-bucketed K/V read); K/V blocks
        ``(1, tile, KV, D)`` span-clamped (see ``_kv_index_map``);
        scratch: f32 accumulator ``(S*H, D)`` plus running max /
        normalizer ``(S*H, 128)`` (lane 0 carries the value), rows
        HEAD-major — head h owns rows ``[h*S*group, (h+1)*S*group)`` so
        each kv head's update touches one contiguous block — revisited
        across the tile dimension."""
        s = pl.program_id(0)
        t = pl.program_id(1)
        span = spans_ref[s]
        n_tiles = lax.div(span + (tile - 1), tile)

        @pl.when(t == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, neg)
            l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(t < n_tiles)
        def _tile():
            # ``span`` counts the keys the LAST query attends: query j
            # sits at position span-S+j and attends keys <= itself,
            # i.e. key < span-(S-1)+j — for S == 1 the causal mask
            # degenerates to the live-span mask (same finfo-min fill as
            # the dense path: exp underflows to probability 0.0 either
            # way)
            kpos = t * tile + lax.broadcasted_iota(jnp.int32, (1, tile), 1)
            qidx = lax.broadcasted_iota(jnp.int32, (s_len * group, tile),
                                        0) // group       # query j per row
            valid = kpos < span - (s_len - 1) + qidx      # (S*g, tile)
            for h in range(kv_heads):
                rows = slice(h * s_len * group, (h + 1) * s_len * group)
                q = q_ref[0, :, h * group:(h + 1) * group, :].reshape(
                    s_len * group, d_head).astype(jnp.float32)
                k = k_ref[0, :, h, :].astype(jnp.float32)    # (tile, D)
                logits = lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) / np.sqrt(d_head)
                logits = jnp.where(valid, logits, neg)       # (S*g, tile)
                m_prev = m_ref[rows, 0:1]                    # (S*g, 1)
                l_prev = l_ref[rows, 0:1]
                m_new = jnp.maximum(
                    m_prev, jnp.max(logits, -1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(logits - m_new)                  # (S*g, tile)
                v = v_ref[0, :, h, :].astype(jnp.float32)    # (tile, D)
                pv = lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                acc_ref[rows, :] = acc_ref[rows, :] * alpha + pv
                m_ref[rows, 0:1] = m_new
                l_ref[rows, 0:1] = (l_prev * alpha
                                    + jnp.sum(p, -1, keepdims=True))

        @pl.when(t == pl.num_programs(1) - 1)
        def _out():
            # every live query attends >= 1 unmasked key whose
            # probability at the running max is exp(0) = 1, so l >= 1;
            # the floor only guards the impossible all-masked row
            for h in range(kv_heads):
                rows = slice(h * s_len * group, (h + 1) * s_len * group)
                l = jnp.maximum(l_ref[rows, 0:1], 1e-30)
                o_ref[0, :, h * group:(h + 1) * group, :] = (
                    acc_ref[rows, :] / l).reshape(
                        s_len, group, d_head).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("tile", "num_tiles",
                                             "interpret", "kv_heads"))
def paged_decode_attention(q: jnp.ndarray,      # (B, H, D) | (B, S, H, D)
                           k: jnp.ndarray,      # (B, max_len, KV, D)
                           v: jnp.ndarray,      # (B, max_len, KV, D)
                           spans: jnp.ndarray,  # (B,) int32 live lengths
                           tile: int,
                           num_tiles: int,
                           interpret: bool = False,
                           kv_heads: Optional[int] = None) -> jnp.ndarray:
    """One decode step's attention for every slot, reading only each
    slot's live K/V span: → same shape as ``q``, in ``q.dtype``.

    ``q`` may carry a query-span dimension ``S`` (``(B, S, H, D)`` —
    the speculative-verify step, where slot b's query j sits at
    position ``spans[b]-S+j``); a 3-D ``q`` is the plain S == 1 decode
    step.  ``spans[b]`` is slot b's live length INCLUDING this step's
    S written positions (the LAST query attends keys ``[0, spans[b])``;
    earlier queries attend one key fewer each — the in-span causal
    mask).  The queries' own K/V must already be written — the
    engine's scatter runs BEFORE attention, as in the dense path.
    ``num_tiles`` is the static bucketed grid length from
    :func:`span_bucket_tiles`; spans beyond ``num_tiles * tile`` would
    be silently truncated, so the caller's bucket must cover the
    longest live span.  ``kv_heads``: the heads of ``k``/``v`` that are
    real, the first ones, where a cache row is padded past them
    (``LlamaConfig.kv_cache_heads``); the padding is fetched with its
    tile and never read."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, S, H, D = q.shape
    KV = k.shape[2]
    heads = kv_heads or KV
    assert H % heads == 0 and heads <= KV, (H, heads, KV)
    group = H // heads

    def kv_index_map(s, t, spans_ref):
        # tiles past the live span clamp to the slot's LAST live tile:
        # the block index repeats, Pallas elides the DMA, and the
        # pl.when gate in the kernel skips the compute — a dead tile
        # costs nothing (the paged read)
        nt = lax.div(spans_ref[s] + (tile - 1), tile)
        return (s, jnp.minimum(t, jnp.maximum(nt - 1, 0)), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, num_tiles),
        in_specs=[
            pl.BlockSpec((1, S, H, D), lambda s, t, *_: (s, 0, 0, 0)),
            pl.BlockSpec((1, tile, KV, D), kv_index_map),
            pl.BlockSpec((1, tile, KV, D), kv_index_map),
        ],
        out_specs=pl.BlockSpec((1, S, H, D),
                               lambda s, t, *_: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((S * H, D), jnp.float32),   # online-softmax acc
            pltpu.VMEM((S * H, 128), jnp.float32),  # running max (lane 0)
            pltpu.VMEM((S * H, 128), jnp.float32),  # normalizer (lane 0)
        ],
    )
    out = pl.pallas_call(
        _make_decode_kernel(heads, group, tile, D, S),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H, D), q.dtype),
        interpret=interpret,
    )(spans.astype(jnp.int32), q, k, v)
    return out[:, 0] if squeeze else out
