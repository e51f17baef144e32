"""Pallas TPU paged decode attention — the serving-side hot-loop kernel.

The dense decode path (:class:`~synapseml_tpu.models.llm.model
.CausalAttention`, vector ``cache_index`` branch) attends every step over
the ENTIRE ``(n_slots, max_len)`` KV cache with a mask, so decode
attention bytes scale with cache *capacity* instead of *live tokens* —
the read-side twin of the write-side waste the PR-8 ``.at[].set``
scatter eliminated.  This kernel is the vLLM paged-KV read pattern
(Kwon et al., PagedAttention) adapted to XLA static shapes, held to the
Flash-style online-softmax contract (Dao et al., FlashAttention):

- **the kernel walks live tiles itself** — grid ``(n_slots,)``; K and V
  stay in HBM and a slot's ``ceil(span / tile)`` live tiles arrive
  through a ring of ``_RING`` VMEM buffers filled by ``make_async_copy``.
  A cursor over the flat list of (slot, tile) pairs, kept in SMEM across
  grid steps, runs ``_RING - 1`` tiles ahead of the compute, over slot
  boundaries too, so the DMA engine always has a tile in flight.  No
  grid step, loop trip or byte is spent on a tile past a live span, and
  one compiled program serves every span (there is no span bucket).
- **all K/V heads of a tile in one contraction, in the layout the tile
  arrives in** — the cache row ``(KV, D)`` of a position is one
  ``(8, 128)`` memory tile, so a K/V tile viewed as flat rows
  ``(tile * KV, D)`` is the same bytes (the reshape of the cache is a
  bitcast; nothing of cache size is copied).  ``q (S*H, D) x rows^T``
  gives logits ``(S*H, tile*KV)`` with the MXU taking each row once;
  column ``(t, kv)`` belongs to query head ``h`` where ``kv == h //
  group``, every other column is masked like a dead key.  The MXU does
  ``KV`` times the needed products and has the room; no per-head
  sublane slice, which is what bound the kernel before (PERF.md §6,
  PR 30).  Operands go to the MXU in the cache's dtype with float32
  accumulation, probabilities are cast to the cache's dtype before the
  PV product, as the dense path does (``model.py``).
- **online softmax** — f32 running (max, sum, accumulator) in VMEM
  scratch across tiles; masking uses ``finfo(f32).min`` exactly like the
  dense path, so a masked key underflows to probability 0.0 in both.

Correctness runs the kernel in INTERPRET mode on CPU (the
``pallas_hist`` pattern): greedy decode through
:class:`~synapseml_tpu.models.llm.slots.SlotEngine` is pinned
token-exact vs the dense path, and kernel-vs-dense logits parity is
pinned ulp-tolerant across spans and tiles (tests/test_llm_paged.py).
Speed is measured where the hardware is; the byte ledger below
(:func:`paged_read_bytes` / :func:`dense_read_bytes`) is the kernel's
exact DMA accounting by construction — it feeds the
``llm_decode_bytes_per_token`` gauge.
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

#: key-tile candidates, largest first.  A tile is the unit of DMA and of
#: span rounding: the kernel fetches ``ceil(span / tile)`` of them a
#: slot, so a smaller tile wastes fewer bytes past the span and a larger
#: one amortises a loop trip over more bytes; the small tail exists for
#: test geometries (every candidate is sublane-aligned for f32)
_TILE_CANDIDATES = (256, 128, 64, 32, 16, 8)

#: K/V bytes of ONE tile (K alone) the ladder aims at: the largest
#: candidate at or under it is the default tile (PERF.md §6, PR 30 has
#: the chip readings behind the number)
_TILE_BYTES = 256 * 1024

#: depth of the DMA ring: ``_RING - 1`` tiles are in flight while one is
#: computed on
_RING = 3

#: elements of one logits chunk ``(S*H, C)``: a tile's flat rows are
#: contracted ``C`` at a time so the f32 logits and probabilities of a
#: wide verify step stay near 256 KiB each
_CHUNK_ELEMS = 64 * 1024

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


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def cache_row_heads(num_kv_heads: int, dtype) -> int:
    """K/V heads a cache row holds (``LlamaConfig.kv_cache_heads``):
    ``num_kv_heads``, padded to whole sublane tiles of ``dtype`` where
    they pass one tile and do not fill their last (30 bfloat16 heads ->
    32), so that a row is whole memory tiles and the kernel's flat-row
    view of the cache is a bitcast."""
    sub = _sublane(dtype)
    return (num_kv_heads if num_kv_heads <= sub or num_kv_heads % sub == 0
            else _pad(num_kv_heads, sub))


def row_relaid(row_heads: int, d_head: int) -> bool:
    """Whether an unpacked cache row of ``row_heads`` heads of ``d_head``
    (``(slots, rows, heads, d_head)``) is NO bitcast of the kernel's flat
    rows ``(slots, rows * heads, d_head)``: a head of several whole lane
    tiles, and more than one head but no whole tile of 8 sublanes.  The
    decode program compiled for the v5e then relays the whole cache into
    the flat order every layer and step (2 heads of 256: temporaries the
    size of the cache; one head, heads one lane tile wide, or 8 heads of
    256 keep theirs under 2 MB).  Such a kind keeps packed rows
    (``LlamaConfig.packed``)."""
    return (d_head > 128 and d_head % 128 == 0 and row_heads > 1
            and row_heads % 8 != 0)


def _chunk_rows(tile: int, row_heads: int, q_rows: int) -> int:
    """Flat K/V rows one contraction takes: the whole tile, halved while
    the ``(q_rows, C)`` logits pass ``_CHUNK_ELEMS`` and the halves stay
    whole positions and lane-aligned."""
    p = tile
    while (q_rows * p * row_heads > _CHUNK_ELEMS and p % 2 == 0
           and (p // 2 * row_heads) % 128 == 0):
        p //= 2
    return p * row_heads


def paged_geometry(max_len: int, num_heads: int, num_kv_heads: int,
                   d_head: int, dtype: Any = jnp.bfloat16,
                   max_query_span: int = 1,
                   tile: Optional[int] = None, *,
                   d_value: Optional[int] = None, pack: int = 1,
                   most: Optional[int] = None,
                   latent: bool = False) -> Optional[PagedGeometry]:
    """The VMEM gate: pick the key-tile length for a
    ``(max_len, num_kv_heads, d_head)`` cache row, or None when no
    geometry fits (the 'auto' backend then stays dense — the
    ``fused_geometry`` idiom of the GBDT kernel).

    The tile must divide ``max_len`` (a tile never runs past the cache
    row), be a sublane multiple for the cache dtype, and leave at least
    two tiles of span granularity (``tile <= max_len // 2``) — a
    one-tile "paged" read would just be the dense row with extra
    steps.  Of the candidates that fit VMEM the default is the largest
    whose K tile is at most ``_TILE_BYTES`` (else the smallest that
    fits): the kernel pays a loop trip a tile and the bytes a tile
    runs past the span, and that size is where the two meet on the chip.

    Working set, as Mosaic lays it out (last two dims of every buffer
    pad to the dtype's (sublane, 128) tile): the ring of ``_RING`` K and
    ``_RING`` V tiles of flat rows ``(tile * row_heads, D)``; the
    double-buffered q and out blocks ``(1, S, H, D)``; the query rows,
    f32 accumulator, running max and normaliser at ``S * pad(H, 8)``
    rows; and one chunk's f32 logits, probabilities and their cast.
    All but the ring scale with ``max_query_span`` (the speculative
    verify step's S), so a spec-enabled engine must gate at the WIDEST
    verify it can launch, not at S=1.

    ``tile`` pins a single candidate instead of the ladder — the tuned
    override path.  It passes through the SAME divisibility/VMEM gate:
    a tuning-table winner that stopped fitting (config drift since it
    was measured) resolves to None, and the caller keeps the default
    geometry — tables can suggest, only the gate admits.

    One geometry a layer KIND: ``max_len`` is the rows the kind's cache
    entry holds (a ring's, for a window layer on one), ``d_value`` the
    value's width where it is not the key's, ``pack`` the K/V heads a
    packed row holds side by side (``model.kv_pack``: the lanes are then
    ``pack`` widths, the flat rows ``num_kv_heads / pack`` a position), and
    ``most`` the largest tile allowed (a ring's block: the position tiles a
    step walks must be distinct tiles of the ring).  ``latent``: the cache
    is latent rows, ``d_head`` wide, for :func:`latent_decode_attention`
    (:func:`_latent_geometry`)."""
    if latent:
        return _latent_geometry(max_len, num_heads, d_head, dtype,
                                max_query_span, tile)
    itemsize = np.dtype(dtype).itemsize
    sub = _sublane(dtype)
    s = max(1, int(max_query_span))
    d_pad = _pad(pack * d_head, 128)
    v_pad = d_pad if d_value is None else _pad(pack * d_value, 128)
    row_heads = cache_row_heads(num_kv_heads, dtype) if pack == 1 \
        else num_kv_heads // pack
    q_rows = s * _pad(num_heads, 8)

    def need(cand):
        chunk = _chunk_rows(cand, row_heads, q_rows)
        return (_RING * _pad(cand * row_heads, sub) * (d_pad + v_pad)
                * itemsize                                       # K+V ring
                + 2 * s * _pad(num_heads, sub) * (d_pad + v_pad)
                * itemsize                                       # q+out x2 buf
                + _pad(q_rows, sub) * d_pad * itemsize           # query rows
                + q_rows * v_pad * 4                             # f32 acc
                + 2 * q_rows * 128 * 4                           # m + l
                + q_rows * _pad(chunk, 128) * (4 + 4 + itemsize))  # logits, p

    fits = [c for c in (_TILE_CANDIDATES if tile is None else (int(tile),))
            if c > 0 and c % sub == 0 and max_len % c == 0
            and c <= max_len // 2 and (most is None or c <= most)
            and need(c) <= _VMEM_BUDGET]
    if not fits:
        return None
    small = [c for c in fits
             if c * row_heads * d_pad * itemsize <= _TILE_BYTES]
    cand = small[0] if small else fits[-1]
    return PagedGeometry(cand, max_len // cand, need(cand))


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
                              max_query_span: int = 1, **kind) -> str:
    """The one parser for ``attention_backend`` (SlotEngine /
    LLMServer) — returns the RESOLVED backend
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
      than dense — tests and parity audits only).

    ``kind``: :func:`paged_geometry`'s ``d_value``, ``pack`` and ``most``
    for the layer kind asked about; a model of several kinds asks for each
    and is paged where all are."""
    if backend not in ATTENTION_BACKENDS:
        raise ValueError(
            f"attention_backend={backend!r}: must be one of "
            f"{ATTENTION_BACKENDS}")
    if backend == "dense":
        return "dense"
    geo = paged_geometry(max_len, num_heads, num_kv_heads, d_head, dtype,
                         max_query_span=max_query_span, **kind)
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


# ---------------------------------------------------------------------------
# the byte ledger (exact DMA accounting, for telemetry)
# ---------------------------------------------------------------------------

def paged_live_tiles(spans, tile: int, window: Optional[int] = None,
                     query_span: int = 1) -> int:
    """Tiles ONE layer's kernel call fetches, and loop trips it makes,
    for ``spans``: ``ceil(span / tile)`` a slot, at least one; behind a
    ``window`` less the ``floor((span - (query_span - 1) - window) /
    tile)`` tiles that lie wholly before the first query's window."""
    spans = np.maximum(np.asarray(spans, np.int64), 1)
    tiles = -(-spans // tile)
    if window is not None:
        tiles = tiles - np.maximum(spans - (query_span - 1) - window, 0) \
            // tile
    return int(tiles.sum())


def paged_read_bytes(spans, tile: int, num_kv_heads: int, d_head: int,
                     itemsize: int, num_layers: int = 1,
                     window: Optional[int] = None,
                     query_span: int = 1, d_value: Optional[int] = None,
                     pack: int = 1) -> int:
    """K/V bytes ONE paged decode step DMAs for ``spans``: each slot
    reads ``ceil(span / tile)`` tiles of K and of V per layer (behind a
    ``window``, the tiles from the window's first on:
    :func:`paged_live_tiles`) — the
    kernel starts one copy of K and one of V a live tile and none
    else, so this is exact by construction, not an estimate
    (``tests/test_llm_paged.py`` counts the copies).

    ``num_kv_heads`` is the heads a cache ROW holds
    (``LlamaConfig.kv_cache_heads``: a padded row's padding arrives with
    its tile), and a head narrower than 128 lanes is fetched at 128 (the
    kernel's wrapper pads it).  ``spans`` must cover EVERY slot in the
    launch, not just the active ones: an inactive slot (span 1) still
    fetches its first tile.  ``d_value``: the value's width where it is
    not the key's; ``pack``: the heads a packed row holds side by side (its
    lanes are ``pack`` widths padded to 128: 2 x 192 is 384, no padding).
    A ring changes nothing here: the tiles walked are the same."""
    k_lanes = _pad(pack * d_head, 128)
    v_lanes = k_lanes if d_value is None else _pad(pack * d_value, 128)
    return int(num_layers
               * paged_live_tiles(spans, tile, window, query_span) * tile
               * (num_kv_heads // pack) * (k_lanes + v_lanes) * itemsize)


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

def _make_decode_kernel(s_len: int, heads: int, group: int, row_heads: int,
                        tile: int, total_tiles: int, d_head: int, chunk: int,
                        window: Optional[int] = None, pack: int = 1,
                        sink: bool = False, ring: bool = False):
    """``heads`` query heads in groups of ``group`` over the first K/V
    heads of a cache row of ``row_heads``; ``d_head`` the model's (the
    lane width the kernel sees may be padded past it).  ``window``: a
    query at position ``p`` sees the keys ``p - window < j <= p``, and a
    slot's walk starts at the tile that holds the first query's first
    visible key.

    ``pack`` K/V heads side by side in one flat row (``row_heads`` is then
    the flat rows a position takes, K/V heads over ``pack``): flat row ``c``
    of a chunk holds heads ``pack * (c % row_heads) ..`` of key ``c //
    row_heads``, the query rows come with their head's lanes filled and the
    other heads' lanes zero, so the one contraction is still each query's
    dot with its own head, and the accumulator's lanes are ``pack`` values
    wide, of which the wrapper keeps the query's own.  ``sink``: one more
    input ``(S*hp, 128)`` float32, the sink logit of each query row; the
    online softmax starts from it (running max the logit, normaliser
    ``exp(0)``, no value) in place of (lowest, 0).  ``ring``: the cache
    row is a ring of ``total_tiles`` tiles and position tile ``t`` lies in
    tile ``t mod total_tiles`` of it; the walk is by position as ever."""
    neg = float(np.finfo(np.float32).min)
    hp = _pad(heads, 8)               # rows a query position takes
    q_rows = s_len * hp
    rows = tile * row_heads           # flat K/V rows of a tile
    scale = 1.0 / np.sqrt(d_head)

    def kernel(spans_ref, q_ref, k_hbm, v_hbm, *refs):
        """Grid ``(n_slots,)``.  q/out blocks ``(1, S, H, D)`` (S == 1
        is the plain decode step; S > 1 the speculative-verify span,
        whose S query positions amortize ONE read of the span); K and V
        whole in HBM as flat rows ``(n_slots, max_len * row_heads, D)``;
        ``kbuf``/``vbuf`` the ring; ``cur_ref`` (SMEM, kept across grid
        steps): tiles consumed, tiles issued, and the (slot, tile) the
        next DMA fetches.  Scratch rows: query position j's head h at
        ``j * hp + h`` (``hp`` is H padded to 8; a padding row is a
        zero query of K/V head 0, finite and never written out)."""
        sink_ref = refs[0] if sink else None
        (o_ref, kbuf, vbuf, sem, cur_ref, qs_ref, acc_ref, m_ref,
         l_ref) = refs[1:] if sink else refs
        s = pl.program_id(0)
        n_slots = pl.num_programs(0)
        span = spans_ref[s]

        def live_tiles(slot):
            # the tile after the last live one: at least the first (an
            # idle slot's span is 1), never past the cache row (a ring
            # has no end: its tiles are counted by position)
            last = lax.div(spans_ref[slot] + (tile - 1), tile)
            if ring:
                return jnp.maximum(last, 1)
            return jnp.clip(last, 1, total_tiles)

        def first_tile(slot):
            # the tile of the first key the first query sees: key
            # span - (S - 1) - window
            if window is None:
                return 0
            return lax.div(jnp.maximum(
                spans_ref[slot] - (s_len - 1) - window, 0), tile)

        def copies(slot, t, buf):
            src = pl.ds((lax.rem(t, total_tiles) if ring else t) * rows,
                        rows)
            return (pltpu.make_async_copy(k_hbm.at[slot, src], kbuf.at[buf],
                                          sem.at[0, buf]),
                    pltpu.make_async_copy(v_hbm.at[slot, src], vbuf.at[buf],
                                          sem.at[1, buf]))

        def issue():
            # fetch the cursor's tile into the ring (if one is left) and
            # move the cursor to the next live tile, this slot's or the
            # next slot's first
            issued, slot, t = cur_ref[1], cur_ref[2], cur_ref[3]

            @pl.when(slot < n_slots)
            def _():
                for c in copies(slot, t, lax.rem(issued, _RING)):
                    c.start()
            last = t + 1 >= live_tiles(jnp.minimum(slot, n_slots - 1))
            cur_ref[1] = issued + 1
            cur_ref[2] = jnp.where(last, slot + 1, slot)
            cur_ref[3] = jnp.where(
                last, first_tile(jnp.minimum(slot + 1, n_slots - 1)), t + 1)

        @pl.when(s == 0)
        def _first():
            for i in range(3):
                cur_ref[i] = 0
            cur_ref[3] = first_tile(0)
            for _ in range(_RING - 1):
                issue()

        acc_ref[...] = jnp.zeros_like(acc_ref)
        if sink:
            m_ref[...] = sink_ref[...]
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, neg)
            l_ref[...] = jnp.zeros_like(l_ref)
        if hp != heads:
            qs_ref[...] = jnp.zeros_like(qs_ref)
        for j in range(s_len):
            qs_ref[j * hp:j * hp + heads, :] = q_ref[0, j]

        # ``span`` counts the keys the LAST query attends: query j sits at
        # position span-S+j and attends keys <= itself, i.e. key <
        # span-(S-1)+j — for S == 1 the causal mask degenerates to the
        # live-span mask (same finfo-min fill as the dense path: exp
        # underflows to probability 0.0 either way).  A flat row c of a
        # chunk is key ``c // row_heads`` of K/V head ``c % row_heads``:
        # a query attends the rows of its own K/V head alone
        r = lax.broadcasted_iota(jnp.int32, (q_rows, 1), 0)
        kv_of_row = jnp.where(r % hp < heads, (r % hp) // group, 0)
        if pack != 1:
            kv_of_row = kv_of_row // pack       # the flat row of its head
        limit = span - (s_len - 1) + r // hp                 # (S*hp, 1)
        floor = None if window is None else limit - window   # first key seen
        c = lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        own = c % row_heads == kv_of_row                     # (S*hp, C)
        key_of_col = c // row_heads                          # (1, C)

        def tile_body(t, carry):
            issue()
            buf = lax.rem(cur_ref[0], _RING)
            for cp in copies(s, t, buf):
                cp.wait()
            q = qs_ref[...]
            for c0 in range(0, rows, chunk):
                k = kbuf[buf, c0:c0 + chunk, :]              # (C, D)
                logits = lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                kpos = t * tile + c0 // row_heads + key_of_col
                seen = jnp.logical_and(own, kpos < limit)
                if floor is not None:
                    seen = jnp.logical_and(seen, kpos >= floor)
                logits = jnp.where(seen, logits, neg)        # (S*hp, C)
                m_prev = m_ref[:, 0:1]
                m_new = jnp.maximum(
                    m_prev, jnp.max(logits, -1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(logits - m_new)
                v = vbuf[buf, c0:c0 + chunk, :]              # (C, D)
                pv = lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc_ref[...] = acc_ref[...] * alpha + pv
                l_ref[:, 0:1] = (l_ref[:, 0:1] * alpha
                                 + jnp.sum(p, -1, keepdims=True))
                m_ref[:, 0:1] = m_new
            cur_ref[0] = cur_ref[0] + 1
            return carry

        lax.fori_loop(first_tile(s), live_tiles(s), tile_body, 0)
        # every live query attends >= 1 unmasked key whose probability
        # at the running max is exp(0) = 1, so l >= 1; the floor only
        # guards the impossible all-masked row
        out = acc_ref[...] / jnp.maximum(l_ref[:, 0:1], 1e-30)
        for j in range(s_len):
            o_ref[0, j] = out[j * hp:j * hp + heads, :].astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("tile", "num_tiles",
                                             "interpret", "kv_heads",
                                             "window", "pack", "ring"))
def paged_decode_attention(q: jnp.ndarray,      # (B, H, D) | (B, S, H, D)
                           k: jnp.ndarray,      # (B, max_len, KV, D)
                           v: jnp.ndarray,      # (B, max_len, KV, D)
                           spans: jnp.ndarray,  # (B,) int32 live lengths
                           tile: int,
                           num_tiles: Optional[int] = None,
                           interpret: bool = False,
                           kv_heads: Optional[int] = None,
                           window: Optional[int] = None,
                           pack: int = 1, ring: bool = False,
                           sink: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """One decode step's attention for every slot, reading only each
    slot's live K/V span: → same shape as ``q``, in ``q.dtype``.

    ``q`` may carry a query-span dimension ``S`` (``(B, S, H, D)`` —
    the speculative-verify step, where slot b's query j sits at
    position ``spans[b]-S+j``); a 3-D ``q`` is the plain S == 1 decode
    step.  ``spans[b]`` is slot b's live length INCLUDING this step's
    S written positions (the LAST query attends keys ``[0, spans[b])``;
    earlier queries attend one key fewer each — the in-span causal
    mask), at least S and at most ``max_len``.  The queries' own K/V
    must already be written — the engine's scatter runs BEFORE
    attention, as in the dense path.  ``kv_heads``: the heads of
    ``k``/``v`` that are real, the first ones, where a cache row is
    padded past them (``LlamaConfig.kv_cache_heads``); the padding is
    fetched with its tile and weighs nothing (it must be finite: the
    cache's zeros).  ``window`` (None: every earlier key): a query at
    position ``p`` attends the keys ``p - window < j <= p``; the walk then
    starts at tile ``floor((span - (S - 1) - window) / tile)``, masks that
    tile's keys before the window, and neither fetches nor counts a tile
    before it.  ``num_tiles`` is accepted and ignored: the kernel
    walks each slot's live tiles itself, there is no span bucket (the
    benchmark harness's naming test still passes it; PERF.md §7).

    A head width that is no multiple of 128 lanes is padded to one here:
    at such a width XLA already relays the whole cache for the kernel
    every step (PERF.md §6, PR 22), and the padding rides that copy.

    PACKED rows (3-D ``k (B, rows * KV / pack, pack * D)`` and ``v (B,
    rows * KV / pack, pack * Dv)``, ``kv_heads`` required): the layout a
    kind with an ``attention_kinds`` entry keeps its cache in; the value's
    width may differ from the key's and the result is ``(B, S, H, Dv)``.
    ``ring``: the ``rows`` are a ring, position ``p`` in row ``p mod rows``
    (a window layer's: the walk touches ``window / tile + 1`` position tiles
    at most, which the ring's rule on sizes keeps distinct), and ``spans``
    may pass ``rows``.  ``sink (H,)`` float32: a logit a query head that
    every query sees beside its keys and that carries no value."""
    del num_tiles
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, S, H, d_head = q.shape
    packed = k.ndim == 3
    if packed:
        heads = int(kv_heads)
        assert H % heads == 0 and heads % pack == 0, (H, heads, pack)
        row_heads = heads // pack
        T = k.shape[1] // row_heads
        d_value = v.shape[-1] // pack
        # a query beside zeros: head h's lanes are those of its K/V head's
        # place in the packed row
        place = (jnp.arange(H) // (H // heads)) % pack              # (H,)
        own = place[:, None] == jnp.arange(pack)[None, :]           # (H, pack)
        q = jnp.where(own[:, :, None], q[..., None, :], 0).reshape(
            B, S, H, pack * d_head)
        D, Dv = _pad(pack * d_head, 128), _pad(pack * d_value, 128)
        if D != q.shape[-1]:
            q = jnp.pad(q, ((0, 0),) * 3 + ((0, D - q.shape[-1]),))
            k = jnp.pad(k, ((0, 0),) * 2 + ((0, D - k.shape[-1]),))
        if Dv != v.shape[-1]:
            v = jnp.pad(v, ((0, 0),) * 2 + ((0, Dv - v.shape[-1]),))
    else:
        T, row_heads = k.shape[1], k.shape[2]
        heads = kv_heads or row_heads
        assert H % heads == 0 and heads <= row_heads, (H, heads, row_heads)
        D = Dv = _pad(d_head, 128)
        if D != d_head:
            lanes = ((0, 0),) * 3 + ((0, D - d_head),)
            q, k, v = (jnp.pad(a, lanes) for a in (q, k, v))
    hp = _pad(H, 8)
    q_rows = S * hp
    rows = tile * row_heads
    chunk = _chunk_rows(tile, row_heads, q_rows)
    in_specs = [
        pl.BlockSpec((1, S, H, D), lambda s, *_: (s, 0, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [q] + ([k, v] if packed else [
        # a position's (row_heads, D) cache row is whole (8, 128) memory
        # tiles, so the flat-row view is the same bytes: a bitcast
        k.reshape(B, T * row_heads, D), v.reshape(B, T * row_heads, D)])
    if sink is not None:
        # each query row's sink logit, on every lane
        per_row = jnp.pad(sink.astype(jnp.float32), (0, hp - H))
        operands.append(jnp.broadcast_to(
            jnp.tile(per_row, S)[:, None], (q_rows, 128)))
        in_specs.append(pl.BlockSpec((q_rows, 128), lambda s, *_: (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, S, H, Dv), lambda s, *_: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_RING, rows, D), k.dtype),   # K ring
            pltpu.VMEM((_RING, rows, Dv), v.dtype),  # V ring
            pltpu.SemaphoreType.DMA((2, _RING)),
            pltpu.SMEM((4,), jnp.int32),             # the DMA cursor
            pltpu.VMEM((q_rows, D), q.dtype),        # query rows
            pltpu.VMEM((q_rows, Dv), jnp.float32),   # online-softmax acc
            pltpu.VMEM((q_rows, 128), jnp.float32),  # running max (lane 0)
            pltpu.VMEM((q_rows, 128), jnp.float32),  # normalizer (lane 0)
        ],
    )
    out = pl.pallas_call(
        _make_decode_kernel(S, H, H // heads, row_heads, tile, T // tile,
                            d_head, chunk, window, pack, sink is not None,
                            ring),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H, Dv), q.dtype),
        # the ring's cursor runs from one slot into the next: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(spans.astype(jnp.int32), *operands)
    if packed:
        out = out[..., :pack * d_value].reshape(B, S, H, pack, d_value)
        out = jnp.take_along_axis(
            out, place[None, None, :, None, None], axis=3)[:, :, :, 0]
    else:
        out = out[..., :d_head]
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# decode over latent rows
# ---------------------------------------------------------------------------

#: a latent tile is keys and values at once, so it takes the bytes of the
#: paged kernel's K tile and V tile together
_LATENT_TILE_BYTES = 2 * _TILE_BYTES


def _latent_geometry(max_len: int, num_heads: int, width: int, dtype: Any,
                     max_query_span: int = 1, tile: Optional[int] = None
                     ) -> Optional[PagedGeometry]:
    """:func:`paged_geometry` for :func:`latent_decode_attention`: rows of
    ``width`` lanes padded to 128, ``S * H`` query rows; the value is the
    row's first lanes, so the ring holds one tile a buffer and the
    accumulator is counted at the row's width.  Of the candidates that fit,
    the largest whose tile is at most ``_LATENT_TILE_BYTES``."""
    itemsize = np.dtype(dtype).itemsize
    sub = _sublane(dtype)
    lanes = _pad(width, 128)
    q_rows = _pad(max(1, int(max_query_span)) * num_heads, 8)

    def need(cand):
        return (_RING * _pad(cand, sub) * lanes * itemsize       # the ring
                + 4 * q_rows * lanes * itemsize                 # q, out x2
                + q_rows * lanes * 4 + 2 * q_rows * 128 * 4     # acc, m, l
                + q_rows * _pad(cand, 128) * (4 + 4 + itemsize))  # scores, p

    fits = [c for c in (_TILE_CANDIDATES if tile is None else (int(tile),))
            if c > 0 and c % sub == 0 and max_len % c == 0
            and c <= max_len // 2 and need(c) <= _VMEM_BUDGET]
    if not fits:
        return None
    small = [c for c in fits if c * lanes * itemsize <= _LATENT_TILE_BYTES]
    cand = small[0] if small else fits[-1]
    return PagedGeometry(cand, max_len // cand, need(cand))


def _make_latent_kernel(s_len: int, heads: int, q_rows: int, tile: int,
                        total_tiles: int, v_lanes: int, scale: float):
    """Grid ``(n_slots,)``; q block ``(1, q_rows, lanes)`` (query position
    ``j``'s head ``h`` at row ``j * heads + h``, rows past ``S * heads``
    padding), the latent rows whole in HBM ``(n_slots, max_len, lanes)``, a
    ring of ``_RING`` tiles and the DMA cursor as in
    :func:`_make_decode_kernel`.  All query rows against a tile in ONE
    contraction over its lanes; the values are the tile's first
    ``v_lanes`` lanes, the same bytes, fetched once."""
    neg = float(np.finfo(np.float32).min)

    def kernel(spans_ref, q_ref, c_hbm, o_ref, cbuf, sem, cur_ref, acc_ref,
               m_ref, l_ref):
        s = pl.program_id(0)
        n_slots = pl.num_programs(0)
        span = spans_ref[s]

        def live_tiles(slot):
            return jnp.clip(lax.div(spans_ref[slot] + (tile - 1), tile), 1,
                            total_tiles)

        def copy(slot, t, buf):
            return pltpu.make_async_copy(c_hbm.at[slot, pl.ds(t * tile, tile)],
                                         cbuf.at[buf], sem.at[buf])

        def issue():
            issued, slot, t = cur_ref[1], cur_ref[2], cur_ref[3]

            @pl.when(slot < n_slots)
            def _():
                copy(slot, t, lax.rem(issued, _RING)).start()
            last = t + 1 >= live_tiles(jnp.minimum(slot, n_slots - 1))
            cur_ref[1] = issued + 1
            cur_ref[2] = jnp.where(last, slot + 1, slot)
            cur_ref[3] = jnp.where(last, 0, t + 1)

        @pl.when(s == 0)
        def _first():
            for i in range(4):
                cur_ref[i] = 0
            for _ in range(_RING - 1):
                issue()

        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, neg)
        l_ref[...] = jnp.zeros_like(l_ref)
        # query j attends keys < span - (S - 1) + j (the in-span causal
        # mask); a padding row is the last query's
        r = lax.broadcasted_iota(jnp.int32, (q_rows, 1), 0)
        limit = span - (s_len - 1) + jnp.minimum(r // heads, s_len - 1)
        col = lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        q = q_ref[0]

        def tile_body(t, carry):
            issue()
            buf = lax.rem(cur_ref[0], _RING)
            copy(s, t, buf).wait()
            rows = cbuf[buf]                                 # (tile, lanes)
            logits = lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (q_rows, tile)
            logits = jnp.where(t * tile + col < limit, logits, neg)
            m_prev = m_ref[:, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(logits - m_new)
            pv = lax.dot_general(
                p.astype(rows.dtype), rows[:, :v_lanes],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            acc_ref[...] = acc_ref[...] * alpha + pv
            l_ref[:, 0:1] = l_ref[:, 0:1] * alpha \
                + jnp.sum(p, -1, keepdims=True)
            m_ref[:, 0:1] = m_new
            cur_ref[0] = cur_ref[0] + 1
            return carry

        lax.fori_loop(0, live_tiles(s), tile_body, 0)
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, 0:1], 1e-30)
                    ).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("tile", "rank", "scale",
                                             "interpret"))
def latent_decode_attention(q: jnp.ndarray,       # (B, S, H, lanes)
                            rows: jnp.ndarray,    # (B, max_len, lanes)
                            spans: jnp.ndarray,   # (B,) int32
                            *, tile: int, rank: int, scale: float,
                            interpret: bool = False) -> jnp.ndarray:
    """Latent attention's decode step in its absorbed form (``model
    .LatentAttention``), reading each slot's live latent rows only: ->
    ``(B, S, H, rank)`` in ``q.dtype``, ``sum_j p_h,ij c_j``.

    ``q`` is ``[q~_h | q_pe_h | zeros]`` a query head, as wide as a cache row;
    the score of key ``j`` is ``q . rows[j] * scale`` and the value its first
    ``rank`` lanes (``c_j``).  ``spans`` as :func:`paged_decode_attention`
    takes them: the LAST query attends keys ``[0, spans[b])``, the rows of
    this step already written.  Grid ``(n_slots,)``: a slot's ``ceil(span /
    tile)`` live tiles arrive through a ring of ``_RING`` VMEM buffers whose
    DMA cursor runs over slot boundaries (the paged kernel's walk), and all ``S * H``
    query rows meet a tile in one contraction over its lanes, products in
    float32, operands in the cache's dtype, the probabilities cast to it
    before they meet the values.  Its name in a device trace is
    ``latent_decode_attention``."""
    B, S, H, lanes = q.shape
    T = rows.shape[1]
    assert rows.shape[-1] == lanes and lanes % 128 == 0, (q.shape, rows.shape)
    q_rows = _pad(S * H, 8)
    v_lanes = min(_pad(rank, 128), lanes)
    q2 = q.reshape(B, S * H, lanes)
    if q_rows != S * H:
        q2 = jnp.pad(q2, ((0, 0), (0, q_rows - S * H), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, q_rows, lanes), lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, q_rows, v_lanes), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_RING, tile, lanes), rows.dtype),   # the ring
            pltpu.SemaphoreType.DMA((_RING,)),
            pltpu.SMEM((4,), jnp.int32),                    # the DMA cursor
            pltpu.VMEM((q_rows, v_lanes), jnp.float32),     # accumulator
            pltpu.VMEM((q_rows, 128), jnp.float32),         # running max
            pltpu.VMEM((q_rows, 128), jnp.float32),         # normaliser
        ])
    out = pl.pallas_call(
        _make_latent_kernel(S, H, q_rows, tile, T // tile, v_lanes,
                            float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, q_rows, v_lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="latent_decode_attention",
        interpret=interpret,
    )(spans.astype(jnp.int32), q2, rows)
    return out[:, :S * H, :rank].reshape(B, S, H, rank)


#: the absorbed form's cost a product against the expanded form's: what
#: :func:`latent_prefill_form` weighs the two counts by (PERF.md section 6
#: has the chip's readings of both forms)
_ABSORBED_COST = 1.0


def latent_prefill_form(S: int, T: int, H: int, rank: int, d_nope: int,
                        d_rope: int, d_v: int, lanes: int) -> str:
    """Which form of latent attention a prefill pass of ``S`` queries after
    a cached prefix takes, over an entry of ``T`` rows: ``"absorbed"`` where
    its products, weighed by ``_ABSORBED_COST``, are fewer than the expanded
    form's, else ``"expanded"``.  The one home of the rule
    (``model.LatentAttention`` and the engine's span both ask it).

    Expanded: every row multiplied out to ``H`` heads' keys and values
    (``T * rank * H * (d_nope + d_v)`` products), then ``S x T`` scores and
    values a head at the keys' and values' widths.  Absorbed: the query
    folded and the result unfolded (``2 * S * H * rank * d`` products), then
    ``S x T`` scores over a row's ``lanes`` and values over ``rank`` a head.
    The expansion grows with the prefix alone, the absorbed scores with
    the tail times the prefix: at equal cost absorbed below ``S* = T rank
    (d_nope + d_v) / (T (lanes + rank - d_nope - d_rope - d_v) + rank (d_nope
    + d_v))`` queries, 156 at A.X-K1's widths over 17,920 rows."""
    expand = 2 * T * rank * H * (d_nope + d_v) \
        + 2 * S * T * H * (d_nope + d_rope + d_v)
    absorb = 2 * S * H * rank * (d_nope + d_v) \
        + 2 * S * T * H * (lanes + rank)
    return "absorbed" if absorb * _ABSORBED_COST < expand else "expanded"


# ---------------------------------------------------------------------------
# prefill: one tiled causal kernel
# ---------------------------------------------------------------------------

#: rows ``bq x group`` of one score block the geometry aims at, and the
#: most keys a block holds: a ``(1024, 512)`` float32 score block is 2 MiB,
#: so scores, probabilities, their cast and the accumulator stay under
#: ``_VMEM_BUDGET`` with K, V, q and the output double-buffered
_PREFILL_ROWS = 1024
_PREFILL_KEYS = 512
#: the fewest keys a block holds where the keys have that many: a score
#: block narrower than the 128 lanes wastes the rest of them
_PREFILL_MIN_KEYS = 128

#: the plain path's ``(heads, S, T)`` float32 scores under which a prefill
#: stays on it.  Under this many bytes the scores' three passes stay cheap
#: beside the kernel's fixed cost (PERF.md §6, PR 37: at 128 heads 32
#: queries over 5,632 keys, 92 MB, and at Olmo's group-1 heads 512 over
#: 1,536, 94 MB, the kernel loses; from 128 MiB on it wins on every kind
#: measured), and a bucket that stays there traces no kernel: set-up
_PREFILL_MIN_SCORE_BYTES = 128 << 20


@dataclasses.dataclass(frozen=True)
class PrefillGeometry:
    """The prefill kernel's tile for one shape: ``bq`` query positions (all
    ``group`` query heads of a K/V head ride one block: ``bq x group``
    rows) by ``bk`` key rows; ``key_steps`` the key blocks one query block
    can visit at most (the grid's last axis: every block of the keys on a
    full layer, those a window can reach behind one); the VMEM working
    set the gate admitted."""
    bq: int
    bk: int
    key_steps: int
    vmem_bytes: int


def _pow2_divisor(n: int, most: int, least: int) -> Optional[int]:
    """The largest power of two in ``[least, most]`` that divides ``n``."""
    b = 1
    while b * 2 <= most:
        b *= 2
    while b >= least:
        if n % b == 0:
            return b
        b //= 2
    return None


def _prefill_key_steps(T: int, bq: int, bk: int,
                       window: Optional[int]) -> int:
    """Key blocks one query block can visit: a span of ``bq + window - 1``
    keys at any alignment touches ``(span - 1) // bk + 2`` blocks."""
    if window is None:
        return T // bk
    return min(T // bk, (bq + window - 2) // bk + 2)


def prefill_geometry(S: int, T: int, H: int, KV: int, D: int, Dv: int,
                     dtype: Any = jnp.bfloat16,
                     window: Optional[int] = None
                     ) -> Optional[PrefillGeometry]:
    """The tile of :func:`prefill_attention` for ``S`` queries of ``H`` heads
    over ``T`` key rows of ``KV`` heads (keys ``D`` wide, values ``Dv``),
    behind a ``window`` or none: the one home of the kernel's VMEM
    arithmetic, as :func:`paged_geometry` is of the decode kernel's.  None
    means the plain dense path, and a shape gets it

    - where the plain path's float32 scores ``H x S x T x 4`` stay under
      ``_PREFILL_MIN_SCORE_BYTES``: the kernel does not win there (or not
      by what tracing it into one more program costs at every start);
    - where no block divides the shape (``S`` into ``bq`` positions a
      sublane multiple, ``T`` into ``bk`` rows of at least 128 where ``T``
      has them), or nothing fits VMEM.

    One algorithm whose parameters follow the shape: ``bq`` is the largest
    power of two that gives at most ``_PREFILL_ROWS`` rows ``bq x (H /
    KV)``, ``bk`` the largest up to ``_PREFILL_KEYS`` (behind a window, up
    to the window: a block wider than the window is mostly masked)."""
    if H % KV or H * S * T * 4 < _PREFILL_MIN_SCORE_BYTES:
        return None
    itemsize = np.dtype(dtype).itemsize
    sub = _sublane(dtype)
    G = H // KV
    most_keys = _PREFILL_KEYS if window is None else \
        min(_PREFILL_KEYS, max(_PREFILL_MIN_KEYS,
                               1 << (int(window) - 1).bit_length()))
    bk = _pow2_divisor(T, most_keys,
                       min(_PREFILL_MIN_KEYS, T) if T % 8 == 0 else T + 1)
    if bk is None:
        return None
    d_pad, v_pad = _pad(D, 128), _pad(Dv, 128)

    def need(bq, bk):
        rows = G * bq
        return (2 * rows * (d_pad + v_pad) * itemsize       # q + out, x2 buf
                + 2 * _pad(bk, sub) * (d_pad + v_pad) * itemsize  # K + V x2
                + rows * v_pad * 4 + 2 * rows * 128 * 4      # acc, m, l
                + rows * _pad(bk, 128) * (4 + 4 + itemsize))  # scores, p

    def fit(bk):
        bq = _pow2_divisor(S, max(sub, min(512, _PREFILL_ROWS // G)), sub)
        while bq is not None and need(bq, bk) > _VMEM_BUDGET:
            bq = _pow2_divisor(S, bq // 2, sub) if bq // 2 >= sub else None
        return bq

    bq = fit(bk)
    # where no query block fits beside the widest key block (a row of 640
    # lanes for 64 query heads: latent attention's absorbed form), narrower
    # key blocks, while they keep the 128 lanes
    while bq is None and bk // 2 >= _PREFILL_MIN_KEYS and T % (bk // 2) == 0:
        bk //= 2
        bq = fit(bk)
    if bq is None:
        return None
    return PrefillGeometry(bq, bk, _prefill_key_steps(T, bq, bk, window),
                           need(bq, bk))


def _prefill_block_bounds(i, bq: int, bk: int, window: Optional[int],
                          qoff, plen, kmin, xp=jnp):
    """First and last key block query block ``i`` visits, in rows of the
    keys: the block of the first key its first query sees (none before
    ``kmin``, none before the window) and the block of its last REAL
    query's own row.  Written once for the kernel (traced scalars) and the
    host's count (``xp=np``: nothing of it may reach the device, whose
    queue an admission would then wait behind)."""
    q_lo = qoff + i * bq
    q_hi = qoff + xp.minimum((i + 1) * bq, plen) - 1
    first = kmin if window is None else \
        xp.maximum(kmin, q_lo - (window - 1))
    return first // bk, q_hi // bk


def prefill_key_blocks(geo: PrefillGeometry, S: int, start: int, plen: int,
                       window: Optional[int] = None,
                       key_offset: Optional[int] = None) -> int:
    """(query block, key block) pairs ONE K/V head of one layer's kernel
    call computes for ``plen`` real tokens of a bucket of ``S`` from
    position ``start``: host arithmetic from the same bounds the kernel
    walks (``engine.admit``'s ``prefill_key_blocks_visited``; with ``plen
    = S`` the bucket's)."""
    off = 0 if key_offset is None else int(key_offset)
    i = np.arange(-(-int(plen) // geo.bq))
    lo, hi = _prefill_block_bounds(i, geo.bq, geo.bk, window,
                                   int(start) - off, int(plen),
                                   max(0, -off), xp=np)
    return int(np.sum(np.maximum(hi - lo + 1, 0)))


def _make_prefill_kernel(group: int, bq: int, bk: int,
                         key_steps: int, d_head: int,
                         window: Optional[int], sink: bool, offset: bool,
                         scale: Optional[float] = None):
    """ONE query block: grid ``(B, KV, key_steps)``, the key axis innermost.
    Blocks: ``q (1, 1, G, bq, D)`` (row ``g * bq + s`` of the score block
    is query head ``g`` of the group at the block's position ``s``), ``k
    (1, 1, bk, D)``, ``v (1, 1, bk, Dv)``, out ``(1, 1, G, bq, Dv)``.
    ``scal`` (SMEM, prefetched): the key row of the block's first query,
    how many of its queries are real, the first row that is a key.  Step
    ``j`` takes key block ``first + j`` while that is at most ``last``
    (:func:`_prefill_block_bounds`; the index map holds the block there
    afterwards, so nothing more is fetched) and does nothing else; a block
    with no real query takes none."""
    neg = float(np.finfo(np.float32).min)
    rows = group * bq
    scale = 1.0 / np.sqrt(d_head) if scale is None else scale

    def kernel(scal, q_ref, k_ref, v_ref, *refs):
        sink_ref = refs[0] if sink else None
        o_ref, acc_ref, m_ref, l_ref = refs[1:] if sink else refs
        j = pl.program_id(2)
        q_lo, plen, kmin = scal[0], scal[1], scal[2]
        first, last = _prefill_block_bounds(0, bq, bk, window, q_lo, plen,
                                            kmin)
        kb = first + j

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            if sink:                    # the sink's own term: exp(0)
                m_ref[...] = sink_ref[0]
                l_ref[...] = jnp.ones_like(l_ref)
            else:
                m_ref[...] = jnp.full_like(m_ref, neg)
                l_ref[...] = jnp.zeros_like(l_ref)

        def update(masked: bool):
            q = q_ref[0, 0].reshape(rows, q_ref.shape[-1])
            logits = lax.dot_general(
                q, k_ref[0, 0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale      # (rows, bk)
            if masked:
                r = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                qrow = q_lo + lax.rem(r, bq)
                c = kb * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
                seen = c <= qrow
                if window is not None:
                    seen = jnp.logical_and(seen, c > qrow - window)
                if offset:
                    seen = jnp.logical_and(seen, c >= kmin)
                # a row with no key in this block holds the lowest number
                # throughout: its exp(0) terms are wiped (alpha = 0) by
                # the first block that holds one, and every real query
                # sees its own key
                logits = jnp.where(seen, logits, neg)
            m_prev = m_ref[:, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(logits - m_new)
            pv = lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[...] = acc_ref[...] * alpha + pv
            l_ref[:, 0:1] = l_ref[:, 0:1] * alpha \
                + jnp.sum(p, -1, keepdims=True)
            m_ref[:, 0:1] = m_new

        active = jnp.logical_and(plen > 0, kb <= last)
        # a block needs its mask where a key lies past the first query's
        # own, before the last query's window, or before the first key
        edge = kb * bk + (bk - 1) > q_lo
        if window is not None:
            edge = jnp.logical_or(edge, kb * bk < q_lo + bq - window)
        if offset:
            edge = jnp.logical_or(edge, kb * bk < kmin)

        @pl.when(jnp.logical_and(active, edge))
        def _edge():
            update(True)

        @pl.when(jnp.logical_and(active, jnp.logical_not(edge)))
        def _inner():
            update(False)

        @pl.when(j == key_steps - 1)
        def _out():
            out = acc_ref[...] / jnp.maximum(l_ref[:, 0:1], 1e-30)
            r = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            # a row past the real queries is zeros, never what VMEM held
            o_ref[0, 0] = jnp.where(lax.rem(r, bq) < plen, out, 0.0).astype(
                o_ref.dtype).reshape(o_ref.shape[2:])

    return kernel


@functools.partial(jax.jit, static_argnames=("bk", "d_head", "window",
                                             "offset", "interpret", "scale"))
def prefill_query_block(scal: jnp.ndarray,     # (3,) int32
                        qt: jnp.ndarray,       # (B, KV, G, bq, D)
                        kt: jnp.ndarray,       # (B, KV, T, D)
                        vt: jnp.ndarray,       # (B, KV, T, Dv)
                        sink_rows: Optional[jnp.ndarray] = None, *,
                        bk: int, d_head: int, window: Optional[int] = None,
                        offset: bool = False,
                        interpret: bool = False,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """The prefill kernel over ONE block of ``bq`` query positions, K/V-head
    major, lanes padded: -> ``(B, KV, G, bq, Dv)``.  ``scal``: the key row
    of the block's first query, how many of its queries are real, the first
    row that is a key.  :func:`prefill_attention` maps it over a pass's
    query blocks.  It is the jitted entry because its shapes do not follow
    the bucket: every prefill program of an engine whose keys are ``T``
    rows calls the same one, so a process traces the kernel once a layer
    kind, not once a bucket (set-up: PERF.md section 6, PR 37)."""
    B, KV, G, bq, d_pad = qt.shape
    T, v_pad = kt.shape[2], vt.shape[-1]
    rows = G * bq
    nk = T // bk
    key_steps = _prefill_key_steps(T, bq, bk, window)

    def key_block(b, h, j, scal):
        first, last = _prefill_block_bounds(0, bq, bk, window, scal[0],
                                            scal[1], scal[2])
        # past the last block it visits the walk holds that block: a block
        # index that does not change is not fetched again
        blk = jnp.minimum(first + j, jnp.maximum(last, first))
        return b, h, jnp.minimum(blk, nk - 1), 0

    in_specs = [
        pl.BlockSpec((1, 1, G, bq, d_pad), lambda b, h, j, s: (b, h, 0, 0, 0)),
        pl.BlockSpec((1, 1, bk, d_pad), key_block),
        pl.BlockSpec((1, 1, bk, v_pad), key_block),
    ]
    operands = [qt, kt, vt]
    if sink_rows is not None:
        operands.append(sink_rows)
        in_specs.append(pl.BlockSpec((1, rows, 128),
                                     lambda b, h, j, s: (h, 0, 0)))
    return pl.pallas_call(
        _make_prefill_kernel(G, bq, bk, key_steps, d_head, window,
                             sink_rows is not None, offset, scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KV, key_steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, G, bq, v_pad),
                                   lambda b, h, j, s: (b, h, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, v_pad), jnp.float32),    # accumulator
                pltpu.VMEM((rows, 128), jnp.float32),      # running max
                pltpu.VMEM((rows, 128), jnp.float32),      # normaliser
            ]),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, bq, v_pad), qt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="prefill_attention",
        interpret=interpret,
    )(scal, *operands)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "kv_heads",
                                             "window", "interpret", "scale"))
def prefill_attention(q: jnp.ndarray,          # (B, S, H, D)
                      k: jnp.ndarray,          # (B, T, KV.., D)
                      v: jnp.ndarray,          # (B, T, KV.., Dv)
                      start: jnp.ndarray,      # () position of query 0
                      plen: jnp.ndarray,       # () real queries
                      *, bq: int, bk: int,
                      kv_heads: Optional[int] = None,
                      window: Optional[int] = None,
                      sink: Optional[jnp.ndarray] = None,
                      key_offset: Optional[jnp.ndarray] = None,
                      interpret: bool = False,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """Causal softmax attention of one prefill pass as one tiled kernel:
    -> ``(B, S, H * Dv)`` in ``q.dtype``.

    Query ``s`` sits at position ``start + s``; the first ``plen`` are real
    (the rest a bucket's padding: their rows come out as zeros).  Row ``j``
    of ``k``/``v`` is key position ``j`` (``key_offset + j`` where one is
    given, a ring's ``[rows before this pass | this pass]``; a row at a
    negative position is no key), already holding this pass's own rows.
    Query at ``p`` sees key ``j`` iff ``j <= p`` and, behind a ``window``,
    ``j > p - window``.  ``kv_heads``: the heads of a row that are real, the
    first ones (a cache row may be padded past them).  ``sink (H,)``: a logit
    a query head that every query sees beside its keys and that carries no
    value: the online softmax's first term.

    All ``H / KV`` query heads of a K/V head in one contraction (rows ``bq
    x group``), keys in blocks of ``bk``; operands in their own dtype,
    products accumulated in float32, scores and the online softmax in
    float32 in VMEM and never in HBM, probabilities cast to ``q.dtype``
    before the product with V as the plain path casts them.  The pass's
    query blocks go through :func:`prefill_query_block` in turn; a block
    visits only the key blocks that hold a key one of its real queries
    sees: none past its causal edge, none before its window, none past
    ``start + plen`` (not fetched either), and a query block wholly past
    ``plen`` none at all: a bucket pays for its real tokens.  ``bq``/``bk``
    come from :func:`prefill_geometry`.  A width that is no multiple of
    128 lanes is padded to one here (the copy into K/V-head-major order
    carries it).  ``scale``: the scores' factor (None: ``D^-0.5``)."""
    B, S, H, D = q.shape
    T, Dv = k.shape[1], v.shape[-1]
    KV = int(kv_heads or k.shape[2])
    assert H % KV == 0 and S % bq == 0 and T % bk == 0, (H, KV, S, bq, T, bk)
    G = H // KV
    # K/V-head-major: a block is then whole (rows, lanes) tiles of one head
    qt = jnp.transpose(q.reshape(B, S, KV, G, D), (0, 2, 3, 1, 4))
    kt = jnp.swapaxes(k[:, :, :KV], 1, 2)                  # (B, KV, T, D)
    vt = jnp.swapaxes(v[:, :, :KV], 1, 2)
    d_pad, v_pad = _pad(D, 128), _pad(Dv, 128)
    if d_pad != D:
        qt = jnp.pad(qt, ((0, 0),) * 4 + ((0, d_pad - D),))
        kt = jnp.pad(kt, ((0, 0),) * 3 + ((0, d_pad - D),))
    if v_pad != Dv:
        vt = jnp.pad(vt, ((0, 0),) * 3 + ((0, v_pad - Dv),))
    off = jnp.int32(0) if key_offset is None else \
        jnp.asarray(key_offset, jnp.int32)
    q_row0 = jnp.asarray(start, jnp.int32) - off
    plen = jnp.asarray(plen, jnp.int32)
    kmin = jnp.maximum(-off, 0)
    sink_rows = None
    if sink is not None:
        # each score row's sink logit, on every lane
        per_row = jnp.repeat(sink.astype(jnp.float32).reshape(KV, G), bq, 1)
        sink_rows = jnp.broadcast_to(per_row[:, :, None],
                                     (KV, G * bq, 128))

    def block(i):
        scal = jnp.stack([q_row0 + i * bq, jnp.clip(plen - i * bq, 0, bq),
                          kmin])
        return prefill_query_block(
            scal, lax.dynamic_slice_in_dim(qt, i * bq, bq, axis=3), kt, vt,
            sink_rows, bk=bk, d_head=D, window=window,
            offset=key_offset is not None, interpret=interpret, scale=scale)

    out = lax.map(block, jnp.arange(S // bq, dtype=jnp.int32))
    # (S / bq, B, KV, G, bq, Dv) -> (B, S / bq, bq, KV, G, Dv)
    out = jnp.transpose(out[..., :Dv], (1, 0, 4, 2, 3, 5))
    return out.reshape(B, S, H * Dv)
