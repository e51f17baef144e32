"""Pallas TPU histogram kernel — the GBDT hot op.

Replaces the XLA scatter-add histogram (TPU scatters serialize) with an MXU
formulation.  Each grid step loads a (features x CHUNK rows) tile of the
binned matrix and builds the features' one-hot bin matrices directly in
transposed "tall" layout (ft·B, CHUNK) in VMEM scratch, then runs ONE matmul
per step:

    hist_tile += OH(f·B+b, c) · vals(c, v)      # (ft·B, C) x (C, S·8)

The tall M dimension keeps the MXU rows busy: the MXU's time is M x C / 128
row pushes whatever the lane count, so S node slots ride the 128 lanes for
the price of one.

The matmul runs **int8 × int8 → int32**.  The one-hot is exact in int8, and
gradients/hessians are quantized to THREE balanced base-128 int8 limbs each
(signed digits in [-64, 63], range ±2^20 on a per-tree max-|value| scale),
so the histogram accumulates EXACT integer sums of 21-bit-quantized values
(quantization noise ~max|g|·2^-21·sqrt(count) per bin, below the error of
the bf16 hi/lo pair it replaced), and no order of rows, chunks or feature
tiles can change a bit of it.  Lanes per slot: [g0 g1 g2 h0 h1 h2 count pad].

What bounds it, read on a TPU v5e at 12,001,280 x 28, 256 bins (PERF.md §5,
PR 32).  Mosaic lowers the product to the MXU's native int8 mode and a plain
(2944, 2048) x (2048, 128) product runs at 362 Tops/s, 92% of the chip's
int8 peak and 1.92x the same product in bf16, so the products of a
2,048-row chunk of the two-level pass are 4.3 us.  The pass took 10.0 us a
chunk: its one-hot build (`(iota == b).astype(int8)`: 58,000 of the chunk's
80,000 vector operations), the 16-fold lane tile of the value block and four
grid steps a chunk all ran in sequence with the products.  One step a chunk
(:func:`fused_geometry`), the one-hot built four rows to a 32-bit word
(:func:`_onehot_words`) and the value block tiled once a tree
(:func:`prep_hist_vals_rows`) leave 4.4 us.  Timings this module quoted before
(15.1 → 10.5 ms a 1M x 28 level pass at 256 bins for int8 against bf16,
2.3 ms at 64 bins, 27 → 10.5 ms for the fused pass) were records of earlier
rounds on another chip and JAX; they are gone.

This is the TPU-native equivalent of LightGBM's C++ histogram construction
(reference: the native code behind LGBM_BoosterUpdateOneIter,
booster/LightGBMBooster.scala:359).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: value channels: g limbs ×3, h limbs ×3, count, pad
VALS = 8
#: value channels per node slot in the batched kernels
SLOT_LANES = 8

#: largest magnitude representable in 3 balanced base-128 digits
#: (63 + 63·128 + 63·16384)
_Q_MAX = 1_040_447.0


def _limbs(q: jnp.ndarray):
    """int32 quantized value → 3 balanced base-128 int32 digits in [-64, 63]."""
    d0 = ((q + 64) & 127) - 64
    q1 = (q - d0) >> 7                 # exact: (q - d0) divisible by 128
    d1 = ((q1 + 64) & 127) - 64
    d2 = (q1 - d1) >> 7                # in [-64, 63] after the clip in _quant
    return d0, d1, d2


def _quant(v: jnp.ndarray, scale: jnp.ndarray):
    q = jnp.clip(jnp.round(v / scale), -_Q_MAX, _Q_MAX).astype(jnp.int32)
    return _limbs(q)


def _reconstruct(out: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """int32 limb histogram (..., 8) → (..., 3) f32 [grad, hess, count].

    Limb sums can exceed 2^24, so each converts to f32 BEFORE combining
    (relative 2^-24 rounding, same class as the f32 adds the old bf16
    hi/lo pair paid)."""
    o = out.astype(jnp.float32)
    g = scales[0] * (o[..., 0] + 128.0 * o[..., 1] + 16384.0 * o[..., 2])
    h = scales[1] * (o[..., 3] + 128.0 * o[..., 4] + 16384.0 * o[..., 5])
    return jnp.stack([g, h, o[..., 6]], axis=-1)


def coarse_bins(total_bins: int, shift: int) -> int:
    """Histogram width of the coarse (``bin >> shift``) level, padded to a
    sublane multiple so the (ft·Bc, chunk) one-hot scratch tiles cleanly."""
    bc = -(-total_bins // (1 << shift))
    return -(-bc // 8) * 8


def _tile_for(total_bins: int):
    """(max features-per-step, rows-per-chunk) for the one-hot scratch of a
    histogram ``total_bins`` wide: at most 2,048 one-hot rows a step.

    The scratch is (ft·B, chunk) one-hot bytes and must fit VMEM (~16
    MB/core) alongside the resident (Fp·B, S·8) int32 accumulator.  Wider
    feature tiles and chunks amortize the per-grid-step cost: a step of
    224 one-hot rows is 0.6 us of products under a fixed cost of the same
    order (four such steps a chunk against one of 896 rows: 1.2 us a
    chunk; my chip run, PR 32)."""
    if total_bins <= 64:
        return 32, 2048
    if total_bins <= 128:
        return 16, 2048
    if total_bins <= 256:
        return 8, 2048
    return 8, 1024


def _feat_tile(num_features: int, cap: int) -> int:
    """Features per grid step: minimize feature padding, then maximize the
    tile.  The bins input is reshaped (G, ft, N) with block (1, ft, chunk)
    — legal for ANY ft because the block's second dim equals the array dim
    — so ft need not be a sublane multiple, and 28 features at B=256 run
    with ZERO junk feature rows in the matmul (ft=7) instead of the 12.5%
    a pad-to-8 layout wastes."""
    best = None
    for ft in range(1, cap + 1):
        pad = -(-num_features // ft) * ft - num_features
        key = (pad, -ft)
        if best is None or key < best:
            best = key
    return -best[1]


#: VMEM budget for kernel working sets (~16 MB/core minus block slack)
_VMEM_BUDGET = 13 * 1024 * 1024

#: the compiler's default scoped VMEM limit, which the budget leaves slack
#: under (a v5e core has 128 MiB)
_SCOPED_VMEM = 16 * 1024 * 1024


def _rows_block_bytes(rows: int, chunk: int) -> int:
    """VMEM of double-buffered int32 blocks of ``rows`` sublane rows in
    all by ``chunk`` lanes."""
    return 2 * rows * chunk * 4


def fused_geometry(num_features: int, total_bins: int, n_slots: int,
                   chunk_override: int = 0, hist_shift: int = 0,
                   refine_k: int = 0):
    """(ft, chunk) for the fused route+hist pass, or None if no geometry
    fits VMEM: the ONE home of the pass's VMEM arithmetic, keyed on the
    tiles it really builds.  ``hist_shift`` > 0 (two-level) builds
    ``Bh = coarse_bins(B, shift)``-row tiles, ``refine_k`` > 0 adds the
    refined block (``K x B`` one-hot rows and their accumulator), so the
    tile ladder is read at ``Bh``: at 28 features x 256 bins, shift 3,
    that is ONE feature group (ft = 28, a step a chunk) where the
    full-resolution pass takes four groups of 7.

    Unlike the per-tile nodes kernel, the fused kernel's accumulator is
    fully resident (routing is computed once per chunk, so the grid runs
    chunk-major and every feature tile must stay hot): its footprint
    scales with F, and wide matrices must shrink the chunk or fall back
    to the scatter path.

    ``chunk_override`` (the tuned ``gbdt_hist_chunk`` winner) replaces
    the ladder's starting chunk; the SAME shrink-to-fit loop still
    applies, so an override can never overcommit VMEM: it can only
    start the search somewhere else."""
    Bh = coarse_bins(total_bins, hist_shift) if hist_shift else total_bins
    cap, chunk = _tile_for(Bh)
    if chunk_override:
        chunk = int(chunk_override)
    ft = _feat_tile(num_features, cap)
    Fp = -(-num_features // ft) * ft
    VN = n_slots * SLOT_LANES
    while chunk >= 1024:
        need = (ft * Bh * chunk                   # one-hot scratch (int8)
                + Fp * Bh * VN * 4                # resident accumulator (i32)
                + 2 * chunk * VN                  # vn scratch + vals (int8)
                + refine_k * total_bins * chunk   # refined one-hot (int8)
                + refine_k * total_bins * VN * 4)  # its accumulator (i32)
        if need <= _VMEM_BUDGET:
            return ft, chunk
        chunk //= 2
    return None


def hist_chunk_ok(num_features: int, total_bins: int, n_slots: int,
                  chunk: int) -> bool:
    """Whether ``chunk`` is a legal tuned rows-per-chunk override for
    BOTH histogram entry points at this geometry: a multiple dividing
    :data:`PAD_MULTIPLE` at or above the fused kernel's 1024 floor,
    admitted by :func:`fused_geometry` WITHOUT shrinking (a winner the
    fit loop would halve is not the config that was measured), and
    fitting the nodes kernel's one-hot scratch.  The ``gbdt_hist_chunk``
    consult site validates winners through this single gate."""
    chunk = int(chunk)
    if chunk < 1024 or PAD_MULTIPLE % chunk:
        return False
    geo = fused_geometry(num_features, total_bins, n_slots,
                         chunk_override=chunk)
    if geo is None or geo[1] != chunk:
        return False
    cap, _ = _tile_for(total_bins)
    ft = _feat_tile(num_features, cap)
    return ft * total_bins * chunk <= _VMEM_BUDGET


def feature_tiles(bins_t: jnp.ndarray, ft: int) -> jnp.ndarray:
    """(F, N) → the kernels' (G, ft, N) tile layout, zero-padding the
    feature axis to ``G * ft``.

    With ONE group (ft == F) it is a view.  Otherwise NOT free on TPU:
    (G, ft, N) with ft < 8 pads each G-slice to 8 sublanes, so XLA
    materializes a copy of the matrix (1.5 GB at 12M x 28, ft = 7).
    Callers that run many kernel passes per jit (the growers) must do
    this ONCE, OUTSIDE their wave loop: inside a ``lax.cond`` branch XLA
    cannot hoist it, and it re-materializes every wave."""
    F, N = bins_t.shape
    G = -(-F // ft)
    if G * ft != F:
        bins_t = jnp.pad(bins_t, ((0, G * ft - F), (0, 0)))
    return bins_t.reshape(G, ft, N)


def prepare_feature_tiles(bins_t: jnp.ndarray, total_bins: int,
                          num_features: int = None) -> jnp.ndarray:
    """Pre-reshape the (F, N) binned matrix to the NODES kernel's
    (G, ft, N) tile layout — pass the result as ``bins_t`` to
    :func:`build_hist_nodes_pallas` (it accepts either layout, keyed on
    ndim).  The fused pass picks its own tile: :func:`fused_geometry`
    and :func:`feature_tiles`."""
    cap, _ = _tile_for(total_bins)
    ft = _feat_tile(num_features if num_features is not None
                    else bins_t.shape[0], cap)
    return feature_tiles(bins_t, ft)


# (the former single-histogram "plain" kernel is gone: every pallas
# histogram — including the leaf-wise grower's per-node builds — routes
# through the node-batched kernel below with per-TREE quantization, so one
# kernel serves all growers and the quantization scale cannot drift
# between them)


#: rows pad to this multiple so every kernel geometry's grid divides
#: evenly (the largest chunk any _tile_for geometry uses is 2048; 8192
#: keeps headroom and costs ≤0.8% padding at 1M rows)
PAD_MULTIPLE = 8192


def hist_pad_multiple() -> int:
    return PAD_MULTIPLE


# --------------------------------------------------------------------------
# node-batched histogram build (depth-level growth)
# --------------------------------------------------------------------------
#
# The leaf-wise loop launches one full-data histogram pass per split — 31
# sequential passes per tree, each paying the full VPU one-hot construction
# cost for an MXU matmul whose N dimension is only 8 lanes (one node's
# value channels) out of the 128-wide MXU tile.  Batching S node slots into
# the lane dimension builds S histograms for the one-hot cost of one:
#
#     hist[f·B+b, j·8+v] += OH(f·B+b, c) · (slot(c)==j) · vals(c, v)
#
# The (C, S·8) per-node value matrix is built in-kernel from the row→slot
# assignment (one wide select of the S-fold lane-tiled value block:
# _slot_values).  A depth level of up to S=16 nodes then costs ONE pass.


def _onehot_words(b: jnp.ndarray, rows: int) -> jnp.ndarray:
    """One-hot of ``rows`` bins for a chunk's bin ids ``b`` (1, C) int32,
    FOUR ROWS TO A 32-BIT WORD: (rows // 4, C) int32 whose byte ``j`` of
    word-row ``i`` is ``b == 4 i + j``.  Stored to an int32 scratch and
    read back through ``ref.bitcast(int8)`` it is the (rows, C) int8
    one-hot the MXU takes, for ONE compare and one select a vreg of 4,096
    one-hot elements.  ``(iota == b).astype(int8)`` lowers on this Mosaic
    to four compares, four selects, two rounds of packs and unpacks and a
    ``vnez`` for the same vreg: some 58,000 of the 80,000 vector
    operations of a 2,048-row chunk of the two-level pass at 28 x 256
    (PERF.md §5).  An id outside ``[0, rows)`` matches no row, as
    before."""
    iota = lax.broadcasted_iota(jnp.int32, (rows // 4, b.shape[1]), 0)
    word = jnp.left_shift(jnp.int32(1), (b & 3) << 3)
    return jnp.where(iota == (b >> 2), word, 0)


def _store_onehot(oh_ref, k: int, b: jnp.ndarray, rows: int) -> None:
    """Feature ``k``'s one-hot into the word scratch (``rows // 4``
    word-rows a feature)."""
    oh_ref[k * rows // 4:(k + 1) * rows // 4, :] = _onehot_words(
        b[None, :], rows)


def _slot_values(vals_ref, slot: jnp.ndarray, S: int) -> jnp.ndarray:
    """The slot-masked value matrix: row ``c``'s 8 channels in the 8
    positions of ``slot[c]``, zero elsewhere (``slot`` -1: nowhere), in ONE
    wide compare against each position's slot index.

    ``vals_ref`` is either :func:`prep_hist_vals`'s (C, 8) limb block,
    lane-tiled S-fold here → (C, S·8); or the block of
    :func:`prep_hist_vals_rows`'s matrix (32, C), channels on the ROWS of
    one whole int8 tile, repeated here by whole vregs → (S·8, C), which
    the product contracts over its last dimension.  Tiling along the
    lanes is 16 lane rotations and as many unpacks and packs a vreg
    (1.3-2.2 us of the fused pass's 2,048-row chunk at 16 slots, PERF.md
    §5), and the row form also needs no relayout of ``slot``: growers
    that make several passes a tree use it.  Select, not multiply:
    ``arith.muli`` on i8 vectors fails to legalize in Mosaic."""
    VN, C = S * SLOT_LANES, slot.shape[0]
    if vals_ref.shape[0] != C:                       # channels on the rows
        pos_j = lax.broadcasted_iota(jnp.int32, (VN, C), 0) // SLOT_LANES
        reps = -(-VN // vals_ref.shape[0])
        tiled = jnp.concatenate([vals_ref[...]] * reps, axis=0)[:VN, :]
        sid = slot[None, :]
    else:
        pos_j = lax.broadcasted_iota(jnp.int32, (C, VN), 1) // SLOT_LANES
        tiled = jnp.concatenate([vals_ref[...]] * S, axis=1)
        sid = slot[:, None]
    return jnp.where(sid == pos_j, tiled, jnp.zeros_like(tiled))


def _vals_layout(vals: jnp.ndarray, N: int, chunk: int):
    """Which of its two layouts the value matrix has → (block shape, block
    index of chunk ``c``, dimension numbers of one-hot x values, channels
    on the rows?): (N, 8) limbs, or channel rows (8·k, N)."""
    if vals.shape == (N, VALS):
        return ((chunk, VALS), lambda c: (c, 0), (((1,), (0,)), ((), ())),
                False)
    assert vals.shape[1] == N and vals.shape[0] % VALS == 0, (
        f"vals {vals.shape}: ({N}, {VALS}) limbs (prep_hist_vals) or "
        f"(8·k, {N}) channel rows (prep_hist_vals_rows)")
    return ((vals.shape[0], chunk), lambda c: (0, c),
            (((1,), (1,)), ((), ())), True)


def _make_hist_nodes_kernel(ft: int, shift: int, dims):
    def kernel(bins_ref, slot_ref, vals_ref, out_ref, oh_ref):
        """Grid (G, N//chunk) — c fastest.  bins block (1, ft, C) int32;
        slot block (1, C) int32 (row's node slot, -1 = no slot); vals block
        (C, 8) int8 limbs or channel rows (32, C) (:func:`_slot_values`); out
        block (1, ft·B, S·8) int32 revisited
        across the chunk dim — per-TILE residency keeps VMEM use
        F-independent (a fully resident accumulator scales with F and
        stops compiling near F≈60 at B=256)."""
        c = pl.program_id(1)

        @pl.when(c == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        B = oh_ref.shape[0] * 4 // ft
        S = out_ref.shape[2] // SLOT_LANES
        for k in range(ft):
            b = bins_ref[0, k, :]
            if shift:
                # two-level mode: coarse (bin >> shift) histograms
                b = b >> shift
            _store_onehot(oh_ref, k, b, B)
        vn = _slot_values(vals_ref, slot_ref[0, :], S)
        contrib = lax.dot_general(oh_ref.bitcast(jnp.int8)[...], vn, dims,
                                  preferred_element_type=jnp.int32)
        out_ref[...] += contrib[None]
    return kernel


def _limb_channels(grad, hess, mask):
    """The 8 value channels of a row as (N,) int32 vectors [g0 g1 g2 h0 h1
    h2 count pad] and the (2,) f32 scales: g/h quantize to 3 balanced
    base-128 digits each on a per-call max-|value| scale (range ±2^20),
    plus an exact 0/1 count."""
    g = grad * mask
    h = hess * mask
    s_g = jnp.maximum(jnp.max(jnp.abs(g)), 1e-30) / _Q_MAX
    s_h = jnp.maximum(jnp.max(jnp.abs(h)), 1e-30) / _Q_MAX
    count = (mask > 0).astype(jnp.int32)
    return ([*_quant(g, s_g), *_quant(h, s_h), count,
             jnp.zeros_like(count)], jnp.stack([s_g, s_h]))


def prep_hist_vals(grad: jnp.ndarray, hess: jnp.ndarray,
                   mask: jnp.ndarray):
    """Per-row value channels → ((N, 8) int8 limb matrix, (2,) f32 scales).
    Hoisted out of the per-level loop: depends only on the iteration's
    grad/hess/mask."""
    chans, scales = _limb_channels(grad, hess, mask)
    return jnp.stack(chans, axis=-1).astype(jnp.int8), scales


#: rows of prep_hist_vals_rows: the 8 channels four times, one int8 tile
VALS_ROWS = 32


def prep_hist_vals_rows(grad: jnp.ndarray, hess: jnp.ndarray,
                        mask: jnp.ndarray):
    """:func:`prep_hist_vals` with the channels on the ROWS, four times →
    ((32, N) int8, scales): one whole int8 tile of rows, which the
    node-batched kernels repeat by whole vregs to their S·8 positions and
    mask by slot (:func:`_slot_values`).  For growers that make several
    passes a tree.  Rows of the data stay on the lanes, as grad and hess
    have them, so XLA writes it in one elementwise pass, where the (N, 8)
    matrix is a transpose that pads 8 lanes to 128 (1.5 GB at 12M rows).

    Row r carries channel r % 8 by a select chain: a stack of (1, N)
    int8 rows is a concatenate of padded operands, three times slower on
    the chip (PERF.md §6, PR 32)."""
    chans, scales = _limb_channels(grad, hess, mask)
    ch = lax.broadcasted_iota(jnp.int32, (VALS_ROWS, grad.shape[0]),
                              0) % VALS
    vals = jnp.zeros_like(ch)                          # channel 7: pad
    for i, c in enumerate(chans[:-1]):
        vals = jnp.where(ch == i, c[None, :], vals)
    return vals.astype(jnp.int8), scales


def _bins_tiles(bins_t: jnp.ndarray, total_bins: int) -> tuple:
    """Normalize the nodes kernel's bins input: (F, N) reshapes here (ONE
    materialized copy — hoist with :func:`prepare_feature_tiles` when
    calling from a loop); (G, ft, N) passes through.  F is always G·ft:
    _feat_tile minimizes padding first and ft=1 pads nothing, so the
    chosen tile always divides the feature count.
    → (bins_r, F, G, ft, N)."""
    if bins_t.ndim == 3:
        G, ft, N = bins_t.shape
        return bins_t, G * ft, G, ft, N
    F, N = bins_t.shape
    ft = _feat_tile(F, _tile_for(total_bins)[0])
    bins_r = feature_tiles(bins_t, ft)
    assert bins_r.shape[0] * ft == F, (bins_r.shape, ft, F)
    return bins_r, F, bins_r.shape[0], ft, N


@functools.partial(jax.jit,
                   static_argnames=("n_slots", "total_bins", "hist_shift",
                                    "interpret", "hist_chunk"))
def build_hist_nodes_pallas(bins_t: jnp.ndarray,   # (F, N) | (G, ft, N) int32
                            slot: jnp.ndarray,     # (N,) int32 in [-1, n_slots)
                            vals: jnp.ndarray,     # (N, 8) | (32, N) int8
                            scales: jnp.ndarray,   # (2,) f32 from prep_hist_vals
                            n_slots: int,
                            total_bins: int,
                            hist_shift: int = 0,
                            interpret: bool = False,
                            hist_chunk: int = 0) -> jnp.ndarray:
    """→ (n_slots, F, Bh, 3) float32 [grad, hess, count] histograms
    (Bh = :func:`coarse_bins` when ``hist_shift`` > 0 — the leaf-wise
    grower's two-level coarse build).

    ``hist_chunk`` overrides the ladder's rows-per-chunk (the tuned
    ``gbdt_hist_chunk`` winner, threaded from
    ``GrowthParams.hist_chunk``).  A jit STATIC on purpose: a tuned
    chunk is a different compiled program and must key the dispatch
    cache — a module-global override would silently serve the first
    compile to every later candidate."""
    B = total_bins
    Bh = coarse_bins(B, hist_shift) if hist_shift else B
    bins_r, F, G, ft, N = _bins_tiles(bins_t, B)
    _, chunk = _tile_for(B)
    if hist_chunk:
        chunk = int(hist_chunk)
        assert ft * Bh * chunk <= _VMEM_BUDGET, (
            f"hist_chunk={chunk}: one-hot scratch ({ft}x{Bh}x{chunk}) "
            "exceeds the VMEM budget — validate overrides through "
            "hist_chunk_ok()")
    assert N % chunk == 0, f"N={N} must be a multiple of {chunk}"
    VN = n_slots * SLOT_LANES
    vblock, vix, dims, _ = _vals_layout(vals, N, chunk)

    out = pl.pallas_call(
        _make_hist_nodes_kernel(ft, hist_shift, dims),
        grid=(G, N // chunk),
        in_specs=[
            pl.BlockSpec((1, ft, chunk), lambda f, c: (f, 0, c)),
            pl.BlockSpec((1, chunk), lambda f, c: (0, c)),
            pl.BlockSpec(vblock, lambda f, c: vix(c)),
        ],
        out_specs=pl.BlockSpec((1, ft * Bh, VN), lambda f, c: (f, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, ft * Bh, VN), jnp.int32),
        scratch_shapes=[pltpu.VMEM((ft * Bh // 4, chunk), jnp.int32)],
        interpret=interpret,
    )(bins_r, slot[None, :], vals)

    # (G, ft·Bh, S·8) → (F, Bh, S, 8) → (S, F, Bh, 3)
    out = out.reshape(G * ft, Bh, n_slots, SLOT_LANES)[:F]
    out = jnp.moveaxis(out, 2, 0)                      # (S, F, Bh, 8)
    return _reconstruct(out, scales)


# --------------------------------------------------------------------------
# fused route + histogram kernel (depth-level growth, one pass per wave)
# --------------------------------------------------------------------------
#
# The wave loop needs two things from the binned matrix: (1) apply the
# selected splits to every row (new node id + histogram slot) and (2) build
# the left-child histograms.  As separate kernels each scans the matrix
# once; fused, the grid runs chunk-major (f innermost) so each chunk's
# routing is computed ONCE at f==0 and the node-masked value matrix stays
# in VMEM for the F/ft histogram steps that follow (one step where one
# feature group holds the tile: fused_geometry).  The histogram
# accumulator is a single constant-index output block (F/ft, ft·B, S·8)
# resident in VMEM for the whole launch.
#
# The split columns are read where they lie, named by a scalar-prefetched
# index.  Routing reads row ``cols[j]`` of a second view of the binned
# matrix whose blocks follow the chunk alone, so they are fetched once a
# chunk: the chunk's whole (G, ft, C) block where that is no more sublane
# rows than a tile of 8 a slot, else one (1, 8, C) slab a slot, the tile
# its column lies in, so the fetch is bounded by the slots and not by the
# matrix's width (:func:`_routing_blocks`).  Their VMEM is asked of the
# compiler on top of the scoped limit: fused_geometry's budget leaves them
# out, as it left out the (16, C) block of rows they replace.  Callers
# that have the rows pass them as an (S, N) matrix, read through the same
# body with ``cols = arange(S)``.
#
# Growers once gathered the rows into such a matrix first, because an
# in-kernel dynamic sublane read had cost more than the matmul it fed (an
# earlier round's reading at 1M x 28, when a grid step held one feature
# tile).  What differs now: routing runs once a chunk and the pass is bound
# by its int8 products, so a row read hides beside them.  On a TPU v5e at
# 12,001,280 x 28, two-level with 8 refined features, a pass took 25.33 ms
# reading its own block, 25.32 ms reading the histogram tile and 25.33 ms
# given the rows, while gathering the 16 rows took 6.73 ms, five times a
# tree (XLA walks the whole matrix in 32,768-row chunks to keep 16 rows).
# One (1, 1, C) block a slot on an (F, 1, N) view would fetch 16 rows
# only, but that view is not free: XLA relays it out to (1, 128) tiles, a
# copy of the whole matrix.


def _route_rows(row, nid, leaf_ref, t1_ref, rlo_ref, rhi_ref, dflt_ref,
                lid_ref, rid_ref, S: int):
    """Apply S splits to a chunk's rows → (new node ids, the slot each row
    went LEFT in, -1 for none).  ``row(j)`` is slot ``j``'s split column
    for these rows.

    The condition is the UNIVERSAL form ``in (rlo, rhi] ? x <= t1 :
    dflt``: plain splits pass rlo=-1/rhi=B so it degrades to ``x <= t1``;
    EFB splits pass the original feature's bundled range so an
    ORIGINAL-feature split routes straight off the bundled column
    (binning.py FeatureBundler.route_tables)."""
    new = nid
    bslot = jnp.full_like(nid, -1)
    for j in range(S):
        inleaf = nid == leaf_ref[j]
        xb = row(j)
        in_range = (xb > rlo_ref[j]) & (xb <= rhi_ref[j])
        # select over int32: Mosaic rejects broadcasting the i1 SCALAR
        # default into a vector select
        gl = jnp.where(in_range, (xb <= t1_ref[j]).astype(jnp.int32),
                       dflt_ref[j]) != 0
        new = jnp.where(inleaf, jnp.where(gl, lid_ref[j], rid_ref[j]), new)
        bslot = jnp.where(inleaf & gl, j, bslot)
    return new, bslot


def _routing_blocks(route, n_slots: int, chunk: int, grid_rank: int):
    """The blocks routing reads its rows from → (specs, operands, read).
    ``route`` (A, R, N) int32 holds every column a slot may name, its
    rows counted across A; ``cols``, the first scalar-prefetch operand,
    names them; grid index 0 counts the chunks.  Where the chunk's whole
    (A, R, C) block is no more sublane rows than a tile of 8 a slot, that
    block; else one (1, 8, C) slab a slot, the tile its column lies in,
    so the fetch is bounded by the slots and not by the matrix's width.
    ``read(route_refs, cols_ref, lanes)`` → ``row(j)``, slot ``j``'s
    column over ``lanes``."""
    A, R, _ = route.shape
    if A * -(-R // 8) * 8 <= n_slots * 8:
        spec = pl.BlockSpec((A, R, chunk), lambda *ix: (0, 0, ix[0]))
        if A == 1:
            # a static leading index: ``cols // R`` there cost the fused
            # pass 3% and the routing pass 10% (TPU v5e, 12M x 28)
            def read(refs, cols, lanes):
                return lambda j: refs[0][0, cols[j], lanes]
        else:
            def read(refs, cols, lanes):
                return lambda j: refs[0][cols[j] // R, cols[j] % R, lanes]
        return [spec], [route], read
    Rt = min(R, 8)
    specs = [pl.BlockSpec((1, Rt, chunk), lambda *ix, j=j: (
        ix[grid_rank][j] // R, ix[grid_rank][j] % R // Rt, ix[0]))
        for j in range(n_slots)]

    def read(refs, cols, lanes):
        return lambda j: refs[j][0, cols[j] % R % Rt, lanes]
    return specs, [route] * n_slots, read


def _routing_rows(route, n_slots: int) -> int:
    """Sublane rows of :func:`_routing_blocks`' blocks, all together."""
    A, R, _ = route.shape
    return min(A * -(-R // 8) * 8, n_slots * 8)


def _make_fused_kernel(ft: int, shift: int, refine: bool, n_route: int,
                       read, dims):
    """``refine=True`` (two-level mode) adds a second histogram output:
    full-resolution histograms of K pre-gathered refined-feature rows
    (``selk``), built at f==0 from the SAME slot-masked value matrix the
    coarse tiles use — one bins read, one routing, one vn build for both
    levels."""
    def kernel(cols_ref, leaf_ref, t1_ref, rlo_ref, rhi_ref, dflt_ref,
               lid_ref, rid_ref, *refs):
        """Grid (N//chunk, G) — f fastest.  Scalars: ``cols`` (S,), the
        row of the routing block each slot routes by, and the splits.
        Blocks: ``n_route`` routing blocks (:func:`_routing_blocks`, whose
        ``read`` names their rows),
        bins (1, ft, C) (histogram tile), nid (1, C), vals (C, 8) int8
        limbs or channel rows (32, C) (:func:`_slot_values`); outputs:
        newid (1, C) and the resident histogram accumulator
        (G, ft·B, S·8) int32."""
        refs = list(refs)
        selk_ref = refs.pop(0) if refine else None
        route_refs = [refs.pop(0) for _ in range(n_route)]
        bins_ref, nid_ref, vals_ref, newid_ref, out_ref = refs[:5]
        outf_ref = refs.pop(5) if refine else None
        oh_ref, vn_ref = refs[5:7]
        ohf_ref = refs[7] if refine else None
        c = pl.program_id(0)
        f = pl.program_id(1)

        @pl.when((c == 0) & (f == 0))
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)
            if refine:
                outf_ref[...] = jnp.zeros_like(outf_ref)

        B = oh_ref.shape[0] * 4 // ft
        S = out_ref.shape[2] // SLOT_LANES

        @pl.when(f == 0)
        def _route():
            new, bslot = _route_rows(
                read(route_refs, cols_ref, slice(None)),
                nid_ref[0, :], leaf_ref, t1_ref, rlo_ref, rhi_ref, dflt_ref,
                lid_ref, rid_ref, S)
            newid_ref[0, :] = new
            vn_ref[...] = _slot_values(vals_ref, bslot, S)
            if refine:
                # fine-K histograms off the SAME slot-masked values: the
                # separate refine pass re-read bins, re-derived slots and
                # re-built vn — here it costs one extra one-hot + matmul
                K = selk_ref.shape[0]
                Bf = ohf_ref.shape[0] * 4 // K
                for k in range(K):
                    _store_onehot(ohf_ref, k, selk_ref[k, :], Bf)
                fcontrib = lax.dot_general(
                    ohf_ref.bitcast(jnp.int8)[...], vn_ref[...], dims,
                    preferred_element_type=jnp.int32)
                outf_ref[...] += fcontrib[None]

        for k in range(ft):
            b = bins_ref[0, k, :]
            if shift:
                # two-level mode: histogram at COARSE (bin >> shift)
                # resolution while routing stays at fine resolution: the
                # one-hot build and the matmul both shrink by 2^shift
                b = b >> shift
            _store_onehot(oh_ref, k, b, B)
        contrib = lax.dot_general(oh_ref.bitcast(jnp.int8)[...], vn_ref[...],
                                  dims, preferred_element_type=jnp.int32)
        out_ref[f, :, :] += contrib
    return kernel


def fused_tiles(bins_t, n_slots: int, total_bins: int, hist_shift: int = 0,
                refine_k: int = 0, hist_chunk: int = 0):
    """Normalize the fused pass's bins input → (bins_r (G, ft, N), chunk)
    at the geometry :func:`fused_geometry` picks for the tiles this
    pass builds.  (F, N) is laid out here (a view with one feature group,
    else ONE copy: growers hoist it with :func:`feature_tiles`);
    (G, ft, N) must already be that geometry's layout."""
    F = bins_t.shape[0] * bins_t.shape[1] if bins_t.ndim == 3 \
        else bins_t.shape[0]
    geo = fused_geometry(F, total_bins, n_slots, chunk_override=hist_chunk,
                         hist_shift=hist_shift, refine_k=refine_k)
    assert geo is not None, (
        f"fused kernel does not fit VMEM at F={F}, B={total_bins}, "
        f"S={n_slots}, shift={hist_shift}, K={refine_k}; the caller must "
        "gate on fused_geometry(...)")
    ft, chunk = geo
    if bins_t.ndim == 2:
        bins_t = feature_tiles(bins_t, ft)
    assert bins_t.shape[1] == ft and bins_t.shape[0] * ft == F, (
        bins_t.shape, ft, F)
    assert bins_t.shape[2] % chunk == 0, (
        f"N={bins_t.shape[2]} must be a multiple of {chunk}")
    return bins_t, chunk


def _split_columns(bins_r, sel, n_slots: int):
    """What the fused pass routes by → (cols (S,) int32, the (A, R, N)
    array of the routing block).  ``sel`` (S,) names columns of the
    (G, ft, N) matrix (clamped into it: the kernel reads VMEM unchecked);
    ``sel`` (S, N) is rows, named by position."""
    if sel.ndim == 2:
        return jnp.arange(n_slots, dtype=jnp.int32), sel[None]
    G, ft, _ = bins_r.shape
    return jnp.clip(sel.astype(jnp.int32), 0, G * ft - 1), bins_r


def _route_and_hist_int(bins_t, node_id, leaf, sel, t1, rlo, rhi, dflt,
                        l_id, r_id, vals, n_slots, total_bins, hist_shift,
                        sel_k, interpret, hist_chunk):
    """The fused pass's raw outputs: (new_node_id (1, N) int32, coarse or
    plain accumulator (G, ft·Bh, S·8) int32[, refined accumulator
    (1, K·B, S·8) int32]).  Integer sums of int8 products: the order of
    rows, chunks and feature tiles cannot change a bit of them."""
    B = total_bins
    Bh = coarse_bins(B, hist_shift) if hist_shift else B
    refine = sel_k is not None
    K = sel_k.shape[0] if refine else 0
    bins_r, chunk = fused_tiles(bins_t, n_slots, B, hist_shift, K,
                                hist_chunk)
    G, ft, N = bins_r.shape
    VN = n_slots * SLOT_LANES
    vblock, vix, dims, rows = _vals_layout(vals, N, chunk)
    cols, route = _split_columns(bins_r, sel, n_slots)
    route_specs, route_ops, read = _routing_blocks(route, n_slots, chunk, 2)
    in_specs = route_specs + [
        pl.BlockSpec((1, ft, chunk), lambda c, f, *_: (f, 0, c)),
        pl.BlockSpec((1, chunk), lambda c, f, *_: (0, c)),
        pl.BlockSpec(vblock, lambda c, f, *_: vix(c)),
    ]
    out_specs = [
        pl.BlockSpec((1, chunk), lambda c, f, *_: (0, c)),
        pl.BlockSpec((G, ft * Bh, VN), lambda c, f, *_: (0, 0, 0)),
    ]
    out_shape = [jax.ShapeDtypeStruct((1, N), jnp.int32),
                 jax.ShapeDtypeStruct((G, ft * Bh, VN), jnp.int32)]
    scratch = [pltpu.VMEM((ft * Bh // 4, chunk), jnp.int32),  # one-hot words
               pltpu.VMEM((VN, chunk) if rows else (chunk, VN),
                          jnp.int8)]                        # _slot_values
    operands = route_ops + [bins_r, node_id[None, :], vals]
    if refine:
        in_specs.insert(0, pl.BlockSpec((K, chunk), lambda c, f, *_: (0, c)))
        out_specs.append(pl.BlockSpec((1, K * B, VN),
                                      lambda c, f, *_: (0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, K * B, VN), jnp.int32))
        scratch.append(pltpu.VMEM((K * B // 4, chunk), jnp.int32))
        operands.insert(0, sel_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(N // chunk, G),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        _make_fused_kernel(ft, hist_shift, refine, len(route_ops), read,
                           dims),
        grid_spec=grid_spec,
        out_shape=out_shape,
        # fused_geometry's budget leaves the routing blocks out
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_SCOPED_VMEM + _rows_block_bytes(
                _routing_rows(route, n_slots), chunk)),
        interpret=interpret,
    )(cols, leaf, t1, rlo, rhi, dflt, l_id, r_id, *operands)


@functools.partial(jax.jit, static_argnames=("n_slots", "total_bins",
                                             "hist_shift", "interpret",
                                             "hist_chunk"))
def route_and_hist_pallas(bins_t: jnp.ndarray,   # (F, N) | (G, ft, N) int32
                          node_id: jnp.ndarray,  # (N,) int32
                          leaf: jnp.ndarray,     # (S,) int32 leaf being split
                          sel: jnp.ndarray,      # (S,) cols | (S, N) rows
                          t1: jnp.ndarray,       # (S,) int32 in-range thr
                          rlo: jnp.ndarray,      # (S,) int32 range (rlo, rhi]
                          rhi: jnp.ndarray,      # (S,) int32
                          dflt: jnp.ndarray,     # (S,) int32 out-of-range dir
                          l_id: jnp.ndarray,     # (S,) int32 left child id
                          r_id: jnp.ndarray,     # (S,) int32 right child id
                          vals: jnp.ndarray,     # (N, 8) | (32, N) int8
                          scales: jnp.ndarray,   # (2,) f32 from prep_hist_vals
                          n_slots: int,
                          total_bins: int,
                          hist_shift: int = 0,
                          sel_k: jnp.ndarray = None,   # (K, N) int32 refined
                          interpret: bool = False,
                          hist_chunk: int = 0):
    """One pass: → (new_node_id (N,), hists (n_slots, F, Bh, 3)[,
    fine_hists (n_slots, K, B, 3) when ``sel_k`` is given]).

    Routing per slot ``j``: rows whose value ``x`` in the split column
    is ``x in (rlo, rhi] ? x <= t1 : dflt`` go left — plain splits pass
    rlo=-1, rhi=B, t1=split_bin; EFB passes the bundled range of the
    ORIGINAL feature being split.  ``sel`` names the split columns
    either as (S,) int32 indices into the feature axis of ``bins_t``,
    read where they lie (what the growers pass: no copy of the columns
    exists), or as the (S, N) matrix of their rows, for a caller that
    has such rows.  The shape decides; both go through one routing
    body.

    ``hist_shift`` > 0 (two-level mode) histograms at the COARSE
    ``bin >> hist_shift`` resolution (Bh = :func:`coarse_bins`) while
    routing stays at fine resolution.  ``sel_k`` (the refined features'
    pre-gathered bin rows) additionally builds their FULL-resolution
    histograms in the same pass, off the same routing and slot-masked
    value matrix — one bins read and one vn build for both levels.

    ``bins_t`` as (G, ft, N) must be :func:`feature_tiles` at the ``ft``
    :func:`fused_geometry` returns for THESE arguments (the tile follows
    what the pass builds: ``hist_shift``, ``sel_k``).

    ``hist_chunk`` is the tuned rows-per-chunk override (jit-static for
    the same dispatch-cache reason as in
    :func:`build_hist_nodes_pallas`); the fused fit loop still applies,
    so an oversized override shrinks to fit rather than overcommitting
    VMEM."""
    B = total_bins
    Bh = coarse_bins(B, hist_shift) if hist_shift else B
    res = _route_and_hist_int(bins_t, node_id, leaf, sel, t1, rlo, rhi, dflt,
                              l_id, r_id, vals, n_slots, B, hist_shift,
                              sel_k, interpret, hist_chunk)
    new_id, out = res[0], res[1]
    # (G, ft·Bh, S·8) → (F, Bh, S, 8): F == G·ft, _feat_tile pads nothing
    out = out.reshape(-1, Bh, n_slots, SLOT_LANES)
    out = jnp.moveaxis(out, 2, 0)                      # (S, F, Bh, 8)
    hists = _reconstruct(out, scales)
    if sel_k is None:
        return new_id[0], hists
    outf = res[2].reshape(sel_k.shape[0], B, n_slots, SLOT_LANES)
    fine = _reconstruct(jnp.moveaxis(outf, 2, 0), scales)
    return new_id[0], hists, fine


# --------------------------------------------------------------------------
# routing alone (the wave that fills the leaf budget)
# --------------------------------------------------------------------------


#: rows of one piece of the routing pass's loop
_ROUTE_PIECE = 1024


def _make_route_kernel(piece: int, n_route: int, read):
    def kernel(cols_ref, leaf_ref, t1_ref, rlo_ref, rhi_ref, dflt_ref,
               lid_ref, rid_ref, *refs):
        """Grid (N//chunk,).  ``n_route`` routing blocks
        (:func:`_routing_blocks`, whose ``read`` names their rows), nid
        and newid (1, C); the chunk routes ``piece`` rows at a time."""
        *route_refs, nid_ref, newid_ref = refs
        S = leaf_ref.shape[0]

        def body(i, carry):
            lanes = pl.ds(pl.multiple_of(i * piece, piece), piece)
            new, _ = _route_rows(
                read(route_refs, cols_ref, lanes), nid_ref[0, lanes],
                leaf_ref, t1_ref, rlo_ref, rhi_ref, dflt_ref, lid_ref,
                rid_ref, S)
            newid_ref[0, lanes] = new
            return carry
        lax.fori_loop(0, nid_ref.shape[1] // piece, body, 0)
    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def route_rows_pallas(bins_t: jnp.ndarray,   # (F, N) | (G, ft, N) int32
                      node_id: jnp.ndarray,  # (N,) int32
                      leaf: jnp.ndarray,     # (S,) int32 leaf being split
                      cols: jnp.ndarray,     # (S,) int32 split columns
                      t1: jnp.ndarray,       # (S,) int32 in-range thr
                      rlo: jnp.ndarray,      # (S,) int32 range (rlo, rhi]
                      rhi: jnp.ndarray,      # (S,) int32
                      dflt: jnp.ndarray,     # (S,) int32 out-of-range dir
                      l_id: jnp.ndarray,     # (S,) int32 left child id
                      r_id: jnp.ndarray,     # (S,) int32 right child id
                      interpret: bool = False) -> jnp.ndarray:
    """→ new_node_id (N,): :func:`route_and_hist_pallas`'s routing with no
    histogram, one pass over the binned matrix and ``node_id``.  For the
    wave whose children never split again.  ``cols`` index the feature
    axis of ``bins_t`` (either layout); a column is read where it lies.

    On a TPU v5e at 12,001,280 x 28 a pass took 2.63-2.65 ms at chunks of
    2,048 to 8,192 rows and pieces of 512 to 2,048, where routing in XLA
    took 7.72 ms with the gather of its 16 rows and 10.9 ms from 16
    dynamic row slices.  It reads the whole matrix, 1.76 ms at the HBM
    peak with ``node_id`` in and out; the rest is routing on vectors of
    one sublane.  It reads the blocks the fused pass reads, a tile of 8
    rows a slot at most."""
    src = bins_t[None] if bins_t.ndim == 2 else bins_t
    A, R, N = src.shape
    S = leaf.shape[0]
    chunk = math.gcd(N, PAD_MULTIPLE)
    piece = min(chunk, _ROUTE_PIECE)
    assert chunk % 128 == 0, f"N={N} must be a multiple of 128"
    # the routing blocks, node_id in and out
    assert _rows_block_bytes(_routing_rows(src, S) + 2 * 8,
                             chunk) <= _VMEM_BUDGET, f"S={S} slots"
    cols = jnp.clip(cols.astype(jnp.int32), 0, A * R - 1)
    route_specs, route_ops, read = _routing_blocks(src, S, chunk, 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(N // chunk,),
        in_specs=route_specs + [pl.BlockSpec((1, chunk),
                                             lambda c, *_: (0, c))],
        out_specs=pl.BlockSpec((1, chunk), lambda c, *_: (0, c)),
    )
    return pl.pallas_call(
        _make_route_kernel(piece, len(route_ops), read),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.int32),
        interpret=interpret,
    )(cols, leaf, t1, rlo, rhi, dflt, l_id, r_id, *route_ops,
      node_id[None, :])[0]
