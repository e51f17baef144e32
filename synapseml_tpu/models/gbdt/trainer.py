"""Leaf-wise histogram tree growth, fully inside ``jit``.

This replaces LightGBM's native C++ tree learner (reference: the black box
behind LGBM_BoosterUpdateOneIter, booster/LightGBMBooster.scala:359; per-iter
histogram build + cross-machine allreduce + split).  The TPU formulation:

- **Static shapes everywhere**: exactly ``num_leaves-1`` split iterations in
  a ``lax.fori_loop``; zero-gain iterations are no-ops guarded by
  ``lax.cond``.  Histograms live in a slot-reused buffer of ``num_leaves+1``
  slots (a split's left child reuses the parent's slot, the right child
  takes a fresh one) so memory stays O(num_leaves · F · B).
- **Histogram subtraction**: only the left child's histogram is built by
  scatter-add; the right child's is parent − left (LightGBM's classic
  optimization, here it also halves scatter traffic).
- **Data-parallel = one psum**: rows are sharded over the mesh ``data``
  axis; passing ``axis_name`` makes every histogram build and root-stat
  reduction a ``lax.psum`` — the entire replacement for the reference's
  driver-socket rendezvous + native allreduce ring
  (NetworkManager.scala:55-205).  The growth loop itself is replicated and
  deterministic on every rank.
- **Missing values**: NaN maps to bin 0 and always routes left (a fixed
  default-left policy).

Split gain follows LightGBM: with G/H the child gradient/hessian sums,
``score(G,H) = T(G)^2 / (H + λ2)`` where T is the L1 soft-threshold, and
``gain = score(GL,HL) + score(GR,HR) - score(G,H)``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ... import telemetry as _telemetry
from ...parallel.planner import planned_psum as _c_planned_psum


def _tl_gauge(grower: str, active: bool) -> None:
    """Record the FINAL per-program two-level decision (the growers apply
    structural exclusions train() cannot see — EFB, monotone, voting,
    VMEM fit), so the gauge answers "which split-search semantics is this
    program actually using".  Runs at trace/step-construction time."""
    try:
        _telemetry.get_registry().gauge(
            "gbdt_two_level_grower_active",
            "1 when the grower program traced with coarse-then-refine "
            "histograms, by growth policy", ("grower",)).set(
                1.0 if active else 0.0, grower=grower)
    except Exception:
        pass


class GrowthParams(NamedTuple):
    """Static growth hyperparameters (hashable → part of the jit key)."""
    num_leaves: int = 31
    max_depth: int = -1               # <=0: unlimited (bounded by num_leaves)
    min_data_in_leaf: float = 20.0
    min_sum_hessian_in_leaf: float = 1e-3
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    total_bins: int = 256             # B (incl. missing bin 0)
    voting_k: int = 0                 # >0: voting-parallel with this top-k
    #: per-feature {-1, 0, +1} (None: unconstrained) — LightGBM's
    #: ``monotone_constraints`` (params/LightGBMParams.scala:168-183);
    #: the "basic" method: violating splits are discarded, child outputs
    #: are clamped to bounds propagated down the tree
    monotone_constraints: Optional[Tuple[int, ...]] = None
    #: gain penalization for splits on constrained features near the root
    #: (LightGBM ``monotone_penalty``, BaseTrainParams.scala:128-130)
    monotone_penalty: float = 0.0
    #: "basic" (midpoint bound propagation) | "intermediate" (bounds from
    #: the opposite sibling SUBTREE's current extreme outputs, recomputed
    #: over the whole tree each wave — much less constraining, LightGBM's
    #: recommended upgrade) | "advanced" (the exact minimal pairwise
    #: constraint set over ordered-and-overlapping leaf boxes — see
    #: :func:`_advanced_bounds`; provably no tighter than intermediate)
    monotone_method: str = "basic"
    #: two-level histograms for wide-bin depthwise growth: "off" | "auto"
    #: (on for N >= TWO_LEVEL_MIN_ROWS; N is shard-local here — train()
    #: resolves "auto" from the GLOBAL row count before building steps)
    #: | "on".  Histograms build and store at COARSE
    #: (bin >> TWO_LEVEL_SHIFT) resolution; the top ``refine_k`` features
    #: — chosen ONCE per tree from the root's coarse per-feature gains —
    #: are refined at full resolution every wave (left children built,
    #: right children by fine subtraction) and each split picks the
    #: better of the refined fine candidates and the unrefined
    #: coarse-boundary candidates.  The 255-bin one-hot build — the
    #: measured VPU bottleneck of the level pass — shrinks 2^shift; split
    #: quality is preserved unless a feature outside the root-chosen
    #: top-K beats every refined feature only on a sub-coarse-boundary
    #: cut (each coarse boundary IS a fine split, so coarse candidates
    #: remain exact lower bounds)
    two_level: str = "off"
    #: features refined at full resolution when two-level is on
    refine_k: int = 0
    #: tuned rows-per-chunk for the Pallas histogram kernels (0 = the
    #: ``_tile_for`` ladder default).  Set from the ``gbdt_hist_chunk``
    #: tuning-table winner by ``BoostingConfig.growth_params()`` —
    #: part of this NamedTuple (and therefore the jit static key) so a
    #: tuned geometry compiles its own program instead of silently
    #: reusing the default's
    hist_chunk: int = 0


class Tree(NamedTuple):
    """Flat tree arrays; node 0 is the root. -1 children ⇒ leaf."""
    split_feature: jnp.ndarray        # (MAX_NODES,) int32
    split_bin: jnp.ndarray            # (MAX_NODES,) int32 (go left if bin<=)
    threshold: jnp.ndarray            # (MAX_NODES,) f32 raw-value threshold
    split_gain: jnp.ndarray           # (MAX_NODES,) f32 (0 for leaves)
    left_child: jnp.ndarray           # (MAX_NODES,) int32
    right_child: jnp.ndarray          # (MAX_NODES,) int32
    leaf_value: jnp.ndarray           # (MAX_NODES,) f32 (already shrunk)
    node_value: jnp.ndarray           # (MAX_NODES,) f32 output at every node
    num_nodes: jnp.ndarray            # () int32
    default_left: jnp.ndarray         # (MAX_NODES,) bool — missing routing
                                      # per node (training always emits
                                      # True; imported models may not)
    node_count: jnp.ndarray           # (MAX_NODES,) f32 — rows covering
                                      # each node (TreeSHAP cover weights)
    missing_zero: jnp.ndarray         # (MAX_NODES,) bool — LightGBM
                                      # missing_type=Zero: |x|<=1e-35 (and
                                      # NaN) routes by default_left at this
                                      # node; training emits all-False


def max_nodes(num_leaves: int) -> int:
    return 2 * num_leaves


def _soft_threshold(g, l1):
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)


def _leaf_score(g, h, l1, l2):
    t = _soft_threshold(g, l1)
    return t * t / (h + l2 + 1e-32)


def _leaf_output(g, h, l1, l2):
    return -_soft_threshold(g, l1) / (h + l2 + 1e-32)


def _build_hist(bins_t, flat_bins, grad, hess, mask, F, B, use_pallas,
                vals8=None, scales=None, hist_shift=0, hist_chunk=0):
    """Histogram for masked rows → (F*Bh, 3) f32 [grad, hess, count]
    (Bh = coarse width when ``hist_shift`` > 0 — the leaf-wise grower's
    two-level coarse build).

    ``mask`` is the row weight (bag/GOSS amplification); the count channel
    counts rows with mask>0 exactly once so GOSS amplification never
    inflates leaf counts.  On TPU the Pallas MXU kernel builds it
    (pallas_hist.py); elsewhere an XLA scatter-add over the precomputed
    flattened bin ids ``flat_bins`` (F, N).

    ``vals8``/``scales``: per-TREE int8 limb quantization from
    :func:`prep_hist_vals` (already weighted by the tree's row mask).
    Passing them keeps the quantization scale identical across every
    histogram of the tree — node-local scales would round differently
    from the depthwise grower's global scale and flip near-tie splits;
    ``mask`` then only selects node membership."""
    if use_pallas:
        from .pallas_hist import build_hist_nodes_pallas, coarse_bins
        assert vals8 is not None, "pallas path requires per-tree vals8/scales"
        slot = jnp.where(mask > 0, 0, -1).astype(jnp.int32)
        Bh = coarse_bins(B, hist_shift) if hist_shift else B
        return build_hist_nodes_pallas(
            bins_t, slot, vals8, scales, 1, B, hist_shift=hist_shift,
            interpret=(use_pallas == "interpret"),
            hist_chunk=hist_chunk)[0].reshape(F * Bh, 3)
    upd = _hist_updates(grad, hess, mask)                                 # (N,3)
    upd = jnp.broadcast_to(upd[None, :, :], (F,) + upd.shape)             # (F,N,3)
    hist = jnp.zeros((F * B, 3), jnp.float32)
    hist = hist.at[flat_bins].add(upd.astype(jnp.float32))
    if hist_shift:
        from .pallas_hist import coarse_bins
        Bh = coarse_bins(B, hist_shift)
        hist = _pool_coarse(hist.reshape(F, B, 3), Bh,
                            hist_shift).reshape(F * Bh, 3)
    return hist


def _mono_penalty_factor(node_depth, penalty: float):
    """LightGBM's ComputeMonotoneSplitGainPenalty: 1 forbids constrained
    splits at the root, higher values reach deeper."""
    eps = 1e-10
    d = node_depth.astype(jnp.float32)
    if penalty <= 1.0:
        fac = 1.0 - penalty / jnp.exp2(d) + eps
    else:
        fac = 1.0 - jnp.exp2(jnp.float32(penalty) - 1.0 - d) + eps
    return jnp.where(jnp.float32(penalty) >= d + 1.0, eps, fac)


def _obj2(g, h, w, l1, l2):
    """2× the objective reduction at leaf output ``w`` — equals
    :func:`_leaf_score` when ``w`` is the unclamped optimum, so constrained
    gains degrade exactly to the unconstrained formula when no bound
    binds."""
    return -(2.0 * g * w + (h + l2) * w * w + 2.0 * l1 * jnp.abs(w))


def _gain_matrix(hist, sum_g, sum_h, sum_c, num_bins, feature_mask,
                 node_depth, p: GrowthParams, node_lo=None, node_hi=None,
                 mono_c=None):
    """Split-gain matrix (F, B) with invalid candidates at -inf, plus the
    cumulative left sums as three (F, B) channel arrays (gl, hl, cl)
    the winner's child stats read from.

    Split at bin b sends bins<=b left, b ∈ [0, B-2].

    With ``mono_c`` ((F,) int32 in {-1,0,1}) and the node's output bounds
    ``node_lo``/``node_hi``, gains come from CLAMPED child outputs, splits
    whose clamped outputs violate the feature's direction are discarded,
    and constrained-feature gains are penalized by depth
    (``monotone_penalty``) — the LightGBM "basic" method.
    """
    F, B, _ = hist.shape
    # unpack channels BEFORE any arithmetic: (..., B, 3) puts 3 in the
    # lane dim, so every op on it touches 128/3 ≈ 43x its logical bytes in
    # (8, 128)-tiled physical layout — slicing pays that once and the
    # scans/gains below run on clean (..., F, B) arrays (measured
    # ~14 ms/tree of split search at B=256 before this reshuffle)
    gch, hch, cch = hist[..., 0], hist[..., 1], hist[..., 2]
    # prefix sums over the bin axis via log-depth associative scan:
    # jnp.cumsum lowers to an O(B^2)-work reduce-window on TPU, and a
    # triangular-matmul formulation reassociates sums differently per
    # batch shape, so the two growers' near-tie splits diverge — the
    # scan's fixed pairwise tree is both O(B log B) and
    # batch-shape-independent
    gl = lax.associative_scan(jnp.add, gch, axis=-1)     # (F, B)
    hl = lax.associative_scan(jnp.add, hch, axis=-1)
    cl = lax.associative_scan(jnp.add, cch, axis=-1)
    gr, hr, cr = sum_g - gl, sum_h - hl, sum_c - cl
    if mono_c is None:
        gain = (_leaf_score(gl, hl, p.lambda_l1, p.lambda_l2)
                + _leaf_score(gr, hr, p.lambda_l1, p.lambda_l2)
                - _leaf_score(sum_g, sum_h, p.lambda_l1, p.lambda_l2))
    else:
        wl = jnp.clip(_leaf_output(gl, hl, p.lambda_l1, p.lambda_l2),
                      node_lo, node_hi)
        wr = jnp.clip(_leaf_output(gr, hr, p.lambda_l1, p.lambda_l2),
                      node_lo, node_hi)
        wp = jnp.clip(_leaf_output(sum_g, sum_h, p.lambda_l1, p.lambda_l2),
                      node_lo, node_hi)
        gain = (_obj2(gl, hl, wl, p.lambda_l1, p.lambda_l2)
                + _obj2(gr, hr, wr, p.lambda_l1, p.lambda_l2)
                - _obj2(sum_g, sum_h, wp, p.lambda_l1, p.lambda_l2))
        cvec = mono_c[:, None]
        viol = (((cvec == 1) & (wl > wr)) | ((cvec == -1) & (wl < wr)))
        gain = jnp.where(viol, -jnp.inf, gain)
        if p.monotone_penalty > 0.0:
            fac = _mono_penalty_factor(node_depth, p.monotone_penalty)
            gain = jnp.where(cvec != 0, gain * fac, gain)
    bins_idx = jnp.arange(B)[None, :]
    valid = ((cl >= p.min_data_in_leaf) & (cr >= p.min_data_in_leaf)
             & (hl >= p.min_sum_hessian_in_leaf)
             & (hr >= p.min_sum_hessian_in_leaf)
             & (bins_idx < (num_bins[:, None] + 1) - 1)   # inside feature's bin range
             & (bins_idx < B - 1)
             & feature_mask[:, None])
    if p.max_depth > 0:
        valid = valid & (node_depth < p.max_depth)
    return jnp.where(valid, gain, -jnp.inf), (gl, hl, cl)


def _best_split(hist, sum_g, sum_h, sum_c, num_bins, feature_mask,
                node_depth, p: GrowthParams, node_lo=None, node_hi=None,
                mono_c=None):
    """Best (gain, feature, bin, left-sums) from a node histogram (F, B, 3)."""
    F, B, _ = hist.shape
    gain, cum = _gain_matrix(hist, sum_g, sum_h, sum_c, num_bins,
                             feature_mask, node_depth, p, node_lo, node_hi,
                             mono_c)
    flat = jnp.argmax(gain)
    bf, bb = flat // B, flat % B
    bgain = gain[bf, bb]
    gl, hl, cl = cum
    return bgain, bf.astype(jnp.int32), bb.astype(jnp.int32), \
        gl[bf, bb], hl[bf, bb], cl[bf, bb]


# -- two-level (coarse-then-refine) histograms ------------------------------
#
# At max_bin=255 the level pass is bounded by the VPU one-hot build
# (measured: the int8 matmul runs at ~122 Tmac/s while the (ft·B, C)
# one-hot construction costs ~1.5x the matmul and the step time equals
# the max of the two).  Two-level growth builds the per-wave histograms
# at COARSE (bin >> TWO_LEVEL_SHIFT) resolution — 2^shift less
# one-hot work and matmul, equally smaller split scans and histogram
# state — then refines only
# a top-K feature subset, chosen ONCE per tree from the ROOT's coarse
# per-feature gains, with ONE narrow full-resolution pass per wave (left
# children only; right children by subtraction from the parent's stored
# fine-K histograms — a per-wave adaptive set would need both children
# built fresh at 2S lanes, which was measured to eat the coarse win).
# Every coarse boundary is itself a fine split, so unrefined features
# keep exact (if coarser) candidates; the tradeoff is only that a
# feature outside the root-chosen top-K cannot win on a
# sub-coarse-boundary cut.

#: rows below which "auto" two-level stays off (small data gains nothing
#: and exactness-vs-255-bins matters more in tests)
TWO_LEVEL_MIN_ROWS = 500_000
#: coarse level is bin >> this shift (255-bin fine -> 32-bin coarse;
#: measured on chip: shift 3 cuts the coarse pass ~17% vs shift 2 with
#: holdout AUC unchanged — the refined top-K carries fine resolution and
#: the 32-bin coarse fallback still bounds every unrefined feature)
TWO_LEVEL_SHIFT = 3


def depthwise_hist_plan(num_features: int, n_rows: int, p: "GrowthParams",
                        n_slots: int, bundled: bool,
                        use_pallas: bool) -> dict:
    """What :func:`grow_tree_depthwise` builds its histograms with, from
    what it can see in its shapes: whether they are two-level, and on the
    pallas path the fused pass's tile.  The grower decides by this, and
    ``booster.train`` writes it on the ``gbdt.fit`` span.

    Two-level is structurally excluded wherever an exactness-pinned path
    needs full resolution (EFB bit-identity: ``bundled``; monotone refresh
    re-picks), under 128 bins, and where the refined block would not fit
    the fused pass's VMEM (an uncapped ``refine_features`` falls back to
    full-resolution growth instead of failing at Mosaic compile time);
    "auto" additionally wants big data, so small-data fits keep exact-255
    semantics.

    → ``two_level``; ``ft``, ``feature_groups``, ``chunk`` and
    ``grid_steps_per_pass`` of a wave's pass (0 off the pallas path, or
    where no tile fits VMEM and the caller must not take it);
    ``split_columns``, how a wave reads the columns its splits route by:
    ``indexed`` on the pallas path (the root, every fused wave and the
    last wave name them by index and read them where they lie), else
    ``per_row`` (the XLA path gathers each row's own column).
    ``num_features`` and ``n_rows`` are what ONE device's grower sees
    (bundled columns, padded rows of its shard)."""
    from .pallas_hist import fused_geometry
    B, K, SH = p.total_bins, p.refine_k, TWO_LEVEL_SHIFT
    mono = p.monotone_constraints is not None and any(p.monotone_constraints)
    geo_tl = (fused_geometry(num_features, B, n_slots, p.hist_chunk,
                             hist_shift=SH, refine_k=K)
              if use_pallas else None)
    tl = (K > 0 and p.two_level != "off" and not bundled and not mono
          and B >= 128 and num_features > K
          and (p.two_level == "on" or n_rows >= TWO_LEVEL_MIN_ROWS)
          and (not use_pallas or geo_tl is not None))
    geo = geo_tl if tl or not use_pallas else fused_geometry(
        num_features, B, n_slots, p.hist_chunk)
    ft, chunk = geo or (0, 0)
    groups = -(-num_features // ft) if ft else 0
    return dict(two_level=bool(tl), ft=ft, feature_groups=groups,
                chunk=chunk,
                grid_steps_per_pass=(n_rows // chunk) * groups if ft else 0,
                split_columns="indexed" if ft else "per_row")


def _pool_coarse(hist, Bc: int, shift: int):
    """Fine (..., B, 3) f32 histograms → coarse (..., Bc, 3) by summing
    the ``1 << shift`` fine bins sharing each coarse index — the XLA-path
    counterpart of the pallas kernel's in-kernel coarse build."""
    B = hist.shape[-2]
    g = 1 << shift
    pad = Bc * g - B
    h = jnp.pad(hist, [(0, 0)] * (hist.ndim - 2) + [(0, pad), (0, 0)])
    return h.reshape(h.shape[:-2] + (Bc, g, 3)).sum(-2)


def _tl_coarse_gains(c_hists, sum_g, sum_h, sum_c, depth, lo, hi,
                     num_bins_c, feature_mask, p: GrowthParams):
    """Batched coarse gain matrices for two-level selection.

    → (gains (S', F, Bc), cum 3-tuple of (S', F, Bc), per-feature max
    gains (S', F))."""
    def one(h, g, hh, c, d, l, u):
        return _gain_matrix(h, g, hh, c, num_bins_c, feature_mask, d, p,
                            l, u, None)
    cg, ccum = jax.vmap(one)(c_hists, sum_g, sum_h, sum_c, depth, lo, hi)
    return cg, ccum, jnp.max(cg, axis=-1)


def _tl_final_pick(cg, ccum, f_hists, topk, sum_g, sum_h, sum_c, depth,
                   lo, hi, num_bins, feature_mask, p: GrowthParams,
                   shift: int):
    """Merge the refined fine candidates with the unrefined coarse
    candidates → per-node best split in FINE bin space.

    ``cg``/``ccum``: coarse gains and cumulative left sums from
    :func:`_tl_coarse_gains`; ``f_hists`` (S', K, B, 3): full-resolution
    histograms of the ``topk`` features.  A coarse candidate at coarse bin
    c maps to the fine boundary ``(c+1)·2^shift - 1`` (the rows ≤ that
    fine bin are exactly the rows ≤ c at coarse resolution, so the coarse
    cum sums are exact for the mapped split)."""
    Sp, F, Bc = cg.shape
    B = f_hists.shape[-2]
    rows = jnp.arange(Sp)
    # coarse candidates exclude the refined features (they compete at
    # fine resolution instead)
    cg = cg.at[:, topk, :].set(-jnp.inf)
    flat = jnp.argmax(cg.reshape(Sp, -1), axis=-1)
    cf, cc = flat // Bc, flat % Bc
    cgain = cg[rows, cf, cc]
    cgl = ccum[0][rows, cf, cc]
    chl = ccum[1][rows, cf, cc]
    ccl = ccum[2][rows, cf, cc]
    step = 1 << shift
    cbin = jnp.minimum(cc * step + step - 1, num_bins[cf] - 1)

    nbk = num_bins[topk]
    fmk = feature_mask[topk]

    def one(h, g, hh, c, d, l, u):
        return _gain_matrix(h, g, hh, c, nbk, fmk, d, p, l, u, None)
    fg, fcum = jax.vmap(one)(f_hists, sum_g, sum_h, sum_c, depth, lo, hi)
    fflat = jnp.argmax(fg.reshape(Sp, -1), axis=-1)
    fk, fb = fflat // B, fflat % B
    fgain = fg[rows, fk, fb]
    fgl = fcum[0][rows, fk, fb]
    fhl = fcum[1][rows, fk, fb]
    fcl = fcum[2][rows, fk, fb]

    use_f = fgain >= cgain
    return (jnp.where(use_f, fgain, cgain),
            jnp.where(use_f, topk[fk], cf).astype(jnp.int32),
            jnp.where(use_f, fb, cbin).astype(jnp.int32),
            jnp.where(use_f, fgl, cgl),
            jnp.where(use_f, fhl, chl),
            jnp.where(use_f, fcl, ccl))


def _tl_root_pick(root_hist, root_g, root_h, root_c, num_bins, num_bins_c,
                  feature_mask, p: GrowthParams, shift: int, K: int,
                  bins_t, B: int, use_pallas, build_fine_root, ar):
    """Shared two-level ROOT setup for both growers: coarse gains → the
    per-tree top-K feature set → gathered/prepared refined-feature
    layouts → root fine histograms → merged root pick.

    ``build_fine_root(bins_kp) -> (1, K, B, 3)`` is the grower-specific
    fine build (fused-path tiles vs flat XLA ids both prepared here).
    → (topk, sel_k, bins_kp, root_fine, (bg, bf, bb, bgl, bhl, bcl))."""
    z1 = jnp.zeros((1,), jnp.int32)
    ninf1 = jnp.full((1,), -jnp.inf)
    inf1 = jnp.full((1,), jnp.inf)
    cg0, ccum0, fgain0 = _tl_coarse_gains(
        root_hist[None], root_g[None], root_h[None], root_c[None],
        z1, ninf1, inf1, num_bins_c, feature_mask, p)
    topk = lax.top_k(fgain0[0], K)[1].astype(jnp.int32)
    # gather + layout the K refined feature rows ONCE per tree (a
    # contiguous feature-axis row copy, NOT the pathological per-row
    # gather); the split loops close over the result
    sel_k = jnp.take(bins_t, topk, axis=0)
    if use_pallas:
        from .pallas_hist import prepare_feature_tiles
        bins_kp = prepare_feature_tiles(sel_k, B, K)
    else:
        bins_kp = sel_k + (jnp.arange(K, dtype=jnp.int32) * B)[:, None]
    root_fine = ar(build_fine_root(bins_kp))               # (1, K, B, 3)
    rbest = _tl_final_pick(cg0, ccum0, root_fine, topk,
                           root_g[None], root_h[None], root_c[None],
                           z1, ninf1, inf1, num_bins, feature_mask,
                           p, shift)
    return topk, sel_k, bins_kp, root_fine, tuple(x[0] for x in rbest)


def _mono_vec(p: GrowthParams, F: int):
    """(F,) int32 constraint vector padded/truncated to the feature count
    this grower sees (pallas feature padding adds unconstrained columns),
    or None when unconstrained."""
    if p.monotone_constraints is None or not any(p.monotone_constraints):
        return None
    c = tuple(p.monotone_constraints)[:F]
    c = c + (0,) * (F - len(c))
    return jnp.asarray(c, jnp.int32)


def _mono_child_bounds(cf, lo, hi, wl, wr):
    """Child output bounds after splitting on a feature with constraint
    ``cf`` (basic method): the clamped child outputs' midpoint caps the
    violating side; unconstrained split features pass bounds through."""
    mid = 0.5 * (wl + wr)
    l_lo = jnp.where(cf == -1, jnp.maximum(lo, mid), lo)
    l_hi = jnp.where(cf == 1, jnp.minimum(hi, mid), hi)
    r_lo = jnp.where(cf == 1, jnp.maximum(lo, mid), lo)
    r_hi = jnp.where(cf == -1, jnp.minimum(hi, mid), hi)
    return l_lo, l_hi, r_lo, r_hi


def _intermediate_bounds(split_feature, left_child, right_child,
                         raw_value, mono_c, n_iters: int = 0):
    """Intermediate-method bounds: a constrained split bounds each child
    SUBTREE by the opposite subtree's extreme leaf outputs (LightGBM's
    IntermediateLeafConstraints semantics) instead of the midpoint.

    Implementation: the constraint set is materialized as explicit pairs
    — for a split at node a on feature f with c=+1, every node of L(a)
    is <= every LEAF of R(a) and every node of R(a) is >= every LEAF of
    L(a) (extremes range over leaves, matching the old scan formulation)
    — then projected through :func:`_project_pairs`, which is exact and
    convergent where the old clip-raw iteration oscillated on
    conflicting raw values.  ``n_iters`` is kept for call-site
    compatibility and ignored.

    Returns (lo, hi, clamped_value), each (M,)."""
    del n_iters
    M = split_feature.shape[0]
    leaf = left_child < 0

    # desc[a, i]: node i lies in a's subtree (children carry higher
    # indices than parents in every grower here, so one backward walk)
    def back(k, desc):
        j = M - 1 - k
        l = jnp.maximum(left_child[j], 0)
        r = jnp.maximum(right_child[j], 0)
        internal = left_child[j] >= 0
        row = jnp.zeros(M, jnp.bool_).at[j].set(True)
        row = row | (jnp.where(internal, desc[l] | desc[r],
                               jnp.zeros(M, jnp.bool_)))
        return desc.at[j].set(row)

    desc = lax.fori_loop(0, M, back, jnp.zeros((M, M), jnp.bool_))

    internal = left_child >= 0
    inL = jnp.where(internal[:, None],
                    desc[jnp.maximum(left_child, 0)], False)    # (M, M)
    inR = jnp.where(internal[:, None],
                    desc[jnp.maximum(right_child, 0)], False)
    c = jnp.where(internal, mono_c[jnp.maximum(split_feature, 0)], 0)
    # side that must stay LOW / HIGH at each constrained split
    low_side = jnp.where((c == 1)[:, None], inL,
                         jnp.where((c == -1)[:, None], inR, False))
    high_side = jnp.where((c == 1)[:, None], inR,
                          jnp.where((c == -1)[:, None], inL, False))
    # P[i, j]: val_i <= val_j with j leaf; Q[i, j]: val_i >= val_j, j leaf
    f32 = jnp.float32
    P = (low_side.T.astype(f32)
         @ (high_side & leaf[None, :]).astype(f32)) > 0
    Q = (high_side.T.astype(f32)
         @ (low_side & leaf[None, :]).astype(f32)) > 0
    return _project_pairs(P, Q, raw_value, leaf)


def _project_pairs(P, Q, raw_value, leaf):
    """Feasible monotone assignment + bounds from explicit constraints.

    ``P[i, j]``: ``val_i <= val_j``; ``Q[i, j]``: ``val_i >= val_j`` —
    in both, j is a LEAF (i may be any node).  Leaves take
    ``(L + U) / 2`` with ``L_i = max(raw_i, max raw over transitive-
    closure predecessors)`` and ``U_i = min(raw_i, min raw over closure
    successors)``: L and U are each non-decreasing along every
    constraint edge, so their average is feasible BY CONSTRUCTION and
    equals raw wherever raw is already feasible — unlike the previous
    clip-raw-to-current-bounds iteration, which oscillated with period 2
    on conflicting raw values and, at an even iteration count, handed
    the raw violating values straight back.  Internal nodes clamp to the
    bounds the final leaf values imply (they never feed back).

    Returns (lo, hi, val), each (M,)."""
    M = raw_value.shape[0]
    leaf_pairs = P & leaf[:, None]
    f32 = jnp.float32

    def sq(le, _):
        return (le | ((le.astype(f32) @ le.astype(f32)) > 0)), None

    rounds = max(int(np.ceil(np.log2(max(M, 2)))), 1)
    close, _ = lax.scan(sq, leaf_pairs, None, length=rounds)
    L = jnp.maximum(raw_value, jnp.max(
        jnp.where(close.T, raw_value[None, :], -jnp.inf), axis=1))
    U = jnp.minimum(raw_value, jnp.min(
        jnp.where(close, raw_value[None, :], jnp.inf), axis=1))
    vleaf = jnp.where(leaf, 0.5 * (L + U), raw_value)
    # per-node bounds from the FINAL leaf values — what split search and
    # internal-node clamping consume
    hi = jnp.min(jnp.where(P, vleaf[None, :], jnp.inf), axis=1)
    lo = jnp.max(jnp.where(Q, vleaf[None, :], -jnp.inf), axis=1)
    val = jnp.where(leaf, vleaf, jnp.clip(raw_value, lo, hi))
    return lo, hi, val


def _advanced_bounds(split_feature, split_bin, left_child, right_child,
                     raw_value, mono_c, total_bins: int, n_iters: int = 6):
    """Advanced-method bounds: the EXACT minimal constraint set for
    single-tree monotonicity.

    ``val_i <= val_j`` is required iff leaves i and j are ORDERED on a
    constrained feature f (i's bin box strictly left of j's) and their
    boxes OVERLAP on every other feature — precisely the pairs some input
    pair x <= x' (differing only in f) can land in, so the set is both
    necessary and sufficient.  Intermediate's opposite-subtree extremes
    are a SUPERSET of these pairs (it also constrains non-overlapping
    boxes), which is why advanced is provably no tighter than
    intermediate; LightGBM's own ``advanced`` pursues the same relaxation
    via threshold-dependent per-leaf constraints
    (reference surfaces the method string only:
    params/LightGBMParams.scala:168-183).  O(M^2 F) memory — fine for
    monotone-model sizes; reject upstream if it ever is not.

    Returns (lo, hi, clamped_value), each (M,); internal nodes clamp to
    the bounds the final leaf values imply."""
    del n_iters                      # _project_pairs is exact, not iterative
    M = split_feature.shape[0]
    F = mono_c.shape[0]
    JUNK = M

    # per-node bin boxes (lo, hi] by a root->children walk (children carry
    # higher indices than parents in every grower here); categorical
    # features use target-ordered bins, so their splits are interval
    # splits too and the box walk stays exact
    lo0 = jnp.full((M + 1, F), -1, jnp.int32)
    hi0 = jnp.full((M + 1, F), total_bins - 1, jnp.int32)

    def fwd(j, boxes):
        lo, hi = boxes
        lraw, rraw = left_child[j], right_child[j]
        internal = lraw >= 0
        l = jnp.where(internal, lraw, JUNK)
        r = jnp.where(internal, rraw, JUNK)
        f = jnp.maximum(split_feature[j], 0)
        b = split_bin[j]
        lhi = hi[j].at[f].set(jnp.minimum(hi[j, f], b))
        rlo = lo[j].at[f].set(jnp.maximum(lo[j, f], b))
        lo = lo.at[l].set(lo[j]).at[r].set(rlo)
        hi = hi.at[l].set(lhi).at[r].set(hi[j])
        return lo, hi

    lo, hi = lax.fori_loop(0, M, fwd, (lo0, hi0))
    lo, hi = lo[:M], hi[:M]

    leaf = left_child < 0
    # boxes (lo, hi] intersect iff lo_i < hi_j and lo_j < hi_i
    ov = ((lo[:, None, :] < hi[None, :, :])
          & (lo[None, :, :] < hi[:, None, :]))          # (M, M, F)
    n_ov = jnp.sum(ov.astype(jnp.int32), axis=-1)       # (M, M)
    # overlap on every feature EXCEPT f
    ov_exc = (n_ov[:, :, None] - ov.astype(jnp.int32)) == (F - 1)
    ordered = hi[:, None, :] <= lo[None, :, :]          # i left of j on f
    # any-node-to-LEAF constraint masks for _project_pairs (internal
    # nodes get bounds from the leaf values but never feed back)
    inc_f = ov_exc & (mono_c[None, None, :] == 1)
    dec_f = ov_exc & (mono_c[None, None, :] == -1)
    # val_i <= val_j: i left of j on a +1 feature, or right of j on a -1
    P_any = (jnp.any(ordered & inc_f, axis=-1)
             | jnp.any(ordered.transpose(1, 0, 2) & dec_f, axis=-1))
    # val_i >= val_j: the mirrored directions
    Q_any = (jnp.any(ordered.transpose(1, 0, 2) & inc_f, axis=-1)
             | jnp.any(ordered & dec_f, axis=-1))
    return _project_pairs(P_any & leaf[None, :], Q_any & leaf[None, :],
                          raw_value, leaf)


def _tree_bounds(split_feature, split_bin, left_child, right_child,
                 raw_value, mono_c, p: "GrowthParams", n_iters: int = 4):
    """Whole-tree bounds refresh for the method in ``p.monotone_method``
    (``intermediate`` or ``advanced``) → (lo, hi, clamped_value)."""
    if p.monotone_method == "advanced":
        return _advanced_bounds(split_feature, split_bin, left_child,
                                right_child, raw_value, mono_c,
                                p.total_bins, n_iters=max(n_iters, 6))
    return _intermediate_bounds(split_feature, left_child, right_child,
                                raw_value, mono_c, n_iters=n_iters)


def _refresh_intermediate(s, mono_c, p: "GrowthParams"):
    """Replace a grower state's node bounds with whole-tree-refresh
    bounds (intermediate or advanced method) recomputed over the whole
    current tree."""
    raw = _leaf_output(s["sum_g"], s["sum_h"], p.lambda_l1, p.lambda_l2)
    lo, hi, _ = _tree_bounds(s["split_feature"], s["split_bin"],
                             s["left_child"], s["right_child"], raw,
                             mono_c, p)
    return dict(s, node_lo=lo, node_hi=hi)


def _mono_node_bounds(mono_cf, p_lo, p_hi, lg, lh, rg, rh, p):
    """One split's child bounds: pass-through when unconstrained
    (``mono_cf`` None), else clamp the children's leaf outputs to the
    parent bounds and cap the violating side at their midpoint — the ONE
    place the basic-method propagation lives for all three growers."""
    if mono_cf is None:
        return p_lo, p_hi, p_lo, p_hi
    wl = jnp.clip(_leaf_output(lg, lh, p.lambda_l1, p.lambda_l2),
                  p_lo, p_hi)
    wr = jnp.clip(_leaf_output(rg, rh, p.lambda_l1, p.lambda_l2),
                  p_lo, p_hi)
    return _mono_child_bounds(mono_cf, p_lo, p_hi, wl, wr)


def _best_split_voting(local_hist, sum_g, sum_h, sum_c, num_bins,
                       feature_mask, node_depth, p: GrowthParams,
                       axis_name: str, node_lo=None, node_hi=None,
                       mono_c=None):
    """Voting-parallel split selection (LightGBM ``voting_parallel`` / the
    PV-Tree algorithm; reference surfaces it as the ``parallelism`` param,
    params/LightGBMParams.scala:25, topK LightGBMBase.scala:251).

    Each rank keeps its histograms LOCAL and: (1) ranks features by local
    best gain and votes for its top-k; (2) votes ride one tiny psum and the
    global top-2k features are selected identically on every rank; (3) only
    those 2k features' histograms are psum'd — O(2k·B) instead of O(F·B)
    ICI traffic — and the true global best split is chosen among them.
    ``sum_g/h/c`` must be the node's GLOBAL stats.
    """
    F, B, _ = local_hist.shape
    k = min(p.voting_k, F)
    sel_n = min(2 * k, F)

    # (1) local view: gains against local node stats (the local root/leaf
    # sums live in every feature's bins; feature 0 spans all rows)
    lsum = jnp.sum(local_hist[0], axis=0)            # (3,)
    lgain, _ = _gain_matrix(local_hist, lsum[0], lsum[1], lsum[2],
                            num_bins, feature_mask, node_depth, p,
                            node_lo, node_hi, mono_c)
    per_feat = jnp.max(lgain, axis=1)                # (F,)
    _, local_top = lax.top_k(per_feat, k)
    votes = jnp.zeros(F, jnp.float32).at[local_top].add(
        jnp.where(per_feat[local_top] > -jnp.inf, 1.0, 0.0))
    votes = lax.psum(votes, axis_name)

    # (2) deterministic global top-2k: votes desc, feature index asc
    # (exact in f32 while votes·(F+1)+F < 2^24)
    score = votes * jnp.float32(F + 1) + jnp.arange(F - 1, -1, -1,
                                                    dtype=jnp.float32)
    _, sel = lax.top_k(score, sel_n)
    sel = sel.astype(jnp.int32)

    # (3) aggregate only the voted features; pick the global best among them
    glob = lax.psum(local_hist[sel], axis_name)      # (sel_n, B, 3)
    ggain, cum = _gain_matrix(glob, sum_g, sum_h, sum_c, num_bins[sel],
                              feature_mask[sel], node_depth, p,
                              node_lo, node_hi,
                              None if mono_c is None else mono_c[sel])
    flat = jnp.argmax(ggain)
    bi, bb = flat // B, flat % B
    gl, hl, cl = cum
    return ggain[bi, bb], sel[bi], bb.astype(jnp.int32), \
        gl[bi, bb], hl[bi, bb], cl[bi, bb]


@functools.partial(jax.jit, static_argnames=("p", "axis_name", "use_pallas",
                                             "cconfig"))
def grow_tree(bins_t: jnp.ndarray,          # (F, N) int32 (transposed bins)
              grad: jnp.ndarray,            # (N,) f32 (0 for pad rows)
              hess: jnp.ndarray,            # (N,) f32 (0 for pad rows)
              row_valid: jnp.ndarray,       # (N,) f32 bag-weight ∈ {0,1} or GOSS weight
              feature_mask: jnp.ndarray,    # (F,) bool — feature_fraction mask
              upper_bounds: jnp.ndarray,    # (F, B-1) f32 raw bin bounds
              num_bins: jnp.ndarray,        # (F,) int32
              learning_rate: float,
              p: GrowthParams,
              axis_name: Optional[str] = None,
              use_pallas: bool = False,
              bundle_map: Optional[dict] = None,
              cconfig=None,
              ) -> Tuple[Tree, jnp.ndarray]:
    """Grow one tree; returns (tree, per-row leaf node ids).

    When ``axis_name`` is set the function must run inside shard_map over
    that axis; histograms and root stats are psum'd so every rank grows the
    identical tree from its row shard.

    ``bundle_map`` (EFB): ``bins_t`` holds BUNDLED columns but split
    search, routing and the emitted tree all live in ORIGINAL feature
    space — histograms unbundle before each pick, splits route through
    :func:`_slot_route_params`.

    ``cconfig`` (a :class:`~synapseml_tpu.parallel.compression.
    CollectiveConfig`, static): puts the per-split histogram allreduce —
    THE data-parallel bandwidth hog — on a quantized wire.  Stateless
    per histogram; every rank still decodes identical bytes, so the
    identical-tree invariant holds.
    """
    F, N = bins_t.shape
    B = p.total_bins
    L = p.num_leaves
    M = max_nodes(L)

    # voting-parallel keeps histograms local and aggregates only the voted
    # features inside _best_split_voting; full data-parallel psums every
    # histogram as it is built
    voting = p.voting_k > 0 and axis_name is not None
    F_search = num_bins.shape[0]           # ORIGINAL feature count
    mono_c = _mono_vec(p, F_search)

    # two-level (coarse-then-refine) histograms for strict leaf-wise
    # growth: same scheme as the depthwise grower (module comment above
    # _pool_coarse) — per-split coarse build + root-chosen fine-K refine;
    # the per-tile nodes kernel needs no extra VMEM gate (its scratch is
    # bounded by the ft cap regardless of K)
    from .pallas_hist import coarse_bins
    tl = (p.refine_k > 0 and p.two_level != "off"
          and bundle_map is None and mono_c is None and not voting
          and B >= 128 and F > p.refine_k
          and (p.two_level == "on" or N >= TWO_LEVEL_MIN_ROWS))
    _tl_gauge("lossguide", tl)
    SH = TWO_LEVEL_SHIFT
    Bc = coarse_bins(B, SH)
    Bh = Bc if tl else B                   # stored-histogram width
    K = p.refine_k
    num_bins_c = -(-num_bins // (1 << SH))

    def ar(x):
        # routed through the planner dispatch so the histogram
        # allreduce — THE data-parallel hot collective — shows up in
        # collective_{calls,bytes}_total (recorded per traced program)
        # AND takes the topology-planned route: with a compression
        # config the wire rides the quantized reduce-scatter +
        # all-gather (or the two-level hierarchical form on a known
        # multi-host topology); without one this traces exactly the
        # bare f32 psum it always did
        if not axis_name or voting:
            return x
        return _c_planned_psum(
            x, axis_name, cconfig,
            op="gbdt_hist_psum" if cconfig is not None else "psum")

    def unb(hist3, g, h, c):
        if bundle_map is None:
            return hist3
        return _unbundle_hists(hist3, bundle_map["gather_src"],
                               jnp.stack([g, h, c], -1))

    if voting:
        def pick(hist3, g, h, c, depth, lo, hi):
            if bundle_map is not None:
                # unbundle the LOCAL histograms before voting: gather and
                # residual are linear, so the selective psum of unbundled
                # columns equals unbundling the psum — votes and the
                # aggregated gains both live in ORIGINAL feature space.
                # The local node totals come from bundled column 0, whose
                # bins cover every row of the node exactly once
                ltot = jnp.sum(hist3[0], axis=0)
                hist3 = _unbundle_hists(hist3, bundle_map["gather_src"],
                                        ltot)
            return _best_split_voting(hist3, g, h, c, num_bins, feature_mask,
                                      depth, p, axis_name, lo, hi, mono_c)
    else:
        def pick(hist3, g, h, c, depth, lo, hi):
            return _best_split(unb(hist3, g, h, c), g, h, c, num_bins,
                               feature_mask, depth, p, lo, hi, mono_c)

    flat_bins = None
    vals8 = scales = None
    bins_pl = bins_t
    if not use_pallas:
        flat_bins = bins_t + (jnp.arange(F, dtype=jnp.int32) * B)[:, None]
    else:
        from .pallas_hist import prep_hist_vals, prepare_feature_tiles
        vals8, scales = prep_hist_vals(grad, hess, row_valid)
        # (G, ft, N) tile reshape ONCE per tree, not per split (the
        # reshape materializes a copy; see prepare_feature_tiles)
        bins_pl = prepare_feature_tiles(bins_t, B, F)

    # root
    root_hist = ar(_build_hist(bins_pl, flat_bins, grad, hess,
                               row_valid, F, B, use_pallas,
                               vals8, scales,
                               hist_shift=(SH if tl else 0),
                               hist_chunk=p.hist_chunk)
                   ).reshape(F, Bh, 3)
    root_stats = jnp.sum(root_hist[0], axis=0)
    if voting:
        root_stats = lax.psum(root_stats, axis_name)
    root_g, root_h, root_c = root_stats[0], root_stats[1], root_stats[2]

    topk = None
    root_fine = None
    if tl:
        def build_fine_k(bkp, mask):
            """(1, K, B, 3) fine histograms of the refined features for
            the masked rows."""
            if use_pallas:
                from .pallas_hist import build_hist_nodes_pallas
                slot = jnp.where(mask > 0, 0, -1).astype(jnp.int32)
                return build_hist_nodes_pallas(
                    bkp, slot, vals8, scales, 1, B,
                    interpret=(use_pallas == "interpret"),
                    hist_chunk=p.hist_chunk)
            return _build_hist_nodes_xla(
                bkp, grad, hess, mask,
                jnp.where(mask > 0, 0, -1).astype(jnp.int32), 1, K, B)

        topk, sel_k, bins_kp, root_fine, rbest0 = _tl_root_pick(
            root_hist, root_g, root_h, root_c, num_bins, num_bins_c,
            feature_mask, p, SH, K, bins_t, B, use_pallas,
            lambda bkp: build_fine_k(bkp, row_valid), ar)

    # per-node state
    zi = jnp.zeros(M, jnp.int32)
    zf = jnp.zeros(M, jnp.float32)
    state = dict(
        node_id=jnp.zeros(N, jnp.int32),
        hist=jnp.zeros((L + 1, F * Bh, 3), jnp.float32).at[0].set(
            root_hist.reshape(F * Bh, 3)),
        slot=zi,                                   # node -> hist slot
        sum_g=zf.at[0].set(root_g),
        sum_h=zf.at[0].set(root_h),
        sum_c=zf.at[0].set(root_c),
        depth=zi,
        best_gain=jnp.full(M, -jnp.inf, jnp.float32),
        best_feat=zi, best_bin=zi,
        best_gl=zf, best_hl=zf, best_cl=zf,
        active=jnp.zeros(M, jnp.bool_).at[0].set(True),
        split_feature=jnp.full(M, -1, jnp.int32),
        split_bin=zi,
        split_gain=zf,
        threshold=zf,
        left_child=jnp.full(M, -1, jnp.int32),
        right_child=jnp.full(M, -1, jnp.int32),
        num_nodes=jnp.ones((), jnp.int32),
        next_slot=jnp.ones((), jnp.int32),
        node_lo=jnp.full(M, -jnp.inf, jnp.float32),
        node_hi=jnp.full(M, jnp.inf, jnp.float32),
    )
    if tl:
        state["hist_f"] = jnp.zeros((L + 1, K * B, 3), jnp.float32).at[
            0].set(root_fine[0].reshape(K * B, 3))
        bg, bf_, bb, bgl, bhl, bcl = rbest0
    else:
        bg, bf_, bb, bgl, bhl, bcl = pick(root_hist, root_g, root_h,
                                          root_c,
                                          jnp.zeros((), jnp.int32),
                                          -jnp.inf, jnp.inf)
    state["best_gain"] = state["best_gain"].at[0].set(bg)
    state["best_feat"] = state["best_feat"].at[0].set(bf_)
    state["best_bin"] = state["best_bin"].at[0].set(bb)
    state["best_gl"] = state["best_gl"].at[0].set(bgl)
    state["best_hl"] = state["best_hl"].at[0].set(bhl)
    state["best_cl"] = state["best_cl"].at[0].set(bcl)

    def do_split(s):
        gains = jnp.where(s["active"], s["best_gain"], -jnp.inf)
        leaf = jnp.argmax(gains).astype(jnp.int32)
        feat, sbin = s["best_feat"][leaf], s["best_bin"][leaf]
        l_id = s["num_nodes"]
        r_id = s["num_nodes"] + 1

        in_leaf = s["node_id"] == leaf
        col_s, t1_s, lo_s, hi_s, df_s = _slot_route_params(
            feat, sbin, B, bundle_map)
        go_left = _route_left(bins_t[col_s, :], t1_s, lo_s, hi_s, df_s)
        new_node_id = jnp.where(in_leaf, jnp.where(go_left, l_id, r_id),
                                s["node_id"])

        # left child hist by one device pass, right by subtraction
        lmask = (new_node_id == l_id).astype(jnp.float32) * row_valid
        l_hist = ar(_build_hist(bins_pl, flat_bins, grad, hess, lmask, F, B,
                                use_pallas, vals8, scales,
                                hist_shift=(SH if tl else 0),
                                hist_chunk=p.hist_chunk))
        parent_slot = s["slot"][leaf]
        r_hist = s["hist"][parent_slot] - l_hist
        r_slot = s["next_slot"]
        hist = s["hist"].at[parent_slot].set(l_hist).at[r_slot].set(r_hist)

        lg, lh, lc = s["best_gl"][leaf], s["best_hl"][leaf], s["best_cl"][leaf]
        rg, rh, rc = s["sum_g"][leaf] - lg, s["sum_h"][leaf] - lh, s["sum_c"][leaf] - lc
        cdepth = s["depth"][leaf] + 1

        p_lo, p_hi = s["node_lo"][leaf], s["node_hi"][leaf]
        l_lo, l_hi, r_lo, r_hi = _mono_node_bounds(
            None if mono_c is None else mono_c[feat],
            p_lo, p_hi, lg, lh, rg, rh, p)

        hist_f = None
        if tl:
            lf = ar(build_fine_k(bins_kp, lmask))[0].reshape(K * B, 3)
            rf = s["hist_f"][parent_slot] - lf
            hist_f = (s["hist_f"].at[parent_slot].set(lf)
                      .at[r_slot].set(rf))
            c_hists = jnp.stack([l_hist, r_hist]).reshape(2, F, Bh, 3)
            f_hists = jnp.stack([lf, rf]).reshape(2, K, B, 3)
            cgm, ccum, _ = _tl_coarse_gains(
                c_hists, jnp.stack([lg, rg]), jnp.stack([lh, rh]),
                jnp.stack([lc, rc]), jnp.stack([cdepth, cdepth]),
                jnp.stack([l_lo, r_lo]), jnp.stack([l_hi, r_hi]),
                num_bins_c, feature_mask, p)
            cb = _tl_final_pick(
                cgm, ccum, f_hists, topk, jnp.stack([lg, rg]),
                jnp.stack([lh, rh]), jnp.stack([lc, rc]),
                jnp.stack([cdepth, cdepth]), jnp.stack([l_lo, r_lo]),
                jnp.stack([l_hi, r_hi]), num_bins, feature_mask, p, SH)
            (lbg, rbg), (lbf, rbf), (lbb, rbb) = cb[0], cb[1], cb[2]
            (lbgl, rbgl), (lbhl, rbhl), (lbcl, rbcl) = cb[3], cb[4], cb[5]
        else:
            lbg, lbf, lbb, lbgl, lbhl, lbcl = pick(
                l_hist.reshape(F, B, 3), lg, lh, lc, cdepth, l_lo, l_hi)
            rbg, rbf, rbb, rbgl, rbhl, rbcl = pick(
                r_hist.reshape(F, B, 3), rg, rh, rc, cdepth, r_lo, r_hi)

        thr = jnp.where(sbin >= 1, upper_bounds[feat, jnp.maximum(sbin - 1, 0)],
                        -jnp.inf)

        return dict(
            node_id=new_node_id,
            hist=hist,
            slot=s["slot"].at[l_id].set(parent_slot).at[r_id].set(r_slot),
            sum_g=s["sum_g"].at[l_id].set(lg).at[r_id].set(rg),
            sum_h=s["sum_h"].at[l_id].set(lh).at[r_id].set(rh),
            sum_c=s["sum_c"].at[l_id].set(lc).at[r_id].set(rc),
            depth=s["depth"].at[l_id].set(cdepth).at[r_id].set(cdepth),
            best_gain=s["best_gain"].at[l_id].set(lbg).at[r_id].set(rbg),
            best_feat=s["best_feat"].at[l_id].set(lbf).at[r_id].set(rbf),
            best_bin=s["best_bin"].at[l_id].set(lbb).at[r_id].set(rbb),
            best_gl=s["best_gl"].at[l_id].set(lbgl).at[r_id].set(rbgl),
            best_hl=s["best_hl"].at[l_id].set(lbhl).at[r_id].set(rbhl),
            best_cl=s["best_cl"].at[l_id].set(lbcl).at[r_id].set(rbcl),
            active=s["active"].at[leaf].set(False).at[l_id].set(True)
                   .at[r_id].set(True),
            split_feature=s["split_feature"].at[leaf].set(feat),
            split_bin=s["split_bin"].at[leaf].set(sbin),
            split_gain=s["split_gain"].at[leaf].set(s["best_gain"][leaf]),
            threshold=s["threshold"].at[leaf].set(thr),
            left_child=s["left_child"].at[leaf].set(l_id),
            right_child=s["right_child"].at[leaf].set(r_id),
            num_nodes=s["num_nodes"] + 2,
            next_slot=s["next_slot"] + 1,
            node_lo=s["node_lo"].at[l_id].set(l_lo).at[r_id].set(r_lo),
            node_hi=s["node_hi"].at[l_id].set(l_hi).at[r_id].set(r_hi),
            **({"hist_f": hist_f} if tl else {}),
        )

    def maybe_intermediate_split(s):
        out = do_split(s)
        if mono_c is None or p.monotone_method not in ("intermediate",
                                                       "advanced"):
            return out
        # intermediate: bounds come from the OPPOSITE subtree's extremes
        # over the whole current tree; the fresh children re-pick under
        # the refreshed (looser) bounds
        out = _refresh_intermediate(out, mono_c, p)
        l_id, r_id = out["num_nodes"] - 2, out["num_nodes"] - 1
        for cid in (l_id, r_id):
            chist = out["hist"][out["slot"][cid]].reshape(F, B, 3)
            cbg, cbf, cbb, cbgl, cbhl, cbcl = pick(
                chist, out["sum_g"][cid], out["sum_h"][cid],
                out["sum_c"][cid], out["depth"][cid],
                out["node_lo"][cid], out["node_hi"][cid])
            out["best_gain"] = out["best_gain"].at[cid].set(cbg)
            out["best_feat"] = out["best_feat"].at[cid].set(cbf)
            out["best_bin"] = out["best_bin"].at[cid].set(cbb)
            out["best_gl"] = out["best_gl"].at[cid].set(cbgl)
            out["best_hl"] = out["best_hl"].at[cid].set(cbhl)
            out["best_cl"] = out["best_cl"].at[cid].set(cbcl)
        return out

    def body(_, s):
        gains = jnp.where(s["active"], s["best_gain"], -jnp.inf)
        can_split = jnp.max(gains) > p.min_gain_to_split
        return lax.cond(can_split, maybe_intermediate_split, lambda x: x, s)

    state = lax.fori_loop(0, L - 1, body, state)

    node_value = _leaf_output(state["sum_g"], state["sum_h"],
                              p.lambda_l1, p.lambda_l2)
    if mono_c is not None:
        if p.monotone_method in ("intermediate", "advanced"):
            _, _, node_value = _tree_bounds(
                state["split_feature"], state["split_bin"],
                state["left_child"], state["right_child"], node_value,
                mono_c, p, n_iters=6)
        else:
            node_value = jnp.clip(node_value, state["node_lo"],
                                  state["node_hi"])
    node_value = learning_rate * node_value
    leaf_value = jnp.where(state["left_child"] < 0, node_value, 0.0)

    tree = Tree(split_feature=state["split_feature"],
                split_bin=state["split_bin"],
                threshold=state["threshold"],
                split_gain=state["split_gain"],
                left_child=state["left_child"],
                right_child=state["right_child"],
                leaf_value=leaf_value,
                node_value=node_value,
                num_nodes=state["num_nodes"],
                default_left=jnp.ones(M, jnp.bool_),
                node_count=state["sum_c"],
                missing_zero=jnp.zeros(M, jnp.bool_))
    return tree, state["node_id"]


# -- depth-level growth ------------------------------------------------------
#
# The leaf-wise grower above launches one full-data histogram pass per split
# (num_leaves-1 sequential passes per tree).  The depth-level grower selects
# up to ``n_slots`` best leaves per wave (gain-ordered, budget-capped — the
# depthwise/lossguide hybrid used by accelerator GBDT implementations) and
# builds ALL their left-child histograms in ONE data pass, with the node
# assignment folded into the matmul lane dimension (pallas_hist.py,
# build_hist_nodes_pallas).  Right children come from histogram subtraction
# as before.  Typical tree cost: 1 root pass + ceil(log2-ish) wave passes
# (≈6 for 31 leaves) instead of 31.


def _hist_updates(grad, hess, mask):
    """(N, 3) [g·m, h·m, count] histogram update values.

    On TPU the values compute in the INGEST dtype (bf16 under fused
    ingest — grad's dtype decides) so the producer chain feeding the
    scatter/kernel stays narrow and scatter input fusion materializes
    the narrow buffer; accumulation is always f32.  On other backends
    the products promote straight to f32 — XLA:CPU materializes the
    scatter's f32 updates operand regardless, and a bf16 intermediate
    would only ADD a buffer (measured: +2.3% bytes accessed; same
    backend-quirk class as the CPU donation guard in
    models/dl/training.py)."""
    if jax.default_backend() == "tpu":
        count = (mask > 0).astype(grad.dtype)
        m = mask.astype(grad.dtype)
        return jnp.stack([grad * m, hess * m, count], axis=-1)
    count = (mask > 0).astype(jnp.float32)
    return jnp.stack([grad * mask, hess * mask, count], axis=-1)


def _build_hist_nodes_xla(flat_bins, grad, hess, mask, slot, n_slots, F, B):
    """XLA scatter fallback: (n_slots, F, B, 3) node-batched histograms.
    Rows with slot -1 scatter into a junk slot that is dropped."""
    s = jnp.where(slot >= 0, slot, n_slots)
    ids = flat_bins + (s * (F * B))[None, :]                  # (F, N)
    upd = _hist_updates(grad, hess, mask)                         # (N,3)
    upd = jnp.broadcast_to(upd[None, :, :], (F,) + upd.shape)     # (F,N,3)
    hist = jnp.zeros(((n_slots + 1) * F * B, 3), jnp.float32)
    hist = hist.at[ids].add(upd.astype(jnp.float32))
    return hist.reshape(n_slots + 1, F, B, 3)[:n_slots]


def _build_hist_nodes(bins_t, flat_bins, vals8, scales, grad, hess, mask,
                      slot, n_slots, F, B, use_pallas, hist_chunk=0):
    """``bins_t`` may be the flat (F, N) matrix OR the pre-reshaped
    (G, ft, N) tile layout (prepare_feature_tiles, F == G*ft always) —
    growers hoist the reshape out of their loops because it materializes
    a copy."""
    if use_pallas:
        from .pallas_hist import build_hist_nodes_pallas
        return build_hist_nodes_pallas(bins_t, slot, vals8, scales, n_slots,
                                       B,
                                       interpret=(use_pallas == "interpret"),
                                       hist_chunk=hist_chunk)
    return _build_hist_nodes_xla(flat_bins, grad, hess, mask, slot,
                                 n_slots, F, B)


def _slot_route_params(feat, tbin, B, bundle_map):
    """Universal routing params for splits chosen on ORIGINAL features.

    Returns (col, t1, rlo, rhi, dflt): rows of column ``col`` go left iff
    ``x in (rlo, rhi] ? x <= t1 : dflt``.  Plain training routes the
    feature's own column with the full range, so the condition degrades to
    ``x <= tbin``; under EFB the split feature's BUNDLED range maps the
    original-bin threshold onto the bundled column (rank(b) = b +
    (b < default) — binning.py FeatureBundler.route_tables), and
    out-of-range rows (feature at its default bin) take the default-bin
    direction.  One formula, so the pallas kernel and every XLA routing
    path stay identical between plain and EFB training."""
    if bundle_map is None:
        return (feat, tbin, jnp.full_like(feat, -1),
                jnp.full_like(feat, B), jnp.ones_like(feat))
    col = bundle_map["col"][feat]
    lo = bundle_map["lo"][feat]
    hi = bundle_map["hi"][feat]
    d = bundle_map["default_bin"][feat]
    t1 = lo + tbin + (tbin < d).astype(tbin.dtype)
    dflt = (d <= tbin).astype(jnp.int32)
    return col, t1, lo, hi, dflt


def _route_left(xb, t1, rlo, rhi, dflt):
    in_range = (xb > rlo) & (xb <= rhi)
    return jnp.where(in_range, xb <= t1, dflt != 0)


def _unbundle_hists(hists, gather_src, tot):
    """Bundled histograms (..., Fb, Bb, 3) → ORIGINAL-feature histograms
    (..., F, B, 3) by static gather; a feature's DEFAULT bin carries the
    residual node mass (rows default in f sit at bundled bin 0 or inside
    other features' ranges).  Exact for exclusive bundles — which is why
    EFB training grows the BIT-IDENTICAL tree to unbundled training while
    the data pass stays compressed (the LightGBM scheme: EFB accelerates
    histogram construction, trees never leave original feature space).

    ``tot``: node totals (..., 3) [grad, hess, count]."""
    lead = hists.shape[:-3]
    F, B = gather_src.shape
    flat = hists.reshape(lead + (-1, 3))
    V = jnp.take(flat, jnp.maximum(gather_src, 0).reshape(-1), axis=-2)
    V = V.reshape(lead + (F, B, 3))
    V = jnp.where((gather_src >= 0)[..., None], V, 0.0)
    resid = tot[..., None, None, :] - jnp.sum(V, axis=-2, keepdims=True)
    return jnp.where((gather_src == -2)[..., None], resid, V)


def default_n_slots(num_leaves: int) -> int:
    """Node slots per wave: 16 slots × 8 value channels = the full 128-lane
    MXU tile; fewer when the leaf budget is smaller."""
    return max(1, min(16, num_leaves - 1))


@functools.partial(jax.jit, static_argnames=("p", "axis_name", "use_pallas",
                                             "n_slots", "cconfig"))
def grow_tree_depthwise(bins_t: jnp.ndarray,     # (F, N) int32
                        grad: jnp.ndarray,       # (N,) f32
                        hess: jnp.ndarray,       # (N,) f32
                        row_valid: jnp.ndarray,  # (N,) f32 bag/GOSS weight
                        feature_mask: jnp.ndarray,   # (F,) bool
                        upper_bounds: jnp.ndarray,   # (F, B-1) f32
                        num_bins: jnp.ndarray,       # (F,) int32
                        learning_rate: float,
                        p: GrowthParams,
                        axis_name: Optional[str] = None,
                        use_pallas: bool = False,
                        n_slots: int = 16,
                        bundle_map: Optional[dict] = None,
                        cconfig=None,
                        ) -> Tuple[Tree, jnp.ndarray]:
    """Grow one tree wave-by-wave; returns (tree, per-row leaf node ids).

    Semantics match :func:`grow_tree` except for the order leaves are split
    in: within a wave all selected leaves split simultaneously, so when the
    leaf budget runs out mid-wave the marginal leaves may differ from strict
    best-first order.  Split decisions per node are identical.

    ``cconfig``: quantized wire for the per-wave histogram psum — see
    :func:`grow_tree`.
    """
    from .pallas_hist import prep_hist_vals_rows

    F, N = bins_t.shape
    B = p.total_bins
    L = p.num_leaves
    M = max_nodes(L)
    S = n_slots
    JUNK = M - 1              # node index never reached (num_nodes <= M-1)
    HJUNK = L                 # hist-buffer junk slot
    rows = jnp.arange(N)

    def ar(x):
        # same planner dispatch as grow_tree's: planned route when a
        # config is in play, the bare f32 psum trace otherwise
        if not axis_name:
            return x
        return _c_planned_psum(
            x, axis_name, cconfig,
            op="gbdt_hist_psum" if cconfig is not None else "psum")

    # the limbs with their channels on the rows: each of the tree's passes
    # masks them by slot, and tiling (N, 8) limbs along the lanes inside
    # the kernel cost a fifth of a pass
    vals8, scales = (prep_hist_vals_rows(grad, hess, row_valid)
                     if use_pallas else (None, None))

    F_search = num_bins.shape[0]           # ORIGINAL feature count
    mono_c = _mono_vec(p, F_search)

    # two-level (coarse-then-refine) histograms and, on the pallas path,
    # the fused pass's tile: one decision, see depthwise_hist_plan
    from .pallas_hist import coarse_bins
    plan = depthwise_hist_plan(F, N, p, S, bundled=bundle_map is not None,
                               use_pallas=bool(use_pallas))
    tl = plan["two_level"]
    _tl_gauge("depthwise", tl)
    SH = TWO_LEVEL_SHIFT
    Bc = coarse_bins(B, SH)
    Bh = Bc if tl else B                   # stored-histogram width
    K = p.refine_k
    num_bins_c = -(-num_bins // (1 << SH))

    flat_bins = None
    bins_pl = bins_t
    if not use_pallas:
        flat_bins = bins_t + (jnp.arange(F, dtype=jnp.int32) * B)[:, None]
    else:
        # the fused pass's (G, ft, N) layout, ONCE per tree: a view when
        # one feature group holds the tile (two-level at 28 x 256), else
        # a copy that XLA would re-materialize every level inside the
        # wave loop's cond
        from .pallas_hist import feature_tiles
        assert plan["ft"], (
            f"fused kernel does not fit VMEM at F={F}, B={B}, S={S}; the "
            "caller must gate on fused_geometry(...)")
        bins_pl = feature_tiles(bins_t, plan["ft"])

    def build(slot):
        # the XLA path's histograms: on the pallas path the root and
        # every wave ride the fused pass
        return ar(_build_hist_nodes_xla(flat_bins, grad, hess, row_valid,
                                        slot, S, F, B))

    def unb(hists, g, h, c):
        if bundle_map is None:
            return hists
        return _unbundle_hists(hists, bundle_map["gather_src"],
                               jnp.stack([g, h, c], -1))

    pick = functools.partial(_best_split, num_bins=num_bins,
                             feature_mask=feature_mask, p=p, mono_c=mono_c)
    vpick = jax.vmap(lambda h, g, hh, c, d, lo, hi: pick(
        h, g, hh, c, node_depth=d, node_lo=lo, node_hi=hi))

    def build_fine_k(bins_kp, slot_vec, n_slots_):
        """Full-resolution histograms of the refined features for the
        two-level refine pass.  ``bins_kp`` is the PRE-GATHERED and
        pre-tiled (pallas) / pre-flattened (XLA) K-feature bin matrix —
        prepared once per tree right after the root picks ``topk`` so the
        wave loop never re-materializes the copy (XLA cannot hoist it out
        of while_loop)."""
        if use_pallas:
            from .pallas_hist import build_hist_nodes_pallas
            return build_hist_nodes_pallas(
                bins_kp, slot_vec, vals8, scales, n_slots_, B,
                interpret=(use_pallas == "interpret"),
                hist_chunk=p.hist_chunk)
        return _build_hist_nodes_xla(bins_kp, grad, hess, row_valid,
                                     slot_vec, n_slots_, K, B)

    # root: one batched pass with every row in slot 0.  On the pallas path
    # this rides the FUSED kernel with a degenerate all-left split of leaf 0
    # on column 0 (t1=B → every row left whatever its value, child id 0 →
    # node ids unchanged): the fused kernel computes its slot mask once per
    # chunk instead of once per (feature-tile, chunk) step (an earlier
    # round's reading, 1M x 28 on another chip: some 25% under the nodes
    # kernel for the same histograms)
    if use_pallas:
        from .pallas_hist import route_and_hist_pallas
        jv = jnp.full((S,), JUNK, jnp.int32)
        _, root_hists = route_and_hist_pallas(
            bins_pl, jnp.zeros(N, jnp.int32), jv.at[0].set(0),
            jnp.zeros(S, jnp.int32), jnp.full((S,), B, jnp.int32),
            jnp.full((S,), -1, jnp.int32), jnp.full((S,), B, jnp.int32),
            jnp.ones(S, jnp.int32), jnp.zeros(S, jnp.int32),
            jnp.zeros(S, jnp.int32), vals8, scales, S, B,
            hist_shift=(SH if tl else 0),
            interpret=(use_pallas == "interpret"),
            hist_chunk=p.hist_chunk)
        root_hist = ar(root_hists)[0]                      # (F, Bh, 3)
    else:
        root_hist = build(jnp.zeros(N, jnp.int32))[0]      # (F, B, 3)
        if tl:
            root_hist = _pool_coarse(root_hist, Bc, SH)
    root_stats = jnp.sum(root_hist[0], axis=0)
    root_g, root_h, root_c = root_stats[0], root_stats[1], root_stats[2]

    zi = jnp.zeros(M, jnp.int32)
    zf = jnp.zeros(M, jnp.float32)
    topk = None
    root_fine = None
    if tl:
        # the refined feature set is chosen ONCE per tree from the ROOT's
        # coarse per-feature gains: a fixed set lets every wave refine
        # LEFT children only (S slot lanes, the full 128-lane tile) and
        # derive right-child fine histograms by subtraction from the
        # parent's stored fine-K histograms — a per-wave adaptive set
        # needs both children built fresh (2S lanes), which doubles the
        # refine matmul and was measured to eat the coarse pass's win
        rslot0 = jnp.where(row_valid > 0, 0, -1).astype(jnp.int32)
        topk, sel_k, bins_kp, root_fine, rbest0 = _tl_root_pick(
            root_hist, root_g, root_h, root_c, num_bins, num_bins_c,
            feature_mask, p, SH, K, bins_t, B, use_pallas,
            lambda bkp: build_fine_k(bkp, rslot0, 1), ar)
        bg, bf_, bb, bgl, bhl, bcl = rbest0
    else:
        bg, bf_, bb, bgl, bhl, bcl = pick(
            unb(root_hist, root_g, root_h, root_c),
            root_g, root_h, root_c,
            node_depth=jnp.zeros((), jnp.int32),
            node_lo=-jnp.inf, node_hi=jnp.inf)
    state = dict(
        node_id=jnp.zeros(N, jnp.int32),
        hist=jnp.zeros((L + 2, F * Bh, 3), jnp.float32).at[0].set(
            root_hist.reshape(F * Bh, 3)),
        slot=zi,
        sum_g=zf.at[0].set(root_g),
        sum_h=zf.at[0].set(root_h),
        sum_c=zf.at[0].set(root_c),
        depth=zi,
        best_gain=jnp.full(M, -jnp.inf, jnp.float32).at[0].set(bg),
        best_feat=zi.at[0].set(bf_), best_bin=zi.at[0].set(bb),
        best_gl=zf.at[0].set(bgl), best_hl=zf.at[0].set(bhl),
        best_cl=zf.at[0].set(bcl),
        active=jnp.zeros(M, jnp.bool_).at[0].set(True),
        split_feature=jnp.full(M, -1, jnp.int32),
        split_bin=zi,
        split_gain=zf,
        threshold=zf,
        left_child=jnp.full(M, -1, jnp.int32),
        right_child=jnp.full(M, -1, jnp.int32),
        num_nodes=jnp.ones((), jnp.int32),
        next_slot=jnp.ones((), jnp.int32),
        node_lo=jnp.full(M, -jnp.inf, jnp.float32),
        node_hi=jnp.full(M, jnp.inf, jnp.float32),
    )
    if tl:
        state["hist_f"] = jnp.zeros((L + 2, K * B, 3), jnp.float32).at[
            0].set(root_fine[0].reshape(K * B, 3))

    def cond(s):
        leaves = (s["num_nodes"] + 1) // 2
        gains = jnp.where(s["active"], s["best_gain"], -jnp.inf)
        return (leaves < L) & (jnp.max(gains) > p.min_gain_to_split)

    def wave(s):
        gains = jnp.where(s["active"], s["best_gain"], -jnp.inf)
        tv, ti = lax.top_k(gains, S)                     # leaves to split
        budget = L - (s["num_nodes"] + 1) // 2
        jidx = jnp.arange(S, dtype=jnp.int32)
        valid = (tv > p.min_gain_to_split) & (jidx < budget)
        n_valid = jnp.sum(valid.astype(jnp.int32))
        parents = jnp.where(valid, ti, JUNK)

        # valid slots are packed first by top_k's sort, so child ids are
        # contiguous: left 2j, right 2j+1 past num_nodes
        l_ids = jnp.where(valid, s["num_nodes"] + 2 * jidx, JUNK)
        r_ids = jnp.where(valid, s["num_nodes"] + 2 * jidx + 1, JUNK)

        # route rows (new node id + histogram slot; JUNK parents match no
        # row) and build every selected leaf's left-child histogram in ONE
        # pass over the binned matrix — the fused kernel computes each
        # chunk's routing once and keeps it in VMEM for the histogram tiles
        rt_col, rt_t1, rt_lo, rt_hi, rt_df = _slot_route_params(
            s["best_feat"][parents], s["best_bin"][parents], B, bundle_map)
        leaves_after = (s["num_nodes"] + 1) // 2 + n_valid
        lf = None
        if use_pallas:
            from .pallas_hist import route_and_hist_pallas, route_rows_pallas

            # the split columns go by index, never copied: each kernel
            # reads them where they lie in the binned matrix
            def fused_wave(_):
                out = route_and_hist_pallas(
                    bins_pl, s["node_id"], parents, rt_col, rt_t1, rt_lo,
                    rt_hi, rt_df, l_ids, r_ids, vals8, scales, S, B,
                    hist_shift=(SH if tl else 0),
                    sel_k=(sel_k if tl else None),
                    interpret=(use_pallas == "interpret"),
                    hist_chunk=p.hist_chunk)
                # under tl the SAME pass also emits the refined features'
                # full-resolution left-child histograms (one bins read,
                # one routing, one slot-masked value build for both
                # levels — a separate refine pass cost ~2.8 ms/wave)
                return out if tl else out + (jnp.zeros(0, jnp.float32),)

            def route_only(_):
                # this wave fills the leaf budget: its child histograms can
                # never feed another split, so skip the one-hot pass (one of
                # five full-data passes per 31-leaf tree) and route alone:
                # the fused pass's routing in one pass over the matrix and
                # node_id.  Child pick stats (sum_g/h/c) come from the
                # parent pick, not from these histograms, so zeros are safe.
                new = route_rows_pallas(
                    bins_pl, s["node_id"], parents, rt_col, rt_t1, rt_lo,
                    rt_hi, rt_df, l_ids, r_ids,
                    interpret=(use_pallas == "interpret"))
                zf_ = (jnp.zeros((S, K, B, 3), jnp.float32) if tl
                       else jnp.zeros(0, jnp.float32))
                return new, jnp.zeros((S, F, Bh, 3), jnp.float32), zf_

            new_node_id, l_hists, lf = lax.cond(leaves_after >= L,
                                                route_only, fused_wave,
                                                None)
            l_hists = ar(l_hists)
            if tl:
                lf = ar(lf)
        else:
            slot_of_leaf = jnp.full(M, -1, jnp.int32).at[parents].set(
                jnp.where(valid, jidx, -1))
            rslot = slot_of_leaf[s["node_id"]]           # (N,)
            safe = jnp.maximum(rslot, 0)
            go_left = _route_left(bins_t[rt_col[safe], rows], rt_t1[safe],
                                  rt_lo[safe], rt_hi[safe], rt_df[safe])
            new_node_id = jnp.where(
                rslot >= 0,
                jnp.where(go_left, l_ids[rslot], r_ids[rslot]),
                s["node_id"])
            bslot = jnp.where(go_left, rslot, -1)
            l_hists = build(bslot)                       # (S, F, B, 3)
            if tl:
                l_hists = _pool_coarse(l_hists, Bc, SH)
        l_flat = l_hists.reshape(S, F * Bh, 3)
        pslot = jnp.where(valid, s["slot"][parents], HJUNK)
        r_flat = s["hist"][pslot] - l_flat
        r_slots = jnp.where(valid, s["next_slot"] + jidx, HJUNK)
        hist = s["hist"].at[pslot].set(l_flat).at[r_slots].set(r_flat)

        lg, lh, lc = (s["best_gl"][parents], s["best_hl"][parents],
                      s["best_cl"][parents])
        rg = s["sum_g"][parents] - lg
        rh = s["sum_h"][parents] - lh
        rc = s["sum_c"][parents] - lc
        cdepth = s["depth"][parents] + 1

        p_lo, p_hi = s["node_lo"][parents], s["node_hi"][parents]   # (S,)
        l_lo, l_hi, r_lo, r_hi = _mono_node_bounds(
            None if mono_c is None else mono_c[s["best_feat"][parents]],
            p_lo, p_hi, lg, lh, rg, rh, p)
        c_lo = jnp.concatenate([l_lo, r_lo])
        c_hi = jnp.concatenate([l_hi, r_hi])

        child_hists = jnp.concatenate(
            [l_flat.reshape(S, F, Bh, 3), r_flat.reshape(S, F, Bh, 3)])
        cg = jnp.concatenate([lg, rg])
        ch = jnp.concatenate([lh, rh])
        cc = jnp.concatenate([lc, rc])
        cd = jnp.concatenate([cdepth, cdepth])
        if tl:
            cgm, ccum, _ = _tl_coarse_gains(
                child_hists, cg, ch, cc, cd, c_lo, c_hi,
                num_bins_c, feature_mask, p)
            if lf is None:
                # XLA fallback: the fused kernel isn't in play, so the
                # refine histograms need their own (budget-gated) build
                lslot = (jnp.full(M, -1, jnp.int32)
                         .at[l_ids].set(jidx).at[JUNK].set(-1))

                def fine(_):
                    return build_fine_k(bins_kp, lslot[new_node_id], S)

                def fine_zeros(_):
                    # budget-filling wave: the children never split
                    # again — skip like the coarse route_only shortcut
                    # (zero hists fail min_data and pick -inf)
                    return jnp.zeros((S, K, B, 3), jnp.float32)

                lf = ar(lax.cond(leaves_after >= L, fine_zeros, fine,
                                 None))
            lf_flat = lf.reshape(S, K * B, 3)
            rf_flat = s["hist_f"][pslot] - lf_flat
            f_hists = jnp.concatenate([lf_flat.reshape(S, K, B, 3),
                                       rf_flat.reshape(S, K, B, 3)])
            cbg, cbf, cbb, cbgl, cbhl, cbcl = _tl_final_pick(
                cgm, ccum, f_hists, topk, cg, ch, cc, cd, c_lo, c_hi,
                num_bins, feature_mask, p, SH)
        else:
            cbg, cbf, cbb, cbgl, cbhl, cbcl = vpick(
                unb(child_hists, cg, ch, cc), cg, ch, cc, cd, c_lo, c_hi)

        cids = jnp.concatenate([l_ids, r_ids])           # (2S,)
        thr = jnp.where(s["best_bin"][parents] >= 1,
                        upper_bounds[s["best_feat"][parents],
                                     jnp.maximum(s["best_bin"][parents] - 1, 0)],
                        -jnp.inf)

        out = dict(
            node_id=new_node_id,
            hist=hist,
            slot=s["slot"].at[l_ids].set(pslot).at[r_ids].set(r_slots),
            sum_g=s["sum_g"].at[cids].set(cg),
            sum_h=s["sum_h"].at[cids].set(ch),
            sum_c=s["sum_c"].at[cids].set(cc),
            depth=s["depth"].at[cids].set(cd),
            best_gain=s["best_gain"].at[cids].set(cbg),
            best_feat=s["best_feat"].at[cids].set(cbf),
            best_bin=s["best_bin"].at[cids].set(cbb),
            best_gl=s["best_gl"].at[cids].set(cbgl),
            best_hl=s["best_hl"].at[cids].set(cbhl),
            best_cl=s["best_cl"].at[cids].set(cbcl),
            active=s["active"].at[parents].set(False).at[cids].set(True),
            split_feature=s["split_feature"].at[parents].set(
                jnp.where(valid, s["best_feat"][parents], -1)),
            split_bin=s["split_bin"].at[parents].set(s["best_bin"][parents]),
            split_gain=s["split_gain"].at[parents].set(
                jnp.where(valid, s["best_gain"][parents], 0.0)),
            threshold=s["threshold"].at[parents].set(thr),
            left_child=s["left_child"].at[parents].set(l_ids),
            right_child=s["right_child"].at[parents].set(r_ids),
            num_nodes=s["num_nodes"] + 2 * n_valid,
            next_slot=s["next_slot"] + n_valid,
            node_lo=s["node_lo"].at[cids].set(c_lo),
            node_hi=s["node_hi"].at[cids].set(c_hi),
        )
        if tl:
            out["hist_f"] = (s["hist_f"].at[pslot].set(lf_flat)
                             .at[r_slots].set(rf_flat))
        if mono_c is not None and p.monotone_method in ("intermediate",
                                                        "advanced"):
            # whole-tree refresh (opposite-subtree extremes, or the exact
            # pairwise set for advanced); this wave's children re-pick
            # under the refreshed (looser-than-midpoint) bounds
            out = _refresh_intermediate(out, mono_c, p)
            cbg2, cbf2, cbb2, cbgl2, cbhl2, cbcl2 = vpick(
                unb(child_hists, cg, ch, cc), cg, ch, cc, cd,
                out["node_lo"][cids], out["node_hi"][cids])
            out["best_gain"] = out["best_gain"].at[cids].set(cbg2)
            out["best_feat"] = out["best_feat"].at[cids].set(cbf2)
            out["best_bin"] = out["best_bin"].at[cids].set(cbb2)
            out["best_gl"] = out["best_gl"].at[cids].set(cbgl2)
            out["best_hl"] = out["best_hl"].at[cids].set(cbhl2)
            out["best_cl"] = out["best_cl"].at[cids].set(cbcl2)
        # the junk row absorbed every masked-out write; scrub it
        out["active"] = out["active"].at[JUNK].set(False)
        out["best_gain"] = out["best_gain"].at[JUNK].set(-jnp.inf)
        out["split_feature"] = out["split_feature"].at[JUNK].set(-1)
        out["left_child"] = out["left_child"].at[JUNK].set(-1)
        out["right_child"] = out["right_child"].at[JUNK].set(-1)
        return out

    state = lax.while_loop(cond, wave, state)

    node_value = _leaf_output(state["sum_g"], state["sum_h"],
                              p.lambda_l1, p.lambda_l2)
    if mono_c is not None:
        if p.monotone_method in ("intermediate", "advanced"):
            _, _, node_value = _tree_bounds(
                state["split_feature"], state["split_bin"],
                state["left_child"], state["right_child"], node_value,
                mono_c, p, n_iters=6)
        else:
            node_value = jnp.clip(node_value, state["node_lo"],
                                  state["node_hi"])
    node_value = learning_rate * node_value
    leaf_value = jnp.where(state["left_child"] < 0, node_value, 0.0)
    tree = Tree(split_feature=state["split_feature"],
                split_bin=state["split_bin"],
                threshold=state["threshold"],
                split_gain=state["split_gain"],
                left_child=state["left_child"],
                right_child=state["right_child"],
                leaf_value=leaf_value,
                node_value=node_value,
                num_nodes=state["num_nodes"],
                default_left=jnp.ones(M, jnp.bool_),
                node_count=state["sum_c"],
                missing_zero=jnp.zeros(M, jnp.bool_))
    return tree, state["node_id"]


# -- feature-parallel growth -------------------------------------------------
#
# LightGBM's ``tree_learner=feature_parallel`` (vertical partitioning; the
# reference only passes the string through to native code,
# params/BaseTrainParams.scala:99): every worker holds ALL rows but only a
# SLICE of the features.  Histograms never cross the interconnect — each
# rank scans its own feature columns, local best splits ride one tiny
# all-gather, and the winning split's owner broadcasts the row routing via
# a psum of owner-exclusive masks.  Communication per wave is O(S·N) bits
# + O(ranks·S) floats instead of O(F·B) histograms — the right trade when
# features outnumber rows.


@functools.partial(jax.jit, static_argnames=("p", "axis_name", "use_pallas",
                                             "n_slots"))
def grow_tree_feature_parallel(
        bins_t: jnp.ndarray,          # (F_local, N) int32 — THIS RANK's slice
        grad: jnp.ndarray,            # (N,) f32 replicated
        hess: jnp.ndarray,            # (N,) f32 replicated
        row_valid: jnp.ndarray,       # (N,) f32 replicated
        feature_mask: jnp.ndarray,    # (F_local,) bool
        upper_bounds: jnp.ndarray,    # (F_local, B-1) f32
        num_bins: jnp.ndarray,        # (F_local,) int32
        learning_rate: float,
        p: GrowthParams,
        axis_name: str,
        use_pallas: bool = False,
        n_slots: int = 16,
        bundle_map: Optional[dict] = None,
) -> Tuple[Tree, jnp.ndarray]:
    """Depth-level growth with the FEATURE axis sharded over ``axis_name``.

    Returns the identical tree on every rank; ``split_feature`` carries
    GLOBAL feature ids (rank · F_local + local id).  Semantics match
    :func:`grow_tree_depthwise` on the unsharded data.

    Under EFB, ``bins_t`` holds THIS RANK's bundled columns (each rank
    bundles its own slice, padded to a common width) and ``bundle_map``
    its route tables: local histograms unbundle before every pick, and
    the owner routes splits through the universal routing form — trees
    stay in ORIGINAL (global) feature space exactly like the other
    growers' EFB paths.
    """
    from .pallas_hist import prep_hist_vals

    FL, N = bins_t.shape              # bundled column count under EFB
    F_loc = num_bins.shape[0]         # ORIGINAL features on this rank
    B = p.total_bins
    L = p.num_leaves
    M = max_nodes(L)
    S = n_slots
    JUNK = M - 1
    rank = lax.axis_index(axis_name)

    vals8, scales = (prep_hist_vals(grad, hess, row_valid) if use_pallas
                     else (None, None))
    flat_bins = None
    bins_pl = bins_t
    if not use_pallas:
        flat_bins = bins_t + (jnp.arange(FL, dtype=jnp.int32) * B)[:, None]
    else:
        from .pallas_hist import prepare_feature_tiles
        bins_pl = prepare_feature_tiles(bins_t, B, FL)

    def build(slot):
        # LOCAL histograms only — the defining property of feature-parallel
        return _build_hist_nodes(bins_pl, flat_bins, vals8, scales, grad,
                                 hess, row_valid, slot, S, FL, B, use_pallas,
                                 hist_chunk=p.hist_chunk)

    # constraints come from the static tuple in p, so the GLOBAL vector is
    # available on every rank; each rank's gain pass slices its own span
    n_ranks = lax.axis_size(axis_name)
    mono_global = _mono_vec(p, F_loc * n_ranks)
    mono_local = (None if mono_global is None else
                  lax.dynamic_slice(mono_global, (rank * F_loc,), (F_loc,)))

    def pick_local(hist, g, h, c, depth, lo, hi):
        if bundle_map is not None:
            # unbundle this rank's LOCAL bundled histograms to its
            # original features before the gain pass (the same linearity
            # the voting pick leans on)
            hist = _unbundle_hists(hist, bundle_map["gather_src"],
                                   jnp.stack([g, h, c], -1))
        return _best_split(hist, g, h, c, num_bins, feature_mask, depth, p,
                           lo, hi, mono_local)

    def global_pick(hist_s, g, h, c, depth, lo, hi):
        """Per-node: local best over this rank's features, then a tiny
        all-gather picks the winner; returns global feature ids and the
        owner's raw-value threshold."""
        bg, bf_, bb, bgl, bhl, bcl = pick_local(hist_s, g, h, c, depth,
                                                lo, hi)
        thr = jnp.where(bb >= 1, upper_bounds[bf_, jnp.maximum(bb - 1, 0)],
                        -jnp.inf)
        packed = jnp.stack([bg, (rank * F_loc + bf_).astype(jnp.float32),
                            bb.astype(jnp.float32), bgl, bhl, bcl, thr])
        allp = lax.all_gather(packed, axis_name)           # (ranks, 7)
        win = jnp.argmax(allp[:, 0])
        wg, wf, wb, wgl, whl, wcl, wthr = (allp[win, i] for i in range(7))
        return (wg, wf.astype(jnp.int32), wb.astype(jnp.int32),
                wgl, whl, wcl, wthr)

    # root: stats directly from grad/hess (no rank owns every feature)
    root_g = jnp.sum(grad * row_valid)
    root_h = jnp.sum(hess * row_valid)
    root_c = jnp.sum((row_valid > 0).astype(jnp.float32))
    root_hist = build(jnp.zeros(N, jnp.int32))[0]

    zi = jnp.zeros(M, jnp.int32)
    zf = jnp.zeros(M, jnp.float32)
    bg, bf_, bb, bgl, bhl, bcl, bthr = global_pick(
        root_hist, root_g, root_h, root_c, jnp.zeros((), jnp.int32),
        -jnp.inf, jnp.inf)
    state = dict(
        node_id=jnp.zeros(N, jnp.int32),
        hist=jnp.zeros((L + 2, FL * B, 3), jnp.float32).at[0].set(
            root_hist.reshape(FL * B, 3)),
        slot=zi,
        sum_g=zf.at[0].set(root_g),
        sum_h=zf.at[0].set(root_h),
        sum_c=zf.at[0].set(root_c),
        depth=zi,
        best_gain=jnp.full(M, -jnp.inf, jnp.float32).at[0].set(bg),
        best_feat=zi.at[0].set(bf_), best_bin=zi.at[0].set(bb),
        best_gl=zf.at[0].set(bgl), best_hl=zf.at[0].set(bhl),
        best_cl=zf.at[0].set(bcl),
        best_thr=zf.at[0].set(bthr),
        active=jnp.zeros(M, jnp.bool_).at[0].set(True),
        split_feature=jnp.full(M, -1, jnp.int32),
        split_bin=zi,
        split_gain=zf,
        threshold=zf,
        left_child=jnp.full(M, -1, jnp.int32),
        right_child=jnp.full(M, -1, jnp.int32),
        num_nodes=jnp.ones((), jnp.int32),
        next_slot=jnp.ones((), jnp.int32),
        node_lo=jnp.full(M, -jnp.inf, jnp.float32),
        node_hi=jnp.full(M, jnp.inf, jnp.float32),
    )

    def cond(s):
        leaves = (s["num_nodes"] + 1) // 2
        gains = jnp.where(s["active"], s["best_gain"], -jnp.inf)
        return (leaves < L) & (jnp.max(gains) > p.min_gain_to_split)

    def wave(s):
        gains = jnp.where(s["active"], s["best_gain"], -jnp.inf)
        tv, ti = lax.top_k(gains, S)
        budget = L - (s["num_nodes"] + 1) // 2
        jidx = jnp.arange(S, dtype=jnp.int32)
        valid = (tv > p.min_gain_to_split) & (jidx < budget)
        n_valid = jnp.sum(valid.astype(jnp.int32))
        parents = jnp.where(valid, ti, JUNK)
        l_ids = jnp.where(valid, s["num_nodes"] + 2 * jidx, JUNK)
        r_ids = jnp.where(valid, s["num_nodes"] + 2 * jidx + 1, JUNK)

        # owner-exclusive routing: this rank contributes the go-left mask
        # only for slots whose winning feature lives in its slice; one psum
        # assembles every slot's mask on every rank.  Routing goes through
        # the universal form so plain and EFB splits share one path
        wf = s["best_feat"][parents]                        # (S,) global ids
        wb = s["best_bin"][parents]
        owner = wf // F_loc
        floc = jnp.clip(wf - rank * F_loc, 0, F_loc - 1)
        mine = (owner == rank) & valid
        col_s, t1_s, lo_s, hi_s, df_s = _slot_route_params(
            floc, wb, B, bundle_map)
        local_gl = _route_left(bins_t[col_s, :], t1_s[:, None],
                               lo_s[:, None], hi_s[:, None],
                               df_s[:, None])               # (S, N)
        gl_slots = lax.psum(
            jnp.where(mine[:, None], local_gl, False).astype(jnp.int8),
            axis_name) > 0                                  # (S, N) bool

        slot_of_leaf = jnp.full(M, -1, jnp.int32).at[parents].set(
            jnp.where(valid, jidx, -1))
        rslot = slot_of_leaf[s["node_id"]]                  # (N,)
        go_left = jnp.take_along_axis(
            gl_slots, jnp.clip(rslot, 0)[None, :], axis=0)[0]
        new_node_id = jnp.where(
            rslot >= 0,
            jnp.where(go_left, l_ids[rslot], r_ids[rslot]),
            s["node_id"])
        bslot = jnp.where(go_left, rslot, -1)

        l_hists = build(bslot)                              # (S, FL, B, 3)
        l_flat = l_hists.reshape(S, FL * B, 3)
        pslot = jnp.where(valid, s["slot"][parents], L)
        r_flat = s["hist"][pslot] - l_flat
        r_slots = jnp.where(valid, s["next_slot"] + jidx, L)
        hist = s["hist"].at[pslot].set(l_flat).at[r_slots].set(r_flat)

        lg = s["best_gl"][parents]
        lh = s["best_hl"][parents]
        lc = s["best_cl"][parents]
        rg = s["sum_g"][parents] - lg
        rh = s["sum_h"][parents] - lh
        rc = s["sum_c"][parents] - lc
        cdepth = s["depth"][parents] + 1

        p_lo, p_hi = s["node_lo"][parents], s["node_hi"][parents]   # (S,)
        l_lo, l_hi, r_lo, r_hi = _mono_node_bounds(
            None if mono_global is None else mono_global[wf],
            p_lo, p_hi, lg, lh, rg, rh, p)
        c_lo = jnp.concatenate([l_lo, r_lo])
        c_hi = jnp.concatenate([l_hi, r_hi])

        child_hists = jnp.concatenate(
            [l_flat.reshape(S, FL, B, 3), r_flat.reshape(S, FL, B, 3)])
        cg = jnp.concatenate([lg, rg])
        ch = jnp.concatenate([lh, rh])
        cc = jnp.concatenate([lc, rc])
        cd = jnp.concatenate([cdepth, cdepth])
        vg = jax.vmap(global_pick)(child_hists, cg, ch, cc, cd, c_lo, c_hi)
        cbg, cbf, cbb, cbgl, cbhl, cbcl, cbthr = vg

        cids = jnp.concatenate([l_ids, r_ids])
        out = dict(
            node_id=new_node_id,
            hist=hist,
            slot=s["slot"].at[l_ids].set(pslot).at[r_ids].set(r_slots),
            sum_g=s["sum_g"].at[cids].set(cg),
            sum_h=s["sum_h"].at[cids].set(ch),
            sum_c=s["sum_c"].at[cids].set(cc),
            depth=s["depth"].at[cids].set(cd),
            best_gain=s["best_gain"].at[cids].set(cbg),
            best_feat=s["best_feat"].at[cids].set(cbf),
            best_bin=s["best_bin"].at[cids].set(cbb),
            best_gl=s["best_gl"].at[cids].set(cbgl),
            best_hl=s["best_hl"].at[cids].set(cbhl),
            best_cl=s["best_cl"].at[cids].set(cbcl),
            best_thr=s["best_thr"].at[cids].set(cbthr),
            active=s["active"].at[parents].set(False).at[cids].set(True),
            split_feature=s["split_feature"].at[parents].set(
                jnp.where(valid, s["best_feat"][parents], -1)),
            split_bin=s["split_bin"].at[parents].set(s["best_bin"][parents]),
            split_gain=s["split_gain"].at[parents].set(
                jnp.where(valid, s["best_gain"][parents], 0.0)),
            threshold=s["threshold"].at[parents].set(s["best_thr"][parents]),
            left_child=s["left_child"].at[parents].set(l_ids),
            right_child=s["right_child"].at[parents].set(r_ids),
            num_nodes=s["num_nodes"] + 2 * n_valid,
            next_slot=s["next_slot"] + n_valid,
            node_lo=s["node_lo"].at[cids].set(c_lo),
            node_hi=s["node_hi"].at[cids].set(c_hi),
        )
        if mono_global is not None and p.monotone_method in ("intermediate",
                                                             "advanced"):
            # the whole-tree refresh runs REPLICATED: tree arrays and
            # sums are identical on every rank (splits are globally
            # agreed), and the constraint vector is the static global
            # tuple — so each rank recomputes the same bounds and the
            # re-pick goes through global_pick's all_gather like any
            # other pick
            out = _refresh_intermediate(out, mono_global, p)
            vg2 = jax.vmap(global_pick)(child_hists, cg, ch, cc, cd,
                                        out["node_lo"][cids],
                                        out["node_hi"][cids])
            cbg2, cbf2, cbb2, cbgl2, cbhl2, cbcl2, cbthr2 = vg2
            out["best_gain"] = out["best_gain"].at[cids].set(cbg2)
            out["best_feat"] = out["best_feat"].at[cids].set(cbf2)
            out["best_bin"] = out["best_bin"].at[cids].set(cbb2)
            out["best_gl"] = out["best_gl"].at[cids].set(cbgl2)
            out["best_hl"] = out["best_hl"].at[cids].set(cbhl2)
            out["best_cl"] = out["best_cl"].at[cids].set(cbcl2)
            out["best_thr"] = out["best_thr"].at[cids].set(cbthr2)
        out["active"] = out["active"].at[JUNK].set(False)
        out["best_gain"] = out["best_gain"].at[JUNK].set(-jnp.inf)
        out["split_feature"] = out["split_feature"].at[JUNK].set(-1)
        out["left_child"] = out["left_child"].at[JUNK].set(-1)
        out["right_child"] = out["right_child"].at[JUNK].set(-1)
        return out

    state = lax.while_loop(cond, wave, state)

    node_value = _leaf_output(state["sum_g"], state["sum_h"],
                              p.lambda_l1, p.lambda_l2)
    if mono_global is not None:
        if p.monotone_method in ("intermediate", "advanced"):
            _, _, node_value = _tree_bounds(
                state["split_feature"], state["split_bin"],
                state["left_child"], state["right_child"], node_value,
                mono_global, p, n_iters=6)
        else:
            node_value = jnp.clip(node_value, state["node_lo"],
                                  state["node_hi"])
    node_value = learning_rate * node_value
    leaf_value = jnp.where(state["left_child"] < 0, node_value, 0.0)
    tree = Tree(split_feature=state["split_feature"],
                split_bin=state["split_bin"],
                threshold=state["threshold"],
                split_gain=state["split_gain"],
                left_child=state["left_child"],
                right_child=state["right_child"],
                leaf_value=leaf_value,
                node_value=node_value,
                num_nodes=state["num_nodes"],
                default_left=jnp.ones(M, jnp.bool_),
                node_count=state["sum_c"],
                missing_zero=jnp.zeros(M, jnp.bool_))
    return tree, state["node_id"]


# -- prediction -------------------------------------------------------------

def predict_binned_tree_featpar(bins_local: jnp.ndarray,   # (FL, N) local
                                tree: Tree,                # replicated
                                depth_bound: int,
                                total_bins: int,
                                axis_name: str,
                                bundle_map: Optional[dict] = None):
    """One tree's leaf values over a FEATURE-SHARDED binned matrix — runs
    INSIDE shard_map.  Each traversal step's go-left mask is computed by
    the rank owning the split feature and broadcast with one psum (the
    same owner-exclusive pattern the feature-parallel grower's routing
    uses), so dart rescoring works without gathering the matrix.  Under
    EFB the owner routes through its local route tables (universal
    routing form)."""
    FL, N = bins_local.shape
    F_loc = (bundle_map["col"].shape[0] if bundle_map is not None else FL)
    rank = lax.axis_index(axis_name)
    rows = jnp.arange(N)

    def step(_, node):
        feat = tree.split_feature[node]                  # GLOBAL id
        is_leaf = feat < 0
        f = jnp.maximum(feat, 0)
        owner = f // F_loc
        floc = jnp.clip(f - rank * F_loc, 0, F_loc - 1)
        col, t1, rlo, rhi, dflt = _slot_route_params(
            floc, tree.split_bin[node], total_bins, bundle_map)
        gl_local = _route_left(bins_local[col, rows], t1, rlo, rhi, dflt)
        # int8 like the grower's routing psum: the owner-exclusive 0/1
        # mask sums to at most 1, and int32 would 4x the ICI traffic
        gl = lax.psum(jnp.where(owner == rank,
                                gl_local.astype(jnp.int8),
                                jnp.int8(0)),
                      axis_name) > 0
        child = jnp.where(gl, tree.left_child[node], tree.right_child[node])
        return jnp.where(is_leaf, node, child)

    leaf = lax.fori_loop(0, depth_bound, step, jnp.zeros(N, jnp.int32))
    return tree.leaf_value[leaf]


def _traverse(binned, tree: Tree, depth_bound: int):
    """Vectorized binned-feature traversal: (N, F) → leaf node id (N,)."""
    N = binned.shape[0]
    rows = jnp.arange(N)

    def step(_, node):
        feat = tree.split_feature[node]
        is_leaf = feat < 0
        f = jnp.maximum(feat, 0)
        go_left = binned[rows, f] <= tree.split_bin[node]
        child = jnp.where(go_left, tree.left_child[node], tree.right_child[node])
        return jnp.where(is_leaf, node, child)

    return lax.fori_loop(0, depth_bound, step,
                         jnp.zeros(N, jnp.int32))


@functools.partial(jax.jit, static_argnames=("depth_bound",))
def predict_binned(binned, tree: Tree, depth_bound: int):
    return tree.leaf_value[_traverse(binned, tree, depth_bound)]


@functools.partial(jax.jit, static_argnames=("depth_bound",))
def predict_binned_stacked(binned, trees_stacked: Tree, depth_bound: int):
    """Sum of all trees' outputs on BINNED features (N, F) — the predict
    path for EFB-bundled models, whose splits live in bin space (bundled
    thresholds have no raw-value meaning)."""
    N = binned.shape[0]
    rows = jnp.arange(N)

    def one_tree(carry, t: Tree):
        def step(_, node):
            feat = t.split_feature[node]
            is_leaf = feat < 0
            f = jnp.maximum(feat, 0)
            go_left = binned[rows, f] <= t.split_bin[node]
            child = jnp.where(go_left, t.left_child[node],
                              t.right_child[node])
            return jnp.where(is_leaf, node, child)

        leaf = lax.fori_loop(0, depth_bound, step, jnp.zeros(N, jnp.int32))
        return carry + t.leaf_value[leaf], leaf

    total, leaves = lax.scan(one_tree, jnp.zeros(N, jnp.float32),
                             trees_stacked)
    return total, leaves


@functools.partial(jax.jit, static_argnames=("depth_bound",))
def predict_raw_features(features, trees_stacked: Tree, depth_bound: int):
    """Sum of all trees' outputs on raw float features — the batched
    replacement for the reference's per-row JNI predict
    (LGBM_BoosterPredictForMatSingle, LightGBMBooster.scala:551).

    trees_stacked: a Tree whose arrays carry a leading tree axis (T, M).
    """
    N = features.shape[0]
    rows = jnp.arange(N)

    def one_tree(carry, t: Tree):
        def step(_, node):
            feat = t.split_feature[node]
            is_leaf = feat < 0
            f = jnp.maximum(feat, 0)
            x = features[rows, f]
            # LightGBM kZeroThreshold: missing_type=Zero treats |x|<=1e-35
            # (and NaN, which it coerces to 0) as missing
            missing = jnp.isnan(x) | (t.missing_zero[node]
                                      & (jnp.abs(x) <= 1e-35))
            go_left = jnp.where(missing, t.default_left[node],
                                x <= t.threshold[node])
            child = jnp.where(go_left, t.left_child[node], t.right_child[node])
            return jnp.where(is_leaf, node, child)

        leaf = lax.fori_loop(0, depth_bound, step, jnp.zeros(N, jnp.int32))
        return carry + t.leaf_value[leaf], leaf

    total, leaves = lax.scan(one_tree, jnp.zeros(N, jnp.float32), trees_stacked)
    return total, leaves   # leaves: (T, N) leaf indices (predict_leaf analogue)


def stack_trees(trees) -> Tree:
    return Tree(*[jnp.stack([getattr(t, f) for t in trees])
                  for f in Tree._fields])


def tree_depth(tree: Tree) -> int:
    """Host-side actual depth (for tight traversal bounds)."""
    lc = np.asarray(tree.left_child)
    rc = np.asarray(tree.right_child)
    depth = np.zeros(lc.shape, np.int32)
    out = 0
    for node in range(len(lc)):
        for child in (lc[node], rc[node]):
            if child >= 0:
                depth[child] = depth[node] + 1
                out = max(out, int(depth[child]))
    return out + 1
