"""Two-level (coarse-then-refine) histograms for wide-bin depthwise
growth.

At max_bin=255 the level pass is bounded by the VPU one-hot build; the
two-level mode histograms every wave at coarse (bin >> TWO_LEVEL_SHIFT)
resolution and
refines a root-chosen top-K feature subset at full resolution (left
children built, right children by fine subtraction).  These tests pin:
the XLA and pallas-interpret implementations grow the SAME tree, the
"auto" gate keeps small-data training at exact full resolution, quality
matches full-resolution training, and the coarse kernel's in-kernel
pooling equals pooled fine histograms exactly.

Reference frame: the native engine's histogram construction behind
LGBM_BoosterUpdateOneIter (booster/LightGBMBooster.scala:359) — this is
a TPU-shaped acceleration of the same depthwise search, not a reference
feature; split selection semantics are documented in BoostingConfig.
"""

import numpy as np
import pytest

from synapseml_tpu.models.gbdt import BoostingConfig, train


def _data(n=60_000, F=28, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    logit = (X[:, 0] * 1.2 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
             + 0.8 * np.sin(2 * X[:, 4]) + 0.3 * X[:, 5] ** 2)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logit))).astype(np.float64)
    return X, y


def test_two_level_interpret_matches_xla():
    """grow_tree_depthwise with two_level='on': the pallas kernels
    (interpret mode, coarse fused + fine-K refine) grow the identical
    tree to the XLA fallback (pooled coarse + gathered fine)."""
    import jax.numpy as jnp
    from synapseml_tpu.models.gbdt.trainer import (
        GrowthParams, default_n_slots, grow_tree_depthwise)

    rng = np.random.default_rng(5)
    N, F, B = 8192, 9, 256
    bins_t = rng.integers(0, B, (F, N)).astype(np.int32)
    grad = rng.normal(size=N).astype(np.float32)
    hess = (np.abs(grad) * 0.5 + 0.2).astype(np.float32)
    p = GrowthParams(num_leaves=31, min_data_in_leaf=5.0, total_bins=B,
                     two_level="on", refine_k=4)
    ub = np.sort(rng.normal(size=(F, B - 1)).astype(np.float32), axis=1)
    args = (jnp.asarray(bins_t), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones(N, jnp.float32), jnp.ones(F, bool), jnp.asarray(ub),
            jnp.full(F, B, jnp.int32), 0.1)
    S = default_n_slots(31)
    t_x, nid_x = grow_tree_depthwise(*args, p=p, use_pallas=False,
                                     n_slots=S)
    t_p, nid_p = grow_tree_depthwise(*args, p=p, use_pallas="interpret",
                                     n_slots=S)
    np.testing.assert_array_equal(np.asarray(nid_x), np.asarray(nid_p))
    for f in ("split_feature", "left_child", "right_child", "num_nodes"):
        np.testing.assert_array_equal(np.asarray(getattr(t_x, f)),
                                      np.asarray(getattr(t_p, f)),
                                      err_msg=f)
    for f in ("leaf_value", "node_value", "node_count"):
        np.testing.assert_allclose(np.asarray(getattr(t_x, f)),
                                   np.asarray(getattr(t_p, f)),
                                   rtol=1e-4, atol=1e-4, err_msg=f)


def test_coarse_kernel_equals_pooled_fine():
    """route_and_hist_pallas with hist_shift=2 == the full-resolution
    histograms pooled over each coarse (bin >> 2) group — the in-kernel
    coarse build is exact, not an approximation."""
    import jax.numpy as jnp
    from synapseml_tpu.models.gbdt.pallas_hist import (
        coarse_bins, prep_hist_vals, route_and_hist_pallas)
    from synapseml_tpu.models.gbdt.trainer import _pool_coarse

    rng = np.random.default_rng(3)
    N, F, B, S = 8192, 7, 256, 4
    bins_t = jnp.asarray(rng.integers(0, B, (F, N)).astype(np.int32))
    grad = jnp.asarray(rng.normal(size=N).astype(np.float32))
    hess = jnp.asarray((np.abs(np.asarray(grad)) * .5 + .2)
                       .astype(np.float32))
    vals8, scales = prep_hist_vals(grad, hess, jnp.ones(N, jnp.float32))
    node_id = jnp.asarray(rng.integers(0, S, N).astype(np.int32))
    leaf = jnp.arange(S, dtype=jnp.int32)
    sel = jnp.take(bins_t, jnp.zeros(S, jnp.int32), axis=0)
    kw = dict(t1=jnp.full((S,), 128, jnp.int32),
              rlo=jnp.full((S,), -1, jnp.int32),
              rhi=jnp.full((S,), B, jnp.int32),
              dflt=jnp.ones(S, jnp.int32),
              l_id=jnp.arange(S, dtype=jnp.int32) + S,
              r_id=jnp.arange(S, dtype=jnp.int32) + 2 * S)
    nid_f, fine = route_and_hist_pallas(
        bins_t, node_id, leaf, sel, vals=vals8, scales=scales,
        n_slots=S, total_bins=B, interpret=True, **kw)
    nid_c, coarse = route_and_hist_pallas(
        bins_t, node_id, leaf, sel, vals=vals8, scales=scales,
        n_slots=S, total_bins=B, hist_shift=2, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(nid_f), np.asarray(nid_c))
    Bc = coarse_bins(B, 2)
    np.testing.assert_allclose(np.asarray(coarse),
                               np.asarray(_pool_coarse(fine, Bc, 2)),
                               rtol=1e-5, atol=1e-5)


def test_auto_gate_keeps_small_data_exact():
    """two_level_hist='auto' (the default) must stay OFF below the row
    threshold: identical margins to an explicit 'off' run."""
    X, y = _data(n=20_000)
    kw = dict(objective="binary", num_iterations=8, num_leaves=15,
              max_bin=255)
    b_auto, _ = train(X, y, BoostingConfig(**kw))
    b_off, _ = train(X, y, BoostingConfig(two_level_hist="off", **kw))
    np.testing.assert_array_equal(b_auto.predict_margin(X[:512]),
                                  b_off.predict_margin(X[:512]))


def test_two_level_quality_parity():
    """Forced two-level training matches full-resolution AUC on a task
    with interactions and non-monotone structure (the coarse fallback +
    root-chosen refined set must not degrade the model)."""
    from synapseml_tpu.models.gbdt.metrics import auc
    X, y = _data(n=60_000)
    kw = dict(objective="binary", num_iterations=20, num_leaves=31,
              max_bin=255)
    b_on, _ = train(X, y, BoostingConfig(two_level_hist="on", **kw))
    b_off, _ = train(X, y, BoostingConfig(two_level_hist="off", **kw))
    Xh, yh = _data(n=30_000, seed=9)
    a_on = float(auc(yh, b_on.predict_margin(Xh)))
    a_off = float(auc(yh, b_off.predict_margin(Xh)))
    assert abs(a_on - a_off) < 0.005, (a_on, a_off)


def test_two_level_structural_gates():
    """Structurally excluded configurations (EFB, monotone constraints,
    low max_bin) silently train at full resolution — same margins as an
    explicit 'off' run even when forced 'on'."""
    X, y = _data(n=20_000, F=8)
    base = dict(objective="binary", num_iterations=6, num_leaves=15)
    cases = [
        dict(max_bin=63),                                   # B < 128
        dict(max_bin=255, enable_bundle=True),              # EFB
        dict(max_bin=255, monotone_constraints=[1] + [0] * 7),
    ]
    for extra in cases:
        b_on, _ = train(X, y, BoostingConfig(two_level_hist="on",
                                             **base, **extra))
        b_off, _ = train(X, y, BoostingConfig(two_level_hist="off",
                                              **base, **extra))
        np.testing.assert_array_equal(b_on.predict_margin(X[:256]),
                                      b_off.predict_margin(X[:256]),
                                      err_msg=str(extra))


@pytest.mark.slow
def test_two_level_data_parallel_mesh():
    """two_level='on' under a data-parallel mesh: coarse and fine-K
    histograms psum across shards, the root-chosen refined set is
    rank-identical, and quality matches the single-device run."""
    from synapseml_tpu.models.gbdt.metrics import auc
    from synapseml_tpu.parallel import data_parallel_mesh
    X, y = _data(n=40_000)
    kw = dict(objective="binary", num_iterations=10, num_leaves=31,
              max_bin=255, two_level_hist="on")
    b_dp, _ = train(X, y, BoostingConfig(**kw), mesh=data_parallel_mesh(8))
    b_1, _ = train(X, y, BoostingConfig(**kw))
    Xh, yh = _data(n=20_000, seed=9)
    a_dp = float(auc(yh, b_dp.predict_margin(Xh)))
    a_1 = float(auc(yh, b_1.predict_margin(Xh)))
    assert abs(a_dp - a_1) < 0.005, (a_dp, a_1)


def test_two_level_odd_bin_count():
    """A non-power-of-two max_bin (coarse width padded to a sublane
    multiple) trains and predicts sanely under forced two-level."""
    from synapseml_tpu.models.gbdt.metrics import auc
    X, y = _data(n=30_000)
    b, _ = train(X, y, BoostingConfig(objective="binary", num_iterations=10,
                                      num_leaves=31, max_bin=199,
                                      two_level_hist="on"))
    Xh, yh = _data(n=20_000, seed=9)
    assert float(auc(yh, b.predict_margin(Xh))) > 0.75


def _int_reference(bins, node_id, leaf, sel, t1, rlo, rhi, dflt, l_id, r_id,
                   vals, S, Bh, shift, sel_k, B):
    """Plain numpy: route every row, then scatter its int8 limbs into the
    histograms of the slot it went LEFT in.  → (new_node_id, coarse-or-
    plain (F, Bh, S, 8) int64, refined (K, B, S, 8) int64 or None)."""
    new, slot = node_id.copy(), np.full(node_id.shape, -1)
    for j in range(S):
        inleaf = node_id == leaf[j]
        x = sel[j]
        gl = np.where((x > rlo[j]) & (x <= rhi[j]), x <= t1[j], dflt[j] != 0)
        new = np.where(inleaf, np.where(gl, l_id[j], r_id[j]), new)
        slot = np.where(inleaf & gl, j, slot)
    live = slot >= 0
    v = vals.astype(np.int64)[live]

    def scatter(rows, width, sh):
        acc = np.zeros((rows.shape[0], width, S, 8), np.int64)
        for f in range(rows.shape[0]):
            np.add.at(acc[f], (rows[f][live] >> sh, slot[live]), v)
        return acc
    return (new, scatter(bins, Bh, shift),
            None if sel_k is None else scatter(sel_k, B, 0))


@pytest.mark.parametrize("F,B,shift,K,S,live,N,hist_chunk,tiled", [
    pytest.param(28, 256, 3, 8, 16, 1, 8192, 0, True, id="two-level-live1"),
    pytest.param(28, 256, 3, 8, 16, 4, 8192, 0, True, id="two-level-live4"),
    pytest.param(28, 256, 3, 8, 16, 8, 8192, 0, True, id="two-level-live8"),
    pytest.param(28, 256, 3, 8, 16, 16, 8192, 0, True,
                 id="two-level-live16"),
    # the (N, 8) limbs, lane-tiled inside the kernel
    pytest.param(28, 256, 3, 8, 16, 8, 8192, 0, False,
                 id="two-level-live8-limbs-untiled"),
    # rows that the ladder's 2,048-row chunk does not divide
    pytest.param(28, 256, 3, 8, 16, 8, 3072, 1024, True,
                 id="two-level-3072-rows-chunk1024"),
    pytest.param(28, 256, 3, 0, 16, 1, 8192, 0, True, id="coarse-only-root"),
    pytest.param(28, 256, 0, 0, 16, 8, 4096, 0, True, id="full-256"),
    pytest.param(28, 64, 0, 0, 16, 8, 4096, 0, False, id="full-64"),
    pytest.param(9, 200, 3, 4, 4, 3, 4096, 0, True, id="odd-bins-4-slots"),
    pytest.param(9, 200, 0, 0, 4, 3, 4096, 0, False, id="odd-bins-full"),
])
def test_fused_pass_is_the_integer_reference_bit_for_bit(
        F, B, shift, K, S, live, N, hist_chunk, tiled):
    """The accumulators are int32 sums of int8 products, so no geometry
    may change a bit of them: ``new_node_id`` and both accumulators equal
    a numpy scatter of the limbs, at the cell's shapes (28 x 256, shift
    3, 8 refined, 16 slots) for every count of live slots a tree has, at
    full resolution, at a tuned chunk, and whether the limbs come as
    ``prep_hist_vals_rows``'s channel rows or as the (N, 8) matrix."""
    import functools

    import jax
    import jax.numpy as jnp
    from synapseml_tpu.models.gbdt import pallas_hist as ph

    rng = np.random.default_rng(F * 1000 + B + live)
    bins = rng.integers(0, B - 1, (F, N)).astype(np.int32)
    node_id = rng.integers(0, live + 1, N).astype(np.int32)  # one not split
    JUNK = 61
    leaf = np.where(np.arange(S) < live, np.arange(S), JUNK).astype(np.int32)
    cols = rng.integers(0, F, S)
    sel = bins[cols]
    t1 = rng.integers(0, B, S).astype(np.int32)
    # slot 0 routes by an EFB-style range with a default direction
    rlo = np.full(S, -1, np.int32)
    rhi = np.full(S, B, np.int32)
    rlo[0], rhi[0] = 20, 180
    dflt = (np.arange(S) % 2).astype(np.int32)
    l_id = (100 + 2 * np.arange(S)).astype(np.int32)
    r_id = l_id + 1
    grad = rng.normal(size=N).astype(np.float32)
    hess = (np.abs(grad) * 0.5 + 0.2).astype(np.float32)
    mask = (rng.random(N) < 0.9).astype(np.float32)
    gh = (jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask))
    vals, _ = ph.prep_hist_vals(*gh)
    if tiled:
        limbs, (vals, _) = vals, ph.prep_hist_vals_rows(*gh)
        np.testing.assert_array_equal(np.asarray(vals),
                                      np.tile(np.asarray(limbs).T, (4, 1)))
    sel_k = bins[rng.choice(F, K, replace=False)] if K else None
    Bh = ph.coarse_bins(B, shift) if shift else B

    run = jax.jit(functools.partial(
        ph._route_and_hist_int, n_slots=S, total_bins=B, hist_shift=shift,
        interpret=True, hist_chunk=hist_chunk))
    res = run(jnp.asarray(bins), jnp.asarray(node_id), jnp.asarray(leaf),
              jnp.asarray(sel), jnp.asarray(t1), jnp.asarray(rlo),
              jnp.asarray(rhi), jnp.asarray(dflt), jnp.asarray(l_id),
              jnp.asarray(r_id), vals,
              sel_k=None if sel_k is None else jnp.asarray(sel_k))
    want_id, want, want_f = _int_reference(
        bins, node_id, leaf, sel, t1, rlo, rhi, dflt, l_id, r_id,
        np.asarray(vals)[:8].T if tiled else np.asarray(vals), S, Bh, shift,
        sel_k, B)
    assert res[1].dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(res[0])[0], want_id)
    np.testing.assert_array_equal(
        np.asarray(res[1]).reshape(F, Bh, S, 8), want)
    if K:
        np.testing.assert_array_equal(
            np.asarray(res[2]).reshape(K, B, S, 8), want_f)
    assert want[..., 6].sum() > 0           # rows did reach a histogram


def _splits(rng, F, B, S, live, ranged):
    """Slot parameters of a wave: ``live`` slots split leaves 0.., the
    rest are JUNK (leaf 61, matching no row, their column out of range:
    the kernel must clamp it, never read past the matrix); ``ranged``
    gives every slot an EFB-style range and default direction."""
    JUNK = 61
    leaf = np.where(np.arange(S) < live, np.arange(S), JUNK).astype(np.int32)
    cols = np.where(np.arange(S) < live, rng.integers(0, F, S),
                    F + 5).astype(np.int32)
    t1 = rng.integers(0, B, S).astype(np.int32)
    rlo = np.full(S, -1, np.int32)
    rhi = np.full(S, B, np.int32)
    dflt = np.ones(S, np.int32)
    if ranged:
        rlo = rng.integers(-1, B // 2, S).astype(np.int32)
        rhi = (rlo + rng.integers(1, B // 2, S)).astype(np.int32)
        dflt = rng.integers(0, 2, S).astype(np.int32)
    l_id = (100 + 2 * np.arange(S)).astype(np.int32)
    return dict(leaf=leaf, cols=cols, t1=t1, rlo=rlo, rhi=rhi, dflt=dflt,
                l_id=l_id, r_id=l_id + 1)


def _ragged_tile_cols(F, live, cols):
    """At 60 features (two groups of 30) the live slots split the last
    rows of the groups' ragged last tiles; any other width keeps its
    random columns."""
    return np.array([29, 59, 24][:live], np.int32) if F == 60 \
        else cols[:live]


@pytest.mark.parametrize("F,B,shift,K,S,live,N,ranged,root", [
    # the cell: one feature group, rows read from the chunk's whole block
    pytest.param(28, 256, 3, 8, 16, 12, 8192, False, False,
                 id="one-group-two-level-refined"),
    pytest.param(28, 256, 0, 0, 16, 12, 4096, False, False,
                 id="four-groups-plain"),
    # five groups of 8 for four slots: a tile of 8 rows a slot
    pytest.param(40, 256, 0, 0, 4, 3, 4096, False, False,
                 id="more-groups-than-slots"),
    # two groups of 30: a slot's tile may be the group's last, rows 24-29
    pytest.param(60, 16, 0, 0, 4, 3, 4096, False, False,
                 id="a-ragged-last-tile"),
    pytest.param(9, 200, 0, 0, 4, 4, 4096, True, False,
                 id="efb-ranged-splits"),
    pytest.param(28, 256, 3, 8, 16, 1, 8192, True, False,
                 id="junk-slots"),
    # the root's pass: every row left of leaf 0 whatever column 0 holds
    pytest.param(28, 256, 3, 0, 16, 1, 8192, False, True,
                 id="root-degenerate-split"),
])
def test_index_form_is_the_rows_form_bit_for_bit(
        F, B, shift, K, S, live, N, ranged, root):
    """``sel`` as (S,) column indices gives, bit for bit, what the (S, N)
    rows of those columns give: ``new_node_id``, the coarse-or-plain
    accumulator and the refined one, and both are the numpy reference."""
    import functools

    import jax
    import jax.numpy as jnp
    from synapseml_tpu.models.gbdt import pallas_hist as ph

    rng = np.random.default_rng(F * 100 + live + 7 * root)
    bins = rng.integers(0, B - 1, (F, N)).astype(np.int32)
    node_id = rng.integers(0, live + 1, N).astype(np.int32)
    sp = _splits(rng, F, B, S, live, ranged)
    sp["cols"][:live] = _ragged_tile_cols(F, live, sp["cols"])
    if root:
        node_id[:] = 0
        sp.update(cols=np.zeros(S, np.int32), t1=np.full(S, B, np.int32),
                  l_id=np.zeros(S, np.int32), r_id=np.zeros(S, np.int32))
    grad = rng.normal(size=N).astype(np.float32)
    vals, _ = ph.prep_hist_vals_rows(
        jnp.asarray(grad), jnp.asarray(np.abs(grad) + 0.2),
        jnp.asarray((rng.random(N) < 0.9).astype(np.float32)))
    sel_k = bins[rng.choice(F, K, replace=False)] if K else None
    rows = bins[np.clip(sp["cols"], 0, F - 1)]
    run = jax.jit(functools.partial(
        ph._route_and_hist_int, n_slots=S, total_bins=B, hist_shift=shift,
        interpret=True, hist_chunk=0))

    def call(sel):
        return run(jnp.asarray(bins), jnp.asarray(node_id),
                   jnp.asarray(sp["leaf"]), jnp.asarray(sel),
                   *(jnp.asarray(sp[k]) for k in ("t1", "rlo", "rhi", "dflt",
                                                  "l_id", "r_id")),
                   vals, sel_k=None if sel_k is None else jnp.asarray(sel_k))
    by_index, by_rows = call(sp["cols"]), call(rows)
    assert len(by_index) == len(by_rows) == (3 if K else 2)
    for a, b in zip(by_index, by_rows):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    Bh = ph.coarse_bins(B, shift) if shift else B
    want_id, want, _ = _int_reference(
        bins, node_id, sp["leaf"], rows, sp["t1"], sp["rlo"], sp["rhi"],
        sp["dflt"], sp["l_id"], sp["r_id"], np.asarray(vals)[:8].T, S, Bh,
        shift, sel_k, B)
    np.testing.assert_array_equal(np.asarray(by_index[0])[0], want_id)
    np.testing.assert_array_equal(
        np.asarray(by_index[1]).reshape(F, Bh, S, 8), want)
    assert want[..., 6].sum() > 0


@pytest.mark.parametrize("F,B,S,live,N,ft,ranged", [
    pytest.param(28, 256, 16, 12, 8192, 28, False, id="one-group"),
    pytest.param(28, 256, 16, 12, 4096, 7, False, id="tiles-of-7"),
    pytest.param(9, 200, 4, 4, 3072, 3, True, id="efb-ranged-splits"),
    pytest.param(28, 256, 16, 1, 8192, 28, True, id="junk-slots"),
    pytest.param(40, 256, 4, 3, 4096, 8, False,
                 id="more-groups-than-slots"),
    pytest.param(60, 16, 4, 3, 4096, 30, False, id="a-ragged-last-tile"),
])
def test_the_last_waves_routing_is_route_left(F, B, S, live, N, ft, ranged):
    """``route_rows_pallas`` (the wave that fills the leaf budget) moves
    every row exactly as the XLA reference ``_route_left`` does, from the
    flat matrix or its (G, ft, N) tiles."""
    import jax.numpy as jnp
    from synapseml_tpu.models.gbdt import pallas_hist as ph
    from synapseml_tpu.models.gbdt.trainer import _route_left

    rng = np.random.default_rng(N + live + ft)
    bins = rng.integers(0, B - 1, (F, N)).astype(np.int32)
    node_id = rng.integers(0, live + 2, N).astype(np.int32)
    sp = _splits(rng, F, B, S, live, ranged)
    sp["cols"][:live] = _ragged_tile_cols(F, live, sp["cols"])
    src = jnp.asarray(bins)
    if ft != F:
        src = ph.feature_tiles(src, ft)
    new = ph.route_rows_pallas(
        src, jnp.asarray(node_id), *(jnp.asarray(sp[k]) for k in (
            "leaf", "cols", "t1", "rlo", "rhi", "dflt", "l_id", "r_id")),
        interpret=True)
    want = node_id.copy()
    for j in range(live):
        gl = np.asarray(_route_left(bins[sp["cols"][j]], sp["t1"][j],
                                    sp["rlo"][j], sp["rhi"][j],
                                    sp["dflt"][j]))
        want = np.where(node_id == sp["leaf"][j],
                        np.where(gl, sp["l_id"][j], sp["r_id"][j]), want)
    np.testing.assert_array_equal(np.asarray(new), want)
    assert (want != node_id).any()


def test_the_wave_program_copies_no_split_columns():
    """The depth-wise grower at the two-level geometry, lowered for the
    TPU: no gather makes an (S, N) matrix of split columns (the root, the
    fused waves and the last wave all name them by index), and both
    kernels are in the program."""
    import re

    import jax
    import jax.numpy as jnp
    from synapseml_tpu.models.gbdt.trainer import (GrowthParams,
                                                   grow_tree_depthwise)

    N, F, B, S = 8192, 28, 256, 16
    p = GrowthParams(num_leaves=31, total_bins=B, two_level="on",
                     refine_k=8)
    sd = jax.ShapeDtypeStruct
    f32 = sd((N,), jnp.float32)
    text = grow_tree_depthwise.trace(
        sd((F, N), jnp.int32), f32, f32, f32, sd((F,), jnp.bool_),
        sd((F, B - 1), jnp.float32), sd((F,), jnp.int32), 0.1, p,
        use_pallas=True, n_slots=S).lower(
            lowering_platforms=("tpu",)).as_text()
    gathers = re.findall(r"stablehlo\.gather.*-> (tensor<[^>]*>)", text)
    assert gathers, "expected the refined features' and the picks' gathers"
    assert f"tensor<{S}x{N}xi32>" not in gathers, gathers
    assert "route_and_hist_pallas" in text and "route_rows_pallas" in text


@pytest.mark.parametrize("F,B,S,shift,K,chunk_override,want", [
    # the cell: the tile a step builds is 28 x 32 coarse rows beside the
    # 8 x 256 refined block, so ONE feature group, one step a chunk
    pytest.param(28, 256, 16, 3, 8, 0, (28, 2048), id="cell-two-level"),
    pytest.param(28, 256, 16, 3, 0, 0, (28, 2048), id="cell-root-coarse"),
    # without two-level: what the ladder gave before this function knew
    # of two-level (four groups of 7 at 256 bins, one group at 64)
    pytest.param(28, 256, 16, 0, 0, 0, (7, 2048), id="full-256"),
    pytest.param(28, 64, 16, 0, 0, 0, (28, 2048), id="full-64"),
    pytest.param(28, 128, 16, 0, 0, 0, (14, 2048), id="full-128"),
    pytest.param(50, 256, 16, 3, 8, 0, (25, 2048), id="two-groups-of-25"),
    # a tuned chunk starts the search elsewhere and still shrinks to fit
    pytest.param(28, 256, 16, 3, 8, 1024, (28, 1024), id="tuned-1024"),
    pytest.param(28, 256, 16, 3, 8, 8192, (28, 2048), id="tuned-too-big"),
    # the split columns' routing block is asked of the compiler beside the
    # budget: a wide matrix keeps the geometry it had when the columns
    # arrived as a (16, N) copy (12 groups of 7 near the budget, 8 of 32)
    pytest.param(84, 256, 16, 0, 0, 0, (7, 1024), id="near-the-budget"),
    pytest.param(256, 64, 16, 0, 0, 0, (32, 2048), id="wide-low-bin"),
    # VMEM cannot hold it: the callers fall back (scatter path, or
    # full-resolution growth for an uncapped refine_features)
    pytest.param(400, 256, 16, 0, 0, 0, None, id="wide-matrix"),
    pytest.param(100, 256, 16, 3, 32, 0, None, id="uncapped-refine"),
])
def test_fused_geometry_follows_the_tiles_the_pass_builds(
        F, B, S, shift, K, chunk_override, want):
    from synapseml_tpu.models.gbdt.pallas_hist import fused_geometry
    assert fused_geometry(F, B, S, chunk_override, hist_shift=shift,
                          refine_k=K) == want


def test_a_wide_matrix_is_refused_by_name_not_by_mosaic():
    """Past the gate the pass itself says what does not fit (the
    booster's gate, ``fused_geometry(...) is None``, takes the scatter
    path before it)."""
    import jax.numpy as jnp
    from synapseml_tpu.models.gbdt.pallas_hist import fused_tiles
    with pytest.raises(AssertionError, match="does not fit VMEM at F=400"):
        fused_tiles(jnp.zeros((400, 2048), jnp.int32), 16, 256)


def test_a_tuned_hist_chunk_still_goes_through_hist_chunk_ok():
    """The ``gbdt_hist_chunk`` winner is admitted at the full-resolution
    geometry of both entry points, as before; the two-level pass then
    starts its own fit loop from it."""
    from synapseml_tpu.models.gbdt.pallas_hist import (fused_geometry,
                                                       hist_chunk_ok)
    assert hist_chunk_ok(28, 256, 16, 1024)
    assert hist_chunk_ok(28, 256, 16, 2048)
    assert hist_chunk_ok(28, 256, 16, 4096)
    assert not hist_chunk_ok(28, 256, 16, 8192)     # the plain pass shrinks
    assert not hist_chunk_ok(28, 256, 16, 512)      # under the 1024 floor
    assert not hist_chunk_ok(28, 256, 16, 3072)     # does not divide the pad
    assert fused_geometry(28, 256, 16, 1024, hist_shift=3,
                          refine_k=8) == (28, 1024)
    assert fused_geometry(28, 256, 16, 4096, hist_shift=3,
                          refine_k=8) == (28, 2048)  # 4,096 + refined block


def test_the_fit_span_says_what_the_geometry_chose():
    """``gbdt.fit`` carries the depth-wise grower's plan: on the CPU no
    pallas tile (zeros) and each row's split column gathered per row,
    and whether the histograms are two-level; the plan itself at the
    cell's shapes is one step a chunk, its split columns read by
    index."""
    from synapseml_tpu import telemetry
    from synapseml_tpu.models.gbdt.trainer import (GrowthParams,
                                                   depthwise_hist_plan)
    p = GrowthParams(num_leaves=31, total_bins=256, two_level="on",
                     refine_k=8)
    assert depthwise_hist_plan(28, 12_001_280, p, 16, bundled=False,
                               use_pallas=True) == dict(
        two_level=True, ft=28, feature_groups=1, chunk=2048,
        grid_steps_per_pass=5860, split_columns="indexed")
    assert depthwise_hist_plan(28, 12_001_280, p, 16, bundled=True,
                               use_pallas=True) == dict(
        two_level=False, ft=7, feature_groups=4, chunk=2048,
        grid_steps_per_pass=23440, split_columns="indexed")
    X, y = _data(n=4_000, F=12)
    train(X, y, BoostingConfig(objective="binary", num_iterations=2,
                               num_leaves=7, max_bin=255,
                               two_level_hist="on"))
    a = telemetry.get_tracer().spans("gbdt.fit")[-1].attrs
    assert a["hist_two_level"] is True
    assert (a["hist_ft"], a["hist_feature_groups"], a["hist_chunk"],
            a["hist_grid_steps_per_pass"]) == (0, 0, 0, 0)
    assert a["hist_split_columns"] == "per_row"


def test_fused_refine_vmem_gate():
    """The fused coarse+refine pass models its OWN VMEM need: the bench
    shape fits, an uncapped refine_features does not (and the grower
    then falls back to full resolution instead of failing in Mosaic)."""
    from synapseml_tpu.models.gbdt.pallas_hist import fused_geometry
    assert fused_geometry(28, 256, 16, hist_shift=3, refine_k=8) is not None
    assert fused_geometry(100, 256, 16, hist_shift=3, refine_k=32) is None


def test_two_level_lossguide_interpret_matches_xla():
    """Two-level in the strict leaf-wise grower: pallas kernels
    (interpret — coarse nodes build + fine-K refine) grow the identical
    tree to the XLA fallback."""
    import jax.numpy as jnp
    from synapseml_tpu.models.gbdt.trainer import GrowthParams, grow_tree

    rng = np.random.default_rng(6)
    N, F, B = 8192, 9, 256
    bins_t = rng.integers(0, B, (F, N)).astype(np.int32)
    grad = rng.normal(size=N).astype(np.float32)
    hess = (np.abs(grad) * 0.5 + 0.2).astype(np.float32)
    p = GrowthParams(num_leaves=15, min_data_in_leaf=5.0, total_bins=B,
                     two_level="on", refine_k=4)
    ub = np.sort(rng.normal(size=(F, B - 1)).astype(np.float32), axis=1)
    args = (jnp.asarray(bins_t), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones(N, jnp.float32), jnp.ones(F, bool), jnp.asarray(ub),
            jnp.full(F, B, jnp.int32), 0.1)
    t_x, nid_x = grow_tree(*args, p=p, use_pallas=False)
    t_p, nid_p = grow_tree(*args, p=p, use_pallas="interpret")
    np.testing.assert_array_equal(np.asarray(nid_x), np.asarray(nid_p))
    for f in ("split_feature", "left_child", "right_child", "num_nodes"):
        np.testing.assert_array_equal(np.asarray(getattr(t_x, f)),
                                      np.asarray(getattr(t_p, f)),
                                      err_msg=f)


def test_two_level_lossguide_quality_parity():
    """Forced two-level lossguide training matches full-resolution AUC,
    like the depthwise case."""
    from synapseml_tpu.models.gbdt.metrics import auc
    X, y = _data(n=60_000)
    kw = dict(objective="binary", num_iterations=15, num_leaves=31,
              max_bin=255, growth_policy="lossguide")
    b_on, _ = train(X, y, BoostingConfig(two_level_hist="on", **kw))
    b_off, _ = train(X, y, BoostingConfig(two_level_hist="off", **kw))
    Xh, yh = _data(n=30_000, seed=9)
    a_on = float(auc(yh, b_on.predict_margin(Xh)))
    a_off = float(auc(yh, b_off.predict_margin(Xh)))
    assert abs(a_on - a_off) < 0.005, (a_on, a_off)
