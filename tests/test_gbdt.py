"""GBDT engine tests: accuracy, modes, distributed parity, estimator API.

Accuracy thresholds follow the reference's benchmark-CSV pattern
(reference: lightgbm/src/test/resources/benchmarks/*.csv — AUC per dataset
per boosting type, compared with per-entry precision by the Benchmarks
trait, core/test/benchmarks/Benchmarks.scala:15-52).  We use seeded
synthetic datasets with known learnable structure instead of shipped CSVs.
"""

import numpy as np
import pytest

from synapseml_tpu import Dataset
from synapseml_tpu.core.pipeline import load_stage
from synapseml_tpu.models.gbdt import (Booster, BoostingConfig,
                                       GBDTClassifier, GBDTRanker,
                                       GBDTRegressionModel, GBDTRegressor,
                                       train)
from synapseml_tpu.models.gbdt.binning import fit_bin_mapper
from synapseml_tpu.models.gbdt.metrics import (auc, binary_error, multi_error,
                                               ndcg_at, rmse)

from fuzzing import EstimatorFuzzing, TestObject


def binary_data(n=3000, F=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    logit = 2 * X[:, 0] - 1.5 * X[:, 1] + X[:, 2] * X[:, 3]
    y = (logit + rng.normal(scale=0.5, size=n) > 0).astype(np.float64)
    return X, y


def vec_dataset(X, y, extra=None):
    cols = {"features": list(X), "label": y}
    if extra:
        cols.update(extra)
    return Dataset(cols)


# -- binning ---------------------------------------------------------------

def test_bin_mapper_roundtrip():
    X = np.array([[0.1, 5], [0.2, 5], [0.3, 7], [np.nan, 9]], np.float32)
    m = fit_bin_mapper(X, max_bin=4)
    b = m.transform(X)
    assert b.shape == X.shape
    assert b[3, 0] == 0                      # NaN bin
    assert b[0, 0] < b[2, 0]                 # order preserved
    assert m.num_bins[1] == 3                # 3 distinct values


def test_bin_mapper_many_uniques_quantile():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(5000, 1)).astype(np.float32)
    m = fit_bin_mapper(X, max_bin=15)
    b = m.transform(X)
    assert b.max() <= 15 and b.min() >= 1
    # roughly equal occupancy
    counts = np.bincount(b[:, 0], minlength=16)[1:]
    assert counts.min() > 100


# -- core training accuracy (benchmark-CSV analogue) ------------------------

BOOSTING_AUC_FLOOR = {"gbdt": 0.95, "goss": 0.95, "dart": 0.93, "rf": 0.90}


@pytest.mark.parametrize("boosting", ["gbdt", "goss", "dart", "rf"])
def test_binary_auc_benchmark(boosting):
    X, y = binary_data()
    cfg = BoostingConfig(objective="binary", boosting_type=boosting,
                         num_iterations=30, num_leaves=15, learning_rate=0.2,
                         min_data_in_leaf=5, bagging_fraction=0.8,
                         bagging_freq=1, seed=7)
    b, _ = train(X[:2400], y[:2400], cfg)
    a = auc(y[2400:], b.predict_margin(X[2400:]))
    assert a > BOOSTING_AUC_FLOOR[boosting], (boosting, a)


def test_regression_rmse():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(3000, 6)).astype(np.float32)
    y = X[:, 0] * 3 + np.sin(3 * X[:, 1]) + rng.normal(scale=0.1, size=3000)
    cfg = BoostingConfig(objective="regression", num_iterations=40,
                         num_leaves=31, learning_rate=0.15, min_data_in_leaf=5)
    b, _ = train(X[:2400], y[:2400].astype(np.float64), cfg)
    assert rmse(y[2400:], b.predict_margin(X[2400:])) < 0.4


def test_multiclass():
    rng = np.random.default_rng(4)
    X = rng.normal(size=(3000, 6)).astype(np.float32)
    y = np.digitize(X[:, 0] + 0.5 * X[:, 1], [-0.7, 0.7]).astype(np.float64)
    cfg = BoostingConfig(objective="multiclass", num_class=3,
                         num_iterations=15, num_leaves=15,
                         learning_rate=0.2, min_data_in_leaf=5)
    b, _ = train(X, y, cfg)
    m = b.predict_margin(X)
    assert m.shape == (3000, 3)
    assert multi_error(y.astype(int), m) < 0.05
    p = b.to_proba(m)
    np.testing.assert_allclose(p.sum(1), 1.0, rtol=1e-5)


def test_early_stopping_and_validation():
    X, y = binary_data()
    cfg = BoostingConfig(objective="binary", num_iterations=200,
                         num_leaves=31, learning_rate=0.3,
                         early_stopping_round=5, min_data_in_leaf=5)
    b, hist = train(X[:2000], y[:2000], cfg, valid=(X[2000:], y[2000:], None))
    assert len(hist) < 200                       # stopped early
    assert b.best_iteration >= 0
    metrics = [h.value for h in hist]
    assert min(metrics) == metrics[b.best_iteration]


def test_distributed_matches_single_device():
    from synapseml_tpu.parallel import data_parallel_mesh
    X, y = binary_data(n=2000)
    cfg = BoostingConfig(objective="binary", num_iterations=8,
                         num_leaves=15, min_data_in_leaf=5)
    b1, _ = train(X, y, cfg)
    b8, _ = train(X, y, cfg, mesh=data_parallel_mesh(8))
    np.testing.assert_allclose(b1.predict_margin(X), b8.predict_margin(X),
                               atol=1e-4)


def test_voting_parallel_close_to_data_parallel():
    """Voting parallel (PV-Tree) aggregates only voted features; with
    top_k >= the number of informative features it should find essentially
    the same trees (reference param: params/LightGBMParams.scala:25)."""
    from synapseml_tpu.parallel import data_parallel_mesh
    X, y = binary_data(n=4000)
    mesh = data_parallel_mesh(8)
    full = BoostingConfig(objective="binary", num_iterations=10,
                          num_leaves=15, min_data_in_leaf=5)
    vote = BoostingConfig(objective="binary", num_iterations=10,
                          num_leaves=15, min_data_in_leaf=5,
                          parallelism="voting_parallel", top_k=6)
    bf, _ = train(X, y, full, mesh=mesh)
    bv, _ = train(X, y, vote, mesh=mesh)
    auc_f = auc(y, 1 / (1 + np.exp(-bf.predict_margin(X))))
    auc_v = auc(y, 1 / (1 + np.exp(-bv.predict_margin(X))))
    assert auc_v > auc_f - 0.01
    # with top_k = F every feature is aggregated → exactly data-parallel
    # (compared against the lossguide grower: voting implies strict
    # best-first leaf order, so the reference must grow the same way)
    exact = BoostingConfig(objective="binary", num_iterations=4,
                           num_leaves=7, min_data_in_leaf=5,
                           parallelism="voting_parallel", top_k=X.shape[1])
    be, _ = train(X, y, exact, mesh=mesh)
    ref = BoostingConfig(objective="binary", num_iterations=4,
                         num_leaves=7, min_data_in_leaf=5,
                         growth_policy="lossguide")
    br, _ = train(X, y, ref, mesh=mesh)
    np.testing.assert_allclose(be.predict_margin(X), br.predict_margin(X),
                               atol=1e-4)


def test_feature_parallel_matches_single_device():
    """Vertical sharding (LightGBM tree_learner=feature_parallel; the
    reference only passes the string to native code,
    params/BaseTrainParams.scala:99): local histograms + gathered best
    splits + owner-broadcast routing must grow the SAME tree as the
    unsharded depthwise grower.  F=11 exercises the feature-padding path
    (11 % 8 != 0)."""
    from synapseml_tpu.parallel import data_parallel_mesh
    rng = np.random.default_rng(2)
    X = rng.normal(size=(2000, 11)).astype(np.float32)
    y = (2 * X[:, 0] - X[:, 1] + X[:, 2] * X[:, 3]
         + rng.normal(scale=0.5, size=2000) > 0).astype(np.float64)
    cfg = BoostingConfig(objective="binary", num_iterations=8,
                         num_leaves=15, min_data_in_leaf=5)
    b1, _ = train(X, y, cfg)
    fp = BoostingConfig(objective="binary", num_iterations=8,
                        num_leaves=15, min_data_in_leaf=5,
                        parallelism="feature_parallel")
    bf, _ = train(X, y, fp, mesh=data_parallel_mesh(8))
    np.testing.assert_allclose(b1.predict_margin(X), bf.predict_margin(X),
                               atol=1e-4)


def test_feature_parallel_estimator_and_guards():
    from synapseml_tpu.parallel import data_parallel_mesh
    X, y = binary_data(n=1500)
    ds = vec_dataset(X, y)
    clf = GBDTClassifier(numIterations=8, numLeaves=15, minDataInLeaf=5,
                         parallelism="feature_parallel", numShards=8)
    model = clf.fit(ds)
    out = model.transform(ds)
    assert auc(y, np.stack(out["probability"])[:, 1]) > 0.9
    # strict lossguide under featpar trains too (one-slot waves are
    # best-first order — pinned against the single-device lossguide tree
    # in test_featpar_lossguide_matches_single_device)


def test_feature_parallel_dart_matches_single_device():
    """dart + feature_parallel (previously rejected): rescoring traverses
    the SHARDED binned matrix with owner-broadcast go-left masks (one
    psum per level, the training routing pattern).  Same host rng seed
    => same drop decisions, and the sharded run grows the same trees as
    single-device depthwise dart."""
    from synapseml_tpu.parallel import data_parallel_mesh
    rng = np.random.default_rng(4)
    X = rng.normal(size=(2000, 11)).astype(np.float32)
    y = (2 * X[:, 0] - X[:, 1] + X[:, 2] * X[:, 3]
         + rng.normal(scale=0.5, size=2000) > 0).astype(np.float64)
    kw = dict(objective="binary", boosting_type="dart", num_iterations=8,
              num_leaves=15, min_data_in_leaf=5, drop_rate=0.3,
              skip_drop=0.2, seed=13)
    b1, _ = train(X, y, BoostingConfig(growth_policy="depthwise", **kw))
    bf, _ = train(X, y, BoostingConfig(parallelism="feature_parallel",
                                       **kw),
                  mesh=data_parallel_mesh(8))
    for t_p, t_e in zip(b1.trees, bf.trees):
        np.testing.assert_array_equal(np.asarray(t_p.split_feature),
                                      np.asarray(t_e.split_feature))
    np.testing.assert_allclose(b1.predict_margin(X[:512]),
                               bf.predict_margin(X[:512]), atol=1e-4)


def test_voting_parallel_estimator():
    X, y = binary_data(n=2000)
    ds = vec_dataset(X, y)
    clf = GBDTClassifier(featuresCol="features", labelCol="label",
                         numIterations=8, numLeaves=15, minDataInLeaf=5,
                         parallelism="voting_parallel", topK=6, numShards=8)
    model = clf.fit(ds)
    out = model.transform(ds)
    assert auc(y, np.stack(out["probability"])[:, 1]) > 0.85


def test_model_string_roundtrip():
    """to_string now emits the LightGBM text format
    (saveToString/loadNativeModelFromString parity)."""
    X, y = binary_data(n=1000)
    cfg = BoostingConfig(objective="binary", num_iterations=5,
                         num_leaves=7, min_data_in_leaf=5)
    b, _ = train(X, y, cfg)
    s = b.to_string()
    assert s.startswith("tree\n") and "Tree=0" in s and "end of trees" in s
    b2 = Booster.from_string(s)
    np.testing.assert_allclose(b.predict_margin(X), b2.predict_margin(X),
                               atol=1e-5)
    # re-export → re-import is a fixed point
    b3 = Booster.from_string(b2.to_string())
    np.testing.assert_allclose(b2.predict_margin(X), b3.predict_margin(X),
                               atol=1e-6)


@pytest.mark.parametrize("objective,boosting", [
    ("regression", "gbdt"), ("binary", "dart"), ("binary", "rf"),
    ("multiclass", "gbdt")])
def test_lgbm_format_roundtrip_modes(objective, boosting):
    rng = np.random.default_rng(6)
    X = rng.normal(size=(800, 5)).astype(np.float32)
    if objective == "multiclass":
        y = np.digitize(X[:, 0], [-0.5, 0.5]).astype(np.float64)
        cfg = BoostingConfig(objective=objective, num_class=3,
                             boosting_type=boosting, num_iterations=6,
                             num_leaves=7, min_data_in_leaf=5)
    else:
        y = ((X[:, 0] + X[:, 1] > 0).astype(np.float64)
             if objective == "binary" else
             (X[:, 0] * 2 + X[:, 1]).astype(np.float64))
        cfg = BoostingConfig(objective=objective, boosting_type=boosting,
                             num_iterations=6, num_leaves=7,
                             min_data_in_leaf=5, bagging_fraction=0.8,
                             bagging_freq=1)
    b, _ = train(X, y, cfg)
    b2 = Booster.from_string(b.to_string())
    np.testing.assert_allclose(b.predict_margin(X), b2.predict_margin(X),
                               rtol=1e-5, atol=1e-5)


def test_import_handwritten_lightgbm_file(tmp_path):
    """A model file in the exact shape LightGBM writes (two trees, one with
    a nested split, leaf children as complement indices) predicts what the
    tree arithmetic says it should."""
    model = """tree
version=v3
num_class=1
num_tree_per_iteration=1
label_index=0
max_feature_idx=2
objective=regression
feature_names=a b c
feature_infos=[-10:10] [-10:10] [-10:10]
tree_sizes=400 200

Tree=0
num_leaves=3
num_cat=0
split_feature=0 1
split_gain=10 5
threshold=0.5 -1.25
decision_type=10 10
left_child=1 -1
right_child=-3 -2
leaf_value=1.5 2.5 -3
leaf_weight=0 0 0
leaf_count=0 0 0
internal_value=0 0.1
internal_weight=0 0
internal_count=0 0
is_linear=0
shrinkage=0.1

Tree=1
num_leaves=2
num_cat=0
split_feature=2
split_gain=1
threshold=0
decision_type=10
left_child=-1
right_child=-2
leaf_value=10 20
leaf_weight=0 0
leaf_count=0 0
internal_value=0
internal_weight=0
internal_count=0
is_linear=0
shrinkage=0.1

end of trees
"""
    p = tmp_path / "model.txt"
    p.write_text(model)
    b = Booster.from_file(str(p))
    assert b.num_trees == 2
    # tree0: x0<=0.5 -> (x1<=-1.25 -> leaf0=1.5 else leaf1=2.5), else leaf2=-3
    # tree1: x2<=0 -> 10 else 20
    X = np.array([
        [0.0, -2.0, -1.0],    # 1.5 + 10 = 11.5
        [0.0,  0.0,  1.0],    # 2.5 + 20 = 22.5
        [1.0,  0.0, -1.0],    # -3 + 10 = 7
        [np.nan, -2.0, np.nan],  # NaN routes left: 1.5 + 10 = 11.5
    ], np.float32)
    np.testing.assert_allclose(b.predict_margin(X),
                               [11.5, 22.5, 7.0, 11.5], atol=1e-6)
    # model-class loader (loadNativeModelFromFile analogue)
    m = GBDTRegressionModel.load_native_model_from_file(str(p))
    ds = Dataset({"features": list(X)})
    np.testing.assert_allclose(np.asarray(m.transform(ds)["prediction"]),
                               [11.5, 22.5, 7.0, 11.5], atol=1e-6)


def test_lgbm_import_rejects_categorical():
    s = """tree
num_class=1
num_tree_per_iteration=1
max_feature_idx=0
objective=regression
tree_sizes=100

Tree=0
num_leaves=2
num_cat=1
split_feature=0
threshold=0.5
decision_type=11
left_child=-1
right_child=-2
leaf_value=1 2

end of trees
"""
    with pytest.raises(ValueError, match="categorical"):
        Booster.from_string(s)


def _brute_force_shap(booster, x):
    """Exact Shapley values by subset enumeration against the tree-path
    cover-weighted conditional expectation — the definition TreeSHAP
    computes in polynomial time."""
    import itertools
    import math

    F = booster.bin_mapper.num_features

    def cond_exp(S):
        total = float(booster.init_score[0])
        for i, t in enumerate(booster.trees):
            w = booster.tree_weights[i]

            def rec(j):
                f = int(t.split_feature[j])
                if f < 0:
                    return float(t.node_value[j])
                if f in S:
                    xv = x[f]
                    go_left = bool(t.default_left[j]) if np.isnan(xv) \
                        else bool(xv <= t.threshold[j])
                    return rec(int(t.left_child[j]) if go_left
                               else int(t.right_child[j]))
                cl, cr = (float(t.node_count[int(t.left_child[j])]),
                          float(t.node_count[int(t.right_child[j])]))
                tot = max(cl + cr, 1e-12)
                return (cl * rec(int(t.left_child[j]))
                        + cr * rec(int(t.right_child[j]))) / tot

            total += rec(0) * w
        return total

    phi = np.zeros(F + 1)
    phi[F] = cond_exp(frozenset())
    for f in range(F):
        rest = [g for g in range(F) if g != f]
        for r in range(F):
            for S in itertools.combinations(rest, r):
                wgt = (math.factorial(r) * math.factorial(F - r - 1)
                       / math.factorial(F))
                phi[f] += wgt * (cond_exp(frozenset(S) | {f})
                                 - cond_exp(frozenset(S)))
    return phi


def test_exact_treeshap_matches_brute_force():
    """predict_contrib is EXACT TreeSHAP (featuresShap parity,
    LightGBMBooster.featuresShap): equals subset-enumeration Shapley on a
    small model, not just the Saabas approximation."""
    rng = np.random.default_rng(12)
    X = rng.normal(size=(400, 4)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float64)
    cfg = BoostingConfig(objective="binary", num_iterations=4, num_leaves=7,
                         min_data_in_leaf=10)
    b, _ = train(X, y, cfg)
    contrib = b.predict_contrib(X[:5])
    for r in range(5):
        expected = _brute_force_shap(b, X[r])
        np.testing.assert_allclose(contrib[r], expected, rtol=1e-4,
                                   atol=1e-5)
    # contributions still sum to the margin
    np.testing.assert_allclose(contrib.sum(1), b.predict_margin(X[:5]),
                               rtol=1e-4, atol=1e-4)
    # the Saabas approximation remains available and also sums to margin
    approx = b.predict_contrib(X[:5], approximate=True)
    np.testing.assert_allclose(approx.sum(1), b.predict_margin(X[:5]),
                               rtol=1e-4, atol=1e-4)
    assert not np.allclose(approx, contrib)      # genuinely different paths


def test_treeshap_counts_survive_lgbm_roundtrip():
    """Cover counts ride the LightGBM text format (leaf_count /
    internal_count), so exact SHAP works on re-imported models."""
    X, y = binary_data(n=800, F=5)
    cfg = BoostingConfig(objective="binary", num_iterations=3, num_leaves=7,
                         min_data_in_leaf=10)
    b, _ = train(X, y, cfg)
    b2 = Booster.from_string(b.to_string())
    c1 = b.predict_contrib(X[:8])
    c2 = b2.predict_contrib(X[:8])
    # per-feature attributions identical through the round trip (bias is
    # folded into the first tree's leaves on export, shifting only how the
    # total splits between bias and feature columns sums)
    np.testing.assert_allclose(c1.sum(1), c2.sum(1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(c1[:, :-1], c2[:, :-1], rtol=1e-3, atol=1e-4)


def test_feature_importance_and_contrib():
    X, y = binary_data(n=2000)
    cfg = BoostingConfig(objective="binary", num_iterations=10,
                         num_leaves=15, min_data_in_leaf=5)
    b, _ = train(X, y, cfg)
    fi = b.feature_importance("split")
    gain = b.feature_importance("gain")
    # informative features dominate
    assert fi[:4].sum() > fi[4:].sum()
    assert gain[0] > gain[5]
    contrib = b.predict_contrib(X[:50])
    assert contrib.shape == (50, X.shape[1] + 1)
    # contributions sum to the margin
    np.testing.assert_allclose(contrib.sum(1), b.predict_margin(X[:50]),
                               rtol=1e-4, atol=1e-4)


def test_sample_weights_shift_model():
    X, y = binary_data(n=1500)
    w = np.where(y > 0, 10.0, 1.0)
    cfg = BoostingConfig(objective="binary", num_iterations=10,
                         num_leaves=7, min_data_in_leaf=5)
    b_w, _ = train(X, y, cfg, sample_weight=w)
    b_u, _ = train(X, y, cfg)
    # upweighting positives pushes margins up on average
    assert b_w.predict_margin(X).mean() > b_u.predict_margin(X).mean()


def test_ranker_lambdarank():
    rng = np.random.default_rng(5)
    Q, D, F = 60, 12, 5
    X = rng.normal(size=(Q * D, F)).astype(np.float32)
    rel = np.clip((X[:, 0] * 2 + rng.normal(scale=0.3, size=Q * D)), -2, 2)
    y = np.digitize(rel, [-0.5, 0.5, 1.2]).astype(np.float64)   # 0..3 grades
    groups = np.full(Q, D)
    cfg = BoostingConfig(objective="lambdarank", num_iterations=20,
                         num_leaves=7, learning_rate=0.2, min_data_in_leaf=3)
    b, _ = train(X, y, cfg, group=groups)
    scores = b.predict_margin(X)
    n = ndcg_at(5)(y, scores, groups)
    n_random = ndcg_at(5)(y, rng.normal(size=Q * D), groups)
    assert n > n_random + 0.15, (n, n_random)


# -- estimator API ----------------------------------------------------------

def test_classifier_estimator_end_to_end():
    X, y = binary_data(n=1200)
    ds = vec_dataset(X, y)
    clf = GBDTClassifier(numIterations=10, numLeaves=15, minDataInLeaf=5,
                         numShards=1)
    model = clf.fit(ds)
    out = model.transform(ds)
    for col in ("prediction", "probability", "rawPrediction"):
        assert col in out.columns
    acc = (out["prediction"] == y).mean()
    assert acc > 0.85
    proba = np.stack(list(out["probability"]))
    np.testing.assert_allclose(proba.sum(1), 1.0, rtol=1e-5)


def test_classifier_validation_indicator():
    X, y = binary_data(n=1200)
    vmask = np.zeros(1200, bool)
    vmask[1000:] = True
    ds = vec_dataset(X, y, {"isVal": vmask})
    clf = GBDTClassifier(numIterations=50, numLeaves=15, minDataInLeaf=5,
                         validationIndicatorCol="isVal",
                         earlyStoppingRound=5, numShards=1)
    model = clf.fit(ds)
    assert model._eval_history          # eval ran
    assert model.get_booster_num_trees() <= 50


def test_regressor_estimator_and_leaf_output():
    rng = np.random.default_rng(8)
    X = rng.normal(size=(800, 5)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1]).astype(np.float64)
    ds = vec_dataset(X, y)
    reg = GBDTRegressor(numIterations=30, learningRate=0.3, numLeaves=15,
                        minDataInLeaf=5, numShards=1)
    model = reg.fit(ds)
    model.set("leafPredictionCol", "leaves")
    out = model.transform(ds)
    assert rmse(y, out["prediction"]) < 0.5
    assert len(out["leaves"][0]) == model.get_booster_num_trees()


def test_ranker_estimator():
    rng = np.random.default_rng(9)
    Q, D = 40, 10
    X = rng.normal(size=(Q * D, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    qid = np.repeat(np.arange(Q), D)
    ds = Dataset({"features": list(X), "label": y, "query": qid})
    ranker = GBDTRanker(numIterations=10, numLeaves=7, minDataInLeaf=3,
                        groupCol="query", numShards=1)
    model = ranker.fit(ds)
    out = model.transform(ds)
    assert "prediction" in out.columns


def test_model_save_load(tmp_path):
    X, y = binary_data(n=600)
    ds = vec_dataset(X, y)
    model = GBDTClassifier(numIterations=5, numLeaves=7, minDataInLeaf=5,
                           numShards=1).fit(ds)
    model.save(str(tmp_path / "m"))
    m2 = load_stage(str(tmp_path / "m"))
    a = model.transform(ds)
    b = m2.transform(ds)
    np.testing.assert_allclose(
        np.stack(list(a["probability"])), np.stack(list(b["probability"])),
        atol=1e-6)


def test_num_batches_warm_start():
    X, y = binary_data(n=1200)
    ds = vec_dataset(X, y)
    clf = GBDTClassifier(numIterations=5, numLeaves=7, minDataInLeaf=5,
                         numBatches=2, numShards=1)
    model = clf.fit(ds)
    # 2 batches × 5 iterations each
    assert model.get_booster_num_trees() == 10


class TestGBDTClassifierFuzzing(EstimatorFuzzing):
    def fuzzing_objects(self):
        X, y = binary_data(n=300)
        return [TestObject(
            GBDTClassifier(numIterations=3, numLeaves=7, minDataInLeaf=5,
                           numShards=1),
            vec_dataset(X, y))]


class TestGBDTRegressorFuzzing(EstimatorFuzzing):
    def fuzzing_objects(self):
        rng = np.random.default_rng(1)
        X = rng.normal(size=(300, 4)).astype(np.float32)
        y = (X[:, 0] + X[:, 1]).astype(np.float64)
        return [TestObject(
            GBDTRegressor(numIterations=3, numLeaves=7, minDataInLeaf=5,
                          numShards=1),
            vec_dataset(X, y))]


def test_depthwise_matches_lossguide_quality():
    """The wave grower (one batched histogram pass per level) must match
    strict leaf-wise quality; trees may differ only in how the tail of the
    leaf budget is allocated."""
    X, y = binary_data()
    aucs = {}
    for pol in ("depthwise", "lossguide"):
        cfg = BoostingConfig(objective="binary", num_iterations=20,
                             num_leaves=15, learning_rate=0.2,
                             min_data_in_leaf=5, growth_policy=pol)
        b, _ = train(X[:2400], y[:2400], cfg)
        aucs[pol] = auc(y[2400:], b.predict_margin(X[2400:]))
    assert abs(aucs["depthwise"] - aucs["lossguide"]) < 0.01, aucs


def test_depthwise_unbounded_budget_matches_lossguide_exactly():
    """With min_gain huge... rather: when every positive-gain leaf fits the
    budget, wave order and best-first order split the SAME node set — the
    growers must agree exactly."""
    rng = np.random.default_rng(11)
    X = rng.normal(size=(500, 4)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    # num_leaves large enough that the budget never truncates a wave
    for pol in ("depthwise", "lossguide"):
        cfg = BoostingConfig(objective="binary", num_iterations=3,
                             num_leaves=64, min_data_in_leaf=60,
                             growth_policy=pol)
        b, _ = train(X, y, cfg)
        if pol == "depthwise":
            ref = b.predict_margin(X)
        else:
            np.testing.assert_allclose(ref, b.predict_margin(X), atol=1e-5)


@pytest.mark.parametrize("rows", [False, True],
                         ids=["limbs-N-by-8", "channel-rows-32-by-N"])
def test_node_batched_hist_matches_scatter(rows):
    """Node-batched Pallas kernel (interpret) vs the XLA scatter fallback,
    with the (N, 8) limbs and with ``prep_hist_vals_rows``'s (32, N)
    matrix, which 5 slots use 40 positions of."""
    import jax.numpy as jnp
    from synapseml_tpu.models.gbdt.pallas_hist import (
        build_hist_nodes_pallas, prep_hist_vals, prep_hist_vals_rows)
    from synapseml_tpu.models.gbdt.trainer import _build_hist_nodes_xla

    rng = np.random.default_rng(3)
    N, F, B, S = 2048, 11, 64, 5
    bins_t = rng.integers(0, B, (F, N)).astype(np.int32)
    grad = rng.normal(size=N).astype(np.float32)
    hess = (np.abs(grad) + 0.1).astype(np.float32)
    mask = (rng.random(N) < 0.7).astype(np.float32) * 1.5
    slot = rng.integers(-1, S, N).astype(np.int32)
    gh = (jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask))
    vals, scales = prep_hist_vals(*gh)
    assert vals.shape == (N, 8) and vals.dtype == jnp.int8
    if rows:
        limbs, (vals, scales) = vals, prep_hist_vals_rows(*gh)
        assert vals.dtype == jnp.int8
        np.testing.assert_array_equal(
            np.asarray(vals), np.tile(np.asarray(limbs).T, (4, 1)))
    out_p = np.asarray(build_hist_nodes_pallas(
        jnp.asarray(bins_t), jnp.asarray(slot), vals, scales, S, B,
        interpret=True))
    flat = bins_t + (np.arange(F, dtype=np.int32) * B)[:, None]
    out_x = np.asarray(_build_hist_nodes_xla(
        jnp.asarray(flat), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(mask), jnp.asarray(slot), S, F, B))
    np.testing.assert_allclose(out_p, out_x, rtol=1e-4, atol=1e-4)



def test_pallas_hist_matches_scatter():
    """Production pallas histogram path (interpret mode) vs the XLA
    scatter path — same histograms (the leaf-wise grower's per-node build:
    per-tree int8 limb quantization + single-slot nodes kernel)."""
    import jax.numpy as jnp
    from synapseml_tpu.models.gbdt.pallas_hist import prep_hist_vals
    from synapseml_tpu.models.gbdt.trainer import _build_hist

    rng = np.random.default_rng(0)
    N, F, B = 2048, 11, 64
    bins_t = rng.integers(0, B, (F, N)).astype(np.int32)
    grad = rng.normal(size=N).astype(np.float32)
    hess = (np.abs(grad) + 0.1).astype(np.float32)
    mask = (rng.random(N) < 0.7).astype(np.float32) * 1.5   # weighted rows

    vals8, scales = prep_hist_vals(jnp.asarray(grad), jnp.asarray(hess),
                                   jnp.asarray(mask))
    out_p = np.asarray(_build_hist(
        jnp.asarray(bins_t), None, jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(mask), F, B, use_pallas="interpret",
        vals8=vals8, scales=scales)).reshape(F, B, 3)
    flat = bins_t + (np.arange(F, dtype=np.int32) * B)[:, None]
    out_s = np.asarray(_build_hist(
        jnp.asarray(bins_t), jnp.asarray(flat), jnp.asarray(grad),
        jnp.asarray(hess), jnp.asarray(mask), F, B,
        use_pallas=False)).reshape(F, B, 3)
    np.testing.assert_allclose(out_p, out_s, rtol=1e-4, atol=1e-4)


def test_training_instrumentation():
    """Per-phase timing measures (LightGBMPerformance.scala analogue)."""
    X, y = binary_data(n=1000)
    clf = GBDTClassifier(featuresCol="features", labelCol="label",
                         numIterations=5, numLeaves=7, minDataInLeaf=5,
                         numShards=1)
    model = clf.fit(vec_dataset(X, y))
    m = model.training_measures
    assert m is not None and m.iterations == 5
    assert m.total_s > 0 and m.training_s > 0 and m.binning_s > 0
    assert m.compile_s <= m.training_s
    d = m.as_dict()
    assert "iterations_per_sec" in d and d["iterations_per_sec"] > 0
    # the resolved histogram builder is on the record: off-TPU it is
    # the XLA scatter path, never silently anything else
    assert m.hist_path == "xla_scatter"


def test_checkpoint_resume_matches_uninterrupted(tmp_path):
    """Step-level checkpoint/resume (beyond the reference, whose only
    resume unit is the numBatches warm start, LightGBMBase.scala:38-59):
    interrupting at iteration 6 and resuming trains the remaining trees
    onto the same model."""
    X, y = binary_data(n=1500)
    ck = str(tmp_path / "ck")

    def cfg(iters):
        return BoostingConfig(objective="binary", num_iterations=iters,
                              num_leaves=7, min_data_in_leaf=5)

    full, _ = train(X, y, cfg(12))
    # "interrupted" run: checkpoints every 3, stops at 6
    train(X, y, cfg(6), checkpoint_dir=ck, checkpoint_interval=3)
    # resume to 12 from the newest checkpoint
    resumed, _ = train(X, y, cfg(12), checkpoint_dir=ck,
                       checkpoint_interval=3)
    assert resumed.num_trees == 12
    np.testing.assert_allclose(full.predict_margin(X),
                               resumed.predict_margin(X), atol=1e-4)
    # asking for fewer iterations than already trained returns the model
    again, hist = train(X, y, cfg(10), checkpoint_dir=ck,
                        checkpoint_interval=3)
    assert again.num_trees >= 10 and hist == []


def test_rf_checkpoint_resume_matches_uninterrupted(tmp_path):
    """rf resume (previously rejected): prediction averages over the tree
    count, so any prefix is a valid rf model — and the bag-key stream
    continues from the carried iteration count (global index it+prior),
    so resumed trees use the SAME subsamples the uninterrupted run's
    later iterations draw.  With constant init-margin gradients that
    makes resume EXACTLY equal to the uninterrupted run."""
    X, y = binary_data(n=1500)
    ck = str(tmp_path / "rf_ck")

    def cfg(iters):
        return BoostingConfig(objective="binary", boosting_type="rf",
                              num_iterations=iters, num_leaves=7,
                              min_data_in_leaf=5, bagging_fraction=0.6,
                              bagging_freq=1, seed=3)

    full, _ = train(X, y, cfg(12))
    train(X, y, cfg(6), checkpoint_dir=ck, checkpoint_interval=3)
    resumed, _ = train(X, y, cfg(12), checkpoint_dir=ck,
                       checkpoint_interval=3)
    assert resumed.num_trees == 12
    np.testing.assert_allclose(full.predict_margin(X),
                               resumed.predict_margin(X), atol=1e-4)
    a = auc(y, resumed.predict_margin(X))
    assert a > 0.85, a
    # dart resumes too, with documented-approximate warm-start semantics
    # (pinned in test_checkpoint.py's dart resume test)


def test_checkpoint_estimator_param(tmp_path):
    X, y = binary_data(n=900)
    ds = vec_dataset(X, y)
    ck = str(tmp_path / "est_ck")
    clf = GBDTClassifier(numIterations=8, numLeaves=7, minDataInLeaf=5,
                         numShards=1, checkpointDir=ck, checkpointInterval=4)
    clf.fit(ds)
    import os
    assert any(f.startswith("iter_") for f in os.listdir(ck))
    # dart resumes with documented-approximate warm-start semantics
    # (pinned in test_checkpoint.py's dart resume test)


def test_distributed_lambdarank_matches_single_device():
    """Distributed lambdarank: whole groups pack onto shards (the
    reference's query-rows-share-a-partition rule) and the shard-aware
    objective computes lambdas locally — trees match the single-device
    ranker."""
    from synapseml_tpu.parallel import data_parallel_mesh
    rng = np.random.default_rng(5)
    Q, F = 64, 5
    sizes = rng.integers(4, 16, Q)                  # ragged groups
    n = int(sizes.sum())
    X = rng.normal(size=(n, F)).astype(np.float32)
    rel = np.clip(X[:, 0] * 2 + rng.normal(scale=0.3, size=n), -2, 2)
    y = np.digitize(rel, [-0.5, 0.5, 1.2]).astype(np.float64)
    cfg = BoostingConfig(objective="lambdarank", num_iterations=20,
                         num_leaves=7, learning_rate=0.2, min_data_in_leaf=3)
    b1, _ = train(X, y, cfg, group=sizes)
    b8, _ = train(X, y, cfg, group=sizes, mesh=data_parallel_mesh(8))
    np.testing.assert_allclose(b1.predict_margin(X), b8.predict_margin(X),
                               atol=1e-4)
    # quality holds on the distributed model
    scores = b8.predict_margin(X)
    n_model = ndcg_at(5)(y, scores, sizes)
    n_random = ndcg_at(5)(y, rng.normal(size=n), sizes)
    assert n_model > n_random + 0.1


@pytest.mark.parametrize("mode", ["voting_parallel", "feature_parallel"])
def test_lambdarank_other_parallelism_modes(mode):
    """lambdarank × voting_parallel / feature_parallel (previously
    rejected): voting shards rows like data_parallel so the whole-group
    packing and shard-local lambdas apply unchanged; feature_parallel
    replicates rows so every rank runs the plain in-memory objective.
    Both must beat random ranking and stay close to the single-device
    ranker."""
    from synapseml_tpu.parallel import data_parallel_mesh
    rng = np.random.default_rng(6)
    Q, F = 48, 5
    sizes = rng.integers(4, 14, Q)
    n = int(sizes.sum())
    X = rng.normal(size=(n, F)).astype(np.float32)
    rel = np.clip(X[:, 0] * 2 + rng.normal(scale=0.3, size=n), -2, 2)
    y = np.digitize(rel, [-0.5, 0.5, 1.2]).astype(np.float64)
    kw = dict(objective="lambdarank", num_iterations=15, num_leaves=7,
              learning_rate=0.2, min_data_in_leaf=3)
    b1, _ = train(X, y, BoostingConfig(**kw), group=sizes)
    bp, _ = train(X, y, BoostingConfig(parallelism=mode, top_k=3, **kw),
                  group=sizes, mesh=data_parallel_mesh(8))
    s1 = ndcg_at(5)(y, b1.predict_margin(X), sizes)
    sp = ndcg_at(5)(y, bp.predict_margin(X), sizes)
    s_rand = ndcg_at(5)(y, rng.normal(size=n), sizes)
    assert sp > s_rand + 0.1
    assert sp > s1 - 0.05, (s1, sp)
    if mode == "feature_parallel":
        # replicated rows + the depthwise-matching grower: exact parity
        # with the single-device depthwise ranker
        bd, _ = train(X, y, BoostingConfig(growth_policy="depthwise",
                                           **kw), group=sizes)
        np.testing.assert_allclose(bd.predict_margin(X),
                                   bp.predict_margin(X), atol=1e-4)


def test_streamed_distributed_lambdarank_matches_in_memory(tmp_path):
    """Ranking trains OUT-OF-CORE on the mesh: the binned matrix streams
    from a ChunkedColumnSource in source order and packs whole groups
    onto shards ON DEVICE — NDCG (and margins) match the in-memory
    distributed path (previously rejected with NotImplementedError)."""
    from synapseml_tpu.io.colstore import ChunkedColumnSource, write_matrix
    from synapseml_tpu.parallel import data_parallel_mesh

    rng = np.random.default_rng(9)
    Q, F = 48, 5
    sizes = rng.integers(4, 14, Q)
    n = int(sizes.sum())
    X = rng.normal(size=(n, F)).astype(np.float32)
    rel = np.clip(X[:, 0] * 2 + rng.normal(scale=0.3, size=n), -2, 2)
    y = np.digitize(rel, [-0.5, 0.5, 1.2]).astype(np.float64)
    path = str(tmp_path / "rank.smlc")
    write_matrix(path, np.concatenate(
        [X, y[:, None].astype(np.float32)], axis=1))

    cfg = BoostingConfig(objective="lambdarank", num_iterations=15,
                         num_leaves=7, learning_rate=0.2, min_data_in_leaf=3)
    mesh = data_parallel_mesh(8)
    b_mem, _ = train(X, y, cfg, group=sizes, mesh=mesh)
    src = ChunkedColumnSource(path, label_col=F, chunk_rows=97)
    b_str, _ = train(src, None, cfg, group=sizes, mesh=mesh)
    np.testing.assert_allclose(b_mem.predict_margin(X),
                               b_str.predict_margin(X), atol=1e-4)
    s_mem = ndcg_at(5)(y, b_mem.predict_margin(X), sizes)
    s_str = ndcg_at(5)(y, b_str.predict_margin(X), sizes)
    assert abs(s_mem - s_str) < 1e-6


def test_checkpoint_resume_on_mesh(tmp_path):
    """Checkpoint/resume composes with data-parallel training."""
    from synapseml_tpu.parallel import data_parallel_mesh
    X, y = binary_data(n=1600)
    ck = str(tmp_path / "mesh_ck")
    mesh = data_parallel_mesh(8)

    def cfg(iters):
        return BoostingConfig(objective="binary", num_iterations=iters,
                              num_leaves=7, min_data_in_leaf=5)

    full, _ = train(X, y, cfg(8), mesh=mesh)
    train(X, y, cfg(4), mesh=mesh, checkpoint_dir=ck, checkpoint_interval=2)
    resumed, _ = train(X, y, cfg(8), mesh=mesh, checkpoint_dir=ck,
                       checkpoint_interval=2)
    assert resumed.num_trees == 8
    np.testing.assert_allclose(full.predict_margin(X),
                               resumed.predict_margin(X), atol=1e-4)


def test_ranker_estimator_sharded():
    """GBDTRanker rides the mesh now that distributed lambdarank exists."""
    rng = np.random.default_rng(9)
    Q, D = 48, 12
    X = rng.normal(size=(Q * D, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    qid = np.repeat(np.arange(Q), D)
    ds = Dataset({"features": list(X), "label": y, "query": qid})
    m1 = GBDTRanker(numIterations=8, numLeaves=7, minDataInLeaf=3,
                    groupCol="query", numShards=1).fit(ds)
    m8 = GBDTRanker(numIterations=8, numLeaves=7, minDataInLeaf=3,
                    groupCol="query", numShards=8).fit(ds)
    a = np.asarray(m1.transform(ds)["prediction"])
    b = np.asarray(m8.transform(ds)["prediction"])
    np.testing.assert_allclose(a, b, atol=1e-4)


def test_fused_route_hist_kernel_matches_xla():
    """Round-3 fused kernel (pre-gathered split rows, lane-iota slot mask;
    interpret mode) vs the plain XLA formulation of routing + node hists."""
    import jax.numpy as jnp
    from synapseml_tpu.models.gbdt.pallas_hist import (
        prep_hist_vals, route_and_hist_pallas)
    from synapseml_tpu.models.gbdt.trainer import _build_hist_nodes_xla

    rng = np.random.default_rng(11)
    N, F, B, S = 2048, 9, 64, 16
    bins_t = rng.integers(0, B, (F, N)).astype(np.int32)
    node_id = rng.integers(0, 8, N).astype(np.int32)
    leaf = np.array([1, 3, 5, 7] + [61] * (S - 4), np.int32)   # junk tail
    feat = rng.integers(0, F, S).astype(np.int32)
    thr = rng.integers(0, B, S).astype(np.int32)
    l_id = np.arange(S, dtype=np.int32) * 2 + 8
    r_id = l_id + 1
    grad = rng.normal(size=N).astype(np.float32)
    hess = (np.abs(grad) + 0.1).astype(np.float32)
    mask = (rng.random(N) < 0.8).astype(np.float32)

    vals, scales = prep_hist_vals(jnp.asarray(grad), jnp.asarray(hess),
                                  jnp.asarray(mask))
    # plain-mode universal routing: full range -> degrades to x <= thr;
    # the routing rows arrive pre-gathered (the production caller's take)
    new_id, hists = route_and_hist_pallas(
        jnp.asarray(bins_t), jnp.asarray(node_id), jnp.asarray(leaf),
        jnp.asarray(bins_t[feat]), jnp.asarray(thr),
        jnp.full(S, -1, jnp.int32), jnp.full(S, B, jnp.int32),
        jnp.ones(S, jnp.int32), jnp.asarray(l_id),
        jnp.asarray(r_id), vals, scales, S, B,
        interpret=True)

    exp_id = node_id.copy()
    exp_slot = np.full(N, -1, np.int32)
    for j in range(S):
        inleaf = node_id == leaf[j]
        gl = bins_t[feat[j], :] <= thr[j]
        exp_id = np.where(inleaf, np.where(gl, l_id[j], r_id[j]), exp_id)
        exp_slot = np.where(inleaf & gl, j, exp_slot)
    np.testing.assert_array_equal(np.asarray(new_id), exp_id)
    flat = bins_t + (np.arange(F, dtype=np.int32) * B)[:, None]
    exp_h = np.asarray(_build_hist_nodes_xla(
        jnp.asarray(flat), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(mask), jnp.asarray(exp_slot), S, F, B))
    np.testing.assert_allclose(np.asarray(hists), exp_h, rtol=1e-4, atol=1e-4)


def test_depthwise_pallas_interpret_full_parity():
    """grow_tree_depthwise via the pallas kernels (interpret mode on CPU)
    == the XLA path, at a leaf budget that exercises the route-only final
    wave (31 leaves: the 5th wave fills the budget and must skip its
    histogram build without changing the tree)."""
    import jax.numpy as jnp
    from synapseml_tpu.models.gbdt.trainer import (
        GrowthParams, default_n_slots, grow_tree_depthwise)

    rng = np.random.default_rng(5)
    N, F, B = 8192, 9, 64
    bins_t = rng.integers(0, B, (F, N)).astype(np.int32)
    grad = rng.normal(size=N).astype(np.float32)
    hess = (np.abs(grad) * 0.5 + 0.2).astype(np.float32)
    rv = np.ones(N, np.float32)
    p = GrowthParams(num_leaves=31, min_data_in_leaf=5.0, total_bins=B)
    ub = np.sort(rng.normal(size=(F, B - 1)).astype(np.float32), axis=1)
    nb = np.full(F, B, np.int32)
    args = (jnp.asarray(bins_t), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(rv), jnp.ones(F, bool), jnp.asarray(ub),
            jnp.asarray(nb), 0.1)
    S = default_n_slots(31)
    t_x, nid_x = grow_tree_depthwise(*args, p=p, use_pallas=False, n_slots=S)
    t_p, nid_p = grow_tree_depthwise(*args, p=p, use_pallas="interpret",
                                     n_slots=S)
    np.testing.assert_array_equal(np.asarray(nid_x), np.asarray(nid_p))
    # split_bin/threshold may differ across EMPTY bins (equal-gain ties the
    # bf16 hi/lo histogram resolves differently) — identical routing (nid
    # above) plus identical structure and leaf stats is the semantic pin
    for f in ("split_feature", "left_child", "right_child", "num_nodes"):
        np.testing.assert_array_equal(np.asarray(getattr(t_x, f)),
                                      np.asarray(getattr(t_p, f)), err_msg=f)
    for f in ("leaf_value", "node_value", "node_count"):
        np.testing.assert_allclose(np.asarray(getattr(t_x, f)),
                                   np.asarray(getattr(t_p, f)),
                                   rtol=1e-4, atol=1e-4, err_msg=f)


def test_lgbm_import_missing_type_zero():
    """missing_type=Zero (decision_type bits 2-3 = 1): |x| <= 1e-35 and NaN
    route by the stored default direction, everything else by threshold —
    LightGBM's zero_as_missing semantics, previously rejected."""
    # decision_type = ZERO(1<<2) | default_left(2) = 6 ... default RIGHT = 4
    model = """tree
version=v3
num_class=1
num_tree_per_iteration=1
label_index=0
max_feature_idx=1
objective=regression
feature_names=a b
feature_infos=[-10:10] [-10:10]
tree_sizes=300

Tree=0
num_leaves=3
num_cat=0
split_feature=0 1
split_gain=10 5
threshold=-0.5 1.0
decision_type=6 4
left_child=1 -1
right_child=-3 -2
leaf_value=1 2 4
leaf_weight=0 0 0
leaf_count=0 0 0
internal_value=0 0
internal_weight=0 0
internal_count=0 0
is_linear=0
shrinkage=0.1

end of trees
"""
    b = Booster.from_string(model)
    # node0: a<=-0.5 -> node1, else leaf2=4; a==0/NaN missing -> LEFT (dt=6)
    # node1: b<=1.0 -> leaf0=1, else leaf1=2; b==0/NaN missing -> RIGHT (dt=4)
    X = np.array([
        [-1.0, 0.5],    # a left by threshold, b<=1 -> 1
        [0.0, 0.5],     # a ZERO-missing -> default LEFT; b -> 1
        [0.0, 0.0],     # a missing left; b ZERO-missing -> default RIGHT: 2
        [np.nan, 5.0],  # NaN also missing under Zero -> left; b>1 -> 2
        [1e-40, 3.0],   # |a|<=1e-35 counts as zero-missing -> left; b>1 -> 2
        [0.3, 0.0],     # a > -0.5 by comparison -> leaf2 = 4
    ], np.float32)
    np.testing.assert_allclose(b.predict_margin(X),
                               [1.0, 1.0, 2.0, 2.0, 2.0, 4.0], atol=1e-6)
    # export keeps the Zero bits: a re-imported copy predicts identically
    b2 = Booster.from_string(b.to_string())
    np.testing.assert_allclose(b2.predict_margin(X), b.predict_margin(X),
                               atol=1e-6)
    assert "decision_type=6 4" in b.to_string()


def test_featpar_lossguide_matches_single_device():
    """Strict lossguide growth under feature_parallel (previously
    rejected): the wave grower with one slot per wave IS best-first
    order — one owner-broadcast per split — and grows the EXACT tree the
    single-device lossguide grower does.  Reference bar: the native
    engine accepts tree_learner=feature with its default leaf-wise
    growth (params/BaseTrainParams.scala:99 pass-through)."""
    from synapseml_tpu.parallel import data_parallel_mesh

    X, y = binary_data(n=4096, F=16)
    kw = dict(objective="binary", num_iterations=6, num_leaves=15,
              min_data_in_leaf=5, growth_policy="lossguide")
    b_fp, _ = train(X, y, BoostingConfig(parallelism="feature_parallel",
                                         **kw),
                    mesh=data_parallel_mesh(8))
    b_1, _ = train(X, y, BoostingConfig(**kw))
    np.testing.assert_allclose(b_fp.predict_margin(X),
                               b_1.predict_margin(X), atol=1e-4)
    for t_fp, t_1 in zip(b_fp.trees, b_1.trees):
        np.testing.assert_array_equal(np.asarray(t_fp.split_feature),
                                      np.asarray(t_1.split_feature))


def test_featpar_lossguide_with_efb():
    """lossguide x feature_parallel x EFB: per-rank bundling composes
    with one-slot waves — margins match unbundled single-device
    lossguide."""
    from synapseml_tpu.parallel import data_parallel_mesh

    rng = np.random.default_rng(11)
    n, F = 4096, 24
    X = np.zeros((n, F), np.float32)
    # mostly-exclusive sparse features so bundling actually happens
    owner = rng.integers(0, F // 4, n)
    for j in range(F):
        rows = owner == (j % (F // 4))
        X[rows, j] = rng.normal(size=rows.sum())
    y = (X.sum(axis=1) + rng.normal(scale=0.3, size=n) > 0).astype(np.float64)
    kw = dict(objective="binary", num_iterations=5, num_leaves=15,
              min_data_in_leaf=5, growth_policy="lossguide")
    b_fp, _ = train(X, y, BoostingConfig(parallelism="feature_parallel",
                                         enable_bundle=True, **kw),
                    mesh=data_parallel_mesh(8))
    b_1, _ = train(X, y, BoostingConfig(**kw))
    np.testing.assert_allclose(b_fp.predict_margin(X),
                               b_1.predict_margin(X), atol=1e-4)
