"""Boosting orchestration + the serializable Booster.

Replaces the reference's iteration loop and booster wrapper
(reference: TrainUtils.scala:98-169 executeTrainingIterations/early stop;
booster/LightGBMBooster.scala:212-560 — iterate/predict/feature-importance/
model-string).  Differences by design:

- the per-iteration "histogram build + allreduce + split" that LightGBM does
  in C++ behind ``LGBM_BoosterUpdateOneIter`` is the jitted
  :func:`~synapseml_tpu.models.gbdt.trainer.grow_tree` (psum when sharded);
- scoring is batched XLA traversal, not one JNI call per row
  (LightGBMBooster.scala:394-405 score);
- the model string is JSON of flat tree arrays (saveToString analogue,
  LightGBMBooster.scala:272-284).

Boosting types: gbdt, rf (bagged trees at constant score, averaged), dart
(tree dropout with normalization), goss (gradient one-side sampling inside
the jitted step).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import logging as _logging
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ... import telemetry as _telemetry
from ...parallel.compression import resolve_collective_config
from ...parallel.mesh import DATA_AXIS, batch_sharding, replicated
from . import metrics as metrics_mod
from .binning import BinMapper, FeatureBundler, fit_bin_mapper
from .objectives import (get_objective, initial_score, softmax_grad_hess)
from .trainer import (GrowthParams, Tree, default_n_slots, grow_tree,
                      grow_tree_depthwise, grow_tree_feature_parallel,
                      max_nodes, predict_binned_stacked,
                      predict_raw_features, stack_trees, tree_depth)


@dataclasses.dataclass
class BoostingConfig:
    """TrainParams analogue (reference: params/BaseTrainParams.scala:58-268).
    Field names follow LightGBM's config strings."""
    objective: str = "regression"
    boosting_type: str = "gbdt"            # gbdt | rf | dart | goss
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    max_bin: int = 255
    feature_fraction: float = 1.0
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    seed: int = 0
    num_class: int = 1
    boost_from_average: bool = True
    early_stopping_round: int = 0
    metric: str = ""
    top_rate: float = 0.2                  # goss
    other_rate: float = 0.1                # goss
    drop_rate: float = 0.1                 # dart
    max_drop: int = 50                     # dart
    skip_drop: float = 0.5                 # dart
    scale_pos_weight: float = 1.0
    is_unbalance: bool = False
    alpha: float = 0.9                     # huber / quantile
    tweedie_variance_power: float = 1.5
    fair_c: float = 1.0
    max_position: int = 10                 # lambdarank ndcg@
    label_gain: Optional[List[float]] = None
    bin_sample_count: int = 200_000
    bagging_seed: int = 3
    verbosity: int = -1
    #: data_parallel (histogram psum) | voting_parallel (PV-Tree top-k
    #: vote) | feature_parallel (vertical sharding: local histograms,
    #: gathered best splits, owner-broadcast routing)
    parallelism: str = "data_parallel"
    top_k: int = 20                        # voting-parallel votes per rank
    #: "depthwise": wave growth, all of a level's histograms in one batched
    #: device pass (fast path); "lossguide": strict best-first leaf-wise
    #: (LightGBM's exact growth order).  voting_parallel implies lossguide.
    growth_policy: str = "depthwise"
    #: two-level (coarse-then-refine) histograms for wide-bin depthwise
    #: growth: "auto" (on at >= 500k global rows), "on", "off".
    #: Histograms build at coarse (bin >> TWO_LEVEL_SHIFT, currently
    #: >> 3) resolution; the top
    #: ``refine_features`` features — chosen once per TREE from the
    #: root's coarse gains — are refined at full resolution every wave.
    #: Faster wide-bin training; split quality is preserved unless a
    #: feature outside the root-chosen top-K wins only on a
    #: sub-coarse-boundary cut.  Implemented for depthwise (fused wave
    #: kernel) AND strict leaf-wise growth (per-split nodes-kernel
    #: builds); structurally off for EFB, monotone constraints,
    #: voting/feature parallelism, max_bin < 127
    two_level_hist: str = "auto"
    #: features refined at full resolution under two_level_hist
    refine_features: int = 8
    #: exclusive feature bundling: merge rarely-co-nonzero (binned)
    #: features into shared HISTOGRAM columns — the sparse/one-hot
    #: densification strategy (LightGBM enable_bundle).  Bundling only
    #: compresses histogram construction; split search, routing, and the
    #: trees stay in ORIGINAL feature space, so predict/SHAP/LightGBM
    #: export/monotone constraints/dart and ALL THREE parallelism modes
    #: work unchanged (feature_parallel bundles each rank's slice
    #: independently — bundles never cross rank boundaries).
    enable_bundle: bool = False
    max_conflict_rate: float = 0.0
    #: feature indexes holding category codes (categoricalSlotIndexes,
    #: params/LightGBMParams.scala): binned by target-statistic order so
    #: bin-range splits act as category-subset splits; such models predict
    #: through bin space (no raw-threshold semantics)
    categorical_feature: Optional[List[int]] = None
    #: per-feature monotone direction {-1, 0, +1} (monotoneConstraints,
    #: params/LightGBMParams.scala:168-183): +1 forces predictions
    #: non-decreasing in the feature, -1 non-increasing.  Implemented
    #: method: "basic" (LightGBM's default) — violating splits discarded,
    #: child outputs clamped by bounds propagated down the tree
    monotone_constraints: Optional[List[int]] = None
    monotone_constraints_method: str = "basic"
    #: gain penalization for constrained-feature splits near the root
    #: (monotonePenalty, BaseTrainParams.scala:128-130): 1 forbids them at
    #: the root, larger values reach deeper
    monotone_penalty: float = 0.0
    #: wire codec for the data-parallel histogram allreduce (EQuARX,
    #: arXiv:2506.17615): "none" (default, byte-identical to the f32
    #: path) | "bf16" | "int8" | a full
    #: :class:`~synapseml_tpu.parallel.compression.CollectiveConfig`.
    #: Stateless per histogram (no error feedback — histograms are
    #: re-derived per split, not an accumulating stream); every rank
    #: decodes identical bytes so trees stay identical across ranks.
    #: Ignored by voting/feature parallelism (their collectives are
    #: already top-k-sparse or local) and by single-device fits.
    collective_compression: Any = "none"
    #: fused bf16 histogram ingest: the objective's grad/hess fuse into
    #: the boosting step and materialize as ONE bf16 array pair instead
    #: of (n_rows,) f32 each — every per-wave histogram build then reads
    #: half the g/h bytes, and the f32 g/h arrays never exist between
    #: the objective and the histogram kernel (compute-and-quantize;
    #: accumulation stays f32/int32 so bin sums are exact over the
    #: rounded values).  "auto" (default) = on; False restores the f32
    #: ingest bit-for-bit.  NOT bit-identical to the f32 ingest — tier-1
    #: pins fused-vs-unfused holdout-AUC parity and preempt->resume
    #: bit-exactness WITH the fused path on.  A checkpoint records its
    #: ingest (the resume guard below refuses a silent fused/unfused mix
    #: mid-model).
    fused_ingest: Any = "auto"
    pass_through: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def growth_params(self, num_features: int = 0) -> GrowthParams:
        mono = None
        if self.monotone_constraints and any(self.monotone_constraints):
            mono = tuple(int(c) for c in self.monotone_constraints)
        hist_chunk = 0
        if num_features:
            hist_chunk = _tuned_hist_chunk(
                int(num_features), self.max_bin + 1,
                default_n_slots(self.num_leaves))
        return GrowthParams(
            hist_chunk=hist_chunk,
            num_leaves=self.num_leaves,
            max_depth=self.max_depth,
            min_data_in_leaf=float(self.min_data_in_leaf),
            min_sum_hessian_in_leaf=self.min_sum_hessian_in_leaf,
            lambda_l1=self.lambda_l1,
            lambda_l2=self.lambda_l2,
            min_gain_to_split=self.min_gain_to_split,
            total_bins=self.max_bin + 1,
            voting_k=self.top_k if self.parallelism == "voting_parallel" else 0,
            monotone_constraints=mono,
            monotone_penalty=float(self.monotone_penalty),
            monotone_method=self.monotone_constraints_method,
            two_level=({True: "on", False: "off"}.get(
                self.two_level_hist, str(self.two_level_hist))),
            refine_k=int(self.refine_features),
        )


def _round_ingest(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` rounded to bfloat16, as the fused ingest states gradients
    enter a histogram.  ``reduce_precision`` first: under
    ``xla_allow_excess_precision`` XLA drops a bare cast to bfloat16 and
    back wherever the objective's chain fuses into the consumer, and
    which consumers it fuses into moves with the grower's program (PR 32:
    the trees of the boosting cell changed with the layout of the value
    matrix until the rounding was made explicit).  The cast after it is
    exact and keeps the materialized arrays at half the bytes."""
    return lax.reduce_precision(
        x, exponent_bits=8, mantissa_bits=7).astype(jnp.bfloat16)


def _tuned_hist_chunk(num_features: int, total_bins: int,
                      n_slots: int) -> int:
    """Tuned rows-per-chunk for the Pallas histogram kernels, or 0.

    Only a ``gbdt_hist_chunk`` tuning-table entry measured on THIS device
    at exactly this (features, total_bins) geometry applies, and only when
    ``hist_chunk_ok`` re-admits the chunk for the slot count this fit will
    use; anything else keeps the ``_tile_for`` ladder default, so fits
    without a table dispatch byte-identical programs."""
    try:
        from ...telemetry.tunetable import geometry_key, get_tuneplane
        from .pallas_hist import hist_chunk_ok

        def _gate(winner):
            c = winner.get("chunk")
            return (isinstance(c, int) and not isinstance(c, bool)
                    and hist_chunk_ok(num_features, total_bins, n_slots, c))

        won = get_tuneplane().consult(
            "BoostingConfig.growth_params", "gbdt_hist_chunk",
            geometry_key(features=int(num_features),
                         total_bins=int(total_bins)),
            validate=_gate)
        if won is not None:
            return int(won["chunk"])
    except Exception:
        pass
    return 0


class Booster:
    """Trained model: host-resident flat tree arrays + binning metadata.
    Serializable to a JSON model string (LightGBMBooster.saveToString
    analogue)."""

    def __init__(self, trees: List[Tree], tree_class: List[int],
                 tree_weights: List[float], num_class: int, objective: str,
                 init_score: np.ndarray, bin_mapper: BinMapper,
                 feature_names: List[str], config: BoostingConfig,
                 best_iteration: int = -1,
                 bundler: Optional[FeatureBundler] = None):
        self.trees = [Tree(*[np.asarray(a) for a in t]) for t in trees]
        self.tree_class = list(tree_class)
        self.tree_weights = list(tree_weights)
        self.num_class = num_class
        self.objective = objective
        self.init_score = np.asarray(init_score, np.float32).reshape(-1)
        self.bin_mapper = bin_mapper
        self.feature_names = list(feature_names)
        self.config = config
        self.best_iteration = best_iteration
        self.bundler = bundler

    # -- prediction --------------------------------------------------------
    @property
    def num_trees(self) -> int:
        return len(self.trees)

    def depth_bound(self) -> int:
        return max((tree_depth(t) for t in self.trees), default=1)

    def _stacked_for_class(self, k: int, num_iteration: Optional[int]) -> Optional[Tree]:
        sel = [i for i, c in enumerate(self.tree_class) if c == k]
        if num_iteration is not None and num_iteration >= 0:
            sel = sel[:num_iteration]
        if not sel:
            return None
        trees = []
        for i in sel:
            t = self.trees[i]
            w = self.tree_weights[i]
            trees.append(t._replace(leaf_value=t.leaf_value * np.float32(w)))
        return stack_trees(trees)

    def predict_margin(self, features: np.ndarray,
                       num_iteration: Optional[int] = None,
                       return_leaves: bool = False):
        """Raw margin (n,) or (n, K); batched XLA traversal."""
        features = np.ascontiguousarray(features, np.float32)
        n = features.shape[0]
        depth = self.depth_bound()
        bundled = None
        if self.bin_mapper.has_categorical:
            if _placeholder_mapper(self.bin_mapper):
                # imported LightGBM categorical model: numeric bounds are
                # placeholders so numeric nodes keep RAW thresholds, while
                # categorical columns map to their (float) bin ids — the
                # import already rewrote cat thresholds to bin space, so
                # one uniform x <= thr traversal serves both node kinds
                features = self._cat_columns_to_bins(features)
            else:
                # categorical models split in (ORIGINAL) bin space: bin,
                # then traverse by split_bin instead of raw thresholds.
                # EFB models need nothing special — bundling only
                # compresses histogram construction; their trees live in
                # original feature space with raw thresholds (the
                # LightGBM scheme)
                binned = self.bin_mapper.transform(features)
                bundled = jnp.asarray(binned.astype(np.int32))
        outs, leaves = [], []
        for k in range(self.num_class):
            stacked = self._stacked_for_class(k, num_iteration)
            if stacked is None:
                outs.append(np.full(n, self.init_score[min(k, len(self.init_score) - 1)],
                                    np.float32))
                leaves.append(np.zeros((0, n), np.int32))
                continue
            if bundled is not None:
                total, lv = predict_binned_stacked(bundled, stacked, depth)
            else:
                total, lv = predict_raw_features(features, stacked, depth)
            base = self.init_score[min(k, len(self.init_score) - 1)]
            total = np.asarray(total) + base
            if self.config.boosting_type == "rf":
                ntree = stacked.split_feature.shape[0]
                total = base + (np.asarray(total) - base) / max(ntree, 1)
            outs.append(np.asarray(total))
            leaves.append(np.asarray(lv))
        margin = outs[0] if self.num_class == 1 else np.stack(outs, axis=1)
        if return_leaves:
            return margin, leaves
        return margin

    def _cat_columns_to_bins(self, features: np.ndarray) -> np.ndarray:
        """Imported-model hybrid view: categorical columns become their
        bin ids (floats); numeric columns pass through unchanged.  Unseen
        categories and NaN land in bin 0, which every bin-space split
        (bin <= t, t >= 0) sends left — the exported complement-bitset
        convention's missing direction."""
        out = features.copy()
        for f, (vals, bins) in (self.bin_mapper.cat_features or {}).items():
            col = features[:, f]
            if len(vals) == 0:
                out[:, f] = 0.0
                continue
            idx = np.searchsorted(vals, col)
            idx_c = np.minimum(idx, len(vals) - 1)
            hit = np.asarray(vals)[idx_c] == col
            out[:, f] = np.where(hit, np.asarray(bins)[idx_c], 0)
        return out

    def predict_leaf(self, features: np.ndarray) -> np.ndarray:
        """Per-tree leaf index (n, num_trees) — predictLeaf analogue
        (LightGBMBooster.scala:407)."""
        _, leaves = self.predict_margin(features, return_leaves=True)
        return np.concatenate([l for l in leaves if l.size], axis=0).T

    def to_proba(self, margin: np.ndarray) -> np.ndarray:
        if self.objective in ("multiclass", "multiclassova"):
            if self.objective == "multiclassova":
                p = 1.0 / (1.0 + np.exp(-margin))
                return p / np.maximum(p.sum(1, keepdims=True), 1e-12)
            m = margin - margin.max(axis=1, keepdims=True)
            e = np.exp(m)
            return e / e.sum(axis=1, keepdims=True)
        p1 = 1.0 / (1.0 + np.exp(-margin))
        return np.stack([1 - p1, p1], axis=1)

    def predict_contrib(self, features: np.ndarray,
                        approximate: bool = False) -> np.ndarray:
        """Per-feature contributions + bias — the featuresShap analogue
        (LightGBMBooster.featuresShap): EXACT TreeSHAP (Lundberg
        polynomial algorithm over the per-node covers) by default;
        ``approximate=True`` selects Saabas path attribution, which is
        also the automatic fallback for models without cover counts
        (old serialized models, LightGBM imports lacking
        ``internal_count``).

        Returns (n, F+1) for single-output models, (n, K*(F+1)) for
        multiclass (last slot of each block = bias)."""
        # categorical models split in BIN space (target-ordered category
        # bins); SHAP runs over the binned matrix with split_bin routing —
        # exact, since binning is a per-feature transform.  EFB models
        # need nothing special: their trees live in original feature space
        imported_cat = (self.bin_mapper.has_categorical
                        and _placeholder_mapper(self.bin_mapper))
        bin_space = self.bin_mapper.has_categorical and not imported_cat
        if imported_cat:
            # imported categorical model: hybrid view (cat columns as bin
            # ids, numeric raw) with thresholds already rewritten at import
            features = self._cat_columns_to_bins(
                np.ascontiguousarray(features, np.float32))
        from .shap import has_cover_counts, tree_shap_values
        if not approximate and has_cover_counts(self):
            return tree_shap_values(self, features, bin_space=bin_space)
        features = np.ascontiguousarray(features, np.float32)
        if bin_space:
            features = self.bin_mapper.transform(features).astype(np.float32)
        n = features.shape[0]
        F = self.bin_mapper.num_features
        out = np.zeros((n, self.num_class, F + 1), np.float64)
        rows = np.arange(n)
        for i, t in enumerate(self.trees):
            k = self.tree_class[i]
            w = self.tree_weights[i]
            if self.config.boosting_type == "rf":
                cls_count = max(sum(1 for c in self.tree_class if c == k), 1)
                w = w / cls_count
            nv = t.node_value.astype(np.float64)
            cur = np.zeros(n, np.int64)
            out[:, k, F] += nv[0] * w
            for _ in range(tree_depth(t)):
                feat = t.split_feature[cur]
                internal = feat >= 0
                if not internal.any():
                    break
                f = np.maximum(feat, 0)
                x = features[rows, f]
                if bin_space:
                    go_left = x <= np.asarray(t.split_bin)[cur]
                else:
                    miss = np.isnan(x) | (np.asarray(t.missing_zero)[cur]
                                          & (np.abs(x) <= 1e-35))
                    go_left = np.where(miss, t.default_left[cur],
                                       x <= t.threshold[cur])
                nxt = np.where(go_left, t.left_child[cur], t.right_child[cur])
                nxt = np.where(internal, nxt, cur)
                delta = (nv[nxt] - nv[cur]) * w
                np.add.at(out, (rows[internal], np.full(internal.sum(), k),
                                f[internal]), delta[internal])
                cur = nxt
        out[:, :, F] += self.init_score[:self.num_class][None, :]
        if self.num_class == 1:
            return out[:, 0, :]
        return out.reshape(n, -1)

    # -- introspection -----------------------------------------------------
    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        """Split counts or total gains per ORIGINAL feature
        (getFeatureImportances analogue, LightGBMBooster.scala); bundled
        splits map back to the original feature owning the split bin."""
        out = np.zeros(len(self.feature_names), np.float64)
        for t in self.trees:
            internal = np.nonzero(np.asarray(t.split_feature) >= 0)[0]
            for node in internal:
                f = int(t.split_feature[node])
                w = (1.0 if importance_type == "split"
                     else float(t.split_gain[node]))
                out[f] += w
        return out

    # -- serialization -----------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return {
            "version": 2,
            "num_class": self.num_class,
            "objective": self.objective,
            "init_score": self.init_score.tolist(),
            "feature_names": self.feature_names,
            "tree_class": self.tree_class,
            "tree_weights": self.tree_weights,
            "best_iteration": self.best_iteration,
            "config": dataclasses.asdict(self.config),
            "bin_mapper": {
                "upper_bounds": self.bin_mapper.upper_bounds.tolist(),
                "num_bins": self.bin_mapper.num_bins.tolist(),
                "max_bin": self.bin_mapper.max_bin,
                "cat_features": {
                    str(f): [v.tolist(), b.tolist()]
                    for f, (v, b) in (self.bin_mapper.cat_features or {}).items()
                } or None,
            },
            "bundler": self.bundler.to_dict() if self.bundler else None,
            "trees": [{f: np.asarray(getattr(t, f)).tolist() for f in Tree._fields}
                      for t in self.trees],
        }

    def to_string(self) -> str:
        """LightGBM text model format (saveToString parity,
        LightGBMBooster.scala:272-284) — loadable by any LightGBM runtime.
        Categorical splits export as native bitset thresholds (the
        complement set with children swapped, so unseen/missing categories
        route identically); the JSON form (:meth:`to_dict`) remains the
        internal format."""
        from .lgbm_format import booster_to_lgbm_string
        return booster_to_lgbm_string(self)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "Booster":
        cfg_d = dict(d["config"])
        cfg = BoostingConfig(**{k: v for k, v in cfg_d.items()
                                if k in {f.name for f in dataclasses.fields(BoostingConfig)}})
        cat_raw = d["bin_mapper"].get("cat_features")
        bm = BinMapper(
            upper_bounds=np.asarray(d["bin_mapper"]["upper_bounds"], np.float32),
            num_bins=np.asarray(d["bin_mapper"]["num_bins"], np.int32),
            max_bin=d["bin_mapper"]["max_bin"],
            cat_features={int(f): (np.asarray(v, np.float32),
                                   np.asarray(b, np.int32))
                          for f, (v, b) in cat_raw.items()}
            if cat_raw else None)
        trees = []
        for td in d["trees"]:
            trees.append(Tree(
                split_feature=np.asarray(td["split_feature"], np.int32),
                split_bin=np.asarray(td["split_bin"], np.int32),
                threshold=np.asarray(td["threshold"], np.float32),
                split_gain=np.asarray(td["split_gain"], np.float32),
                left_child=np.asarray(td["left_child"], np.int32),
                right_child=np.asarray(td["right_child"], np.int32),
                leaf_value=np.asarray(td["leaf_value"], np.float32),
                node_value=np.asarray(td["node_value"], np.float32),
                num_nodes=np.asarray(td["num_nodes"], np.int32),
                default_left=np.asarray(
                    td.get("default_left",
                           np.ones(len(td["leaf_value"]), bool)), bool),
                node_count=np.asarray(
                    td.get("node_count",
                           np.zeros(len(td["leaf_value"]))), np.float32),
                missing_zero=np.asarray(
                    td.get("missing_zero",
                           np.zeros(len(td["leaf_value"]), bool)), bool)))
        if d.get("bundler") and int(d.get("version", 1)) < 2:
            raise ValueError(
                "this EFB model was saved by a pre-round-3 build whose "
                "bundled trees split BUNDLED columns; round 3 stores "
                "original-feature trees (the LightGBM scheme) — re-train "
                "the model")
        bundler = (FeatureBundler.from_dict(d["bundler"])
                   if d.get("bundler") else None)
        return Booster(trees, d["tree_class"], d["tree_weights"], d["num_class"],
                       d["objective"], np.asarray(d["init_score"], np.float32),
                       bm, d["feature_names"], cfg, d["best_iteration"],
                       bundler=bundler)

    @staticmethod
    def from_string(s: str) -> "Booster":
        """Parse either format: LightGBM text models (native interop,
        LightGBMClassifier.scala:196-211) or the internal JSON."""
        if s.lstrip().startswith("{"):
            return Booster.from_dict(json.loads(s))
        from .lgbm_format import booster_from_lgbm_string
        return booster_from_lgbm_string(s)

    @staticmethod
    def from_file(path: str) -> "Booster":
        """loadNativeModelFromFile analogue (LightGBMClassifier.scala:196)."""
        with open(path) as f:
            return Booster.from_string(f.read())


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

def _step_factory_args(config: "BoostingConfig", K: int, mesh, featpar: bool,
                       use_pallas, objective_fn=None, num_features: int = 0):
    """The exact ``_make_step`` (args, kwargs) — built in ONE place so the
    warm-compile thread and the training loop hit the same lru_cache entry
    (any drift would silently compile a program that is never used).
    ``objective_fn`` overrides the cached-factory objective (lambdarank)."""
    if objective_fn is None and K == 1 and config.objective != "lambdarank":
        obj_kwargs = {}
        if config.objective in ("huber", "quantile"):
            obj_kwargs["alpha"] = config.alpha
        elif config.objective == "fair":
            obj_kwargs["c"] = config.fair_c
        elif config.objective == "tweedie":
            obj_kwargs["rho"] = config.tweedie_variance_power
        # cached factory -> stable function identity, so the _make_step
        # cache hits across train() calls even with objective kwargs
        objective_fn = _objective_with_kwargs(
            config.objective, tuple(sorted(obj_kwargs.items())))
    is_rf = config.boosting_type == "rf"
    use_bagging = (config.bagging_fraction < 1.0
                   and (is_rf or config.bagging_freq > 0))
    args = (config.growth_params(num_features=num_features if use_pallas
                                 else 0), objective_fn, K,
            1.0 if is_rf else config.learning_rate, mesh,
            config.boosting_type == "goss",
            config.top_rate, config.other_rate)
    # compressed histogram wire applies only where the histogram psum
    # exists: data-parallel growth over a real mesh (voting aggregates
    # top-k-sparse, feature_parallel keeps histograms local)
    cconfig = resolve_collective_config(config.collective_compression)
    if _hist_psum_nulled(config, mesh is not None):
        cconfig = None
    kwargs = dict(ova=(config.objective == "multiclassova"),
                  use_pallas=use_pallas,
                  growth_policy=config.growth_policy,
                  feature_parallel=featpar,
                  bundled_featpar=bool(featpar and config.enable_bundle),
                  bagging_fraction=(config.bagging_fraction
                                    if use_bagging else 1.0),
                  cconfig=cconfig,
                  fused_ingest=_fused_ingest_on(config))
    return args, kwargs


def _fused_ingest_on(config: "BoostingConfig") -> bool:
    """Resolve the ``fused_ingest`` knob ("auto" = on) — THE predicate
    both the step factory and the resume guard consult, so a checkpoint
    stamped by one can never disagree with the program the other
    builds."""
    v = config.fused_ingest
    if v in ("auto", "on", True):
        return True
    if v in ("off", False):
        return False
    raise ValueError(f"fused_ingest={v!r}: must be 'auto', 'on', 'off', "
                     "True or False")


#: iterations per scanned dispatch — the whole-run loop runs as
#: ceil(T / SCAN_CHUNK) dispatches of ONE compiled program (the chunk
#: length is static but the iteration offset is a traced operand, so the
#: program is independent of num_iterations and the compile cache hits
#: across runs of any length).  25 divides LightGBM's default 100.
SCAN_CHUNK = 25


@functools.lru_cache(maxsize=16)
def _make_scan(sargs, skw_items, bagging_freq: int,
               seed: int, is_rf: bool, cache_step: bool = True):
    """Chunk-of-the-training-run program: ``lax.scan`` over the step.

    The per-iteration Python loop pays three host dispatches per tree
    (fold_in + PRNGKey + step); the scan runs SCAN_CHUNK iterations per
    dispatch.  Key derivation matches the Python loop
    exactly (PRNGKey(seed·100003 + it) under 32-bit seeds;
    fold_in(bag_root, it // bagging_freq)), so scanned and looped training
    grow identical trees.  Used for the common fire-and-forget path; dart /
    per-iteration validation / callbacks / checkpoints stay on the Python
    loop, which needs each tree on the host mid-run.
    """
    # lambdarank's objective closes over per-dataset arrays: caching the
    # step would pin them (same reason train() bypasses _make_step's cache)
    maker = _make_step if cache_step else _make_step.__wrapped__
    step = maker(*sargs, **dict(skw_items))
    freq = max(bagging_freq, 1)
    seed_base = (seed * 100003) & 0xffffffff

    def run(bins_t, scores, labels, weights, base_bag, bag_root_key,
            fmask, upper_bounds, num_bins, bundle_map, init_scores, it0):
        def body(sc, it):
            bag_key = jax.random.fold_in(bag_root_key, it // freq)
            key = jax.random.PRNGKey(jnp.uint32(seed_base)
                                     + it.astype(jnp.uint32))
            tstack, new_sc = step(bins_t, sc, labels, weights,
                                  (base_bag, bag_key), fmask, key,
                                  upper_bounds, num_bins, bundle_map)
            if is_rf:
                new_sc = init_scores   # rf: gradients stay at init margin
            return new_sc, tstack
        return lax.scan(body, scores, jnp.arange(SCAN_CHUNK) + it0)
    return jax.jit(run)


#: module-level jit (an inline jit(lambda) would recompile every train()):
#: flattens every chunk's tree stack into one f32 vector for ONE readback
_pack_flat = jax.jit(lambda cs: jnp.concatenate(
    [a.astype(jnp.float32).reshape(-1) for ts in cs for a in ts]))


@functools.lru_cache(maxsize=None)
def _objective_with_kwargs(name, kwargs_items):
    """Objective + frozen kwargs as a STABLE function object, so the
    _make_step cache below keys on something that repeats across calls."""
    base = get_objective(name)
    if not kwargs_items:
        return base
    kw = dict(kwargs_items)
    return lambda s, l, ww: base(s, l, ww, **kw)


@functools.lru_cache(maxsize=16)
def _make_step(p: GrowthParams, objective_fn, num_class: int,
               learning_rate: float, mesh: Optional[Mesh], use_goss: bool,
               top_rate: float, other_rate: float, ova: bool = False,
               use_pallas: bool = False, bagging_fraction: float = 1.0,
               growth_policy: str = "depthwise",
               feature_parallel: bool = False,
               bundled_featpar: bool = False,
               cconfig=None, fused_ingest: bool = True):
    """Build the jitted one-iteration step.

    step(binned, scores, labels, weights, (base_bag, bag_key),
         feature_mask, key, upper_bounds, num_bins, bundle_map)
      -> (trees, new_scores)

    Bagging happens ON DEVICE: ``base_bag`` is the constant pad-row mask
    and the per-iteration row subsample is drawn from ``bag_key`` when
    ``bagging_fraction < 1`` — no per-iteration host mask upload.  Passing
    the same bag_key across iterations reproduces bagging_freq persistence.
    Each shard folds its mesh index into the key, so bagged models are
    deterministic for a fixed mesh size but differ across mesh sizes
    (the unbagged paths remain mesh-invariant).

    For num_class==1 labels are float targets; for multiclass labels are
    int class ids and scores are (N, K).
    """
    axis = DATA_AXIS if mesh is not None else None
    if feature_parallel:
        # strict lossguide order under vertical sharding = the wave
        # grower with ONE slot per wave: the top-1 "wave" is exactly the
        # best-first split, at the cost of one owner-broadcast per SPLIT
        # instead of per level (the native engine's tree_learner=feature
        # runs its default leaf-wise growth the same way)
        fp_slots = (1 if growth_policy == "lossguide"
                    else default_n_slots(p.num_leaves))
        grower = functools.partial(grow_tree_feature_parallel,
                                   n_slots=fp_slots)
    elif growth_policy == "depthwise" and p.voting_k == 0:
        grower = functools.partial(grow_tree_depthwise,
                                   n_slots=default_n_slots(p.num_leaves),
                                   cconfig=cconfig)
    else:
        # lossguide / voting-parallel (the grower itself skips the
        # compressed wire on its voting collectives)
        grower = functools.partial(grow_tree, cconfig=cconfig)

    def goss_weights(g_abs, bag, key):
        """Gradient one-side sampling: keep top_rate by |grad|, sample
        other_rate of the rest with amplification (1-a)/b.  k is computed
        from the REAL (bag>0) row count so pallas pad rows don't distort
        the top-k threshold."""
        n = g_abs.shape[0]
        n_real = jnp.sum((bag > 0).astype(jnp.int32))
        k = jnp.maximum(1, (n_real.astype(jnp.float32) * top_rate).astype(jnp.int32))
        sorted_desc = -jnp.sort(-(g_abs * (bag > 0)))
        thresh = sorted_desc[jnp.minimum(k - 1, n - 1)]
        topset = g_abs >= thresh
        rest_keep = jax.random.uniform(key, (n,)) < other_rate
        amp = (1.0 - top_rate) / jnp.maximum(other_rate, 1e-6)
        return jnp.where(topset, 1.0, jnp.where(rest_keep, amp, 0.0)) * bag

    def one_step(bins_t, scores, labels, weights, bag_in, feature_mask,
                 key, upper_bounds, num_bins, bundle_map=None):
        base_bag, bag_key = bag_in
        if bagging_fraction < 1.0:
            # feature-parallel replicates rows: every rank must draw the
            # SAME bag; data-parallel ranks each own distinct rows
            if axis is not None and not feature_parallel:
                bag_key = jax.random.fold_in(bag_key, lax.axis_index(axis))
            bag_mask = base_bag * (
                jax.random.uniform(bag_key, base_bag.shape)
                < bagging_fraction).astype(jnp.float32)
        else:
            bag_mask = base_bag
        trees = []
        if num_class == 1:
            grad, hess = objective_fn(scores, labels, weights)
            rv = bag_mask
            if use_goss:
                # GOSS ranks |grad| at full f32 resolution, BEFORE the
                # ingest quantization below
                rv = goss_weights(jnp.abs(grad), bag_mask, key)
            if fused_ingest:
                # fused bf16 ingest: the objective's elementwise chain
                # fuses straight into this rounding, so the ONLY
                # materialized g/h arrays are bf16 — every histogram
                # build (all waves of the tree) reads half the bytes;
                # bin accumulation promotes back to f32, exact over the
                # rounded values
                grad, hess = _round_ingest(grad), _round_ingest(hess)
            tree, node_id = grower(bins_t, grad, hess, rv, feature_mask,
                                   upper_bounds, num_bins, learning_rate,
                                   p, axis, use_pallas,
                                   bundle_map=bundle_map)
            new_scores = scores + tree.leaf_value[node_id]
            trees.append(tree)
        else:
            onehot = jax.nn.one_hot(labels.astype(jnp.int32), num_class)
            if ova:
                # multiclassova: independent per-class sigmoid losses
                pk = jax.nn.sigmoid(scores)
                grad = (pk - onehot) * weights[:, None]
                hess = jnp.maximum(pk * (1.0 - pk), 1e-16) * weights[:, None]
            else:
                grad, hess = softmax_grad_hess(scores, onehot, weights)
            g_hist, h_hist = grad, hess
            if fused_ingest:       # see the single-class branch above
                g_hist, h_hist = _round_ingest(grad), _round_ingest(hess)
            new_scores = scores
            for k in range(num_class):
                rv = bag_mask
                if use_goss:
                    rv = goss_weights(jnp.abs(grad[:, k]), bag_mask,
                                      jax.random.fold_in(key, k))
                tree, node_id = grower(bins_t, g_hist[:, k], h_hist[:, k],
                                       rv, feature_mask, upper_bounds,
                                       num_bins, learning_rate, p, axis,
                                       use_pallas, bundle_map=bundle_map)
                new_scores = new_scores.at[:, k].add(tree.leaf_value[node_id])
                trees.append(tree)
        return stack_trees(trees), new_scores

    if mesh is None:
        return jax.jit(one_step)

    ndim_scores = 1 if num_class == 1 else 2
    if feature_parallel:
        # vertical sharding: FEATURES split over the axis, rows replicated.
        # Under EFB the per-rank route tables shard on their (stacked)
        # original-feature axis exactly like bounds/nbins
        bm_spec = ({"col": P(DATA_AXIS), "lo": P(DATA_AXIS),
                    "hi": P(DATA_AXIS), "default_bin": P(DATA_AXIS),
                    "gather_src": P(DATA_AXIS, None)}
                   if bundled_featpar else P())
        in_specs = (P(DATA_AXIS, None),                    # bins_t (Fb, N)
                    P(), P(), P(),                         # scores/labels/w
                    (P(), P()),                            # (base_bag, key)
                    P(DATA_AXIS), P(),                     # fmask/key
                    P(DATA_AXIS, None), P(DATA_AXIS),      # bounds/nbins
                    bm_spec)                               # route tables
        out_specs = (P(), P())                             # all replicated
    else:
        in_specs = (P(None, DATA_AXIS),                    # bins_t (F, N)
                    P(DATA_AXIS) if ndim_scores == 1 else P(DATA_AXIS, None),
                    P(DATA_AXIS), P(DATA_AXIS),            # labels/weights
                    (P(DATA_AXIS), P()),                   # (base_bag, bag_key)
                    P(), P(), P(), P(), P())   # fmask/key/bounds/nbins/bundle
        out_specs = (P(),                                  # trees replicated
                     P(DATA_AXIS) if ndim_scores == 1 else P(DATA_AXIS, None))
    return jax.jit(jax.shard_map(one_step, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


@functools.partial(jax.jit, static_argnames=("depth_bound",))
def _predict_binned_tree(bins_t, tree: Tree, depth_bound: int,
                         bundle_map=None, total_bins: int = 1 << 20):
    """Leaf values of one tree on (F, N) binned features (dart/valid eval).

    ``bundle_map``: when the device matrix is EFB-BUNDLED, trees still
    live in ORIGINAL feature space — each node's split routes through the
    same universal form training uses (``x in (rlo, rhi] ? x <= t1 :
    default``, trainer._slot_route_params), so dart rescoring traverses
    the bundled matrix exactly."""
    from .trainer import _route_left, _slot_route_params

    N = bins_t.shape[1]
    rows = jnp.arange(N)

    def step(_, node):
        feat = tree.split_feature[node]
        is_leaf = feat < 0
        f = jnp.maximum(feat, 0)
        col, t1, rlo, rhi, dflt = _slot_route_params(
            f, tree.split_bin[node], total_bins, bundle_map)
        go_left = _route_left(bins_t[col, rows], t1, rlo, rhi, dflt)
        child = jnp.where(go_left, tree.left_child[node], tree.right_child[node])
        return jnp.where(is_leaf, node, child)

    leaf = lax.fori_loop(0, depth_bound, step, jnp.zeros(N, jnp.int32))
    return tree.leaf_value[leaf]


@dataclasses.dataclass
class EvalRecord:
    iteration: int
    metric: str
    value: float


@dataclasses.dataclass
class InstrumentationMeasures:
    """Per-phase wall-clock training instrumentation (reference:
    TaskInstrumentationMeasures / InstrumentationMeasures,
    lightgbm/.../LightGBMPerformance.scala:11-111).  Attached to the
    trained Booster as ``.measures`` and surfaced by the estimators."""
    binning_s: float = 0.0            # bin-mapper fit + transform (sampling)
    data_prep_s: float = 0.0          # labels/weights/padding/device put
    compile_s: float = 0.0            # first-iteration jit compile + run
    training_s: float = 0.0           # whole boosting loop
    eval_s: float = 0.0               # validation metric evaluation
    iterations: int = 0
    total_s: float = 0.0
    #: which histogram builder the fit's step program traced: ``"pallas"``
    #: (the Mosaic kernels; TPU with a fitting geometry) or
    #: ``"xla_scatter"`` (every other backend, and a geometry miss) — the
    #: choice is made from the platform and the shapes, silently, so the
    #: outcome is recorded where a caller can assert it
    hist_path: str = ""

    def iterations_per_sec(self) -> float:
        post = self.training_s - self.compile_s
        steady = max(self.iterations - 1, 1)
        return steady / post if post > 0 else 0.0

    def as_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["iterations_per_sec"] = self.iterations_per_sec()
        return d


def _hist_psum_nulled(config: "BoostingConfig", mesh_present: bool) -> bool:
    """True where the data-parallel histogram psum does not exist (no
    mesh, feature/voting parallelism) — THE predicate for 'is the codec
    live', consumed by both ``_step_factory_args`` (which nulls the
    cconfig the growers trace) and ``_effective_wire_key`` (the resume
    guard), so the two can never drift apart."""
    return (not mesh_present
            or config.parallelism in ("feature_parallel",
                                      "voting_parallel"))


def _mesh_world_size(mesh: Optional[Mesh]) -> int:
    """Device count of a fit's mesh (1 with no mesh) — the ONE
    world-size derivation for both the resume-time comparison and the
    checkpoint stamp, so the two can never read differently-computed
    values."""
    if mesh is None:
        return 1
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))


def _effective_wire_key(config: "BoostingConfig", mesh: Optional[Mesh]):
    """The histogram-psum wire a fit ACTUALLY uses, as a comparable key:
    ``None`` for the flat f32 wire (no codec, or
    :func:`_hist_psum_nulled`), else ``(compression, min_size, chunk)``
    with chunk zeroed for non-int8 codecs (bf16 never chunks) — plus
    the RESOLVED planner routing as a 4th element when it is anything
    but certainly-flat (ISSUE 14: a hierarchical route quantizes
    intra-host SUMS where flat quantizes per-rank payloads — different
    histogram numerics, so a routing toggle against an existing
    checkpoint refuses exactly like a codec toggle; 'auto' on unknown
    topology resolves flat and keeps pre-planner 3-element keys
    comparing equal).  DL-only fields (error_feedback/sharded_update/
    manual) never enter the key."""
    cc = resolve_collective_config(config.collective_compression)
    if cc is None or _hist_psum_nulled(config, mesh is not None):
        return None
    from ...parallel.planner import get_planner
    routing = get_planner().resolved_routing(
        cc, world=_mesh_world_size(mesh))
    if not cc.compresses and routing == "flat":
        return None
    key = ((cc.compression, cc.min_size,
            cc.chunk if cc.compression == "int8" else 0)
           if cc.compresses else ("none", 0, 0))
    if routing != "flat":
        key = key + (routing,)
    return key


def _latest_checkpoint(directory: str) -> Optional[Booster]:
    import os
    import re as _re
    if not os.path.isdir(directory):
        return None
    found = []
    for name in os.listdir(directory):
        m = _re.match(r"iter_(\d+)\.json$", name)
        if m:
            found.append((int(m.group(1)), name))
    if not found:
        return None
    _, name = max(found)
    with open(os.path.join(directory, name)) as f:
        return Booster.from_string(f.read())


def _write_checkpoint(directory: str, booster: Booster,
                      keep: int = 3) -> None:
    import os
    import re as _re

    from ...resilience.faults import get_faults
    os.makedirs(directory, exist_ok=True)
    n = booster.num_trees // max(booster.num_class, 1)
    path = os.path.join(directory, f"iter_{n:08d}.json")
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(booster.to_dict(), f)
    # a SIGKILL between write and publish must leave only the tmp file,
    # which _latest_checkpoint never matches — resume sees the prior step
    get_faults().kill_point("gbdt.checkpoint.pre_publish", iteration=n)
    os.replace(tmp, path)
    # the published step is this rank's durable position: report it on
    # the heartbeat channel so the gang supervisor's verdicts (and the
    # elastic-resume recovery clock) carry real training progress
    from ...parallel.heartbeat import beat
    from ...telemetry.flight import record as _flight_record
    beat(step=n)
    _flight_record("checkpoint", step=n, path=path)
    get_faults().kill_point("gbdt.checkpoint", iteration=n)
    matches = (_re.match(r"iter_(\d+)\.json$", x)
               for x in os.listdir(directory))
    steps = sorted(int(m.group(1)) for m in matches if m)
    for old in steps[:-keep]:
        try:
            os.remove(os.path.join(directory, f"iter_{old:08d}.json"))
        except OSError:
            pass


def _available_host_bytes() -> int:
    """Best-effort available host memory (MemAvailable, then sysconf),
    0 when neither source exists."""
    import os
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    try:
        return os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    except (AttributeError, OSError, ValueError):
        return 0


def _advanced_mask_budget_bytes(config: "BoostingConfig") -> int:
    """Byte budget for the advanced-monotone (M, M, F) overlap masks.

    Priority: ``pass_through={"advanced_mask_bytes": ...}`` kwarg, then
    the ``SYNAPSEML_TPU_ADV_MONO_MASK_BYTES`` env var (both taken
    verbatim), then a quarter of the host's available memory clamped to
    [1 GiB (the historical fixed guard), 8 GiB] — the mask estimate
    excludes XLA's compile/temp headroom, so the auto budget stays well
    inside even a big host and anything larger must be opted into."""
    import os
    override = config.pass_through.get(
        "advanced_mask_bytes",
        os.environ.get("SYNAPSEML_TPU_ADV_MONO_MASK_BYTES"))
    if override is not None:
        return int(float(override))
    return min(max(1 << 30, _available_host_bytes() // 4), 8 << 30)


def _placeholder_mapper(m: BinMapper) -> bool:
    return bool(np.all(m.num_bins <= 1)) and bool(np.all(np.isinf(m.upper_bounds)))


def _replay_margin(b: Booster, X: np.ndarray) -> np.ndarray:
    """Warm-start margin re-based in the TRAINING accumulation order.

    The train loop advances scores one f32 add per tree
    (``scores + leaf_value[node_id]``); ``predict_margin``'s fused
    traversal reassociates the tree sum, which drifts by ulps and makes
    an otherwise-deterministic gbdt/goss resume diverge from the
    uninterrupted run on near-tie splits.  Replaying per-tree leaf values
    sequentially in f32 reproduces training's exact rounding, so the
    resumed run continues bit-identically.  dart/rf reweight trees at
    predict time — their resume is documented-approximate, use the fused
    path."""
    if b.config.boosting_type not in ("gbdt", "goss") \
            or any(w != 1.0 for w in b.tree_weights):
        return b.predict_margin(X)
    _, leaves = b.predict_margin(X, return_leaves=True)
    n = len(X)
    K = max(b.num_class, 1)
    cols = []
    for k in range(K):
        base = b.init_score[min(k, len(b.init_score) - 1)]
        m = np.full(n, np.float32(base), np.float32)
        ids = leaves[k]                          # (T_k, n) leaf node ids
        ktrees = [t for t, kc in zip(b.trees, b.tree_class) if kc == k] \
            if K > 1 else b.trees
        for t, tree in enumerate(ktrees):
            m = m + np.asarray(tree.leaf_value, np.float32)[ids[t]]
        cols.append(m)
    return cols[0] if K == 1 else np.stack(cols, axis=1)


def train(X: np.ndarray, y: np.ndarray, config: BoostingConfig,
          sample_weight: Optional[np.ndarray] = None,
          valid: Optional[Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]] = None,
          feature_names: Optional[Sequence[str]] = None,
          mesh: Optional[Mesh] = None,
          init_model: Optional[Booster] = None,
          callbacks: Optional[Sequence[Callable]] = None,
          group: Optional[np.ndarray] = None,
          valid_group: Optional[np.ndarray] = None,
          checkpoint_dir: Optional[str] = None,
          checkpoint_interval: int = 0,
          step_profiler=None,
          ) -> Tuple[Booster, List[EvalRecord]]:
    """Full training run (trainOneDataBatch analogue, LightGBMBase.scala:393).

    ``checkpoint_dir`` + ``checkpoint_interval`` enable STEP-LEVEL
    checkpoint/resume (beyond the reference, whose only resume unit is the
    numBatches warm-start fold, LightGBMBase.scala:38-59): every N
    iterations the partial booster is written atomically; a later call
    with the same dir resumes from the newest file and trains only the
    remaining iterations.  Resume re-bases scores from the saved model, so
    unbagged gbdt/goss runs continue on the identical tree sequence;
    bagged runs continue with a fresh subsample stream and dart runs
    freeze the carried trees at their checkpointed weights with a fresh
    drop stream over the new trees (both the documented-approximate
    semantics of the reference's warm start, LightGBMBase.scala:38-59).

    When ``mesh`` is given, rows are sharded over its ``data`` axis and each
    iteration's histograms ride one psum — the entire distributed story.

    ``X`` may be a numpy matrix OR a chunked source (anything with
    ``num_rows``/``num_features``/``iter_chunks``/``sample_rows`` — e.g.
    :class:`~synapseml_tpu.io.colstore.ChunkedColumnSource`): then features
    stream from disk in micro-batches into the device-resident binned
    matrix and host memory stays O(chunk) — the StreamingPartitionTask
    ingestion model (StreamingPartitionTask.scala:101-422).  With a source
    carrying a label column, ``y=None`` reads labels from it.

    ``step_profiler`` (a :class:`~synapseml_tpu.telemetry.gangplane.
    StepProfiler`) decomposes each boosting iteration's wall time into
    data (mask/bag prep) / compute (tree grow + download) / collective /
    other (eval, checkpoint) segments.  Profiling forces the eager host
    path — the fused ``lax.scan`` dispatch admits no per-iteration
    boundary to time.
    """
    with _telemetry.span("gbdt.fit", objective=config.objective) as fit_span:
        return _train(fit_span, X, y, config, sample_weight, valid,
                      feature_names, mesh, init_model, callbacks, group,
                      valid_group, checkpoint_dir, checkpoint_interval,
                      step_profiler)


def _train(fit_span, X, y, config, sample_weight, valid, feature_names, mesh,
           init_model, callbacks, group, valid_group, checkpoint_dir,
           checkpoint_interval, step_profiler
           ) -> Tuple[Booster, List[EvalRecord]]:
    """:func:`train` under its ``gbdt.fit`` span.  The phases are spans
    too (``gbdt.fit.bin``, ``.upload``, ``.compile``, ``.boost``,
    ``.download``), opened and closed where ``InstrumentationMeasures``
    reads its clock; none waits for the device, so one around
    asynchronous uploads or dispatches times the enqueue."""
    import time as _time
    measures = InstrumentationMeasures()
    _t0 = _time.perf_counter()
    _phase = _telemetry.span("gbdt.fit.bin", part="bin_mapper").start()
    # ``checkpoint_dir`` also accepts a core.checkpoint.CheckpointManager
    # (anything carrying ``.directory``): preemption-tolerant callers hand
    # the same manager to every trainer and the booster writes its
    # iteration checkpoints into its directory
    if checkpoint_dir is not None and not isinstance(checkpoint_dir, str):
        checkpoint_dir = getattr(checkpoint_dir, "directory", checkpoint_dir)
    if checkpoint_dir and checkpoint_interval > 0:
        # dart resume uses the warm-start (init_model) semantics LightGBM
        # itself documents as APPROXIMATE: the carried trees are frozen
        # at their checkpointed weights (they re-based the score margin)
        # and the fresh run's drop/normalize stream applies only to the
        # trees grown after resume.  Exact continuation is impossible —
        # later drops reweight EARLIER trees, so the uninterrupted
        # drop/normalize sequence cannot be replayed from a prefix — and
        # the reference's own numBatches warm start has the same
        # stated-approximate behavior (LightGBMBase.scala:38-59).
        resumed = _latest_checkpoint(checkpoint_dir)
        if resumed is not None:
            # codec guard (the DL _CheckpointLoop's counterpart): the
            # remaining trees would grow on a different histogram wire
            # than the carried ones — bit-exact with neither clean run —
            # so a collective_compression toggle against an existing
            # checkpoint fails loudly instead of silently changing the
            # numerics mid-model.  The key is the EFFECTIVE wire, not
            # the declared config: only the fields the histogram psum
            # reads (codec, min_size, int8 chunk — error_feedback/
            # sharded_update/manual are DL-only, bf16 never chunks),
            # nulled where the psum itself is nulled (_step_factory_args:
            # no mesh, feature/voting parallelism) — so a topology change
            # like gang-fit → single-device-resume flips the key even
            # under an unchanged config, and a single-device fit that
            # declared a (documented-ignored) codec resumes freely.
            # Checkpoints carry the writer's key (stamped below) because
            # mesh-ness is a train() arg the config alone cannot encode.
            saved_pt = resumed.config.pass_through or {}
            if "_codec_wire_key" in saved_pt:
                saved_cc = saved_pt["_codec_wire_key"]
                saved_cc = tuple(saved_cc) if saved_cc is not None else None
            else:
                # unstamped checkpoint: the codec fields did not exist
                # when it was written, so it trained on the f32 wire
                saved_cc = None
            cur_cc = _effective_wire_key(config, mesh)
            if saved_cc != cur_cc:
                raise ValueError(
                    f"checkpoint at {checkpoint_dir} was trained with "
                    f"collective_compression wire {saved_cc!r} but this "
                    f"fit requests {cur_cc!r}; resuming would grow the "
                    "remaining trees under different histogram numerics "
                    "— use a fresh checkpoint_dir or keep the codec")
            # same contract for the ingest dtype: trees grown on bf16
            # g/h are not bit-compatible with f32-ingest continuation
            # (an unstamped checkpoint predates fused ingest = f32)
            saved_fused = bool(saved_pt.get("_fused_ingest", False))
            cur_fused = _fused_ingest_on(config)
            if saved_fused != cur_fused:
                raise ValueError(
                    f"checkpoint at {checkpoint_dir} was trained with "
                    f"fused_ingest={saved_fused} but this fit requests "
                    f"{cur_fused}; resuming would grow the remaining "
                    "trees under a different histogram ingest dtype — "
                    "use a fresh checkpoint_dir or keep the knob "
                    "(fused_ingest=False resumes pre-fused checkpoints)")
            # world size is deliberately NOT part of the refusal key: an
            # elastic gang resize resumes an N-rank checkpoint on M ranks
            # (rows re-pad and re-shard over the new mesh below; the
            # histogram psum is a sum over ALL rows, so the partition is
            # not model state).  The stamped writer size is
            # informational — a resized resume is recorded, never
            # refused, as long as the effective wire matches.
            cur_ws = _mesh_world_size(mesh)
            saved_ws = saved_pt.get("_fit_world_size")
            if saved_ws is not None and int(saved_ws) != cur_ws:
                from ...resilience.faults import get_faults
                from ...telemetry.flight import record as _flight_rec
                get_faults().note("gbdt.resize_resume",
                                  saved=int(saved_ws), current=cur_ws)
                _flight_rec("resize_resume", trainer="gbdt",
                            saved_shards=int(saved_ws),
                            current_shards=cur_ws)
            done = resumed.num_trees // max(resumed.num_class, 1)
            if done >= config.num_iterations:
                return resumed, []
            config = dataclasses.replace(
                config, num_iterations=config.num_iterations - done)
            init_model = resumed
        # stamp THIS fit's effective wire into the config the written
        # checkpoints carry (the guard above reads it back; JSON
        # round-trips the tuple as a list), plus the writer's device
        # count for resize observability
        key = _effective_wire_key(config, mesh)
        config = dataclasses.replace(config, pass_through={
            **config.pass_through,
            "_codec_wire_key": list(key) if key is not None else None,
            "_fused_ingest": _fused_ingest_on(config),
            "_fit_world_size": _mesh_world_size(mesh)})
    source = X if hasattr(X, "iter_chunks") else None
    if source is not None:
        n, F = source.num_rows, source.num_features
        if y is None:
            y = source.read_labels()
            if y is None:
                raise ValueError("streaming train with y=None needs the "
                                 "source to carry a label_col")
        if sample_weight is None:
            sample_weight = source.read_weights()
    else:
        X = np.ascontiguousarray(X, np.float32)
        n, F = X.shape

    if config.two_level_hist not in ("auto", "on", "off", True, False):
        raise ValueError(
            f"two_level_hist={config.two_level_hist!r}: must be 'auto', "
            "'on', or 'off'")
    # fail fast on a bad codec string / ingest knob, before
    # binning/compiles start
    resolve_collective_config(config.collective_compression)
    _fused_ingest_on(config)

    if config.monotone_constraints and any(config.monotone_constraints):
        if config.monotone_constraints_method not in ("basic",
                                                      "intermediate",
                                                      "advanced"):
            raise ValueError(
                f"monotone_constraints_method="
                f"{config.monotone_constraints_method!r}: must be 'basic', "
                "'intermediate' or 'advanced'")
        if len(config.monotone_constraints) != F:
            raise ValueError(
                f"monotone_constraints has "
                f"{len(config.monotone_constraints)} entries for {F} "
                "features")
        if any(int(c) not in (-1, 0, 1) for c in config.monotone_constraints):
            raise ValueError("monotone_constraints entries must be -1, 0, "
                             "or 1")
        cats = set(config.categorical_feature or [])
        if any(int(c) != 0 and i in cats
               for i, c in enumerate(config.monotone_constraints)):
            raise ValueError("monotone constraints on categorical features "
                             "are not meaningful (category-subset splits "
                             "have no direction)")
        if config.monotone_constraints_method == "advanced":
            # the advanced refresh materializes (M, M, F) overlap masks
            # (bool + int32 reductions, ~5 bytes/entry) inside the jitted
            # per-wave refresh — guard the O(M^2 F) memory here so a
            # config that cannot fit fails fast instead of OOMing or
            # stalling compilation mid-train.  The budget scales with the
            # host's available memory (not a fixed 1 GiB), so big hosts
            # degrade to slow instead of refusing (ADVICE r5 item 2);
            # SYNAPSEML_TPU_ADV_MONO_MASK_BYTES or
            # pass_through={"advanced_mask_bytes": ...} overrides it
            from .trainer import max_nodes
            m_nodes = max_nodes(config.num_leaves)
            adv_bytes = 5 * m_nodes * m_nodes * F
            budget = _advanced_mask_budget_bytes(config)
            if adv_bytes > budget:
                raise ValueError(
                    f"monotone_constraints_method='advanced' with "
                    f"num_leaves={config.num_leaves} and {F} features "
                    f"needs ~{adv_bytes / 2**30:.1f} GiB of (M, M, F) "
                    f"constraint masks per refresh (M={m_nodes} nodes), "
                    f"over this host's {budget / 2**30:.1f} GiB budget; "
                    "use monotone_constraints_method='intermediate' "
                    "(a provable superset of the advanced constraint "
                    "set) for models this size, or raise the budget via "
                    "SYNAPSEML_TPU_ADV_MONO_MASK_BYTES / "
                    "pass_through={'advanced_mask_bytes': ...}")

    # distributed lambdarank: pack WHOLE groups onto shards up front (the
    # reference's query-rows-share-a-partition rule); rows permute into
    # per-shard slabs padded to a common length, lambdas stay shard-local
    lr_pack = None
    lr_stream_perm = None
    if (config.objective == "lambdarank" and mesh is not None
            and config.parallelism != "feature_parallel"):
        # data_parallel AND voting_parallel shard ROWS, so whole groups
        # pack onto shards and lambdas compute shard-locally.
        # feature_parallel REPLICATES rows, so it skips the packing and
        # uses the plain in-memory objective on every rank
        if group is None:
            raise ValueError("lambdarank requires group sizes (groupCol)")
        from .pallas_hist import hist_pad_multiple
        from .ranking import pack_groups_for_shards
        _shards = mesh.shape[DATA_AXIS]
        _B = config.max_bin + 1
        _unit = (hist_pad_multiple()
                 if (jax.default_backend() == "tpu" and _B <= 512
                     and _B % 8 == 0) else 1)
        perm, _sq, _smask, _L = pack_groups_for_shards(
            np.asarray(group), _shards, _unit, max_group_size=128)
        _valid = (perm >= 0)
        pc = np.maximum(perm, 0)
        if source is not None:
            # streamed ranking: labels/weights permute on HOST (tiny);
            # the binned matrix streams to device in SOURCE order and
            # permutes into the per-shard group slabs ON DEVICE after
            # assembly — whole groups land on one shard exactly like the
            # in-memory path, host memory stays O(chunk)
            lr_stream_perm = (pc, _valid, n)      # n = source row count
        else:
            X = X[pc]
            X[~_valid] = np.nan    # pads must not shift the bin quantiles
        y = np.asarray(y)[pc] * _valid
        sw = (np.asarray(sample_weight, np.float32)[pc]
              if sample_weight is not None
              else np.ones(len(pc), np.float32))
        sample_weight = (sw * _valid).astype(np.float32)
        n = len(pc)
        lr_pack = (_sq, _smask, _L, _valid)
    K = config.num_class if config.objective in ("multiclass", "multiclassova") else 1
    feature_names = list(feature_names) if feature_names else [f"f{i}" for i in range(F)]
    rng = np.random.default_rng(config.seed)

    # -- binning (calculateRowStatistics analogue) -------------------------
    # imported LightGBM models carry a placeholder mapper (all-inf bounds);
    # warm-starting from one must fit a REAL mapper or every row would land
    # in bin 1 and the new trees would be stumps
    if init_model is not None and not _placeholder_mapper(init_model.bin_mapper):
        mapper = init_model.bin_mapper
    elif source is not None:
        # streamed samples carry no aligned labels: categorical bins order
        # by value instead of target statistic (documented fallback)
        mapper = fit_bin_mapper(
            source.sample_rows(config.bin_sample_count, config.seed),
            config.max_bin, sample_count=config.bin_sample_count,
            seed=config.seed,
            categorical_features=config.categorical_feature)
    else:
        mapper = fit_bin_mapper(X, config.max_bin,
                                sample_count=config.bin_sample_count,
                                seed=config.seed,
                                categorical_features=config.categorical_feature,
                                y=np.asarray(y, np.float64))
    measures.binning_s = _time.perf_counter() - _t0
    _phase.close()
    _t_prep = _time.perf_counter()

    # -- labels / weights --------------------------------------------------
    w = np.ones(n, np.float32) if sample_weight is None else \
        np.asarray(sample_weight, np.float32).copy()
    w_scaled = False
    if config.objective == "binary":
        yb = (np.asarray(y) > 0).astype(np.float32)
        if config.is_unbalance or config.scale_pos_weight != 1.0:
            pos = max(float(yb.sum()), 1.0)
            neg = max(float(n - yb.sum()), 1.0)
            spw = (neg / pos) if config.is_unbalance else config.scale_pos_weight
            w = np.where(yb > 0, w * spw, w).astype(np.float32)
            w_scaled = True
        labels_np = yb
    elif K > 1:
        labels_np = np.asarray(y, np.float32)
    else:
        labels_np = np.asarray(y, np.float32)

    # -- init score (boost_from_average) -----------------------------------
    if init_model is not None:
        if config.boosting_type == "rf":
            # rf trees are INDEPENDENT fits at the constant init margin —
            # continued training must not boost from the ensemble margin
            # (and must not pay a full carried-model prediction pass only
            # to discard it)
            base_margin = None
        elif source is not None:
            base_margin = np.concatenate(
                [init_model.predict_margin(cx)
                 for cx, _, _ in source.iter_chunks()])
        else:
            base_margin = _replay_margin(init_model, X)
        init_sc = init_model.init_score
    elif (config.boost_from_average
          and config.objective not in ("multiclass", "multiclassova")):
        s0 = initial_score(config.objective, labels_np, w)
        init_sc = np.full(K, s0, np.float32)
        base_margin = None                 # constant margin built on device
    else:
        init_sc = np.zeros(K, np.float32)
        base_margin = None

    # -- padding + device placement ---------------------------------------
    # pallas kernel constraints: B must be sublane-aligned and the one-hot
    # working set must fit VMEM; otherwise scatter fallback
    B_total = config.max_bin + 1
    pallas_candidate = (jax.default_backend() == "tpu"
                        and B_total <= 512 and B_total % 8 == 0)
    shards = mesh.shape[DATA_AXIS] if mesh is not None else 1
    featpar = config.parallelism == "feature_parallel" and mesh is not None
    use_pallas = pallas_candidate
    uses_fused = (config.growth_policy == "depthwise" and not featpar
                  and config.parallelism != "voting_parallel")
    if pallas_candidate and uses_fused:
        # the fused route+hist kernel keeps its whole accumulator VMEM-
        # resident, which scales with F — wide matrices fall back to the
        # scatter path (EFB re-gates on the bundled width below)
        from .pallas_hist import fused_geometry
        use_pallas = fused_geometry(
            F, B_total, default_n_slots(config.num_leaves)) is not None
    # feature_parallel replicates ROWS and shards FEATURES: rows pad only
    # for the pallas chunk, features pad to the rank count
    row_shards = 1 if featpar else shards
    pad_unit = row_shards
    if pallas_candidate:       # pad for the kernel even if EFB re-gates
        from .pallas_hist import hist_pad_multiple
        pad_unit = row_shards * hist_pad_multiple()
    Fp = F
    if featpar:
        Fp = F + (-F) % shards
    pad = (-n) % pad_unit
    if pad:
        labels_np = np.concatenate([labels_np, np.zeros(pad, labels_np.dtype)])
        if sample_weight is not None or w_scaled:
            w = np.concatenate([w, np.zeros(pad, np.float32)])
    N = n + pad

    def put(xx, ndim):
        if mesh is None:
            return jnp.asarray(xx)
        if featpar:                       # rows replicated on every rank
            return jax.device_put(xx, replicated(mesh))
        return jax.device_put(xx, batch_sharding(mesh, ndim))

    def dev_fill(fill, shape):
        """Constant arrays are built ON the chip — no host→device
        traffic."""
        if mesh is None:
            return jnp.full(shape, fill, jnp.float32)
        sh = replicated(mesh) if featpar else batch_sharding(mesh, len(shape))
        return jax.jit(lambda: jnp.full(shape, fill, jnp.float32),
                       out_shardings=sh)()

    if config.two_level_hist == "auto":
        # resolve here, where BOTH the global row count (the grower only
        # sees shard-local rows, which would scale the documented 500k
        # threshold with device count) and the pallas decision are known:
        # on the XLA scatter fallback two-level only ADDS work (fine
        # hists get built then pooled) while coarsening non-top-K splits,
        # so auto requires a pallas grower that implements it — the
        # fused depthwise path, or the single-device/data-parallel
        # lossguide path (per-tile nodes kernel).  feature/voting
        # parallel growers ignore two_level, so auto must stay "off"
        # there (a stale "on" would also fork the GrowthParams jit key
        # for an identical program).  Must resolve BEFORE the
        # warm-compile thread below — GrowthParams is the jit/lru cache
        # key, so a thread warming the 'auto' config would compile a
        # program the run never uses.  (The EFB re-gate further down can
        # only flip use_pallas when enable_bundle is set, and EFB
        # structurally disables two-level in the grower anyway.)
        from .trainer import TWO_LEVEL_MIN_ROWS
        _tl_lossguide = (config.growth_policy == "lossguide"
                         and not featpar
                         and config.parallelism != "voting_parallel")
        _tl_resolved = ("on" if (n >= TWO_LEVEL_MIN_ROWS and use_pallas
                                 and (uses_fused or _tl_lossguide))
                        else "off")
        if _tl_resolved == "on":
            # 'auto' flipping to coarse-then-refine CHANGES split-search
            # semantics (non-top-K features split only on coarse-bin
            # boundaries) — say so once, visibly, so a user can tell
            # which semantics produced a model (ADVICE r5 item 1)
            _logging.getLogger("synapseml_tpu.gbdt").info(
                "two_level_hist='auto' resolved to 'on' (%d rows >= %d, "
                "pallas grower): histograms build coarse and only the top "
                "%d features refine at full resolution; set "
                "two_level_hist='off' for exact full-resolution splits",
                n, TWO_LEVEL_MIN_ROWS, config.refine_features)
        config = dataclasses.replace(config, two_level_hist=_tl_resolved)
    # set on EVERY fit (not just the 'auto' branch), else an explicit
    # 'on'/'off' fit would leave the previous fit's resolution standing;
    # unlabeled on purpose — a per-policy label would leave the OTHER
    # policy's series stale across fits.  Guarded: telemetry must never
    # break training (same contract as _publish_measures/_tl_gauge).
    try:
        _telemetry.get_registry().gauge(
            "gbdt_two_level_resolved",
            "1 when the current fit's two_level_hist (after 'auto' "
            "resolution) requests coarse-then-refine histograms").set(
                1.0 if config.two_level_hist in ("on", True) else 0.0)
    except Exception:
        pass

    # -- compile/transfer overlap ------------------------------------------
    # the jitted step's first compile (cold: tens of seconds, warm cache:
    # seconds) and the host-side binning + u8 upload are independent; warm
    # the step on a helper thread with zero-dummies of the final shapes so
    # the wall clock pays max(compile, binning+upload), not the sum.
    # _make_step is lru-cached, so the real construction below returns the
    # SAME jitted callable the thread compiled.  Restricted to the plain
    # single-device path (sharded dummies would need placement logic, and
    # EFB/lambdarank only learn their shapes after binning).
    _warm_thread = None
    if (use_pallas and mesh is None and K == 1 and not config.enable_bundle
            and config.objective != "lambdarank" and n >= 200_000):
        _wargs, _wkw = _step_factory_args(config, K, mesh, featpar,
                                          use_pallas, num_features=F)
        # warm the program the run will actually use: the scanned
        # whole-run program for fire-and-forget fits, else the one-step
        _w_scan_ok = (not (config.boosting_type == "dart" or valid is not None
                           or callbacks or step_profiler is not None
                           or (checkpoint_dir and checkpoint_interval > 0))
                      and config.feature_fraction >= 1.0
                      and config.num_iterations >= SCAN_CHUNK)
        if _w_scan_ok:
            _wrun = _make_scan(_wargs, tuple(sorted(_wkw.items())),
                               config.bagging_freq, config.seed,
                               config.boosting_type == "rf")
        else:
            _wstep = _make_step(*_wargs, **_wkw)
        _w_ub_cols = mapper.upper_bounds.shape[1]

        def _warm_compile():
            try:
                zf32 = functools.partial(jnp.zeros, dtype=jnp.float32)
                _cargs = (jnp.zeros((F, N), jnp.int32), zf32(N), zf32(N),
                          jnp.ones(N, jnp.float32))
                _ctail = (jnp.ones(F, bool),
                          jnp.zeros((F, _w_ub_cols), jnp.float32),
                          jnp.full(F, config.max_bin + 1, jnp.int32),
                          None)
                if _w_scan_ok:
                    # a real (junk-data) call: only the dispatch path
                    # populates jit's executable cache, and one SCAN_CHUNK
                    # of empty trees is ~1 s of device time overlapped
                    # with binning
                    out = _wrun(*_cargs, jnp.ones(N, jnp.float32),
                                jax.random.PRNGKey(0), _ctail[0], _ctail[1],
                                _ctail[2], _ctail[3], zf32(N),
                                jnp.zeros((), jnp.int32))
                else:
                    out = _wstep(*_cargs, (jnp.ones(N, jnp.float32),
                                 jax.random.PRNGKey(0)), _ctail[0],
                                 jax.random.PRNGKey(1), _ctail[1],
                                 _ctail[2], _ctail[3])
                jax.block_until_ready(out[1])
            except Exception:
                pass           # warming is best-effort; the loop compiles

        import threading as _threading
        _warm_thread = _threading.Thread(target=_warm_compile, daemon=True)
        _warm_thread.start()

    # host-bin to the narrowest integer type (native multithreaded search)
    # and upcast/transpose on device: ships 1-2 bytes/cell instead of 4 —
    # the upload, not the searchsorted, is the fixed cost that bounds short
    # training runs
    _t_bin2 = _time.perf_counter()
    # host binning, chunk by chunk; each chunk's upload is enqueued as
    # it is binned
    _phase = _telemetry.span("gbdt.fit.bin", part="bin_rows").start()

    def bin_host(mat):
        if mapper.has_categorical:
            # categorical LUTs live in the python mapper; the native fast
            # path handles the numeric-only common case
            out = mapper.transform(mat)
            return out.astype(np.uint8 if mapper.max_bin <= 255
                              else np.uint16)
        if mapper.max_bin <= 255:
            from ...native import bin_columns_u8
            return bin_columns_u8(mat, mapper.upper_bounds, mapper.max_bin)
        return mapper.transform(mat).astype(np.uint16)

    # exclusive feature bundling: fit on a binned sample, then every
    # chunk/matrix flows through the bundle remap before device upload.
    # feature_parallel fits ONE BUNDLER PER RANK SLICE (bundles never
    # cross rank boundaries, so vertical sharding and bundling compose);
    # every rank's bundled block pads to the widest rank's bundle count
    # so the sharded matrix stays rectangular
    bundler = None
    rank_bundlers = None
    Fsl = Fp // shards if featpar else 0
    # ONE padded num_bins vector (pad features: 1 bin, never split) and ONE
    # column padder — the route tables, bundler fits, chunk binning and the
    # device num_bins below must all agree on the padding convention
    _nb_pad = mapper.num_bins if Fp == F else np.concatenate(
        [mapper.num_bins, np.ones(Fp - F, mapper.num_bins.dtype)])

    def _pad_cols_to_fp(mat):
        if Fp == F:
            return mat
        return np.concatenate(
            [mat, np.zeros((len(mat), Fp - F), mat.dtype)], axis=1)

    if config.enable_bundle:
        if init_model is not None and init_model.bundler is not None \
                and not featpar:
            bundler = init_model.bundler
        else:
            if source is not None:
                sample_mat = source.sample_rows(
                    min(config.bin_sample_count, 50_000), config.seed)
            else:
                take = min(n, 50_000)
                sample_mat = X[:take]
            sample_b = bin_host(np.ascontiguousarray(sample_mat, np.float32))
            if featpar:
                sample_b = _pad_cols_to_fp(sample_b)
                rank_bundlers = [
                    FeatureBundler.fit(
                        sample_b[:, r * Fsl:(r + 1) * Fsl],
                        _nb_pad[r * Fsl:(r + 1) * Fsl],
                        max_total_bins=config.max_bin + 1,
                        max_conflict_rate=config.max_conflict_rate)
                    for r in range(shards)]
            else:
                bundler = FeatureBundler.fit(
                    sample_b, mapper.num_bins,
                    max_total_bins=config.max_bin + 1,
                    max_conflict_rate=config.max_conflict_rate)
    Fb_rank = (max(b.num_bundles for b in rank_bundlers)
               if rank_bundlers else 0)

    if (bundler is not None and pallas_candidate and uses_fused
            and not use_pallas):
        # bundling shrank the feature axis: the fused kernel may fit now
        from .pallas_hist import fused_geometry
        use_pallas = fused_geometry(
            bundler.num_bundles, B_total,
            default_n_slots(config.num_leaves)) is not None
    measures.hist_path = "pallas" if use_pallas else "xla_scatter"

    def bin_eff(mat):
        b = bin_host(mat)
        if rank_bundlers is not None:
            b = _pad_cols_to_fp(b)
            parts = []
            for r, br in enumerate(rank_bundlers):
                t = br.transform(b[:, r * Fsl:(r + 1) * Fsl])
                if t.shape[1] < Fb_rank:
                    t = np.concatenate(
                        [t, np.zeros((len(t), Fb_rank - t.shape[1]),
                                     t.dtype)], axis=1)
                parts.append(t)
            return np.concatenate(parts, axis=1)
        return bundler.transform(b) if bundler is not None else b

    if mesh is None:
        bins_spec = None
    elif featpar:
        bins_spec = NamedSharding(mesh, P(DATA_AXIS, None))   # F sharded
    else:
        bins_spec = NamedSharding(mesh, P(None, DATA_AXIS))   # N sharded

    def put_bins(mat):
        """Upload a host (rows, F) small-int block.  Feature-parallel pads
        the feature axis on HOST and ships each rank only its own feature
        slice (P(None, data)) — replicating the full matrix would multiply
        both link traffic and HBM by the rank count."""
        if featpar:
            if rank_bundlers is None:
                # (the EFB path pads + bundles inside bin_eff already)
                mat = _pad_cols_to_fp(mat)
            return jax.device_put(mat, NamedSharding(mesh, P(None, DATA_AXIS)))
        return put(mat, 2)

    def finish_bins(stacked_dev):
        """(N, Fp) small-int device array → (Fp, N) int32 with the mode's
        sharding (for feature-parallel the transpose is shard-local)."""
        def fn(b):
            out = b.astype(jnp.int32).T
            if bins_spec is not None:
                out = jax.lax.with_sharding_constraint(out, bins_spec)
            return out
        return jax.jit(fn)(stacked_dev)

    # micro-batch push (StreamingPartitionTask analogue) for BOTH sources:
    # each chunk is binned and shipped independently (device_put is async,
    # so chunk k's bytes upload while chunk k+1 bins on the host — the
    # fixed cost pays ~max(binning, upload) instead of their sum); the
    # full matrix exists only on DEVICE, assembled by one concatenate, so
    # streamed host peak stays O(chunk).  Row-sharded uploads require a row
    # count divisible by the shard count: a host-side carry re-chunks
    # arbitrary chunk/tail sizes to shard multiples, and the remainder
    # merges into the pad block (n + pad is a shard multiple by
    # construction, so the combined tail always divides evenly).
    if source is not None:
        chunk_iter = (cx for cx, _, _ in source.iter_chunks())
    else:
        crows = max(row_shards, 131_072 // row_shards * row_shards)
        chunk_iter = (X[lo:lo + crows] for lo in range(0, n, crows))
    bin_dt = np.uint8 if mapper.max_bin <= 255 else np.uint16
    # streamed ranking permutes AFTER assembly: the stream's own tail pad
    # only needs shard divisibility for the source row count
    stream_pad = pad if lr_stream_perm is None \
        else (-lr_stream_perm[2]) % row_shards
    dev_chunks = []
    carry = None
    for cx in chunk_iter:
        b = bin_eff(cx)
        if carry is not None and len(carry):
            b = np.concatenate([carry, b])
        keep = len(b) - len(b) % row_shards
        carry = b[keep:].copy()    # view would pin the whole chunk
        if keep:
            dev_chunks.append(put_bins(b[:keep]))
    _phase.close()
    # the device-resident state assembled: the bins' tail, concatenate
    # and transpose, labels, weights, margins, bounds (and what else
    # prepares the loop, up to data_prep_s's end)
    _phase = _telemetry.span("gbdt.fit.upload").start()
    tail_rows = (len(carry) if carry is not None else 0) + stream_pad
    if tail_rows:
        if rank_bundlers is not None:
            pad_f = shards * Fb_rank
        elif bundler is not None:
            pad_f = bundler.num_bundles
        else:
            pad_f = F
        tail = np.zeros((tail_rows, pad_f), bin_dt)
        if carry is not None and len(carry):
            tail[:len(carry)] = carry
        dev_chunks.append(put_bins(tail))
    if len(dev_chunks) > 1:
        stacked = jax.jit(lambda *cs: jnp.concatenate(cs))(*dev_chunks)
    else:
        stacked = dev_chunks[0]
    bins_t = finish_bins(stacked)
    del dev_chunks, stacked
    if lr_stream_perm is not None:
        # device-side whole-group packing: gather source-order columns
        # into the per-shard slabs; pad slots get the NaN row's bins
        # (bin 0 per feature, through the bundler when EFB is on) so the
        # packed matrix is bit-identical to the in-memory path's
        pc_h, valid_h, _n_src = lr_stream_perm
        pad_bins = bin_eff(np.full((1, F), np.nan, np.float32))[0]
        pc_d = jnp.asarray(pc_h.astype(np.int32))
        valid_d = jnp.asarray(valid_h)
        pad_d = jnp.asarray(pad_bins.astype(np.int32))

        def _pack(b):
            out = jnp.where(valid_d[None, :], jnp.take(b, pc_d, axis=1),
                            pad_d[:, None])
            if bins_spec is not None:
                out = jax.lax.with_sharding_constraint(out, bins_spec)
            return out
        bins_t = jax.jit(_pack)(bins_t)
    measures.binning_s += _time.perf_counter() - _t_bin2
    labels = put(labels_np, 1)
    if sample_weight is None and not w_scaled:
        weights = dev_fill(1.0, (N,))
    else:
        weights = put(w, 1)
    if init_model is not None and base_margin is not None:
        if pad:
            shp = (pad,) if base_margin.ndim == 1 else (pad, K)
            base_margin = np.concatenate(
                [base_margin, np.zeros(shp, np.float32)])
        scores = put(base_margin.astype(np.float32), base_margin.ndim)
    else:
        scores = dev_fill(float(init_sc[0]), (N,) if K == 1 else (N, K))
    init_scores_dev = scores            # rf resets to this every iteration
    # split search, thresholds and trees live in ORIGINAL feature space
    # even under EFB (bundling only compresses histogram construction —
    # the LightGBM scheme), so bounds/bin counts are always the mapper's
    ub_np = mapper.upper_bounds
    nb_np = mapper.num_bins
    bundle_map_dev = None
    if rank_bundlers is not None:
        # per-rank route tables stacked on the ORIGINAL feature axis and
        # sharded like bounds/nbins — each rank sees its own tables, whose
        # col/gather_src indices point into its own padded bundled slice
        maps = [br.route_tables(_nb_pad[r * Fsl:(r + 1) * Fsl], B_total)
                for r, br in enumerate(rank_bundlers)]
        bundle_map_dev = {}
        for k in maps[0]:
            stacked = np.concatenate([m[k] for m in maps], axis=0)
            spec = P(DATA_AXIS, None) if stacked.ndim == 2 else P(DATA_AXIS)
            bundle_map_dev[k] = jax.device_put(
                jnp.asarray(stacked.astype(np.int32)),
                NamedSharding(mesh, spec))
    elif bundler is not None:
        bm = bundler.route_tables(mapper.num_bins, B_total)
        bundle_map_dev = {k: jnp.asarray(v.astype(np.int32))
                          for k, v in bm.items()}
        if mesh is not None:
            bundle_map_dev = {k: jax.device_put(v, replicated(mesh))
                              for k, v in bundle_map_dev.items()}
    if Fp != F:                         # padded features: 1 bin, never split
        ub_np = np.concatenate(
            [ub_np, np.full((Fp - F, ub_np.shape[1]), np.inf, np.float32)])
        nb_np = _nb_pad.astype(np.int32)
    upper_bounds = jnp.asarray(ub_np)
    num_bins = jnp.asarray(nb_np)
    if mesh is not None:
        fp_sh = (NamedSharding(mesh, P(DATA_AXIS, None)) if featpar
                 else replicated(mesh))
        fp_sh1 = (NamedSharding(mesh, P(DATA_AXIS)) if featpar
                  else replicated(mesh))
        upper_bounds = jax.device_put(upper_bounds, fp_sh)
        num_bins = jax.device_put(num_bins, fp_sh1)

    # -- objective ---------------------------------------------------------
    objective_fn = None            # non-lambdarank: _step_factory_args builds it
    if config.objective == "lambdarank":
        if group is None:
            raise ValueError("lambdarank requires group sizes (groupCol)")
        from .ranking import (build_group_index, make_lambdarank_objective,
                              make_lambdarank_objective_sharded)
        lg_arr = (np.asarray(config.label_gain, np.float32)
                  if config.label_gain else None)
        if lr_pack is not None:
            _sq, _smask, _L, _ = lr_pack
            objective_fn = make_lambdarank_objective_sharded(
                _sq, _smask, n_rows_local=_L, axis_name=DATA_AXIS,
                sigma=1.0, max_position=config.max_position,
                label_gain=lg_arr)
        else:
            qidx, qmask = build_group_index(np.asarray(group))
            objective_fn = make_lambdarank_objective(
                qidx, qmask, n_rows=n + pad, sigma=1.0,
                max_position=config.max_position, label_gain=lg_arr)
    is_rf = config.boosting_type == "rf"
    is_dart = config.boosting_type == "dart"
    use_goss = config.boosting_type == "goss"
    lr = 1.0 if is_rf else config.learning_rate

    # the histogram kernels see the BUNDLED / per-rank feature width, and
    # the tuned-chunk consult keys on exactly that width (a mismatched
    # geometry falls back to the ladder default).  Must mirror the warm-
    # compile call above (plain path: width == F) or the lru cache forks.
    if rank_bundlers:
        _hist_F = Fb_rank
    elif bundler is not None:
        _hist_F = bundler.num_bundles
    elif featpar:
        _hist_F = Fp // shards
    else:
        _hist_F = F
    _sargs, _skw = _step_factory_args(config, K, mesh, featpar, use_pallas,
                                      objective_fn=objective_fn,
                                      num_features=_hist_F)
    if uses_fused:
        # what the depth-wise grower will build its histograms with (the
        # grower decides by the same function at trace time): hist_ft,
        # hist_feature_groups, hist_chunk, hist_grid_steps_per_pass,
        # hist_two_level, hist_split_columns on the gbdt.fit span
        from .trainer import depthwise_hist_plan
        fit_span.set(**{f"hist_{k}": v for k, v in depthwise_hist_plan(
            _hist_F, N // max(row_shards, 1), _sargs[0],
            default_n_slots(config.num_leaves),
            bundled=bundle_map_dev is not None,
            use_pallas=bool(use_pallas)).items()})
    # lambdarank's objective closes over per-dataset arrays: a cache entry
    # would both never hit again and pin the arrays — bypass the cache
    make = (_make_step.__wrapped__ if config.objective == "lambdarank"
            else _make_step)
    step = make(*_sargs, **_skw)

    # -- validation setup (validationIndicatorCol analogue) ----------------
    have_valid = valid is not None
    if have_valid:
        Xv, yv, wv = valid
        Xv = np.ascontiguousarray(Xv, np.float32)
        binned_v = jnp.asarray(np.ascontiguousarray(
            bin_host(Xv).astype(np.int32).T))
        yv = (np.asarray(yv) > 0).astype(np.float32) if config.objective == "binary" \
            else np.asarray(yv, np.float32)
        # contributions accumulate separately from the init margin so rf can
        # average only the tree part
        valid_contrib = np.zeros((len(yv), K) if K > 1 else len(yv), np.float32)
        if init_model is not None:
            # warm start: eval margins must include the carried-over trees
            valid_init = init_model.predict_margin(Xv).astype(np.float32)
        else:
            valid_init = init_sc[0] if K == 1 else init_sc[None, :]
        metric_name = config.metric or metrics_mod.default_metric(config.objective, K)
        if metric_name.startswith("ndcg"):
            if valid_group is None:
                raise ValueError("ndcg eval requires valid_group sizes")
            ndcg_fn = metrics_mod.ndcg_at(config.max_position)
            metric_fn = lambda yy, mm, ww: ndcg_fn(yy, mm, valid_group, ww)  # noqa: E731
            larger_better = True
        else:
            metric_fn, larger_better = metrics_mod.METRICS.get(
                metric_name, metrics_mod.METRICS["l2"])


    measures.data_prep_s = _time.perf_counter() - _t_prep
    _phase.close()
    _t_train = _time.perf_counter()
    # up to the first dispatch: the wait for the warm thread's compile
    # (a fit without one compiles inside its first dispatch)
    _phase = _telemetry.span("gbdt.fit.compile").start()
    trees: List[Tree] = []
    tree_class: List[int] = []
    tree_weights: List[float] = []
    eval_history: List[EvalRecord] = []
    best_val = None
    best_iter = -1
    rounds_no_improve = 0

    # continued training picks the bag/key streams up where the carried
    # model left off: replaying iteration indices from 0 would hand a
    # resumed rf the SAME subsamples (and, at the constant init margin,
    # the IDENTICAL trees) it already has
    prior_iters = (len(init_model.trees) // max(K, 1)
                   if init_model is not None else 0)
    if prior_iters and config.feature_fraction < 1.0:
        k = max(1, int(round(F * config.feature_fraction)))
        for _ in range(prior_iters):      # fast-forward the host stream
            rng.choice(F, k, replace=False)

    rf_denominator = 0
    bag = np.ones(N, np.float32)
    if lr_pack is not None:
        bag = lr_pack[3].astype(np.float32)     # pad rows interspersed
    if pad:
        bag[n:] = 0.0
    # host round trips dominate small-step training: dart, per-iter
    # validation and callbacks need each tree on the host DURING the loop;
    # everything else runs fully async — device-resident masks are hoisted
    # and tree downloads deferred until after the last dispatch
    ckpt_every = (checkpoint_interval
                  if checkpoint_dir and checkpoint_interval > 0 else 0)
    eager_host = (is_dart or have_valid or bool(callbacks)
                  or bool(ckpt_every) or step_profiler is not None)
    pending_stacks: List[Tuple[Tree, List[float]]] = []
    base_bag_dev = jnp.asarray(bag)     # pad-row mask, uploaded once
    bag_root_key = jax.random.PRNGKey(config.bagging_seed)
    # fire-and-forget runs collapse the whole boosting loop into ONE
    # on-device lax.scan dispatch (_make_scan: fewer dispatches);
    # feature_fraction draws its mask from the host rng each iteration so
    # it stays looped
    use_scan = not eager_host and config.feature_fraction >= 1.0

    fmask_dev = None
    rf_reset_scores = None
    # leaf-wise depth is bounded by num_leaves-1 splits; never truncate
    depth_hint = max(2, config.num_leaves)

    # dart under feature_parallel: rescoring traverses the SHARDED binned
    # matrix with owner-broadcast go-left masks (one psum per level, the
    # training routing pattern) instead of gathering columns
    _fp_tree_predict = None
    if featpar and is_dart:
        _bm_spec = ({"col": P(DATA_AXIS), "lo": P(DATA_AXIS),
                     "hi": P(DATA_AXIS), "default_bin": P(DATA_AXIS),
                     "gather_src": P(DATA_AXIS, None)}
                    if bundle_map_dev is not None else None)
        from .trainer import predict_binned_tree_featpar as _fp_body

        def _mk_fp_predict():
            in_specs = [P(DATA_AXIS, None), P()]
            if _bm_spec is not None:
                in_specs.append(_bm_spec)

            def inner(bl, tree, *bm):
                return _fp_body(bl, tree, depth_hint, B_total, DATA_AXIS,
                                bundle_map=bm[0] if bm else None)

            sm = jax.shard_map(inner, mesh=mesh, in_specs=tuple(in_specs),
                               out_specs=P(), check_vma=False)
            if _bm_spec is not None:
                return jax.jit(lambda b, t: sm(b, t, bundle_map_dev))
            return jax.jit(sm)
        _fp_tree_predict = _mk_fp_predict()

    def _dart_tree_predict(tree_dev):
        if _fp_tree_predict is not None:
            return _fp_tree_predict(bins_t, tree_dev)
        return _predict_binned_tree(bins_t, tree_dev, depth_hint,
                                    bundle_map_dev, B_total)

    if _warm_thread is not None:
        _warm_thread.join()
    _phase.close()
    # from the main thread's first dispatch (a cached factory call and a
    # mask upload ahead of it) to the trees on the host
    boost_span = _telemetry.span("gbdt.fit.boost").start()

    scan_start = 0          # iterations handled by scanned dispatches
    n_scan_chunks = config.num_iterations // SCAN_CHUNK if use_scan else 0
    if n_scan_chunks:
        feature_mask = np.zeros(Fp, bool)
        feature_mask[:F] = True
        fmask_dev = jnp.asarray(feature_mask)
        if featpar:
            fmask_dev = jax.device_put(
                fmask_dev, NamedSharding(mesh, P(DATA_AXIS)))
        if config.objective == "lambdarank":
            scan_fn = _make_scan.__wrapped__(
                _sargs, tuple(sorted(_skw.items())),
                config.bagging_freq, config.seed, is_rf, cache_step=False)
        else:
            scan_fn = _make_scan(_sargs, tuple(sorted(_skw.items())),
                                 config.bagging_freq, config.seed, is_rf)
        chunk_stacks = []
        sc = scores
        for ci in range(n_scan_chunks):
            # the dispatch of one scanned chunk: the enqueue (the first
            # returns once compiled; the device runs behind)
            with _telemetry.span("gbdt.boost.chunk", index=ci,
                                 iterations=SCAN_CHUNK):
                sc, tstacks = scan_fn(
                    bins_t, sc, labels, weights, base_bag_dev, bag_root_key,
                    fmask_dev, upper_bounds, num_bins, bundle_map_dev,
                    init_scores_dev if is_rf else scores,
                    jnp.asarray(prior_iters + ci * SCAN_CHUNK, jnp.int32))
            chunk_stacks.append(tstacks)
            if ci == 0:
                # first dispatch returns once compiled; execution is async
                # until the download below
                measures.compile_s = _time.perf_counter() - _t_train
        # ONE readback for every tree of every chunk (per-field np.asarray
        # would pay a blocking transfer each, 11 fields x chunks); tree
        # ints fit f32 exactly (ids < 2^7, counts <= N < 2^24)
        with _telemetry.span("gbdt.fit.download", chunks=n_scan_chunks):
            flat = np.asarray(_pack_flat(chunk_stacks))
        off = 0
        host_stacks = []
        for ts in chunk_stacks:
            fields = []
            for a in ts:
                n_el = int(np.prod(a.shape))
                fields.append(flat[off:off + n_el].reshape(a.shape)
                              .astype(np.dtype(a.dtype)))
                off += n_el
            host_stacks.append(fields)
        for all_fields in host_stacks:
            for i in range(SCAN_CHUNK):
                for k in range(K):
                    trees.append(Tree(*[a[i, k] for a in all_fields]))
                    tree_class.append(k)
                    tree_weights.append(1.0)
        if is_rf:
            rf_denominator = n_scan_chunks * SCAN_CHUNK
        scores = sc
        scan_start = n_scan_chunks * SCAN_CHUNK

    # the whole boosting loop runs under the profiler guard: an
    # escaping exception (e.g. an injected mid-checkpoint preemption)
    # must close the open step and restore the thread-local active
    # profiler, or later collectives on this thread would keep
    # accumulating into a dead profiler's abandoned step
    try:
        for it in range(scan_start, config.num_iterations):
            if step_profiler is not None:
                step_profiler.step_begin(it)
            # bagging (bagging_fraction/freq semantics): the mask is drawn on
            # device from this key; reusing a key across freq iterations
            # reproduces the persist-until-refresh behavior
            bag_key = jax.random.fold_in(
                bag_root_key, (prior_iters + it) // max(config.bagging_freq, 1))
            if config.feature_fraction < 1.0:
                k = max(1, int(round(F * config.feature_fraction)))
                feature_mask = np.zeros(Fp, bool)  # padded features stay off
                feature_mask[rng.choice(F, k, replace=False)] = True
                fmask_dev = None
            elif fmask_dev is None:
                feature_mask = np.zeros(Fp, bool)
                feature_mask[:F] = True
            if fmask_dev is None:
                fmask_dev = jnp.asarray(feature_mask)
                if featpar:
                    fmask_dev = jax.device_put(
                        fmask_dev, NamedSharding(mesh, P(DATA_AXIS)))

            # dart: drop trees, rebase scores
            dropped: List[int] = []
            if is_dart and trees and rng.random() >= config.skip_drop:
                drop_mask = rng.random(len(trees)) < config.drop_rate
                dropped = list(np.nonzero(drop_mask)[0][:config.max_drop])
                for d in dropped:
                    contrib = (_dart_tree_predict(_to_device_tree(trees[d]))
                               * tree_weights[d])
                    scores = _sub_scores(scores, contrib, tree_class[d], K)

            # mask to 32 bits so looped and scanned runs derive identical keys
            # even under jax_enable_x64 (the scan's seed_base is masked too)
            key = jax.random.PRNGKey(
                (config.seed * 100003 + prior_iters + it) & 0xffffffff)
            if step_profiler is not None:
                step_profiler.mark("data")
                if step_profiler.capture_xla:
                    step_profiler.capture_cost(
                        "gbdt_step", step, bins_t, scores, labels, weights,
                        (base_bag_dev, bag_key), fmask_dev, key,
                        upper_bounds, num_bins, bundle_map_dev,
                        items=N // max(row_shards, 1))   # per-device rows
            tstack, new_scores = step(bins_t, scores, labels, weights,
                                      (base_bag_dev, bag_key), fmask_dev,
                                      key, upper_bounds, num_bins,
                                      bundle_map_dev)
            if eager_host:
                # the host-side download synchronizes, so the compute mark
                # below times the executed tree grow, not just its dispatch
                new_trees = [Tree(*[np.asarray(a[k]) for a in tstack])
                             for k in range(K)]
            else:
                new_trees = None                  # downloaded after the loop
            if it == 0:
                jax.block_until_ready(new_scores)
                measures.compile_s = _time.perf_counter() - _t_train
            if step_profiler is not None:
                step_profiler.mark("compute")

            dropped_weight_changes = []
            if is_dart and dropped:
                # normalize: new trees weighted 1/(|D|+1); dropped scaled |D|/(|D|+1)
                ndrop = len(dropped)
                new_w = 1.0 / (ndrop + 1)
                factor = ndrop / (ndrop + 1)
                for k in range(K):
                    contrib = (_dart_tree_predict(_to_device_tree(new_trees[k]))
                               * new_w)
                    scores = _add_scores(scores, contrib, k, K)
                for d in dropped:
                    old_w = tree_weights[d]
                    tree_weights[d] = old_w * factor
                    dropped_weight_changes.append((d, old_w))
                    contrib = (_dart_tree_predict(_to_device_tree(trees[d]))
                               * tree_weights[d])
                    scores = _add_scores(scores, contrib, tree_class[d], K)
                weights_new = [new_w] * K
            else:
                scores = new_scores
                weights_new = [1.0] * K

            if eager_host:
                for k in range(K):
                    trees.append(new_trees[k])
                    tree_class.append(k)
                    tree_weights.append(weights_new[k])
            else:
                pending_stacks.append((tstack, weights_new))
            if is_rf:
                rf_denominator += 1
                # rf: gradients always at init margin → reset scores (the
                # reset array is device-resident once, reused every iteration)
                if rf_reset_scores is None:
                    rf_reset_scores = init_scores_dev
                scores = rf_reset_scores

            # validation eval + early stopping (TrainUtils.scala:143-169)
            if have_valid:
                _t_eval = _time.perf_counter()
                # incremental: new trees, plus weight deltas of dart-dropped trees
                for k in range(K):
                    contrib = np.asarray(_predict_binned_tree(
                        binned_v, _to_device_tree(new_trees[k]), depth_hint))
                    if K == 1:
                        valid_contrib += contrib * weights_new[0]
                    else:
                        valid_contrib[:, k] += contrib * weights_new[k]
                for d, old_w in dropped_weight_changes:
                    contrib = np.asarray(_predict_binned_tree(
                        binned_v, _to_device_tree(trees[d]), depth_hint))
                    delta_w = tree_weights[d] - old_w
                    if K == 1:
                        valid_contrib += contrib * delta_w
                    else:
                        valid_contrib[:, tree_class[d]] += contrib * delta_w
                if is_rf:
                    # the final rf model averages over ALL trees (carried +
                    # new): un-average the carried model's margin and re-pool
                    base_ = (init_sc[0] if K == 1
                             else np.asarray(init_sc)[None, :])
                    old_sum = (valid_init - base_) * prior_iters
                    vm = base_ + ((old_sum + valid_contrib)
                                  / max(prior_iters + rf_denominator, 1))
                else:
                    vm = valid_init + valid_contrib
                val = metric_fn(yv, vm, wv)
                eval_history.append(EvalRecord(it, metric_name, val))
                improved = (best_val is None
                            or (val > best_val if larger_better else val < best_val))
                if improved:
                    best_val, best_iter, rounds_no_improve = val, it, 0
                else:
                    rounds_no_improve += 1
                    if (config.early_stopping_round > 0
                            and rounds_no_improve >= config.early_stopping_round):
                        measures.eval_s += _time.perf_counter() - _t_eval
                        break
                measures.eval_s += _time.perf_counter() - _t_eval
            if callbacks:
                for cb in callbacks:
                    cb(it, trees, eval_history)
            if ckpt_every and (it + 1) % ckpt_every == 0:
                pre_t, pre_c, pre_w = (
                    (init_model.trees, init_model.tree_class,
                     init_model.tree_weights) if init_model else ([], [], []))
                _write_checkpoint(checkpoint_dir, Booster(
                    pre_t + trees, pre_c + tree_class, pre_w + tree_weights,
                    K, config.objective, init_sc, mapper, feature_names,
                    config, bundler=bundler))
            if step_profiler is not None:
                step_profiler.step_end()      # eval + checkpoint → "other"
    finally:
        if step_profiler is not None:
            step_profiler.finish()    # early-stop break / exception path

    # deferred mode: one sync for the whole run, then download every tree in
    # ONE transfer per field (T, K, M) — per-stack downloads pay a blocking
    # round trip each, which dominates small-tree training
    if pending_stacks:
        # one jitted computation for ALL fields: stacking field-by-field in
        # eager ops compiles 11 tiny XLA programs (~13 s on a cold cache);
        # a single fused stack compiles once
        with _telemetry.span("gbdt.fit.download",
                             trees=len(pending_stacks)):
            stacked = jax.jit(
                lambda ts: Tree(*[jnp.stack([getattr(t, f) for t in ts])
                                  for f in Tree._fields]))(
                [t for t, _ in pending_stacks])
            all_fields = [np.asarray(a) for a in stacked]
        for i, (_, per_class_weights) in enumerate(pending_stacks):
            for k in range(K):
                trees.append(Tree(*[a[i, k] for a in all_fields]))
                tree_class.append(k)
                tree_weights.append(per_class_weights[k])
    measures.training_s = _time.perf_counter() - _t_train
    measures.iterations = len(trees) // max(K, 1)  # this fit only — before
    boost_span.set(iterations=measures.iterations)
    boost_span.close()
    if init_model is not None:                     # the warm-start fold-in
        # continued training: carry previous trees forward (modelString
        # warm-start fold-in, LightGBMBase.scala:38-59)
        trees = init_model.trees + trees
        tree_class = init_model.tree_class + tree_class
        tree_weights = init_model.tree_weights + tree_weights
    measures.total_s = _time.perf_counter() - _t0
    _publish_measures(measures, config, fit_span, n_rows=n, n_features=F)
    booster = Booster(trees, tree_class, tree_weights, K, config.objective,
                      init_sc, mapper, feature_names, config,
                      best_iteration=best_iter, bundler=bundler)
    booster.measures = measures
    return booster, eval_history


#: per-phase wall-clock buckets: sub-second phases through multi-minute fits
_PHASE_BUCKETS = (0.01, 0.05, 0.1, 0.5, 1.0, 2.0, 5.0, 10.0, 30.0, 60.0,
                  120.0, 300.0, 600.0)


def _publish_measures(measures: "InstrumentationMeasures",
                      config: "BoostingConfig", fit_span, n_rows: int,
                      n_features: int) -> None:
    """Mirror one fit's InstrumentationMeasures into the process
    telemetry: a per-phase histogram (the round-over-round "which boost
    phase regressed" answer), an iteration counter, the resolved
    two-level-mode gauge, and the fit's attribution on its ``gbdt.fit``
    span."""
    try:
        reg = _telemetry.get_registry()
        hist = reg.histogram(
            "gbdt_phase_seconds", "per-phase wall clock of gbdt fits",
            ("phase",), buckets=_PHASE_BUCKETS)
        for phase, secs in (("binning", measures.binning_s),
                            ("data_prep", measures.data_prep_s),
                            ("compile", measures.compile_s),
                            ("training", measures.training_s),
                            ("eval", measures.eval_s),
                            ("total", measures.total_s)):
            hist.observe(secs, phase=phase)
        reg.counter("gbdt_iterations_total",
                    "boosting iterations trained").inc(
                        max(measures.iterations, 0))
        reg.gauge("gbdt_two_level_active",
                  "1 when the finished fit trained with coarse-then-"
                  "refine histograms", ()).set(
                      1.0 if config.two_level_hist in ("on", True) else 0.0)
        fit_span.set(
            rows=n_rows, features=n_features,
            iterations=measures.iterations, hist_path=measures.hist_path,
            two_level=str(config.two_level_hist),
            **{k: round(v, 4) for k, v in measures.as_dict().items()
               if isinstance(v, float)})
    except Exception:    # telemetry must never break training
        pass


def _to_device_tree(t: Tree) -> Tree:
    return Tree(*[jnp.asarray(a) for a in t])


def _sub_scores(scores, contrib, k, K):
    if K == 1:
        return scores - contrib
    return scores.at[:, k].add(-contrib)


def _add_scores(scores, contrib, k, K):
    if K == 1:
        return scores + contrib
    return scores.at[:, k].add(contrib)
