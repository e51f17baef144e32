"""Roofline byte-diet pins: remat bit-exactness, precision-policy
parity, fused-GBDT bf16 ingest parity + resume, the roofline auditor's
cost capture, and the bf16 colstore round-trip.

The numerics contracts (what is bitwise vs what is parity-pinned) live
in models/dl/precision.py's module docstring; these tests are the pins.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from synapseml_tpu.core.dataset import Dataset
from synapseml_tpu.telemetry.roofline import capture, top_byte_hlos

pytestmark = pytest.mark.perf



# ---------------------------------------------------------------------------
# roofline auditor
# ---------------------------------------------------------------------------

class TestRooflineAuditor:
    def test_capture_reports_cost_and_top_hlos(self):
        fn = jax.jit(lambda a, b: (a @ b).sum())
        a = jnp.ones((128, 128), jnp.float32)
        cost = capture(fn, a, a)
        assert cost is not None
        assert cost["flops"] > 0 and cost["bytes_accessed"] > 0
        assert isinstance(cost["top_hlos"], list)
        # the matmul's operands/result dominate this tiny program; the
        # top movers must carry positive MB estimates, sorted descending
        if cost["top_hlos"]:
            mbs = [h["mbytes"] for h in cost["top_hlos"]]
            assert mbs == sorted(mbs, reverse=True)
            assert all(m > 0 for m in mbs)

    def test_capture_never_raises(self):
        assert capture(object()) is None

    def test_top_byte_hlos_skips_fused_computations(self):
        text = """\
%fused_computation.1 (p: f32[1000000]) -> f32[1000000] {
  %huge = f32[1000000]{0} add(f32[1000000]{0} %p, f32[1000000]{0} %p)
}
ENTRY %main (a: f32[16]) -> f32[16] {
  %small = f32[16]{0} multiply(f32[16]{0} %a, f32[16]{0} %a)
  ROOT %f = f32[16]{0} fusion(f32[16]{0} %small), kind=kLoop
}
"""
        tops = top_byte_hlos(text)
        assert all(h["mbytes"] < 0.001 for h in tops), tops


# ---------------------------------------------------------------------------
# DL: remat bit-exactness + precision parity
# ---------------------------------------------------------------------------

def _vision_ds(n=16, side=24, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    imgs = [rng.normal(size=(side, side, 3)).astype(np.float32)
            for _ in range(n)]
    labels = rng.integers(0, classes, n).astype(np.float64)
    return Dataset({"image": imgs, "label": labels})


def _vision_losses(ds, **params):
    from synapseml_tpu.models.dl.estimators import DeepVisionClassifier
    est = DeepVisionClassifier(backbone="resnet18", batchSize=16,
                               maxEpochs=1, seed=0, **params)
    model = est.fit(ds)
    return [h["loss"] for h in model.modelPayload["history"]]


class TestRematPrecisionDL:
    @pytest.fixture(scope="class")
    def vds(self):
        return _vision_ds()

    @pytest.fixture(scope="class")
    def base_losses(self, vds):
        return _vision_losses(vds)

    def test_vision_full_remat_bit_exact(self, vds, base_losses):
        """The acceptance pin: the remat leg's loss trajectory is
        BIT-identical to no-remat (jax.checkpoint re-runs the identical
        ops on the identical values)."""
        assert _vision_losses(vds, rematPolicy="full") == base_losses

    def test_remat_does_not_change_param_paths(self):
        """nn.remat must not rename the blocks — a renamed tree would
        draw DIFFERENT init weights (and break pretrained imports)."""
        from synapseml_tpu.models.dl.resnet import make_backbone
        x = np.zeros((2, 24, 24, 3), np.float32)
        v0 = make_backbone("resnet18", num_classes=3).init(
            jax.random.PRNGKey(0), x, train=False)
        v1 = make_backbone("resnet18", num_classes=3, remat="full").init(
            jax.random.PRNGKey(0), x, train=False)
        assert (jax.tree_util.tree_structure(v0)
                == jax.tree_util.tree_structure(v1))
        for a, b in zip(jax.tree_util.tree_leaves(v0),
                        jax.tree_util.tree_leaves(v1)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_vision_bf16_grad_parity(self, vds, base_losses):
        """'bf16_grad' rounds the gradient stream — NOT bitwise, but the
        first-step loss (identical init, loss computed before the first
        update) must match bitwise and the trajectory stays close."""
        got = _vision_losses(vds, precision="bf16_grad")
        assert np.isfinite(got).all()
        # one step per epoch in this setup, so history[0] IS the first
        # step's loss — computed from the forward pass BEFORE the grad
        # cast touches anything, hence bitwise
        assert got[0] == base_losses[0]
        assert abs(got[-1] - base_losses[-1]) < 0.05

    def test_text_remat_and_precision(self):
        from synapseml_tpu.models.dl.estimators import DeepTextClassifier
        texts = [f"w{i % 7} t{i % 3} x" for i in range(16)]
        ds = Dataset({"text": texts,
                      "label": (np.arange(16) % 2).astype(np.float64)})

        def losses(**params):
            est = DeepTextClassifier(modelSize="tiny", batchSize=8,
                                     maxEpochs=1, maxTokenLen=12, seed=0,
                                     **params)
            return [h["loss"]
                    for h in est.fit(ds).modelPayload["history"]]

        base = losses()
        # transformer blocks re-round through different fusions under
        # remat (dropout/layernorm chains) — parity, not bitwise
        for params in (dict(rematPolicy="full"),
                       dict(rematPolicy="dots_saveable"),
                       dict(precision="bf16_grad")):
            got = losses(**params)
            assert np.isfinite(got).all()
            assert abs(got[-1] - base[-1]) < 0.05, (params, got, base)

    def test_precision_resolve_errors(self):
        from synapseml_tpu.models.dl.precision import (remat_policy,
                                                       resolve_precision)
        with pytest.raises(ValueError, match="precision"):
            resolve_precision("fp8")
        with pytest.raises(ValueError, match="rematPolicy"):
            remat_policy("everything")
        assert remat_policy(None) == (False, None)
        assert remat_policy(True)[0] is True
        assert resolve_precision(None).name == "bf16"
        assert resolve_precision("bf16_grad").casts_grads

    def test_precision_switch_refuses_resume(self, tmp_path, vds):
        """'bf16_grad' changes the numerics the resumed batches train
        under — the checkpoint config guard must refuse the switch."""
        from synapseml_tpu.models.dl.estimators import DeepVisionClassifier
        kw = dict(backbone="resnet18", batchSize=16, seed=0,
                  checkpointDir=str(tmp_path / "ck"), checkpointInterval=1)
        DeepVisionClassifier(maxEpochs=1, **kw).fit(vds)
        with pytest.raises(ValueError, match="data-order config"):
            DeepVisionClassifier(precision="bf16_grad", maxEpochs=2,
                                 **kw).fit(vds)


# ---------------------------------------------------------------------------
# GBDT: fused bf16 ingest
# ---------------------------------------------------------------------------

def _gbdt_task(n=20_000, f=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] * 2 - X[:, 1] + X[:, 2] * X[:, 3]
         + rng.normal(scale=0.5, size=n) > 0).astype(np.float64)
    return X, y


class TestFusedGBDTIngest:
    def test_fused_vs_unfused_holdout_auc_parity(self):
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        from synapseml_tpu.models.gbdt.metrics import auc
        X, y = _gbdt_task()
        Xh, yh = _gbdt_task(seed=7)
        aucs = {}
        for fused in (False, True):
            cfg = BoostingConfig(objective="binary", num_iterations=20,
                                 num_leaves=31, max_bin=63,
                                 fused_ingest=fused)
            booster, _ = train(X, y, cfg)
            aucs[fused] = auc(yh, booster.predict_margin(Xh))
        assert abs(aucs[True] - aucs[False]) <= 0.005, aucs

    def test_fused_preempt_resume_bit_exact(self, tmp_path):
        """kill→resume through the CheckpointManager stays bit-exact
        WITH the fused (bf16-ingest) path on: the resumed run's margins
        equal the uninterrupted fused run's bitwise."""
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        X, y = _gbdt_task(n=5_000)
        cfg = dict(objective="binary", num_leaves=15, max_bin=63,
                   fused_ingest=True)
        full, _ = train(X, y, BoostingConfig(num_iterations=10, **cfg))
        ck = str(tmp_path / "ck")
        train(X, y, BoostingConfig(num_iterations=5, **cfg),
              checkpoint_dir=ck, checkpoint_interval=1)
        resumed, _ = train(X, y, BoostingConfig(num_iterations=10, **cfg),
                           checkpoint_dir=ck, checkpoint_interval=1)
        np.testing.assert_array_equal(resumed.predict_margin(X[:512]),
                                      full.predict_margin(X[:512]))

    def test_ingest_toggle_refuses_resume(self, tmp_path):
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        X, y = _gbdt_task(n=2_000)
        ck = str(tmp_path / "ck")
        train(X, y, BoostingConfig(objective="binary", num_iterations=3,
                                   num_leaves=15, max_bin=63),
              checkpoint_dir=ck, checkpoint_interval=1)
        with pytest.raises(ValueError, match="fused_ingest"):
            train(X, y,
                  BoostingConfig(objective="binary", num_iterations=6,
                                 num_leaves=15, max_bin=63,
                                 fused_ingest=False),
                  checkpoint_dir=ck, checkpoint_interval=1)

    def test_bad_knob_fails_fast(self):
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        X, y = _gbdt_task(n=200)
        with pytest.raises(ValueError, match="fused_ingest"):
            train(X, y, BoostingConfig(objective="binary",
                                       num_iterations=1,
                                       fused_ingest="sometimes"))

    def test_fused_step_materializes_bf16_ingest(self):
        """The point of the fusion: the g/h arrays the histogram builds
        consume are bf16 under fused ingest (f32 unfused) — asserted on
        the traced step itself, not inferred from timings."""
        from synapseml_tpu.models.gbdt.booster import (_make_step,
                                                       _step_factory_args,
                                                       BoostingConfig)

        def gh_dtypes(fused):
            cfg = BoostingConfig(objective="binary", num_iterations=1,
                                 num_leaves=7, max_bin=63,
                                 fused_ingest=fused)
            args, kw = _step_factory_args(cfg, 1, None, False, False)
            step = _make_step.__wrapped__(*args, **kw)
            N, F, B = 256, 4, 64
            jaxpr = jax.make_jaxpr(step)(
                jnp.zeros((F, N), jnp.int32), jnp.zeros(N), jnp.zeros(N),
                jnp.ones(N), (jnp.ones(N), jax.random.PRNGKey(0)),
                jnp.ones(F, bool), jax.random.PRNGKey(1),
                jnp.zeros((F, B), jnp.float32),
                jnp.full(F, B, jnp.int32), None)
            return str(jaxpr)

        assert "bf16" in gh_dtypes(True)
        assert "bf16" not in gh_dtypes(False)


# ---------------------------------------------------------------------------
# bf16 colstore
# ---------------------------------------------------------------------------

class TestBf16Colstore:
    def test_round_trip_matches_jax_rne(self):
        from synapseml_tpu.io.colstore import (bf16_bits_to_f32,
                                               f32_to_bf16_bits)
        rng = np.random.default_rng(0)
        v = (rng.normal(size=4096).astype(np.float32)
             * np.float32(10.0) ** rng.integers(-20, 20, 4096))
        v[:4] = [np.nan, np.inf, -np.inf, 0.0]
        got = bf16_bits_to_f32(f32_to_bf16_bits(v))
        ref = np.asarray(jnp.asarray(v).astype(jnp.bfloat16)
                         .astype(jnp.float32))
        fin = np.isfinite(v)
        np.testing.assert_array_equal(got[fin], ref[fin])
        assert np.isnan(got[0])
        assert got[1] == np.inf and got[2] == -np.inf

    def test_colstore_half_bytes_and_reads(self, tmp_path):
        from synapseml_tpu.io.colstore import (ChunkedColumnSource,
                                               bf16_bits_to_f32,
                                               f32_to_bf16_bits,
                                               write_matrix)
        rng = np.random.default_rng(1)
        mat = rng.normal(size=(3_000, 5)).astype(np.float32)
        p32 = str(tmp_path / "m32.smlc")
        p16 = str(tmp_path / "m16.smlc")
        write_matrix(p32, mat)
        write_matrix(p16, mat, dtype="bf16")
        assert os.path.getsize(p16) < 0.51 * os.path.getsize(p32) + 64
        src = ChunkedColumnSource(p16, label_col=4, chunk_rows=512)
        Xs = np.concatenate([cx for cx, _, _ in src.iter_chunks()])
        expect = bf16_bits_to_f32(f32_to_bf16_bits(mat[:, :4]))
        np.testing.assert_array_equal(Xs, expect)
        np.testing.assert_array_equal(
            src.read_labels(), bf16_bits_to_f32(f32_to_bf16_bits(mat[:, 4])))
        # shard + sample read the same upcast path
        sh = src.shard(1, 3)
        assert sh.num_rows == 1000
        assert sh.sample_rows(10).shape == (10, 4)

    def test_streamed_train_from_bf16_colstore(self, tmp_path):
        from synapseml_tpu.io.colstore import ChunkedColumnSource, write_matrix
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        from synapseml_tpu.models.gbdt.metrics import auc
        X, y = _gbdt_task(n=6_000, f=5)
        p = str(tmp_path / "t.smlc")
        write_matrix(p, np.concatenate(
            [X, np.asarray(y, np.float32)[:, None]], axis=1), dtype="bf16")
        src = ChunkedColumnSource(p, label_col=5, chunk_rows=2048)
        booster, _ = train(src, None,
                           BoostingConfig(objective="binary",
                                          num_iterations=10, max_bin=63))
        Xh, yh = _gbdt_task(n=4_000, f=5, seed=9)
        assert auc(yh, booster.predict_margin(Xh)) > 0.8

