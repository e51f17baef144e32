"""Compressed + sharded collectives (parallel/compression.py).

Pins the full contract of the quantized-allreduce layer: codec
round-trips (seeded fuzz, per-chunk scale correctness, NaN/Inf
pass-through), the error-feedback convergence recursion, sharded
weight-update equivalence against the replicated pjit step, holdout
parity for int8-compressed GBDT/DL training, wire-byte accounting
(`collective_wire_bytes_total` / `collective_compression_ratio`), and
checkpoint compatibility (kill→resume bit-exact with compression on,
error-feedback residuals riding the CheckpointManager pytree).
"""

import functools
import os
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

import flax.linen as nn

from synapseml_tpu.core.checkpoint import CheckpointManager
from synapseml_tpu.models.dl.training import DLTrainer, OptimizerConfig
from synapseml_tpu.parallel.collectives import allreduce_fn
from synapseml_tpu.parallel.compression import (
    CollectiveConfig, bf16_decode, bf16_encode, compressed_psum,
    compressed_tree_sync, int8_decode, int8_encode, logical_nbytes,
    resolve_collective_config, wire_nbytes)
from synapseml_tpu.parallel.mesh import DATA_AXIS, data_parallel_mesh
from synapseml_tpu.telemetry import get_registry

pytestmark = pytest.mark.comms

CHUNK = 256


def _pad_chunks(x, chunk=CHUNK):
    pad = (-len(x)) % chunk
    return np.pad(x, (0, pad))


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------

class TestCodecs:
    @pytest.mark.parametrize("seed", range(6))
    def test_int8_roundtrip_fuzz(self, seed):
        """Seeded shapes/scales: decode error per element stays within
        half a quantization step of its chunk (scale = amax/127)."""
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 9)) * CHUNK
        scale = 10.0 ** rng.integers(-4, 4)
        x = (rng.normal(size=n) * scale).astype(np.float32)
        q, s = jax.jit(functools.partial(int8_encode, chunk=CHUNK))(
            jnp.asarray(x))
        assert q.dtype == jnp.int8 and s.shape == (n // CHUNK,)
        dec = np.asarray(int8_decode(q, s))
        amax = np.abs(x.reshape(-1, CHUNK)).max(axis=1)
        bound = amax / 127.0 / 2.0 + 1e-7 * scale
        err = np.abs(dec - x).reshape(-1, CHUNK)
        assert (err <= bound[:, None] + 1e-12).all(), err.max()

    def test_int8_per_chunk_scale_correctness(self):
        x = np.zeros(2 * CHUNK, np.float32)
        x[10] = 254.0          # chunk 0 amax
        x[CHUNK + 3] = -0.127  # chunk 1 amax
        q, s = int8_encode(jnp.asarray(x), CHUNK)
        np.testing.assert_allclose(np.asarray(s), [2.0, 0.001], rtol=1e-6)
        # the amax element hits +/-127 exactly → lossless at the extreme
        assert int(np.asarray(q).reshape(-1)[10]) == 127
        assert int(np.asarray(q).reshape(-1)[CHUNK + 3]) == -127

    def test_zero_chunk_roundtrips_to_zero(self):
        x = jnp.zeros(CHUNK, jnp.float32)
        dec = int8_decode(*int8_encode(x, CHUNK))
        np.testing.assert_array_equal(np.asarray(dec), np.zeros(CHUNK))

    @pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
    def test_nonfinite_chunk_passthrough(self, bad):
        """A chunk holding any non-finite decodes to ALL-NaN (overflow
        detection still trips, at chunk granularity); clean neighbor
        chunks are untouched."""
        x = np.ones(3 * CHUNK, np.float32)
        x[CHUNK + 7] = bad
        dec = np.asarray(int8_decode(*int8_encode(jnp.asarray(x), CHUNK)))
        assert np.isnan(dec[CHUNK:2 * CHUNK]).all()
        assert np.isfinite(dec[:CHUNK]).all()
        assert np.isfinite(dec[2 * CHUNK:]).all()

    def test_bf16_roundtrip(self):
        x = np.linspace(-3, 3, 1024, dtype=np.float32)
        dec = np.asarray(bf16_decode(bf16_encode(jnp.asarray(x))))
        np.testing.assert_allclose(dec, x, rtol=1 / 128)
        # non-finites cast through natively
        assert np.isnan(float(bf16_decode(bf16_encode(jnp.float32(np.nan)))))

    def test_wire_bytes_accounting(self):
        big = jnp.zeros(4096, jnp.float32)
        assert logical_nbytes(big) == 4096 * 4
        i8 = CollectiveConfig(compression="int8", min_size=1024, chunk=CHUNK)
        assert wire_nbytes(big, i8) == 4096 + (4096 // CHUNK) * 4
        assert logical_nbytes(big) / wire_nbytes(big, i8) > 3.8
        bf = CollectiveConfig(compression="bf16", min_size=1024)
        assert wire_nbytes(big, bf) == 4096 * 2
        # the min-size threshold keeps tiny tensors f32 on the wire
        tiny = jnp.zeros(16, jnp.float32)
        assert wire_nbytes(tiny, i8) == 16 * 4
        # non-float payloads never compress
        ints = jnp.zeros(4096, jnp.int32)
        assert wire_nbytes(ints, i8) == 4096 * 4
        # a non-chunk-multiple total rounds up to whole chunks (the flat
        # stream pads before encoding — those pad values ride the wire)
        odd = jnp.zeros(4096 + 100, jnp.float32)
        padded = -(-(4096 + 100) // CHUNK) * CHUNK
        assert wire_nbytes(odd, i8) == padded + (padded // CHUNK) * 4

    def test_wire_bytes_count_channel_padding(self):
        """channel_major accounting mirrors _channel_major_padded: each
        trailing channel pads to a chunk multiple (the per_channel=1931
        boundary case), so the reported wire includes the pad bytes the
        codec actually ships instead of overstating the win."""
        i8 = CollectiveConfig(compression="int8", min_size=1024, chunk=CHUNK)
        hist = jnp.zeros((1931, 3), jnp.float32)        # 1931 % CHUNK != 0
        per_p = -(-1931 // CHUNK) * CHUNK
        vals = 3 * per_p
        assert wire_nbytes(hist, i8, channel_major=True) \
            == vals + (vals // CHUNK) * 4
        # without the layout flag (flat-stream callers) only the stream
        # tail rounds up
        flat_vals = -(-(1931 * 3) // CHUNK) * CHUNK
        assert wire_nbytes(hist, i8) == flat_vals + (flat_vals // CHUNK) * 4

    def test_resolve_shorthand(self):
        assert resolve_collective_config(None) is None
        assert resolve_collective_config("none") is None
        cfg = resolve_collective_config("int8")
        assert cfg.compression == "int8" and cfg.error_feedback
        full = CollectiveConfig(sharded_update=True)
        assert resolve_collective_config(full) is full
        # the dataclasses.asdict form round-trips (checkpointed
        # BoostingConfigs carry CollectiveConfig values as plain dicts)
        import dataclasses as _dc
        assert resolve_collective_config(_dc.asdict(full)) == full
        assert resolve_collective_config(
            _dc.asdict(CollectiveConfig())) is None
        with pytest.raises(ValueError):
            resolve_collective_config("fp4")
        with pytest.raises(TypeError):
            resolve_collective_config(123)
        with pytest.raises(ValueError):
            CollectiveConfig(compression="fp8")


# ---------------------------------------------------------------------------
# compressed psum over a real mesh
# ---------------------------------------------------------------------------

def _psum_fn(mesh, cfg):
    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=P(DATA_AXIS), out_specs=P(),
                       check_vma=False)
    def red(v):
        return compressed_psum(v.sum(0), DATA_AXIS, cfg)
    return red


class TestCompressedPsum:
    def test_int8_matches_f32_within_quant_tolerance(self):
        mesh = data_parallel_mesh(4)
        rng = np.random.default_rng(1)
        v = rng.normal(size=(4, 2048)).astype(np.float32)
        out = np.asarray(_psum_fn(
            mesh, CollectiveConfig(compression="int8", min_size=64))(v))
        ref = v.sum(0)
        assert np.abs(out - ref).max() / np.abs(ref).max() < 0.02

    def test_bf16_matches_f32_within_tolerance(self):
        mesh = data_parallel_mesh(4)
        rng = np.random.default_rng(2)
        v = rng.normal(size=(4, 1024)).astype(np.float32)
        out = np.asarray(_psum_fn(
            mesh, CollectiveConfig(compression="bf16", min_size=64))(v))
        np.testing.assert_allclose(out, v.sum(0), rtol=0.05, atol=0.05)

    def test_none_config_is_bit_identical_to_psum(self):
        mesh = data_parallel_mesh(4)
        rng = np.random.default_rng(3)
        v = rng.normal(size=(4, 512)).astype(np.float32)
        out = np.asarray(_psum_fn(mesh, None)(v))
        ref = np.asarray(_psum_fn(
            mesh, CollectiveConfig(compression="none"))(v))
        np.testing.assert_array_equal(out, ref)

    @pytest.mark.parametrize("per_channel", [2048, 1931])
    def test_channel_major_chunking_protects_small_channels(self,
                                                            per_channel):
        """Histogram payloads carry counts ~1e4x gradients on the last
        axis; interleaved chunking would quantize the gradient channel
        to zero.  The channel-major relayout + per-channel chunk
        padding keeps each chunk single-channel, so the small channel
        survives with relative (not count-dominated) precision — ALSO
        when the per-channel element count is not a chunk multiple
        (1931: the real GBDT case, features x bins rarely aligns; a
        boundary chunk spanning hess|count would flatten the hess
        half)."""
        mesh = data_parallel_mesh(4)
        rng = np.random.default_rng(4)
        n = per_channel
        hist = np.stack([rng.normal(size=(4, n)) * 1e-2,         # grads
                         np.abs(rng.normal(size=(4, n))) * 1e-2,
                         rng.integers(100, 20000, (4, n)).astype(float)],
                        axis=-1).astype(np.float32)              # counts
        out = np.asarray(_psum_fn(
            mesh, CollectiveConfig(compression="int8", min_size=64))(hist))
        ref = hist.sum(0)
        for ch in (0, 1):                       # both small channels
            err = np.abs(out[..., ch] - ref[..., ch]).max()
            assert err < np.abs(ref[..., ch]).max() * 0.02, (ch, err)

    def test_small_payload_stays_f32(self):
        mesh = data_parallel_mesh(4)
        v = np.random.default_rng(5).normal(size=(4, 32)).astype(np.float32)
        out = np.asarray(_psum_fn(
            mesh, CollectiveConfig(compression="int8", min_size=2048))(v))
        np.testing.assert_array_equal(out, np.asarray(_psum_fn(mesh, None)(v)))

    def test_wire_metrics_and_flight_codec(self):
        """The host-dispatched compressed allreduce lands wire bytes
        (< logical / 1.8 for int8) in collective_wire_bytes_total and
        tags its flight collective.end with codec + both byte counts."""
        from synapseml_tpu.telemetry.flight import get_flight
        mesh = data_parallel_mesh(4)
        cfg = CollectiveConfig(compression="int8", min_size=64)
        fn = allreduce_fn(mesh, config=cfg)
        x = np.random.default_rng(6).normal(size=(4, 4096)).astype(np.float32)
        reg = get_registry()

        def wire():
            m = reg.get("collective_wire_bytes_total")
            return (m.value(op="allreduce_fn", axis=DATA_AXIS, codec="int8",
                            strategy="flat")
                    if m else 0.0)

        before = wire()
        out = np.asarray(fn(jnp.asarray(x)))
        # quantization error compounds over both wire phases and 4
        # summed ranks — this test pins the ACCOUNTING, the codec's
        # accuracy bounds live in TestCodecs/TestCompressedPsum
        np.testing.assert_allclose(out, x.sum(0), atol=0.5)
        logical = x.size * 4             # the stacked payload _record sees
        gained = wire() - before
        assert gained == wire_nbytes(jnp.asarray(x), cfg), gained
        assert 0 < gained <= logical / 1.8, (gained, logical)
        ratio = reg.get("collective_compression_ratio").value(
            op="allreduce_fn", axis=DATA_AXIS, codec="int8",
            strategy="flat")
        assert ratio >= 1.8
        ends = [e for e in get_flight().events()
                if e.get("kind") == "collective.end"
                and e.get("op") == "allreduce_fn"
                and e.get("codec") == "int8"]
        assert ends, "no codec-tagged collective.end flight event"
        ev = ends[-1]
        assert ev["nbytes"] < ev["logical_nbytes"] / 1.8


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------

class TestErrorFeedback:
    def _descend(self, error_feedback: bool, compression="int8",
                 steps=400):
        """Quantized gradient descent on a quadratic whose gradient
        chunk carries a CONSTANT spike coordinate pinning the int8
        chunk scale at ~100/127: the true per-coordinate gradients
        (≤ 0.02) sit far below half a quantization step, so WITHOUT
        error feedback they round to zero on every single step and the
        quadratic never moves; WITH it the residual accumulates until
        it crosses the step and the time-average tracks the f32
        trajectory.  The spike is excluded from the update (its role is
        only to hold the scale up, the way a large-magnitude layer pins
        the scale of a shared bucket)."""
        mesh = data_parallel_mesh(1)
        cfg = CollectiveConfig(compression=compression,
                               error_feedback=error_feedback, min_size=8)
        target = jnp.ones(CHUNK, jnp.float32)

        @jax.jit
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(), P(DATA_AXIS), P()),
            out_specs=(P(), P(DATA_AXIS)), check_vma=False)
        def step(w, res, lr):
            g = 0.02 * (w - target)
            g = g.at[0].set(100.0)
            red, new_res = compressed_tree_sync(
                {"w": g}, DATA_AXIS, cfg,
                residuals={"w": res} if error_feedback else None,
                mean=True)
            upd = red["w"].at[0].set(0.0)
            return w - lr * upd, (new_res["w"] if error_feedback
                                  else jnp.zeros_like(res))

        w = jnp.zeros(CHUNK, jnp.float32)
        res = jnp.zeros((1, CHUNK), jnp.float32)
        for t in range(steps):
            w, res = step(w, res, jnp.float32(2.0 / (1.0 + t / 40.0)))
        return float(jnp.mean((w[1:] - 1.0) ** 2))

    def test_error_feedback_reaches_f32_quality(self):
        loss_ef = self._descend(error_feedback=True)
        loss_f32 = self._descend(error_feedback=True, compression="bf16")
        # int8+EF lands in f32-quality territory (bf16 is effectively
        # f32 at this scale; both ~1e-4 vs the no-EF stall at 1.0)
        assert loss_ef < 1e-3, loss_ef
        assert loss_f32 < 1e-2, loss_f32

    def test_without_error_feedback_stalls(self):
        loss_no_ef = self._descend(error_feedback=False)
        loss_ef = self._descend(error_feedback=True)
        # every true gradient rounds to zero: the loss never leaves its
        # initial value of 1.0 per coordinate
        assert loss_no_ef > 0.5, loss_no_ef
        assert loss_no_ef > 100 * max(loss_ef, 1e-8), (loss_no_ef, loss_ef)


# ---------------------------------------------------------------------------
# DLTrainer: sharded update + compressed gradient sync
# ---------------------------------------------------------------------------

class _MLP(nn.Module):
    @nn.compact
    def __call__(self, x, deterministic=True):
        x = nn.Dense(64)(x)
        x = nn.relu(x)
        x = nn.Dense(64)(x)
        x = nn.relu(x)
        return nn.Dense(4)(x)


def _mlp_data(n=64, d=16, k=4, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, k, n).astype(np.int32)
    return X, y


def _run_trainer(collective, steps=8, clip=1.0, devices=4):
    mesh = data_parallel_mesh(devices)
    X, y = _mlp_data()
    opt = OptimizerConfig(name="adamw", learning_rate=1e-2,
                          schedule="constant", grad_clip_norm=clip)
    tr = DLTrainer(_MLP(), opt, mesh, collective=collective)
    state = tr.init_state(0, X[:8])
    step = tr.train_step()
    key = jax.random.PRNGKey(0)
    bi, bl = tr.shard_batch((X, y))
    metrics = {}
    for _ in range(steps):
        state, metrics = step(state, (bi,), bl, key)
    return tr, state, step, {k: float(v) for k, v in metrics.items()}


class TestShardedUpdate:
    def test_sharded_update_matches_replicated(self):
        """Acceptance: reduce-scatter + 1/N-shard optimizer update +
        param all-gather is bit-comparable to the replicated pjit
        update (same data, same optimizer, global-norm clip active on
        both sides)."""
        _, s_base, _, m_base = _run_trainer(None)
        _, s_sh, _, m_sh = _run_trainer(CollectiveConfig(sharded_update=True))
        for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(
                            s_base.params)),
                        jax.tree_util.tree_leaves(jax.device_get(
                            s_sh.params))):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
        assert abs(m_base["loss"] - m_sh["loss"]) < 1e-5

    def test_manual_step_takes_a_model_with_partitioning_metadata(self):
        """The models users train (``TextEncoder``, ResNet) box their
        params in ``nn.Partitioned`` with LOGICAL axis names.  Inside
        the manual shard_map step those names must not be applied as a
        sharding constraint over the pure ``data`` mesh (flax 0.12 does
        so wherever jax has a mesh context set, which a shard_map body
        does): the manual step runs, and its first loss equals the pjit
        step's (same init, same batch, dropout off)."""
        from synapseml_tpu.models.dl.transformer import (TextEncoder,
                                                         TransformerConfig)
        cfg = TransformerConfig.tiny(num_classes=2, dropout_rate=0.0)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (16, 16))
        mask = np.ones((16, 16), bool)
        labels = rng.integers(0, 2, 16)
        losses = {}
        for tag, collective in (("pjit", None),
                                ("manual", CollectiveConfig(manual=True))):
            tr = DLTrainer(TextEncoder(cfg), OptimizerConfig(
                learning_rate=1e-3), data_parallel_mesh(4),
                collective=collective)
            state = tr.init_state(0, ids, mask)
            bi, bm, bl = tr.shard_batch((ids, mask, labels))
            _, m = tr.train_step()(state, (bi, bm), bl,
                                   jax.random.PRNGKey(0))
            losses[tag] = float(m["loss"])
        assert np.isfinite(losses["manual"])
        assert abs(losses["pjit"] - losses["manual"]) < 1e-4, losses

    def test_sharded_moments_are_actually_sharded(self):
        tr, state, _, _ = _run_trainer(CollectiveConfig(sharded_update=True))
        info = tr._shard_info
        flat_leaves = [lf for lf in jax.tree_util.tree_leaves(
                           state.opt_state["flat"])
                       if getattr(lf, "ndim", 0) >= 1
                       and lf.shape[0] == info["padded"]]
        assert flat_leaves, "no flat moment buffers found"
        for lf in flat_leaves:
            spec = lf.sharding.spec
            assert tuple(spec)[:1] == (DATA_AXIS,), spec

    def test_sharded_update_composes_with_int8(self):
        _, s_base, _, m_base = _run_trainer(None)
        _, s_c, _, m_c = _run_trainer(CollectiveConfig(
            compression="int8", error_feedback=True, sharded_update=True,
            min_size=64))
        # quantized wire: close, not equal
        assert abs(m_base["loss"] - m_c["loss"]) < 0.05

    def test_sharded_update_with_no_eligible_leaves_still_runs(self):
        """min_size above every leaf: the flat stream is empty padding,
        every param rides the replicated small path — the step must
        trace (no empty-concatenate) and match the baseline exactly
        (f32 wire, same optimizer)."""
        _, s_base, _, m_base = _run_trainer(None, steps=4)
        _, s_sh, _, m_sh = _run_trainer(CollectiveConfig(
            sharded_update=True, min_size=1 << 20), steps=4)
        for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(
                            s_base.params)),
                        jax.tree_util.tree_leaves(jax.device_get(
                            s_sh.params))):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
        assert abs(m_base["loss"] - m_sh["loss"]) < 1e-5

    def test_zero1_and_collective_are_mutually_exclusive(self):
        mesh = data_parallel_mesh(2)
        with pytest.raises(ValueError, match="mutually exclusive"):
            DLTrainer(_MLP(), OptimizerConfig(), mesh, zero1=True,
                      collective=CollectiveConfig(sharded_update=True))

    def test_non_data_mesh_rejected(self):
        from synapseml_tpu.parallel.mesh import dp_tp_mesh
        mesh = dp_tp_mesh(2, jax.devices()[:4])
        with pytest.raises(ValueError, match="pure data meshes"):
            DLTrainer(_MLP(), OptimizerConfig(), mesh,
                      collective=CollectiveConfig(compression="int8"))


class TestDLParity:
    def test_int8_training_matches_f32_loss(self):
        """Tier-1 parity pin: compression='int8' (with error feedback)
        reaches the same training loss as the f32 sync within a fixed
        epsilon."""
        _, _, _, m_base = _run_trainer(None, steps=12)
        _, _, _, m_i8 = _run_trainer(
            CollectiveConfig(compression="int8", error_feedback=True,
                             min_size=64), steps=12)
        assert abs(m_base["loss"] - m_i8["loss"]) < 0.05, (m_base, m_i8)
        _, _, _, m_bf = _run_trainer(
            CollectiveConfig(compression="bf16", error_feedback=True,
                             min_size=64), steps=12)
        assert abs(m_base["loss"] - m_bf["loss"]) < 0.05


# ---------------------------------------------------------------------------
# GBDT: compressed histogram psum
# ---------------------------------------------------------------------------

def _gbdt_task(n=6000, f=10, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] * 2 - X[:, 1] + X[:, 2] * X[:, 3]
         + rng.normal(scale=0.5, size=n) > 0).astype(np.float64)
    return X, y


class TestGBDTParity:
    def test_int8_histogram_psum_matches_f32_holdout_auc(self):
        """Tier-1 parity pin: compression='int8' GBDT training over a
        4-way data-parallel mesh matches the f32 holdout AUC within a
        fixed epsilon."""
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        from synapseml_tpu.models.gbdt.metrics import auc
        X, y = _gbdt_task()
        mesh = data_parallel_mesh(4)
        aucs = {}
        for comp in ("none", "int8", "bf16"):
            cfg = BoostingConfig(objective="binary", num_iterations=10,
                                 num_leaves=15,
                                 collective_compression=comp)
            booster, _ = train(X, y, cfg, mesh=mesh)
            rng = np.random.default_rng(7)
            Xh = rng.normal(size=(4000, 10)).astype(np.float32)
            yh = (Xh[:, 0] * 2 - Xh[:, 1] + Xh[:, 2] * Xh[:, 3] > 0)
            aucs[comp] = float(auc(yh.astype(np.float64),
                                   booster.predict_margin(Xh)))
        assert abs(aucs["none"] - aucs["int8"]) < 0.01, aucs
        assert abs(aucs["none"] - aucs["bf16"]) < 0.01, aucs

    def test_estimator_param_threads_to_training(self):
        from synapseml_tpu.core.dataset import Dataset
        from synapseml_tpu.models.gbdt.estimators import GBDTClassifier
        X, y = _gbdt_task(n=4096)
        ds = Dataset({"features": list(X.astype(np.float64)), "label": y})
        reg = get_registry()

        def wire():
            m = reg.get("collective_wire_bytes_total")
            return (m.value(op="gbdt_hist_psum", axis=DATA_AXIS,
                            codec="int8", strategy="flat") if m else 0.0)

        before = wire()
        model = GBDTClassifier(numIterations=5, numLeaves=7, numShards=4,
                               collectiveCompression="int8").fit(ds)
        assert model.get_booster_num_trees() == 5
        assert wire() > before, "compressed histogram psum never traced"

    def test_bad_codec_fails_fast(self):
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        X, y = _gbdt_task(n=256)
        with pytest.raises(ValueError, match="fp4"):
            train(X, y, BoostingConfig(objective="binary", num_iterations=1,
                                       collective_compression="fp4"))


# ---------------------------------------------------------------------------
# checkpoint compatibility: kill→resume bit-exact with compression on
# ---------------------------------------------------------------------------

@pytest.mark.fault
class TestCheckpointCompat:
    def test_gbdt_int8_preempt_resume_bit_exact(self, fault_registry,
                                                monkeypatch, tmp_path):
        """The gang kill/resume pin's compression='int8' leg: an
        injected mid-train preempt + re-fit against the same
        CheckpointManager matches the uninterrupted int8 model
        bit-exactly (the codec is stateless and deterministic, so the
        resumed run replays the identical quantized reductions)."""
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        from synapseml_tpu.resilience.faults import PreemptionError
        X, y = _gbdt_task(n=2000, f=8)
        mesh = data_parallel_mesh(4)

        def cfg(n):
            return BoostingConfig(objective="binary", num_iterations=n,
                                  num_leaves=7, min_data_in_leaf=5, seed=11,
                                  collective_compression="int8")

        full, _ = train(X, y, cfg(6), mesh=mesh)
        monkeypatch.setenv("SML_FAULTS",
                           "gbdt.checkpoint=preempt:after=1:times=1")
        fault_registry.configure_from_env()
        mgr = CheckpointManager(str(tmp_path))
        with pytest.raises(PreemptionError):
            train(X, y, cfg(6), mesh=mesh, checkpoint_dir=mgr,
                  checkpoint_interval=2)
        fault_registry.clear()
        resumed, _ = train(X, y, cfg(6), mesh=mesh, checkpoint_dir=mgr,
                           checkpoint_interval=2)
        assert resumed.num_trees == 6
        np.testing.assert_array_equal(
            np.asarray(full.predict_margin(X)),
            np.asarray(resumed.predict_margin(X)))

    def test_dl_residuals_roundtrip_through_checkpoint_bit_exact(
            self, tmp_path):
        """Error-feedback residuals are live training state: saving
        (state, residuals) mid-run via CheckpointManager and restoring
        into a fresh trainer continues the EXACT trajectory of the
        uninterrupted compressed run.

        Runs in a SUBPROCESS: the first jitted step after device_put of
        a restored state can abort at the native level on some jax
        builds (the same pre-existing crash test_resilience's DL
        preempt-resume test isolates), and a SIGABRT must fail THIS
        test with output attached, not kill the pytest process."""
        import subprocess
        import sys
        script = (
            "import os\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '')\n"
            "    + ' --xla_force_host_platform_device_count=8').strip()\n"
            "import numpy as np, jax, jax.numpy as jnp\n"
            "import flax.linen as nn\n"
            "import synapseml_tpu\n"
            "from synapseml_tpu.core.checkpoint import CheckpointManager\n"
            "from synapseml_tpu.models.dl.training import (DLTrainer,\n"
            "    OptimizerConfig)\n"
            "from synapseml_tpu.parallel.compression import CollectiveConfig\n"
            "from synapseml_tpu.parallel.mesh import data_parallel_mesh\n"
            "class MLP(nn.Module):\n"
            "    @nn.compact\n"
            "    def __call__(self, x, deterministic=True):\n"
            "        x = nn.relu(nn.Dense(64)(x))\n"
            "        return nn.Dense(4)(x)\n"
            "mesh = data_parallel_mesh(4)\n"
            "rng = np.random.default_rng(0)\n"
            "X = rng.normal(size=(64, 16)).astype(np.float32)\n"
            "y = rng.integers(0, 4, 64).astype(np.int32)\n"
            "opt = OptimizerConfig(name='adamw', learning_rate=1e-2,\n"
            "                      schedule='constant')\n"
            "cfg = CollectiveConfig(compression='int8',\n"
            "                       error_feedback=True, min_size=64)\n"
            "key = jax.random.PRNGKey(0)\n"
            "def make():\n"
            "    tr = DLTrainer(MLP(), opt, mesh, collective=cfg)\n"
            "    state = tr.init_state(0, X[:8])\n"
            "    return tr, state, tr.train_step()\n"
            "tr, state, step = make()\n"
            "bi, bl = tr.shard_batch((X, y))\n"
            "for _ in range(10):\n"
            "    state, _ = step(state, (bi,), bl, key)\n"
            "full = jax.device_get(state.params)\n"
            "tr2, s2, step2 = make()\n"
            "for _ in range(5):\n"
            "    s2, _ = step2(s2, (bi,), bl, key)\n"
            "assert step2.residuals is not None\n"
            f"mgr = CheckpointManager({str(tmp_path)!r})\n"
            "mgr.save(5, jax.device_get((s2, step2.residuals)))\n"
            "tr3, s3, step3 = make()\n"
            "restored, res = mgr.restore_state_dict((s3, step3.residuals))\n"
            "restored = jax.device_put(restored, tr3.state_shardings)\n"
            "res = jax.device_put(res, jax.tree_util.tree_map(\n"
            "    lambda _: tr3.residual_sharding(), res))\n"
            "step3.set_residuals(res)\n"
            "s3 = restored\n"
            "for _ in range(5):\n"
            "    s3, _ = step3(s3, (bi,), bl, key)\n"
            "for a, b in zip(jax.tree_util.tree_leaves(full),\n"
            "                jax.tree_util.tree_leaves(\n"
            "                    jax.device_get(s3.params))):\n"
            "    np.testing.assert_array_equal(a, b)\n"
            "print('RESUME_BIT_EXACT')\n")
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "RESUME_BIT_EXACT" in proc.stdout

    def test_codec_toggle_against_checkpoint_fails_loudly(self, tmp_path):
        """The checkpoint config guard writes the codec fields even when
        compression is OFF, so resuming a compression-off checkpoint
        with a codec (or vice versa) mismatches instead of slipping
        through the saved∩current key intersection."""
        import types

        from synapseml_tpu.models.dl.estimators import _CheckpointLoop

        mgr = CheckpointManager(str(tmp_path))

        def est():
            return types.SimpleNamespace(
                checkpointInterval=1,
                get_or_default=lambda k: {"batchSize": 8.0, "seed": 0.0,
                                          "validationFraction": 0.0,
                                          "precision": "bf16"}[k],
                get=lambda k: {"checkpointManager": mgr}.get(k))

        def trainer(collective):
            return types.SimpleNamespace(
                mesh=types.SimpleNamespace(shape={"data": 2}),
                collective=collective, state_shardings=None)

        loop = _CheckpointLoop(est(), trainer(None),
                               {"w": np.zeros(2, np.float32)})
        loop.after_step(1, {"w": np.zeros(2, np.float32)})
        with pytest.raises(ValueError, match="different data-order"):
            _CheckpointLoop(est(), trainer(CollectiveConfig(
                compression="bf16")), {"w": np.zeros(2, np.float32)})

    def test_pre_codec_checkpoint_refuses_compression_on(self, tmp_path):
        """A checkpoint written BEFORE the compression keys existed never
        recorded them; their absence means the pjit step at
        compression-off wrote it, so enabling any codec/manual knob
        against it mismatches (missing keys compare as 0.0) instead of
        slipping the saved∩current key intersection — while a
        compression-off resume still restores."""
        import types

        from synapseml_tpu.models.dl.estimators import _CheckpointLoop

        import collections
        S = collections.namedtuple("S", ["step", "w"])
        state = S(step=np.asarray(5), w=np.zeros(2, np.float32))
        mgr = CheckpointManager(str(tmp_path))
        # simulate the pre-codec writer: data-order keys only
        mgr.save(1, state,
                 metrics={"batchSize": 8.0, "seed": 0.0,
                          "validationFraction": 0.0, "shards": 2.0})

        def est():
            return types.SimpleNamespace(
                checkpointInterval=1,
                get_or_default=lambda k: {"batchSize": 8.0, "seed": 0.0,
                                          "validationFraction": 0.0,
                                          "precision": "bf16"}[k],
                get=lambda k: {"checkpointManager": mgr}.get(k))

        def trainer(collective):
            return types.SimpleNamespace(
                mesh=types.SimpleNamespace(shape={"data": 2}),
                collective=collective, state_shardings=None)

        with pytest.raises(ValueError, match="different data-order"):
            _CheckpointLoop(est(), trainer(CollectiveConfig(
                compression="int8", error_feedback=True)), state)
        loop = _CheckpointLoop(est(), trainer(None), state)
        assert loop.start_step == 5

    def test_gbdt_codec_toggle_against_checkpoint_fails_loudly(
            self, tmp_path):
        """The GBDT resume counterpart of the DL guard: re-fitting
        against a checkpoint dir trained under a different
        collective_compression raises instead of growing the remaining
        trees on a different histogram wire."""
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        X, y = _gbdt_task(n=500, f=6)
        mesh = data_parallel_mesh(4)

        def cfg(n, comp):
            return BoostingConfig(objective="binary", num_iterations=n,
                                  num_leaves=7, min_data_in_leaf=5, seed=3,
                                  collective_compression=comp)

        train(X, y, cfg(2, "int8"), mesh=mesh,
              checkpoint_dir=str(tmp_path), checkpoint_interval=1)
        with pytest.raises(ValueError, match="collective_compression"):
            train(X, y, cfg(4, "none"), mesh=mesh,
                  checkpoint_dir=str(tmp_path), checkpoint_interval=1)
        # same codec resumes fine (and idempotent re-fit still returns)
        booster, _ = train(X, y, cfg(4, "int8"), mesh=mesh,
                           checkpoint_dir=str(tmp_path),
                           checkpoint_interval=1)
        assert booster.num_trees == 4
        # DL-only fields (error_feedback/sharded_update/manual) are
        # documented-ignored by the histogram psum: the 'int8' shorthand
        # (EF on) and an explicit EF-off config are the SAME wire, so
        # this is a legitimate resume, not a toggle
        again, _ = train(X, y, cfg(4, CollectiveConfig(compression="int8")),
                         mesh=mesh, checkpoint_dir=str(tmp_path),
                         checkpoint_interval=1)
        assert again.num_trees == 4
        # a topology change flips the EFFECTIVE wire even under an
        # unchanged config: resuming the gang-compressed checkpoint
        # single-device would grow the remaining trees f32 (the codec
        # nulls without a mesh) while the carried ones grew quantized
        with pytest.raises(ValueError, match="collective_compression"):
            train(X, y, cfg(5, "int8"), checkpoint_dir=str(tmp_path),
                  checkpoint_interval=1)

    def test_gbdt_single_device_declared_codec_resumes_own_checkpoint(
            self, tmp_path):
        """A single-device fit with a declared (documented-ignored)
        codec trains on the f32 wire; its checkpoints record that
        EFFECTIVE wire, so the identical call resumes freely instead of
        mismatching its own checkpoint."""
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        X, y = _gbdt_task(n=400, f=5)

        def cfg(n):
            return BoostingConfig(objective="binary", num_iterations=n,
                                  num_leaves=7, min_data_in_leaf=5, seed=3,
                                  collective_compression="int8")

        train(X, y, cfg(2), checkpoint_dir=str(tmp_path),
              checkpoint_interval=1)
        booster, _ = train(X, y, cfg(4), checkpoint_dir=str(tmp_path),
                           checkpoint_interval=1)
        assert booster.num_trees == 4
        # and the f32-everywhere wire also matches a 'none' resume
        more, _ = train(X, y, BoostingConfig(
            objective="binary", num_iterations=5, num_leaves=7,
            min_data_in_leaf=5, seed=3), checkpoint_dir=str(tmp_path),
            checkpoint_interval=1)
        assert more.num_trees == 5

    def test_resume_without_residuals_fails_loudly(self, tmp_path):
        """A compression-off checkpoint cannot silently resume a
        compression-on run: the residual leaves change the pytree leaf
        count and restore refuses."""
        mesh = data_parallel_mesh(2)
        X, _ = _mlp_data()
        opt = OptimizerConfig(name="adamw", schedule="constant")
        tr = DLTrainer(_MLP(), opt, mesh)
        state = tr.init_state(0, X[:8])
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, jax.device_get(state))

        tr2 = DLTrainer(_MLP(), opt, mesh, collective=CollectiveConfig(
            compression="int8", error_feedback=True, min_size=64))
        s2 = tr2.init_state(0, X[:8])
        step2 = tr2.train_step()
        with pytest.raises(ValueError, match="leaves"):
            mgr.restore_state_dict((s2, step2.residuals))
