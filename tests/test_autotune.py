"""Self-tuning performance plane (telemetry/autotune.py +
telemetry/tunetable.py, ISSUE 20).

Pins the full contract: the ``StepProfiler.measure`` timing protocol's
statistics under a deterministic injectable clock (paired
median-of-deltas / multi min-of-blocks, self-timing legs, leg-order
alternation), tuning-table round-trip + the honesty rule (fabricated
measurements refuse to enter; absent/mismatched/stale/invalid tables
change NOTHING), SIGKILL-atomic table writes, the autotuner harness
(warm-then-measure, error candidates dropped, empty spaces claim
nothing, every registered space's entry point resolves against the
warmup lattice — the source-scan lint), every construction-site
consult (SlotEngine paged tile + bucket grid, GBDT ``growth_params``
hist chunk incl. the program-key fork, int8 codec chunk), the fitted
collective cost model (α-β recovery, crossover formula vs the priced
routes, refusal of degenerate fits) and its planner integration
(spec-model decisions byte-identical to the hardcoded cutoff, fitted
models re-routing + the ``model=`` provenance label), ``GET /tunez``
(schema, ``?space=`` filter, hostile-label round-trip, served while
draining), cross-process table reuse via ``SMLTPU_TUNE_TABLE_DIR``,
and the bench's re-pointed timing legs.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from synapseml_tpu.telemetry import get_registry
from synapseml_tpu.telemetry.artifact import SchemaError, read_json
from synapseml_tpu.telemetry.autotune import (
    AUTOTUNE_METRICS, COST_MODEL_GEOMETRY, COST_MODEL_SPACE, Autotuner,
    CollectiveCostModel, TuneSpace, fit_alpha_beta, registered_spaces,
    resolve_entry_point)
from synapseml_tpu.telemetry.gangplane import StepProfiler
from synapseml_tpu.telemetry.tunetable import (
    CONSULT_OUTCOMES, TUNE_TABLE_ENV, TUNE_TABLE_SCHEMA_VERSION, TunePlane,
    check_tune_table, check_tunez, geometry_key, get_tuneplane,
    set_tuneplane, table_path)

pytestmark = pytest.mark.tune

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def plane(tmp_path):
    """A fresh table-backed plane pinned as the process default for the
    test and ALWAYS restored — a leaked pinned plane would silently
    re-tune every other suite's engines."""
    fresh = TunePlane(directory=str(tmp_path))
    prev = set_tuneplane(fresh)
    try:
        yield fresh
    finally:
        set_tuneplane(prev)


@pytest.fixture
def no_table():
    """The explicit table-less plane (directory=None): every consult is
    ``disabled`` and every construction site keeps its defaults."""
    fresh = TunePlane(directory=None)
    prev = set_tuneplane(fresh)
    try:
        yield fresh
    finally:
        set_tuneplane(prev)


@pytest.fixture(scope="module")
def tiny_model():
    from synapseml_tpu.models.llm import LlamaConfig, LlamaModel
    cfg = LlamaConfig.tiny(num_layers=2, max_len=64, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 8), jnp.int32))
    return cfg, model, variables


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode("utf-8")


# ---------------------------------------------------------------------------
# StepProfiler.measure — the extracted bench protocol (satellite a)
# ---------------------------------------------------------------------------

class TestMeasureProtocol:
    def test_paired_median_of_deltas_min_block(self):
        """Paired mode statistic, pinned through self-timing legs: per
        block, the MEDIAN of base times and of other-minus-base deltas;
        the reported pair is the block with the minimum delta."""
        base_vals = iter([1.0] * 6)
        other_vals = iter([1.5, 1.2, 1.9,    # block 1: deltas .5/.2/.9
                           1.1, 1.4, 1.3])   # block 2: deltas .1/.4/.3
        base, delta = StepProfiler.measure(
            (lambda: next(base_vals), lambda: next(other_vals)),
            blocks=2, pairs=3)
        assert base == pytest.approx(1.0)
        # median(block2 deltas) = 0.3 < median(block1 deltas) = 0.5
        assert delta == pytest.approx(0.3)

    def test_paired_leg_order_alternates_within_a_block(self):
        """Pair-to-pair leg-order alternation (the monotone host-drift
        cancellation) is load-bearing: pin the exact call sequence."""
        calls = []

        def base():
            calls.append("b")
            return 1.0

        def other():
            calls.append("o")
            return 2.0

        StepProfiler.measure((base, other), blocks=1, pairs=4)
        assert calls == ["b", "o", "o", "b", "b", "o", "o", "b"]

    def test_multi_min_of_blocks_and_order_reversal(self):
        """Multi mode: each leg once per block in an order that reverses
        block to block; the statistic is the per-leg MIN across blocks
        (contention only ever inflates a block)."""
        order = []

        def mk(name, vals):
            it = iter(vals)

            def leg():
                order.append(name)
                return next(it)
            return leg

        out = StepProfiler.measure(
            {"x": mk("x", [3.0, 1.0]), "y": mk("y", [2.0, 4.0])}, blocks=2)
        assert out == {"x": pytest.approx(1.0), "y": pytest.approx(2.0)}
        assert order == ["x", "y", "y", "x"]

    def test_wall_clock_through_injected_timer(self):
        """Legs that do not self-time are measured between ``timer()``
        calls — pinned with a scripted deterministic clock."""
        ticks = iter([0.0, 2.0, 2.0, 5.0])
        out = StepProfiler.measure(
            {"a": lambda: None, "b": lambda: None},
            blocks=1, timer=lambda: next(ticks))
        assert out == {"a": pytest.approx(2.0), "b": pytest.approx(3.0)}

    def test_bool_return_is_not_a_self_timed_measurement(self):
        """``True`` is an int — but NOT a measurement; a bool-returning
        leg falls back to the wall clock (the bool-is-int pitfall)."""
        ticks = iter([0.0, 7.0])
        out = StepProfiler.measure({"t": lambda: True},
                                   blocks=1, timer=lambda: next(ticks))
        assert out == {"t": pytest.approx(7.0)}

    def test_int_return_is_trusted_as_seconds(self):
        out = StepProfiler.measure({"s": lambda: 3}, blocks=1)
        assert out == {"s": pytest.approx(3.0)}

    def test_bad_legs_shape_raises(self):
        with pytest.raises(TypeError):
            StepProfiler.measure(42)
        with pytest.raises(TypeError):
            StepProfiler.measure((lambda: None,))


# ---------------------------------------------------------------------------
# the tuning table — round-trip, honesty, atomicity
# ---------------------------------------------------------------------------

class TestTunePlane:
    def test_record_consult_round_trip(self, plane, tmp_path):
        plane.record("sp", "g=1", {"tile": 8}, measured_ms=1.5, trials=3)
        won = plane.consult("site", "sp", "g=1")
        assert won == {"tile": 8}
        # the persisted file passes the schema and a FRESH plane loads it
        read_json(table_path(str(tmp_path)), schema=check_tune_table)
        plane2 = TunePlane(directory=str(tmp_path))
        assert plane2.consult("site", "sp", "g=1") == {"tile": 8}

    def test_honesty_gate_refuses_fabricated_measurements(self, plane):
        for bad_ms in (0.0, -1.0, float("nan"), float("inf")):
            with pytest.raises(SchemaError):
                plane.record("sp", "g", {"x": 1}, measured_ms=bad_ms,
                             trials=1)
        with pytest.raises(SchemaError):
            plane.record("sp", "g", {"x": 1}, measured_ms=1.0, trials=0)
        with pytest.raises(SchemaError):
            plane.record("sp", "g", {}, measured_ms=1.0, trials=1)
        with pytest.raises(ValueError):
            TunePlane(directory=None).record("sp", "g", {"x": 1},
                                             measured_ms=1.0, trials=1)

    def test_consult_outcome_ladder(self, plane):
        """Every outcome in the closed set, each keeping defaults
        (``None``) except ``loaded``."""
        # disabled: no directory at all
        off = TunePlane(directory=None)
        assert off.consult("s", "sp", "g") is None
        assert off.snapshot()["consults"][-1]["outcome"] == "disabled"
        # absent: nobody ever tuned this space
        assert plane.consult("s", "never_tuned", "g") is None
        assert plane.snapshot()["consults"][-1]["outcome"] == "absent"
        plane.record("sp", "g=1", {"x": 1}, measured_ms=1.0, trials=1)
        # mismatch: the space was tuned, but not on THIS geometry
        assert plane.consult("s", "sp", "g=2") is None
        assert plane.snapshot()["consults"][-1]["outcome"] == "mismatch"
        # invalid: the caller's own gate rejects the winner (a raising
        # validator counts as rejection, never as trust)
        assert plane.consult("s", "sp", "g=1",
                             validate=lambda w: False) is None
        assert plane.snapshot()["consults"][-1]["outcome"] == "invalid"
        assert plane.consult("s", "sp", "g=1",
                             validate=lambda w: 1 / 0) is None
        # loaded
        assert plane.consult("s", "sp", "g=1") == {"x": 1}
        assert plane.snapshot()["consults"][-1]["outcome"] == "loaded"
        outcomes = {c["outcome"] for c in plane.snapshot()["consults"]}
        assert outcomes <= set(CONSULT_OUTCOMES)

    def test_wrong_device_kind_is_a_mismatch(self, tmp_path):
        """An entry measured on another chip matches NOTHING here — a
        v5p winner can never resize this process's kernels."""
        other = TunePlane(directory=str(tmp_path), kind="tpu_v5")
        other.record("sp", "g=1", {"x": 9}, measured_ms=1.0, trials=1)
        mine = TunePlane(directory=str(tmp_path), kind="cpu")
        assert mine.consult("s", "sp", "g=1") is None
        assert mine.snapshot()["consults"][-1]["outcome"] == "mismatch"

    def test_stale_entries_keep_defaults(self, tmp_path):
        p = TunePlane(directory=str(tmp_path), kind="cpu")
        p.record("sp", "g=1", {"x": 1}, measured_ms=1.0, trials=1)
        aged = TunePlane(directory=str(tmp_path), kind="cpu",
                         max_age_s=1e-9)
        time.sleep(0.01)
        assert aged.consult("s", "sp", "g=1") is None
        snap = aged.snapshot()
        assert snap["consults"][-1]["outcome"] == "stale"
        assert snap["entries"][0]["stale"] is True

    def test_schema_version_mismatch_refuses_wholesale(self, tmp_path):
        """A table written under another schema version loads NOTHING —
        defaults everywhere, never a partial reinterpretation."""
        with open(table_path(str(tmp_path)), "w", encoding="utf-8") as f:
            json.dump({"schema_version": TUNE_TABLE_SCHEMA_VERSION + 1,
                       "entries": [], "written_unix": 0.0}, f)
        p = TunePlane(directory=str(tmp_path), kind="cpu")
        assert p.consult("s", "sp", "g") is None
        snap = p.snapshot()
        assert snap["load_error"] is not None
        assert snap["consults"][-1]["outcome"] == "mismatch"

    def test_sigkill_mid_record_never_tears_the_table(self, tmp_path):
        """The crash-consistency pin: a writer SIGKILLed mid-record
        leaves either the previous table or the new one — the survivor
        file always passes the full schema (write_json's tmpfile +
        fsync + rename discipline)."""
        code = (
            "import sys\n"
            "from synapseml_tpu.telemetry.tunetable import TunePlane\n"
            "plane = TunePlane(directory=sys.argv[1], kind='cpu')\n"
            "print('ready', flush=True)\n"
            "i = 0\n"
            "while True:\n"
            "    plane.record('kill_space', f'g={i % 7}', {'x': i},\n"
            "                 1.0 + i, 1)\n"
            "    i += 1\n")
        proc = subprocess.Popen(
            [sys.executable, "-c", code, str(tmp_path)],
            stdout=subprocess.PIPE, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        try:
            assert proc.stdout.readline().strip() == b"ready"
            time.sleep(0.3)
        finally:
            proc.kill()   # SIGKILL — no atexit, no flush
            proc.wait()
        obj = read_json(table_path(str(tmp_path)), schema=check_tune_table)
        assert obj["entries"], "the writer recorded before the kill"

    def test_cross_process_reuse_via_env(self, plane, tmp_path):
        """The fleet contract: one process tunes, a DIFFERENT process
        (the supervisor's worker env) consults the same table through
        ``SMLTPU_TUNE_TABLE_DIR`` and loads the winner."""
        plane.record("xproc_space", "g=1", {"chunk": 512},
                     measured_ms=2.0, trials=2)
        code = (
            "import json\n"
            "from synapseml_tpu.telemetry.tunetable import get_tuneplane\n"
            "p = get_tuneplane()\n"
            "w = p.consult('child', 'xproc_space', 'g=1')\n"
            "print(json.dumps({'dir': p.directory, 'winner': w}))\n")
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 TUNE_TABLE_ENV: str(tmp_path)},
            check=True, timeout=120)
        got = json.loads(out.stdout)
        assert got["dir"] == str(tmp_path)
        assert got["winner"] == {"chunk": 512}

    def test_supervisor_threads_table_dir_to_workers(self, tmp_path):
        from synapseml_tpu.parallel.supervisor import GangSupervisor
        sup = GangSupervisor("mp_tasks:noop", n_processes=1,
                             tune_table_dir=str(tmp_path))
        assert sup.env_extra[TUNE_TABLE_ENV] == str(tmp_path)

    def test_get_tuneplane_follows_env_unless_pinned(self, monkeypatch,
                                                     tmp_path):
        prev = set_tuneplane(None)
        try:
            monkeypatch.delenv(TUNE_TABLE_ENV, raising=False)
            assert get_tuneplane().directory is None
            monkeypatch.setenv(TUNE_TABLE_ENV, str(tmp_path))
            assert get_tuneplane().directory == str(tmp_path)
            pinned = TunePlane(directory=None)
            set_tuneplane(pinned)
            assert get_tuneplane() is pinned   # env no longer consulted
        finally:
            set_tuneplane(prev)


# ---------------------------------------------------------------------------
# the autotuner harness
# ---------------------------------------------------------------------------

def _synthetic_space(trials, name="synthetic_test_space"):
    # a REAL registered entry point (the lint below holds every space to
    # this); the trials themselves are injected self-timing runners
    return TuneSpace(
        name=name,
        entry_point="synapseml_tpu.parallel.compression:int8_roundtrip_jit",
        build=lambda: ("g=test", trials))


class TestAutotunerHarness:
    def test_winner_is_the_measured_minimum_and_persists(self, plane):
        space = _synthetic_space([({"x": 1}, lambda: 0.005),
                                  ({"x": 2}, lambda: 0.002)])
        res = Autotuner(plane=plane).run(space)
        assert res["winner"] == {"x": 2}
        assert res["measured_ms"] == pytest.approx(2.0)
        assert res["trial_count"] == 2
        assert set(res["trials_ms"]) == {"x=1", "x=2"}
        assert isinstance(res["roofline"], dict) and res["roofline"]
        # the winner landed in the table, consultable by any site
        assert plane.consult("s", space.name, "g=test") == {"x": 2}

    def test_error_candidates_are_dropped_not_timed(self, plane):
        def boom():
            raise RuntimeError("candidate cannot run here")
        c = get_registry().get("autotune_trials_total")
        before = c.value(space="synthetic_err", outcome="error")
        space = _synthetic_space([({"x": 1}, boom),
                                  ({"x": 2}, lambda: 0.002)],
                                 name="synthetic_err")
        res = Autotuner(plane=plane).run(space)
        assert res["winner"] == {"x": 2}
        assert res["trial_count"] == 1
        assert c.value(space="synthetic_err",
                       outcome="error") == before + 1

    def test_empty_space_claims_nothing(self, plane):
        c = get_registry().get("autotune_trials_total")
        before = c.value(space="synthetic_empty", outcome="empty")
        res = Autotuner(plane=plane).run(
            _synthetic_space([], name="synthetic_empty"))
        assert res is None
        assert c.value(space="synthetic_empty",
                       outcome="empty") == before + 1
        assert plane.consult("s", "synthetic_empty", "g=test") is None

    def test_persist_false_leaves_the_table_alone(self, plane):
        space = _synthetic_space([({"x": 1}, lambda: 0.001)],
                                 name="synthetic_nopersist")
        assert Autotuner(plane=plane).run(space, persist=False) is not None
        assert plane.consult("s", "synthetic_nopersist", "g=test") is None

    def test_every_registered_space_entry_point_resolves(self):
        """The source-scan lint (satellite f): a search space can never
        time a program the compile plane cannot warm."""
        spaces = registered_spaces()
        assert {"paged_attn_tile", "gbdt_hist_chunk", "llm_bucket_grid",
                "int8_chunk"} <= set(spaces)
        for space in spaces.values():
            fn = resolve_entry_point(space.entry_point)
            assert hasattr(fn, "lower") and hasattr(fn, "_cache_size")

    def test_unregistered_entry_points_refuse(self):
        with pytest.raises(ValueError):
            resolve_entry_point("synapseml_tpu.parallel.compression:nope")
        with pytest.raises(ValueError):
            resolve_entry_point("not_a_spec")

    def test_real_int8_space_end_to_end(self, plane):
        """One REAL space measured end to end on this backend: the int8
        round-trip at a tiny payload — real wall clock, a real winner,
        a schema-valid persisted entry."""
        space = registered_spaces()["int8_chunk"]
        res = Autotuner(plane=plane).run(space, numel=4096,
                                         candidates=(64, 128))
        assert res["trial_count"] == 2
        assert res["winner"]["chunk"] in (64, 128)
        assert res["measured_ms"] > 0
        entry = plane.snapshot()["entries"][0]
        assert entry["space"] == "int8_chunk"
        assert entry["geometry"] == geometry_key(numel=4096)
        assert entry["measured_ms"] > 0 and entry["trials"] == 2


# ---------------------------------------------------------------------------
# construction-site consults — tuned dispatch vs byte-identical defaults
# ---------------------------------------------------------------------------

class TestSlotEngineConsults:
    def _engine(self, tiny_model, **kw):
        from synapseml_tpu.models.llm import SlotEngine
        cfg, model, variables = tiny_model
        return SlotEngine(model, variables, n_slots=2, max_len=64,
                          attention_backend="interpret", **kw)

    def test_no_table_keeps_default_geometry(self, no_table, tiny_model):
        from synapseml_tpu.models.llm.pallas_attn import paged_geometry
        cfg = tiny_model[0]
        eng = self._engine(tiny_model)
        default = paged_geometry(64, cfg.num_heads, cfg.num_kv_heads,
                                 cfg.d_head, cfg.dtype, max_query_span=1)
        assert eng._paged_geo == default
        assert eng._buckets[0] == 8

    def test_paged_tile_winner_changes_dispatch_geometry(self, plane,
                                                         tiny_model):
        """A loaded ``paged_attn_tile`` winner provably re-tiles the
        decode kernel: the tile is a jit static, so the geometry IS the
        program key."""
        from synapseml_tpu.models.llm.pallas_attn import paged_geometry_key
        cfg = tiny_model[0]
        geom = paged_geometry_key(64, cfg.num_kv_heads, cfg.d_head,
                                  cfg.dtype, 1)
        plane.record("paged_attn_tile", geom, {"tile": 16},
                     measured_ms=1.0, trials=2)
        eng = self._engine(tiny_model)
        assert eng._paged_geo.tile == 16        # default here is 32

    def test_gate_rejected_tile_keeps_defaults(self, plane, tiny_model):
        """A winner the VMEM/divisibility gate refuses (tile 64 never
        fits this max_len) is ``invalid`` — dispatch stays identical to
        a table-less process."""
        from synapseml_tpu.models.llm.pallas_attn import paged_geometry_key
        cfg = tiny_model[0]
        geom = paged_geometry_key(64, cfg.num_kv_heads, cfg.d_head,
                                  cfg.dtype, 1)
        plane.record("paged_attn_tile", geom, {"tile": 64},
                     measured_ms=1.0, trials=2)
        eng = self._engine(tiny_model)
        assert eng._paged_geo.tile == 32
        consults = [c for c in plane.snapshot()["consults"]
                    if c["space"] == "paged_attn_tile"]
        assert consults[-1]["outcome"] == "invalid"

    def test_min_bucket_winner_retunes_the_grid(self, plane, tiny_model):
        plane.record("llm_bucket_grid", geometry_key(max_len=64),
                     {"min_bucket": 16}, measured_ms=1.0, trials=3)
        eng = self._engine(tiny_model)
        assert eng._buckets == (16, 32, 64)
        # an EXPLICIT min_bucket wins outright — the table only fills
        # the None sentinel
        eng2 = self._engine(tiny_model, min_bucket=4)
        assert eng2._buckets[0] == 4


class TestGBDTConsult:
    def test_growth_params_consults_the_table(self, plane):
        from synapseml_tpu.models.gbdt.booster import BoostingConfig
        plane.record("gbdt_hist_chunk",
                     geometry_key(features=16, total_bins=256),
                     {"chunk": 1024}, measured_ms=50.0, trials=3)
        gp = BoostingConfig().growth_params(num_features=16)
        assert gp.hist_chunk == 1024

    def test_no_table_means_hist_chunk_zero(self, no_table):
        from synapseml_tpu.models.gbdt.booster import BoostingConfig
        assert BoostingConfig().growth_params(num_features=16).hist_chunk == 0
        # geometry the table was never tuned on also keeps the default
        assert BoostingConfig().growth_params().hist_chunk == 0

    def test_gate_rejected_chunk_keeps_default(self, plane):
        from synapseml_tpu.models.gbdt.booster import BoostingConfig
        # 512 is below the fused kernel's 1024 floor: hist_chunk_ok says
        # no, the consult is `invalid`, dispatch keeps chunk 0
        plane.record("gbdt_hist_chunk",
                     geometry_key(features=16, total_bins=256),
                     {"chunk": 512}, measured_ms=50.0, trials=3)
        assert BoostingConfig().growth_params(num_features=16).hist_chunk == 0

    @pytest.mark.slow
    def test_hist_chunk_forks_the_program_key_same_histogram(self):
        """The tuned chunk is a jit static: same histogram bytes, a new
        compiled program — the 'winner provably dispatched' pin at the
        kernel level."""
        from synapseml_tpu.models.gbdt import pallas_hist as ph
        N, F, B, S = ph.PAD_MULTIPLE, 4, 64, 2
        rng = np.random.default_rng(0)
        bins_t = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int32)
        slot = jnp.asarray(rng.integers(0, S, (N,)), jnp.int32)
        vals, scales = ph.prep_hist_vals(
            jnp.asarray(rng.standard_normal(N), jnp.float32),
            jnp.asarray(rng.uniform(0.5, 1.5, N), jnp.float32),
            jnp.ones((N,), jnp.float32))
        kw = dict(interpret=True)
        h0 = ph.build_hist_nodes_pallas(bins_t, slot, vals, scales, S, B,
                                        hist_chunk=0, **kw)
        c0 = ph.build_hist_nodes_pallas._cache_size()
        h1 = ph.build_hist_nodes_pallas(bins_t, slot, vals, scales, S, B,
                                        hist_chunk=1024, **kw)
        assert ph.build_hist_nodes_pallas._cache_size() > c0
        np.testing.assert_allclose(np.asarray(h0), np.asarray(h1),
                                   rtol=1e-5, atol=1e-5)


class TestInt8Consult:
    def test_codec_shorthand_loads_the_tuned_chunk(self, plane):
        from synapseml_tpu.parallel import (CollectiveConfig,
                                            resolve_collective_config)
        plane.record("int8_chunk", geometry_key(numel=1 << 18),
                     {"chunk": 512}, measured_ms=0.5, trials=4)
        assert resolve_collective_config("int8").chunk == 512
        # an EXPLICIT config is the caller's decision — untouched
        explicit = CollectiveConfig(compression="int8",
                                    error_feedback=True, chunk=64)
        assert resolve_collective_config(explicit).chunk == 64

    def test_no_table_is_byte_identical_to_head(self, no_table):
        from synapseml_tpu.parallel import (CollectiveConfig,
                                            resolve_collective_config)
        assert resolve_collective_config("int8") == CollectiveConfig(
            compression="int8", error_feedback=True)


# ---------------------------------------------------------------------------
# the fitted collective cost model
# ---------------------------------------------------------------------------

class TestCostModel:
    def test_alpha_beta_recovery_from_linear_timings(self):
        alpha, beta = 2e-4, 3e-9
        samples = [(n, alpha + beta * n) for n in (1e5, 1e6, 1e7)]
        a, b = fit_alpha_beta(samples)
        assert a == pytest.approx(alpha, rel=1e-9)
        assert b == pytest.approx(beta, rel=1e-9)

    def test_fit_refusals(self):
        with pytest.raises(ValueError):
            fit_alpha_beta([(1e6, 1.0)])                      # one size
        with pytest.raises(ValueError):
            fit_alpha_beta([(1e6, 1.0), (1e6, 2.0)])          # same size
        with pytest.raises(ValueError):
            fit_alpha_beta([(1e6, float("nan")), (2e6, 1.0)])
        # a fit with a flat/negative slope cannot price bandwidth
        with pytest.raises(ValueError):
            CollectiveCostModel.fitted([(1e5, 2.0), (1e6, 1.0)])
        with pytest.raises(ValueError):
            CollectiveCostModel(alpha_s=1e-4, beta_s_per_byte=0.0,
                                source="fitted")
        with pytest.raises(ValueError):
            CollectiveCostModel(source="measured")

    def test_crossover_matches_the_priced_routes(self):
        """``tree_cutoff_bytes`` IS the payload where the tree's
        ``L·(α+βn)`` equals the ring's ``2(w−1)·(α+βn/w)`` — verify the
        closed form against the two cost expressions it compares."""
        import math
        m = CollectiveCostModel(alpha_s=2e-4, beta_s_per_byte=3e-9,
                                source="fitted")
        for w in (4, 8, 16):
            n = m.tree_cutoff_bytes(w)
            L, hops = math.ceil(math.log2(w)), 2 * (w - 1)

            def tree(x):
                return L * (m.alpha_s + m.beta_s_per_byte * x)

            def ring(x):
                return hops * (m.alpha_s + m.beta_s_per_byte * x / w)

            assert tree(n) == pytest.approx(ring(n), rel=1e-6)
            assert tree(n // 2) < ring(n // 2)     # below: tree wins
            assert tree(n * 2) > ring(n * 2)       # above: ring wins

    def test_w2_crossover_is_unbounded(self):
        m = CollectiveCostModel(alpha_s=1e-4, beta_s_per_byte=1e-9,
                                source="fitted")
        assert m.tree_cutoff_bytes(2) == CollectiveCostModel.UNBOUNDED

    def test_spec_model_returns_its_constant(self):
        m = CollectiveCostModel.spec(12345)
        assert m.tree_cutoff_bytes(8) == 12345
        assert m.predict_s(1 << 20) is None
        with pytest.raises(ValueError):
            CollectiveCostModel(source="spec").tree_cutoff_bytes(8)
        f = CollectiveCostModel(alpha_s=1e-4, beta_s_per_byte=1e-9,
                                source="fitted")
        assert f.predict_s(1000) == pytest.approx(1e-4 + 1e-6)
        assert set(f.describe()) == {"source", "alpha_us",
                                     "beta_us_per_mib",
                                     "spec_cutoff_bytes"}


# ---------------------------------------------------------------------------
# planner integration — spec identity + fitted provenance
# ---------------------------------------------------------------------------

class TestPlannerIntegration:
    def _cfg(self, **kw):
        from synapseml_tpu.parallel import CollectiveConfig
        return CollectiveConfig(compression="int8", strategy="auto",
                                error_feedback=True, **kw)

    def test_spec_model_is_byte_identical_to_no_model(self):
        """The honesty anchor: planning with the spec cost model (what a
        table-less process resolves) decides EXACTLY what the pre-model
        hardcoded cutoff decided, over the whole decision surface."""
        from synapseml_tpu.parallel import TopologySpec
        from synapseml_tpu.parallel.planner import (TREE_CUTOFF_BYTES,
                                                    _decide)
        spec_model = CollectiveCostModel.spec(TREE_CUTOFF_BYTES)
        cfg = self._cfg()
        specs = (TopologySpec(n_hosts=2, devices_per_host=4),
                 TopologySpec(n_hosts=1, devices_per_host=8), None)
        for spec in specs:
            for world in (1, 2, 4, 8):
                for n in (1, 1024, TREE_CUTOFF_BYTES,
                          TREE_CUTOFF_BYTES + 1, 10 << 20):
                    assert (_decide(n, world, spec, cfg) ==
                            _decide(n, world, spec, cfg,
                                    cost_model=spec_model))

    def test_model_label_semantics(self):
        """``fallback`` = no cost model consulted (forced strategies,
        single rank, unknown topology); ``spec``/``fitted`` = that
        model priced the auto decision."""
        from synapseml_tpu.parallel import CollectiveConfig, TopologySpec
        from synapseml_tpu.parallel.planner import _decide
        spec = TopologySpec(n_hosts=2, devices_per_host=4)
        cfg = self._cfg()
        flat = CollectiveConfig(compression="int8", strategy="flat",
                                error_feedback=True)
        assert _decide(1 << 20, 8, spec, flat)[3] == "fallback"
        assert _decide(1 << 20, 1, spec, cfg)[3] == "fallback"
        assert _decide(1 << 20, 8, None, cfg)[3] == "fallback"
        forced = CollectiveConfig(compression="int8", strategy="ring",
                                  error_feedback=True)
        assert _decide(1 << 20, 8, spec, forced)[3] == "fallback"
        assert _decide(1024, 8, spec, cfg)[3] == "spec"
        fitted = CollectiveCostModel(alpha_s=0.0, beta_s_per_byte=1e-9,
                                     source="fitted")
        assert _decide(1024, 8, spec, cfg, cost_model=fitted)[3] == "fitted"

    def test_fitted_model_rereoutes_and_labels_plans(self):
        """An injected fitted model with a 0-byte crossover flips a
        small payload from the latency tree to the bandwidth routes —
        and the plan counter carries ``model='fitted'`` provenance."""
        from synapseml_tpu.parallel import CollectivePlanner, TopologySpec
        spec = TopologySpec(n_hosts=2, devices_per_host=4)
        cfg = self._cfg()
        c = get_registry().get("collective_plans_total")

        p_spec = CollectivePlanner(spec=spec)
        before = c.value(strategy="tree", reason="latency_bound",
                         model="spec")
        assert p_spec.plan(1024, 8, cfg).strategy == "tree"
        assert c.value(strategy="tree", reason="latency_bound",
                       model="spec") == before + 1

        p_fit = CollectivePlanner(spec=spec)
        p_fit.set_cost_model(CollectiveCostModel(
            alpha_s=0.0, beta_s_per_byte=1e-9, source="fitted"))
        before = c.value(strategy="hierarchical", reason="multi_host",
                         model="fitted")
        assert p_fit.plan(1024, 8, cfg).strategy == "hierarchical"
        assert c.value(strategy="hierarchical", reason="multi_host",
                       model="fitted") == before + 1

    def test_planner_resolves_fitted_model_from_the_table(self, plane):
        """The full loop: a recorded α-β fit (the bench's cost-model
        sweep) is what a FRESH planner resolves and prices with."""
        from synapseml_tpu.parallel import CollectivePlanner, TopologySpec
        plane.record(COST_MODEL_SPACE, COST_MODEL_GEOMETRY,
                     {"alpha_s": 2e-4, "beta_s_per_byte": 3e-9},
                     measured_ms=1.0, trials=4)
        p = CollectivePlanner(spec=TopologySpec(n_hosts=2,
                                                devices_per_host=4))
        m = p.cost_model()
        assert m.source == "fitted"
        assert m.alpha_s == pytest.approx(2e-4)
        assert m.beta_s_per_byte == pytest.approx(3e-9)

    def test_no_table_resolves_the_spec_model(self, no_table):
        from synapseml_tpu.parallel import CollectivePlanner, TopologySpec
        from synapseml_tpu.parallel.planner import TREE_CUTOFF_BYTES
        p = CollectivePlanner(spec=TopologySpec(n_hosts=2,
                                                devices_per_host=4))
        m = p.cost_model()
        assert m.source == "spec"
        assert m.tree_cutoff_bytes(8) == TREE_CUTOFF_BYTES


# ---------------------------------------------------------------------------
# GET /tunez
# ---------------------------------------------------------------------------

class TestTunezEndpoint:
    def test_tunez_is_reserved_and_schema_valid(self, plane):
        from synapseml_tpu.serving.server import (RESERVED_GET_PATHS,
                                                  ServingServer)
        assert "/tunez" in RESERVED_GET_PATHS
        plane.record("sp_a", "g=1", {"tile": 8}, measured_ms=1.0, trials=2)
        plane.record("sp_b", "g=2", {"chunk": 64}, measured_ms=2.0,
                     trials=3)
        plane.consult("site", "sp_a", "g=1")
        srv = ServingServer()
        try:
            host, port = srv.address
            status, body = _get(f"http://{host}:{port}/tunez")
            assert status == 200
            snap = json.loads(body)
            check_tunez(snap)
            assert {e["space"] for e in snap["entries"]} == {"sp_a", "sp_b"}
            assert any(c["outcome"] == "loaded" for c in snap["consults"])
            # ?space= filters both entries and consults
            status, body = _get(f"http://{host}:{port}/tunez?space=sp_a")
            filt = json.loads(body)
            assert {e["space"] for e in filt["entries"]} == {"sp_a"}
            assert all(c["space"] == "sp_a" for c in filt["consults"])
        finally:
            srv.close()

    def test_tunez_served_while_draining(self, plane):
        from synapseml_tpu.serving.server import ServingServer
        srv = ServingServer()
        try:
            srv.health.begin_drain()
            host, port = srv.address
            assert _get(f"http://{host}:{port}/tunez")[0] == 200
        finally:
            srv.close()

    def test_hostile_labels_round_trip(self, plane):
        """Geometry/site strings with quotes, angle brackets, and
        unicode survive the record → snapshot → JSON → check_tunez
        round trip (the /tracez hostile-label discipline)."""
        from synapseml_tpu.serving.server import ServingServer
        hostile = 'g="<script>&é中"'
        plane.record("sp_h", hostile, {"x": 1}, measured_ms=1.0, trials=1)
        plane.consult('site"<&>é', "sp_h", hostile)
        srv = ServingServer()
        try:
            host, port = srv.address
            status, body = _get(f"http://{host}:{port}/tunez")
            assert status == 200
            snap = json.loads(body)
            check_tunez(snap)
            assert any(e["geometry"] == hostile for e in snap["entries"])
            assert any(c["site"] == 'site"<&>é'
                       for c in snap["consults"])
        finally:
            srv.close()
