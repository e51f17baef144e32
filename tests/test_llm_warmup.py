"""Compile plane tests (ISSUE 15 acceptance criteria).

- **lattice completeness** — every module-level jitted entry point in
  ``slots.py``/``pallas_attn.py`` is registered with the warmup module
  and enumerated by the program lattice; a NEW jitted entry point fails
  the sweep until it is registered (and thereby either joins the
  lattice or gets an explicit exemption).
- **zero in-loop compiles** — a warmed engine serves a ragged trace
  (multiple prefill buckets, prefix reuse, speculative verifies) with
  the jit dispatch caches UNCHANGED and ``llm_compile_stalls_total``
  silent: the compile-counter pin.
- **token exactness** — warmup changes when programs compile, never
  what they compute: greedy through a warmed engine (plain and
  speculative) stays token-identical to the dense ``generate`` path.
- **readiness gating** — ``/readyz`` answers 503 ``"warming"`` (live
  plane snapshot in the payload) until the lattice is warm, and a
  request arriving DURING warmup is held in queue — exempt from SLO
  shedding — and served after, not shed (the satellite-1 pin).
- **router semantics** — a warming replica probes ``warming``:
  skipped by routing like ``draining``, with NO breaker signal (the
  satellite-2 pin), and re-enters rotation on the first post-warm
  probe.
- **persistent compilation cache** — a second process under the same
  ``JAX_COMPILATION_CACHE_DIR`` hits what the first stored (subprocess
  pair), and (slow) a relaunched gang, whose workers inherit the
  variable, reuses the cache across attempts.
"""

import json
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from synapseml_tpu.models.llm import (LlamaConfig, LlamaModel, SlotEngine,
                                      engine_jit_cache_size, generate,
                                      program_lattice)
from synapseml_tpu.models.llm import warmup as warmup_mod
from synapseml_tpu.parallel import compilecache as cc

pytestmark = pytest.mark.llmserve


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(num_layers=2, max_len=64, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 8), jnp.int32))
    return cfg, model, variables


def _prompts(cfg, n, length, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, (n, length)).astype(np.int32)


def _stall_count() -> float:
    from synapseml_tpu.telemetry import get_registry
    c = get_registry().get("llm_compile_stalls_total")
    if c is None:
        return 0.0
    return float(sum(c.series().values()))


class TestLatticeCompleteness:
    def test_every_jit_entry_point_is_registered(self):
        """The tier-1 sweep: a new ``jax.jit`` at module level in
        slots.py or pallas_attn.py fails here until it is added to
        ``REGISTERED_ENTRY_POINTS`` — the lattice can never silently
        fall behind the serving code."""
        from synapseml_tpu.models.llm import pallas_attn, slots
        for mod in (slots, pallas_attn):
            found = set(warmup_mod.jit_entry_points(mod))
            registered = warmup_mod.REGISTERED_ENTRY_POINTS[mod.__name__]
            assert found == set(registered), (
                f"{mod.__name__}: jitted entry points {sorted(found)} != "
                f"registered {sorted(registered)} — register new entry "
                "points with the warmup lattice (models/llm/warmup.py)")

    def test_lattice_enumerates_the_engine_config(self, tiny_model):
        """Lattice contents follow from static config alone: every
        prefill bucket, one decode step, one verify per span bucket S,
        and the prefix copy — with keys matching the engine's
        step-dispatch labels."""
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                         spec_draft_len=4)
        keys = {s.key for s in program_lattice(eng)}
        assert keys == {
            "decode_dense", "prefix_copy",
            "prefill_b8", "prefill_b16", "prefill_b32", "prefill_b64",
            "verify_dense_s2", "verify_dense_s4", "verify_dense_s8"}
        # every slots.py entry point is exercised by some lattice kind
        kinds = {s.kind for s in program_lattice(eng)}
        assert kinds == {"decode", "prefix_copy", "prefill", "verify"}

    def test_verify_lattice_warms_before_prefill_buckets(self,
                                                         tiny_model):
        """A speculative engine's first step after admission can
        dispatch ANY span bucket S of the verify, so the verify lattice is
        part of the admission base: it must be enumerated BEFORE the
        prefill buckets (which admission bumps to the front on demand)
        — otherwise a request admitted mid-warm stalls the whole loop
        on a cold verify compile."""
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                         spec_draft_len=4)
        kinds = [s.kind for s in program_lattice(eng)]
        assert max(i for i, k in enumerate(kinds) if k == "verify") \
            < min(i for i, k in enumerate(kinds) if k == "prefill")

    def test_paged_lattice_has_one_decode_program(self, tiny_model):
        """The paged kernel walks live tiles itself: one decode program
        and one verify program a span bucket S, whatever the spans."""
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                         attention_backend="interpret", spec_draft_len=2)
        keys = {s.key for s in program_lattice(eng)}
        assert eng._paged_geo is not None and eng._paged_geo.total_tiles > 1
        assert {k for k in keys if not k.startswith("prefill_")} == {
            "decode_interpret", "prefix_copy",
            "verify_interpret_s2", "verify_interpret_s4"}


    def test_the_prefill_kernel_adds_no_program_to_the_lattice(
            self, tiny_model, every_prefill_tiled, monkeypatch):
        """The lattice's program keys are the parent's: with every bucket's
        prefill through the kernel the keys are those of the plain path;
        and a query block's shapes do not follow the bucket, so two
        buckets' programs, every layer of both, trace the kernel once."""
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                         attention_backend="interpret")
        assert eng._prefill_plan(32)[0] == "tiled"
        assert {s.key for s in program_lattice(eng)} == {
            "decode_interpret", "prefix_copy",
            "prefill_b8", "prefill_b16", "prefill_b32", "prefill_b64"}
        from synapseml_tpu.models.llm import pallas_attn
        from synapseml_tpu.models.llm.slots import _prefill_slot_jit
        built = []
        make = pallas_attn._make_prefill_kernel
        monkeypatch.setattr(
            pallas_attn, "_make_prefill_kernel",
            lambda *a, **kw: built.append(a) or make(*a, **kw))
        pallas_attn.prefill_query_block.clear_cache()
        for pb in (32, 64):
            text = str(jax.make_jaxpr(
                lambda t: _prefill_slot_jit.__wrapped__(
                    model, variables, eng.cache, t, 20, 0, 0,
                    attention_backend="interpret"))(
                        jnp.zeros((pb,), jnp.int32)))
            assert text.count("name=prefill_attention") >= cfg.num_layers
        assert len(built) == 1


class TestZeroInLoopCompiles:
    def test_warmed_engine_serves_trace_with_zero_compiles(self,
                                                           tiny_model):
        """THE compile-counter pin: after a sync warmup, a ragged trace
        crossing several prefill buckets, taking the prefix-reuse copy
        path, and running speculative verifies adds NOTHING to the jit
        dispatch caches and raises no stall counter — the serving loop
        never pays an XLA compile."""
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=4, max_len=64,
                         spec_draft_len=4, min_prefix=8,
                         warmup="sync", name="warm-pin")
        plane = eng.compile_plane
        assert plane is not None and plane.status == "warm"
        size0 = engine_jit_cache_size()
        stalls0 = _stall_count()
        rng = np.random.default_rng(3)
        shared = rng.integers(1, cfg.vocab_size, 12).astype(np.int32)
        # ragged open-loop-ish trace: bucket-8/16/32 prefills, a
        # shared-prefix pair (the _copy_prefix_jit path), spec steps
        waves = [
            [(rng.integers(1, cfg.vocab_size, 7).astype(np.int32), 6),
             (np.concatenate([shared, shared[:4]]), 5)],
            [(np.concatenate([shared, shared[4:8]]), 5),
             (rng.integers(1, cfg.vocab_size, 20).astype(np.int32), 8)],
            [(rng.integers(1, cfg.vocab_size, 9).astype(np.int32), 12)],
        ]
        for wave in waves:
            for prompt, max_new in wave:
                assert eng.admit(prompt, max_new) is not None
            for _ in range(3):
                eng.step()
        eng.run_to_completion()
        assert engine_jit_cache_size() == size0, (
            "a warmed engine compiled in-loop: the warmup lattice "
            "missed a program the trace hit")
        assert _stall_count() == stalls0

    def test_cold_engine_with_plane_counts_stalls(self, tiny_model):
        """The inverse pin, via the steady-state accounting seam: a
        program the plane has not warmed that compiles inside the
        serving loop increments ``llm_compile_stalls_total`` (detected
        by the process compile tally, so an already-compiled program is
        correctly NOT a stall)."""
        cfg, model, variables = tiny_model
        # n_slots=3 is a cache geometry no other test in this process
        # uses, so every program this engine dispatches is a genuinely
        # fresh compile (the jit caches key on the cache shape)
        eng = SlotEngine(model, variables, n_slots=3, max_len=64,
                         warmup="off", name="stall-pin")
        from synapseml_tpu.models.llm.warmup import CompilePlane
        plane = CompilePlane(eng, name="stall-pin")
        eng.compile_plane = plane       # plane installed but never warmed
        stalls0 = _stall_count()
        compiles0 = cc.cache_stats()["compiles"]
        prompt = np.arange(1, 8, dtype=np.int32)
        eng.admit(prompt, 2)
        eng.run_to_completion()
        assert cc.cache_stats()["compiles"] > compiles0
        assert _stall_count() > stalls0


class TestTokenExactness:
    def test_warmed_plain_and_spec_engines_token_exact(self, tiny_model):
        """Warmup must not change a single output token: greedy through
        warmed engines (plain and speculative) == dense generate."""
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 3, 7, seed=5)
        ref = generate(model, variables, ids, max_new_tokens=10)
        for spec in (0, 4):
            eng = SlotEngine(model, variables, n_slots=4, max_len=64,
                             spec_draft_len=spec, warmup="sync",
                             name=f"exact-{spec}")
            slots = {i: eng.admit(ids[i], 10).slot for i in range(3)}
            outs = eng.run_to_completion()
            for i in range(3):
                assert np.array_equal(outs[slots[i]], ref[i]), (
                    f"warmed engine (spec_draft_len={spec}) diverged "
                    "from dense greedy")


class TestSyncWarmupMemory:
    def test_sync_warmup_never_holds_two_cache_trees(self, tiny_model,
                                                     monkeypatch):
        """Inline, the lattice threads the engine's own cache and the
        engine gets fresh zeros afterwards: the weights, TWO cache trees
        and a prefill's temporaries do not fit the chip at the
        benchmark's largest model.  A background warmup runs beside a
        serving engine and keeps its scratch tree."""
        cfg, model, variables = tiny_model
        real_init, real_warm = warmup_mod.init_cache, \
            warmup_mod.CompilePlane._warm_all
        planes, engine_had_none = [], []

        def warm_all(plane, **kw):
            planes.append(plane)
            return real_warm(plane, **kw)

        def init_cache(*a):
            # what the plane allocates, and what the engine held then
            engine_had_none.append(planes[-1].engine.cache is None)
            return real_init(*a)
        monkeypatch.setattr(warmup_mod.CompilePlane, "_warm_all", warm_all)
        monkeypatch.setattr(warmup_mod, "init_cache", init_cache)
        eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                         warmup="sync", name="own-cache")
        assert eng.compile_plane.status == "warm"
        assert engine_had_none == [True]
        assert all(not np.asarray(leaf).any()
                   for leaf in jax.tree.leaves(eng.cache))
        ids = _prompts(cfg, 1, 9, seed=2)
        slot = eng.admit(ids[0], 6).slot
        assert np.array_equal(
            eng.run_to_completion()[slot],
            generate(model, variables, ids, max_new_tokens=6)[0])


class TestReadinessGating:
    def test_readyz_gates_until_warm_and_requests_are_held(self,
                                                           tiny_model):
        """End-to-end: with ``warmup='background'`` the replica's
        ``/readyz`` answers 503 ``"warming"`` (plane snapshot in the
        payload) while the lattice compiles; a request that arrives in
        that window is HELD — not shed, despite waiting far past the
        TTFT SLO (the satellite-1 exemption) — and served once warm;
        ``/readyz`` then flips to 200 with ``"warmup"`` attached."""
        from synapseml_tpu.serving.llm import LLMServer
        cfg, model, variables = tiny_model
        gate = threading.Event()
        # the warm thread reads the hook at start; it is cleared only
        # in the outermost finally so the read can never race the clear
        warmup_mod._PRE_WARM_HOOK = gate.wait
        srv = None

        def readyz():
            try:
                with urllib.request.urlopen(
                        srv.server.url_for("/readyz"), timeout=5) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        try:
            srv = LLMServer(model, variables, n_slots=2, max_len=64,
                            warmup="background", ttft_slo_s=0.05)
            status, body = readyz()
            assert status == 503 and body["status"] == "warming"
            assert body["warmup"]["state"] == "warming"
            assert body["warmup"]["programs_total"] > 0

            result = {}

            def post():
                ids = _prompts(cfg, 1, 7, seed=9)[0]
                req = urllib.request.Request(
                    srv.url, method="POST",
                    data=json.dumps({"ids": [int(t) for t in ids],
                                     "max_new_tokens": 4}).encode())
                try:
                    with urllib.request.urlopen(req, timeout=60) as r:
                        result["status"] = r.status
                        result["body"] = json.loads(r.read())
                except urllib.error.HTTPError as e:
                    result["status"] = e.code

            t = threading.Thread(target=post, daemon=True)
            t.start()
            time.sleep(0.3)        # 6x the 50ms SLO, inside the warmup
            gate.set()
            assert srv.engine.compile_plane.wait_ready(180)
            t.join(60)
            assert result.get("status") == 200, (
                "request arriving during warmup was shed instead of "
                f"held: {result}")
            assert len(result["body"]["ids"]) == 4
            status, body = readyz()
            assert status == 200 and body["status"] == "ready"
            assert body["warmup"]["state"] == "warm"
            assert body["warmup"]["programs_warm"] \
                == body["warmup"]["programs_total"]
        finally:
            gate.set()
            warmup_mod._PRE_WARM_HOOK = None
            if srv is not None:
                srv.close()


class TestFailedWarmupUngates:
    def test_failed_or_unknown_plane_does_not_wedge_readyz(self):
        """A failed warmup (or a broken snapshot fn) must NOT keep the
        replica answering 503 forever: the engine serves with lazy
        compiles, so /readyz un-gates with the failure visible in the
        payload — only cold/warming states gate."""
        from synapseml_tpu.resilience.health import HealthState
        h = HealthState(name="failed-warm")
        state = {"state": "warming"}
        h.set_warmup(lambda: dict(state))
        assert h.readyz()[0] == 503
        for ungated in ("failed", "unknown", "warm"):
            state["state"] = ungated
            code, body, _ = h.readyz()
            assert code == 200, f"state={ungated!r} wedged readyz"
            assert json.loads(body)["warmup"]["state"] == ungated

        def broken():
            raise RuntimeError("probe exploded")
        h.set_warmup(broken)
        assert h.readyz()[0] == 200

    def test_failed_lattice_is_failed_not_warm(self, tiny_model,
                                               monkeypatch):
        """A program that does not compile leaves the plane ``failed``
        with the error in the snapshot — never ``warm`` — and un-gated
        (``is_warm``).  A background warm keeps serving; a synchronous
        one raises to the caller that was waiting on it, so
        ``SlotEngine(warmup='sync')`` cannot hand back an engine whose
        lattice did not compile."""
        cfg, model, variables = tiny_model

        def boom(cache):
            raise RuntimeError("mosaic said no")
        monkeypatch.setattr(
            warmup_mod, "program_lattice",
            lambda engine: [warmup_mod.ProgramSpec("decode_dense",
                                                   "decode", boom)])
        eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                         warmup="background", name="fail-bg")
        plane = eng.compile_plane
        assert plane.wait_ready(60)
        snap = plane.snapshot()
        assert plane.status == "failed" and plane.is_warm
        assert snap["state"] == "failed" and "mosaic said no" in snap["error"]
        assert snap["programs_warm"] == 0
        with pytest.raises(RuntimeError, match="mosaic said no"):
            SlotEngine(model, variables, n_slots=2, max_len=64,
                       warmup="sync", name="fail-sync")


class TestRouterWarmingState:
    def test_warming_replica_probes_warming_without_breaker_signal(self):
        """Satellite 2: a warming replica is draining-EQUIVALENT to the
        router — probe says ``warming``, routing skips it, no breaker
        trips — and the first post-warm probe returns it to rotation."""
        from synapseml_tpu.serving.distributed import (
            NoHealthyReplicaError, ReplicaRouter, probe_replica)
        from synapseml_tpu.serving.server import ServingServer
        srv = ServingServer(port=0)
        state = {"state": "warming", "programs_warm": 0,
                 "programs_total": 5}
        srv.health.set_warmup(lambda: dict(state))
        host, port = srv.address
        try:
            assert probe_replica(host, port) == "warming"
            router = ReplicaRouter([(host, port)],
                                   name=f"warm-router-{port}")
            router.probe_all()
            assert router.statuses() == {0: "warming"}
            assert router.breaker(0).state != "open"
            with pytest.raises(NoHealthyReplicaError) as ei:
                router.route()
            assert "warming" in str(ei.value)
            # lattice done: next probe readmits without breaker drama
            state["state"] = "warm"
            assert router.probe(0) == "healthy"
            assert router.route().rank == 0
        finally:
            srv.close()


#: cache every program, however small (the defaults skip sub-second
#: compiles — all of this toy's) — thresholds, like the directory, are
#: placed from outside through jax's own variables
_CACHE_ALL_ENV = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                  "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}


class TestPersistentCompileCache:
    def test_second_process_hits_the_cache(self, tmp_path):
        """The relaunch-shaped pin, cheap enough for tier-1: two fresh
        processes find the same ``JAX_COMPILATION_CACHE_DIR`` in their
        environment and compile the same program — the first misses
        (and stores), the second HITS (the cache-hit counter), i.e. a
        relaunched worker skips XLA."""
        child = (
            "import json\n"
            "import jax, jax.numpy as jnp\n"
            "from synapseml_tpu.parallel import compilecache as cc\n"
            "cc.install_compile_listeners()\n"
            "f = jax.jit(lambda x: (x @ x.T).sum())\n"
            "float(f(jnp.ones((64, 64))))\n"
            "print('STATS:' + json.dumps(\n"
            "    dict(cc.cache_stats(), dir=cc.compilation_cache_dir())))\n")

        def run():
            import os
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xc"),
                       **_CACHE_ALL_ENV)
            out = subprocess.run(
                [sys.executable, "-c", child],
                capture_output=True, text=True, timeout=120, env=env)
            assert out.returncode == 0, out.stderr[-2000:]
            line = [ln for ln in out.stdout.splitlines()
                    if ln.startswith("STATS:")][-1]
            return json.loads(line[len("STATS:"):])

        first = run()
        assert first["dir"] == str(tmp_path / "xc")
        assert first["cache_misses"] > 0 and first["cache_hits"] == 0
        second = run()
        assert second["cache_hits"] > 0, (
            f"second construction did not reuse the cache: {second}")

    @pytest.mark.slow
    @pytest.mark.gang
    def test_relaunched_gang_reuses_compile_cache(self, tmp_path,
                                                  monkeypatch):
        """The full gang-level pin: two GangSupervisor attempts under
        the same ``JAX_COMPILATION_CACHE_DIR`` (workers inherit the
        driver's environment) — the worker of the second launch reports
        persistent-cache HITS for the programs the first launch
        compiled."""
        from synapseml_tpu.parallel.supervisor import GangSupervisor
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "xc"))

        def launch():
            sup = GangSupervisor(
                "mp_tasks:compile_cache_probe", n_processes=1,
                devices_per_process=1, timeout_s=180,
                heartbeat_interval_s=0.5, env_extra=_CACHE_ALL_ENV)
            return sup.run()[0]

        first = launch()
        assert first["dir"] == str(tmp_path / "xc")
        assert first["cache_misses"] > 0
        second = launch()
        assert second["cache_hits"] > 0, (
            f"relaunched gang did not reuse the compile cache: {second}")
