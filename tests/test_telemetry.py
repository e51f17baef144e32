"""Telemetry subsystem tests: registry semantics, span tracing,
Prometheus exposition through the serving server, collectives counters
on the simulated mesh, instrumented trainers, and artifact-writer
crash-safety (the BENCH_r05 truncation regression class)."""

import contextlib
import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from synapseml_tpu.telemetry import (MetricsRegistry, SchemaError, Tracer,
                                     dumps_checked, get_registry, get_tracer,
                                     read_json, render_prometheus, span,
                                     step_span, write_json)


@contextlib.contextmanager
def profiler_session(directory):
    """A jax profiler session on the CPU (no Python frames: cheap)."""
    import jax
    from jax.profiler import ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_plane_names(directory):
    """The event names on the ``/host:CPU`` plane of the capture."""
    from jax.profiler import ProfileData
    pb, = glob.glob(os.path.join(str(directory), "plugins", "profile", "*",
                                 "*.xplane.pb"))
    names = set()
    for plane in ProfileData.from_file(pb).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


# -- registry ----------------------------------------------------------------

class TestRegistry:
    def test_counter_labels_and_values(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs_total", "requests", ("api", "code"))
        c.inc(api="/a", code="200")
        c.inc(2, api="/a", code="200")
        c.inc(api="/b", code="500")
        assert c.value(api="/a", code="200") == 3
        assert c.value(api="/b", code="500") == 1
        assert c.value(api="/c", code="200") == 0        # untouched series

    def test_counter_rejects_decrease_and_wrong_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total", "", ("op",))
        with pytest.raises(ValueError):
            c.inc(-1, op="x")
        with pytest.raises(ValueError):
            c.inc(1)                                     # missing label
        with pytest.raises(ValueError):
            c.inc(1, op="x", extra="y")                  # extra label

    def test_get_or_create_and_kind_mismatch(self):
        reg = MetricsRegistry()
        c1 = reg.counter("same", "", ("a",))
        assert reg.counter("same", "", ("a",)) is c1
        with pytest.raises(ValueError):
            reg.gauge("same")                            # kind mismatch
        with pytest.raises(ValueError):
            reg.counter("same", "", ("b",))              # label mismatch

    def test_gauge_set_inc_dec(self):
        reg = MetricsRegistry()
        g = reg.gauge("g")
        g.set(5.0)
        g.inc(2)
        g.dec(3)
        assert g.value() == 4.0

    def test_histogram_cumulative_buckets(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", "", (), buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 5, 50, 500):
            h.observe(v)
        st = h.stats()
        assert st["buckets"] == [1, 2, 3]                # cumulative <= bound
        assert st["count"] == 4
        assert st["sum"] == pytest.approx(555.5)

    def test_concurrent_increments_lose_nothing(self):
        reg = MetricsRegistry()
        c = reg.counter("n_total", "", ("t",))
        h = reg.histogram("lat", "", (), buckets=(0.5,))

        def work():
            for _ in range(1000):
                c.inc(t="x")
                h.observe(0.1)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value(t="x") == 8000
        assert h.stats()["count"] == 8000

    def test_reset_zeroes_but_keeps_registration(self):
        reg = MetricsRegistry()
        c = reg.counter("r_total", "", ("k",))
        c.inc(5, k="a")
        reg.reset()
        assert c.value(k="a") == 0
        c.inc(k="a")                                     # old handle works
        assert reg.counter("r_total", "", ("k",)).value(k="a") == 1

    def test_snapshot_is_jsonable(self):
        reg = MetricsRegistry()
        reg.counter("a_total", "", ("x",)).inc(2, x="1")
        reg.histogram("b", "", (), buckets=(1,)).observe(0.5)
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["a_total"]["series"][0]["value"] == 2
        assert snap["b"]["series"][0]["count"] == 1

    def test_histogram_bucket_mismatch_rejected(self):
        reg = MetricsRegistry()
        h = reg.histogram("hb", "", (), buckets=(1.0, 2.0))
        assert reg.histogram("hb", "", ()) is h          # None: no claim
        assert reg.histogram("hb", "", (), buckets=(2.0, 1.0)) is h  # same set
        with pytest.raises(ValueError):
            reg.histogram("hb", "", (), buckets=(1.0, 3.0))

    def test_invalid_names_rejected(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("bad name")
        with pytest.raises(ValueError):
            reg.counter("ok", "", ("bad-label",))


# -- prometheus exposition ---------------------------------------------------

class TestExposition:
    def test_text_format(self):
        reg = MetricsRegistry()
        reg.counter("x_total", "help text", ("op",)).inc(3, op='a"b\nc')
        reg.gauge("g").set(2.5)
        reg.histogram("h", "", (), buckets=(1.0,)).observe(0.5)
        text = render_prometheus(reg)
        assert "# TYPE x_total counter" in text
        assert 'x_total{op="a\\"b\\nc"} 3' in text
        assert "g 2.5" in text
        assert 'h_bucket{le="1"} 1' in text
        assert 'h_bucket{le="+Inf"} 1' in text
        assert "h_sum 0.5" in text and "h_count 1" in text

    def test_nonfinite_gauge_renders_not_raises(self):
        # a poisoned gauge must not kill every subsequent /metrics scrape
        reg = MetricsRegistry()
        reg.gauge("bad").set(float("nan"))
        reg.gauge("worse").set(float("-inf"))
        text = render_prometheus(reg)
        assert "bad NaN" in text and "worse -Inf" in text


# -- span tracing ------------------------------------------------------------

class TestTracing:
    def test_nesting_and_attribution(self):
        tr = Tracer()
        with tr.span("outer", phase="fit"):
            with tr.span("inner"):
                time.sleep(0.01)
        outer, = tr.spans("outer")
        inner, = tr.spans("inner")
        assert inner.parent_id == outer.span_id
        assert outer.parent_id is None
        assert inner.duration_s >= 0.01
        assert outer.duration_s >= inner.duration_s
        assert outer.attrs == {"phase": "fit"}
        assert outer.host and isinstance(outer.process_index, int)
        assert tr.children(outer) == [inner]
        assert tr.roots() == [outer]

    def test_sibling_threads_do_not_nest(self):
        tr = Tracer()
        done = threading.Event()

        def other():
            with tr.span("t2"):
                pass
            done.set()

        with tr.span("t1"):
            threading.Thread(target=other).start()
            assert done.wait(5)
        assert tr.spans("t2")[0].parent_id is None

    def test_chrome_trace_export(self, tmp_path):
        tr = Tracer()
        with tr.span("a", n=1):
            pass
        tr.record("b", 0.25, rows=10)
        path = str(tmp_path / "trace.json")
        exported = tr.export_chrome(path)
        on_disk = json.load(open(path))
        assert on_disk == exported
        events = {e["name"]: e for e in on_disk["traceEvents"]}
        assert events["a"]["ph"] == "X" and events["a"]["args"]["n"] == 1
        assert events["b"]["dur"] == pytest.approx(0.25e6)

    def test_bounded_and_resettable(self):
        tr = Tracer(max_spans=2)
        for _ in range(4):
            with tr.span("s"):
                pass
        assert len(tr.spans()) == 2 and tr.dropped == 2
        tr.reset()
        assert tr.spans() == [] and tr.dropped == 0

    def test_module_level_span_uses_default_tracer(self):
        before = len(get_tracer().spans("default_span_test"))
        with span("default_span_test"):
            pass
        assert len(get_tracer().spans("default_span_test")) == before + 1

    def test_ring_keeps_the_newest_and_counts_the_dropped(self):
        tr = Tracer(max_spans=3)
        for i in range(10):
            with tr.span("s", i=i):
                pass
        assert [s.attrs["i"] for s in tr.spans()] == [7, 8, 9]
        assert tr.dropped == 7
        tr.record("late", 0.5)
        assert [s.name for s in tr.spans()] == ["s", "s", "late"]
        assert tr.dropped == 8

    def test_clock_is_monotonic_ns(self):
        tr = Tracer()
        a = time.monotonic_ns()
        with tr.span("timed") as sp:
            b = time.monotonic_ns()
        c = time.monotonic_ns()
        assert a <= sp.start_ns <= b <= sp.end_ns <= c
        assert sp.duration_s == (sp.end_ns - sp.start_ns) / 1e9
        assert abs(sp.start_wall_s - time.time()) < 5.0
        # an interval measured elsewhere lands on the same clock
        rec = tr.record("request", 0.25, start_ns=a, trace_id="abc")
        assert (rec.start_ns, rec.end_ns) == (a, a + 250_000_000)
        assert rec.trace_id == "abc"
        ev = {e["name"]: e for e in tr.chrome_trace()["traceEvents"]}
        assert ev["request"]["args"]["trace_id"] == "abc"
        assert ev["request"]["dur"] == pytest.approx(0.25e6)

    def test_attrs_until_close_and_trace_id_down_the_stack(self):
        tr = Tracer()
        with tr.span("admit") as outer:
            outer.trace_id = "req-1"
            with tr.span("prefill", trace_id=None) as inner:
                inner.set(bucket=8)
            with tr.span("other", trace_id="req-2"):
                pass
        assert tr.spans("prefill")[0].trace_id == "req-1"
        assert tr.spans("prefill")[0].attrs == {"bucket": 8}
        assert tr.spans("other")[0].trace_id == "req-2"

    def test_a_region_left_open_ends_with_its_ancestor(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("fit") as fit:
                phase = tr.span("fit.bin").start()
                raise RuntimeError("binning failed")
        assert phase.end_ns == fit.end_ns
        assert [s.name for s in tr.spans()] == ["fit.bin", "fit"]
        with tr.span("next") as nxt:        # the stack is clean again
            pass
        assert nxt.parent_id is None
        phase.close()                       # closing twice records once
        assert len(tr.spans("fit.bin")) == 1

    def test_step_span_without_a_session_is_the_shared_noop(self):
        import tracemalloc
        tr = get_tracer()
        before = len(tr.spans())
        first = step_span("engine.step")
        assert first.live is False
        assert step_span("loop.tick") is first      # one object, ever

        def site():
            with step_span("engine.step") as sp:
                if sp.live:
                    sp.set(never=1)
        site()
        tracemalloc.start()
        try:
            a = tracemalloc.take_snapshot()
            for _ in range(1000):
                site()
            b = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        here = [st for st in b.compare_to(a, "filename")
                if st.traceback[0].filename.endswith("tracing.py")]
        assert sum(st.size_diff for st in here) == 0
        assert len(tr.spans()) == before

    def test_step_span_records_inside_a_profiler_session(self, tmp_path):
        tr = get_tracer()
        with profiler_session(tmp_path):
            with span("always.outer") as outer:
                with step_span("step.inner") as inner:
                    assert inner.live
                    inner.set(tokens=3)
        assert step_span("step.after").live is False
        got = [s for s in tr.spans("step.inner")
               if s.parent_id == outer.span_id]
        assert len(got) == 1 and got[0].attrs == {"tokens": 3}
        assert outer.start_ns <= got[0].start_ns <= got[0].end_ns \
            <= outer.end_ns
        # both lie on the profiler's host plane, under their own names
        assert {"always.outer", "step.inner"} <= host_plane_names(tmp_path)


    def test_gang_plane_cursor_survives_a_wrapped_ring(self, monkeypatch):
        from synapseml_tpu.telemetry import gangplane
        tr = Tracer(max_spans=4)
        monkeypatch.setattr(gangplane, "get_tracer", lambda: tr)

        def work(*tags):
            for t in tags:
                with tr.span("w", tag=t):
                    pass

        def tags(payload):
            return [e["args"]["tag"] for e in payload["spans"]]

        work(1, 2, 3)
        payload, cur, _ = gangplane.telemetry_batch(0)
        assert tags(payload) == [1, 2, 3] and cur == 3
        work(4, 5)                            # the ring wraps: 2..5 remain
        payload, cur, _ = gangplane.telemetry_batch(0, span_cursor=cur)
        assert tags(payload) == [4, 5] and cur == 5
        work(6, 7, 8, 9, 10, 11)              # more than a ring between polls
        payload, cur, _ = gangplane.telemetry_batch(0, span_cursor=cur)
        assert tags(payload) == [8, 9, 10, 11] and cur == 11
        payload, cur, _ = gangplane.telemetry_batch(0, span_cursor=cur)
        assert tags(payload) == [] and cur == 11
        tr.reset()                            # a reset mid-run starts over
        work(12)
        payload, cur, _ = gangplane.telemetry_batch(0, span_cursor=cur)
        assert tags(payload) == [12] and cur == 1


# -- spans where the work happens ----------------------------------------------

def _names_under(tr, parent):
    return [s.name for s in sorted(tr.children(parent),
                                   key=lambda s: s.start_ns)]


class TestProgramSpans:
    @pytest.fixture(scope="class")
    def tiny_model(self):
        import jax
        import jax.numpy as jnp
        from synapseml_tpu.models.llm import LlamaConfig, LlamaModel
        cfg = LlamaConfig.tiny(num_layers=2, max_len=96, dtype=jnp.float32)
        model = LlamaModel(cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((2, 8), jnp.int32))
        return cfg, model, variables

    def test_engine_step_and_admit_spans(self, tiny_model, tmp_path):
        from synapseml_tpu.models.llm import SlotEngine
        cfg, model, variables = tiny_model
        rng = np.random.default_rng(0)
        ids = rng.integers(1, cfg.vocab_size, (2, 7)).astype(np.int32)
        eng = SlotEngine(model, variables, n_slots=4, max_len=64)
        tr = get_tracer()
        tr.reset()
        # no profiler session: a step and an admission record no span
        eng.admit(ids[0], 8)
        eng.step()
        assert tr.spans() == []
        # that step's call handed the device the next one too, so the slot
        # admitted now joins the step after it
        with profiler_session(tmp_path):
            res = eng.admit(ids[1], 8)
            alone = eng.step()
            events = eng.step()
        admit, = tr.spans("engine.admit")
        assert _names_under(tr, admit) == [
            "engine.admit.lookup", "engine.admit.prefill",
            "engine.admit.commit"]
        assert admit.attrs == {"bucket": res.bucket, "prompt_tokens": 7,
                               "reused_tokens": 0, "path": "cold",
                               "padding_rows": res.bucket - 7,
                               "prefill_attention": "dense",
                               "prefill_key_blocks_visited": 0,
                               "prefill_key_blocks_bucket": 0}
        first, step = tr.spans("engine.step")
        for s in (first, step):
            # the prepare is the next step's, the wait and commit this one's
            assert _names_under(tr, s) == [
                "engine.step.prepare", "engine.step.wait",
                "engine.step.commit"]
        for prepare in tr.spans("engine.step.prepare"):
            assert _names_under(tr, prepare) == [
                "engine.step.prepare.upload", "engine.step.prepare.dispatch"]
        assert first.attrs["tokens"] == len(alone) == 1
        assert first.attrs["slots"] == 1
        assert first.attrs["kv_span_sum"] == 8 + 1      # one stepped before
        assert step.attrs["tokens"] == len(events) == 2
        assert step.attrs["slots"] == 2
        assert step.attrs["kv_span_sum"] == (8 + 2) + 8
        assert step.attrs["program"] == "decode_dense"
        assert first.attrs["overlapped"] and step.attrs["overlapped"]
        assert sum(s.duration_s for s in tr.children(step)) \
            <= step.duration_s
        assert {"engine.step", "engine.step.wait", "engine.admit.prefill"} \
            <= host_plane_names(tmp_path)

    def test_paged_step_span_counts_live_and_walked_tiles(self, tiny_model,
                                                          tmp_path):
        """``engine.step`` of a paged engine: tiles the kernel fetched
        for the returned step, over every attention layer, and the loop
        trips it made for them (one a tile: no dead tile is walked)."""
        from synapseml_tpu.models.llm import SlotEngine
        cfg, model, variables = tiny_model
        rng = np.random.default_rng(1)
        eng = SlotEngine(model, variables, n_slots=3, max_len=96,
                         attention_backend="interpret")
        tile = eng._paged_geo.tile
        eng.admit(rng.integers(1, cfg.vocab_size, 2 * tile + 3)
                  .astype(np.int32), 4)
        eng.admit(rng.integers(1, cfg.vocab_size, 5).astype(np.int32), 4)
        tr = get_tracer()
        tr.reset()
        with profiler_session(tmp_path):
            eng.step()
        step, = tr.spans("engine.step")
        # three live tiles, one, and the idle slot's first
        live = cfg.num_attention_layers * (3 + 1 + 1)
        assert step.attrs["paged_tiles_live"] == live
        assert step.attrs["paged_tiles_walked"] == live
        assert step.attrs["program"] == "decode_interpret"

    def test_speculative_step_has_a_draft_span(self, tiny_model, tmp_path):
        from synapseml_tpu.models.llm import SlotEngine
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                         spec_draft_len=2)
        eng.admit(np.array([5, 6, 5, 6, 5, 6, 5], np.int32), 8)
        tr = get_tracer()
        tr.reset()
        with profiler_session(tmp_path):
            events = eng.step()
        step, = tr.spans("engine.step")
        names = _names_under(tr, step)
        assert names[0] == "engine.step.draft"
        assert names[1:] == ["engine.step.prepare", "engine.step.wait",
                             "engine.step.commit"]
        assert step.attrs["tokens"] == len(events)
        assert step.attrs["program"] == eng.last_program

    def test_a_served_request_starts_at_the_listeners_enqueue(
            self, tiny_model, tmp_path):
        from synapseml_tpu.serving import LLMServer
        cfg, model, variables = tiny_model
        srv = LLMServer(model, variables, n_slots=2, max_len=64,
                        engine_kwargs={"name": "t-spans"})
        seen = []
        parse = srv._loop.input_parser
        srv._loop.input_parser = lambda req: (seen.append(req), parse(req))[1]
        tr = get_tracer()
        tr.reset()
        try:
            with profiler_session(tmp_path):
                # the idle tick that was waiting for a request when the
                # session began is no live span: let it run out first
                time.sleep(3 * srv._loop.idle_timeout_s)
                req = urllib.request.Request(
                    srv.url, data=json.dumps(
                        {"ids": [3, 4, 5, 6, 7], "max_new_tokens": 4}).encode(),
                    method="POST",
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=60) as r:
                    assert len(json.loads(r.read())["ids"]) == 4
        finally:
            srv.close()
        request, = tr.spans("serving.request")
        assert request.start_ns == int(seen[0].enqueued_at * 1e9)
        assert request.trace_id and request.attrs["outcome"] == "retired"
        assert request.attrs["tokens"] == 4
        assert 0.0 <= request.attrs["queue_wait_s"] \
            <= request.attrs["ttft_s"] <= request.duration_s
        # the request's admission hangs under the tick's loop.admit, by
        # parent and by trace id
        admit, = tr.spans("engine.admit")
        loop_admit, = [s for s in tr.spans("loop.admit")
                       if s.span_id == admit.parent_id]
        assert admit.trace_id == loop_admit.trace_id == request.trace_id
        assert loop_admit.attrs == {"admitted": 1, "waiting": 0}
        tick, = [s for s in tr.spans("loop.tick")
                 if s.span_id == loop_admit.parent_id]
        assert _names_under(tr, tick)[:3] == ["loop.pump", "loop.admit",
                                              "loop.expire"]
        emits = tr.spans("loop.emit")
        assert len(emits) == 3 and all(e.attrs == {"events": 1}
                                       for e in emits)
        steps = tr.spans("engine.step")
        assert [s.parent_id for s in steps] == [e.parent_id for e in emits]

    def test_warmup_spans_one_per_program(self, tiny_model):
        from synapseml_tpu.models.llm import SlotEngine
        cfg, model, variables = tiny_model
        tr = get_tracer()
        tr.reset()
        eng = SlotEngine(model, variables, n_slots=2, max_len=32,
                         warmup="sync", name="t-warm-spans")
        warm, = tr.spans("llm.warmup")
        programs = tr.children(warm)
        assert {s.name for s in programs} == {"llm.warmup.program"}
        assert warm.attrs["programs"] == len(programs) \
            == eng.compile_plane.snapshot()["programs_total"]
        for s in programs:
            assert s.attrs["key"] and s.attrs["seconds"] >= 0
            assert isinstance(s.attrs["compiled"], bool)
        assert sum(s.duration_s for s in programs) <= warm.duration_s

    def test_gbdt_fit_spans(self):
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        from synapseml_tpu.models.gbdt.booster import SCAN_CHUNK
        tr = get_tracer()
        tr.reset()
        rng = np.random.default_rng(0)
        X = rng.normal(size=(600, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float64)
        iters = SCAN_CHUNK + 2              # one scanned chunk, two looped
        booster, _ = train(X, y, BoostingConfig(
            objective="binary", num_iterations=iters, num_leaves=7))
        fit, = tr.spans("gbdt.fit")
        assert _names_under(tr, fit) == [
            "gbdt.fit.bin", "gbdt.fit.bin", "gbdt.fit.upload",
            "gbdt.fit.compile", "gbdt.fit.boost"]
        assert fit.attrs["rows"] == 600 and fit.attrs["features"] == 4
        assert fit.attrs["iterations"] == iters
        assert fit.attrs["hist_path"] == booster.measures.hist_path
        assert fit.attrs["two_level"] and fit.attrs["objective"] == "binary"
        boost, = tr.spans("gbdt.fit.boost")
        assert boost.attrs["iterations"] == iters == booster.measures.iterations
        assert _names_under(tr, boost) == [
            "gbdt.boost.chunk", "gbdt.fit.download", "gbdt.fit.download"]
        chunk, = tr.spans("gbdt.boost.chunk")
        assert chunk.attrs == {"index": 0, "iterations": SCAN_CHUNK}
        assert tr.spans("gbdt.train") == []


# -- the request's account and the loop's ---------------------------------------

class _ScriptApi:
    """The listener's half of an API, driven by hand."""

    def __init__(self):
        import uuid
        self.path = f"/acct-{uuid.uuid4().hex[:8]}"
        self.max_queue, self.reply_timeout_s = 64, 60.0
        self.queue, self.replies = [], {}

    def poll(self, n):
        out, self.queue = self.queue[:int(n)], self.queue[int(n):]
        return out

    get_batch = lambda self, n, timeout_s: self.poll(n)    # noqa: E731

    def reply(self, rid, rep):
        self.replies[rid] = rep
        return True

    def send(self, max_new, prompt=(1, 2, 3), tenant="default"):
        from synapseml_tpu.serving.server import ServingRequest
        req = ServingRequest(
            id=f"r{len(self.replies) + len(self.queue)}-{id(self)}",
            method="POST", path="/", headers={}, body=json.dumps(
                {"ids": list(prompt), "max_new_tokens": max_new}).encode(),
            enqueued_at=time.monotonic(), tenant=tenant)
        self.queue.append(req)
        return req


class _ScriptEngine:
    """Slots, budgets and a clock: ``admit`` sleeps the next scripted
    prefill, ``step`` the scripted step and hands every slot a token.  It
    keeps ``phase_seconds`` as ``SlotEngine`` does (all of a step its wait)
    and leaves ``trace_sink`` to the loop."""

    def __init__(self, n_slots, step_s=0.0, prefills=()):
        self.n_slots, self.step_s = n_slots, step_s
        self.prefills = list(prefills)
        self.left = {}
        self.trace_sink = None
        self.phase_seconds = {"prepare": 0.0, "wait": 0.0, "commit": 0.0}

    active_count = property(lambda self: len(self.left))
    free_slot_count = property(lambda self: self.n_slots - len(self.left))

    def min_remaining_tokens(self):
        return None

    def admit(self, ids, max_new):
        import types
        slot = min(set(range(self.n_slots)) - set(self.left))
        seconds, bucket = self.prefills.pop(0) if self.prefills else (0.0, 8)
        time.sleep(seconds)
        if max_new > 1:
            self.left[slot] = max_new - 1
        return types.SimpleNamespace(slot=slot, token=7, bucket=bucket,
                                     finished=max_new == 1, reason=None)

    def step(self):
        import types
        t0 = time.perf_counter()
        time.sleep(self.step_s)
        events = []
        for slot in sorted(self.left):
            self.left[slot] -= 1
            done = self.left[slot] == 0
            events.append(types.SimpleNamespace(
                slot=slot, token=7, finished=done,
                reason="length" if done else None))
            if done:
                del self.left[slot]
        self.phase_seconds["wait"] += time.perf_counter() - t0
        return events

    def cancel(self, slot):
        self.left.pop(slot, None)


def _hand_driven_loop(engine, api=None):
    """A ``_DecodeLoop`` whose thread is stopped: ticks run by hand."""
    from synapseml_tpu.serving.server import _DecodeLoop
    from synapseml_tpu.telemetry import RequestTraceStore
    api = api or _ScriptApi()
    loop = _DecodeLoop(None, api, engine,
                       input_parser=lambda req: json.loads(req.body),
                       request_tracer=RequestTraceStore())
    loop._stop.set()
    loop._thread.join(timeout=5)
    assert not loop._thread.is_alive()
    return loop, api


def _request_span(loop, req):
    """The one ``serving.request`` span of ``req`` (by its API and the
    listener's enqueue, where the span starts)."""
    sp, = [sp for sp in get_tracer().spans("serving.request")
           if sp.attrs.get("api") == loop.api.path
           and sp.start_ns == int(req.enqueued_at * 1e9)]
    return sp


class TestAccounts:
    STEP, PREFILL_A, PREFILL_B, PREFILL_C = 0.010, 0.020, 0.050, 0.030
    #: what a sleep may overshoot by on a loaded machine
    LATE = 0.05

    @pytest.fixture(scope="class")
    def served(self):
        """A decodes alone, then B and C arrive together: B's prefill and
        C's stand between two of A's tokens, and B's before C's own."""
        engine = _ScriptEngine(3, self.STEP, [
            (self.PREFILL_A, 16), (self.PREFILL_B, 64), (self.PREFILL_C, 32)])
        loop, api = _hand_driven_loop(engine)
        loop._publish_account()             # the account starts here
        a = api.send(8)
        loop._tick()
        loop._tick()
        b, c = api.send(3), api.send(3)
        time.sleep(0.015)                   # in the listener's queue
        t_wide = time.monotonic()
        loop._tick()                        # the widest tick of A's life
        wide = time.monotonic() - t_wide
        while engine.active_count:
            loop._tick()
        loop._publish_account()
        spans = {k: _request_span(loop, r) for k, r in
                 (("a", a), ("b", b), ("c", c))}
        return loop, spans, wide, get_tracer().spans("loop.account")[-1]

    def test_the_accounts_identities(self, served):
        _, spans, _, _ = served
        for sp in spans.values():
            at = sp.attrs
            assert at["listener_wait_s"] + at["slot_wait_s"] \
                == pytest.approx(at["queue_wait_s"], abs=1e-9)
            assert at["queue_wait_s"] + at["prefill_s"] \
                == pytest.approx(at["ttft_s"], abs=1e-9)
            assert 0.0 <= at["stalled_s"] <= at["decode_s"]
            assert at["ttft_s"] + at["decode_s"] <= sp.duration_s
            assert at["gap_max_s"] <= at["decode_s"]
            assert at["steps"] == at["tokens"] - 1
            assert 0.0 <= at["behind_prefill_s"] <= at["slot_wait_s"]

    def test_waits_and_what_stood_ahead(self, served):
        _, spans, _, _ = served
        a, b, c = (spans[k].attrs for k in "abc")
        assert a["admissions_ahead"] == b["admissions_ahead"] == 0
        assert a["behind_prefill_s"] == b["behind_prefill_s"] == 0.0
        assert b["listener_wait_s"] >= 0.015 > b["slot_wait_s"]
        # C stood on the waiting list through B's prefill
        assert c["admissions_ahead"] == 1
        assert self.PREFILL_B <= c["behind_prefill_s"] <= c["slot_wait_s"] \
            < self.PREFILL_B + self.LATE
        assert self.PREFILL_A <= a["prefill_s"] < self.PREFILL_A + self.LATE
        assert self.PREFILL_C <= c["prefill_s"] < self.PREFILL_C + self.LATE

    def test_the_widest_gap_and_its_cause(self, served):
        _, spans, wide, _ = served
        a, b, c = (spans[k].attrs for k in "abc")
        # A's widest gap is the period that held both prefills and a
        # step (and the test's own 15 ms between two ticks)
        scripted = self.PREFILL_B + self.PREFILL_C + self.STEP
        assert scripted <= a["gap_max_s"] <= wide + 0.015 + self.LATE
        assert a["gap_max_cause"] == "admit"
        assert a["gap_max_admissions"] == 2 and a["gap_max_bucket"] == 64
        assert a["admissions_during"] == 2
        assert self.PREFILL_B + self.PREFILL_C <= a["stalled_s"] \
            < scripted + self.LATE
        # B's first gap holds C's prefill; C's own gaps are plain steps
        assert b["gap_max_cause"] == "admit"
        assert (b["gap_max_admissions"], b["gap_max_bucket"]) == (1, 32)
        assert self.PREFILL_C <= b["stalled_s"] < self.PREFILL_C + self.LATE
        assert b["admissions_during"] == 1
        assert c["gap_max_cause"] == "step" and c["stalled_s"] == 0.0
        assert "gap_max_admissions" not in c
        assert self.STEP <= c["gap_max_s"] < self.STEP + self.LATE

    def test_the_registry_holds_the_same_sums(self, served):
        loop, spans, _, _ = served
        reg, api = get_registry(), loop.api.path
        stalled = sum(sp.attrs["stalled_s"] for sp in spans.values())
        decode = sum(sp.attrs["decode_s"] for sp in spans.values())
        assert reg.get("llm_request_stalled_seconds_total").value(api=api) \
            == pytest.approx(stalled)
        assert reg.get("llm_request_decode_seconds_total").value(api=api) \
            == pytest.approx(decode)
        gaps = reg.get("llm_request_token_gap_max_seconds").stats(api=api)
        assert gaps["count"] == 3
        assert gaps["sum"] == pytest.approx(
            sum(sp.attrs["gap_max_s"] for sp in spans.values()))

    def test_loop_account_phases_add_up(self, served):
        loop, spans, _, account = served
        at = account.attrs
        phases = {k: v for k, v in at.items() if k.endswith("_s")}
        assert sorted(phases) == sorted(
            ["pump_s", "idle_s", "admit_s", "expire_s", "step_prepare_s",
             "step_wait_s", "step_commit_s", "step_other_s", "emit_s"])
        assert sum(phases.values()) == pytest.approx(account.duration_s,
                                                     abs=1e-6)
        assert all(v >= -1e-6 for v in phases.values())
        assert at["api"] == loop.api.path
        assert at["admissions"] == 3 and at["prompt_tokens"] == 9
        assert at["tokens"] == 8 + 3 + 3
        assert at["steps"] == 7 == spans["a"].attrs["steps"]
        assert at["ticks"] == at["steps"]
        # the scripted engine spends a step in its wait, the loop an
        # admission in the prefill
        assert 7 * self.STEP <= at["step_wait_s"] < 7 * (self.STEP + self.LATE)
        assert 0.0 <= at["step_other_s"] < self.LATE
        assert 0.1 <= at["admit_s"] < 0.1 + 3 * self.LATE
        reg = get_registry().get("llm_loop_seconds_total")
        assert reg.value(api=loop.api.path, phase="step_wait") \
            == pytest.approx(at["step_wait_s"])
        assert reg.value(api=loop.api.path, phase="admit") \
            >= at["admit_s"]
        assert get_registry().get("llm_tokens_total").value(
            api=loop.api.path) == 14

    def test_a_long_timeline_keeps_its_end(self):
        """1,000 tokens: the timeline is its transitions, the terminal
        event carries the totals, nothing is dropped, and the store is
        called once a transition, never a token."""
        loop, api = _hand_driven_loop(_ScriptEngine(1))
        calls = []
        event = loop._tracer.event
        loop._tracer.event = lambda tid, name, **a: (
            calls.append(name), event(tid, name, **a))[1]
        req = api.send(1000)
        loop._tick()
        while loop.engine.active_count:
            loop._tick()
        tr, = loop._tracer.traces(5)
        assert calls == ["queued", "admitted", "prefill", "retired"]
        assert [e["name"] for e in tr["events"]] == calls
        assert tr["dropped_events"] == 0 and tr["outcome"] == "retired"
        assert tr["events"][-1]["tokens"] == 1000
        assert tr["events"][-1]["steps"] == 999
        sp = _request_span(loop, req)
        assert sp.attrs["steps"] == 999 and sp.attrs["tokens"] == 1000

    def test_a_request_that_ends_at_its_first_token(self):
        loop, api = _hand_driven_loop(_ScriptEngine(1))
        req = api.send(1)
        loop._tick()
        at = _request_span(loop, req).attrs
        assert at["outcome"] == "retired" and at["tokens"] == 1
        assert at["decode_s"] == 0.0 and at["steps"] == 0
        assert "gap_max_s" not in at and at["stalled_s"] == 0.0

    @pytest.mark.parametrize("values", [
        [(0.004, 32)], [(0.011, 20), (0.0055, 12)],
        [(2.0, 3), (0.0001, 1), (float("nan"), 4), (0.01, 0)]])
    def test_weighted_observation_is_the_per_token_loop(self, values):
        from synapseml_tpu.telemetry import (SERVING_TOKEN_LATENCY_BUCKETS,
                                             WindowedHistogram)
        reg = MetricsRegistry()
        one = reg.histogram("one", "", ("api",),
                            buckets=SERVING_TOKEN_LATENCY_BUCKETS)
        many = reg.histogram("many", "", ("api",),
                             buckets=SERVING_TOKEN_LATENCY_BUCKETS)
        w_one = WindowedHistogram(SERVING_TOKEN_LATENCY_BUCKETS)
        w_many = WindowedHistogram(SERVING_TOKEN_LATENCY_BUCKETS)
        for value, n in values:
            for _ in range(n):
                one.observe(value, api="/t")
                w_one.observe(value, now=100.0)
            many.observe_n(value, n, api="/t")
            w_many.observe_n(value, n, now=100.0)
        a, b = one.stats(api="/t"), many.stats(api="/t")
        assert a["buckets"] == b["buckets"] and a["count"] == b["count"]
        assert a["sum"] == pytest.approx(b["sum"], rel=1e-12)
        a, b = w_one.merged(now=100.0), w_many.merged(now=100.0)
        assert a["buckets"] == b["buckets"] and a["count"] == b["count"]
        assert a["sum"] == pytest.approx(b["sum"], rel=1e-12)

    def test_a_step_of_32_slots_costs_no_event_and_one_observation(
            self, monkeypatch):
        """The real engine under the loop, no profiler: one decode step
        of 32 slots (two tenants) calls ``RequestTraceStore.event`` not
        once and each histogram at most once a tenant, and leaves the
        histograms as 32 single observations would."""
        import jax
        import jax.numpy as jnp
        from synapseml_tpu.models.llm import (LlamaConfig, LlamaModel,
                                              SlotEngine)
        from synapseml_tpu.telemetry import Histogram, WindowedHistogram
        cfg = LlamaConfig.tiny(num_layers=1, max_len=32, dtype=jnp.float32)
        model = LlamaModel(cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((2, 8), jnp.int32))
        engine = SlotEngine(model, variables, n_slots=32, max_len=32,
                            name="t-acct-32")
        loop, api = _hand_driven_loop(engine)
        for i in range(32):
            api.send(6, prompt=(3 + i % 5, 4, 5),
                     tenant="even" if i % 2 == 0 else "odd")
        loop._tick()                # 32 admissions and the first step
        loop._tick()
        assert engine.active_count == 32 and not loop._fresh
        before = loop._m_tok_lat.stats(api=api.path)
        events, observed = [], []
        monkeypatch.setattr(loop._tracer, "event",
                            lambda *a, **k: events.append(a))
        for cls in (Histogram, WindowedHistogram):
            real = cls.observe_n
            monkeypatch.setattr(cls, "observe_n", (
                lambda self, value, n, *a, _real=real, **k: (
                    observed.append((id(self), n)),
                    _real(self, value, n, *a, **k))[1]))
        loop._tick()
        monkeypatch.undo()
        assert events == []
        by_histogram = {}
        for which, n in observed:
            by_histogram.setdefault(which, []).append(n)
        # the API's histogram, its SLO window, the two tenants' windows
        assert sorted(map(sorted, by_histogram.values())) \
            == [[16], [16], [16, 16], [16, 16]]
        after = loop._m_tok_lat.stats(api=api.path)
        assert after["count"] - before["count"] == 32
        while engine.active_count:
            loop._tick()
        tr = loop._tracer.traces(1)[0]
        assert [e["name"] for e in tr["events"]] == [
            "queued", "admitted", "prefill", "decode", "retired"]
        assert tr["events"][-1]["steps"] == 5 == tr["attrs"]["steps"]


# -- artifact writer ---------------------------------------------------------

class TestArtifact:
    def test_round_trip_and_schema(self, tmp_path):
        path = str(tmp_path / "a.json")
        obj = {"metric": "x", "value": 1.5, "nested": {"k": [1, 2]}}
        parsed = write_json(path, obj, schema=("metric", "value"))
        assert parsed == obj
        assert read_json(path) == obj

    def test_schema_rejects_before_touching_disk(self, tmp_path):
        path = str(tmp_path / "a.json")
        write_json(path, {"metric": "x"}, schema=("metric",))
        with pytest.raises(SchemaError):
            write_json(path, {"wrong": 1}, schema=("metric",))
        assert read_json(path) == {"metric": "x"}        # old file intact
        assert os.listdir(tmp_path) == ["a.json"]        # no tmp litter

    def test_callable_schema(self):
        def must_be_positive(obj):
            if obj["v"] <= 0:
                raise SchemaError("v must be positive")
        assert json.loads(dumps_checked({"v": 1}, must_be_positive)) == {"v": 1}
        with pytest.raises(SchemaError):
            dumps_checked({"v": 0}, must_be_positive)

    def test_nan_rejected_not_emitted(self, tmp_path):
        # NaN would serialize as the non-JSON token `NaN` and poison every
        # later parse — exactly the "unparseable artifact" class
        with pytest.raises(ValueError):
            write_json(str(tmp_path / "n.json"), {"v": float("nan")})

    def test_numpy_scalars_serialize(self, tmp_path):
        parsed = write_json(str(tmp_path / "np.json"),
                            {"a": np.float32(1.5), "b": np.int64(3),
                             "c": np.arange(3)})
        assert parsed == {"a": 1.5, "b": 3, "c": [0, 1, 2]}

    def test_failed_write_leaves_old_file(self, tmp_path, monkeypatch):
        import synapseml_tpu.telemetry.artifact as art
        path = str(tmp_path / "a.json")
        write_json(path, {"v": 1})

        def boom(*a, **k):
            raise OSError("disk gone")
        monkeypatch.setattr(art.os, "replace", boom)
        with pytest.raises(OSError):
            write_json(path, {"v": 2})
        monkeypatch.undo()
        assert read_json(path) == {"v": 1}
        assert os.listdir(tmp_path) == ["a.json"]

    def test_kill_mid_write_never_corrupts(self, tmp_path):
        """SIGKILL a child that rewrites the artifact in a tight loop; at
        every instant the destination must be absent or fully parseable
        (the atomic-rename guarantee BENCH_r05 lacked)."""
        path = str(tmp_path / "bench.json")
        child = subprocess.Popen(
            [sys.executable, "-c", (
                "import sys\n"
                f"sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})\n"
                "from synapseml_tpu.telemetry.artifact import write_json\n"
                "payload = {'metric': 'x', 'blob': 'y' * 200000}\n"
                "i = 0\n"
                "while True:\n"
                "    payload['i'] = i\n"
                "    write_json(sys.argv[1], payload, schema=('metric',))\n"
                "    i += 1\n"), path],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        try:
            deadline = time.monotonic() + 10
            while not os.path.exists(path):
                assert time.monotonic() < deadline, "child never wrote"
                assert child.poll() is None, "child died early"
                time.sleep(0.01)
            time.sleep(0.1)                  # let a few rewrites happen
        finally:
            child.kill()
            child.wait(timeout=10)
        obj = read_json(path, schema=("metric", "blob"))
        assert obj["metric"] == "x" and len(obj["blob"]) == 200000


# -- /metrics exposition through the serving server --------------------------

class TestServingMetrics:
    def test_metrics_endpoint_and_serving_gauges(self, devices8):
        """The acceptance surface: ONE /metrics scrape must carry a
        collective counter, a GBDT phase histogram, and a serving
        throughput gauge — the registry is process-wide, so training and
        serving in the same process expose through the same endpoint."""
        from synapseml_tpu import Dataset
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        from synapseml_tpu.parallel import allreduce_fn
        from synapseml_tpu.parallel.mesh import make_mesh
        from synapseml_tpu.serving import ContinuousClient, PipelineServer

        # populate the non-serving families this scrape must include
        np.asarray(allreduce_fn(make_mesh({"data": 8}, devices8))(
            np.ones((8, 4), np.float32)))
        rng = np.random.default_rng(0)
        Xg = rng.normal(size=(300, 4)).astype(np.float32)
        train(Xg, (Xg[:, 0] > 0).astype(np.float64),
              BoostingConfig(objective="binary", num_iterations=2,
                             num_leaves=5))

        class _Doubler:
            def transform(self, ds):
                x = np.asarray([float(v) for v in ds["x"]])
                return Dataset({"x": ds["x"], "prediction": 2.0 * x})

        ps = PipelineServer(_Doubler(), lambda r: {"x": r.json()["x"]})
        try:
            req = urllib.request.Request(
                ps.server.url, data=b'{"x": 2.0}', method="POST")
            assert json.loads(urllib.request.urlopen(
                req, timeout=10).read())["prediction"] == 4.0
            with ContinuousClient(*ps.server.address, "/") as c:
                replies = c.request_many([b'{"x": 1.0}'] * 16)
                assert all(s == 200 for s, _ in replies)

            url = ps.server.url_for("/metrics")
            text = urllib.request.urlopen(url, timeout=10).read().decode()
            assert "# TYPE serving_records_total counter" in text
            assert 'serving_records_total{api="/"}' in text
            assert "# TYPE serving_records_per_sec gauge" in text
            assert "serving_batch_size_bucket" in text
            # client-side continuous counters ride the same registry
            assert ("serving_continuous_client_records_total"
                    in text)
            # the cross-layer acceptance criterion: collective counter +
            # gbdt phase histogram + serving throughput gauge, one scrape
            assert 'collective_calls_total{op="allreduce_fn",axis="data"}' \
                in text
            assert "gbdt_phase_seconds_bucket" in text
            assert 'serving_records_per_sec{api="/"}' in text

            j = json.loads(urllib.request.urlopen(
                url + "?format=json", timeout=10).read())
            total = sum(s["value"]
                        for s in j["serving_records_total"]["series"])
            assert total >= 17
        finally:
            ps.close()


# -- collectives instrumentation on the simulated mesh -----------------------

class TestCollectivesMetrics:
    def test_allreduce_fn_counts_bytes_and_latency(self, devices8):
        import jax
        from synapseml_tpu.parallel import allreduce_fn
        from synapseml_tpu.parallel.mesh import make_mesh

        reg = get_registry()
        calls = reg.counter("collective_calls_total", "", ("op", "axis"))
        nbytes = reg.counter("collective_bytes_total", "", ("op", "axis"))
        c0 = calls.value(op="allreduce_fn", axis="data")
        b0 = nbytes.value(op="allreduce_fn", axis="data")

        mesh = make_mesh({"data": 8}, devices8)
        fn = allreduce_fn(mesh)
        x = np.ones((8, 16), np.float32)
        out = np.asarray(fn(x))
        assert out.shape == (16,) and np.all(out == 8)

        assert calls.value(op="allreduce_fn", axis="data") == c0 + 1
        assert nbytes.value(op="allreduce_fn", axis="data") == b0 + 8 * 16 * 4
        lat = reg.histogram("collective_latency_seconds", "",
                            ("op", "axis"))
        assert lat.stats(op="allreduce_fn", axis="data")["count"] >= 1

    def test_in_jit_psum_records_at_trace_time(self, devices8):
        import jax
        from jax.sharding import PartitionSpec as P
        from synapseml_tpu.parallel import psum, shard_map_over
        from synapseml_tpu.parallel.mesh import make_mesh

        reg = get_registry()
        calls = reg.counter("collective_calls_total", "", ("op", "axis"))
        c0 = calls.value(op="psum", axis="data")

        mesh = make_mesh({"data": 8}, devices8)
        fn = jax.jit(shard_map_over(mesh, P("data"), P())(
            lambda x: psum(x.sum(0), "data")))
        x = np.ones((8, 4), np.float32)
        np.asarray(fn(x))
        np.asarray(fn(x))                       # second call: cached trace
        c_after = calls.value(op="psum", axis="data")
        assert c_after >= c0 + 1                # traced at least once
        assert c_after <= c0 + 2                # not once per execution


# -- instrumented trainers ---------------------------------------------------

class TestTrainerMetrics:
    def test_gbdt_phase_histogram_and_two_level_gauge(self):
        from synapseml_tpu.models.gbdt import BoostingConfig, train

        reg = get_registry()
        rng = np.random.default_rng(0)
        X = rng.normal(size=(400, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float64)
        booster, _ = train(X, y, BoostingConfig(
            objective="binary", num_iterations=3, num_leaves=7))

        hist = reg.get("gbdt_phase_seconds")
        assert hist is not None
        for phase in ("binning", "compile", "training", "total"):
            assert hist.stats(phase=phase)["count"] >= 1
        iters = reg.get("gbdt_iterations_total")
        assert iters.value() >= 3
        # 400 rows on the CPU fallback: auto must have resolved to off
        tl = reg.get("gbdt_two_level_resolved")
        assert tl is not None and tl.value() == 0.0
        assert reg.get("gbdt_two_level_active").value() == 0.0
        # the fit's span carries its attribution
        spans = [s for s in get_tracer().spans("gbdt.fit")
                 if s.attrs.get("rows") == 400]
        assert spans and spans[-1].attrs["objective"] == "binary"

    def test_dl_step_counters(self, devices8):
        import flax.linen as nn
        import jax
        from synapseml_tpu.models.dl.training import (DLTrainer,
                                                      OptimizerConfig,
                                                      make_dl_mesh)

        class Tiny(nn.Module):
            @nn.compact
            def __call__(self, x, deterministic=True):
                return nn.Dense(2)(x)

        reg = get_registry()
        s0 = reg.counter("dl_train_samples_total").value()
        mesh = make_dl_mesh(num_devices=8)
        tr = DLTrainer(Tiny(), OptimizerConfig(), mesh)
        x = np.ones((16, 4), np.float32)
        yl = np.zeros(16, np.int64)
        state = tr.init_state(0, x)
        step = tr.train_step()
        bi, bl = tr.shard_batch((x, yl))
        state, m = step(state, (bi,), bl, jax.random.PRNGKey(0))
        state, m = step(state, (bi,), bl, jax.random.PRNGKey(0))
        float(np.asarray(m["loss"]))
        assert reg.counter("dl_train_samples_total").value() == s0 + 32
        assert reg.gauge("dl_train_samples_per_sec").value() > 0
