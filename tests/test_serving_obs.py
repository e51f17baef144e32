"""Request-scoped serving traces + the windowed SLO plane (ISSUE 13).

The contract under test:

- ``Histogram.quantile`` / the windowed digests estimate percentiles by
  bucket interpolation within ONE bucket width of the exact value on a
  synthetic stream (the acceptance pin);
- :class:`RequestTraceStore` samples deterministically, bounds both
  axes (traces and events), always adopts a propagated id, and
  publishes finished requests as Tracer spans + flight events;
- the reserved ``GET /tracez`` / ``GET /sloz`` endpoints serve the
  store/window snapshots, hostile attribute values round-trip through
  the export, and ``/sloz`` is schema-checked (``check_sloz``) before
  it is served;
- every reserved GET endpoint on ``ServingServer`` is named in
  ``RESERVED_GET_PATHS``, routed through the one handler table, and
  documented in docs/api/serving.md (the endpoint-docs lint);
- greedy serving output stays token-exact with tracing ON (plain and
  speculative engines), and a traced ``LLMServer`` round-trip leaves a
  complete queued → admitted → prefill → decode → retired timeline.
"""

import json
import math
import os
import re
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from synapseml_tpu.telemetry import get_registry
from synapseml_tpu.telemetry.registry import (
    SERVING_TOKEN_LATENCY_BUCKETS, SERVING_TTFT_BUCKETS, Histogram)
from synapseml_tpu.telemetry.slo import (SloStore, WindowedCounter,
                                         WindowedHistogram, check_sloz)
from synapseml_tpu.telemetry.tracing import (RequestTraceStore,
                                             get_request_tracer,
                                             get_tracer, mint_trace_id)

pytestmark = pytest.mark.slo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bucket_width_at(bounds, value):
    """Width of the bucket holding ``value`` (the estimator's error
    bound); lower edge 0 before the first bound."""
    prev = 0.0
    for b in bounds:
        if value <= b:
            return b - prev
        prev = b
    return float("inf")


# ---------------------------------------------------------------------------
# Histogram.quantile (the registry satellite)
# ---------------------------------------------------------------------------

class TestHistogramQuantile:
    def test_pinned_against_exact_percentiles(self):
        """The acceptance pin: bucket-interpolated quantiles vs exact
        percentiles of a synthetic latency stream, within one bucket
        width at p50/p95/p99."""
        rng = np.random.default_rng(0)
        values = np.abs(rng.lognormal(mean=-4.0, sigma=1.2, size=5000))
        h = Histogram("q_pin_seconds", buckets=SERVING_TTFT_BUCKETS)
        for v in values:
            h.observe(float(v))
        for q in (0.50, 0.95, 0.99):
            exact = float(np.percentile(values, q * 100))
            est = h.quantile(q)
            width = _bucket_width_at(SERVING_TTFT_BUCKETS, exact)
            assert abs(est - exact) <= width, (q, est, exact, width)

    def test_exact_at_bucket_boundaries(self):
        h = Histogram("q_edge_seconds", buckets=(1.0, 2.0, 4.0))
        for _ in range(50):
            h.observe(1.0)
        for _ in range(50):
            h.observe(2.0)
        # 50% of mass sits exactly at bound 1.0: the cumulative count
        # reaches the p50 rank exactly there
        assert h.quantile(0.5) == pytest.approx(1.0)
        assert h.quantile(1.0) == pytest.approx(2.0)

    def test_empty_is_nan_and_inf_bucket_clamps(self):
        h = Histogram("q_nan_seconds", buckets=(1.0, 2.0))
        assert math.isnan(h.quantile(0.5))
        h.observe(100.0)                       # lands in +Inf
        assert h.quantile(0.99) == 2.0         # clamps to last bound

    def test_bucket_quantile_labels(self):
        h = Histogram("q_lab_seconds", buckets=(1.0, 2.0),
                      labelnames=("api",))
        h.observe(0.5, api="/a")
        assert h.quantile(0.5, api="/a") <= 1.0
        assert math.isnan(h.quantile(0.5, api="/b"))

    def test_serving_buckets_sub_ms_resolution(self):
        """The bucket-set satellite: the serving ladders resolve the
        regimes the defaults collapse — sub-ms decode steps and
        single-digit-ms TTFT."""
        assert min(SERVING_TOKEN_LATENCY_BUCKETS) < 0.0005
        assert sum(1 for b in SERVING_TOKEN_LATENCY_BUCKETS
                   if b < 0.005) >= 4
        assert min(SERVING_TTFT_BUCKETS) < 0.005
        assert max(SERVING_TTFT_BUCKETS) >= 30.0


# ---------------------------------------------------------------------------
# windowed digests
# ---------------------------------------------------------------------------

class TestWindowedDigests:
    def test_windowed_quantiles_within_one_bucket_width(self):
        rng = np.random.default_rng(1)
        values = np.abs(rng.lognormal(mean=-6.5, sigma=1.0, size=4000))
        w = WindowedHistogram(SERVING_TOKEN_LATENCY_BUCKETS,
                              window_s=60.0, slices=6)
        for i, v in enumerate(values):
            w.observe(float(v), now=100.0 + i * 0.01)  # spread over 40 s
        now = 100.0 + len(values) * 0.01
        for q in (0.50, 0.95, 0.99):
            exact = float(np.percentile(values, q * 100))
            est = w.quantile(q, now=now)
            width = _bucket_width_at(SERVING_TOKEN_LATENCY_BUCKETS, exact)
            assert abs(est - exact) <= width, (q, est, exact, width)

    def test_old_slices_roll_off(self):
        w = WindowedHistogram((1.0, 2.0), window_s=10.0, slices=5)
        w.observe(0.5, now=0.0)
        assert w.count(now=1.0) == 1
        w.observe(1.5, now=9.0)
        assert w.count(now=9.0) == 2
        # the slice holding t=0 leaves the window by t=12
        assert w.count(now=12.0) == 1
        assert w.count(now=25.0) == 0
        assert math.isnan(w.quantile(0.5, now=25.0))

    def test_mean_and_fraction_below(self):
        w = WindowedHistogram((0.05, 0.1, 0.2), window_s=60.0)
        for _ in range(80):
            w.observe(0.04, now=10.0)
        for _ in range(20):
            w.observe(0.15, now=10.0)
        assert w.mean(now=10.0) == pytest.approx(0.062)
        # threshold on a bucket bound: attainment is exact
        assert w.fraction_below(0.05, now=10.0) == pytest.approx(0.8)
        assert w.fraction_below(0.2, now=10.0) == pytest.approx(1.0)

    def test_windowed_counter_rates(self):
        c = WindowedCounter(window_s=10.0, slices=5)
        for i in range(20):
            c.inc(now=float(i % 8))
        assert c.count(now=8.0) == 20
        assert c.rate(now=8.0) == pytest.approx(2.0)
        assert c.count(now=30.0) == 0


# ---------------------------------------------------------------------------
# the SLO window + /sloz schema
# ---------------------------------------------------------------------------

class TestSloWindow:
    def test_attainment_and_burn_rate(self):
        store = SloStore()
        w = store.window("t-slo-plane")
        w.set_objective("ttft", 0.05, target=0.99)
        for _ in range(95):
            w.observe_ttft(0.04, now=5.0)
        for _ in range(5):
            w.observe_ttft(0.2, now=5.0)
        assert w.attainment("ttft", now=5.0) == pytest.approx(0.95)
        # (1 - 0.95) / (1 - 0.99) = 5x budget burn
        assert w.burn_rate("ttft", now=5.0) == pytest.approx(5.0)

    def test_shed_ratio_and_snapshot_schema(self):
        store = SloStore()
        w = store.window("t-slo-snap")
        w.set_objective("ttft", 0.25)
        w.set_objective("token_latency", 0.005)
        for _ in range(30):
            w.observe_ttft(0.01)
            w.observe_token_latency(0.001)
        w.observe_occupancy(0.75)
        w.count("admitted", 30)
        w.count("shed", 10)
        w.count("retired", 28)
        assert w.shed_ratio() == pytest.approx(0.25)
        snap = store.snapshot()
        check_sloz(snap)                          # raises on any hole
        plane = snap["planes"]["t-slo-snap"]
        assert plane["signals"]["ttft"]["count"] == 30
        assert plane["slo"]["ttft"]["attainment"] == pytest.approx(1.0)
        assert plane["rates"]["shed_ratio"] == pytest.approx(0.25)
        assert plane["occupancy"]["mean"] == pytest.approx(0.75)
        # and the snapshot is JSON-clean (no NaN leaves)
        json.loads(json.dumps(snap, allow_nan=False))

    def test_empty_window_snapshot_is_null_not_nan(self):
        store = SloStore()
        w = store.window("t-slo-empty")
        w.set_objective("ttft", 0.1)
        snap = store.snapshot()
        check_sloz(snap)
        plane = snap["planes"]["t-slo-empty"]
        assert plane["signals"]["ttft"]["p95_s"] is None
        assert plane["slo"]["ttft"]["attainment"] is None

    def test_snapshot_window_s_tracks_registered_windows(self):
        """The top-level window_s is the planes' COMMON window — a
        custom-window plane must not be misreported as the default,
        and mixed windows read null (per-plane blocks stay exact)."""
        store = SloStore()
        store.window("a", window_s=30.0)
        snap = store.snapshot()
        check_sloz(snap)
        assert snap["window_s"] == 30.0
        store.window("b", window_s=60.0)
        snap = store.snapshot()
        check_sloz(snap)
        assert snap["window_s"] is None
        assert snap["planes"]["a"]["window_s"] == 30.0
        assert snap["planes"]["b"]["window_s"] == 60.0

    def test_check_sloz_rejects_malformed(self):
        with pytest.raises(ValueError, match="missing key"):
            check_sloz({"generated_unix": 0.0, "window_s": 60.0})
        store = SloStore()
        store.window("x")
        snap = store.snapshot()
        snap["planes"]["x"]["signals"]["ttft"]["p95_s"] = "oops"
        with pytest.raises(ValueError, match="numeric or null"):
            check_sloz(snap)

    def test_export_gauges(self):
        store = SloStore()
        w = store.window("t-slo-gauge")
        w.set_objective("ttft", 0.25)
        for _ in range(10):
            w.observe_ttft(0.01)
        w.observe_occupancy(0.5)
        w.count("admitted", 10)
        w.export_gauges()
        reg = get_registry()
        assert reg.get("slo_attainment").value(
            plane="t-slo-gauge", signal="ttft") == pytest.approx(1.0)
        assert reg.get("slo_burn_rate").value(
            plane="t-slo-gauge", signal="ttft") == pytest.approx(0.0)
        assert reg.get("slo_window_occupancy").value(
            plane="t-slo-gauge") == pytest.approx(0.5)
        q = reg.get("slo_window_quantile_seconds").value(
            plane="t-slo-gauge", signal="ttft", quantile="p95")
        assert 0.0 < q <= 0.025


# ---------------------------------------------------------------------------
# the request-trace store
# ---------------------------------------------------------------------------

class TestRequestTraceStore:
    def test_deterministic_sampling(self):
        s = RequestTraceStore(sample_every=3)
        ids = [s.begin() for _ in range(9)]
        assert sum(1 for t in ids if t is not None) == 3
        assert s.sampled == 3

    def test_propagated_id_always_sampled(self):
        s = RequestTraceStore(sample_every=0)      # minting disabled
        assert s.begin() is None
        assert s.begin("upstream-id") == "upstream-id"
        assert s.get("upstream-id") is not None

    def test_bounded_traces_and_events(self):
        s = RequestTraceStore(max_traces=2, max_events=2)
        a, b, c = s.begin(), s.begin(), s.begin()
        assert s.get(a) is None                    # evicted oldest-first
        assert s.get(b) and s.get(c)
        for i in range(5):
            s.event(c, f"e{i}")
        tr = s.get(c)
        # the cap's last place is the terminal event's: one transition
        # is kept, four are counted
        assert [e["name"] for e in tr["events"]] == ["e0"]
        assert tr["dropped_events"] == 4
        assert s.dropped_events == 4
        s.event(c, "retired", tokens=7)
        assert [e["name"] for e in s.get(c)["events"]] == ["e0", "retired"]
        assert s.get(c)["events"][-1]["tokens"] == 7
        s.event(c, "retired")                  # a second one has no place
        assert len(s.get(c)["events"]) == 2 and s.dropped_events == 5

    def test_none_id_is_noop(self):
        s = RequestTraceStore()
        s.event(None, "x")
        s.finish(None, "retired")                  # never raises

    def test_finish_publishes_span_and_flight_event(self):
        from synapseml_tpu.telemetry.flight import get_flight
        s = RequestTraceStore()
        tid = s.begin(api="/t")
        s.event(tid, "queued")
        s.finish(tid, "retired", tokens=4)
        spans = [sp for sp in get_tracer().spans("serving.request")
                 if sp.trace_id == tid]
        assert len(spans) == 1
        assert spans[0].attrs["outcome"] == "retired"
        assert spans[0].attrs["tokens"] == 4
        flights = [e for e in get_flight().events()
                   if e.get("kind") == "request"
                   and e.get("trace_id") == tid]
        assert len(flights) == 1 and flights[0]["outcome"] == "retired"
        # double-finish is a no-op (cancel paths can race retirement)
        s.finish(tid, "error")
        assert s.get(tid)["outcome"] == "retired"

    def test_chrome_trace_export(self):
        s = RequestTraceStore()
        tid = s.begin(api="/t")
        s.event(tid, "queued", prompt_tokens=7)
        s.event(tid, "retired", tokens=3)
        s.finish(tid, "retired")
        ct = s.chrome_trace(tid)
        assert ct["traceEvents"][0]["ph"] == "X"
        names = [e["name"] for e in ct["traceEvents"][1:]]
        assert names == ["queued", "retired"]
        assert ct["traceEvents"][1]["args"]["prompt_tokens"] == 7
        assert s.chrome_trace("nope") is None

    def test_mint_trace_id_unique(self):
        assert mint_trace_id() != mint_trace_id()

    def test_chrome_trace_of_live_trace(self):
        """Exporting a trace that has NOT finished must work — a
        request stuck mid-decode is exactly the one an operator
        exports (regression: the copy used to drop the perf-counter
        base and the live branch raised KeyError, dropping the
        /tracez?id= connection)."""
        s = RequestTraceStore()
        tid = s.begin(api="/t")
        s.event(tid, "queued")
        ct = s.chrome_trace(tid)                  # live: no finish()
        assert ct["traceEvents"][0]["ph"] == "X"
        assert ct["traceEvents"][0]["dur"] >= 0
        assert ct["traceEvents"][0]["args"]["outcome"] is None

    def test_traces_limit_zero_returns_none(self):
        """``limit=0`` must bound to NOTHING, not slice ``[-0:]`` into
        the whole store."""
        s = RequestTraceStore()
        s.begin()
        assert s.traces(0) == []
        assert s.traces(-5) == []
        assert len(s.snapshot(0)["traces"]) == 0


# ---------------------------------------------------------------------------
# /tracez + /sloz endpoints (no jax: a bare ServingServer)
# ---------------------------------------------------------------------------

def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read()


class TestReservedObservabilityEndpoints:
    def test_tracez_hostile_attrs_round_trip(self):
        """The acceptance pin: attribute values carrying quotes,
        newlines, and backslashes survive the /tracez export byte-for-
        byte (JSON escaping, the exposition-escaping pin's sibling)."""
        from synapseml_tpu.serving.server import ServingServer
        hostile = 'hang at step 3 ("no heartbeat")\nkilled\\now'
        store = get_request_tracer()
        tid = store.begin(verdict=hostile)
        store.event(tid, "queued", note=hostile)
        store.finish(tid, "retired")
        srv = ServingServer()
        try:
            host, port = srv.address
            status, body = _get(f"http://{host}:{port}/tracez?limit=500")
            assert status == 200
            snap = json.loads(body)
            tr = [t for t in snap["traces"] if t["trace_id"] == tid][0]
            assert tr["attrs"]["verdict"] == hostile
            assert tr["events"][0]["note"] == hostile
            # per-request Chrome export round-trips them too
            status, body = _get(f"http://{host}:{port}/tracez?id={tid}")
            assert status == 200
            ct = json.loads(body)
            assert ct["traceEvents"][0]["args"]["verdict"] == hostile
            assert ct["traceEvents"][1]["args"]["note"] == hostile
            # unknown id: a clean 404, not a stack trace
            with pytest.raises(urllib.error.HTTPError) as exc:
                _get(f"http://{host}:{port}/tracez?id=missing")
            assert exc.value.code == 404
        finally:
            srv.close()

    def test_sloz_schema_checked_and_served(self):
        from synapseml_tpu.serving.server import ServingServer
        from synapseml_tpu.telemetry import get_slo_store
        w = get_slo_store().window("t-sloz-endpoint")
        w.set_objective("ttft", 0.25)
        for _ in range(5):
            w.observe_ttft(0.01)
        srv = ServingServer()
        try:
            host, port = srv.address
            status, body = _get(f"http://{host}:{port}/sloz")
            assert status == 200
            snap = json.loads(body)
            check_sloz(snap)
            assert "t-sloz-endpoint" in snap["planes"]
        finally:
            srv.close()

    def test_endpoints_served_while_draining(self):
        """Reserved observability paths answer BEFORE the draining
        shed — the moment you most need /tracez and /sloz is exactly
        when the server is shedding."""
        from synapseml_tpu.serving.server import ServingServer
        srv = ServingServer()
        try:
            srv.health.begin_drain()
            host, port = srv.address
            assert _get(f"http://{host}:{port}/tracez")[0] == 200
            assert _get(f"http://{host}:{port}/sloz")[0] == 200
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# the reserved-endpoint docs lint (tier-1 CI tooling)
# ---------------------------------------------------------------------------

class TestReservedEndpointDocsLint:
    SERVER_SRC = os.path.join(REPO, "synapseml_tpu", "serving", "server.py")
    SERVING_MD = os.path.join(REPO, "docs", "api", "serving.md")

    def test_handler_table_matches_reserved_tuple(self):
        """Every path in the dispatch handler table is declared in
        RESERVED_GET_PATHS and vice versa — one registration point."""
        from synapseml_tpu.serving.server import RESERVED_GET_PATHS
        src = open(self.SERVER_SRC, encoding="utf-8").read()
        m = re.search(r"def _reserved_handler.*?\.get\(bare\)", src, re.S)
        assert m, "_reserved_handler table not found"
        table = set(re.findall(r'"(/[a-z0-9_]+)":', m.group(0)))
        assert table == set(RESERVED_GET_PATHS), (
            f"handler table {sorted(table)} != RESERVED_GET_PATHS "
            f"{sorted(RESERVED_GET_PATHS)}")
        # no reserved path may be compared inline, bypassing the table
        stray = re.findall(r'bare\.rstrip\("/"\)\s*==\s*"(/[a-z0-9_]*)"',
                           src)
        assert not stray, f"reserved paths bypassing the table: {stray}"

    def test_every_reserved_endpoint_documented(self):
        """The lint the ISSUE asks for: every reserved GET endpoint
        registered on ServingServer is documented in
        docs/api/serving.md as `GET /path` — a future endpoint cannot
        land undocumented."""
        from synapseml_tpu.serving.server import RESERVED_GET_PATHS
        docs = open(self.SERVING_MD, encoding="utf-8").read()
        missing = [p for p in RESERVED_GET_PATHS
                   if f"`GET {p}`" not in docs]
        assert not missing, (
            f"reserved endpoints absent from docs/api/serving.md "
            f"(document as `GET <path>`): {missing}")


# ---------------------------------------------------------------------------
# token-exactness with tracing ON + the served timeline (jax)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.models.llm import LlamaConfig, LlamaModel
    cfg = LlamaConfig.tiny(num_layers=2, max_len=96, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 8), jnp.int32))
    return cfg, model, variables


def _prompts(cfg, n, length, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, (n, length)).astype(np.int32)


class TestTracedServingExactness:
    def test_engine_token_exact_with_trace_sink(self, tiny_model):
        """The acceptance pin: greedy output through a fully-traced
        engine is token-identical to the dense path, and the sink saw
        each slot's transitions and nothing a token: one ``decode`` at
        its first step, one ``retired`` with the totals of its stay."""
        from synapseml_tpu.models.llm import SlotEngine, generate
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 3, 7, seed=40)
        ref = generate(model, variables, ids, max_new_tokens=10)
        seen = []
        eng = SlotEngine(model, variables, n_slots=4, max_len=64,
                         trace_sink=lambda slot, name, **a:
                         seen.append((slot, name, a)))
        slots = {i: eng.admit(ids[i], 10).slot for i in range(3)}
        out = eng.run_to_completion()
        for i in range(3):
            np.testing.assert_array_equal(out[slots[i]], ref[i])
        assert len(seen) == 3 * 2           # nothing for the 27 slot-steps
        for slot in slots.values():
            decode, retired = [(n, a) for s, n, a in seen if s == slot]
            assert decode == ("decode", {"tokens": 1})
            # 9 decode steps a slot; the prefill gave the first token
            assert retired == ("retired", {"reason": "length", "tokens": 10,
                                           "steps": 9})

    def test_spec_engine_token_exact_with_trace_sink(self, tiny_model):
        """Speculative engine under tracing: output stays exactly
        greedy and the slot's ``retired`` carries the drafted and
        accepted totals of its verify steps."""
        from synapseml_tpu.models.llm import SlotEngine, generate
        cfg, model, variables = tiny_model
        rng = np.random.default_rng(41)
        base = rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
        prompt = np.concatenate([base, base, base])     # periodic text
        ref = generate(model, variables, prompt[None, :],
                       max_new_tokens=16)[0]
        seen = []
        eng = SlotEngine(model, variables, n_slots=2, max_len=96,
                         spec_draft_len=4,
                         trace_sink=lambda slot, name, **a:
                         seen.append((slot, name, a)))
        r = eng.admit(prompt, 16)
        eng.run_to_completion()
        np.testing.assert_array_equal(eng.generated_ids(r.slot), ref)
        assert [name for _, name, _ in seen] == ["decode", "retired"]
        first, total = seen[0][2], seen[1][2]
        assert first["tokens"] >= 1
        assert total["reason"] == "length" and total["tokens"] == 16
        assert total["steps"] == eng.steps_run
        assert total["verify_steps"] == eng.spec_steps <= total["steps"]
        assert (total["drafted"], total["accepted"]) \
            == (eng.spec_drafted, eng.spec_accepted)
        assert total["accepted"] <= total["drafted"]
        # every token is the prefill's, a step's own or an accepted draft
        # (a last span the budget cut may accept more than it commits)
        assert 16 <= 1 + total["steps"] + total["accepted"]

    def test_llmserver_timeline_and_propagated_id(self, tiny_model):
        """HTTP round-trip with tracing on: output token-exact, the
        propagated X-SML-Trace-Id is adopted + echoed, and /tracez
        serves the full lifecycle timeline."""
        from synapseml_tpu.models.llm import generate
        from synapseml_tpu.serving import LLMServer
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 1, 7, seed=42)
        ref = generate(model, variables, ids, max_new_tokens=6)[0]
        srv = LLMServer(model, variables, n_slots=2, max_len=64,
                        ttft_slo_s=30.0,
                        engine_kwargs={"name": "t-traced"})
        tid = mint_trace_id()
        try:
            req = urllib.request.Request(
                srv.url, data=json.dumps(
                    {"ids": [int(t) for t in ids[0]],
                     "max_new_tokens": 6}).encode(),
                method="POST",
                headers={"Content-Type": "application/json",
                         "X-SML-Trace-Id": tid})
            with urllib.request.urlopen(req, timeout=30) as r:
                assert r.status == 200
                assert r.headers["X-SML-Trace-Id"] == tid
                body = json.loads(r.read())
            assert body["ids"] == [int(t) for t in ref]
            host, port = srv.server.address
            status, raw = _get(f"http://{host}:{port}/tracez?limit=500")
            tr = [t for t in json.loads(raw)["traces"]
                  if t["trace_id"] == tid][0]
            assert tr["outcome"] == "retired"
            names = [e["name"] for e in tr["events"]]
            # the transitions, and nothing a token: the prefill emits
            # token 1, the first of five decode steps is the one event
            assert names == ["queued", "admitted", "prefill", "decode",
                             "retired"]
            assert tr["events"][-1]["tokens"] == 6
            assert tr["events"][-1]["steps"] == 5
            assert tr["attrs"]["steps"] == 5
            # the SLO plane saw the request
            status, raw = _get(f"http://{host}:{port}/sloz")
            snap = json.loads(raw)
            check_sloz(snap)
            plane = snap["planes"]["/generate"]
            assert plane["signals"]["ttft"]["count"] >= 1
            assert plane["slo"]["ttft"]["threshold_s"] == 30.0
        finally:
            srv.close()

    def test_shed_request_traced(self, tiny_model):
        """A shed request's timeline ends queued → shed, and the shed
        lands in the windowed rates."""
        import threading

        from synapseml_tpu.serving import LLMServer
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 2, 7, seed=43)
        srv = LLMServer(model, variables, n_slots=1, max_len=96,
                        ttft_slo_s=0.01,
                        engine_kwargs={"name": "t-traced-shed"})
        results = {}

        def long_call():
            req = urllib.request.Request(
                srv.url, data=json.dumps(
                    {"ids": [int(t) for t in ids[0]],
                     "max_new_tokens": 60}).encode(), method="POST")
            with urllib.request.urlopen(req, timeout=60) as r:
                results["long"] = r.status
        tid = mint_trace_id()
        try:
            t = threading.Thread(target=long_call)
            t.start()
            deadline = time.monotonic() + 10
            while (srv.engine.active_count == 0
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            req = urllib.request.Request(
                srv.url, data=json.dumps(
                    {"ids": [int(t) for t in ids[1]],
                     "max_new_tokens": 4}).encode(),
                method="POST", headers={"X-SML-Trace-Id": tid})
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(req, timeout=30)
            assert exc.value.code == 503
            assert exc.value.headers["X-SML-Trace-Id"] == tid
            tr = get_request_tracer().get(tid)
            assert tr["outcome"] == "shed"
            assert [e["name"] for e in tr["events"]] == ["queued", "shed"]
            from synapseml_tpu.telemetry import get_slo_store
            snap = get_slo_store().snapshot()
            assert snap["planes"]["/generate"]["rates"]["shed_per_s"] > 0
            t.join(timeout=60)
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# session-affinity counters (the router satellite)
# ---------------------------------------------------------------------------

_AFF_NAMES = iter(range(10_000))


class TestAffinityCounters:
    def _router(self, n=3, **kw):
        from synapseml_tpu.serving import ReplicaRouter
        table = [("127.0.0.1", 9100 + i) for i in range(n)]
        return ReplicaRouter(table, name=f"t-affc-{next(_AFF_NAMES)}",
                             **kw)

    def _val(self, router, outcome):
        return get_registry().get("serving_affinity_total").value(
            router=router.name, outcome=outcome)

    def test_hit_miss_repin_counted(self):
        from synapseml_tpu.serving.distributed import DEAD
        r = self._router()
        rank0 = r.route(session="conv-1").rank     # first route: miss
        assert self._val(r, "miss") == 1.0
        for _ in range(3):
            r.route(session="conv-1")              # pinned: hits
        assert self._val(r, "hit") == 3.0
        assert self._val(r, "repin") == 0.0
        with r._lock:
            r._status[rank0] = DEAD                # pinned replica dies
        r.route(session="conv-1")                  # falls back: repin
        assert self._val(r, "repin") == 1.0
        r.route(session="conv-1")                  # new pin holds: hit
        assert self._val(r, "hit") == 4.0

    def test_unpinned_traffic_not_counted(self):
        r = self._router()
        for _ in range(4):
            r.route()
        for outcome in ("hit", "miss", "repin"):
            assert self._val(r, outcome) == 0.0
