"""The readers whose source is ``program_span``, on spans and a reduced trace
made by hand; and the names by which the older readers find the program's
jitted entry points and kernels in a device trace."""

import importlib

import numpy as np
import pytest

from benchmark import harness, span_read

BENCH = harness.load_benchmark(harness.ROOT)
SPAN_METRICS = [m["name"] for m in BENCH["per_layer"]
                if m["source"] == "program_span"]
MS = 1_000_000                      # ns
W0, H0 = 5_000_000_000, 10.0        # the window's start on the two clocks


def reader(name):
    return importlib.import_module("benchmark.metrics." + name)


def reduced_trace(busy_ms=((10, 30), (50, 80)), window_ms=100):
    """One device, busy in two intervals of a 100 ms window, each one run of
    the decode program: idle 0-10, 30-50 and 80-100 ms."""
    busy = [(W0 + a * MS, W0 + b * MS) for a, b in busy_ms]
    return {"window_ns": (W0, W0 + window_ms * MS), "window_s": window_ms / 1e3,
            "busy_s": sum(b - a for a, b in busy) / 1e9, "host": [],
            "devices": [{"name": "/device:TPU:0", "busy": busy,
                         "busy_ns": float(sum(b - a for a, b in busy)),
                         "ops": {},
                         "modules": {"jit__decode_step_jit": {
                             "total_ns": float(sum(b - a for a, b in busy)),
                             "count": len(busy), "intervals": list(busy)}}}]}


FACTS = {"trace_host": (H0, H0 + 0.1), "t0": H0 - 1.0, "t1": H0 + 29.0}


@pytest.fixture
def tracer():
    from synapseml_tpu.telemetry import get_tracer
    t = get_tracer()
    t.reset()
    yield t
    t.reset()


def put(tracer, name, start_ms, end_ms, **attrs):
    """A finished span ``start_ms``..``end_ms`` after the window's start."""
    return tracer.record(name, (end_ms - start_ms) / 1e3,
                         start_ns=int(H0 * 1e9) + start_ms * MS, **attrs)


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_a_reader_without_spans_says_nothing(tracer, metric):
    given = dict(trace=reduced_trace(), facts=dict(FACTS), cell=None,
                 values={}, peak={}, work=None, chips=1)
    assert reader(metric).read(**given) is None


def test_there_are_nine_and_each_names_its_cell():
    assert len(SPAN_METRICS) == 9
    for m in BENCH["per_layer"]:
        if m["source"] == "program_span":
            assert len(m["workloads"]) == 1


@pytest.mark.parametrize("metric,span", [
    ("step_prepare_ms", "engine.step.prepare"),
    ("step_commit_ms", "engine.step.commit"),
    ("loop_emit_ms", "loop.emit")])
def test_a_mean_is_over_the_spans_of_the_traced_part(tracer, metric, span):
    put(tracer, span, 0, 10)
    put(tracer, span, 40, 44)
    put(tracer, span, 150, 250)          # after the traced part: left out
    put(tracer, "another.span", 0, 90)
    assert reader(metric).read(trace=reduced_trace(), facts=FACTS) \
        == pytest.approx((10 + 4) / 2)


def test_roundtrip_is_the_wait_less_the_programs_device_time(tracer):
    put(tracer, "engine.step.wait", 8, 31)        # 23 ms for 20 on the device
    put(tracer, "engine.step.wait", 47, 82)       # 35 ms for 30
    got = reader("step_roundtrip_ms").read(trace=reduced_trace(), facts=FACTS)
    assert got == pytest.approx((23 + 35) / 2 - (20 + 30) / 2)
    no_program = reduced_trace()
    no_program["devices"][0]["modules"] = {}
    assert reader("step_roundtrip_ms").read(trace=no_program,
                                            facts=FACTS) is None


def test_queue_wait_is_over_the_requests_admitted_in_the_window(tracer):
    waits = [0.001 * k for k in range(1, 21)]
    for k, w in enumerate(waits):
        put(tracer, "serving.request", 100 * k, 100 * k + 900, queue_wait_s=w)
    put(tracer, "serving.request", -5000, -4000, queue_wait_s=0.5)  # before
    put(tracer, "serving.request", 200, 300)          # shed: never admitted
    got = reader("queue_wait_p90_ms").read(facts=FACTS)
    assert got == pytest.approx(1e3 * float(np.percentile(waits, 90)))


def test_setup_parts_read_the_newest_fit_and_warmup(tracer):
    old = put(tracer, "gbdt.fit", -90000, -80000)
    put(tracer, "gbdt.fit.bin", -90000, -85000, parent_id=old.span_id)
    fit = put(tracer, "gbdt.fit", -50000, -1000)
    put(tracer, "gbdt.fit.bin", -50000, -47000, parent_id=fit.span_id)
    put(tracer, "gbdt.fit.bin", -46000, -39000, parent_id=fit.span_id)
    put(tracer, "gbdt.fit.upload", -39000, -37500, parent_id=fit.span_id)
    put(tracer, "llm.warmup", -60000, -20000)
    assert reader("setup_bin_s").read() == pytest.approx(3.0 + 7.0)
    assert reader("setup_upload_s").read() == pytest.approx(1.5)
    assert reader("setup_warmup_s").read() == pytest.approx(40.0)


def test_idle_is_attributed_where_a_span_covers_it(tracer, capsys):
    put(tracer, "loop.tick", 0, 100)
    put(tracer, "engine.step.prepare", 0, 10)         # the first gap
    put(tracer, "engine.step.wait", 10, 30)           # the device is busy
    put(tracer, "engine.step.commit", 30, 50)         # the second gap
    put(tracer, "serving.request", 0, 100)            # says nothing of the host
    read = reader("idle_attributed_share").read
    # the third gap (80-100 ms, 20 of the 50 idle) lies in the tick alone
    assert read(trace=reduced_trace(), facts=FACTS) == pytest.approx(60.0)
    by = span_read.idle_by_span(reduced_trace(), FACTS)
    assert by == pytest.approx({"engine.step.prepare": 0.010,
                                "engine.step.commit": 0.020,
                                "loop.tick": 0.020})
    assert "idle by span: engine.step.commit 0.020000 s" in capsys.readouterr().err
    put(tracer, "loop.emit", 80, 100)
    assert read(trace=reduced_trace(), facts=FACTS) == pytest.approx(100.0)


def test_the_clocks_meet_at_both_ends_of_the_window(tracer):
    """A profiler clock that runs 1% fast: the spans are stretched with it."""
    put(tracer, "engine.step.commit", 30, 50)
    trace = reduced_trace(busy_ms=((10.1, 30.3), (50.5, 80.8)), window_ms=101)
    (name, a, b), = span_read.on_trace_clock(
        span_read.spans("engine.step.commit"), trace, FACTS)
    assert (a - W0, b - W0) == pytest.approx((30.3 * MS, 50.5 * MS))


# -- the names the device-trace readers match -------------------------------------
# benchmark/metrics/*.py find programs and kernels in a trace by these names
# (decode_step_device_ms and step_roundtrip_ms: ``_decode_step_jit``;
# prefill_device_share: ``_prefill_slot_jit``; paged_decode_attention_roofline:
# ``paged_decode_attention``; hist_kernel_roofline and boost_iter_mfu:
# ``route_and_hist_pallas``, ``build_hist_nodes_pallas``).  A program is an
# event ``jit_<function>(<fingerprint>)``; a Pallas kernel's operation carries
# the name of the jitted function that makes the ``pallas_call``.  A rename
# zeroes a metric: rename here, in the readers and in PERF.md together.

def _tpu_text(jitted, *args, **kw):
    """The function lowered for the TPU (no chip, nothing compiled)."""
    return jitted.trace(*args, **kw).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


def test_the_serving_programs_are_named_as_the_readers_match_them():
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.models.llm import LlamaConfig, LlamaModel, slots
    from synapseml_tpu.models.llm.model import init_cache

    cfg = LlamaConfig.tiny(num_layers=1, max_len=32, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))
    cache = jax.eval_shape(lambda: init_cache(cfg, 2, 32))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    active = jax.ShapeDtypeStruct((2,), jnp.bool_)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    kw = dict(attention_backend="dense", paged_num_tiles=None, paged_tile=None)
    lowered = {
        "_decode_step_jit": slots._decode_step_jit.lower(
            model, variables, cache, i32(2), i32(2), active, key, 0.0, 0, 1.0,
            **kw),
        "_verify_step_jit": slots._verify_step_jit.lower(
            model, variables, cache, i32(2, 2), i32(2), active, **kw),
        "_prefill_slot_jit": slots._prefill_slot_jit.lower(
            model, variables, cache, i32(8), 3, 0, 0)}
    for name, low in lowered.items():
        assert f"module @jit_{name} " in low.as_text()


def test_the_paged_kernel_is_named_as_its_reader_matches_it():
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.models.llm.pallas_attn import paged_decode_attention

    q = jax.ShapeDtypeStruct((2, 4, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 256, 2, 128), jnp.bfloat16)
    spans = jax.ShapeDtypeStruct((2,), jnp.int32)
    text = _tpu_text(paged_decode_attention, q, k, k, spans, tile=128,
                     num_tiles=2)
    assert "module @jit_paged_decode_attention " in text
    assert "tpu_custom_call" in text
    assert '"jit(paged_decode_attention)/pallas_call"' in text


def test_the_histogram_kernels_are_named_as_their_readers_match_them():
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.models.gbdt.pallas_hist import (
        build_hist_nodes_pallas, route_and_hist_pallas)

    N, F, B, S = 2048, 9, 64, 16
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    vals = jax.ShapeDtypeStruct((N, 8), jnp.int8)
    scales = jax.ShapeDtypeStruct((2,), jnp.float32)
    text = _tpu_text(build_hist_nodes_pallas, i32(F, N), i32(N), vals, scales,
                     S, B)
    assert "module @jit_build_hist_nodes_pallas " in text
    assert '"jit(build_hist_nodes_pallas)/pallas_call"' in text
    text = _tpu_text(route_and_hist_pallas, i32(F, N), i32(N), i32(S),
                     i32(S, N), i32(S), i32(S), i32(S), i32(S), i32(S), i32(S),
                     vals, scales, S, B)
    assert "module @jit_route_and_hist_pallas " in text
    assert '"jit(route_and_hist_pallas)/pallas_call"' in text
