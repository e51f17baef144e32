"""``step_overlap_share``: its reader on spans made by hand, on the spans a
tiny engine records, and its entry in ``BENCHMARK.json``.

The metric counts per traced step what ``SlotEngine`` counts in
``llm_steps_overlapped_total``; the count reaches the reader as the
``overlapped`` attribute of the program's ``engine.step`` span."""

import importlib

import pytest

from benchmark import harness

BENCH = harness.load_benchmark(harness.ROOT)
MS = 1_000_000                      # ns
H0 = 10.0                           # the traced part's start, host clock
FACTS = {"trace_host": (H0, H0 + 0.1), "t0": H0 - 1.0, "t1": H0 + 29.0}
#: the metric, and the span and attribute of the program it reads
READS = [("step_overlap_share", "engine.step", "overlapped")]


def reader(name):
    return importlib.import_module("benchmark.metrics." + name)


@pytest.fixture
def tracer():
    from synapseml_tpu.telemetry import get_tracer
    t = get_tracer()
    t.reset()
    yield t
    t.reset()


def put(tracer, name, start_ms, end_ms, **attrs):
    return tracer.record(name, (end_ms - start_ms) / 1e3,
                         start_ns=int(H0 * 1e9) + start_ms * MS, **attrs)


@pytest.mark.parametrize("metric,span,attr", READS)
def test_the_entry_names_the_scheduler_and_the_chat_cell(metric, span, attr):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == metric]
    assert entry == BENCH["per_layer"][-1]        # appended, nothing moved
    assert entry["layer"] == "slot scheduler (models/llm/slots.py SlotEngine)"
    assert entry["moves"] == "tokens_per_s" and entry["better"] == "higher"
    assert entry["unit"] == "%"
    assert entry["workloads"] == ["mistral-7b.chat-closed32"]
    cell = harness.Cell(BENCH, "mistral-7b.chat-closed32", harness.ROOT)
    assert metric in [m["name"] for m in cell.per_layer()]
    assert cell.reader(metric).read.__module__


@pytest.mark.parametrize("metric,span,attr", READS)
def test_the_share_is_over_the_steps_of_the_traced_part(tracer, metric, span,
                                                        attr):
    put(tracer, span, 0, 10, **{attr: False})     # after an empty engine
    put(tracer, span, 20, 30, **{attr: True})
    put(tracer, span, 40, 50, **{attr: True})
    put(tracer, span, 60, 70, **{attr: True})
    put(tracer, span, 150, 160, **{attr: False})  # after the traced part
    put(tracer, span, -30, -20, **{attr: False})  # before it
    put(tracer, "engine.step.wait", 20, 30, **{attr: False})
    assert reader(metric).read(trace=None, facts=FACTS) \
        == pytest.approx(100.0 * 3 / 4)


@pytest.mark.parametrize("metric,span,attr", READS)
def test_a_program_that_counts_no_overlap_gives_nothing(tracer, metric, span,
                                                        attr):
    given = dict(trace=None, facts=dict(FACTS), cell=None, values={},
                 peak={}, work=None, chips=1)
    assert reader(metric).read(**given) is None   # no span at all
    put(tracer, span, 0, 10, slots=4, tokens=4)   # the parent's spans
    put(tracer, span, 20, 30, slots=4, tokens=4)
    assert reader(metric).read(**given) is None
    put(tracer, span, 40, 50, **{attr: True})     # one step that says
    assert reader(metric).read(**given) == pytest.approx(100.0)


@pytest.mark.parametrize("metric,span,attr", READS)
def test_the_engine_writes_what_the_reader_reads(tracer, tmp_path, metric,
                                                 span, attr):
    """A tiny engine under a profiler session: the first step follows no
    step, the others were dispatched ahead; the reader's share is the
    engine's own count over its steps."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileOptions

    from synapseml_tpu.models.llm import LlamaConfig, LlamaModel, SlotEngine

    cfg = LlamaConfig.tiny(num_layers=1, max_len=32, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    eng = SlotEngine(model, variables, n_slots=2, max_len=32,
                     attention_backend="dense")
    eng.admit(np.arange(1, 6, dtype=np.int32), 6)
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run_to_completion()
    finally:
        jax.profiler.stop_trace()
    steps = tracer.spans(span)
    assert len(steps) == eng.steps_run == 5
    assert [s.attrs[attr] for s in steps] == [False, True, True, True, True]
    assert eng.steps_overlapped == 4
    assert reader(metric).read(trace=None, facts={"trace_host": None}) \
        == pytest.approx(100.0 * eng.steps_overlapped / eng.steps_run)
