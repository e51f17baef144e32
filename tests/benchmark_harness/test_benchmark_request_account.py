"""The five readers of the request's account and the loop's
(``serving.request``'s ``stalled_s``, ``decode_s``, ``gap_max_s``,
``slot_wait_s``; ``loop.account``), on spans made by hand: each against
numbers computed here, each silent on a program without its attribute, each
within the window's bounds.  None is declared in ``BENCHMARK.json`` yet
(PERF.md §7 pin (1)): a ``benchmark`` PR appends their entries."""

import importlib

import numpy as np
import pytest

MS = 1_000_000                      # ns
T0, T1 = 100.0, 130.0               # the window, monotonic seconds
FACTS = {"t0": T0, "t1": T1, "trace_host": (T0 + 1.0, T0 + 4.0)}
READERS = ("decode_stall_share", "token_gap_max_p95_ms", "slot_wait_p90_ms",
           "host_slack_ms", "loop_accounted_share")


def reader(name):
    return importlib.import_module("benchmark.metrics." + name)


@pytest.fixture
def tracer():
    from synapseml_tpu.telemetry import get_tracer
    t = get_tracer()
    t.reset()
    yield t
    t.reset()


def request(tracer, start_s, seconds, **attrs):
    """A ``serving.request`` span ``start_s`` after the window's start."""
    return tracer.record("serving.request", seconds,
                         start_ns=int((T0 + start_s) * 1e9), **attrs)


def account(tracer, start_s, seconds=1.0, **attrs):
    return tracer.record("loop.account", seconds,
                         start_ns=int((T0 + start_s) * 1e9), **attrs)


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_without_its_attribute_says_nothing(tracer, metric):
    assert reader(metric).read(facts=dict(FACTS)) is None
    # the parent's spans: a request with its wait and TTFT alone, and no
    # account of the loop
    request(tracer, 1.0, 2.0, queue_wait_s=0.01, ttft_s=0.05, tokens=9)
    tracer.record("loop.tick", 0.01, start_ns=int((T0 + 1) * 1e9))
    assert reader(metric).read(facts=dict(FACTS), trace=None, cell=None,
                               values={}, peak={}, work=None,
                               chips=1) is None


def test_stall_share_is_over_the_requests_that_finished_in_the_window(tracer):
    request(tracer, 1.0, 4.0, decode_s=3.0, stalled_s=1.5)
    request(tracer, 2.0, 10.0, decode_s=9.0, stalled_s=0.9)
    request(tracer, -8.0, 9.0, decode_s=8.0, stalled_s=0.0)   # began before
    request(tracer, -9.0, 5.0, decode_s=4.0, stalled_s=4.0)   # ended before
    request(tracer, 25.0, 6.0, decode_s=5.0, stalled_s=5.0)   # ends after
    request(tracer, 3.0, 1.0, queue_wait_s=0.2)               # shed
    got = reader("decode_stall_share").read(facts=FACTS)
    assert got == pytest.approx(100.0 * (1.5 + 0.9 + 0.0) / (3.0 + 9.0 + 8.0))
    # requests of one token decoded for no time: nothing to divide by
    tracer.reset()
    request(tracer, 1.0, 1.0, decode_s=0.0, stalled_s=0.0)
    assert reader("decode_stall_share").read(facts=FACTS) is None


def test_gap_is_a_p95_from_thirty_requests_on_and_a_maximum_under(
        tracer, capsys):
    gaps = [0.010 + 0.001 * k for k in range(29)]
    for k, g in enumerate(gaps):
        request(tracer, 0.5 * k, 1.0, gap_max_s=g, gap_max_cause="step")
    request(tracer, 40.0, 1.0, gap_max_s=9.0)                 # after
    request(tracer, 5.0, 1.0, decode_s=0.0)                   # one token
    assert reader("token_gap_max_p95_ms").read(facts=FACTS) \
        == pytest.approx(1e3 * max(gaps))
    assert "maximum of 29 requests" in capsys.readouterr().err
    request(tracer, 20.0, 1.0, gap_max_s=1.2, gap_max_cause="admit")
    gaps.append(1.2)
    assert reader("token_gap_max_p95_ms").read(facts=FACTS) \
        == pytest.approx(1e3 * float(np.percentile(gaps, 95)))
    assert "p95 of 30 requests" in capsys.readouterr().err


def test_slot_wait_is_over_the_requests_admitted_in_the_window(tracer):
    waits = [0.002 * k for k in range(1, 21)]
    for k, w in enumerate(waits):
        request(tracer, k, 2.0, queue_wait_s=w + 0.001, slot_wait_s=w,
                listener_wait_s=0.001)
    # enqueued before the window and admitted inside it: counted
    request(tracer, -0.5, 3.0, queue_wait_s=0.75, slot_wait_s=0.7)
    # admitted before the window; admitted after it
    request(tracer, -5.0, 8.0, queue_wait_s=0.5, slot_wait_s=0.4)
    request(tracer, 29.9, 3.0, queue_wait_s=0.2, slot_wait_s=0.2)
    request(tracer, 3.0, 1.0, queue_wait_s=0.2)       # the parent's span
    got = reader("slot_wait_p90_ms").read(facts=FACTS)
    assert got == pytest.approx(
        1e3 * float(np.percentile(waits + [0.7], 90)))


PHASES = {"pump_s": 0.01, "idle_s": 0.0, "admit_s": 0.25, "expire_s": 0.01,
          "step_prepare_s": 0.45, "step_wait_s": 0.2, "step_commit_s": 0.03,
          "step_other_s": 0.01, "emit_s": 0.04}


def test_slack_is_the_wait_a_step_over_every_second_of_the_window(tracer):
    account(tracer, 0.0, **dict(PHASES, steps=80, ticks=80))
    account(tracer, 1.0, **dict(PHASES, steps=90, ticks=95, step_wait_s=0.31))
    account(tracer, 2.0, **dict(PHASES, steps=0, ticks=50, step_wait_s=0.0))
    account(tracer, -1.0, **dict(PHASES, steps=70, step_wait_s=0.9))  # before
    account(tracer, 30.0, **dict(PHASES, steps=70, step_wait_s=0.9))  # after
    got = reader("host_slack_ms").read(facts=FACTS)
    assert got == pytest.approx(1e3 * (0.2 + 0.31) / (80 + 90))
    # the traced part does not bound it
    assert got == reader("host_slack_ms").read(
        facts=dict(FACTS, trace_host=(T0 + 1.5, T0 + 1.6)))
    tracer.reset()
    account(tracer, 0.0, **dict(PHASES, steps=0, step_wait_s=0.0))
    assert reader("host_slack_ms").read(facts=FACTS) is None


def test_accounted_share_is_the_phases_over_the_spans_own_time(tracer):
    assert sum(PHASES.values()) == pytest.approx(1.0)
    account(tracer, 0.0, 1.0, **dict(PHASES, steps=80, api="/generate"))
    account(tracer, 1.0, 1.25, **dict(PHASES, steps=80, idle_s=0.25))
    assert reader("loop_accounted_share").read(facts=FACTS) \
        == pytest.approx(100.0)
    # a second whose phases leave a tenth of it out
    account(tracer, 2.25, 1.0, **dict(PHASES, admit_s=0.15))
    account(tracer, 31.0, 1.0, steps=5, admit_s=0.1)          # after
    assert reader("loop_accounted_share").read(facts=FACTS) \
        == pytest.approx(100.0 * (1.0 + 1.25 + 0.9) / 3.25)


class _Engine:
    """Two slots, a 2 ms prefill, a 1 ms step that gives each a token."""

    n_slots, trace_sink = 2, None

    def __init__(self):
        self.left = {}
        self.phase_seconds = {"prepare": 0.0, "wait": 0.0, "commit": 0.0}

    active_count = property(lambda self: len(self.left))
    free_slot_count = property(lambda self: self.n_slots - len(self.left))
    min_remaining_tokens = lambda self: None              # noqa: E731

    def admit(self, ids, max_new):
        import time
        import types
        slot = min({0, 1} - set(self.left))
        time.sleep(0.002)
        self.left[slot] = max_new - 1
        return types.SimpleNamespace(slot=slot, token=1, bucket=8,
                                     finished=False, reason=None)

    def step(self):
        import time
        import types
        time.sleep(0.001)
        self.phase_seconds["wait"] += 0.001
        events = []
        for slot in sorted(self.left):
            self.left[slot] -= 1
            events.append(types.SimpleNamespace(
                slot=slot, token=1, finished=not self.left[slot],
                reason=None))
        self.left = {s: n for s, n in self.left.items() if n}
        return events


def test_the_program_records_what_the_readers_read(tracer):
    """A served window of the program itself, ticks run by hand: every
    reader finds its attribute under the name the program gives it."""
    import json
    import time

    from synapseml_tpu.serving.server import ServingRequest, _DecodeLoop
    from synapseml_tpu.telemetry import RequestTraceStore

    class Api:
        path, max_queue, reply_timeout_s = "/readers", 8, 60.0
        queue = []

        def poll(self, n):
            out, self.queue = self.queue[:n], self.queue[n:]
            return out

        get_batch = lambda self, n, timeout_s: self.poll(n)   # noqa: E731
        reply = lambda self, rid, rep: True                   # noqa: E731

    api, engine = Api(), _Engine()
    loop = _DecodeLoop(None, api, engine, request_tracer=RequestTraceStore(),
                       input_parser=lambda req: json.loads(req.body))
    loop._stop.set()
    loop._thread.join(timeout=5)
    assert not loop._thread.is_alive()
    t0 = time.monotonic()
    loop._acct.lap("idle_s")            # the account's next second starts
    loop._publish_account()             # inside the window
    for k in range(3):
        api.queue = api.queue + [ServingRequest(
            id=f"q{k}", method="POST", path="/", headers={},
            body=json.dumps({"ids": [1, 2], "max_new_tokens": 4}).encode(),
            enqueued_at=time.monotonic())]
        loop._tick()
    while engine.active_count or loop._waiting:
        loop._tick()
    loop._publish_account()
    facts = {"t0": t0, "t1": time.monotonic()}
    got = {name: reader(name).read(facts=facts) for name in READERS}
    assert all(v is not None for v in got.values()), got
    assert got["loop_accounted_share"] == pytest.approx(100.0, abs=0.01)
    # the second request's prefill stood between two of the first's
    # tokens, the third waited for a slot behind both
    assert 0.0 < got["decode_stall_share"] < 100.0
    assert got["token_gap_max_p95_ms"] >= 3.0
    assert got["slot_wait_p90_ms"] > 0.0
    assert got["host_slack_ms"] == pytest.approx(1.0)
