"""The reduction from a trace to busy, idle and per-operation time: its
interval arithmetic on hand-made cases, and the whole of it pinned on a small
trace recorded on the v5e (a few decode steps of ``mistral-7b.chat-closed32``,
cut from this benchmark's first traced chip run)."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "recorded", "chat-closed32.steps.xplane.pb")
PINNED = os.path.join(HERE, "recorded", "chat-closed32.steps.json")


def test_union_and_subtract():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert u == [(0, 3), (5, 8)] and tr.total(u) == 6
    assert tr.subtract([(0, 10)], u) == [(3, 5), (8, 10)]
    assert tr.subtract(u, [(0, 10)]) == []
    assert tr.subtract([(0, 4), (6, 9)], [(1, 2), (3, 7)]) == \
        [(0, 1), (2, 3), (7, 9)]


def test_self_time_takes_nested_events_out():
    ev = [(0.0, 100.0, "while"), (10.0, 30.0, "a"), (40.0, 90.0, "cond"),
          (50.0, 60.0, "b"), (120.0, 130.0, "c")]
    assert tr._self_times(ev) == [30.0, 20.0, 40.0, 10.0, 10.0]


def test_names():
    assert tr.op_name("%fusion.12 = bf16[8,4096]{1,0:T(8,128)} fusion(...)") == \
        ("fusion", "fusion bf16[8,4096]")
    assert tr.op_name("%paged_decode_attention.3 = bf16[8,1,8,128]{3,2,1,0} "
                      "custom-call(...)")[0] == "paged_decode_attention"
    assert tr.op_name("%while.141 = (s32[]{:T(128)}, f32[3]) while(...)")[0] == \
        "while"
    assert tr.module_name("jit__decode_step_jit(14260578375487711027)") == \
        "jit__decode_step_jit"


def test_idle_goes_to_the_innermost_annotation():
    reduced = {"window_ns": (0.0, 100.0),
               "devices": [{"busy": [(10.0, 20.0), (50.0, 60.0)],
                            "busy_ns": 20.0}],
               "host": [("outer", 0.0, 80.0), ("inner", 20.0, 40.0)]}
    gaps = dict(tr.idle_gaps_by_host(reduced))
    assert gaps["inner"] == pytest.approx(20e-9)
    assert gaps["outer"] == pytest.approx((10 + 10 + 20) * 1e-9)
    assert gaps["unspanned"] == pytest.approx(20e-9)


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce_trace(TRACE, ["engine.step", "engine.admit"])


def test_recorded_trace_busy_idle_and_operations(recorded):
    with open(PINNED) as f:
        pin = json.load(f)
    dev = tr.fullest(recorded)
    assert recorded["window_s"] == pytest.approx(pin["window_s"], rel=1e-9)
    assert recorded["busy_s"] == pytest.approx(pin["busy_s"], rel=1e-9)
    assert sum(r["self_ns"] for r in dev["ops"].values()) == \
        pytest.approx(dev["busy_ns"], rel=1e-9)
    s, n = tr.module_seconds(dev, "_decode_step_jit")
    assert (n, s) == (pin["decode_steps"], pytest.approx(pin["decode_s"], rel=1e-9))
    s, n = tr.op_seconds(dev, ["paged_decode_attention"], "self_ns")
    assert (n, s) == (pin["kernel_calls"], pytest.approx(pin["kernel_s"], rel=1e-9))
    top = tr.device_ops_top(recorded, 3)
    assert [t[0] for t in top] == pin["top3"]
    gaps = dict(tr.idle_gaps_by_host(recorded))
    assert gaps["engine.step"] == pytest.approx(pin["idle_engine_step_s"], rel=1e-9)
    assert sum(gaps.values()) == pytest.approx(
        recorded["window_s"] - dev["busy_ns"] / 1e9, rel=1e-9)
