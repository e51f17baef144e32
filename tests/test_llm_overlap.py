"""The decode step in flight: ``SlotEngine`` hands the device step N+1 before
it reads step N's tokens.

Pinned here, on the dense and on the paged (Pallas interpreter) backend:

- greedy tokens equal a reference that shares none of the engine's code
  (``generate`` per prompt) through everything that can happen between a
  step's dispatch and its read: an admission into a freed slot, a
  retirement by length, an EOS, a ``cancel`` followed at once by an
  admission into the same slot, ``preempt``/``resume`` and ``reset()``;
- every ``step()`` call returns one whole step's events, the first call
  included, and nothing a slot waits on stays in flight afterwards;
- ``steps_overlapped``, ``llm_steps_overlapped_total`` and the
  ``engine.step`` span's ``overlapped`` count what happened;
- an engine with a drafter keeps each step's dispatch and read in one call.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from synapseml_tpu.models.llm import (LlamaConfig, LlamaModel, SlotEngine,
                                      generate)
from synapseml_tpu.models.llm.warmup import engine_jit_cache_size
from synapseml_tpu.telemetry import get_registry

BACKENDS = [pytest.param("dense", id="plain"),
            pytest.param("interpret", id="paged", marks=pytest.mark.pallas)]


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(num_layers=2, max_len=96, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 8), jnp.int32))
    return cfg, model, variables


def _prompt(cfg, length, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, length).astype(np.int32)


def _reference(model, variables, prompt, max_new, eos=None):
    """Greedy continuation by the dense-cache ``generate`` path, cut after
    the first ``eos``."""
    out = np.asarray(generate(model, variables, prompt[None, :],
                              max_new_tokens=max_new))[0]
    if eos is not None and eos in out:
        out = out[:list(out).index(eos) + 1]
    return [int(t) for t in out]


def _late_eos(tokens):
    """A token of a continuation that first appears at its third place or
    later: as the engine's EOS it stops that request in mid-stream."""
    return next(t for i, t in enumerate(tokens) if i >= 2
                and t not in tokens[:i])


class Drive:
    """What a serving loop keeps beside the engine: which request holds
    which slot, and the tokens each has been handed."""

    def __init__(self, eng):
        self.eng = eng
        self.by_slot = {}
        self.tokens = {}
        self.reasons = {}

    def admit(self, name, prompt, max_new):
        res = self.eng.admit(prompt, max_new)
        assert res is not None
        self.tokens[name] = [res.token]
        if res.finished:
            self.reasons[name] = res.reason
        else:
            self.by_slot[res.slot] = name
        return res.slot

    def drop(self, name):
        slot, = [s for s, n in self.by_slot.items() if n == name]
        del self.by_slot[slot]
        return slot

    def step(self):
        events = self.eng.step()
        slots = [ev.slot for ev in events]
        assert len(set(slots)) == len(slots)      # one step: one token a slot
        for ev in events:
            name = self.by_slot[ev.slot]          # never a slot nobody holds
            self.tokens[name].append(ev.token)
            if ev.finished:
                self.reasons[name] = ev.reason
                del self.by_slot[ev.slot]
        return events

    def run(self):
        while self.eng.active.any():
            assert self.step()


@pytest.mark.parametrize("backend", BACKENDS)
def test_every_call_returns_one_step_and_counts_it(tiny_model, backend):
    cfg, model, variables = tiny_model
    name = f"t-overlap-{backend}"
    eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                     attention_backend=backend, name=name)
    prompt = _prompt(cfg, 6, seed=1)
    d = Drive(eng)
    d.admit("a", prompt, 7)
    for k in range(6):
        events = d.step()
        assert len(events) == 1                   # the first call included
        assert eng.steps_run == k + 1
        assert int(eng._generated[0]) == k + 2    # the committed state
    assert d.tokens["a"] == _reference(model, variables, prompt, 7)
    assert d.reasons == {"a": "length"}
    assert eng.step() == [] and eng._flight is None
    # the first step follows no step; every later one was dispatched ahead
    assert eng.steps_overlapped == 5
    assert get_registry().get("llm_steps_overlapped_total").value(
        engine=name) == 5.0


@pytest.mark.parametrize("backend", BACKENDS)
def test_tokens_are_exact_through_what_happens_under_a_step(tiny_model,
                                                            backend):
    cfg, model, variables = tiny_model
    p = {k: _prompt(cfg, n, seed=20 + i) for i, (k, n) in enumerate(
        [("a", 6), ("b", 9), ("c", 5), ("d", 7), ("e", 6), ("f", 8),
         ("g", 5)])}
    # c stops on an EOS in mid-stream
    eos = _late_eos(_reference(model, variables, p["c"], 12))
    budget = {"a": 30, "b": 3, "c": 12, "d": 6, "e": 5, "f": 9, "g": 4}
    ref = {k: _reference(model, variables, p[k], budget[k], eos) for k in p}
    assert len(ref["c"]) < 12 and ref["c"][-1] == eos

    eng = SlotEngine(model, variables, n_slots=3, max_len=64, eos_id=eos,
                     attention_backend=backend, warmup="sync",
                     name=f"t-overlap-mix-{backend}")
    programs = engine_jit_cache_size()
    d = Drive(eng)
    for k in "abc":
        d.admit(k, p[k], budget[k])
    # b retires by length and c on its EOS, each with the next step in
    # flight; d goes into the first slot freed while that step runs
    while "b" not in d.reasons and "c" not in d.reasons:
        d.step()
    assert eng._flight is not None
    d.admit("d", p["d"], budget["d"])
    while len(d.reasons) < 2:
        d.step()
    # a is cancelled under a running step and its slot given to e at once:
    # whatever that step computed for a is never delivered
    assert eng._flight is not None and eng.free_slot_count == 1
    d.admit("f", p["f"], budget["f"])
    slot_a = d.drop("a")
    eng.cancel(slot_a)
    assert d.admit("e", p["e"], budget["e"]) == slot_a
    d.step()
    d.step()
    # f is preempted under a running step, and resumed later
    ticket = eng.preempt(d.drop("f"))
    assert ticket is not None and eng._flight is not None
    d.step()
    d.by_slot[eng.resume(ticket)] = "f"
    d.run()
    assert eng._flight is None                    # nothing left in flight
    assert d.reasons["b"] == "length" and d.reasons["c"] == "eos"
    for k in "bcdef":
        assert d.tokens[k] == ref[k], k
    assert d.tokens["a"] == ref["a"][:len(d.tokens["a"])]
    assert len(d.tokens["a"]) < len(ref["a"])
    # reset() with a step in flight drops it with everything else
    d.admit("a", p["a"], budget["a"])
    d.step()
    assert eng._flight is not None
    eng.reset()
    assert eng._flight is None and eng.step() == []
    d = Drive(eng)
    d.admit("g", p["g"], budget["g"])
    d.run()
    assert d.tokens["g"] == ref["g"]
    assert 0 < eng.steps_overlapped < eng.steps_run
    # one decode program still: none compiled since the warm-up
    assert engine_jit_cache_size() == programs


@pytest.mark.parametrize("backend", BACKENDS)
def test_an_eos_leaves_nothing_in_flight(tiny_model, backend):
    """The EOS is known a step late: the slot rode the next step, whose
    output is dropped; ``run_to_completion`` ends with no step pending and
    the next occupant of the slot is served exactly."""
    cfg, model, variables = tiny_model
    prompt, other = _prompt(cfg, 6, seed=5), _prompt(cfg, 7, seed=6)
    eos = _late_eos(_reference(model, variables, prompt, 10))
    eng = SlotEngine(model, variables, n_slots=1, max_len=64, eos_id=eos,
                     attention_backend=backend)
    r = eng.admit(prompt, 10)
    out = eng.run_to_completion()[r.slot]
    assert [int(t) for t in out] == _reference(model, variables, prompt, 10,
                                               eos)
    assert 2 < len(out) < 10 and out[-1] == eos
    assert eng._flight is None and not eng.active.any()
    # the step that ran the retired slot once more never counted
    assert eng.steps_run == len(out) - 1
    r = eng.admit(other, 5)
    out = eng.run_to_completion()[r.slot]
    assert [int(t) for t in out] == _reference(model, variables, other, 5,
                                               eos)


def test_a_cancel_between_calls_drops_the_step_in_flight(tiny_model):
    cfg, model, variables = tiny_model
    eng = SlotEngine(model, variables, n_slots=1, max_len=64)
    r = eng.admit(_prompt(cfg, 6, seed=8), 20)
    eng.step()
    assert eng._flight is not None
    before = eng.steps_run
    eng.cancel(r.slot)
    assert eng.step() == [] and eng._flight is None
    assert eng.steps_run == before
    assert int(eng.kv_len[r.slot]) == int(eng.lengths[r.slot]) - 1


def test_an_engine_with_a_drafter_reads_each_step_in_its_call(tiny_model):
    cfg, model, variables = tiny_model
    prompt = np.array([5, 6, 7, 5, 6, 7, 5, 6], np.int32)
    eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                     spec_draft_len=2)
    r = eng.admit(prompt, 12)
    other = eng.admit(_prompt(cfg, 6, seed=9), 9)
    while eng.active.any():
        assert eng.step()
        assert eng._flight is None
    assert eng.steps_overlapped == 0 and eng.steps_run > 0
    assert [int(t) for t in eng.generated_ids(r.slot)] \
        == _reference(model, variables, prompt, 12)
    assert [int(t) for t in eng.generated_ids(other.slot)] \
        == _reference(model, variables, _prompt(cfg, 6, seed=9), 9)


def test_the_loop_times_a_step_between_two_returns(tiny_model):
    """``_DecodeLoop`` hands ``_emit`` the time between two steps' returns:
    a call may return a step the device finished under the last tick."""
    import time

    from synapseml_tpu.serving.server import _DecodeLoop, _LoopAccount

    class Engine:
        n_slots, active_count, free_slot_count = 1, 1, 0

        def step(self):
            return []

    class Api:
        path, max_queue = "/t", 4

        def poll(self, n):
            return []

        def get_batch(self, n, timeout_s):
            return []

    loop = _DecodeLoop.__new__(_DecodeLoop)
    loop.engine, loop.api = Engine(), Api()
    loop._waiting, loop._parked, loop._by_slot = [], [], {}
    loop._stepped_at, loop.idle_timeout_s, seen = None, 0.0, []
    loop._acct = _LoopAccount()
    loop._admit_waiting = lambda sp: None
    loop._cancel_expired = loop._export_slo = lambda: None
    loop._emit = lambda events, dt: seen.append(dt)
    loop._tick()
    time.sleep(0.03)                  # the host's work between two steps
    loop._tick()
    assert seen[0] < 0.02 <= seen[1]
    loop.engine.active_count = 0      # an idle engine forgets the last one
    loop._tick()
    assert loop._stepped_at is None and len(seen) == 2
