"""Multi-tenant QoS scheduler-core tests (ISSUE 18).

The contract under test (``synapseml_tpu/serving/qos.py`` — pure
bookkeeping, deliberately jax-free, driven here on an injectable fake
clock with no engine at all):

- deficit accounting: refill by ``quantum x weight`` per round, charge
  by COMMITTED tokens, clamped to ``±burst_quanta`` quanta so neither
  banked credit nor dug holes are unbounded;
- DRR admission order: weighted interleave within a priority class,
  FIFO within a tenant, single-tenant queues come back in arrival
  order (the old FIFO is the degenerate case);
- priority classes: strictly descending tiers; preemption verdicts
  name the lowest-priority longest-remaining victim, only for demand
  STRICTLY above the victim's class, rate-limited by the anti-thrash
  cooldown;
- shed budgets: the PR 2 token bucket on the injectable clock — an
  over-budget tenant sheds with a computed Retry-After and recovers
  exactly when the bucket refills;
- spec-decode token-weighting: charging multi-token commit spans (what
  a speculative engine emits) moves the share/deficit by TOKENS, not
  requests;
- ``jain_fairness`` edge cases, and the module stays jax-free.
"""

import types

import pytest

from synapseml_tpu.serving.qos import (DEFAULT_PRIORITY, DEFAULT_TENANT,
                                       QosScheduler, TenantPolicy,
                                       jain_fairness)

pytestmark = pytest.mark.qos


def _item(tenant, max_new=8, priority=None, remaining=0, tag=None):
    return types.SimpleNamespace(tenant=tenant, max_new=max_new,
                                 priority=priority, remaining=remaining,
                                 tag=tag)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------

def test_policy_validation_rejects_nonpositive_weight_and_rate():
    with pytest.raises(ValueError):
        TenantPolicy(weight=0.0)
    with pytest.raises(ValueError):
        TenantPolicy(weight=-1.0)
    with pytest.raises(ValueError):
        TenantPolicy(rate_tokens_per_s=0.0)
    TenantPolicy(weight=2.0, rate_tokens_per_s=10.0)  # valid


def test_default_policy_and_priority_resolution():
    q = QosScheduler(policies={"gold": TenantPolicy(priority=5)})
    assert q.policy("unknown") is q.default_policy
    assert q.priority_of(_item("unknown")) == DEFAULT_PRIORITY
    # tenant policy supplies the class when the item declares none
    assert q.priority_of(_item("gold")) == 5
    # an item-level priority overrides its tenant's policy
    assert q.priority_of(_item("gold", priority=2)) == 2


def test_set_policy_rearms_budget_from_new_rate():
    clk = FakeClock()
    q = QosScheduler(policies={"a": TenantPolicy(rate_tokens_per_s=1.0,
                                                 burst_tokens=1.0)},
                     clock=clk)
    admit, _ = q.shed_verdict("a", 1.0)
    assert admit
    admit, _ = q.shed_verdict("a", 1.0)
    assert not admit
    # raising the rate re-arms the bucket at the new capacity
    q.set_policy("a", TenantPolicy(rate_tokens_per_s=100.0,
                                   burst_tokens=50.0))
    admit, _ = q.shed_verdict("a", 40.0)
    assert admit


# ---------------------------------------------------------------------------
# deficit accounting
# ---------------------------------------------------------------------------

def test_refill_tracks_committed_tokens_by_weight_share():
    """Virtual-time DRR: a round refills each waiting tenant by its
    weight share of the tokens committed since the LAST round — total
    refill equals total charge, so deficits measure distance from the
    fair share.  An idle loop ticking rounds with no commits refills
    nothing (the old quantum-per-round refill would saturate every
    tenant at the burst cap between token commits)."""
    q = QosScheduler(policies={"a": TenantPolicy(weight=3.0),
                               "b": TenantPolicy(weight=1.0)},
                     quantum_tokens=10.0, burst_quanta=8.0,
                     clock=FakeClock())
    both = [_item("a"), _item("b")]
    for _ in range(50):                # idle rounds: no commits
        q.admission_order(both)
    assert q.deficit("a") == 0.0
    assert q.deficit("b") == 0.0
    q.charge("a", 12)                  # 12 committed tokens, all by a
    q.admission_order(both)            # refill: a += 9, b += 3
    assert q.deficit("a") == pytest.approx(9.0 - 12.0)
    assert q.deficit("b") == pytest.approx(3.0)
    assert q.committed("a") == 12


def test_deficit_clamped_to_burst_cap_both_directions():
    q = QosScheduler(quantum_tokens=10.0, burst_quanta=2.0,
                     clock=FakeClock())
    cap = 10.0 * 1.0 * 2.0
    # a starved waiting tenant cannot bank unbounded credit while a
    # neighbor commits a flood of tokens
    q.charge("flood", 10_000)
    q.admission_order([_item("a")])
    assert q.deficit("a") == pytest.approx(cap)
    # and a flooding tenant cannot dig an unbounded hole
    assert q.deficit("flood") == pytest.approx(-cap)


def test_charge_accumulates_committed_and_share():
    q = QosScheduler(clock=FakeClock())
    q.charge("a", 30)
    q.charge("b", 10)
    share = q.committed_share()
    assert share["a"] == pytest.approx(0.75)
    assert share["b"] == pytest.approx(0.25)
    q.reset()
    assert q.committed("a") == 0
    assert q.committed_share() == {}


# ---------------------------------------------------------------------------
# DRR admission order
# ---------------------------------------------------------------------------

def test_single_tenant_queue_is_fifo():
    q = QosScheduler(clock=FakeClock())
    items = [_item(DEFAULT_TENANT, tag=i) for i in range(6)]
    assert [it.tag for it in q.admission_order(items)] == list(range(6))


def test_weighted_interleave_within_one_class():
    q = QosScheduler(policies={"a": TenantPolicy(weight=3.0),
                               "b": TenantPolicy(weight=1.0)},
                     quantum_tokens=8.0, clock=FakeClock())
    items = [_item(t, max_new=8, tag=f"{t}{i}")
             for t in ("a", "b") for i in range(4)]
    order = q.admission_order(items)
    tenants = [it.tenant for it in order]
    # the 3:1 tenant lands 3 of the first 4 picks; neither tenant sweeps
    assert tenants[:4].count("a") == 3
    assert set(tenants[:2]) == {"a", "b"} or tenants[:3].count("a") == 3
    # FIFO within each tenant
    assert [it.tag for it in order if it.tenant == "a"] == \
        ["a0", "a1", "a2", "a3"]
    assert [it.tag for it in order if it.tenant == "b"] == \
        ["b0", "b1", "b2", "b3"]


def test_saturated_weighted_pair_converges_to_weight_shares():
    """A 3:1 weight pair whose backlogs never empty: one slot frees a
    round, the head of the round's order takes it and is charged its
    committed tokens.  The committed split converges to 0.75/0.25 and
    the weight-normalized Jain index to 1."""
    q = QosScheduler(policies={"heavy": TenantPolicy(weight=3.0),
                               "light": TenantPolicy(weight=1.0)},
                     quantum_tokens=8.0, clock=FakeClock())
    backlog = [_item(t, max_new=8) for t in ("heavy", "light")
               for _ in range(3)]
    for _ in range(400):
        pick = q.admission_order(backlog)[0]
        backlog.remove(pick)
        q.charge(pick.tenant, pick.max_new)
        backlog.append(_item(pick.tenant, max_new=8))
    share = q.committed_share()
    assert share["heavy"] == pytest.approx(0.75, abs=0.01)
    assert share["light"] == pytest.approx(0.25, abs=0.01)
    assert jain_fairness([share["heavy"] / 3.0, share["light"] / 1.0]) \
        == pytest.approx(1.0, abs=1e-3)


def test_flooding_tenant_cannot_sweep_a_round():
    q = QosScheduler(quantum_tokens=8.0, clock=FakeClock())
    flood = [_item("flood", max_new=8, tag=f"f{i}") for i in range(20)]
    victim = [_item("victim", max_new=8, tag="v0")]
    order = q.admission_order(flood + victim)
    # equal weights: the victim's single request lands in the first two
    assert "v0" in [it.tag for it in order[:2]]


def test_priority_classes_strictly_descending():
    q = QosScheduler(clock=FakeClock())
    lo = [_item("bulk", priority=0, tag=f"lo{i}") for i in range(3)]
    hi = [_item("gold", priority=5, tag=f"hi{i}") for i in range(2)]
    order = q.admission_order(lo + hi)
    assert [it.tag for it in order] == ["hi0", "hi1", "lo0", "lo1", "lo2"]


def test_depleted_deficit_defers_tenant_next_round():
    q = QosScheduler(quantum_tokens=8.0, burst_quanta=8.0,
                     clock=FakeClock())
    # "hog" committed a pile of tokens; "quiet" committed none
    q.charge("hog", 64)
    order = q.admission_order([_item("hog", tag="h"),
                               _item("quiet", tag="q")])
    assert [it.tag for it in order] == ["q", "h"]


def test_custom_cost_function_drives_the_scratch_debit():
    q = QosScheduler(quantum_tokens=4.0, clock=FakeClock())
    items = [_item("a", max_new=100, tag="a0"), _item("a", tag="a1"),
             _item("b", max_new=1, tag="b0"), _item("b", tag="b1")]
    # cost=1 per item: pure round-robin regardless of max_new
    order = q.admission_order(items, cost=lambda it: 1.0)
    assert [it.tenant for it in order[:2]] in (["a", "b"], ["b", "a"])


# ---------------------------------------------------------------------------
# spec-decode token-weighting
# ---------------------------------------------------------------------------

def test_spec_decode_commit_spans_charge_tokens_not_requests():
    """A speculative engine commits multi-token spans per step event.
    Equal REQUEST counts must still skew share/deficit by TOKENS."""
    q = QosScheduler(quantum_tokens=8.0, burst_quanta=8.0,
                     clock=FakeClock())
    for _ in range(10):          # 10 step events each
        q.charge("spec", 4)      # 4-token accepted spans
        q.charge("plain", 1)     # one token at a time
    assert q.committed("spec") == 40
    assert q.committed("plain") == 10
    assert q.committed_share()["spec"] == pytest.approx(0.8)
    # the span tenant dug the deeper hole -> the plain tenant goes first
    order = q.admission_order([_item("spec", tag="s"),
                               _item("plain", tag="p")])
    assert [it.tag for it in order] == ["p", "s"]


# ---------------------------------------------------------------------------
# shed budgets
# ---------------------------------------------------------------------------

def test_budget_shed_and_retry_after_math_on_fake_clock():
    clk = FakeClock()
    q = QosScheduler(policies={"a": TenantPolicy(rate_tokens_per_s=10.0,
                                                 burst_tokens=20.0)},
                     clock=clk)
    admit, ra = q.shed_verdict("a", 20.0)      # drains the bucket
    assert admit and ra == 0.0
    admit, ra = q.shed_verdict("a", 10.0)
    assert not admit
    # empty bucket, want 10 tokens at 10 tok/s -> ~1s to refill
    assert ra == pytest.approx(1.0, abs=1e-6)
    assert q.budget_sheds == {"a": 1}
    # advancing the clock past Retry-After admits again
    clk.advance(1.0)
    admit, _ = q.shed_verdict("a", 10.0)
    assert admit


def test_oversized_request_retry_after_clamped_to_capacity():
    clk = FakeClock()
    q = QosScheduler(policies={"a": TenantPolicy(rate_tokens_per_s=10.0,
                                                 burst_tokens=5.0)},
                     clock=clk)
    assert q.shed_verdict("a", 5.0)[0]          # drain the bucket
    admit, ra = q.shed_verdict("a", 1000.0)
    assert not admit
    # Retry-After waits for a FULL bucket, not an impossible 100s
    assert 0.0 < ra <= 5.0 / 10.0 + 1e-6
    # and the hint is HONEST: waiting it out really does admit —
    # cost > capacity charges the capacity, not the impossible cost
    clk.advance(ra)
    assert q.shed_verdict("a", 1000.0)[0]


def test_oversized_request_admits_on_a_full_bucket():
    """cost > burst capacity must not be a permanent 429: a full
    bucket admits the oversized request (charged the whole capacity,
    draining to empty) so it is throttled like everything else."""
    clk = FakeClock()
    q = QosScheduler(policies={"a": TenantPolicy(rate_tokens_per_s=10.0,
                                                 burst_tokens=5.0)},
                     clock=clk)
    admit, ra = q.shed_verdict("a", 1000.0)     # fresh bucket: full
    assert admit and ra == 0.0
    assert q.shed_verdict("a", 1.0)[0] is False  # it really drained
    assert q.budget_sheds == {"a": 1}


def test_unlimited_tenant_never_sheds():
    q = QosScheduler(clock=FakeClock())
    for _ in range(100):
        admit, ra = q.shed_verdict(DEFAULT_TENANT, 1e6)
        assert admit and ra == 0.0
    assert q.budget_sheds == {}


def test_budget_isolation_one_tenant_shed_other_untouched():
    clk = FakeClock()
    q = QosScheduler(policies={"limited": TenantPolicy(
        rate_tokens_per_s=1.0, burst_tokens=1.0)}, clock=clk)
    assert q.shed_verdict("limited", 1.0)[0]
    assert not q.shed_verdict("limited", 1.0)[0]
    for _ in range(10):
        assert q.shed_verdict("other", 100.0)[0]
    assert q.budget_sheds == {"limited": 1}


# ---------------------------------------------------------------------------
# preemption
# ---------------------------------------------------------------------------

def test_preemption_victim_lowest_priority_then_longest_remaining():
    clk = FakeClock()
    q = QosScheduler(clock=clk, preempt_min_interval_s=0.25)
    active = [_item("a", priority=2, remaining=50, tag="p2"),
              _item("b", priority=0, remaining=10, tag="short"),
              _item("b", priority=0, remaining=90, tag="long"),
              _item("c", priority=1, remaining=99, tag="p1")]
    v = q.preemption_victim(3, active)
    assert v.tag == "long"        # lowest class, most tokens left
    # the verdict alone counts nothing — only the caller's confirm
    # (after the engine actually issued a ticket) does
    assert q.preemptions == 0
    q.commit_preemption()
    assert q.preemptions == 1


def test_preemption_requires_strictly_higher_demand():
    q = QosScheduler(clock=FakeClock())
    active = [_item("a", priority=2, remaining=10)]
    assert q.preemption_victim(2, active) is None    # equal class: no
    assert q.preemption_victim(1, active) is None    # lower class: no
    assert q.preemptions == 0


def test_preemption_cooldown_rate_limits_verdicts():
    clk = FakeClock()
    q = QosScheduler(clock=clk, preempt_min_interval_s=0.25)
    active = [_item("a", priority=0, remaining=10, tag="v1"),
              _item("a", priority=0, remaining=20, tag="v2")]
    assert q.preemption_victim(5, active) is not None
    q.commit_preemption()
    # inside the cooldown a flapping queue gets no second verdict
    clk.advance(0.1)
    assert q.preemption_victim(5, active) is None
    clk.advance(0.2)
    assert q.preemption_victim(5, active) is not None
    q.commit_preemption()
    assert q.preemptions == 2


def test_declined_verdict_burns_neither_counter_nor_cooldown():
    """``engine.preempt`` returning None abandons the eviction — the
    uncommitted verdict must not count as a preemption or delay the
    NEXT (legitimate) one by the anti-thrash interval."""
    clk = FakeClock()
    q = QosScheduler(clock=clk, preempt_min_interval_s=0.25)
    active = [_item("a", priority=0, remaining=10)]
    assert q.preemption_victim(5, active) is not None
    # ...the engine declined: no commit_preemption() call.  A retry on
    # the very next tick is allowed immediately, not 0.25s later.
    assert q.preemption_victim(5, active) is not None
    assert q.preemptions == 0
    q.commit_preemption()
    assert q.preemptions == 1
    assert q.preemption_victim(5, active) is None   # NOW it cools down


def test_pressure_snapshot_attributes_the_verdict():
    q = QosScheduler(clock=FakeClock())
    q.charge("bulk", 12)
    waiting = [_item("gold", priority=5), _item("gold", priority=5),
               _item("bulk", priority=0)]
    snap = q.pressure_snapshot(waiting, free_slots=0)
    assert snap["free_slots"] == 0
    assert snap["waiting"] == 3
    assert snap["waiting_by_priority"] == {"0": 1, "5": 2}
    assert snap["deficits"]["bulk"] == pytest.approx(-12.0)


# ---------------------------------------------------------------------------
# fairness index + hygiene
# ---------------------------------------------------------------------------

def test_jain_fairness_index():
    assert jain_fairness([]) == 1.0
    assert jain_fairness([0.0, 0.0]) == 1.0
    assert jain_fairness([1.0, 1.0, 1.0, 1.0]) == pytest.approx(1.0)
    assert jain_fairness([1.0, 0.0]) == pytest.approx(0.5)
    assert jain_fairness([1.0, 0.0, 0.0, 0.0]) == pytest.approx(0.25)
    assert jain_fairness([3.0, 1.0]) == pytest.approx(16.0 / 20.0)


def test_scheduler_core_is_jax_free():
    """The QoS policy core must import (and run) without jax — the
    whole point of the injectable clock is engine-free testing."""
    import synapseml_tpu.serving.qos as qosmod
    src = open(qosmod.__file__).read()
    assert "import jax" not in src
    import synapseml_tpu.serving.server as srvmod
    assert "import jax" not in open(srvmod.__file__).read()


# ---------------------------------------------------------------------------
# decode-loop policy plumbing (fake engine + fake api — still jax-free):
# the overload/failure contracts the scheduler core cannot see on its
# own: bounded pump backpressure, the dynamic-tenant cardinality cap,
# reply-window expiry of queued requests, and engine-failure
# notification of PARKED (preempted) sequences.
# ---------------------------------------------------------------------------

import json as _json
import time as _time
import uuid as _uuid

from synapseml_tpu.serving.server import (ServingRequest, _DecodeLoop,
                                          _DecodeSeq)


class _FakeApi:
    """Duck-typed ApiHandle: records pull sizes, captures replies."""

    def __init__(self, max_queue=8, reply_timeout_s=30.0):
        self.path = f"/qos-fake-{_uuid.uuid4().hex[:8]}"
        self.max_queue = max_queue
        self.reply_timeout_s = reply_timeout_s
        self.queue = []
        self.replies = {}
        self.poll_rooms = []

    def poll(self, n):
        self.poll_rooms.append(int(n))
        out, self.queue = self.queue[:int(n)], self.queue[int(n):]
        return out

    def get_batch(self, n, timeout_s):
        return self.poll(n)

    def reply(self, rid, rep):
        self.replies[rid] = rep
        return True


class _FakeEngine:
    """Duck-typed engine: slots bookkeeping only, no decoding."""

    def __init__(self, n_slots=2):
        self.n_slots = n_slots
        self.slots = {}
        self._next = 0

    @property
    def active_count(self):
        return len(self.slots)

    @property
    def free_slot_count(self):
        return self.n_slots - len(self.slots)

    def admit(self, ids, max_new):
        if self.free_slot_count == 0:
            return None
        slot, self._next = self._next, self._next + 1
        self.slots[slot] = (list(ids), int(max_new))
        import types as _types
        return _types.SimpleNamespace(slot=slot, token=1, finished=False,
                                      reason=None)

    def step(self):
        return []

    def cancel(self, slot):
        self.slots.pop(slot, None)

    def min_remaining_tokens(self):
        return None


def _make_loop(api=None, engine=None, **kw):
    """A _DecodeLoop driven synchronously: the background thread is
    stopped before any request exists, then ticks run by hand."""
    api = api or _FakeApi()
    engine = engine or _FakeEngine()
    loop = _DecodeLoop(None, api, engine,
                       input_parser=lambda req: _json.loads(req.body),
                       **kw)
    loop._stop.set()
    loop._thread.join(timeout=5)
    api.poll_rooms.clear()      # drop the idle spins before the join
    return loop, api, engine


def _req(payload, tenant="default", rid=None):
    return ServingRequest(id=rid or _uuid.uuid4().hex, method="POST",
                          path="/", headers={},
                          body=_json.dumps(payload).encode(),
                          enqueued_at=_time.monotonic(), tenant=tenant)


def _seq(req, max_new=4):
    return _DecodeSeq(req, [1, 2, 3], max_new, False)


def test_pump_stops_pulling_once_the_backlog_reaches_the_cap():
    """room = cap - (waiting + parked): a full backlog pulls NOTHING
    (so the api queue fills and enqueue-time 503 backpressure fires)
    instead of draining the queue into an unbounded waiting list."""
    api = _FakeApi(max_queue=6)
    loop, api, engine = _make_loop(api=api, engine=_FakeEngine(n_slots=1))
    cap = max(2 * engine.n_slots, api.max_queue)          # = 6
    loop._waiting = [_seq(_req({"ids": [1]})) for _ in range(cap)]
    api.queue = [_req({"ids": [1]}) for _ in range(10)]
    loop._pump_queue()
    assert api.poll_rooms == []          # no room: no pull at all
    assert len(loop._waiting) == cap
    assert len(api.queue) == 10          # left queued -> queue-full 503s
    # parked sequences count against the same cap
    loop._waiting, loop._parked = loop._waiting[:3], loop._waiting[3:]
    loop._pump_queue()
    assert api.poll_rooms == []
    # freeing backlog frees exactly that much room
    loop._parked = []
    loop._pump_queue()
    assert api.poll_rooms == [cap - 3]
    assert len(loop._waiting) == cap


def test_dynamic_tenant_cap_rejects_429_but_registered_admits():
    """Client-minted tenant ids materialise planes only up to
    max_tenants; past it an unregistered id answers 429 while a
    REGISTERED tenant is always granted its plane."""
    loop, api, _ = _make_loop(max_tenants=2,
                              qos=QosScheduler(policies={
                                  "vip": TenantPolicy(priority=3)},
                                  clock=FakeClock()))
    api.queue = [_req({"ids": [1]}, tenant="dyn1", rid="r-dyn1"),
                 _req({"ids": [1]}, tenant="dyn2", rid="r-dyn2"),
                 _req({"ids": [1]}, tenant="vip", rid="r-vip"),
                 _req({"ids": [1]}, tenant="dyn1", rid="r-dyn1b")]
    loop._pump_queue()
    # default + dyn1 fill the cap; dyn2 is rejected with the honest
    # remediation; vip rides its registered policy past the cap; dyn1
    # keeps being admitted (its plane already exists)
    assert "r-dyn1" not in api.replies
    assert "r-dyn1b" not in api.replies
    assert "r-vip" not in api.replies
    assert api.replies["r-dyn2"].status == 429
    assert b"tenant plane limit" in api.replies["r-dyn2"].body
    assert sorted(s.tenant for s in loop._waiting) == \
        ["dyn1", "dyn1", "vip"]


def test_overlong_tenant_id_is_a_parse_error():
    loop, api, _ = _make_loop()
    api.queue = [_req({"ids": [1], "tenant": "t" * 300}, rid="r-long")]
    loop._pump_queue()
    assert api.replies["r-long"].status == 400
    assert loop._waiting == []


def test_expired_waiting_requests_are_dropped_not_decoded():
    """A queued request past its reply window is dead weight — the
    listener already answered 504 — so the sweep drops it instead of
    letting it occupy a slot (and SLO-shed live traffic behind it)."""
    api = _FakeApi(reply_timeout_s=5.0)
    loop, api, _ = _make_loop(api=api)
    stale = _req({"ids": [1]}, rid="r-stale")
    stale.enqueued_at = _time.monotonic() - 60.0
    fresh = _req({"ids": [1]}, rid="r-fresh")
    loop._waiting = [_seq(stale), _seq(fresh)]
    loop._cancel_expired()
    assert [s.req.id for s in loop._waiting] == ["r-fresh"]


def test_engine_failure_also_fails_parked_sequences():
    """_fail_inflight must notify PARKED (preempted) sequences too —
    their resume tickets die with the engine; leaving them silent
    would hang the clients until reply-timeout on a broken engine."""
    loop, api, engine = _make_loop()
    running = _seq(_req({"ids": [1]}, rid="r-run"))
    running.slot = 0
    engine.slots[0] = ([1], 4)
    loop._by_slot[0] = running
    parked = _seq(_req({"ids": [1]}, rid="r-parked"))
    parked.ticket = {"fake": "ticket"}
    loop._parked = [parked]
    loop._fail_inflight(RuntimeError("engine down"))
    assert api.replies["r-run"].status == 500
    assert api.replies["r-parked"].status == 500
    assert loop._parked == [] and loop._by_slot == {}
