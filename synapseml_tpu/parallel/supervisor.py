"""Gang supervision: missed-heartbeat failure detection and elastic,
checkpoint-resumed relaunch.

The reference's NetworkManager treats worker loss as a whole-job event —
retry the rendezvous socket, rebuild the ring from scratch
(NetworkManager.scala:294-340) — and a HUNG worker is not even noticed
until the global timeout expires.  This module closes both gaps,
Horovod-elastic / TPU-pod style (preemption is the common case):

- :class:`HeartbeatMonitor` — a phi-accrual-flavored missed-heartbeat
  detector over the per-rank ``SMLMP_HB`` beats the launcher's reader
  threads feed it.  Suspicion for a rank is ``elapsed / expected
  interval`` where *expected* adapts to the observed mean inter-arrival
  (a loaded host stretches everyone's cadence together, so the detector
  stretches with it instead of false-positiving); a rank is declared
  failed at ``hang_intervals`` (default 3) missed beats, i.e. in
  O(heartbeat interval) rather than O(global timeout).  Verdicts are
  structured: ``hang at step N``, ``no heartbeat``, and advisory
  ``straggler`` for ranks whose step lags the gang leader.

- :class:`GangSupervisor` — the elastic relaunch driver.  One attempt =
  one whole gang (a formed ``jax.distributed`` cluster cannot re-admit a
  replacement rank); on failure the launcher has already torn every rank
  down (SIGTERM → grace → SIGKILL) and the supervisor relaunches under
  the caller's :class:`~synapseml_tpu.resilience.RetryPolicy` with a
  FRESH coordinator port.  A ``checkpoint_dir`` threads through to every
  worker (``SMLTPU_CKPT_DIR``), so trainers that checkpoint (GBDT/DL)
  resume from the last *complete* step — a retry costs seconds, not the
  job.  ``last_recovery_s`` clocks kill-to-resumed-step wall time.

- **Elastic resize** (this PR): a permanently lost rank no longer kills
  the job.  With ``min_ranks`` set, repeated failure of the same rank
  shrinks the next relaunch to the largest healthy size ≥ ``min_ranks``
  (degraded mode, resumed from the last durable checkpoint);
  :meth:`GangSupervisor.resize` / ``capacity_fn`` grow it back when
  capacity returns.  Checkpoints are world-size-independent by contract
  (DL state re-shards on restore; the booster is its own state), so an
  N-rank checkpoint resumes on M ranks.

Telemetry: ``gang_restarts_total{task}``, ``gang_failures_total{task,
cause}``, ``gang_resizes_total{task,direction}``,
``rank_heartbeat_age_seconds{rank}`` (updated live by the launcher's
watch loop; departed ranks' series are removed).  The fault registry's
call log records observed beats (``gang.heartbeat``), teardown signals
(``gang.teardown``), restarts (``gang.restart``) and resizes
(``gang.resize``) when ``record_calls`` is set, so chaos tests assert
the supervision schedule itself.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Optional

from ..resilience import RetryPolicy
from ..resilience.faults import get_faults
from ..telemetry import get_registry
from ..telemetry.gangplane import GangPlane, write_postmortem

__all__ = ["HeartbeatMonitor", "GangSupervisor", "RankHealth"]


@dataclass
class RankHealth:
    """Per-rank liveness state (driver side)."""
    rank: int
    started: float
    beats: int = 0
    last_beat: Optional[float] = None
    last_step: Optional[int] = None
    #: EWMA of inter-arrival seconds (None until two beats)
    mean_interval: Optional[float] = None
    done: bool = False

    def snapshot(self) -> Dict[str, Any]:
        return {"rank": self.rank, "beats": self.beats,
                "last_step": self.last_step,
                "mean_interval": self.mean_interval, "done": self.done}


class HeartbeatMonitor:
    """Phi-style missed-heartbeat detector for one gang attempt.

    Thread-safe: the launcher's per-rank reader threads call
    :meth:`observe` while the watch loop polls :meth:`verdicts`.
    ``clock`` is injectable so tests drive time deterministically.
    """

    #: EWMA weight of the newest inter-arrival sample
    EWMA_ALPHA = 0.25

    def __init__(self, n_ranks: int, interval_s: float,
                 hang_intervals: float = 3.0,
                 startup_grace_s: float = 120.0,
                 straggler_lag_steps: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic,
                 on_observe: Optional[Callable[[int, Optional[int]], None]]
                 = None,
                 ranks: Optional[Iterable[int]] = None):
        if interval_s <= 0:
            raise ValueError("interval_s must be > 0")
        self.interval_s = float(interval_s)
        self.hang_intervals = float(hang_intervals)
        self.startup_grace_s = float(startup_grace_s)
        self.straggler_lag_steps = straggler_lag_steps
        self._clock = clock
        self._on_observe = on_observe
        self._lock = threading.Lock()
        now = clock()
        # the watched rank set comes from the LIVE attempt: the
        # supervisor rebuilds the monitor per attempt at its (post-
        # resize) world size, so verdicts/ages/stragglers never
        # reference a departed rank.  ``ranks`` additionally lets a
        # caller watch a sparse/explicit id set (gang ranks always
        # renumber 0..n-1, so the supervisor itself never needs it).
        rank_ids = (list(ranks) if ranks is not None
                    else list(range(n_ranks)))
        self.ranks: Dict[int, RankHealth] = {
            r: RankHealth(rank=r, started=now) for r in rank_ids}

    # -- feeding -----------------------------------------------------------
    def observe(self, rank: int, step: Optional[int] = None,
                ts: Optional[float] = None) -> None:
        """One received beat (``ts`` is the sender's wall clock, carried
        for logs; detection uses the driver's own monotonic clock)."""
        now = self._clock()
        with self._lock:
            h = self.ranks.get(rank)
            if h is None:
                return
            if h.last_beat is not None:
                d = now - h.last_beat
                h.mean_interval = (d if h.mean_interval is None else
                                   (1 - self.EWMA_ALPHA) * h.mean_interval
                                   + self.EWMA_ALPHA * d)
            h.last_beat = now
            h.beats += 1
            if step is not None and (h.last_step is None
                                     or step >= h.last_step):
                h.last_step = step
        get_faults().note("gang.heartbeat", rank=rank, step=step)
        if self._on_observe is not None:
            self._on_observe(rank, step)

    def mark_done(self, rank: int) -> None:
        """Rank exited cleanly: stop watching it (a finished rank is not
        a hung rank)."""
        with self._lock:
            h = self.ranks.get(rank)
            if h is not None:
                h.done = True

    # -- reading -----------------------------------------------------------
    def age(self, rank: int) -> float:
        """Seconds since this rank's last beat (since start when none)."""
        now = self._clock()
        with self._lock:
            h = self.ranks[rank]
            return now - (h.last_beat if h.last_beat is not None
                          else h.started)

    def ages(self) -> Dict[int, float]:
        now = self._clock()
        with self._lock:
            return {r: now - (h.last_beat if h.last_beat is not None
                              else h.started)
                    for r, h in self.ranks.items() if not h.done}

    def last_steps(self) -> Dict[int, Optional[int]]:
        with self._lock:
            return {r: h.last_step for r, h in self.ranks.items()}

    def max_step(self) -> Optional[int]:
        with self._lock:
            steps = [h.last_step for h in self.ranks.values()
                     if h.last_step is not None]
        return max(steps) if steps else None

    def _expected_interval(self, h: RankHealth) -> float:
        """The adaptive beat period: never tighter than the configured
        interval, stretched by the observed mean when the host is slow."""
        if h.mean_interval is None:
            return self.interval_s
        return max(self.interval_s, h.mean_interval)

    def suspicion(self, rank: int) -> float:
        """phi-style suspicion: elapsed beats-worth of silence (0 when
        the rank just beat; >= ``hang_intervals`` ⇒ declared failed)."""
        now = self._clock()
        with self._lock:
            h = self.ranks[rank]
            if h.done:
                return 0.0
            if h.last_beat is None:
                return 0.0
            return (now - h.last_beat) / self._expected_interval(h)

    def verdicts(self) -> Dict[int, str]:
        """rank → structured failure cause, for every rank the detector
        declares failed NOW (empty dict: gang looks alive)."""
        now = self._clock()
        out: Dict[int, str] = {}
        with self._lock:
            for r, h in self.ranks.items():
                if h.done:
                    continue
                if h.last_beat is None:
                    silent = now - h.started
                    if silent > self.startup_grace_s:
                        out[r] = f"no heartbeat (none in {silent:.1f}s)"
                    continue
                silent = now - h.last_beat
                phi = silent / self._expected_interval(h)
                if phi >= self.hang_intervals:
                    step = ("?" if h.last_step is None else h.last_step)
                    out[r] = (f"hang at step {step} (no heartbeat for "
                              f"{silent:.1f}s, {phi:.1f} intervals)")
        return out

    def stragglers(self) -> Dict[int, str]:
        """Advisory rank → cause for ranks alive but lagging the gang
        leader by more than ``straggler_lag_steps`` (empty when the
        feature is off or nobody lags)."""
        lag = self.straggler_lag_steps
        if lag is None:
            return {}
        with self._lock:
            steps = {r: h.last_step for r, h in self.ranks.items()
                     if not h.done and h.last_step is not None}
            if len(steps) < 2:
                return {}
            lead = max(steps.values())
            return {r: f"straggler at step {s} (leader at step {lead})"
                    for r, s in steps.items() if lead - s > lag}


class GangSupervisor:
    """Elastic whole-gang launcher: detect fast, tear down, relaunch,
    resume from the last complete checkpoint — and, with a resize
    policy, RESIZE the gang instead of dying with it.

    One instance supervises one logical job; :meth:`run` returns the
    per-rank results of the first attempt that completes.  State left on
    the instance afterward: ``restarts`` (relaunch count),
    ``last_failure`` (the last :class:`~synapseml_tpu.parallel.launcher.
    WorkerFailure`), ``last_recovery_s`` (seconds from failure detection
    to the relaunched gang re-reaching the failed attempt's best step —
    the elastic-resume cost), ``monitor`` (the live attempt's detector),
    ``plane`` (the attempt's merged cross-rank telemetry when the
    observability plane is on), ``last_postmortem`` (path of the bundle
    the last dead attempt left in ``observability_dir``),
    ``world_size`` (the live attempt's rank count — ``n_processes``
    until a resize), ``resize_history`` (every applied resize).

    Elastic resize (Horovod-elastic shrink-to-survive semantics,
    arXiv:1802.05799): ``min_ranks < n_processes`` arms the shrink
    policy — when the SAME rank is blamed for ``shrink_after``
    consecutive failed attempts (a really-lost TPU host keeps failing
    however often the gang relaunches at the same size), the next
    relaunch drops to the largest healthy size ≥ ``min_ranks`` and
    resumes from the last durable checkpoint in DEGRADED mode.  Growth:
    :meth:`resize` requests a new size (a running healthy attempt is
    torn down at the next watch poll and relaunched — resume from the
    last durable checkpoint makes that a between-checkpoints boundary),
    and ``capacity_fn`` (→ currently placeable rank count) lets a
    degraded gang grow back toward ``n_processes`` automatically at the
    next relaunch boundary.  Resizes ride the caller's
    :class:`~synapseml_tpu.resilience.RetryPolicy` (failure-driven
    shrinks consume a retry + its backoff exactly like a same-size
    relaunch) plus their own brake: ``resize_cooldown_s`` between
    automatic shrinks and a ``max_resizes`` budget.  Checkpoints must be
    world-size-independent for this to be sound — GBDT boosters are
    (the model is the state), DL TrainStates re-shard on restore (see
    ``docs/api/gang.md`` "Elastic resize").
    """

    def __init__(self, task: str, n_processes: int = 2,
                 devices_per_process: int = 2, task_args: Any = None,
                 timeout_s: float = 300.0,
                 env_extra: Optional[Dict[str, str]] = None,
                 retry_policy: Optional[RetryPolicy] = None,
                 heartbeat_interval_s: float = 1.0,
                 hang_intervals: float = 3.0,
                 startup_grace_s: float = 120.0,
                 straggler_lag_steps: Optional[int] = None,
                 checkpoint_dir: Optional[Any] = None,
                 term_grace_s: float = 2.0,
                 tail_lines: int = 400,
                 observability_dir: Optional[str] = None,
                 tm_interval_s: Optional[float] = None,
                 min_ranks: Optional[int] = None,
                 shrink_after: int = 2,
                 resize_cooldown_s: float = 0.0,
                 max_resizes: int = 8,
                 capacity_fn: Optional[Callable[[], int]] = None,
                 tune_table_dir: Optional[str] = None):
        self.task = task
        self.n_processes = int(n_processes)
        self.devices_per_process = int(devices_per_process)
        self.task_args = task_args
        self.timeout_s = float(timeout_s)
        self.env_extra = dict(env_extra or {})
        self.retry_policy = retry_policy
        self.heartbeat_interval_s = float(heartbeat_interval_s)
        self.hang_intervals = float(hang_intervals)
        self.startup_grace_s = float(startup_grace_s)
        self.straggler_lag_steps = straggler_lag_steps
        # a CheckpointManager (or anything with .directory) passes its
        # directory; plain strings pass through
        if checkpoint_dir is not None and not isinstance(checkpoint_dir, str):
            checkpoint_dir = getattr(checkpoint_dir, "directory",
                                     checkpoint_dir)
        self.checkpoint_dir = checkpoint_dir
        # persisted autotune tuning tables (ISSUE 20): every worker (and
        # every relaunch/resize generation) resolves its TunePlane
        # against the shared dir, so a winner measured once serves the
        # whole gang's lifetime.  (The compile cache needs no threading:
        # workers inherit JAX_COMPILATION_CACHE_DIR from the environment.)
        self.tune_table_dir = str(tune_table_dir) if tune_table_dir else None
        if self.tune_table_dir:
            from ..telemetry.tunetable import TUNE_TABLE_ENV
            self.env_extra.setdefault(TUNE_TABLE_ENV, self.tune_table_dir)
        self.term_grace_s = float(term_grace_s)
        self.tail_lines = int(tail_lines)
        # the gang-wide observability plane: an obs dir turns wire export
        # on (cadence defaulting to the heartbeat interval), collects
        # flight dumps, and receives postmortem.json / gang_trace.json
        self.observability_dir = observability_dir
        if tm_interval_s is None:
            tm_interval_s = (self.heartbeat_interval_s
                             if observability_dir else 0.0)
        self.tm_interval_s = float(tm_interval_s)

        # -- elastic resize policy ----------------------------------------
        if min_ranks is not None:
            min_ranks = int(min_ranks)
            if not 1 <= min_ranks <= self.n_processes:
                raise ValueError(
                    f"min_ranks={min_ranks}: must be in "
                    f"[1, n_processes={self.n_processes}]")
        self.min_ranks = min_ranks
        self.shrink_after = max(1, int(shrink_after))
        self.resize_cooldown_s = float(resize_cooldown_s)
        self.max_resizes = int(max_resizes)
        self.capacity_fn = capacity_fn

        self.restarts = 0
        self.last_failure: Optional[BaseException] = None
        self.last_recovery_s: Optional[float] = None
        self.monitor: Optional[HeartbeatMonitor] = None
        #: the live (or last) attempt's merged cross-rank telemetry
        self.plane: Optional[GangPlane] = None
        #: path of the last written post-mortem bundle, if any
        self.last_postmortem: Optional[str] = None
        #: rank count of the live (or next) attempt
        self.world_size = self.n_processes
        #: applied resizes: [{"attempt", "from", "to", "direction",
        #: "cause"}] — also lands in post-mortem bundles
        self.resize_history: List[Dict[str, Any]] = []
        self._max_world = self.n_processes
        self._fail_streak: Dict[int, int] = {}
        self._resizes_done = 0
        self._last_shrink_at: Optional[float] = None
        self._resize_lock = threading.Lock()
        self._requested_size: Optional[int] = None
        self._interrupt = threading.Event()
        #: callables invoked with each applied-resize event dict (the
        #: same record appended to :attr:`resize_history`) — how a
        #: budget holder (the serving CapacityArbiter) keeps its chip
        #: accounting honest when the gang resizes for its OWN reasons
        #: (failure-driven shrink, capacity probe), not just when asked
        self._resize_listeners: List[Any] = []

        reg = get_registry()
        self._c_restarts = reg.counter(
            "gang_restarts_total",
            "elastic whole-gang relaunches", ("task",))
        self._c_failures = reg.counter(
            "gang_failures_total",
            "gang attempts that failed, by first-listed cause kind",
            ("task", "cause"))
        self._c_resizes = reg.counter(
            "gang_resizes_total",
            "applied elastic gang resizes, by direction",
            ("task", "direction"))
        self._g_world = reg.gauge(
            "gang_world_size",
            "rank count of the live (or next) gang attempt", ("task",))
        self._g_world.set(self.world_size, task=self.task)

    def _new_monitor(self, watermark: Optional[int],
                     failed_at: Optional[float]) -> Optional[HeartbeatMonitor]:
        if self.heartbeat_interval_s <= 0:
            return None

        recovered = {"done": watermark is None or failed_at is None}
        # surfaced so run() can close the clock at gang COMPLETION when
        # no beat ever re-reached the watermark (the dead attempt's best
        # step was the last step — the relaunch restores it and has
        # nothing left to replay)
        self._recovery_pending = recovered

        def on_observe(rank: int, step: Optional[int]) -> None:
            # kill-to-resumed-step clock: first beat of the relaunched
            # gang that re-reaches the failed attempt's best step
            if recovered["done"] or step is None or step < watermark:
                return
            recovered["done"] = True
            self.last_recovery_s = time.monotonic() - failed_at

        # rank set from the LIVE attempt (post-resize size), never the
        # fixed construction-time n_processes
        return HeartbeatMonitor(
            self.world_size, self.heartbeat_interval_s,
            hang_intervals=self.hang_intervals,
            startup_grace_s=self.startup_grace_s,
            straggler_lag_steps=self.straggler_lag_steps,
            on_observe=on_observe)

    #: verdict-prefix → metric label for gang_failures_total{cause}
    _CAUSE_KINDS = (("hang", "hang"), ("no heartbeat", "no_heartbeat"),
                    ("exit", "exit"), ("timeout", "timeout"),
                    ("no result", "no_result"), ("straggler", "straggler"),
                    ("injected", "injected"))

    @classmethod
    def _cause_kind(cls, causes: Dict[int, str]) -> str:
        if not causes:
            return "unknown"
        first = causes[sorted(causes)[0]]
        for prefix, kind in cls._CAUSE_KINDS:
            if first.startswith(prefix):
                return kind
        return "other"

    def _clear_flight_dumps(self) -> None:
        """Remove a previous attempt's (or run's) on-disk flight rings
        before launching: flight ``seq`` counters restart per process, so
        a stale dump with a high ``last_seq`` would outrank the NEW
        attempt's wire tail in the post-mortem gather and attribute the
        wrong events to a dead rank."""
        obs = self.observability_dir
        if not obs or not os.path.isdir(obs):
            return
        for r in range(self._max_world):
            try:
                os.unlink(os.path.join(obs, f"flight-rank{r}.json"))
            except OSError:
                pass

    def _write_postmortem(self, attempt: int, failure) -> None:
        """One dead attempt → schema-checked
        ``postmortem-attempt<N>.json`` in the obs dir, with
        ``postmortem.json`` always the LATEST attempt's bundle (plus the
        stitched multi-lane trace of whatever spans the wire delivered
        before the gang died).  Per-attempt files mean an early
        attempt's verdict — often the root cause — survives later
        retries.  Never raises: bundling evidence must not mask the
        failure being bundled."""
        obs = self.observability_dir
        if not obs:
            return
        try:
            os.makedirs(obs, exist_ok=True)
            last_steps = (self.monitor.last_steps()
                          if self.monitor is not None else {})
            bundle = write_postmortem(
                os.path.join(obs, f"postmortem-attempt{attempt}.json"),
                task=self.task, causes=dict(failure.causes),
                attempt=attempt, n_ranks=self.world_size,
                plane=self.plane, last_steps=last_steps, obs_dir=obs,
                resize_history=list(self.resize_history))
            from ..telemetry.artifact import write_json
            from ..telemetry.gangplane import check_postmortem
            latest = os.path.join(obs, "postmortem.json")
            write_json(latest, bundle, schema=check_postmortem)
            # only after the write lands: a swallowed failure must not
            # leave this pointing at a missing/stale file
            self.last_postmortem = latest
            if self.plane is not None:
                self.plane.export_chrome(os.path.join(obs,
                                                      "gang_trace.json"))
        except Exception:
            pass

    def _export_trace(self) -> None:
        obs = self.observability_dir
        if obs and self.plane is not None:
            try:
                os.makedirs(obs, exist_ok=True)
                self.plane.export_chrome(os.path.join(obs,
                                                      "gang_trace.json"))
            except Exception:
                pass

    # -- elastic resize ----------------------------------------------------
    def resize(self, n: int) -> None:
        """Request the gang run at ``n`` ranks from the next attempt on.

        Thread-safe and callable mid-run: a running healthy attempt is
        torn down at the next watch poll (SIGTERM → grace → SIGKILL, the
        normal teardown) and the relaunch at the new size resumes from
        the last durable checkpoint — so the request lands *between
        checkpoints*, never inside one.  An explicit request is an
        operator action: it bypasses the automatic ``max_resizes``
        budget and the shrink cooldown — but NOT the validity floor:
        ``n <= 0`` and ``n < min_ranks`` are caller errors rejected
        here, loudly, instead of entering the relaunch path with a gang
        shape the policy forbids."""
        n = int(n)
        if n < 1:
            raise ValueError(
                f"resize({n}): a gang needs at least one rank — to stop "
                "the gang, let the task finish or tear the supervisor "
                "down; resize only changes a LIVE gang's shape")
        if self.min_ranks is not None and n < self.min_ranks:
            raise ValueError(
                f"resize({n}): below this supervisor's elastic floor "
                f"min_ranks={self.min_ranks} — shrink requests must stay "
                f"in [{self.min_ranks}, ...]; raise min_ranks at "
                "construction if the floor itself is wrong")
        with self._resize_lock:
            if n == self.world_size:
                # already there: a no-op request must not tear down a
                # healthy running gang — it only CANCELS any pending
                # request for a different size (and its wakeup; the
                # event is set nowhere else)
                self._requested_size = None
                self._interrupt.clear()
                return
            # set the wakeup under the SAME lock that consumes the
            # request: setting it after release races
            # _plan_before_launch (request consumed, event cleared, THEN
            # set) into tearing down the next healthy, correctly-sized
            # attempt for nothing
            self._requested_size = n
            self._interrupt.set()

    def add_resize_listener(self, fn) -> None:
        """Register ``fn(event_dict)`` to run on every APPLIED resize
        (requested, failure-driven, or capacity-driven) — the
        budget-aware hook: an external chip-budget holder stays
        consistent with resizes it did not initiate.  Listener errors
        are swallowed: accounting must not break the relaunch path."""
        with self._resize_lock:
            self._resize_listeners.append(fn)

    def _apply_resize(self, attempt: int, new_size: int, cause: str,
                      automatic: bool) -> None:
        # the world_size write happens under the SAME lock resize()'s
        # no-op comparison reads it under — otherwise a request racing
        # the application of a capacity/failure resize compares against
        # a stale size and needlessly tears down the next attempt
        with self._resize_lock:
            old = self.world_size
            if new_size == old:
                return
            direction = "shrink" if new_size < old else "grow"
            self.world_size = new_size
        self._max_world = max(self._max_world, new_size)
        if automatic:
            self._resizes_done += 1
            if direction == "shrink":
                self._last_shrink_at = time.monotonic()
        # rank indices renumber 0..new-1 on relaunch: stale streaks
        # would blame the wrong process
        self._fail_streak.clear()
        event = {"attempt": int(attempt), "from": old, "to": new_size,
                 "direction": direction, "cause": cause}
        self.resize_history.append(event)
        self._c_resizes.inc(1, task=self.task, direction=direction)
        self._g_world.set(new_size, task=self.task)
        with self._resize_lock:
            listeners = list(self._resize_listeners)
        for fn in listeners:
            try:
                fn(dict(event))
            except Exception:
                pass
        get_faults().note("gang.resize", **event)
        try:
            from ..telemetry.flight import record as flight_record
            flight_record("gang_resize", task=self.task, **event)
        except Exception:
            pass
        # world size changed → topology snapshot refreshed → reduction
        # plan cache invalidated (ISSUE 14: the collective planner
        # re-plans at every resize boundary; workers are fresh
        # processes, so their planners rebuild at relaunch — this keeps
        # the DRIVER-side planner honest too)
        self._replan(f"resize_{direction}", new_size)

    def _replan(self, reason: str, world_size: int) -> None:
        """Invalidate the process collective-plan cache (recorded in the
        fault call log + flight ring as ``plan.refresh`` /
        ``plan_invalidate``).  Never raises: re-planning is advisory —
        a failed refresh must not take the supervisor down with it."""
        try:
            from .planner import get_planner
            get_planner().refresh(reason, world_size=int(world_size))
        except Exception:
            pass

    def _resize_budget_ok(self) -> bool:
        return self._resizes_done < self.max_resizes

    def _shrink_cooled_down(self) -> bool:
        """THE cooldown gate for every AUTOMATIC shrink — failure-driven
        and capacity-driven alike, so a flapping capacity probe cannot
        sidestep the brake the operator configured."""
        return (self._last_shrink_at is None
                or time.monotonic() - self._last_shrink_at
                >= self.resize_cooldown_s)

    def _plan_after_failure(self, causes: Dict[int, str]) -> Optional[int]:
        """Shrink-to-survive decision for one failed attempt → target
        size, or None.  A rank is *persistently* failing once it is
        blamed (non-advisory cause) in ``shrink_after`` consecutive
        failed attempts — the permanent-loss signature (a transient
        crash resumes fine at the same size; a cordoned host fails
        every relaunch).  Target: largest healthy size ≥ ``min_ranks``.
        """
        blamed = {r for r, c in causes.items()
                  if not str(c).startswith("straggler")}
        for r in list(self._fail_streak):
            if r not in blamed:
                del self._fail_streak[r]
        for r in blamed:
            self._fail_streak[r] = self._fail_streak.get(r, 0) + 1
        if self.min_ranks is None:
            return None
        persistent = [r for r in blamed
                      if self._fail_streak[r] >= self.shrink_after]
        if not persistent:
            return None
        target = max(self.min_ranks, self.world_size - len(persistent))
        if target >= self.world_size or not self._resize_budget_ok() \
                or not self._shrink_cooled_down():
            return None
        return target

    def _plan_before_launch(self, attempt: int) -> None:
        """Attempt-boundary resize decisions: consume an explicit
        :meth:`resize` request, then let ``capacity_fn`` shrink a gang
        whose capacity left or grow a degraded gang back toward
        ``n_processes`` when capacity returned."""
        with self._resize_lock:
            req = self._requested_size
            self._requested_size = None
            # a request set while no attempt ran left the event set;
            # consuming the request consumes the wakeup too
            self._interrupt.clear()
        if req is not None:
            self._apply_resize(attempt, req, cause="requested",
                               automatic=False)
            return
        if self.capacity_fn is None:
            return
        try:
            cap = int(self.capacity_fn())
        except Exception:
            return                      # a flaky probe must not kill the job
        floor = self.min_ranks if self.min_ranks is not None else 1
        if cap < self.world_size:
            target = max(floor, cap)
            if (target < self.world_size and self._resize_budget_ok()
                    and self._shrink_cooled_down()):
                self._apply_resize(attempt, target,
                                   cause=f"capacity {cap}", automatic=True)
        elif self.world_size < self.n_processes and cap > self.world_size:
            target = min(self.n_processes, cap)
            if self._resize_budget_ok():
                self._apply_resize(attempt, target,
                                   cause=f"capacity {cap}", automatic=True)

    def run(self) -> List[Any]:
        """Launch (and relaunch/resize) until a gang completes; per-rank
        results in rank order (length = the completing attempt's
        ``world_size``), or the LAST attempt's failure when retries
        exhaust."""
        from .launcher import GangInterrupted, WorkerFailure, _launch_once

        policy = self.retry_policy
        retries_left = policy.max_retries if policy else 0
        watermark: Optional[int] = None
        failed_at: Optional[float] = None
        attempt = 0
        while True:
            self._plan_before_launch(attempt)
            self.monitor = self._new_monitor(watermark, failed_at)
            self.plane = (GangPlane(self.world_size)
                          if (self.tm_interval_s > 0
                              or self.observability_dir) else None)
            self._clear_flight_dumps()
            try:
                results = _launch_once(
                    self.task, self.world_size, self.devices_per_process,
                    self.task_args, self.timeout_s, self.env_extra,
                    monitor=self.monitor,
                    heartbeat_interval_s=self.heartbeat_interval_s,
                    checkpoint_dir=self.checkpoint_dir,
                    term_grace_s=self.term_grace_s,
                    tail_lines=self.tail_lines,
                    plane=self.plane, tm_interval_s=self.tm_interval_s,
                    obs_dir=self.observability_dir,
                    interrupt=self._interrupt)
                if (failed_at is not None
                        and not getattr(self, "_recovery_pending",
                                        {"done": True})["done"]):
                    # the relaunched gang completed without ever beating
                    # a step ≥ watermark (everything durable was already
                    # done): completion IS the recovery
                    self.last_recovery_s = time.monotonic() - failed_at
                self._export_trace()
                return results
            except GangInterrupted:
                # a deliberate resize teardown: no retry burned, no
                # post-mortem — but the recovery clock starts, so
                # resize_recovery_seconds covers requested grows too
                failed_at = time.monotonic()
                if self.monitor is not None:
                    step = self.monitor.max_step()
                    if step is not None and (watermark is None
                                             or step > watermark):
                        watermark = step
                self.restarts += 1
                self._c_restarts.inc(1, task=self.task)
                # ``attempt`` is the FAILURE index (postmortem naming)
                # and does not advance here; ``restart`` is the
                # monotonic launch counter both restart paths share, so
                # fault-log consumers can order the timeline
                get_faults().note("gang.restart", attempt=attempt,
                                  restart=self.restarts, causes={},
                                  watermark=watermark, resize=True)
                # every relaunch boundary re-plans (a resize teardown
                # already refreshed in _apply_resize when the size
                # changes; this covers same-size interrupts too)
                self._replan("relaunch", self.world_size)
                continue
            except WorkerFailure as e:
                self.last_failure = e
                failed_at = time.monotonic()
                if self.monitor is not None:
                    step = self.monitor.max_step()
                    if step is not None and (watermark is None
                                             or step > watermark):
                        watermark = step
                self._c_failures.inc(1, task=self.task,
                                     cause=self._cause_kind(e.causes))
                self._write_postmortem(attempt, e)
                target = self._plan_after_failure(e.causes)
                if policy is None or retries_left <= 0 \
                        or not policy.acquire_retry():
                    raise
                retries_left -= 1
                if target is not None:
                    self._apply_resize(attempt, target,
                                       cause=self._cause_kind(e.causes),
                                       automatic=True)
                self.restarts += 1
                self._c_restarts.inc(1, task=self.task)
                get_faults().note("gang.restart", attempt=attempt + 1,
                                  restart=self.restarts,
                                  causes=dict(e.causes),
                                  watermark=watermark)
                # relaunch boundary: the failed attempt's topology may
                # be gone (that is often WHY it failed) — re-plan
                self._replan("relaunch", self.world_size)
                policy.sleep(policy.backoff_s(attempt),
                             site="launcher.backoff")
                attempt += 1
