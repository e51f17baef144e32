"""AOT warmup of the serving program lattice — the compile plane.

Every compiled program a :class:`~synapseml_tpu.models.llm.slots.
SlotEngine` can ever need is enumerable from its STATIC config: one
prefill per prompt bucket (``slots.prefill_buckets``), one decode step,
one verify per span bucket ``S`` when speculative decoding is armed, and
the prefix-copy transfer.
Orca/vLLM-class schedulers treat that finite lattice as something to
warm *before admission*, not to discover lazily inside the decode loop
— a lazy first hit stalls every active slot for the full XLA compile
and recompiles from scratch after every gang relaunch/resize.

This module provides:

- :func:`program_lattice` — the enumeration, as ``ProgramSpec`` rows
  whose ``run`` closures execute the REAL jitted entry points of
  :mod:`~synapseml_tpu.models.llm.slots` against scratch state shaped
  exactly like the engine's, so the module-level jit caches are
  populated with exactly the keys serving will hit (an AOT
  ``lower().compile()`` would build the executable but not the jit
  dispatch cache — the warm path must be the serving path).
- :class:`CompilePlane` — drives the lattice at engine construction
  (synchronously, or on a background thread with ``/readyz`` gating on
  completion), reprioritizes a held request's cold bucket to the front
  of the remaining queue (:meth:`ensure_async` — the decode loop keeps
  stepping already-warm buckets meanwhile), and attributes every
  compile: ``llm_compile_seconds{program}`` histograms via
  :func:`~synapseml_tpu.parallel.compilecache.compile_label`,
  ``llm_compile_stalls_total`` for programs that compiled INSIDE the
  serving loop, warmup state in the ``/readyz`` payload, and flight
  events per warmed program.

The tier-1 lattice-completeness sweep (tests/test_llm_warmup.py) holds
``REGISTERED_ENTRY_POINTS`` equal to the set of module-level jitted
entry points in ``slots.py``/``pallas_attn.py`` — a new jitted entry
point fails the sweep until it is registered here (and thereby thought
about: either it joins the lattice or its exemption is explicit).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ...parallel.compilecache import (cache_stats, compile_label,
                                      install_compile_listeners)
from ...telemetry import get_registry, span
from .model import init_cache, projection_layout
from .slots import (_copy_prefix_jit, _decode_program_key,
                    _decode_step_jit, _next_pow2, _prefill_program_key,
                    _prefill_slot_jit, _restore_program_key,
                    _restore_span_jit, _verify_program_key,
                    _verify_step_jit)

__all__ = ["CompilePlane", "ProgramSpec", "REGISTERED_ENTRY_POINTS",
           "engine_jit_cache_size", "jit_entry_points", "program_lattice"]

#: module-level jitted entry points the lattice accounts for, per module
#: (the completeness sweep's contract).  ``paged_decode_attention`` (and
#: ``latent_decode_attention``, its sibling over latent rows) is
#: covered THROUGH the decode/verify programs — the kernel is invoked
#: inside their traces, never as its own serving-path dispatch — and
#: ``prefill_attention`` (and the one query block it maps,
#: ``prefill_query_block``) through the prefill programs of the buckets
#: ``prefill_geometry`` has a tile for: they add no program and no key,
#: and the kernel is traced once a layer kind for ALL of an engine's
#: buckets (a query block's shapes do not follow the bucket).
REGISTERED_ENTRY_POINTS = {
    "synapseml_tpu.models.llm.slots": frozenset({
        "_prefill_slot_jit", "_decode_step_jit", "_verify_step_jit",
        "_copy_prefix_jit", "_restore_span_jit"}),
    "synapseml_tpu.models.llm.pallas_attn": frozenset({
        "paged_decode_attention", "latent_decode_attention",
        "prefill_attention", "prefill_query_block"}),
    # the recurrence of linear-attention layers: inside the decode and
    # prefill programs as the paged kernel is inside decode
    "synapseml_tpu.models.llm.pallas_gdn": frozenset({
        "gated_delta_decode", "gated_delta_prefill"}),
    # the grouped product of expert layers: inside every program of a
    # model that has them
    "synapseml_tpu.models.llm.experts": frozenset({"expert_ffn"}),
    # non-LLM tunable entry points: not part of the serving lattice, but
    # the autotune source-scan lint requires every registered search
    # space to time a program listed here — the registry doubles as the
    # "what can be warmed/tuned" contract across the codebase
    "synapseml_tpu.models.gbdt.pallas_hist": frozenset({
        "build_hist_nodes_pallas", "route_and_hist_pallas"}),
    "synapseml_tpu.parallel.compression": frozenset({
        "int8_roundtrip_jit"}),
}

#: the entry points whose jit dispatch caches the zero-in-loop-compile
#: pin sums (``paged_decode_attention`` populates a cache only when
#: called at top level — tests do, serving never does)
_ENGINE_ENTRY_POINTS = (_prefill_slot_jit, _decode_step_jit,
                        _verify_step_jit, _copy_prefix_jit,
                        _restore_span_jit)


def jit_entry_points(module) -> Dict[str, Any]:
    """Module-level jit-wrapped callables of ``module`` (name → fn) —
    duck-typed on the PjitFunction surface (``lower`` +
    ``_cache_size``), so the sweep survives wrapper-class renames."""
    out = {}
    for name, obj in vars(module).items():
        if callable(obj) and hasattr(obj, "lower") \
                and hasattr(obj, "_cache_size"):
            out[name] = obj
    return out


def engine_jit_cache_size() -> int:
    """Total compiled-program count across the engine's jitted entry
    points — the compile-counter hook: snapshot after warmup, serve a
    trace, assert unchanged ⇒ zero in-loop compiles."""
    return int(sum(f._cache_size() for f in _ENGINE_ENTRY_POINTS))


@dataclasses.dataclass
class ProgramSpec:
    """One row of the program lattice: a stable key (the metric/trace
    label), its kind, and a closure running the real jitted entry point
    once against scratch state (takes and returns the scratch cache —
    the jitted programs donate their cache argument); ``rows``, the rows
    of the model's pass (``B * S``; 0: a program with no pass)."""
    key: str
    kind: str                      # prefill | decode | verify | prefix_copy
    run: Callable[[Any], Any]
    rows: int = 0


def program_lattice(engine) -> List[ProgramSpec]:
    """Enumerate the engine's full program lattice from its static
    config.  Ordered so a background warm makes the engine useful
    earliest: decode steps first (every active slot needs one), then
    the prefix copy, then the verify lattice (a speculative engine's
    first step can dispatch ANY span bucket S, so admission must wait
    on all of them — they are part of the base, and warming them
    before the prefills keeps that wait minimal), then prefill buckets
    ascending — last, because a held request's bucket is bumped to the
    front of whatever remains (:meth:`CompilePlane.ensure_async`).

    The closures reproduce the serving call sites argument-for-argument
    (python ints where serving passes python ints, arrays of the same
    shape/dtype/weak-type elsewhere) so the jit cache keys they create
    are EXACTLY the keys serving hits — the whole point."""
    import jax.numpy as jnp

    model, variables = engine.model, engine.variables
    n = engine.n_slots
    backend = engine.attention_backend
    step_kwargs = {"attention_backend": backend,
                   "paged_tile": engine._paged_tile}

    def decode_inputs():
        tokens = jnp.asarray(np.full(n, engine.pad_id, np.int32))
        lengths = jnp.asarray(np.ones(n, np.int32))
        active = jnp.asarray(np.zeros(n, bool))
        return tokens, lengths, active

    specs: List[ProgramSpec] = []

    def run_decode(cache):
        tokens, lengths, active = decode_inputs()
        cache, nxt, _ = _decode_step_jit(
            model, variables, cache, tokens, lengths, active,
            jax.random.PRNGKey(0), engine.temperature, engine.top_k,
            engine.top_p, prev_nxt=jnp.zeros_like(engine._no_prev),
            feed_host=jnp.asarray(np.ones(n, bool)), **step_kwargs)
        jax.block_until_ready(nxt)
        return cache
    specs.append(ProgramSpec(_decode_program_key(backend), "decode",
                             run_decode, n))

    def run_copy(cache):
        cache = _copy_prefix_jit(cache, 0, min(1, n - 1),
                                 engine._copy_length(1))
        jax.block_until_ready(jax.tree.leaves(cache)[0])
        return cache
    if not engine.recurrent:       # such an engine never copies a prefix
        specs.append(ProgramSpec("prefix_copy", "prefix_copy", run_copy))

    if engine.spec_draft_len:
        s_max = max(2, _next_pow2(1 + engine.spec_draft_len))
        s = 2
        while s <= s_max:
            def run_verify(cache, s=s):
                tokens = jnp.asarray(
                    np.full((n, s), engine.pad_id, np.int32))
                _, lengths, active = decode_inputs()
                cache, g = _verify_step_jit(
                    model, variables, cache, tokens, lengths, active,
                    **step_kwargs)
                jax.block_until_ready(g)
                return cache
            specs.append(ProgramSpec(_verify_program_key(backend, s),
                                     "verify", run_verify, n * s))
            s *= 2

    for pb in engine._buckets:
        def run_prefill(cache, pb=pb):
            tokens = jnp.asarray(np.full(pb, engine.pad_id, np.int32))
            cache, last = _prefill_slot_jit(model, variables, cache,
                                            tokens, 1, 0, 0,
                                            attention_backend=backend)
            jax.block_until_ready(last)
            return cache
        specs.append(ProgramSpec(_prefill_program_key(pb), "prefill",
                                 run_prefill, pb))

    if getattr(engine, "kv_arena", None) is not None:
        # host-restore programs: one per prefill bucket (the restored
        # span pads to the same grid).  Only an arena-attached engine
        # can dispatch them, so a plain engine's lattice stays exactly
        # as before.
        cfg = engine.cfg
        for pb in engine._buckets:
            def run_restore(cache, pb=pb):
                rows = [{"k": jnp.zeros((pb, cfg.num_kv_heads,
                                         cfg.d_head), cfg.dtype),
                         "v": jnp.zeros((pb, cfg.num_kv_heads,
                                         cfg.d_head), cfg.dtype)}
                        for _ in range(cfg.num_layers)]
                cache = _restore_span_jit(cache, rows, 0)
                jax.block_until_ready(jax.tree.leaves(cache)[0])
                return cache
            specs.append(ProgramSpec(_restore_program_key(pb), "restore",
                                     run_restore))
    return specs


#: test seam: when set, the warm thread calls this BEFORE running the
#: lattice (tests park it on an Event to observe the warming window
#: deterministically).  Never set in production.
_PRE_WARM_HOOK: Optional[Callable[[], None]] = None


class CompilePlane:
    """The engine's compile plane: lattice warmup + steady-state
    compile accounting.

    States: ``cold`` (created, not started) → ``warming`` (lattice
    running) → ``warm`` (every program compiled; ``ready_at`` set) or
    ``failed`` (a spec raised — the failure is in the snapshot; a
    background warm leaves the engine serving with lazy compiles, a
    synchronous one re-raises to the caller that was waiting on it).
    ``/readyz`` serves :meth:`snapshot` and gates only on
    ``cold``/``warming``
    (:class:`~synapseml_tpu.resilience.health.HealthState.set_warmup`),
    so ``is_warm`` means "no longer warming", not "succeeded": read
    :attr:`status` for the verdict.
    """

    def __init__(self, engine, name: str = "llm"):
        self.engine = engine
        self.name = name
        self._lock = threading.Lock()
        self._warmed: set = set()
        self._pending: List[ProgramSpec] = []
        self._by_key: Dict[str, ProgramSpec] = {}
        self._status = "cold"
        self._error: Optional[str] = None
        self.ready_at: Optional[float] = None
        self.warmup_seconds: Optional[float] = None
        self._ready = threading.Event()
        #: set once every non-prefill program — decode, prefix copy,
        #: and (speculative engines) the whole verify lattice, any of
        #: which an admitted slot's very next step may dispatch — is
        #: warm: the floor every admission needs regardless of bucket
        self._base_ready = threading.Event()
        self._thread: Optional[threading.Thread] = None
        install_compile_listeners()
        reg = get_registry()
        self._m_stalls = reg.counter(
            "llm_compile_stalls_total",
            "serving-loop steps that paid an in-loop XLA compile (a "
            "program the warmup lattice had not yet — or never — "
            "compiled)", ("engine",))
        self._m_warmed = reg.counter(
            "llm_warmup_programs_total",
            "programs compiled by the warmup lattice", ("engine", "kind"))
        self._g_state = reg.gauge(
            "llm_warmup_state",
            "compile-plane state: 0 cold, 0.5 warming, 1 warm, "
            "-1 failed", ("engine",))
        self._g_state.set(0.0, engine=name)

    # -- state -------------------------------------------------------------
    @property
    def status(self) -> str:
        with self._lock:
            return self._status

    @property
    def is_warm(self) -> bool:
        return self._ready.is_set()

    def wait_ready(self, timeout: Optional[float] = None) -> bool:
        return self._ready.wait(timeout)

    def snapshot(self) -> Dict[str, Any]:
        """The ``/readyz`` payload: state, progress, timings."""
        with self._lock:
            out = {"state": self._status,
                   "programs_warm": len(self._warmed),
                   "programs_total": len(self._warmed) + len(self._pending)}
            if self.warmup_seconds is not None:
                out["warmup_seconds"] = round(self.warmup_seconds, 4)
            if self._error is not None:
                out["error"] = self._error
        return out

    # -- warmup ------------------------------------------------------------
    def start(self, background: bool = True) -> "CompilePlane":
        """Enumerate the lattice and compile it — on a daemon thread
        (``background=True``; gate traffic on :meth:`is_warm`) or
        inline, where a program that fails to compile or run raises
        here, after the ``failed`` state is recorded."""
        with self._lock:
            if self._status != "cold":
                return self
            self._status = "warming"
            self._pending = program_lattice(self.engine)
            self._by_key = {s.key: s for s in self._pending}
        self._g_state.set(0.5, engine=self.name)
        if background:
            self._thread = threading.Thread(
                target=self._warm_all, name=f"warmup-{self.name}",
                daemon=True)
            self._thread.start()
        else:
            self._warm_all(reraise=True)
        return self

    def _pop_next(self) -> Optional[ProgramSpec]:
        with self._lock:
            return self._pending.pop(0) if self._pending else None

    def _warm_all(self, reraise: bool = False) -> None:
        hook = _PRE_WARM_HOOK
        if hook is not None:
            hook()
        t0 = time.monotonic()
        cfg = self.engine.cfg
        warm_span = span("llm.warmup", engine=self.name).start()
        # inline, the engine is under construction and has admitted
        # nothing: the lattice threads the engine's OWN cache, which gets
        # fresh zeros afterwards (a second tree beside the weights, the
        # cache and a prefill's temporaries did not fit the chip at the
        # benchmark's largest model: 13.56 + 2.21 + 2.36 GB)
        own = reraise and not self.engine.active.any()
        try:
            # state shaped exactly like the engine's cache: the jitted
            # programs donate their cache argument, so one tree threads
            # through the whole lattice.  A background warmup runs beside
            # a serving engine and takes a scratch tree that dies with
            # this frame (transiently 2x cache memory)
            if own:
                cache, self.engine.cache = self.engine.cache, None
            else:
                cache = init_cache(cfg, self.engine.n_slots,
                                   self.engine.max_len)
            while True:
                spec = self._pop_next()
                if spec is None:
                    break
                cache = self._run_spec(spec, cache)
                with self._lock:
                    base_done = all(s.kind == "prefill"
                                    for s in self._pending)
                if base_done:
                    self._base_ready.set()
        except Exception as e:  # noqa: BLE001 — a failed background
            #                     warmup must not kill serving; programs
            #                     compile lazily and the failure is visible
            with self._lock:
                self._status = "failed"
                self._error = f"{type(e).__name__}: {e}"
            self._g_state.set(-1.0, engine=self.name)
            self._base_ready.set()
            self._ready.set()       # gate must not wedge the replica
            warm_span.set(error=self._error)
            if reraise:
                raise
            return
        finally:
            if own:
                cache = None        # the junk the programs wrote: freed
                self.engine.cache = init_cache(cfg, self.engine.n_slots,
                                               self.engine.max_len)
            warm_span.set(programs=len(self._warmed))
            warm_span.close()
        self.warmup_seconds = time.monotonic() - t0
        with self._lock:
            self._status = "warm"
        self.ready_at = time.monotonic()
        self._g_state.set(1.0, engine=self.name)
        self._base_ready.set()
        self._ready.set()
        try:
            from ...telemetry.flight import record as flight_record
            flight_record("warmup_done", engine=self.name,
                          programs=len(self._warmed),
                          seconds=round(self.warmup_seconds, 4))
        except Exception:  # noqa: BLE001 — flight is advisory
            pass

    def _run_spec(self, spec: ProgramSpec, cache):
        t0 = time.monotonic()
        before = cache_stats()["compiles"]
        fold = projection_layout(self.engine.cfg, spec.rows) if spec.rows \
            else {"cut": 0, "kept": 0}
        with span("llm.warmup.program", key=spec.key,
                  projections_fold_cut=fold["cut"],
                  projections_fold_kept=fold["kept"]) as sp:
            with compile_label(spec.key):
                cache = spec.run(cache)
            sp.set(seconds=round(time.monotonic() - t0, 4),
                   compiled=cache_stats()["compiles"] > before)
        with self._lock:
            self._warmed.add(spec.key)
        self._m_warmed.inc(1, engine=self.name, kind=spec.kind)
        try:
            from ...telemetry.flight import record as flight_record
            flight_record("warmup_program", engine=self.name,
                          program=spec.key,
                          seconds=round(time.monotonic() - t0, 4))
        except Exception:  # noqa: BLE001
            pass
        return cache

    # -- admission gating --------------------------------------------------
    def admission_ready(self, prompt_len: int) -> bool:
        """Can a prompt of ``prompt_len`` tokens admit without an
        in-loop compile?  True once the plane is warm; during warming,
        true when the non-prefill base — decode, prefix copy, and a
        speculative engine's whole verify lattice (its first step may
        dispatch any (S, span) pair) — AND the prompt's padded prefill
        bucket are compiled.  A cold bucket is bumped to the FRONT of
        the remaining lattice (:meth:`ensure_async`) so the held
        request waits one compile, not the whole tail."""
        if self._ready.is_set():
            return True
        key = _prefill_program_key(self.engine._bucket(prompt_len))
        with self._lock:
            bucket_warm = key in self._warmed
        if not bucket_warm:
            self.ensure_async(key)
            return False
        return self._base_ready.is_set()

    def ensure_async(self, key: str) -> bool:
        """Reprioritize ``key`` to compile next (warming: moves it to
        the queue head; warm-with-gap — a program the lattice missed or
        a failed warmup left cold — compiles on a fresh side thread
        with its own scratch state).  Returns True when the program is
        already warm."""
        with self._lock:
            if key in self._warmed:
                return True
            spec = self._by_key.get(key)
            if spec is None:
                return False              # not a lattice program
            if self._status == "warming":
                if spec in self._pending:
                    self._pending.remove(spec)
                    self._pending.insert(0, spec)
                # else: the warm thread is compiling it right now
                return False
            if spec in self._pending:     # failed warmup left a tail
                self._pending.remove(spec)

        def side():
            try:
                cache = init_cache(self.engine.cfg, self.engine.n_slots,
                                   self.engine.max_len)
                self._run_spec(spec, cache)
            except Exception:  # noqa: BLE001 — lazy compile still works
                pass
        threading.Thread(target=side, daemon=True,
                         name=f"warmup-side-{self.name}").start()
        return False

    # -- steady-state accounting -------------------------------------------
    def step_region(self, key: str):
        """Context manager the engine wraps each jitted serving call
        in: labels any compile inside it with ``key`` (feeding
        ``llm_compile_seconds{program}``) and counts an actual backend
        compile as an in-loop stall (``llm_compile_stalls_total``) —
        detection is by the process compile tally, so a program some
        OTHER engine already compiled is correctly not a stall."""
        return _StepRegion(self, key)


class _StepRegion:
    __slots__ = ("plane", "key", "_label_cm", "_before")

    def __init__(self, plane: CompilePlane, key: str):
        self.plane = plane
        self.key = key

    def __enter__(self):
        self._before = cache_stats()["compiles"]
        self._label_cm = compile_label(self.key)
        self._label_cm.__enter__()
        return self

    def __exit__(self, *exc):
        self._label_cm.__exit__(*exc)
        if exc[0] is None \
                and cache_stats()["compiles"] > self._before:
            plane = self.plane
            with plane._lock:
                fresh = self.key not in plane._warmed
                plane._warmed.add(self.key)
            if fresh:
                plane._m_stalls.inc(1, engine=plane.name)
                try:
                    from ...telemetry.flight import record as flight_record
                    flight_record("compile_stall", engine=plane.name,
                                  program=self.key)
                except Exception:  # noqa: BLE001
                    pass
        return False
