"""Measured autotuning harness + fitted collective cost model — the
measurement half of the self-tuning performance plane (ROADMAP item 3).

Five chip-side tuning remainders (Pallas paged-attention tile, GBDT
histogram chunk, prefill/span bucket grids, int8 chunk size, the
planner's link-class cost model) consolidate into ONE subsystem:

- a :class:`TuneSpace` names a search space, the REAL jitted entry
  point its candidates dispatch through (held by a tier-1 source-scan
  lint to ``warmup.REGISTERED_ENTRY_POINTS`` — no tuning of programs
  the compile plane can't warm), and a ``build()`` hook producing the
  concrete ``(candidate config, runner)`` trials for this process;
- :meth:`Autotuner.run` warms every candidate (compiles are not the
  measurement), times them through
  :meth:`StepProfiler.measure`'s alternating min-of-blocks protocol,
  and persists the winner into the
  :mod:`~synapseml_tpu.telemetry.tunetable` — every trial observable
  (``autotune_trials_total{space,outcome}`` + flight events carrying
  measured ms and cost-analysis bytes, a roofline block per winner);
- :class:`CollectiveCostModel` fits per-link α-β (latency s, s/byte)
  from measured dispatch timings across payload sizes — the synthesis
  formulation of arXiv:2110.10548, with the ring/tree baselines of
  Horovod (arXiv:1802.05799) and the quantized two-level EQuARX
  (arXiv:2506.17615) as the strategies it prices — and derives the
  planner's tree-vs-ring payload crossover from the fit.  With no fit
  loaded the model degrades to the spec constants (``spec`` source) and
  the planner's decisions stay byte-identical to the hardcoded cutoff.

The honesty rule is inherited from the table: an empty candidate set
(kernel can't run on this backend) records NOTHING; measured numbers
are real wall clock on THIS process's backend, keyed by its
``device_kind`` — a CPU interpret-mode measurement can never be
mistaken for a chip's.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
import threading
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple)

from .flight import record as flight_record
from .gangplane import StepProfiler
from .registry import get_registry
from .tunetable import TunePlane, geometry_key, get_tuneplane

__all__ = [
    "AUTOTUNE_METRICS", "TuneSpace", "Autotuner",
    "register_space", "registered_spaces", "resolve_entry_point",
    "fit_alpha_beta", "CollectiveCostModel", "COST_MODEL_SPACE",
    "COST_MODEL_GEOMETRY",
]

#: metrics this module (and the table loader) own — the metric-hygiene
#: sweep + docs contract
AUTOTUNE_METRICS = frozenset({
    "autotune_trials_total",
    "autotune_table_consults_total",
})

#: the tuning-table space/geometry the planner's fitted model loads from
COST_MODEL_SPACE = "collective_cost_model"
COST_MODEL_GEOMETRY = "link=ici"


def resolve_entry_point(spec: str):
    """``"pkg.mod:fn"`` → the function object, verified to be a REAL
    jitted entry point: it must be registered in
    ``warmup.REGISTERED_ENTRY_POINTS[pkg.mod]`` and duck-type as a jit
    wrapper (``lower`` + ``_cache_size``).  Raises ``ValueError``
    otherwise — a search space can never time a program the compile
    plane cannot warm."""
    mod_name, _, fn_name = str(spec).partition(":")
    if not mod_name or not fn_name:
        raise ValueError(f"entry point {spec!r}: want 'module:function'")
    from ..models.llm.warmup import REGISTERED_ENTRY_POINTS
    registered = REGISTERED_ENTRY_POINTS.get(mod_name)
    if registered is None or fn_name not in registered:
        raise ValueError(
            f"entry point {spec!r} is not in REGISTERED_ENTRY_POINTS — "
            "register it with the warmup lattice (models/llm/warmup.py) "
            "before tuning through it")
    mod = importlib.import_module(mod_name)
    fn = getattr(mod, fn_name, None)
    if fn is None or not (hasattr(fn, "lower")
                          and hasattr(fn, "_cache_size")):
        raise ValueError(f"entry point {spec!r} did not resolve to a "
                         "module-level jitted function")
    return fn


@dataclasses.dataclass(frozen=True)
class TuneSpace:
    """One registered search space.

    ``build(**ctx)`` returns ``(geometry, trials)`` where ``geometry``
    is the :func:`~synapseml_tpu.telemetry.tunetable.geometry_key` the
    winner is recorded under (and the one the construction site
    consults with), and ``trials`` is a list of
    ``(candidate_config, runner)`` pairs — ``runner()`` dispatches the
    entry point with the candidate applied and blocks until done.  An
    optional third element ``cost()`` returns an XLA cost-analysis dict
    (``flops``/``bytes_accessed``) for the candidate's compiled
    program, carried on the trial's flight event and the winner's
    roofline block.  An EMPTY trial list means nothing is measurable on
    this backend — the harness claims nothing.

    ``ctx`` parameterizes the geometry (a test tunes the exact tiny
    geometry its engine will consult with; the defaults are
    representative ones).
    """
    name: str
    entry_point: str
    build: Callable[..., Tuple[str, List[tuple]]]
    description: str = ""


_SPACES: Dict[str, TuneSpace] = {}
_spaces_lock = threading.Lock()
_builtin_done = False


def register_space(space: TuneSpace) -> TuneSpace:
    with _spaces_lock:
        _SPACES[space.name] = space
    return space


def registered_spaces() -> Dict[str, TuneSpace]:
    """Name → space, builtin spaces included (registered lazily; their
    ``build`` hooks import jax-heavy modules only when run)."""
    _ensure_builtin_spaces()
    with _spaces_lock:
        return dict(_SPACES)


# ---------------------------------------------------------------------------
# the harness
# ---------------------------------------------------------------------------

class Autotuner:
    """Enumerate → warm → measure → persist, one space at a time.

    Timing is :meth:`StepProfiler.measure`'s multi-leg protocol: every
    candidate runs once per block, leg order reversing block to block,
    statistic = per-candidate minimum across ``blocks`` blocks ("how
    fast CAN this candidate go" — contention only inflates a block).
    """

    def __init__(self, plane: Optional[TunePlane] = None,
                 blocks: int = 3):
        self._plane = plane
        self.blocks = max(1, int(blocks))
        self._c_trials = get_registry().counter(
            "autotune_trials_total",
            "autotune candidate trials, by search space and outcome "
            "(ok = measured; error = candidate raised; empty = nothing "
            "measurable on this backend)", ("space", "outcome"))

    @property
    def plane(self) -> TunePlane:
        return self._plane if self._plane is not None else get_tuneplane()

    def run(self, space: TuneSpace, persist: bool = True,
            **ctx: Any) -> Optional[dict]:
        """Measure every candidate of ``space`` → result dict
        (``winner``, ``measured_ms``, per-candidate ``trials_ms``,
        ``roofline``), persisting the winner into the tuning table.
        ``None`` when the space has no measurable candidates here."""
        resolve_entry_point(space.entry_point)   # fail fast, pre-measure
        geometry, trials = space.build(**ctx)
        legs: Dict[str, Callable[[], Any]] = {}
        configs: Dict[str, dict] = {}
        costs: Dict[str, Optional[dict]] = {}
        for trial in trials:
            cand, runner = trial[0], trial[1]
            cost_fn = trial[2] if len(trial) > 2 else None
            label = ",".join(f"{k}={v}" for k, v in sorted(cand.items()))
            # warm first: the compile is the lattice's job, not part of
            # the measurement; a candidate that cannot even run once is
            # an error trial, not a slow one
            try:
                runner()
            except Exception as e:
                self._c_trials.inc(1, space=space.name, outcome="error")
                flight_record("autotune_trial", space=space.name,
                              geometry=geometry, candidate=label,
                              outcome="error", error=repr(e))
                continue
            legs[label] = runner
            configs[label] = dict(cand)
            costs[label] = _safe_cost(cost_fn)
        if not legs:
            self._c_trials.inc(1, space=space.name, outcome="empty")
            flight_record("autotune_trial", space=space.name,
                          geometry=geometry, outcome="empty")
            return None

        measured = StepProfiler.measure(legs, blocks=self.blocks)
        for label, seconds in measured.items():
            self._c_trials.inc(1, space=space.name, outcome="ok")
            event = {"space": space.name, "geometry": geometry,
                     "candidate": label, "outcome": "ok",
                     "measured_ms": seconds * 1e3}
            cost = costs.get(label)
            if cost:
                event["cost_bytes"] = cost.get("bytes_accessed")
                event["cost_flops"] = cost.get("flops")
            flight_record("autotune_trial", **event)

        winner_label = min(measured, key=lambda k: measured[k])
        winner_ms = measured[winner_label] * 1e3
        result = {
            "space": space.name,
            "geometry": geometry,
            "winner": configs[winner_label],
            "measured_ms": winner_ms,
            "trial_count": len(measured),
            "trials_ms": {k: v * 1e3 for k, v in measured.items()},
            "roofline": self._winner_roofline(space.name, winner_label,
                                              measured[winner_label],
                                              costs.get(winner_label)),
        }
        if persist and self.plane.directory:
            self.plane.record(space.name, geometry, configs[winner_label],
                              winner_ms, trials=len(measured))
        return result

    def _winner_roofline(self, space_name: str, label: str,
                         seconds: float, cost: Optional[dict]) -> dict:
        """One StepProfiler step accounting the winner's measured time
        as compute (+ its cost-analysis entry when the candidate
        captured one) → the profiler's roofline-ready summary block."""
        prof = StepProfiler(f"autotune_{space_name}")
        prof.step_begin(0)
        prof._open["t_last"] -= seconds   # attribute the measured time
        prof.mark("compute")
        if cost:
            prof.costs[label] = dict(cost)
        prof.step_end()
        return prof.summary()

    def run_all(self, persist: bool = True) -> Dict[str, Optional[dict]]:
        return {name: self.run(space, persist=persist)
                for name, space in sorted(registered_spaces().items())}


def _safe_cost(cost_fn) -> Optional[dict]:
    if cost_fn is None:
        return None
    try:
        cost = cost_fn()
        return dict(cost) if cost else None
    except Exception:
        return None


def _cost_of(jitted, *args, **kw) -> Optional[dict]:
    """XLA cost analysis of a compiled call (flops / bytes_accessed),
    None where the backend doesn't expose it."""
    try:
        analysis = jitted.lower(*args, **kw).compile().cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else None
        if not analysis:
            return None
        out = {}
        for k in ("flops", "bytes accessed", "bytes_accessed"):
            if k in analysis:
                out[k.replace(" ", "_")] = float(analysis[k])
        return out or None
    except Exception:
        return None


# ---------------------------------------------------------------------------
# builtin search spaces
# ---------------------------------------------------------------------------

def _interpret_mode() -> bool:
    """Pallas kernels run in interpret mode off-TPU (the test-suite
    convention); measured ms stay honest because the table keys them by
    this process's device_kind."""
    import jax
    return jax.default_backend() != "tpu"


def _build_paged_attn_tile(max_len: int = 256, num_heads: int = 4,
                           num_kv_heads: int = 2, d_head: int = 64,
                           n_slots: int = 4, span: int = 1):
    """Candidates: every tile the VMEM/divisibility gate admits at this
    geometry; runner: one decode step of the paged kernel over full
    spans (every tile of the cache live)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..models.llm import pallas_attn

    dtype = jnp.float32
    geometry = pallas_attn.paged_geometry_key(max_len, num_kv_heads,
                                              d_head, dtype, span)
    interpret = _interpret_mode()
    rng = np.random.default_rng(0)
    q_shape = ((n_slots, num_heads, d_head) if span == 1
               else (n_slots, span, num_heads, d_head))
    q = jnp.asarray(rng.standard_normal(q_shape), dtype)
    k = jnp.asarray(rng.standard_normal(
        (n_slots, max_len, num_kv_heads, d_head)), dtype)
    v = jnp.asarray(rng.standard_normal(
        (n_slots, max_len, num_kv_heads, d_head)), dtype)
    spans = jnp.full((n_slots,), max_len, jnp.int32)
    trials = []
    for tile in pallas_attn._TILE_CANDIDATES:
        geo = pallas_attn.paged_geometry(max_len, num_heads, num_kv_heads,
                                         d_head, dtype=dtype,
                                         max_query_span=span, tile=tile)
        if geo is None:
            continue

        def runner(tile=tile):
            jax.block_until_ready(pallas_attn.paged_decode_attention(
                q, k, v, spans, tile=tile, interpret=interpret))

        def cost(tile=tile):
            return _cost_of(pallas_attn.paged_decode_attention,
                            q, k, v, spans, tile=tile, interpret=interpret)

        trials.append(({"tile": int(tile)}, runner, cost))
    return geometry, trials


def _build_gbdt_hist_chunk(num_features: int = 16, total_bins: int = 256,
                           n_slots: int = 2, n_rows: Optional[int] = None):
    """Candidates: the legal row-chunk overrides for the histogram
    kernels (``hist_chunk_ok``); runner: one node-batched histogram
    build over a PAD_MULTIPLE row block."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..models.gbdt import pallas_hist as ph

    N = int(n_rows) if n_rows else ph.PAD_MULTIPLE
    geometry = geometry_key(features=int(num_features),
                            total_bins=int(total_bins))
    interpret = _interpret_mode()
    rng = np.random.default_rng(0)
    bins_t = jnp.asarray(
        rng.integers(0, total_bins, (num_features, N)), jnp.int32)
    slot = jnp.asarray(rng.integers(0, n_slots, (N,)), jnp.int32)
    grad = jnp.asarray(rng.standard_normal(N), jnp.float32)
    hess = jnp.asarray(rng.uniform(0.5, 1.5, N), jnp.float32)
    mask = jnp.ones((N,), jnp.float32)
    vals, scales = ph.prep_hist_vals(grad, hess, mask)
    trials = []
    for chunk in (1024, 2048, 4096):
        if N % chunk or not ph.hist_chunk_ok(num_features, total_bins,
                                             n_slots, chunk):
            continue

        def runner(chunk=chunk):
            jax.block_until_ready(ph.build_hist_nodes_pallas(
                bins_t, slot, vals, scales, n_slots, total_bins,
                interpret=interpret, hist_chunk=chunk))

        def cost(chunk=chunk):
            return _cost_of(ph.build_hist_nodes_pallas,
                            bins_t, slot, vals, scales, n_slots,
                            total_bins, interpret=interpret,
                            hist_chunk=chunk)

        trials.append(({"chunk": int(chunk)}, runner, cost))
    return geometry, trials


def _build_llm_bucket_grid(max_len: int = 64, num_layers: int = 2,
                           prompt_lens: Sequence[int] = (5, 11, 23),
                           candidates: Sequence[int] = (4, 8, 16)):
    """Candidates: the bucket-grid floor (``min_bucket``); runner: an
    admit+cancel cycle over representative prompt lengths — a finer
    grid pays less prefill padding, a coarser one compiles fewer
    programs.  Heavier build than the kernel spaces (constructs one
    tiny engine per candidate), sized accordingly."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..models.llm import LlamaConfig, LlamaModel, SlotEngine

    geometry = geometry_key(max_len=int(max_len))
    cfg = LlamaConfig.tiny(num_layers=int(num_layers), max_len=int(max_len),
                           dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 8), jnp.int32))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, (int(n),)).astype(np.int32)
               for n in prompt_lens if int(n) < max_len]
    trials = []
    for mb in candidates:
        mb = int(mb)
        if mb < 1 or mb > max_len or (mb & (mb - 1)):
            continue
        eng = SlotEngine(model, variables, n_slots=1, max_len=max_len,
                         min_bucket=mb)

        def runner(eng=eng):
            for prompt in prompts:
                res = eng.admit(prompt, max_new_tokens=2)
                eng.cancel(res.slot)

        trials.append(({"min_bucket": mb}, runner))
    return geometry, trials


def _build_int8_chunk(numel: int = 1 << 18,
                      candidates: Sequence[int] = (64, 128, 256, 512,
                                                   1024)):
    """Candidates: the int8 codec's quantization-chunk size; runner: a
    full encode+decode round trip of a representative flat gradient."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..parallel import compression as comp

    numel = int(numel)
    geometry = geometry_key(numel=numel)
    rng = np.random.default_rng(0)
    flat = jnp.asarray(rng.standard_normal(numel), jnp.float32)
    trials = []
    for chunk in candidates:
        chunk = int(chunk)
        if chunk < 8 or numel % chunk:
            continue

        def runner(chunk=chunk):
            jax.block_until_ready(comp.int8_roundtrip_jit(flat, chunk))

        def cost(chunk=chunk):
            return _cost_of(comp.int8_roundtrip_jit, flat, chunk)

        trials.append(({"chunk": chunk}, runner, cost))
    return geometry, trials


def _ensure_builtin_spaces() -> None:
    global _builtin_done
    with _spaces_lock:
        if _builtin_done:
            return
        _builtin_done = True
    for space in (
        TuneSpace(
            name="paged_attn_tile",
            entry_point="synapseml_tpu.models.llm.pallas_attn:"
                        "paged_decode_attention",
            build=_build_paged_attn_tile,
            description="paged decode-attention K/V tile length"),
        TuneSpace(
            name="gbdt_hist_chunk",
            entry_point="synapseml_tpu.models.gbdt.pallas_hist:"
                        "build_hist_nodes_pallas",
            build=_build_gbdt_hist_chunk,
            description="GBDT histogram-kernel rows-per-chunk"),
        TuneSpace(
            name="llm_bucket_grid",
            entry_point="synapseml_tpu.models.llm.slots:_prefill_slot_jit",
            build=_build_llm_bucket_grid,
            description="prefill/span bucket-grid floor (min_bucket)"),
        TuneSpace(
            name="int8_chunk",
            entry_point="synapseml_tpu.parallel.compression:"
                        "int8_roundtrip_jit",
            build=_build_int8_chunk,
            description="int8 codec quantization-chunk size"),
    ):
        register_space(space)


# ---------------------------------------------------------------------------
# fitted collective cost model
# ---------------------------------------------------------------------------

def fit_alpha_beta(samples: Sequence[Tuple[float, float]]
                   ) -> Tuple[float, float]:
    """Closed-form least squares of ``t(n) = α + β·n`` over
    ``(payload_bytes, seconds)`` samples → ``(alpha_s,
    beta_s_per_byte)``.  Needs measurements at ≥ 2 distinct payload
    sizes; raises ``ValueError`` otherwise — a fit that would have to
    invent a slope is no fit (the honesty rule)."""
    pts = [(float(n), float(t)) for n, t in samples]
    if any(not math.isfinite(n) or not math.isfinite(t) for n, t in pts):
        raise ValueError("fit_alpha_beta: non-finite sample")
    if len(pts) < 2 or len({n for n, _ in pts}) < 2:
        raise ValueError(
            "fit_alpha_beta needs measurements at >= 2 distinct payload "
            f"sizes, got {len(pts)} samples")
    n = len(pts)
    mx = sum(x for x, _ in pts) / n
    my = sum(y for _, y in pts) / n
    sxx = sum((x - mx) ** 2 for x, _ in pts)
    sxy = sum((x - mx) * (y - my) for x, y in pts)
    beta = sxy / sxx
    alpha = my - beta * mx
    return alpha, beta


class CollectiveCostModel:
    """α-β pricing of collective routes, feeding the planner's
    ``_decide``.

    Per-hop transfer time is ``t(n) = α + β·n``.  A recursive-doubling
    tree over ``w`` pow-2 ranks pays ``L = log2(w)`` serial hops of the
    full payload: ``L·(α + β·n)``; a ring all-reduce pays ``2(w-1)``
    hops of ``n/w``: ``2(w-1)·(α + β·n/w)``.  The tree wins while the
    latency term dominates; the crossover payload is::

        n* = α · (2(w-1) − L) / (β · (L − 2(w-1)/w))

    (for ``w = 2`` the bandwidth coefficients tie and the tree's single
    hop always wins — the crossover is unbounded).

    ``source`` is the provenance label on every plan
    (``collective_plans_total{model=...}``): ``fitted`` = α-β from real
    measured dispatch timings via the tuning table; ``spec`` = the
    hardcoded cutoff constant + ``CHIP_ICI_BW`` table — the fallback,
    whose decisions are byte-identical to the pre-model planner.
    """

    #: "the tree always wins" sentinel cutoff (w = 2, or degenerate fits)
    UNBOUNDED = 1 << 62

    def __init__(self, alpha_s: float = 0.0,
                 beta_s_per_byte: float = 0.0,
                 source: str = "spec",
                 spec_cutoff_bytes: Optional[int] = None):
        if source not in ("fitted", "spec"):
            raise ValueError(f"cost-model source {source!r}")
        if source == "fitted":
            a, b = float(alpha_s), float(beta_s_per_byte)
            if not (math.isfinite(a) and math.isfinite(b)
                    and a >= 0.0 and b > 0.0):
                raise ValueError(
                    f"fitted cost model needs alpha >= 0 and beta > 0, got "
                    f"alpha={alpha_s!r} beta={beta_s_per_byte!r} — a flat "
                    "or negative slope cannot price bandwidth; refusing "
                    "rather than extrapolating")
        self.alpha_s = float(alpha_s)
        self.beta_s_per_byte = float(beta_s_per_byte)
        self.source = source
        self._spec_cutoff = (int(spec_cutoff_bytes)
                             if spec_cutoff_bytes is not None else None)

    @classmethod
    def fitted(cls, samples: Sequence[Tuple[float, float]]
               ) -> "CollectiveCostModel":
        a, b = fit_alpha_beta(samples)
        return cls(max(0.0, a), b, source="fitted")

    @classmethod
    def spec(cls, cutoff_bytes: int) -> "CollectiveCostModel":
        return cls(source="spec", spec_cutoff_bytes=cutoff_bytes)

    def predict_s(self, nbytes: int) -> Optional[float]:
        """Per-hop transfer seconds (fitted models only)."""
        if self.source != "fitted":
            return None
        return self.alpha_s + self.beta_s_per_byte * max(0, int(nbytes))

    def tree_cutoff_bytes(self, world: int) -> int:
        """Payloads ≤ this ride the latency-optimal tree (the planner's
        small-payload branch).  Spec models return the constant they
        were built with; fitted models derive the crossover above."""
        if self.source == "spec":
            if self._spec_cutoff is None:
                raise ValueError("spec cost model built without a cutoff")
            return self._spec_cutoff
        w = max(2, int(world))
        L = math.ceil(math.log2(w))
        ring_hops = 2 * (w - 1)
        coeff = L - ring_hops / w
        if coeff <= 0:
            return self.UNBOUNDED
        n_star = self.alpha_s * (ring_hops - L) / (self.beta_s_per_byte
                                                   * coeff)
        if not math.isfinite(n_star) or n_star >= self.UNBOUNDED:
            return self.UNBOUNDED
        return max(0, int(n_star))

    def describe(self) -> dict:
        return {"source": self.source,
                "alpha_us": self.alpha_s * 1e6,
                "beta_us_per_mib": self.beta_s_per_byte * 1e6 * (1 << 20),
                "spec_cutoff_bytes": self._spec_cutoff}
