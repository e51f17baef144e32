"""Tier-1 guard: every bench/multichip artifact in the repo root must be
parseable JSON, so a truncated write (a driver-captured stdout line cut
off, ``"parsed"`` null — how round 5's headline was lost) is caught at
commit time instead of at read time rounds later.

Artifacts are additionally held to the inner-record standard: when the
driver wrapper carries a ``parsed`` field it must be a JSON object, and
a ``tail`` that looks like it carries a JSON line must end in one that
parses.
"""

import glob
import json
import os

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the continuous-batching serving block: when a bench record carries
#: ANY ``llmserve_`` key it must carry the full acceptance-criteria set
#: (throughput pair + ratio, TTFT percentiles, per-token latency ratio,
#: slot occupancy, admission/eviction counters) so a partially-failed
#: serving leg can't masquerade as a complete measurement
LLMSERVE_REQUIRED = (
    "llmserve_continuous_tokens_per_sec",
    "llmserve_static8_tokens_per_sec",
    "llmserve_throughput_ratio",
    "llmserve_continuous_ttft_p50_ms",
    "llmserve_continuous_ttft_p95_ms",
    "llmserve_continuous_ttft_p99_ms",
    "llmserve_token_latency_ratio_p95",
    "llmserve_slot_occupancy",
    "llmserve_admissions_total",
    "llmserve_evictions_total",
)

#: the continuous+spec pair (ISSUE 12): when a record carries ANY
#: ``llmserve_spec_`` key it must carry the whole paired set —
#: trace throughput + TTFT/latency percentiles, the accepted-tokens
#: headline, acceptance/hit-rate context, and BOTH throughput ratios
#: with the step-cost honesty field that relates them — so a
#: partially-failed spec leg can't ship a tokens/step claim alone
#: the bare-vs-traced serving pair (ISSUE 13): an overhead claim must
#: ship with both sides of the pair that produced it
LLMSERVE_TRACE_REQUIRED = (
    "llmserve_trace_overhead_pct",
    "llmserve_trace_bare_step_ms",
    "llmserve_trace_traced_step_ms",
)

#: the session-survivability plane (ISSUE 17): when a record carries
#: ANY ``kvtier_`` key it must carry the whole set — the restore-vs-
#: cold TTFT pair with the admit counts that produced it, arena
#: capacity, spill/restore counts, and the journal-failover recovery
#: time — so a partially-failed survivability leg can't ship a restore
#: win without its cold anchor
KVTIER_REQUIRED = (
    "kvtier_restore_ttft_p50_ms",
    "kvtier_restore_ttft_p95_ms",
    "kvtier_cold_ttft_p50_ms",
    "kvtier_cold_ttft_p95_ms",
    "kvtier_restored_admits",
    "kvtier_cold_admits",
    "kvtier_sessions_per_gb",
    "kvtier_spills",
    "kvtier_restores",
    "kvtier_journal_replay_recovery_s",
)

#: the flat-vs-planned routing pair (ISSUE 14): a record carrying ANY
#: ``comms_topo_`` key must carry the whole paired set — both sides of
#: the large (int8 flat vs hierarchical) and small (f32 flat vs tree)
#: routing pairs, the per-strategy plan-count histogram, and the
#: strategy-labeled wire bytes — so a partially-failed routing leg
#: cannot ship a speedup claim without its anchors (CPU caveat lives in
#: the leg docstring: the shared-memory wire means the routing win
#: needs real ICI/DCN)
COMMS_TOPO_REQUIRED = (
    "comms_topo_devices",
    "comms_topo_hosts",
    "comms_topo_large_flat_ms",
    "comms_topo_large_planned_ms",
    "comms_topo_small_flat_ms",
    "comms_topo_small_planned_ms",
    "comms_topo_routing_speedup_large",
    "comms_topo_routing_speedup_small",
    "comms_topo_plans_flat",
    "comms_topo_plans_ring",
    "comms_topo_plans_tree",
    "comms_topo_plans_hierarchical",
    "comms_topo_wire_bytes_flat",
    "comms_topo_wire_bytes_hierarchical",
)

#: the compile-plane warmup sweep (ISSUE 15): a record carrying ANY
#: ``llmserve_warmup_`` key must carry the whole paired set — the
#: cold-vs-warm TTFT p99 pair over the same arrival trace WITH both
#: legs' in-loop compile counts (the warm leg's must be zero — the pin
#: lives in test_llm_warmup, the schema just refuses a lone claim),
#: the warmup cost/size, and the cache-on first-vs-second engine
#: construction pair with its speedup and the second child's hit count
#: — so a partially-failed warmup leg cannot ship a TTFT win without
#: its cold anchor or a cache claim without both constructions
LLMSERVE_WARMUP_REQUIRED = (
    "llmserve_warmup_seconds",
    "llmserve_warmup_programs",
    "llmserve_warmup_cold_ttft_p99_s",
    "llmserve_warmup_warm_ttft_p99_s",
    "llmserve_warmup_cold_inloop_compiles",
    "llmserve_warmup_warm_inloop_compiles",
    "llmserve_warmup_cache_first_construct_s",
    "llmserve_warmup_cache_second_construct_s",
    "llmserve_warmup_cache_speedup",
    "llmserve_warmup_cache_second_hits",
)

#: the SLO-driven autoscaler sweep (ISSUE 16): a record carrying ANY
#: ``autoscale_`` key must carry the whole paired set — autoscaled AND
#: static-provisioned attainment + chip-seconds over the same trace
#: (with the savings they imply), the decision-mix counters with the
#: flight-recorded count that must back them, and the chip-budget
#: arbiter block (yield/reclaim moves, final training shape, the
#: durable-step and zero-drop honesty bits) — so a partially-failed
#: autoscale leg cannot ship a chip-savings claim without its static
#: anchor or an arbiter claim without its loss accounting
AUTOSCALE_REQUIRED = (
    "autoscale_requests",
    "autoscale_attainment",
    "autoscale_shed_requests",
    "autoscale_chip_seconds",
    "autoscale_peak_replicas",
    "autoscale_grow_decisions",
    "autoscale_shrink_decisions",
    "autoscale_hold_decisions",
    "autoscale_flight_decisions",
    "autoscale_static_attainment",
    "autoscale_static_chip_seconds",
    "autoscale_chip_savings_pct",
    "autoscale_trace_seconds",
    "autoscale_arbiter_total_chips",
    "autoscale_arbiter_yields",
    "autoscale_arbiter_reclaims",
    "autoscale_arbiter_training_final_ranks",
    "autoscale_arbiter_training_state_ok",
    "autoscale_arbiter_serving_answered",
    "autoscale_arbiter_serving_dropped",
)

#: the multi-tenant QoS plane (ISSUE 18): a record carrying ANY
#: ``qos_`` key must carry the whole set — the victim-TTFT triple
#: (solo / FIFO-aggregate / QoS) with BOTH ratios, the preemption and
#: flood-budget-shed counts, per-tenant attainment, and the weighted
#: share-convergence block with its fairness indices — so a partially-
#: failed QoS leg cannot ship an isolation win without its FIFO anchor
#: or a share claim without its error-vs-weights honesty field
QOS_REQUIRED = (
    "qos_victim_ttft_p50_ms_solo",
    "qos_victim_ttft_p99_ms_solo",
    "qos_victim_ttft_p99_ms_fifo",
    "qos_victim_ttft_p99_ms_qos",
    "qos_victim_ttft_ratio_fifo",
    "qos_victim_ttft_ratio_qos",
    "qos_preemptions",
    "qos_flood_budget_sheds",
    "qos_victim_attainment_qos",
    "qos_flood_attainment_qos",
    "qos_share_heavy",
    "qos_share_light",
    "qos_share_target_heavy",
    "qos_share_err_pct",
    "qos_fairness_jain_raw",
    "qos_fairness_jain_weighted",
    "qos_probes",
    "qos_flood_burst",
)

#: the disaggregated prefill/decode plane (ISSUE 19): a record carrying
#: ANY ``disagg_`` key must carry the whole set — the decode-side admit
#: TTFT pair (disagg vs colocated) with the end-to-end honesty anchor,
#: EVERY handoff outcome counter in the closed set (a lone ``ok`` count
#: can't hide attributed degradations), the token-exactness count with
#: the turn total it must equal, per-phase utilization, and both sides
#: of the independent-resize demonstration — so a partially-failed
#: disagg leg cannot ship an admit win without its colocated anchor or
#: an outcome claim without the full attribution
DISAGG_REQUIRED = (
    "disagg_ttft_p50_ms",
    "disagg_ttft_p99_ms",
    "disagg_colocated_ttft_p50_ms",
    "disagg_colocated_ttft_p99_ms",
    "disagg_admit_speedup_p50",
    "disagg_e2e_ttft_p50_ms",
    "disagg_e2e_ttft_p99_ms",
    "disagg_handoffs_ok",
    "disagg_handoffs_corrupt",
    "disagg_handoffs_timeout",
    "disagg_handoffs_expired",
    "disagg_handoffs_fallback",
    "disagg_prefill_util",
    "disagg_decode_util",
    "disagg_sessions",
    "disagg_turns",
    "disagg_token_exact_turns",
    "disagg_prefill_replicas_before",
    "disagg_prefill_replicas_after",
    "disagg_decode_replicas_before",
    "disagg_decode_replicas_after",
)

#: the self-tuning performance plane (ISSUE 20): a record carrying ANY
#: ``autotune_`` key must carry the whole set — every search space's
#: trial count, winner timing, and winner config (null when nothing was
#: measurable on the backend), the table size, and BOTH sides of the
#: cost-model story (the fitted α-β, the fitted crossover, AND the spec
#: constant it replaces with their ratio) — so a partially-failed
#: autotune leg cannot ship a fitted cutoff without the measured fit it
#: came from, or a winner claim without its measured milliseconds
AUTOTUNE_REQUIRED = (
    "autotune_paged_attn_tile_trials",
    "autotune_paged_attn_tile_ms",
    "autotune_paged_attn_tile_winner_tile",
    "autotune_gbdt_hist_chunk_trials",
    "autotune_gbdt_hist_chunk_ms",
    "autotune_gbdt_hist_chunk_winner_chunk",
    "autotune_llm_bucket_grid_trials",
    "autotune_llm_bucket_grid_ms",
    "autotune_llm_bucket_grid_winner_min_bucket",
    "autotune_int8_chunk_trials",
    "autotune_int8_chunk_ms",
    "autotune_int8_chunk_winner_chunk",
    "autotune_total_trials",
    "autotune_table_bytes",
    "autotune_costmodel_alpha_us",
    "autotune_costmodel_beta_us_per_mib",
    "autotune_costmodel_fitted_cutoff_bytes",
    "autotune_costmodel_spec_cutoff_bytes",
    "autotune_costmodel_cutoff_ratio",
)

LLMSERVE_SPEC_REQUIRED = (
    "llmserve_spec_tokens_per_sec",
    "llmserve_spec_tokens_per_step",
    "llmserve_spec_acceptance_rate",
    "llmserve_spec_draft_hit_rate",
    "llmserve_spec_ttft_p50_ms",
    "llmserve_spec_ttft_p95_ms",
    "llmserve_spec_token_p95_ms",
    "llmserve_spec_slot_occupancy",
    "llmserve_spec_step_cost_ratio",
    "llmserve_spec_throughput_ratio",
    "llmserve_spec_throughput_ratio_step_normalized",
)


def _artifact_paths():
    paths = []
    for pattern in ("BENCH_*.json", "MULTICHIP_*.json"):
        paths.extend(glob.glob(os.path.join(REPO_ROOT, pattern)))
    return sorted(paths)


def test_artifacts_exist():
    assert _artifact_paths(), "no bench artifacts found in repo root"


@pytest.mark.parametrize("path", _artifact_paths(),
                         ids=[os.path.basename(p) for p in _artifact_paths()])
def test_artifact_parses(path):
    with open(path, "r", encoding="utf-8") as f:
        obj = json.load(f)        # raises on any truncated/corrupt file
    name = os.path.basename(path)
    if isinstance(obj, dict) and "parsed" in obj:
        assert isinstance(obj["parsed"], dict), (
            f"{name}: driver wrapper carries parsed=null — the inner "
            "bench line was truncated or unparseable")
    if isinstance(obj, dict) and isinstance(obj.get("tail"), str):
        lines = [ln for ln in obj["tail"].strip().splitlines()
                 if ln.lstrip().startswith("{")]
        if lines:
            json.loads(lines[-1])     # the bench record itself must parse


def _bench_records():
    """Every parseable bench record (inner ``parsed`` dict, or the
    top-level object when there is no driver wrapper)."""
    records = []
    for path in _artifact_paths():
        with open(path, "r", encoding="utf-8") as f:
            try:
                obj = json.load(f)
            except ValueError:
                continue              # test_artifact_parses owns this
        if isinstance(obj, dict):
            rec = obj.get("parsed") if isinstance(obj.get("parsed"),
                                                  dict) else obj
            records.append((os.path.basename(path), rec))
    return records


def test_roofline_blocks_paired_and_complete():
    """Same schema discipline as the llmserve sweep: a record carrying
    ANY ``*_roofline_*`` key must carry the FULL paired block — both the
    ``_before`` and ``_after`` side for that leg, each a dict with
    exactly the canonical field set (bytes_per_sample / flops_per_sample
    / compute_ms / bandwidth_ms / measured_ms /
    frac_of_bandwidth_roofline), every field numeric or null — so a
    half-captured pair can't masquerade as a before/after measurement."""
    import re

    from synapseml_tpu.telemetry.roofline import check_roofline_block

    pat = re.compile(r"^(.+)_roofline_(before|after)$")
    for name, rec in _bench_records():
        for key in rec:
            m = pat.match(key)
            if not m:
                assert "_roofline_" not in key, (
                    f"{name}: {key} looks roofline-shaped but is neither "
                    "_before nor _after")
                continue
            leg, side = m.group(1), m.group(2)
            other = f"{leg}_roofline_" + ("after" if side == "before"
                                          else "before")
            assert other in rec, (
                f"{name}: {key} present without its pair {other}")
            try:
                check_roofline_block(rec[key])
            except ValueError as e:
                raise AssertionError(f"{name}: {key}: {e}") from None


def _labeled_partial(rec):
    """A ``--only`` run with no prior BENCH_latest.json to merge over
    stamps its record ``metric: "partial bench (--only ...)"`` — a
    deliberate, labeled partial, exempt from block-completeness (the
    label IS the honesty marker; full sweeps stay held to the full set)."""
    return str(rec.get("metric", "")).startswith("partial bench")


def test_llmserve_fields_complete():
    """A record carrying any continuous-batching serving field carries
    the whole set, each numeric or null (roofline blocks are dicts by
    design — their schema is owned by the paired-roofline sweep)."""
    for name, rec in _bench_records():
        if not any(k.startswith("llmserve_") for k in rec) \
                or _labeled_partial(rec):
            continue
        missing = [k for k in LLMSERVE_REQUIRED if k not in rec]
        assert not missing, f"{name}: incomplete llmserve block: {missing}"
        bad = [k for k in rec if k.startswith("llmserve_")
               and "_roofline_" not in k
               and rec[k] is not None
               and not isinstance(rec[k], (int, float))]
        assert not bad, f"{name}: non-numeric llmserve fields: {bad}"


def test_llmserve_spec_fields_complete():
    """ISSUE 12: a record carrying any ``llmserve_spec_`` field (the
    continuous+spec pair) carries the WHOLE set, each numeric or null
    — the PR 8/11 pattern (numerics are already swept by
    test_llmserve_fields_complete via the shared prefix)."""
    for name, rec in _bench_records():
        if not any(k.startswith("llmserve_spec_") for k in rec) \
                or _labeled_partial(rec):
            continue
        missing = [k for k in LLMSERVE_SPEC_REQUIRED if k not in rec]
        assert not missing, (
            f"{name}: incomplete llmserve_spec block: {missing}")


def test_llmserve_warmup_fields_complete():
    """ISSUE 15: a record carrying any ``llmserve_warmup_`` field (the
    cold-vs-warm serving pair + the persistent-cache construction
    pair) carries the WHOLE set, each numeric or null (numerics swept
    by test_llmserve_fields_complete via the shared prefix)."""
    for name, rec in _bench_records():
        if not any(k.startswith("llmserve_warmup_") for k in rec) \
                or _labeled_partial(rec):
            continue
        missing = [k for k in LLMSERVE_WARMUP_REQUIRED if k not in rec]
        assert not missing, (
            f"{name}: incomplete llmserve_warmup block: {missing}")


def test_autoscale_fields_complete():
    """ISSUE 16: a record carrying any ``autoscale_`` field (the
    autoscaled-vs-static serving pair + the chip-budget arbiter block)
    carries the WHOLE set, each numeric or null."""
    for name, rec in _bench_records():
        scale_keys = [k for k in rec if k.startswith("autoscale_")]
        if not scale_keys or _labeled_partial(rec):
            continue
        missing = [k for k in AUTOSCALE_REQUIRED if k not in rec]
        assert not missing, f"{name}: incomplete autoscale block: {missing}"
        bad = [k for k in scale_keys
               if rec[k] is not None
               and not isinstance(rec[k], (int, float))]
        assert not bad, f"{name}: non-numeric autoscale fields: {bad}"


def test_llmserve_trace_pair_complete():
    """ISSUE 13: a record carrying any ``llmserve_trace_`` field (the
    bare-vs-traced serving observability pair) carries the WHOLE
    triple — overhead % plus both per-step timings — each numeric or
    null (numerics already swept by test_llmserve_fields_complete via
    the shared prefix)."""
    for name, rec in _bench_records():
        if not any(k.startswith("llmserve_trace_") for k in rec) \
                or _labeled_partial(rec):
            continue
        missing = [k for k in LLMSERVE_TRACE_REQUIRED if k not in rec]
        assert not missing, (
            f"{name}: incomplete llmserve_trace pair: {missing}")


def test_kvtier_fields_complete():
    """ISSUE 17: a record carrying any ``kvtier_`` field (the session-
    survivability plane) carries the WHOLE set, each numeric or null —
    no restore-TTFT claim without its cold anchor and the counts that
    produced both sides."""
    for name, rec in _bench_records():
        kv_keys = [k for k in rec if k.startswith("kvtier_")]
        if not kv_keys or _labeled_partial(rec):
            continue
        missing = [k for k in KVTIER_REQUIRED if k not in rec]
        assert not missing, f"{name}: incomplete kvtier block: {missing}"
        bad = [k for k in kv_keys
               if rec[k] is not None
               and not isinstance(rec[k], (int, float))]
        assert not bad, f"{name}: non-numeric kvtier fields: {bad}"


def test_qos_fields_complete():
    """ISSUE 18: a record carrying any ``qos_`` field (the multi-tenant
    QoS plane) carries the WHOLE set, each numeric or null — no victim
    isolation claim without its FIFO-aggregate anchor, no share claim
    without its error-vs-weights field."""
    for name, rec in _bench_records():
        qos_keys = [k for k in rec if k.startswith("qos_")]
        if not qos_keys or _labeled_partial(rec):
            continue
        missing = [k for k in QOS_REQUIRED if k not in rec]
        assert not missing, f"{name}: incomplete qos block: {missing}"
        bad = [k for k in qos_keys
               if rec[k] is not None
               and not isinstance(rec[k], (int, float))]
        assert not bad, f"{name}: non-numeric qos fields: {bad}"


def test_disagg_fields_complete():
    """ISSUE 19: a record carrying any ``disagg_`` field (the
    disaggregated prefill/decode plane) carries the WHOLE set, each
    numeric or null — no admit-TTFT win without its colocated anchor,
    no handoff claim without every outcome counter in the closed set."""
    for name, rec in _bench_records():
        disagg_keys = [k for k in rec if k.startswith("disagg_")]
        if not disagg_keys or _labeled_partial(rec):
            continue
        missing = [k for k in DISAGG_REQUIRED if k not in rec]
        assert not missing, f"{name}: incomplete disagg block: {missing}"
        bad = [k for k in disagg_keys
               if rec[k] is not None
               and not isinstance(rec[k], (int, float))]
        assert not bad, f"{name}: non-numeric disagg fields: {bad}"


def test_autotune_fields_complete():
    """ISSUE 20: a record carrying any ``autotune_`` field (the
    self-tuning plane's measured sweep) carries the WHOLE set, each
    numeric or null — no fitted cost-model cutoff without the α-β fit
    it came from, no winner config without its measured trials."""
    for name, rec in _bench_records():
        tune_keys = [k for k in rec if k.startswith("autotune_")]
        if not tune_keys or _labeled_partial(rec):
            continue
        missing = [k for k in AUTOTUNE_REQUIRED if k not in rec]
        assert not missing, f"{name}: incomplete autotune block: {missing}"
        bad = [k for k in tune_keys
               if rec[k] is not None
               and not isinstance(rec[k], (int, float))]
        assert not bad, f"{name}: non-numeric autotune fields: {bad}"


def test_comms_topo_fields_complete():
    """ISSUE 14: a record carrying any ``comms_topo_`` field (the
    flat-vs-planned routing pair) carries the WHOLE set, each numeric
    or null (``comms_topo_error`` is the labeled child-failure marker,
    string by design — a record carrying it is exempt, like the
    ``--only`` partial label)."""
    for name, rec in _bench_records():
        topo_keys = [k for k in rec if k.startswith("comms_topo_")]
        if not topo_keys or _labeled_partial(rec) \
                or "comms_topo_error" in rec:
            continue
        missing = [k for k in COMMS_TOPO_REQUIRED if k not in rec]
        assert not missing, f"{name}: incomplete comms_topo block: {missing}"
        bad = [k for k in topo_keys
               if rec[k] is not None
               and not isinstance(rec[k], (int, float))]
        assert not bad, f"{name}: non-numeric comms_topo fields: {bad}"


def test_llmserve_decode_requires_paired_roofline():
    """ISSUE 11: ANY ``llmserve_decode_*`` key (the paged-vs-dense
    decode measurement) requires the FULL paired roofline block —
    ``llmserve_decode_roofline_before`` AND ``_after``, each holding
    the canonical numeric-or-null field set — plus a numeric-or-null
    ``llmserve_decode_bytes_reduction``, so a partially-failed paged
    leg cannot ship a bytes claim without its dense anchor."""
    from synapseml_tpu.telemetry.roofline import check_roofline_block

    for name, rec in _bench_records():
        if not any(k.startswith("llmserve_decode_") for k in rec):
            continue
        for side in ("before", "after"):
            key = f"llmserve_decode_roofline_{side}"
            assert key in rec, (
                f"{name}: llmserve_decode_* present without {key}")
            try:
                check_roofline_block(rec[key])
            except ValueError as e:
                raise AssertionError(f"{name}: {key}: {e}") from None
        assert "llmserve_decode_bytes_reduction" in rec, (
            f"{name}: paged decode pair without its bytes_reduction")
        red = rec["llmserve_decode_bytes_reduction"]
        assert red is None or isinstance(red, (int, float)), (
            f"{name}: non-numeric llmserve_decode_bytes_reduction: {red!r}")
