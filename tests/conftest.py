"""Test session setup: simulate an 8-device TPU slice on CPU.

The reference runs all unit tests on a shared local-mode Spark session
(``master=local[*]``, reference: core/test/base/TestBase.scala:54-71); our
analogue is JAX's host-platform device-count override — 8 virtual CPU
devices form the mesh that ICI collectives ride in tests.
"""

import os

# force CPU whatever the ambient platform: unit tests run on the simulated
# slice (Pallas kernels in interpret mode); the chip is reached only through
# `python chip_smoke.py`, one process per chip
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# a jax imported before this file (a plugin, PYTHONSTARTUP) has already
# snapshotted the environment: set the live config too
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# -- slow marking + sharding -------------------------------------------------
#
# The reference shards long suites into split1/split2/split3 source dirs so CI
# agents run them in parallel (reference: lightgbm/src/test/scala/.../split1,
# pipeline.yaml:455-640).  The analogue here: a central `slow` mark (fast dev
# path: `pytest -m "not slow"`, target < 3 min) and a deterministic
# `--shard i/n` option that partitions the collected items, so
# `pytest --shard 1/3 & pytest --shard 2/3 & pytest --shard 3/3` covers the
# full suite across agents.

#: whole modules that are slow (subprocess examples recompile jit programs)
SLOW_MODULES = {"test_examples"}

#: individual tests > ~4 s on the 8-device CPU mesh (from --durations)
SLOW_TESTS = {
    "test_resume_matches_uninterrupted",
    "test_generated_suite_passes",
    "test_generated_suite_catches_stub_drift",
    "test_deep_text_classifier_moe",
    "test_tp_matches_dp_training",
    "test_deep_vision_classifier_learns",
    "test_zero1_optimizer_sharding_matches_replicated",
    "test_moe_expert_parallel_training",
    "test_deep_text_classifier_learns",
    "test_deep_text_classifier_zero1_flag",
    "test_deep_text_classifier_remat_flag",
    "test_remat_identical_gradients",
    "test_text_model_save_load",
    "test_deep_text_nondefault_labels",
    "test_moe_matches_dense_structure",
    "test_greedy_matches_argmax_chain",
    "test_llm_transformer_stage",
    "test_tp_sharded_generation",
    "test_eos_pads_after_stop",
    "test_cached_decode_matches_full_forward",
    "test_deep_text_classifier_checkpoint_fine_tune",
    "test_bert_import_preserves_tp_sharding",
    "test_bert_import_matches_hf_forward",
    "test_llama_import_matches_hf_forward",
    "test_null_effect_not_significant",
    "test_recovers_known_ate",
    "test_heterogeneous_effects_ordered",
    "test_recovers_group_effect_magnitudes",
    "test_random_search_improves",
    "test_unreferenced_model_gets_default_trial",
    "test_grid_search_all_trials",
    "test_picks_better_model",
    "test_voting_parallel_close_to_data_parallel",
    "test_distributed_matches_single_device",
    "test_regression_rmse",
    "test_sample_weights_shift_model",
    "test_depthwise_matches_lossguide_quality",
    "test_model_serving_end_to_end",
    "test_pipeline_gradients_match",
    "test_keyword_attribution",
}

#: fuzzing classes for heavyweight estimators
SLOW_CLASSES = {"TestDeepTextFuzzing", "TestDeepVisionFuzzing"}

#: (class, test) pairs slow only in one suite — the invalid-input axis
#: poisons labels, which flips TrainClassifier/TrainRegressor's wrapped
#: GBDT into a fresh multiclass compile per poison kind (~3 min total)
SLOW_CLASS_TESTS = {
    ("TestTrainClassifier", "test_invalid_input_fuzzing"),
    ("TestTrainRegressor", "test_invalid_input_fuzzing"),
}

#: measured fast-path wall-clock per module (seconds, 2-core CI host,
#: warm XLA cache).  Collection is reordered CHEAP MODULES FIRST (stable
#: within a module) so a wall-clock-capped CI run — the tier-1 verify
#: runs under `timeout 870` — executes the maximal number of tests
#: before the cap instead of burning the budget on the heavy GBDT
#: modules mid-alphabet.  Unlisted modules default to mid-weight.
MODULE_COST_S = {
    "test_plot": 1, "test_automl": 1,
    "test_native": 1, "test_batchers": 1, "test_services": 1,
    "test_exploratory_iforest": 1, "test_parallel": 1, "test_codegen": 1,
    "test_recommendation": 1, "test_nn": 2, "test_cyber": 2,
    "test_io_files": 2, "test_online_generic": 2, "test_core": 2,
    "test_onnx": 3, "test_io_serving": 4, "test_checkpoint": 5,
    "test_resilience": 25, "test_rowguard": 20, "test_gang": 30,
    "test_causal": 6, "test_telemetry": 6, "test_explainers": 7,
    "test_online": 9, "test_dl": 13, "test_gbdt_categorical": 14,
    "test_pipeline_parallel": 17, "test_ops": 18,
    "test_benchmark_fixtures": 20, "test_colstore_streaming": 26,
    "test_multiprocess": 40, "test_checkpoint_import": 52,
    "test_llm_serving": 55, "test_llm_paged": 26, "test_llm_spec": 35,
    "test_llm_warmup": 18,
    "test_serving_obs": 14, "test_collective_planner": 25,
    "test_autotune": 8, "test_chip_smoke": 12,
    "test_autoscaler": 8, "test_disagg": 40,
    "test_perf_roofline": 150,
    "test_llm": 78, "test_gbdt_efb": 86, "test_onnx_resnet50": 89,
    "test_gbdt_monotone": 90, "test_gbdt": 98, "test_examples": 200,
    "test_gbdt_two_level": 375,
}
_DEFAULT_COST_S = 10


def pytest_addoption(parser):
    parser.addoption(
        "--shard", default=None,
        help="i/n: run the i-th (1-based) of n deterministic suite shards")


def pytest_collection_modifyitems(config, items):
    slow = pytest.mark.slow
    for item in items:
        module = item.nodeid.split("::", 1)[0].rsplit("/", 1)[-1][:-3]
        base_name = item.name.split("[", 1)[0]
        cls = item.cls.__name__ if item.cls else ""
        if (module in SLOW_MODULES or base_name in SLOW_TESTS
                or cls in SLOW_CLASSES
                or (cls, base_name) in SLOW_CLASS_TESTS):
            item.add_marker(slow)

    # cheap-modules-first ordering (stable: in-module order preserved)
    def _module_cost(item):
        module = item.nodeid.split("::", 1)[0].rsplit("/", 1)[-1][:-3]
        return MODULE_COST_S.get(module, _DEFAULT_COST_S)

    items.sort(key=_module_cost)

    shard = config.getoption("--shard")
    if shard:
        i, n = (int(x) for x in shard.split("/"))
        assert 1 <= i <= n, f"--shard {shard}: need 1 <= i <= n"
        ordered = sorted(items, key=lambda it: it.nodeid)
        keep_ids = {it.nodeid for k, it in enumerate(ordered)
                    if k % n == i - 1}
        kept = [it for it in items if it.nodeid in keep_ids]
        deselected = [it for it in items if it.nodeid not in keep_ids]
        if deselected:
            config.hook.pytest_deselected(items=deselected)
            items[:] = kept


@pytest.fixture
def fault_registry():
    """The process-wide fault registry, cleared and re-seeded around each
    test so injection schedules (probability draws, jittered backoffs
    recorded in ``sleep_log``) are reproducible run to run.  ``no_sleep``
    records backoffs without sleeping them — fault tests assert the
    schedule, not the wall clock."""
    from synapseml_tpu.resilience import get_faults
    reg = get_faults()
    reg.clear()
    reg.seed(20260803)
    reg.no_sleep = True
    rank_before = reg.rank
    yield reg
    reg.clear()
    reg.rank = rank_before   # rank-gating tests must not leak identity


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def devices8():
    import jax
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 simulated devices, got {devs}"
    return devs[:8]


@pytest.fixture
def every_prefill_tiled(monkeypatch):
    """Toy prefills through the Pallas prefill kernel, in several blocks: the
    threshold under which ``prefill_geometry`` keeps the plain path is put
    at zero and its tile at 16 score rows by 16 keys at most.  The prefill
    program's jit cache keys on (model, backend) and not on those numbers,
    so it is emptied on both sides of the test."""
    from synapseml_tpu.models.llm import pallas_attn, slots
    monkeypatch.setattr(pallas_attn, "_PREFILL_MIN_SCORE_BYTES", 0)
    monkeypatch.setattr(pallas_attn, "_PREFILL_ROWS", 16)
    monkeypatch.setattr(pallas_attn, "_PREFILL_KEYS", 16)
    monkeypatch.setattr(pallas_attn, "_PREFILL_MIN_KEYS", 8)
    slots._prefill_slot_jit.clear_cache()
    yield
    slots._prefill_slot_jit.clear_cache()
