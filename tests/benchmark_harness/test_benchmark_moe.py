"""What the Command A+ cell adds to the yardstick: ``work_moe``'s counts
against counts by hand, its five readers on a reduced trace and program spans
made by hand (nothing to read without the kernel or the counts, a known share
with them), the configuration against the catalog's row, and the tiny model
through the real runner with ``fp8`` not correct."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, peaks, span_read, work, work_moe

BENCH = harness.load_benchmark(harness.ROOT)
CELL = "command-a-plus.preamble-closed24"
PEAK = peaks.peaks("TPU v5 lite")
MS = 1_000_000
W0, H0 = 5_000_000_000, 10.0
NEW = ["expert_ffn_decode_roofline", "expert_ffn_prefill_roofline",
       "expert_ffn_decode_share", "paged_window_attention_roofline",
       "serve_mfu_moe"]


def reader(name):
    return importlib.import_module("benchmark.metrics." + name)


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(BENCH, CELL, harness.ROOT)


# -- counts by hand ----------------------------------------------------------------

def test_a_layer_by_hand(cell):
    c = cell.config
    assert work_moe.expert_params(4096, 4096) == 50_331_648
    assert work_moe.attention_params(4096, 128, 8, 128) == 142_606_336
    # attention 142.6M, router 0.5M, four shared experts 201.3M: 344.4M
    assert work_moe.layer_params(c, 0) == 344_457_216
    assert work_moe.layer_params(c, 16) == 344_457_216 + 805_306_368
    assert work_moe.layer_params(c, 128) - work_moe.layer_params(c, 0) == \
        6_442_450_944
    assert work_moe.token_flops(c) == 2.0 * 4 * 344_457_216
    assert work_moe.pair_flops(c) == 6.0 * 4096 * 4096
    assert work_moe.window_layers(c) == (1, 3)
    assert work_moe.window_layers({"num_hidden_layers": 5}) == (5, 0)


def test_the_grouped_products_bytes_and_the_windows_by_hand(cell):
    c = cell.config
    # 50 experts touched over the four layers, 96 pairs
    assert work_moe.expert_bytes(c, 50, 96) == \
        50 * 100_663_296 + 96 * (8192 + 16384 + 16384)
    w = work_moe.expert_work(c, 64, 1000)
    assert w["ops"] == 1000 * 6 * 4096 * 4096
    # a prefill's pairs are bound by the weights until 1,020 or so a touched
    # expert: 100.7 MB at 819 GB/s against 100.7 MFLOP a pair at 197 TFLOP/s
    assert work.least_seconds(w, PEAK, ops_key="ops") == \
        w["bytes"] / PEAK["hbm_bytes_per_s"]
    # 24 slots of 4,800 keys: the full layer reads them all, the three
    # window layers 4,096 of each; a row is 8 heads x 128 x 2 x 2 B
    assert work_moe.window_kv_bytes(c, 24 * 4800, 24 * 4096) == \
        4096 * (24 * 4800 + 3 * 24 * 4096)
    assert work_moe.window_kv_bytes(c, 100, 100) == 4096 * 4 * 100


# -- the readers on a reduced trace and spans made by hand --------------------------

def reduced_trace(kernel=True, paged_ms=6.0):
    """One device, 100 ms traced: two runs of the decode program (10-30,
    50-80 ms) and one of the prefill program (30-45 ms).  ``expert_ffn``
    runs 8 times in each decode run (1 ms each) and 8 times in the prefill
    (0.5 ms each); the paged kernel 4 times a decode run."""
    dec = [(W0 + 10 * MS, W0 + 30 * MS), (W0 + 50 * MS, W0 + 80 * MS)]
    pre = [(W0 + 30 * MS, W0 + 45 * MS)]
    ops = {"fusion f32[24]": {"base": "fusion", "self_ns": 1.0 * MS,
                              "total_ns": 1.0 * MS, "count": 3,
                              "intervals": []}}
    if kernel:
        iv = [(s + (2 * j + 1) * MS, s + (2 * j + 2) * MS)
              for s, _ in dec for j in range(8)]
        ops["expert_ffn bf16[448,4096]"] = {
            "base": "expert_ffn", "self_ns": 16.0 * MS, "total_ns": 16.0 * MS,
            "count": 16, "intervals": iv}
        iv = [(pre[0][0] + j * MS, pre[0][0] + (j + 0.5) * MS)
              for j in range(8)]
        ops["expert_ffn bf16[2304,4096]"] = {
            "base": "expert_ffn", "self_ns": 4.0 * MS, "total_ns": 4.0 * MS,
            "count": 8, "intervals": iv}
        ops["paged_decode_attention bf16[24,1,128,128]"] = {
            "base": "paged_decode_attention", "self_ns": paged_ms * MS,
            "total_ns": paged_ms * MS, "count": 8, "intervals": []}
    mods = {"jit__decode_step_jit": {"total_ns": 50.0 * MS, "count": 2,
                                     "intervals": list(dec)},
            "jit__prefill_slot_jit": {"total_ns": 15.0 * MS, "count": 1,
                                      "intervals": list(pre)}}
    return {"window_ns": (W0, W0 + 100 * MS), "window_s": 0.1,
            "busy_s": 0.065, "host": [],
            "devices": [{"name": "/device:TPU:0", "busy": dec[:1] + pre + dec[1:],
                         "busy_ns": 65.0 * MS, "ops": ops, "modules": mods}]}


def span(name, at, **attrs):
    return types.SimpleNamespace(name=name, start_ns=int((H0 + at) * 1e9),
                                 end_ns=int((H0 + at + 0.01) * 1e9),
                                 attrs=attrs, parent_id=None, span_id=1)


@pytest.fixture
def spans(monkeypatch):
    """Two traced steps, one traced admission, and one of each outside the
    traced part; a step of a program without the counts."""
    found = [
        span("engine.step", 0.01, slots=24, tokens=24, kv_span_sum=24 * 4800,
             kv_window_span_sum=24 * 4096, expert_pairs_held=100,
             experts_touched=50),
        span("engine.step", 0.05, slots=22, tokens=22, kv_span_sum=22 * 4700,
             kv_window_span_sum=22 * 4096, expert_pairs_held=80,
             experts_touched=44),
        span("engine.step", 0.5, slots=24, tokens=24, kv_span_sum=1,
             kv_window_span_sum=1, expert_pairs_held=9999,
             experts_touched=64),
        span("engine.admit", 0.03, prompt_tokens=4500, reused_tokens=4096,
             expert_pairs_held=1700, bucket=512, path="reuse"),
        span("engine.admit", -1.0, prompt_tokens=4500, reused_tokens=0,
             expert_pairs_held=18000, bucket=5632, path="cold")]
    monkeypatch.setattr(span_read, "spans", lambda name=None: [
        s for s in found if name is None or s.name == name])
    return found


FACTS = {"trace_host": (H0, H0 + 0.1), "records": []}


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_kernel_or_the_counts_gives_nothing(
        cell, metric, monkeypatch):
    given = dict(facts=FACTS, cell=cell, values={}, peak=PEAK, work=work,
                 chips=1)
    # the parent: no span carries a count, no kernel in the trace
    monkeypatch.setattr(span_read, "spans", lambda name=None: [
        span("engine.step", 0.01, slots=24, tokens=24, kv_span_sum=100)])
    assert reader(metric).read(trace=reduced_trace(kernel=False),
                               **given) is None
    # a dense cell's configuration has no router
    dense = harness.Cell(BENCH, "mistral-7b.chat-closed32", harness.ROOT)
    assert reader("serve_mfu_moe").read(trace=reduced_trace(), **dict(
        given, cell=dense)) is None


def test_the_kernels_time_is_told_apart_by_its_program():
    dev = reduced_trace()["devices"][0]
    assert work_moe.kernel_seconds_in(dev, "expert_ffn", "_decode_step_jit") \
        == (pytest.approx(0.016), 16)
    assert work_moe.kernel_seconds_in(dev, "expert_ffn", "_prefill_slot_jit") \
        == (pytest.approx(0.004), 8)
    assert work_moe.kernel_seconds_in(dev, "expert_ffn", "nowhere") == (0, 0)


def test_decode_roofline_and_share(cell, spans):
    got = reader("expert_ffn_decode_roofline").read(
        trace=reduced_trace(), facts=FACTS, cell=cell, peak=PEAK)
    c = cell.config
    per_step = (work_moe.expert_bytes(c, 50, 100)
                + work_moe.expert_bytes(c, 44, 80)) / 2
    # 16 calls: two a layer a step, so two steps traced
    assert got == pytest.approx(100 * (2 * per_step / 819e9) / 0.016)
    assert 0 < got < 100
    assert reader("expert_ffn_decode_share").read(trace=reduced_trace()) == \
        pytest.approx(100 * 16.0 / 50.0)


def test_prefill_roofline_takes_the_larger_of_bytes_and_operations(
        cell, spans):
    got = reader("expert_ffn_prefill_roofline").read(
        trace=reduced_trace(), facts=FACTS, cell=cell, peak=PEAK)
    w = work_moe.expert_work(cell.config, 64, 1700)
    by, op = w["bytes"] / 819e9, w["ops"] / 197e12
    assert by > op                      # an eighth of a deployment's pairs
    assert got == pytest.approx(100 * by * 1 / 0.004)
    # eight times the pairs a touched expert and the products bound it
    heavy = work_moe.expert_work(cell.config, 64, 64 * 2000)
    assert heavy["ops"] / 197e12 > heavy["bytes"] / 819e9


def test_window_roofline_counts_a_window_layer_by_its_window(cell, spans):
    got = reader("paged_window_attention_roofline").read(
        trace=reduced_trace(), facts=FACTS, cell=cell, peak=PEAK)
    per_step = 4096 * ((24 * 4800 + 22 * 4700) / 2
                       + 3 * (24 + 22) / 2 * 4096)
    # 8 calls: one a layer a step, two steps
    assert got == pytest.approx(100 * (2 * per_step / 819e9) / 0.006)
    assert 0 < got < 100
    # what the accepted reader would count: every layer's whole span
    whole = 4096 * 4 * (24 * 4800 + 22 * 4700) / 2
    assert 1.10 < whole / per_step < 1.16


def test_serve_mfu_moe_counts_what_this_chip_computes(cell, spans):
    got = reader("serve_mfu_moe").read(facts=FACTS, cell=cell, peak=PEAK,
                                       work=work)
    c = cell.config
    tokens = 24 + 22 + (4500 - 4096)            # the reused prefix is no work
    flops = tokens * work_moe.token_flops(c) \
        + (100 + 80 + 1700) * work_moe.pair_flops(c) \
        + (24 + 22 + 1) * 2.0 * 4096 * 262144
    assert got == pytest.approx(100 * flops / (0.1 * 197e12))
    assert 0 < got < 100


def test_the_new_cell_reports_what_the_benchmark_can_declare(cell):
    """The cell reports the accepted serving metrics but the two whose
    readers count another model's work.  The five readers above are files
    without an entry in ``BENCHMARK.json``: ``test_benchmark_step_overlap.py``
    pins ``step_overlap_share`` as the last of ``per_layer`` and is no model
    PR's to edit (``PERF.md`` section 7 has the entries ready).  Not on
    ``tpot_p95_ms``'s list: over some 160 requests a window its p95 spread
    past a fifth of the bound on the chip (``PERF.md`` section 2)."""
    assert [m["name"] for m in cell.end_to_end()] == \
        ["tokens_per_s", "setup_s"]
    assert {m["name"] for m in cell.per_layer()} == {
        "ttft_p50_ms", "ttft_p95_ms", "slot_occupancy", "compiles_in_window",
        "decode_step_device_ms", "prefill_device_share"}
    declared = {m["name"] for m in BENCH["per_layer"]}
    for name in NEW:
        assert name not in declared and callable(reader(name).read)
    assert cell.chips == 1 and cell.entry["traffic"] == "preamble4k-closed24"
    t = cell.traffic
    assert (t["clients"], t["shared_prefix_len"]) == (24, 4096)
    assert (t["prompt_len"]["lo"], t["prompt_len"]["hi"]) == (4128, 5120)
    assert t["prompt_len"]["hi"] + t["output_len"]["hi"] + 1 <= \
        cell.config["engine"]["max_len"]


def test_no_width_differs_from_the_catalogs_row(cell):
    """The numbers of the published ``config.json`` (the catalog's row, copied
    here), every one under its own key but those that ``reduced`` lists."""
    published = {
        "attention_bias": False, "expert_selection_fn": "sigmoid",
        "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 4096,
        "layer_norm_eps": 1e-05, "layer_switch": 4, "logit_scale": 1,
        "max_position_embeddings": 200000, "model_type": "cohere2_moe",
        "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_key_value_heads": 8,
        "num_shared_experts": 4,
        "order_of_interleaved_layers": "local_attn_first",
        "position_embedding_type": "rope_gptj",
        "prefix_dense_intermediate_size": 16384,
        "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
        "rope_theta": 50000, "rotary_pct": 1,
        "shared_expert_combination_strategy": "average",
        "sliding_window": 4096, "tf_legacy_loss": False,
        "tie_word_embeddings": True, "use_embedding_sharing": True,
        "use_gated_activation": True, "use_parallel_block": True,
        "use_parallel_embedding": False, "use_qk_norm": False,
        "vocab_size": 262144}
    c = cell.config
    for key, value in published.items():
        if key in c["reduced"]:
            assert c["published"][key] == value and c[key] != value, key
        else:
            assert c[key] == value, key
    assert c["rope_parameters"] == {"rope_theta": 50000,
                                    "rope_type": "default"}
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert c["published"]["layer_types"] == period * 8
    assert c["layer_types"] == period == c["published"]["layer_types"][:4]
    assert c["reduced"] == ["num_hidden_layers", "layer_types", "num_experts"]
    # the vocabulary is as published: no key stands in for vocab_size
    assert c["vocab_size"] == 262144 and "vocab_rows" not in c
    assert "NOT sliced" in c["reduced_why"]
    assert (c["num_experts"], c["router_experts"], c["experts_first"]) == \
        (16, 128, 0)
    assert c["deployment"]["chips_sharing_a_layer"] == 8
    for key in ("intermediate_size", "shared_experts", "routing", "window",
                "positions", "block", "dense_prefix", "weights", "engine"):
        assert key in c["assumed"], key
    assert c["engine"]["n_slots"] == 24 and c["engine"]["max_len"] == 5632
    assert c["engine"]["expect_attention_backend"] == "paged"
    assert "served_logit_gap" in c["limits"]
    assert c["check"]["controls"] == ["fp8", "no_window"]
    # both read not correct on the chip over the decided tokens (the file's
    # limits_why has the readings), so neither is merely informative
    assert "informative" not in c["check"]
    assert c["limits"]["served_logit_gap"] == 0.2
    # one shaped matrix, and the reference's comment says why; the routed
    # experts are at the others' std, so a pair left out reads at full size
    assert c["o_proj_init_std"] < 0.02 and "routed_down_init_std" not in c
    args = c["model"]["config_args"]
    assert (args["num_experts"], args["experts_held"], args["experts_first"]) \
        == ("router_experts", "num_experts", "experts_first")


def test_the_references_weights_come_from_the_seed_expert_by_expert(cell):
    ref = cell.reference()
    small = {"hidden_size": 32, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 16,
             "vocab_size": 64, "num_hidden_layers": 2, "router_experts": 8,
             "num_experts": 2, "experts_first": 4, "num_experts_per_tok": 2,
             "num_shared_experts": 2}
    a = ref.layer_weights(small, 2 ** 31 + 5, 1)
    b = ref.layer_weights(small, 2 ** 31 + 5, 1)
    other = ref.layer_weights(small, 5, 1)
    whole = ref.layer_weights(dict(small, num_experts=8, experts_first=0),
                              2 ** 31 + 5, 1)
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    np.testing.assert_array_equal(f32(a["experts_up"]), f32(b["experts_up"]))
    assert np.any(f32(a["wq"]) != f32(other["wq"]))
    assert a["experts_gate"].shape == (2, 32, 16)
    assert a["router"].shape == (32, 8) and a["shared_gate"].shape == (32, 32)
    # expert e is the same expert in every share
    np.testing.assert_array_equal(f32(a["experts_down"]),
                                  f32(whole["experts_down"])[4:6])
    np.testing.assert_array_equal(f32(a["wq"]), f32(whole["wq"]))
    assert set(ref.outer_weights(small, 1)) == {"embed", "ln_final"}


# -- the tiny model through the runner -------------------------------------------------

@pytest.fixture(scope="module")
def tiny_readings():
    """One seed's readings of ``tiny_moe`` beside this file (three window
    layers and a full one, 8 of 16 experts held, a shared preamble three
    windows long, the Pallas interpreter), through ``runners/llm_serve.py``
    and the reference as a chip run drives them."""
    import jax
    import synapseml_tpu  # noqa: F401
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "tiny_moe", "BENCHMARK.json")) as f:
        bench = json.load(f)
    tiny = harness.Cell(bench, "tiny-moe.tiny-preamble4", harness.ROOT)
    assert tiny.bench_dir == os.path.join(here, "tiny_moe")
    return tiny, tiny.runner().control(tiny, 2 ** 31 + 13, 2.0, jax.devices(),
                                       harness.CompileCounter())


def test_the_runner_serves_the_share_and_the_reference_accepts_it(
        tiny_readings):
    tiny, r = tiny_readings
    assert r["failed"] == 0 and r["tokens"] > 30
    # hidden 64, logit std 0.16: the program reads some 5e-5, fp8 0.013, a
    # window ignored 0.37; the tiny file's limit 0.003 lies between
    assert r["program"]["served_logit_gap"] < \
        tiny.config["limits"]["served_logit_gap"]


def test_a_tokens_routing_margin_by_hand(cell):
    """Three tokens over 8 experts, 2 a token, experts 2 and 3 held: the
    least distance of a held expert's logit from the edge it would cross, in
    standard deviations of the token's logits."""
    import jax.numpy as jnp
    ref = cell.reference()
    r = np.array([
        # top 2 are experts 0 and 1 (edges 4.0 and 3.0): 2 is 1.0 under, 3 is 2.0
        [5.0, 4.0, 3.0, 2.0, 0.0, 0.0, 0.0, 0.0],
        # expert 2 is selected second (third is 2.5): 0.5 over; 3 is 3.0 under
        [6.0, 0.0, 3.0, 0.0, 2.5, 0.0, 0.0, 0.0],
        # expert 3 ties with the edge but for 0.01: undecided at any margin
        [6.0, 4.0, 0.0, 3.99, 0.0, 0.0, 0.0, 0.0]], np.float32)
    got = np.asarray(ref.routing_margin(jnp.asarray(r), k=2,
                                        first=jnp.asarray(2), held=2))
    np.testing.assert_allclose(got, np.array([1.0, 0.5, 0.01]) / r.std(-1),
                               rtol=1e-5)
    # with no expert held near an edge a token is decided however close the
    # others lie: held experts 6 and 7 are far under
    far = np.asarray(ref.routing_margin(jnp.asarray(r), k=2,
                                        first=jnp.asarray(6), held=2))
    np.testing.assert_allclose(far, np.array([4.0, 3.0, 4.0]) / r.std(-1),
                               rtol=1e-5)
    assert got[2] < ref.ROUTING_MARGIN < got[1]


def test_the_widest_gap_is_read_over_the_decided_tokens(cell, monkeypatch):
    """``served_gaps`` with the forward pass replaced by logits and margins
    made by hand: an undecided token's gap, however wide, is not the
    number; with nothing decided the number is NaN, which is not correct."""
    ref = cell.reference()
    logits = np.zeros((4, 8), np.float32)
    logits[:, 0] = 1.0                       # the reference's best: token 0
    logits[1, 3], logits[2, 5] = 0.9, 0.2    # the served tokens' own logits
    margins = np.array([0.5, 0.3, 0.001, 0.5], np.float32)
    monkeypatch.setattr(ref, "forward_margins",
                        lambda *a, **k: ([logits], [margins]))
    g = ref.served_gaps(cell.config, 1, [[7, 7]], [[0, 3, 5, 0]], 16)
    assert g["widest_gap"] == pytest.approx(0.1)        # token 3 at row 1
    assert g["widest_gap_all"] == pytest.approx(0.8)    # the flip at row 2
    assert (g["tokens"], g["tokens_undecided"], g["mismatches"]) == (3, 1, 2)
    monkeypatch.setattr(ref, "forward_margins",
                        lambda *a, **k: ([logits], [margins * 0]))
    g = ref.served_gaps(cell.config, 1, [[7, 7]], [[0, 3, 5, 0]], 16)
    assert np.isnan(g["widest_gap"]) and g["tokens"] == 0
    assert not harness.decide({"compared": {"served_logit_gap": {
        "value": g["widest_gap"], "limit": 1.0}}, "failed": 0})


def test_the_fp8_control_is_not_correct_and_no_window_is_read(tiny_readings):
    tiny, r = tiny_readings
    limit = tiny.config["limits"]["served_logit_gap"]
    assert set(r["control"]) == {"fp8", "no_window"}
    assert r["control"]["fp8"]["served_logit_gap"] > limit
    assert not harness.decide({"compared": {"served_logit_gap": {
        "value": r["control"]["fp8"]["served_logit_gap"], "limit": limit}},
        "failed": 0})
    # three windows of preamble: a sliding layer that attends every earlier
    # key is not correct either
    low = r["control"]["no_window"]
    assert low["mismatches"] > 0 and low["served_logit_gap"] > limit
