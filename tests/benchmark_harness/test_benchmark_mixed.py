"""What the MiMo-V2.5 cell adds to the yardstick: ``work_kinds``'s counts
against counts by hand, its four readers on a reduced trace and program spans
made by hand (nothing to read in another configuration's cell or without the
kernel or the counts, a known share with them), the configuration against the
catalog's row, the traffic against the issue's numbers, and the tiny model
through the real runner over a ring with ``fp8``, ``no_window`` and
``no_sink`` not correct."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, peaks, span_read, traffic, work, work_kinds

BENCH = harness.load_benchmark(harness.ROOT)
CELL = "mimo-v2.5.longctx-closed24"
PEAK = peaks.peaks("TPU v5 lite")
MS = 1_000_000
W0, H0 = 5_000_000_000, 10.0
NEW = ["paged_kinds_attention_roofline", "expert_ffn_kinds_decode_roofline",
       "expert_ffn_kinds_prefill_roofline", "serve_mfu_kinds"]


def reader(name):
    return importlib.import_module("benchmark.metrics." + name)


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(BENCH, CELL, harness.ROOT)


# -- counts by hand ----------------------------------------------------------------

def test_the_layers_by_hand(cell):
    c = cell.config
    assert work_kinds.applies(c)
    assert not work_kinds.applies(harness.Cell(
        BENCH, "command-a-plus.preamble-closed24", harness.ROOT).config)
    kinds = work_kinds.layers(c)
    assert [(k["sliding"], k["moe"], k["kv"]) for k in kinds] == \
        [(False, False, 4)] + [(True, True, 8)] * 5 + [(False, True, 4)]
    assert work_kinds.attention_params(c, 4) == 89_128_960
    assert work_kinds.attention_params(c, 8) == 94_371_840
    assert work_kinds.expert_params(c) == 25_165_824
    assert work_kinds.expert_layers(c) == 6
    # layer 0: attention and the dense feed-forward; the others: attention
    # and the router over all 256
    router = 4096 * 256
    assert work_kinds.token_params(c) == \
        (89_128_960 + 201_326_592) + 5 * (94_371_840 + router) \
        + (89_128_960 + router)
    assert work_kinds.token_flops(c) == 2.0 * work_kinds.token_params(c)
    assert work_kinds.pair_flops(c) == 6.0 * 4096 * 2048
    assert work_kinds.head_flops(c) == 2.0 * 4096 * 152576


def test_the_bytes_by_kind_by_hand(cell):
    c = cell.config
    # K and V of a position: 4 x (192 + 128) x 2 B and 8 x 320 x 2 B
    assert work_kinds.kv_row_bytes(c, 4) == 2560
    assert work_kinds.kv_row_bytes(c, 8) == 5120
    # 24 slots at 9,000 keys: two full layers read them all, five window
    # layers 128 of each
    assert work_kinds.kv_bytes(c, 24 * 9000, 24 * 128) == \
        2 * 2560 * 24 * 9000 + 5 * 5120 * 24 * 128
    # 50 experts touched over the six layers, 12 pairs
    assert work_kinds.expert_bytes(c, 50, 12) == \
        50 * 50_331_648 + 12 * (8192 + 8192 + 16384)
    w = work_kinds.expert_work(c, 96, 70_000)
    assert w["ops"] == 70_000 * 6 * 4096 * 2048
    # a prefill of 8,852 tokens puts 0.5 of 8 pairs a token here: 730 pairs
    # a held expert, and the products, not the weights, bound it
    assert w["ops"] / PEAK["flops_bf16"] > w["bytes"] / PEAK["hbm_bytes_per_s"]
    few = work_kinds.expert_work(c, 96, 96 * 100)
    assert few["ops"] / PEAK["flops_bf16"] < \
        few["bytes"] / PEAK["hbm_bytes_per_s"]


# -- the readers on a reduced trace and spans made by hand --------------------------

def reduced_trace(kernel=True):
    """One device, 100 ms traced: two runs of the decode program (10-30,
    50-80 ms) and one of the prefill program (30-45 ms).  ``expert_ffn``
    runs 12 times in each decode run (1 ms each) and 12 times in the prefill
    (1 ms each); the paged kernel 7 times a decode run."""
    dec = [(W0 + 10 * MS, W0 + 30 * MS), (W0 + 50 * MS, W0 + 80 * MS)]
    pre = [(W0 + 30 * MS, W0 + 45 * MS)]
    ops = {"fusion f32[24]": {"base": "fusion", "self_ns": 1.0 * MS,
                              "total_ns": 1.0 * MS, "count": 3,
                              "intervals": []}}
    if kernel:
        iv = [(s + (j + 1) * MS, s + (j + 2) * MS)
              for s, _ in dec for j in range(12)]
        ops["expert_ffn bf16[272,4096]"] = {
            "base": "expert_ffn", "self_ns": 24.0 * MS, "total_ns": 24.0 * MS,
            "count": 24, "intervals": iv}
        iv = [(pre[0][0] + j * MS, pre[0][0] + (j + 1.0) * MS)
              for j in range(12)]
        ops["expert_ffn bf16[12288,4096]"] = {
            "base": "expert_ffn", "self_ns": 12.0 * MS, "total_ns": 12.0 * MS,
            "count": 12, "intervals": iv}
        ops["paged_decode_attention bf16[24,1,64,256]"] = {
            "base": "paged_decode_attention", "self_ns": 4.0 * MS,
            "total_ns": 4.0 * MS, "count": 14, "intervals": []}
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
    traced part."""
    found = [
        span("engine.step", 0.01, slots=24, tokens=24, kv_span_sum=24 * 9000,
             kv_window_span_sum=24 * 128, expert_pairs_held=70,
             experts_touched=50),
        span("engine.step", 0.05, slots=22, tokens=22, kv_span_sum=22 * 9500,
             kv_window_span_sum=22 * 128, expert_pairs_held=60,
             experts_touched=46),
        span("engine.step", 0.5, slots=24, tokens=24, kv_span_sum=1,
             kv_window_span_sum=1, expert_pairs_held=9999,
             experts_touched=96),
        span("engine.admit", 0.03, prompt_tokens=8933, reused_tokens=0,
             expert_pairs_held=26000, bucket=16384, path="cold"),
        span("engine.admit", -1.0, prompt_tokens=4467, reused_tokens=0,
             expert_pairs_held=13000, bucket=8192, path="cold")]
    monkeypatch.setattr(span_read, "spans", lambda name=None: [
        s for s in found if name is None or s.name == name])
    return found


FACTS = {"trace_host": (H0, H0 + 0.1), "records": []}


@pytest.mark.parametrize("metric", NEW)
def test_another_cell_or_a_program_without_the_counts_gives_nothing(
        cell, metric, monkeypatch, spans):
    given = dict(facts=FACTS, values={}, peak=PEAK, work=work, chips=1)
    # a configuration without layer kinds: the Command A+ cell, with every
    # span and kernel there
    other = harness.Cell(BENCH, "command-a-plus.preamble-closed24",
                         harness.ROOT)
    assert reader(metric).read(trace=reduced_trace(), cell=other,
                               **given) is None
    # the parent: no span carries a count, no kernel in the trace
    monkeypatch.setattr(span_read, "spans", lambda name=None: [
        span("engine.step", 0.01, slots=24, tokens=24, kv_span_sum=100)])
    assert reader(metric).read(trace=reduced_trace(kernel=False), cell=cell,
                               **given) is None


def test_the_paged_kernels_roofline_counts_each_layer_as_its_kind(cell, spans):
    got = reader("paged_kinds_attention_roofline").read(
        trace=reduced_trace(), facts=FACTS, cell=cell, peak=PEAK)
    per_step = (2 * 2560 * (24 * 9000 + 22 * 9500) / 2
                + 5 * 5120 * (24 + 22) / 2 * 128)
    # 14 calls: one a layer a step, two steps
    assert got == pytest.approx(100 * (2 * per_step / 819e9) / 0.004)
    assert 0 < got < 100
    # a key row padded to 256 lanes would be counted a fifth higher: the
    # needed bytes are the published widths'
    assert (256 + 128) / (192 + 128) == 1.2


def test_the_grouped_products_rooflines_at_the_experts_own_width(cell, spans):
    c = cell.config
    got = reader("expert_ffn_kinds_decode_roofline").read(
        trace=reduced_trace(), facts=FACTS, cell=cell, peak=PEAK)
    per_step = (work_kinds.expert_bytes(c, 50, 70)
                + work_kinds.expert_bytes(c, 46, 60)) / 2
    # 24 calls: two an expert layer a step, six expert layers, two steps
    assert got == pytest.approx(100 * (2 * per_step / 819e9) / 0.024)
    assert 0 < got < 100
    got = reader("expert_ffn_kinds_prefill_roofline").read(
        trace=reduced_trace(), facts=FACTS, cell=cell, peak=PEAK)
    w = work_kinds.expert_work(c, 96, 26000)
    by, op = w["bytes"] / 819e9, w["ops"] / 197e12
    assert got == pytest.approx(100 * max(by, op) * 1 / 0.012)
    assert 0 < got < 100
    # the accepted reader takes intermediate_size for the expert's width,
    # which here is the dense layer's: eight times the bytes
    from benchmark import work_moe
    assert work_moe.expert_params(4096, c["intermediate_size"]) == \
        8 * work_kinds.expert_params(c)


def test_serve_mfu_kinds_counts_what_this_chip_computes(cell, spans):
    got = reader("serve_mfu_kinds").read(facts=FACTS, cell=cell, peak=PEAK)
    c = cell.config
    tokens = 24 + 22 + 8933
    flops = tokens * work_kinds.token_flops(c) \
        + (70 + 60 + 26000) * work_kinds.pair_flops(c) \
        + (24 + 22 + 1) * 2.0 * 4096 * 152576
    assert got == pytest.approx(100 * flops / (0.1 * 197e12))
    assert 0 < got < 100


def test_the_new_cell_reports_what_the_benchmark_can_declare(cell):
    """The cell reports the accepted serving metrics but those whose readers
    count another model's work.  The four readers above are files without an
    entry in ``BENCHMARK.json``: ``test_benchmark_step_overlap.py`` pins
    ``step_overlap_share`` as the last of ``per_layer`` and is no model PR's
    to edit (``PERF.md`` section 7 has the entries ready).  Not on
    ``tpot_p95_ms``'s list: some fifty requests finish in a window."""
    assert [m["name"] for m in cell.end_to_end()] == \
        ["tokens_per_s", "setup_s"]
    assert {m["name"] for m in cell.per_layer()} == {
        "ttft_p50_ms", "ttft_p95_ms", "slot_occupancy", "compiles_in_window",
        "decode_step_device_ms", "prefill_device_share"}
    declared = {m["name"] for m in BENCH["per_layer"]}
    for name in NEW:
        assert name not in declared and callable(reader(name).read)
    assert cell.chips == 1 and cell.entry["traffic"] == "longctx-closed24"
    assert cell.entry["config"] == "mimo-v2.5-l7-e16"


def test_the_traffic_is_the_issues(cell):
    t = cell.traffic
    assert (t["loop"], t["clients"], t["shared_prefix_len"], t["order"],
            t["sampling"], t["stream"]) == \
        ("closed", 24, 0, "stratified", "greedy", True)
    assert (t["trace_lead_s"], t["trace_seconds"]) == (2.0, 3.0)
    plens = traffic.quantile_lengths(t["prompt_len"])
    olens = traffic.quantile_lengths(t["output_len"])
    assert (plens[0], plens[-1], len(plens)) == (4467, 15024, 8)
    assert (olens[0], olens[-1], len(olens)) == (279, 939, 8)
    assert round(np.mean(plens)) == 8853 and round(np.mean(olens)) == 553
    assert len(traffic.request_order(t, 2 ** 31 + 7)) == 64
    assert plens[-1] + olens[-1] + 1 <= cell.config["engine"]["max_len"]
    assert cell.config["engine"]["n_slots"] == t["clients"]


def test_no_width_differs_from_the_catalogs_row(cell):
    """The numbers of the published ``config.json`` (the catalog's row, copied
    here), every one under its own key but those that ``reduced`` lists."""
    pattern = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
    published = {
        "attention_bias": False, "attention_chunk_size": 128,
        "attention_value_scale": 0.707,
        "attention_projection_layout": "fused_qkv",
        "add_full_attention_sink_bias": False,
        "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
        "swa_num_attention_heads": 64, "swa_head_dim": 192,
        "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
        "hidden_size": 4096, "hybrid_block_size": None,
        "hybrid_layer_pattern": pattern, "intermediate_size": 16384,
        "layernorm_epsilon": 1e-05, "max_position_embeddings": 1048576,
        "model_type": "mimo_v2", "moe_intermediate_size": 2048,
        "moe_layer_freq": [0] + [1] * 47, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": None,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "partial_rotary_factor": 0.334,
        "rope_theta": 10000000, "routed_scaling_factor": None,
        "scoring_func": "sigmoid", "sliding_window": 128,
        "sliding_window_size": 128, "swa_rope_theta": 10000,
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152576}
    c = cell.config
    for key, value in published.items():
        if key in c["reduced"]:
            assert c["published"][key] == value and c[key] != value, key
        else:
            assert c[key] == value, key
    assert c["rope_scaling"] == {"rope_type": "default", "type": "default"}
    assert c["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                            "moe_layer_freq", "n_routed_experts"]
    # the cut: published layer 0 and the whole period 6-11
    assert c["num_hidden_layers"] == 7
    assert c["hybrid_layer_pattern"] == [pattern[0]] + pattern[6:12]
    assert c["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert (c["n_routed_experts"], c["router_experts"], c["experts_first"]) \
        == (16, 256, 0)
    assert c["vocab_size"] == 152576 and "vocab_rows" not in c
    assert c["deployment"]["chips_sharing_a_layer"] == 16
    # the program's words say the same as the published keys
    kinds = c["attention_kinds"]
    assert kinds["full_attention"] == {
        "num_kv_heads": 4, "head_dim": 192, "v_head_dim": 128,
        "rotary_dim": int(192 * 0.334), "rope_theta": 1e7, "sink": False,
        "value_scale": 0.707}
    assert kinds["sliding_attention"] == {
        "num_kv_heads": 8, "head_dim": 192, "v_head_dim": 128,
        "rotary_dim": 64, "rope_theta": 1e4, "sink": True,
        "value_scale": 0.707}
    assert c["layer_types"] == ["sliding_attention" if w else "full_attention"
                                for w in c["hybrid_layer_pattern"]]
    assert c["ffn_types"] == ["experts" if m else "dense"
                              for m in c["moe_layer_freq"]]
    for key in ("rotary", "value_scale", "sink", "scores", "routing",
                "unused_keys", "towers", "weights", "engine", "ring"):
        assert key in c["assumed"], key
    e = c["engine"]
    assert (e["n_slots"], e["max_len"], e["warmup"],
            e["expect_attention_backend"]) == (24, 16384, "sync", "paged")
    assert c["check"] == {"sample_requests": 6,
                          "controls": ["fp8", "no_window", "no_sink"]}
    assert "served_logit_gap" in c["limits"] and c["limits_why"]
    args = c["model"]["config_args"]
    assert (args["num_experts"], args["experts_held"], args["experts_first"]) \
        == ("router_experts", "n_routed_experts", "experts_first")
    assert (args["d_ff"], args["expert_d_ff"]) == \
        ("intermediate_size", "moe_intermediate_size")


def test_the_references_weights_come_from_the_seed_expert_by_expert(cell):
    ref = cell.reference()
    small = {"hidden_size": 32, "num_attention_heads": 4,
             "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
             "head_dim": 12, "v_head_dim": 8, "intermediate_size": 48,
             "moe_intermediate_size": 16, "vocab_size": 64,
             "num_hidden_layers": 2, "hybrid_layer_pattern": [0, 1],
             "moe_layer_freq": [0, 1], "rope_theta": 1e7,
             "swa_rope_theta": 1e4, "add_full_attention_sink_bias": False,
             "add_swa_attention_sink_bias": True, "router_experts": 8,
             "n_routed_experts": 2, "experts_first": 4,
             "num_experts_per_tok": 2}
    a = ref.layer_weights(small, 2 ** 31 + 5, 1)
    b = ref.layer_weights(small, 2 ** 31 + 5, 1)
    other = ref.layer_weights(small, 5, 1)
    whole = ref.layer_weights(dict(small, n_routed_experts=8, experts_first=0),
                              2 ** 31 + 5, 1)
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    np.testing.assert_array_equal(f32(a["experts_up"]), f32(b["experts_up"]))
    assert np.any(f32(a["wq"]) != f32(other["wq"]))
    assert a["experts_gate"].shape == (2, 32, 16)
    assert a["router"].shape == (32, 8) and a["router_bias"].shape == (8,)
    assert a["wk"].shape == (32, 2 * 12) and a["wv"].shape == (32, 2 * 8)
    assert a["wo"].shape == (4 * 8, 32) and a["sink"].shape == (4,)
    # the sinks around the logsumexp of a window's scores, the bias in units
    # of a score; both float32
    assert a["sink"].dtype == a["router_bias"].dtype == np.float32
    assert 3.0 < float(np.mean(a["sink"])) < 9.0
    assert 0 < float(np.abs(a["router_bias"]).max()) < 0.3
    # expert e is the same expert in every share
    np.testing.assert_array_equal(f32(a["experts_down"]),
                                  f32(whole["experts_down"])[4:6])
    np.testing.assert_array_equal(f32(a["wq"]), f32(whole["wq"]))
    dense = ref.layer_weights(small, 1, 0)
    assert set(dense) == {"wq", "wk", "wv", "wo", "ln_attn", "ln_mlp",
                          "w_gate", "w_up", "w_down"}
    assert dense["wk"].shape == (32, 12) and dense["w_gate"].shape == (32, 48)
    assert set(ref.outer_weights(small, 1)) == {"embed", "ln_final", "head"}


def test_a_tokens_routing_margin_reads_the_biased_selection(cell):
    """Three tokens over 8 experts, 2 a token, experts 2 and 3 held: the
    margin is read on the values the selection is made by, score plus bias."""
    import jax.numpy as jnp
    ref = cell.reference()
    sel = np.array([
        [0.9, 0.8, 0.6, 0.4, 0.1, 0.1, 0.1, 0.1],
        [0.9, 0.1, 0.6, 0.1, 0.5, 0.1, 0.1, 0.1],
        [0.9, 0.8, 0.1, 0.798, 0.1, 0.1, 0.1, 0.1]], np.float32)
    got = np.asarray(ref.routing_margin(jnp.asarray(sel), k=2,
                                        first=jnp.asarray(2), held=2))
    np.testing.assert_allclose(got, np.array([0.2, 0.1, 0.002]) / sel.std(-1),
                               rtol=1e-4)
    assert got[2] < ref.ROUTING_MARGIN < got[1]
    # route(): selected by score + bias, weighed by the score alone
    h = jnp.eye(4, dtype=jnp.float32)
    w = jnp.asarray(np.log(np.array([[3.0, 1.0, 0.5, 0.25]] * 4)), jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0], jnp.float32)
    idx, wt, _ = ref.route(h, w, bias, k=2, quant=None)
    s = 1 / (1 + 1 / np.array([3.0, 1.0, 0.5, 0.25]))
    assert sorted(np.asarray(idx)[0]) == [0, 2]      # 0.33 + 0.5 beats 0.5
    np.testing.assert_allclose(sorted(np.asarray(wt)[0]),
                               sorted(np.array([s[0], s[2]]) / (s[0] + s[2])),
                               rtol=1e-5)


# -- the tiny model through the runner -------------------------------------------------

@pytest.fixture(scope="module")
def tiny_readings():
    """One seed's readings of ``tiny_mixed`` beside this file (full, window,
    window, full with layer 0 dense; attention by kind; a ring of 32 rows
    behind a window of 8 with ``RING_BLOCK`` 16, a bfloat16 tile; 8 of 16 experts held under
    the biased router; the Pallas interpreter), through
    ``runners/llm_serve.py`` and the reference as a chip run drives them."""
    import jax
    import synapseml_tpu  # noqa: F401
    from synapseml_tpu.models.llm import model as M
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "tiny_mixed", "BENCHMARK.json")) as f:
        bench = json.load(f)
    tiny = harness.Cell(bench, "tiny-mixed.tiny-long4", harness.ROOT)
    assert tiny.bench_dir == os.path.join(here, "tiny_mixed")
    patch = pytest.MonkeyPatch()
    patch.setattr(M, "RING_BLOCK", 16)
    try:
        return tiny, tiny.runner().control(
            tiny, 2 ** 31 + 13, 2.0, jax.devices(), harness.CompileCounter())
    finally:
        patch.undo()


def test_the_runner_serves_over_a_ring_and_the_reference_accepts_it(
        tiny_readings):
    tiny, r = tiny_readings
    assert r["failed"] == 0 and r["tokens"] > 30
    assert r["program"]["served_logit_gap"] < \
        tiny.config["limits"]["served_logit_gap"]


def test_each_control_is_not_correct(tiny_readings):
    tiny, r = tiny_readings
    limit = tiny.config["limits"]["served_logit_gap"]
    assert set(r["control"]) == {"fp8", "no_window", "no_sink"}
    for name, low in r["control"].items():
        assert low["served_logit_gap"] > limit, name
        assert not harness.decide({"compared": {"served_logit_gap": {
            "value": low["served_logit_gap"], "limit": limit}}, "failed": 0})
    assert r["control"]["no_window"]["mismatches"] > 0
