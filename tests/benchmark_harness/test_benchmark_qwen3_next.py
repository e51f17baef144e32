"""What the Qwen3-Next cell adds to the yardstick: ``work_qwen3_next``'s counts
against counts by hand, its four readers on a reduced trace and program spans
made by hand (nothing to read in another configuration's cell or without the
kernels or the counts, a known share with them), the configuration against
the catalog's row, the traffic against the cell's stated numbers, the
reference's layout and routing margin, and the tiny model through the real
runner with ``fp8``, ``no_output_gate`` and ``key_heads_tiled`` not
correct."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, peaks, span_read, traffic, work_qwen3_next

BENCH = harness.load_benchmark(harness.ROOT)
CELL = "qwen3-next.longout-closed64"
PEAK = peaks.peaks("TPU v5 lite")
MS = 1_000_000
W0, H0 = 5_000_000_000, 10.0
NEW = ["expert_tile_fill", "gated_delta_decode_grouped_roofline",
       "expert_ffn_interval_decode_roofline", "serve_mfu_interval"]
wq = work_qwen3_next


def reader(name):
    return importlib.import_module("benchmark.metrics." + name)


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(BENCH, CELL, harness.ROOT)


# -- counts by hand ----------------------------------------------------------------

def test_the_counts_by_hand(cell):
    c = cell.config
    assert wq.applies(c)
    for other in ("olmo-hybrid-7b.chat-closed32", "ax-k1.preamble16k-closed16"):
        assert not wq.applies(harness.Cell(BENCH, other, harness.ROOT).config)
    assert wq.layer_kinds(c) == ["linear_attention"] * 3 + ["full_attention"]
    assert wq.expert_layers(c) == 4
    # a linear layer: in_proj_qkvz 2,048 x 12,288, in_proj_ba 2,048 x 64,
    # out_proj 4,096 x 2,048; the full layer: q with its gate 2,048 x 8,192,
    # k and v 2,048 x 512, o 4,096 x 2,048
    assert wq.linear_params(c) == 2048 * 12288 + 2048 * 64 + 4096 * 2048
    assert wq.full_params(c) == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    assert wq.moe_shared_params(c) == 2048 * 512 + 3 * 2048 * 512 + 2048
    assert wq.token_params(c) == 3 * wq.linear_params(c) \
        + wq.full_params(c) + 4 * wq.moe_shared_params(c)
    assert wq.recurrence_flops(c) == 3 * 32 * 6 * 128 * 128
    assert wq.pair_flops(c) == 6 * 2048 * 512
    # an expert's three matrices once, a pair's rows
    assert wq.expert_bytes(c, 1, 0) == 3 * 2048 * 512 * 2
    assert wq.expert_bytes(c, 0, 1) == 2048 * 2 + 2 * 512 * 2 + 4 * 2048
    # the decode kernel: 2 MiB of state a slot in and out, q and k at the 16
    # key heads
    per_slot = 2 * 32 * 128 * 128 * 4 + 4 * (2 * 16 * 128 + 2 * 32 * 128
                                             + 2 * 32)
    assert wq.gdn_decode_call_bytes(c, 64) == 64 * per_slot
    assert 2 * 32 * 128 * 128 * 4 == 4 * 1024 * 1024


# -- the readers on a reduced trace and spans made by hand --------------------------

def reduced_trace(kernels=True):
    """One device, 100 ms traced: two runs of the decode program (10-30,
    50-80 ms) and one of the prefill program (30-45 ms).  In each decode run
    ``gated_delta_decode`` runs 3 times (1 ms each) and ``expert_ffn`` 8
    times (2 ms each); in the prefill ``expert_ffn`` runs twice more."""
    dec = [(W0 + 10 * MS, W0 + 30 * MS), (W0 + 50 * MS, W0 + 80 * MS)]
    pre = [(W0 + 30 * MS, W0 + 45 * MS)]
    ops = {"fusion f32[16]": {"base": "fusion", "self_ns": 1.0 * MS,
                              "total_ns": 1.0 * MS, "count": 3,
                              "intervals": []}}
    if kernels:
        iv = [(s + (j + 1) * MS, s + (j + 2) * MS)
              for s, _ in dec for j in range(3)]
        ops["gated_delta_decode f32[64,32,128,128]"] = {
            "base": "gated_delta_decode", "self_ns": 6.0 * MS,
            "total_ns": 6.0 * MS, "count": 6, "intervals": iv}
        iv = [(s + 2 * MS + 2 * j * MS, s + 4 * MS + 2 * j * MS)
              for s, _ in dec for j in range(8)]
        iv += [(pre[0][0] + j * MS, pre[0][0] + (j + 1) * MS)
               for j in range(2)]
        ops["expert_ffn bf16[640,512]"] = {
            "base": "expert_ffn", "self_ns": 34.0 * MS,
            "total_ns": 34.0 * MS, "count": 18, "intervals": iv}
    mods = {"jit__decode_step_jit": {"total_ns": 50.0 * MS, "count": 2,
                                     "intervals": list(dec)},
            "jit__prefill_slot_jit": {"total_ns": 15.0 * MS, "count": 1,
                                      "intervals": list(pre)}}
    return {"window_ns": (W0, W0 + 100 * MS), "window_s": 0.1,
            "busy_s": 0.065, "host": [],
            "devices": [{"name": "/device:TPU:0",
                         "busy": dec[:1] + pre + dec[1:],
                         "busy_ns": 65.0 * MS, "ops": ops, "modules": mods}]}


def span(name, at, **attrs):
    return types.SimpleNamespace(name=name, start_ns=int((H0 + at) * 1e9),
                                 end_ns=int((H0 + at + 0.01) * 1e9),
                                 attrs=attrs, parent_id=None, span_id=1)


STEP = {"slots": 64, "tokens": 64, "kv_span_sum": 64 * 3000,
        "expert_pairs_held": 1280, "experts_touched": 730,
        "expert_tiles_active": 730, "expert_tile_rows": 730 * 16}
ADMIT = {"prompt_tokens": 1000, "reused_tokens": 0, "bucket": 1024,
         "path": "cold", "expert_pairs_held": 20000,
         "expert_tiles_active": 1200, "expert_tile_rows": 1200 * 32}


@pytest.fixture
def spans(monkeypatch):
    """Two traced steps, one traced admission, and one of each outside the
    traced part."""
    found = [
        span("engine.step", 0.01, **STEP),
        span("engine.step", 0.05, **dict(STEP, slots=63, tokens=63,
                                         expert_pairs_held=1260,
                                         expert_tile_rows=720 * 16,
                                         experts_touched=720,
                                         expert_tiles_active=720)),
        span("engine.step", 0.5, **dict(STEP, expert_pairs_held=1)),
        span("engine.admit", 0.03, **ADMIT),
        span("engine.admit", -1.0, **ADMIT)]
    monkeypatch.setattr(span_read, "spans", lambda name=None: [
        s for s in found if name is None or s.name == name])
    return found


FACTS = {"trace_host": (H0, H0 + 0.1), "records": [],
         "steps": [(64, 64 * 3000), (63, 63 * 3010)]}


@pytest.mark.parametrize("metric", NEW)
def test_another_cell_or_a_program_without_the_counts_gives_nothing(
        cell, metric, monkeypatch, spans):
    given = dict(facts=FACTS, values={}, peak=PEAK, chips=1)
    # the parent: no span carries a tile or pair count, no kernel traced
    monkeypatch.setattr(span_read, "spans", lambda name=None: [
        span("engine.step", 0.01, slots=64, tokens=64, kv_span_sum=100)])
    assert reader(metric).read(trace=reduced_trace(kernels=False), cell=cell,
                               **given) is None
    if metric == "expert_tile_fill":
        return
    # a configuration of another layout, with every span and kernel
    monkeypatch.setattr(span_read, "spans", lambda name=None: [
        s for s in spans if name is None or s.name == name])
    for other in ("olmo-hybrid-7b.chat-closed32", "ax-k1.preamble16k-closed16"):
        assert reader(metric).read(
            trace=reduced_trace(),
            cell=harness.Cell(BENCH, other, harness.ROOT), **given) is None


def test_the_tile_fill(spans):
    got = reader("expert_tile_fill").read(facts=FACTS)
    assert got == pytest.approx(100 * (1280 + 1260) / (16 * (730 + 720)))
    assert 0 < got < 100


def test_the_grouped_decode_kernels_roofline(cell, spans):
    c = cell.config
    got = reader("gated_delta_decode_grouped_roofline").read(
        trace=reduced_trace(), facts=FACTS, cell=cell, peak=PEAK)
    per_call = (wq.gdn_decode_call_bytes(c, 64)
                + wq.gdn_decode_call_bytes(c, 63)) / 2
    # six calls: three linear layers, two steps, 6 ms of kernel
    assert got == pytest.approx(100 * 6 * per_call / 819e9 / 0.006)
    assert 0 < got < 100


def test_the_expert_decode_roofline(cell, spans):
    c = cell.config
    got = reader("expert_ffn_interval_decode_roofline").read(
        trace=reduced_trace(), facts=FACTS, cell=cell, peak=PEAK)
    per_step = (wq.expert_bytes(c, 730, 1280)
                + wq.expert_bytes(c, 720, 1260)) / 2
    # sixteen calls inside the decode program (32 ms), two a layer a step:
    # two steps; the prefill's two calls lie outside it
    assert got == pytest.approx(100 * 2 * per_step / 819e9 / 0.032)
    assert 0 < got < 100


def test_the_serving_mfu(cell, spans):
    c = cell.config
    got = reader("serve_mfu_interval").read(facts=FACTS, cell=cell, peak=PEAK)
    flops = (64 + 63 + 1000) * wq.token_flops(c) \
        + (1280 + 1260 + 20000) * wq.pair_flops(c) \
        + (64 + 63 + 1) * wq.head_flops(c)
    assert got == pytest.approx(100 * flops / (0.1 * 197e12))
    assert 0 < got < 100


def test_the_new_cell_reports_what_the_benchmark_can_declare(cell):
    """The cell reports the accepted serving metrics whose readers read it
    right.  The four readers above are files without an entry in
    ``BENCHMARK.json``: ``test_benchmark_step_overlap.py`` pins
    ``step_overlap_share`` as the last of ``per_layer`` and is no model PR's
    to edit (``PERF.md`` section 7 has the entries ready).  Not on
    ``tpot_p95_ms``'s list: some 50 requests finish in a window, and a p95
    over them is nearly a maximum."""
    assert [m["name"] for m in cell.end_to_end()] == \
        ["tokens_per_s", "setup_s"]
    assert {"ttft_p50_ms", "ttft_p95_ms", "slot_occupancy",
            "compiles_in_window", "decode_step_device_ms",
            "prefill_device_share"} <= {m["name"] for m in cell.per_layer()}
    declared = {m["name"] for m in BENCH["per_layer"]}
    for name in NEW:
        assert name not in declared and callable(reader(name).read)
    # work_moe's readers count an expert at intermediate_size, ten times the
    # width of this one: they do not list the cell
    for name in ("expert_ffn_decode_roofline", "serve_mfu_moe",
                 "expert_ffn_prefill_roofline"):
        assert name not in {m["name"] for m in cell.per_layer()}
    assert cell.config["intermediate_size"] == \
        10 * cell.config["moe_intermediate_size"]
    assert cell.chips == 1 and cell.entry["traffic"] == "longout-closed64"
    assert cell.entry["config"] == "qwen3-next-80b-a3b-l4-e256"


def test_the_traffic_is_as_stated(cell):
    t = cell.traffic
    assert (t["loop"], t["clients"], t["shared_prefix_len"], t["order"],
            t["sampling"], t["stream"]) == \
        ("closed", 64, 0, "stratified", "greedy", True)
    assert (t["trace_lead_s"], t["trace_seconds"]) == (2.0, 3.0)
    assert (t["prompt_len"]["lo"], t["prompt_len"]["hi"]) == (512, 2048)
    assert (t["output_len"]["lo"], t["output_len"]["hi"]) == (1024, 8192)
    plens = traffic.quantile_lengths(t["prompt_len"])
    olens = traffic.quantile_lengths(t["output_len"])
    assert len(plens) == len(olens) == 16
    assert (plens[0], plens[-1], olens[0], olens[-1]) == (535, 1961, 1093,
                                                          7677)
    e = cell.config["engine"]
    assert plens[-1] + olens[-1] + 1 <= e["max_len"] == 10240
    assert e["n_slots"] == t["clients"] == 64
    # a reply of 7,677 tokens streams for as many decode steps, and one sent
    # as the window closes has to end inside the load generator's 60 s
    # drain: under 7.8 ms a step on average while the others drain
    assert e["reply_timeout_s"] >= 300 and olens[-1] * 0.0078 < 60
    r = traffic.request(t, 2 ** 31 + 7, 3, cell.config["vocab_size"])
    assert max(r["ids"]) < cell.config["vocab_size"] == 151936


def test_no_width_differs_from_the_catalogs_row(cell):
    """Every number of the published ``config.json`` (the catalog's row,
    copied here) under its own key, but those that ``reduced`` lists."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    c = cell.config
    for key, value in published.items():
        if key in c["reduced"]:
            assert c["published"][key] == value and c[key] != value, key
        else:
            assert c[key] == value, key
    assert (c["num_hidden_layers"], c["num_experts"], c["router_experts"],
            c["experts_first"]) == (4, 256, 512, 0)
    assert c["deployment"]["chips_sharing_a_layer"] == 2
    assert c["layer_types"] == wq.layer_kinds(c)
    for key in ("mtp", "weights", "norms", "shared_gate", "engine"):
        assert key in c["assumed"], key
    e = c["engine"]
    assert (e["n_slots"], e["max_len"], e["warmup"],
            e["expect_attention_backend"]) == (64, 10240, "sync", "paged")
    assert "attention_kinds" not in c     # packed rows come by shape
    assert c["check"]["controls"] == ["fp8", "no_output_gate",
                                      "key_heads_tiled"]
    assert "served_logit_gap" in c["limits"] and c["limits_why"]


def test_the_references_layout_and_margin(cell):
    import jax.numpy as jnp
    ref = cell.reference()
    small = {"hidden_size": 8, "num_attention_heads": 2,
             "num_key_value_heads": 1, "head_dim": 8,
             "partial_rotary_factor": 0.25, "rope_theta": 10000,
             "linear_num_key_heads": 2, "linear_num_value_heads": 4,
             "linear_key_head_dim": 3, "linear_value_head_dim": 5,
             "linear_conv_kernel_dim": 4, "moe_intermediate_size": 4,
             "shared_expert_intermediate_size": 6, "vocab_size": 16,
             "num_hidden_layers": 4, "router_experts": 8, "num_experts": 2,
             "experts_first": 4, "num_experts_per_tok": 2,
             "full_attention_interval": 4}
    hf = ref.hf_layer_weights(small, 2 ** 31 + 5, 0)
    w = ref.layer_weights(small, 2 ** 31 + 5, 0)
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    # key head 1's block of in_proj_qkvz: [q 3 | k 3 | v 2 x 5 | z 2 x 5]
    blk = f32(hf["in_proj_qkvz"]).reshape(8, 2, 26)[:, 1]
    np.testing.assert_array_equal(f32(w["gdn_wq"])[:, 3:6], blk[:, :3])
    np.testing.assert_array_equal(f32(w["gdn_wk"])[:, 3:6], blk[:, 3:6])
    # value heads 2 and 3 are key head 1's two
    np.testing.assert_array_equal(f32(w["gdn_wv"])[:, 10:20], blk[:, 6:16])
    np.testing.assert_array_equal(f32(w["gdn_wg"])[:, 10:20], blk[:, 16:])
    ba = f32(hf["in_proj_ba"]).reshape(8, 2, 4)
    np.testing.assert_array_equal(f32(w["gdn_wb"])[:, 2:], ba[:, 1, :2])
    np.testing.assert_array_equal(f32(w["gdn_wa"])[:, 2:], ba[:, 1, 2:])
    assert w["gdn_conv"].shape == (4, 2 * 2 * 3 + 4 * 5)
    assert "in_proj_qkvz" not in w and w["gdn_out_proj"].shape == (20, 8)
    # the held experts are the uncut layer's
    whole = ref.hf_layer_weights(dict(small, num_experts=8, experts_first=0),
                                 2 ** 31 + 5, 0)
    np.testing.assert_array_equal(f32(w["experts_down"]),
                                  f32(whole["experts_down"])[4:6])
    full = ref.layer_weights(small, 2 ** 31 + 5, 3)
    assert full["wq"].shape == (8, 2 * 2 * 8) and full["q_norm"].shape == (8,)
    # q_norm and k_norm near 1 + 1, the block's norms near 1 + 0
    assert 0.7 < float(np.mean(f32(full["q_norm"]))) < 1.3
    assert abs(float(np.mean(f32(full["ln_attn"])))) < 0.1
    # three tokens over 8 experts, 2 a token, experts 4 and 5 held: the
    # least distance of a held logit from the edge of the top 2, in
    # standard deviations of the token's logits
    r = jnp.asarray([[3.0, 2.5, 0.0, 0.0, 2.0, 1.0, 0.0, 0.0],   # 4 out by .5
                     [0.0, 0.0, 0.0, 0.0, 3.0, 2.9, 2.0, 0.0],   # 5 in by .9
                     [3.0, 0.0, 0.0, 0.0, 2.0, 2.0, 0.0, 0.0]])  # a tie
    m = np.asarray(ref.routing_margin(r, k=2, first=jnp.asarray(4), held=2))
    std = np.asarray(r).std(-1)
    np.testing.assert_allclose(m, np.array([0.5, 0.9, 0.0]) / std, atol=1e-6)


# -- the tiny model through the runner -------------------------------------------------

@pytest.fixture(scope="module")
def tiny_readings():
    """One seed's readings of ``tiny_qwen3_next`` beside this file (three
    linear layers of 2 key heads for 4 value heads and a gated full layer, 8
    of 16 experts top-4 with a gated shared expert of its own width; the
    Pallas interpreter), through ``runners/llm_serve.py`` and the reference
    as a chip run drives them."""
    import jax
    import synapseml_tpu  # noqa: F401
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "tiny_qwen3_next", "BENCHMARK.json")) as f:
        bench = json.load(f)
    tiny = harness.Cell(bench, "tiny-qwen3-next.tiny-longout4", harness.ROOT)
    assert tiny.bench_dir == os.path.join(here, "tiny_qwen3_next")
    return tiny, tiny.runner().control(
        tiny, 2 ** 31 + 13, 2.0, jax.devices(), harness.CompileCounter())


def test_the_runner_serves_the_model_and_the_reference_accepts_it(
        tiny_readings):
    tiny, r = tiny_readings
    assert r["failed"] == 0 and r["tokens"] > 100
    assert r["program"]["served_logit_gap"] < \
        tiny.config["limits"]["served_logit_gap"]


def test_each_control_is_not_correct(tiny_readings):
    tiny, r = tiny_readings
    limit = tiny.config["limits"]["served_logit_gap"]
    assert set(r["control"]) == {"fp8", "no_output_gate", "key_heads_tiled"}
    for name, low in r["control"].items():
        assert low["served_logit_gap"] > limit, name
        assert not harness.decide({"compared": {"served_logit_gap": {
            "value": low["served_logit_gap"], "limit": limit}}, "failed": 0})
