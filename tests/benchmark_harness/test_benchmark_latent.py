"""What the A.X-K1 cell adds to the yardstick: ``work_latent``'s counts against
counts by hand, its three readers on a reduced trace and program spans made by
hand (nothing to read in another configuration's cell or without the kernel or
the counts, a known share with them), the configuration against the catalog's
row, the traffic against the cell's stated numbers, the reference's grouped routing
margin, and the tiny latent model through the real runner with ``fp8``,
``no_yarn`` and ``no_kv_norm`` not correct."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, peaks, span_read, traffic, work, work_latent

BENCH = harness.load_benchmark(harness.ROOT)
CELL = "ax-k1.preamble16k-closed16"
PEAK = peaks.peaks("TPU v5 lite")
MS = 1_000_000
W0, H0 = 5_000_000_000, 10.0
NEW = ["latent_decode_attention_roofline", "latent_prefill_attention_roofline",
       "latent_decode_share"]


def reader(name):
    return importlib.import_module("benchmark.metrics." + name)


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(BENCH, CELL, harness.ROOT)


# -- counts by hand ----------------------------------------------------------------

def test_the_counts_by_hand(cell):
    c = cell.config
    assert work_latent.applies(c)
    assert not work_latent.applies(harness.Cell(
        BENCH, "mimo-v2.5.longctx-closed24", harness.ROOT).config)
    # a decode step of 16 slots at 17,000 keys: each row once a layer, 576
    # values of 2 B; a score over 576 and a value over 512 a head
    w = work_latent.decode_work(c, 16 * 17000)
    assert w["bytes"] == 5 * 16 * 17000 * 576 * 2        # 1.57 GB
    assert w["ops"] == 2.0 * 5 * 16 * 17000 * 64 * (576 + 512)   # 189 GF
    # near balance: 121 FLOP a byte, the bytes bound
    assert 115 < w["ops"] / w["bytes"] < 125
    assert w["bytes"] / PEAK["hbm_bytes_per_s"] > \
        w["ops"] / PEAK["flops_bf16"]
    # a tail of 520 after 16,384 reused, expanded: every key of the pass's
    # queries at 192 + 128 a head, and 17,920 rows a layer expanded
    attrs = {"prompt_tokens": 16904, "reused_tokens": 16384,
             "latent_prefill_form": "expanded",
             "latent_rows_expanded": 5 * 17920}
    pairs = 520 * 16384 + 520 * 521 / 2
    w = work_latent.prefill_work(c, attrs)
    assert w["ops"] == 2.0 * 5 * 64 * pairs * 320
    assert w["expand_ops"] == 2.0 * 5 * 17920 * 512 * 64 * 256
    assert w["bytes"] == 5 * 16904 * 64 * 320 * 2
    # absorbed: scores over 576, values over 512, fold and unfold a query
    w = work_latent.prefill_work(c, dict(attrs, latent_prefill_form="absorbed",
                                         latent_rows_expanded=0))
    assert w["ops"] == 2.0 * 5 * 64 * (pairs * 1088 + 520 * 512 * 256)
    assert w["expand_ops"] == 0 and w["bytes"] == 5 * 16904 * 1088 * 2
    # a cold pass: its own keys
    w = work_latent.prefill_work(c, {"prompt_tokens": 100, "reused_tokens": 0,
                                     "latent_prefill_form": "cold",
                                     "latent_rows_expanded": 5 * 128})
    assert w["ops"] == 2.0 * 5 * 64 * (100 * 101 / 2) * 320


# -- the readers on a reduced trace and spans made by hand --------------------------

def reduced_trace(kernel=True):
    """One device, 100 ms traced: two runs of the decode program (10-30,
    50-80 ms) and one of the prefill program (30-45 ms).  The latent decode
    kernel runs 5 times in each decode run (1 ms each); the prefill kernel
    10 times in the prefill (1 ms each)."""
    dec = [(W0 + 10 * MS, W0 + 30 * MS), (W0 + 50 * MS, W0 + 80 * MS)]
    pre = [(W0 + 30 * MS, W0 + 45 * MS)]
    ops = {"fusion f32[16]": {"base": "fusion", "self_ns": 1.0 * MS,
                              "total_ns": 1.0 * MS, "count": 3,
                              "intervals": []}}
    if kernel:
        iv = [(s + (j + 1) * MS, s + (j + 2) * MS)
              for s, _ in dec for j in range(5)]
        ops["latent_decode_attention bf16[16,64,512]"] = {
            "base": "latent_decode_attention", "self_ns": 10.0 * MS,
            "total_ns": 10.0 * MS, "count": 10, "intervals": iv}
        iv = [(pre[0][0] + j * MS, pre[0][0] + (j + 1.0) * MS)
              for j in range(10)]
        ops["prefill_attention bf16[1,64,1,512,128]"] = {
            "base": "prefill_attention", "self_ns": 10.0 * MS,
            "total_ns": 10.0 * MS, "count": 10, "intervals": iv}
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


ADMIT = {"prompt_tokens": 16904, "reused_tokens": 16384, "bucket": 1024,
         "path": "reuse", "prefill_attention": "tiled",
         "latent_prefill_form": "expanded", "latent_rows_expanded": 89600}


@pytest.fixture
def spans(monkeypatch):
    """Two traced steps, one traced admission, and one of each outside the
    traced part."""
    found = [
        span("engine.step", 0.01, slots=16, tokens=16, kv_span_sum=16 * 17000,
             latent_tiles_walked=5 * 16 * 67,
             kv_bytes_latent_attention=5 * 16 * 17000 * 1152),
        span("engine.step", 0.05, slots=15, tokens=15, kv_span_sum=15 * 17100,
             latent_tiles_walked=5 * 15 * 67,
             kv_bytes_latent_attention=5 * 15 * 17100 * 1152),
        span("engine.step", 0.5, slots=16, tokens=16, kv_span_sum=1,
             latent_tiles_walked=5, kv_bytes_latent_attention=1),
        span("engine.admit", 0.03, **ADMIT),
        span("engine.admit", -1.0, **dict(ADMIT, prompt_tokens=17000))]
    monkeypatch.setattr(span_read, "spans", lambda name=None: [
        s for s in found if name is None or s.name == name])
    return found


FACTS = {"trace_host": (H0, H0 + 0.1), "records": []}


@pytest.mark.parametrize("metric", NEW)
def test_another_cell_or_a_program_without_the_counts_gives_nothing(
        cell, metric, monkeypatch, spans):
    given = dict(facts=FACTS, values={}, peak=PEAK, work=work, chips=1)
    # the parent: no span carries a latent count, no kernel in the trace
    monkeypatch.setattr(span_read, "spans", lambda name=None: [
        span("engine.step", 0.01, slots=16, tokens=16, kv_span_sum=100)])
    assert reader(metric).read(trace=reduced_trace(kernel=False), cell=cell,
                               **given) is None
    if metric == "latent_decode_share":
        return
    # a configuration without latent attention, with every span and kernel
    other = harness.Cell(BENCH, "mimo-v2.5.longctx-closed24", harness.ROOT)
    assert reader(metric).read(trace=reduced_trace(), cell=other,
                               **given) is None


def test_the_decode_kernels_roofline_and_share(cell, spans):
    c = cell.config
    got = reader("latent_decode_attention_roofline").read(
        trace=reduced_trace(), facts=FACTS, cell=cell, peak=PEAK)
    least = np.mean([max(w["bytes"] / 819e9, w["ops"] / 197e12) for w in (
        work_latent.decode_work(c, 16 * 17000),
        work_latent.decode_work(c, 15 * 17100))])
    # ten calls: one a layer a step, five layers, two steps
    assert got == pytest.approx(100 * 2 * least / 0.010)
    assert 0 < got < 100
    share = reader("latent_decode_share").read(trace=reduced_trace())
    assert share == pytest.approx(100 * 10 / 50)


def test_the_prefill_kernels_roofline(cell, spans):
    c = cell.config
    got = reader("latent_prefill_attention_roofline").read(
        trace=reduced_trace(), facts=FACTS, cell=cell, peak=PEAK)
    w = work_latent.prefill_work(c, ADMIT)
    want = 100 * max(w["bytes"] / 819e9, w["ops"] / 197e12) / 0.010
    assert got == pytest.approx(want) and 0 < got < 100


def test_the_new_cell_reports_what_the_benchmark_can_declare(cell):
    """The cell reports the accepted serving metrics but those that read
    another kernel or another model's work.  The three readers above are
    files without an entry in ``BENCHMARK.json``:
    ``test_benchmark_step_overlap.py`` pins ``step_overlap_share`` as the last
    of ``per_layer`` and is no model PR's to edit (``PERF.md`` section 7 has
    the entries ready).  Not on ``tpot_p95_ms``'s list: its p95 over some 160
    requests spread past a fifth of its bound in the other preamble cell."""
    assert [m["name"] for m in cell.end_to_end()] == \
        ["tokens_per_s", "setup_s"]
    assert {m["name"] for m in cell.per_layer()} == {
        "ttft_p50_ms", "ttft_p95_ms", "slot_occupancy", "compiles_in_window",
        "decode_step_device_ms", "prefill_device_share"}
    declared = {m["name"] for m in BENCH["per_layer"]}
    for name in NEW:
        assert name not in declared and callable(reader(name).read)
    assert cell.chips == 1 and cell.entry["traffic"] == "preamble16k-closed16"
    assert cell.entry["config"] == "ax-k1-l5-e12"


def test_the_traffic_is_as_stated(cell):
    t = cell.traffic
    assert (t["loop"], t["clients"], t["shared_prefix_len"], t["order"],
            t["sampling"], t["stream"]) == \
        ("closed", 16, 16384, "stratified", "greedy", True)
    assert (t["trace_lead_s"], t["trace_seconds"]) == (2.0, 3.0)
    assert (t["prompt_len"]["lo"], t["prompt_len"]["hi"]) == (16416, 17408)
    assert (t["output_len"]["lo"], t["output_len"]["hi"]) == (64, 384)
    plens = traffic.quantile_lengths(t["prompt_len"])
    olens = traffic.quantile_lengths(t["output_len"])
    assert len(plens) == len(olens) == 16
    # tails of 62-992 tokens after the preamble: buckets 64 to 1,024
    assert (plens[0] - 16384, plens[-1] - 16384) == (62, 992)
    assert plens[-1] + olens[-1] + 1 <= cell.config["engine"]["max_len"]
    assert 16384 + 1024 <= cell.config["engine"]["max_len"] == 17920
    assert cell.config["engine"]["n_slots"] == t["clients"]
    r = traffic.request(t, 2 ** 31 + 7, 3, cell.config["vocab_size"])
    q = traffic.request(t, 2 ** 31 + 7, 4, cell.config["vocab_size"])
    assert r["ids"][:16384] == q["ids"][:16384]


def test_no_width_differs_from_the_catalogs_row(cell):
    """Every number of the published ``config.json`` (the catalog's row,
    copied here) under its own key, but those that ``reduced`` lists."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
        "kv_lora_rank": 512, "max_position_embeddings": 131072,
        "model_type": "axk1", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 64,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "none", "v_head_dim": 128, "vocab_size": 163840}
    c = cell.config
    for key, value in published.items():
        if key in c["reduced"]:
            assert c["published"][key] == value and c[key] != value, key
        else:
            assert c[key] == value, key
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["router_experts"], c["experts_first"]) == (5, 12, 192, 0)
    assert c["deployment"]["chips_sharing_a_layer"] == 16
    assert c["layer_types"] == ["latent_attention"] * 5
    assert c["ffn_types"] == ["dense"] + ["experts"] * 4
    for key in ("attention", "rotary", "scale", "routing", "shared",
                "unused_keys", "block", "weights", "cache", "engine"):
        assert key in c["assumed"], key
    e = c["engine"]
    assert (e["n_slots"], e["max_len"], e["warmup"],
            e["expect_attention_backend"]) == (16, 17920, "sync", "paged")
    assert c["check"]["controls"] == ["fp8", "no_yarn", "no_kv_norm"]
    assert "served_logit_gap" in c["limits"] and c["limits_why"]


def test_the_references_weights_and_grouped_margin(cell):
    import jax.numpy as jnp
    ref = cell.reference()
    small = {"hidden_size": 32, "num_attention_heads": 2, "q_lora_rank": 16,
             "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
             "v_head_dim": 8, "intermediate_size": 48,
             "moe_intermediate_size": 16, "vocab_size": 64,
             "num_hidden_layers": 2, "router_experts": 8,
             "n_routed_experts": 2, "experts_first": 4,
             "num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
             "routed_scaling_factor": 2.5, "first_k_dense_replace": 1}
    a = ref.layer_weights(small, 2 ** 31 + 5, 1)
    whole = ref.layer_weights(dict(small, n_routed_experts=8, experts_first=0),
                              2 ** 31 + 5, 1)
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    np.testing.assert_array_equal(f32(a["experts_down"]),
                                  f32(whole["experts_down"])[4:6])
    np.testing.assert_array_equal(f32(a["wkv_b"]), f32(whole["wkv_b"]))
    assert a["wkv_b"].shape == (16, 2, 16) and a["router"].shape == (32, 8)
    assert a["shared_gate"].shape == (32, 16)
    assert set(ref.layer_weights(small, 1, 0)) >= {"w_gate", "w_up",
                                                   "w_down", "wq_a"}
    # W_UQ and W_UKV drawn wider than the rest
    assert 1.5 < float(np.std(f32(a["wkv_b"]))) / float(
        np.std(f32(a["wkv_a"]))) < 4.5
    # three tokens over 8 experts in 4 groups of 2, 2 groups kept, 2 a
    # token, experts 4 and 5 (group 2) held
    s = np.array([
        [0.9, 0.8, 0.1, 0.1, 0.7, 0.6, 0.2, 0.1],   # group 2 kept, clear
        [0.9, 0.8, 0.7, 0.6, 0.3, 0.2, 0.1, 0.1],   # group 2 out, clear
        [0.9, 0.8, 0.7, 0.6, 0.65, 0.65, 0.1, 0.1]], np.float32)  # at the edge
    idx, wt, sc, kept, gs = ref.route(jnp.eye(3, dtype=jnp.float32),
                                      jnp.asarray(np.log(s / (1 - s))),
                                      k=2, G=4, Gk=2, scale=2.5, quant=None)
    assert np.asarray(kept).tolist()[0] == [True, False, True, False]
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 1]
    np.testing.assert_allclose(np.asarray(wt).sum(-1), 2.5, rtol=1e-6)
    m = np.asarray(ref.routing_margin(sc, kept, gs, k=2, Gk=2,
                                      first=jnp.asarray(4), held=2))
    std = s.std(-1)
    # token 0: group 2 (1.3) over group 1 (0.2) by 1.1; its experts 0.7 and
    # 0.6 under the top two (0.9, 0.8) by 0.1 and 0.2: the least is 0.1
    # token 1: group 2 (0.5) under the second group (1.3) by 0.8
    # token 2: group 2 (1.3) ties group 1 (1.3): no margin at all
    np.testing.assert_allclose(m, np.array([0.1, 0.8, 0.0]) / std, atol=1e-5)


# -- the tiny model through the runner -------------------------------------------------

@pytest.fixture(scope="module")
def tiny_readings():
    """One seed's readings of ``tiny_latent`` beside this file (three latent
    attention layers, ranks 32, YaRN past its original 16 positions, layer 0
    dense, 4 of 16 experts in 4 groups with 2 kept; a shared preamble of 40
    tokens, so that tails take both forms; the Pallas interpreter), through
    ``runners/llm_serve.py`` and the reference as a chip run drives them."""
    import jax
    import synapseml_tpu  # noqa: F401
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "tiny_latent", "BENCHMARK.json")) as f:
        bench = json.load(f)
    tiny = harness.Cell(bench, "tiny-latent.tiny-preamble4", harness.ROOT)
    assert tiny.bench_dir == os.path.join(here, "tiny_latent")
    return tiny, tiny.runner().control(
        tiny, 2 ** 31 + 13, 2.0, jax.devices(), harness.CompileCounter())


def test_the_runner_serves_latent_rows_and_the_reference_accepts_it(
        tiny_readings):
    tiny, r = tiny_readings
    assert r["failed"] == 0 and r["tokens"] > 30
    assert r["program"]["served_logit_gap"] < \
        tiny.config["limits"]["served_logit_gap"]


def test_each_control_is_not_correct(tiny_readings):
    tiny, r = tiny_readings
    limit = tiny.config["limits"]["served_logit_gap"]
    assert set(r["control"]) == {"fp8", "no_yarn", "no_kv_norm"}
    for name, low in r["control"].items():
        assert low["served_logit_gap"] > limit, name
        assert not harness.decide({"compared": {"served_logit_gap": {
            "value": low["served_logit_gap"], "limit": limit}}, "failed": 0})
