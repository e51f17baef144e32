"""What the Olmo-Hybrid cell adds to the yardstick: ``work_gdn``'s counts
against counts by hand, its four readers on a reduced trace made by hand
(nothing to read without the kernels, a known share with them), and the
reference's recurrence against a ten-token example worked in numpy."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmark import harness, peaks, work, work_gdn

BENCH = harness.load_benchmark(harness.ROOT)
CELL = "olmo-hybrid-7b.chat-closed32"
PEAK = peaks.peaks("TPU v5 lite")
MS = 1_000_000
W0, H0 = 5_000_000_000, 10.0


def reader(name):
    return importlib.import_module("benchmark.metrics." + name)


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(BENCH, CELL, harness.ROOT)


# -- counts by hand ----------------------------------------------------------------

def test_a_linear_layer_by_hand(cell):
    # q, k: 3840 x 2880; v, g, o: 3840 x 5760; a, b: 3840 x 30; SwiGLU 3 x
    mixer = 3840 * (2880 + 2880 + 5760 + 5760 + 5760) + 2 * 3840 * 30
    assert mixer == 88_704_000
    assert work_gdn.linear_layer_params(3840, 30, 96, 192, 11008) == \
        mixer + 3 * 3840 * 11008 == 215_516_160
    full = work.decoder_layer_params(3840, 30, 30, 128, 11008)
    assert full == 4 * 3840 * 3840 + 3 * 3840 * 11008 == 185_794_560
    assert work_gdn.hybrid_token_flops(cell.config) == \
        2.0 * (12 * 215_516_160 + 4 * 185_794_560)
    # what serve_mfu's Llama layer would have counted: a tenth less
    llama = work.decoder_token_flops(16, 3840, 30, 30, 128, 11008)
    assert 0.88 < llama / work_gdn.hybrid_token_flops(cell.config) < 0.90
    assert work_gdn.linear_dims(cell.config) == (30, 96, 192)


def test_the_recurrences_bytes_and_operations_by_hand():
    assert work_gdn.state_bytes(30, 96, 192) == 2_211_840
    assert work_gdn.token_io_bytes(30, 96, 192) == 4 * 30 * 578 == 69_360
    assert work_gdn.decode_call_bytes(32, 30, 96, 192) == \
        32 * (2 * 2_211_840 + 69_360)
    assert work_gdn.decode_call_bytes(0, 30, 96, 192) == 0
    w = work_gdn.prefill_call_work(1000, 30, 96, 192)
    assert w["bytes"] == 2 * 2_211_840 + 1000 * 69_360
    assert w["ops"] == 1000 * 30 * 6 * 96 * 192
    # a slot's state as the program's gauge counts it, less the window
    from synapseml_tpu.models.llm import pallas_gdn
    assert pallas_gdn.slot_state_bytes(30, 96, 192, 0, 0) == \
        work_gdn.state_bytes(30, 96, 192)


# -- the readers on a reduced trace made by hand -------------------------------------

def reduced_trace(decode_kernel_ms=0.0, decode_calls=0, prefill_kernel_ms=0.0,
                  prefill_calls=0):
    """One device, 100 ms traced, busy 10-30 and 50-80 ms in two runs of the
    decode program; the kernels' own time as given."""
    busy = [(W0 + 10 * MS, W0 + 30 * MS), (W0 + 50 * MS, W0 + 80 * MS)]
    ops = {"fusion f32[32]": {"base": "fusion", "self_ns": 1.0 * MS,
                              "total_ns": 1.0 * MS, "count": 3,
                              "intervals": []}}
    for base, ms, calls in (
            ("gated_delta_decode", decode_kernel_ms, decode_calls),
            ("gated_delta_prefill", prefill_kernel_ms, prefill_calls)):
        if calls:
            ops[f"{base} f32[32,15,96,384]"] = {
                "base": base, "self_ns": ms * MS, "total_ns": ms * MS,
                "count": calls, "intervals": []}
    return {"window_ns": (W0, W0 + 100 * MS), "window_s": 0.1,
            "busy_s": 0.05, "host": [],
            "devices": [{"name": "/device:TPU:0", "busy": busy,
                         "busy_ns": 50.0 * MS, "ops": ops,
                         "modules": {"jit__decode_step_jit": {
                             "total_ns": 50.0 * MS, "count": 2,
                             "intervals": list(busy)}}}]}


def facts(prompts=(100, 1000)):
    """Two traced steps of 32 and 30 slots; requests whose first token came
    in the traced part, one before it and one that failed."""
    records = [{"error": None, "prompt_len": n, "times": [H0 + 0.01 * (i + 1),
                                                          H0 + 0.05]}
               for i, n in enumerate(prompts)]
    records.append({"error": None, "prompt_len": 512, "times": [H0 - 1.0]})
    records.append({"error": "boom", "prompt_len": 64, "times": []})
    return {"trace_host": (H0, H0 + 0.1), "steps": [(32, 20000), (30, 18000)],
            "records": records}


NEW = ["gated_delta_decode_roofline", "gated_delta_prefill_roofline",
       "gated_delta_decode_share"]


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_kernels_gives_nothing_to_read(cell, metric):
    given = dict(trace=reduced_trace(), facts=facts(), cell=cell, values={},
                 peak=PEAK, work=work, chips=1)
    assert reader(metric).read(**given) is None


def test_decode_roofline_is_needed_bytes_over_the_kernels_time(cell):
    # 24 calls: 12 linear layers in each of two steps; mean slots 31
    trace = reduced_trace(decode_kernel_ms=12.0, decode_calls=24)
    got = reader("gated_delta_decode_roofline").read(
        trace=trace, facts=facts(), cell=cell, peak=PEAK)
    per_call = (32 + 30) / 2 * (2 * 2_211_840 + 69_360)
    assert got == pytest.approx(100 * (24 * per_call / 819e9) / 0.012)
    assert 0 < got < 100
    # no step sampled: nothing to scale by
    assert reader("gated_delta_decode_roofline").read(
        trace=trace, facts={"steps": []}, cell=cell, peak=PEAK) is None


def test_decode_share_is_the_kernels_time_over_the_programs(cell):
    trace = reduced_trace(decode_kernel_ms=12.0, decode_calls=24)
    assert reader("gated_delta_decode_share").read(trace=trace) == \
        pytest.approx(100 * 12.0 / 50.0)


def test_prefill_roofline_takes_the_larger_of_bytes_and_operations(cell):
    trace = reduced_trace(prefill_kernel_ms=8.0, prefill_calls=24)
    got = reader("gated_delta_prefill_roofline").read(
        trace=trace, facts=facts(), cell=cell, peak=PEAK, work=work)
    least = []
    for n in (100, 1000):           # the two admissions traced, 12 calls each
        by = (2 * 2_211_840 + n * 69_360) / 819e9
        op = n * 30 * 6 * 96 * 192 / 197e12
        assert by > op              # float32 inputs: memory-bound
        least.append(by)
    assert got == pytest.approx(100 * np.mean(least) * 24 / 0.008)
    assert 0 < got < 100
    none_traced = dict(facts(), records=facts()["records"][2:])
    assert reader("gated_delta_prefill_roofline").read(
        trace=trace, facts=none_traced, cell=cell, peak=PEAK,
        work=work) is None


def test_serve_mfu_hybrid_counts_each_layer_as_its_kind(cell):
    f = facts()
    got = reader("serve_mfu_hybrid").read(facts=f, cell=cell, peak=PEAK,
                                          work=work)
    # tokens in the traced 0.1 s: two a request of the first two (and their
    # prompts, whose first token came inside it); the third's came before
    out_tok, all_tok = 4, 4 + 100 + 1000
    flops = all_tok * work_gdn.hybrid_token_flops(cell.config) \
        + out_tok * 2.0 * 3840 * 100352
    assert got == pytest.approx(100 * flops / (0.1 * 197e12))
    # a dense configuration has no layer_types: the metric is not its own
    dense = harness.Cell(BENCH, "mistral-7b.chat-closed32", harness.ROOT)
    assert reader("serve_mfu_hybrid").read(facts=f, cell=dense, peak=PEAK,
                                           work=work) is None
    assert reader("serve_mfu_hybrid").read(facts={}, cell=cell, peak=PEAK,
                                           work=work) is None


def test_the_new_cells_report_what_the_benchmark_can_declare():
    """Both cells report the accepted serving metrics.  The four readers
    above are files without an entry in ``BENCHMARK.json``, and neither cell
    is on ``step_overlap_share``'s list: ``test_benchmark_step_overlap.py``
    pins that entry as the last of ``per_layer`` and to one cell, and is no
    model PR's to edit (``PERF.md`` section 7 has the entries ready)."""
    olmo = harness.Cell(BENCH, CELL, harness.ROOT)
    doc = harness.Cell(BENCH, "mistral-7b.doc-closed16", harness.ROOT)
    assert [m["name"] for m in olmo.end_to_end()] == \
        ["tokens_per_s", "tpot_p95_ms", "setup_s"]
    assert [m["name"] for m in doc.end_to_end()] == ["tokens_per_s", "setup_s"]
    shared = {"ttft_p50_ms", "ttft_p95_ms", "slot_occupancy",
              "compiles_in_window", "decode_step_device_ms",
              "prefill_device_share", "paged_decode_attention_roofline"}
    assert {m["name"] for m in olmo.per_layer()} == shared
    assert {m["name"] for m in doc.per_layer()} == shared | {"serve_mfu"}
    declared = {m["name"] for m in BENCH["per_layer"]}
    for name in NEW + ["serve_mfu_hybrid"]:
        assert name not in declared and callable(reader(name).read)
    # the paged reader's inputs are in the file
    assert olmo.config["head_dim"] == 128
    assert olmo.config["num_key_value_heads"] == 30


def test_no_width_differs_from_the_catalogs_row(cell):
    """The numbers of the published ``config.json`` (the catalog's row, copied
    here), every one under its own key but the two that ``reduced`` lists."""
    published = {
        "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "max_position_embeddings": 65536,
        "rms_norm_eps": 1e-06, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4}
    c = cell.config
    for key, value in published.items():
        if key in c["reduced"]:
            assert c["published"][key] == value and c[key] != value
        else:
            assert c[key] == value, key
    assert c["model_type"] == "olmo_hybrid" and c["hidden_act"] == "silu"
    assert c["attention_bias"] is False and c["tie_word_embeddings"] is False
    assert c["linear_allow_neg_eigval"] is True
    assert c["rope_parameters"] == {"rope_theta": None}
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert c["published"]["layer_types"] == period * 8
    assert c["layer_types"] == period * 4 == c["published"]["layer_types"][:16]
    for key in ("block_order", "qk_norm", "rope_theta", "head_dim",
                "gate_init", "engine", "state_precision"):
        assert key in c["assumed"], key
    assert c["check"]["controls"] == ["fp8", "state_bf16"]
    # read, but it cannot come out as not correct: the file says so
    assert list(c["check"]["informative"]) == ["state_bf16"]
    assert set(json.dumps(c["limits"])) and "served_logit_gap" in c["limits"]


# -- the reference's recurrence against numpy ------------------------------------------

def numpy_recurrence(q, k, v, alpha, beta):
    """The equations as ISSUE 29 writes them, one token and one head at a
    time, float64."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    S = np.zeros((H, dk, dv))
    out = np.zeros((T, H, dv))
    for t in range(T):
        for h in range(H):
            kS = k[t, h] @ S[h]                                # (dv,)
            S[h] = alpha[t, h] * (S[h] - beta[t, h] * np.outer(k[t, h], kS)) \
                + beta[t, h] * np.outer(k[t, h], v[t, h])
            out[t, h] = S[h].T @ q[t, h]
    return out, S


def test_the_references_recurrence_on_ten_tokens(cell):
    ref = cell.reference()
    rng = np.random.default_rng(29)
    T, H, dk, dv = 10, 3, 8, 12
    q = rng.normal(size=(T, H, dk))
    k = rng.normal(size=(T, H, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(T, H, dv))
    alpha = rng.uniform(0.5, 1.0, size=(T, H))
    beta = rng.uniform(0.0, 2.0, size=(T, H))
    assert beta.max() > 1.0
    want, want_state = numpy_recurrence(q, k, v, alpha, beta)
    f32 = [np.asarray(x, np.float32) for x in (q, k, v, alpha, beta)]
    got, state = (np.asarray(x) for x in ref.gated_delta_rule(*f32))
    # float32 against float64 over ten tokens
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5)
    # the state rounded to bfloat16 after every token is another result
    low, low_state = (np.asarray(x) for x in
                      ref.gated_delta_rule(*f32, round_state=True))
    assert 1e-3 < np.abs(low - want).max() < 0.2
    assert 1e-3 < np.abs(low_state - want_state).max() < 0.2
    # the first token by hand: S = beta k v^T, o = beta (k . q) v
    first = beta[0, 0] * (k[0, 0] @ q[0, 0]) * v[0, 0]
    np.testing.assert_allclose(got[0, 0], first, atol=1e-5)


def test_the_references_convolution_and_norms_by_hand(cell):
    ref = cell.reference()
    x = np.arange(12, dtype=np.float32).reshape(6, 2)
    w = np.asarray([[1, 0], [0, 1], [2, 0], [1, 1]], np.float32)
    got = np.asarray(ref.causal_conv(x, w))
    want = np.zeros_like(x)
    for t in range(6):
        for j in range(4):
            src = t - 3 + j               # the last tap is the token itself
            if src >= 0:
                want[t] += w[j] * x[src]
    np.testing.assert_allclose(got, want)
    v = np.asarray([[3.0, 4.0]], np.float32)
    np.testing.assert_allclose(ref.l2norm(v), v / np.sqrt(25 + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(
        ref.rms_norm(v, np.asarray([2.0, 1.0], np.float32), 0.0),
        [[2 * 3 / np.sqrt(12.5), 4 / np.sqrt(12.5)]], rtol=1e-6)


def test_weights_come_from_the_seed_and_the_gates_span_their_range(cell):
    ref = cell.reference()
    small = dict(cell.config, hidden_size=64, intermediate_size=128,
                 vocab_size=128, num_attention_heads=2, num_key_value_heads=2,
                 head_dim=32, linear_num_value_heads=2, linear_key_head_dim=8,
                 linear_value_head_dim=16)
    a = ref.layer_weights(small, 2147483659, 0)
    b = ref.layer_weights(small, 2147483659, 0)
    c = ref.layer_weights(small, 2147483660, 0)
    assert set(a) >= {"gdn_wq", "gdn_conv", "gdn_A_log", "gdn_dt_bias"}
    assert set(ref.layer_weights(small, 1, 3)) >= {"wq", "q_norm", "k_norm"}
    np.testing.assert_array_equal(np.asarray(a["gdn_wq"], np.float32),
                                  np.asarray(b["gdn_wq"], np.float32))
    assert np.any(np.asarray(a["gdn_wq"], np.float32)
                  != np.asarray(c["gdn_wq"], np.float32))
    # alpha = exp(-exp(A_log) softplus(dt_bias + small)): inside (0.5, 1)
    rate = np.exp(np.asarray(a["gdn_A_log"]))
    assert 0.05 <= rate.min() and rate.max() <= 0.25
    sp = np.log1p(np.exp(np.asarray([-1.3, 1.3])))
    assert np.exp(-0.25 * sp[1]) > 0.5 and np.exp(-0.05 * sp[0]) < 1.0


# -- the hybrid through the runner, at toy widths --------------------------------------

@pytest.fixture(scope="module")
def tiny_readings():
    """One seed's readings of ``tiny_hybrid`` beside this file (two periods
    of three linear layers and one full layer, the Pallas interpreter),
    through ``runners/llm_serve.py`` and the reference as a chip run drives
    them: the program's gap and each control's."""
    import jax
    import synapseml_tpu  # noqa: F401
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "tiny_hybrid", "BENCHMARK.json")) as f:
        bench = json.load(f)
    tiny = harness.Cell(bench, "tiny-hybrid.tiny-closed4", harness.ROOT)
    assert tiny.bench_dir == os.path.join(here, "tiny_hybrid")
    return tiny, tiny.runner().control(tiny, 2 ** 31 + 11, 2.0, jax.devices(),
                                       harness.CompileCounter())


def test_the_runner_serves_the_hybrid_and_the_reference_accepts_it(
        tiny_readings):
    tiny, r = tiny_readings
    assert r["failed"] == 0 and r["tokens"] > 50
    limit = tiny.config["limits"]["served_logit_gap"]
    # bfloat16 against float32 at hidden 64, logit std 0.16: a served token
    # lies up to 0.15 under the reference's best (the linear layers pass a
    # rounding on some fifteen times louder than attention layers do)
    assert r["program"]["served_logit_gap"] < limit


def test_the_fp8_control_is_not_correct_and_state_bf16_is_read(tiny_readings):
    tiny, r = tiny_readings
    limit = tiny.config["limits"]["served_logit_gap"]
    assert set(r["control"]) == {"fp8", "state_bf16"}
    assert r["control"]["fp8"]["served_logit_gap"] > limit
    assert not harness.decide({"compared": {"served_logit_gap": {
        "value": r["control"]["fp8"]["served_logit_gap"], "limit": limit}},
        "failed": 0})
    # the state rounded to bfloat16 after every token moves some tokens off
    # the reference's first place, by less than the program's own rounding
    low = r["control"]["state_bf16"]
    assert low["mismatches"] > 0 and 0 < low["served_logit_gap"]
