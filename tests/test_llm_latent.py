"""Latent attention (DeepSeek-V3's multi-head latent attention at A.X-K1's
numbers: ``model.LatentAttention``), YaRN's rotary embedding, group-limited
routing with a scaling factor, and the engine over a cache of latent rows.

Pinned here, at small sizes on the CPU (hidden 64, 4 heads, query and K/V
latents of 32, head parts 16 + 16 and values 16, a latent row of 48 values in
128 lanes; YaRN with factor 32 over an original 16 positions; 16 experts in 4
groups with 2 kept and 4 a token, this share holding experts 4-7, one shared
expert, scale 2.5; layer 0 dense):

- the model (full forward, prefill then decode through the latent cache, a
  tail after a reused prefix in both forms, then ``SlotEngine``) against the
  plain float32 reference the benchmark keeps for A.X-K1
  (``benchmark/references/ax-k1-l5-e12.py``, which shares no code with the
  program and computes the expanded form alone), by LOGITS, on the dense path
  and with the kernels in interpret mode;
- the absorbed form against the expanded one on the same cache;
- YaRN's frequencies and scale against the formula, past the original
  context;
- grouped selection: no pair outside the kept groups; 16 shares of 12 of 192
  experts, the shared expert counted once, add up to the uncut layer; the
  scaling factor;
- the latent decode kernel against the equations, one query at a time;
- the spans, counters and gauges, and what the engine refuses.

Tolerances.  Program and reference both compute in float32 from the same
bfloat16-rounded weights and differ in summation order over three layers:
some 3e-7 on logits of spread 0.16.  ``LOGIT_TOL`` = 5e-5 leaves two orders of
room and lies under every fault read below (YaRN left out 1e-2, bfloat16 in
place of float32 4e-3).  The two forms differ in the order of one contraction
over the latent: ``FORM_TOL`` = 1e-5 (3e-7 read).
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from synapseml_tpu.models.llm import (HostKVArena, LlamaConfig,  # noqa: E402
                                      LlamaModel, SlotEngine, init_cache)
from synapseml_tpu.models.llm import experts as X  # noqa: E402
from synapseml_tpu.models.llm import model as M  # noqa: E402
from synapseml_tpu.models.llm import pallas_attn as P  # noqa: E402
from synapseml_tpu.telemetry import get_registry  # noqa: E402

LOGIT_TOL = 5e-5
FORM_TOL = 1e-5
SEED = 41
MAX_LEN = 128
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", "ax-k1-l5-e12.json")
TINY_FILE = os.path.join(ROOT, "tests", "benchmark_harness", "tiny_latent",
                         "configs", "tiny-latent.json")
BACKENDS = [pytest.param("dense", id="xla"),
            pytest.param("interpret", id="kernels", marks=pytest.mark.pallas)]


@pytest.fixture(scope="module")
def benchmark_config():
    with open(CONFIG_FILE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small_keys():
    """The tiny benchmark's configuration: the published keys at toy sizes
    and the share in the reference's names."""
    with open(TINY_FILE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref(benchmark_config):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "references", benchmark_config["reference"] + ".py"))


def program_config(hc, **kw):
    """The program's description from the published keys, the share from
    the reference's three (``from_hf`` reads ``n_routed_experts`` as the
    router's width, which is what it is in a ``config.json``)."""
    hf = dict(hc, n_routed_experts=hc["router_experts"])
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("max_len", MAX_LEN)
    return LlamaConfig.from_hf(hf, experts_first=hc["experts_first"],
                               experts_held=hc["n_routed_experts"], **kw)


@pytest.fixture(scope="module")
def small(ref, benchmark_config, small_keys):
    """(cfg, model, variables): float32, the reference's seeded weights laid
    into the program's parameter tree by the configuration file's own map."""
    from benchmark.runners import llm_serve
    cfg = program_config(small_keys)
    variables = llm_serve.build_variables(
        dict(small_keys, model=benchmark_config["model"]), ref, SEED)
    return cfg, LlamaModel(cfg), jax.tree.map(
        lambda a: jnp.asarray(a, jnp.float32), variables)


def _prompt(length, seed):
    return np.random.default_rng(seed).integers(1, 256, length).astype(np.int32)


def _ref_logits(ref, hc, ids, positions, quant=None):
    return ref.forward(hc, SEED, [np.asarray(ids, np.int32)],
                       [np.asarray(positions)], MAX_LEN, quant)[0]


_apply = jax.jit(lambda model, v, *a, **k: model.apply(v, *a, **k),
                 static_argnums=0, static_argnames=("attention_backend",))


@pytest.fixture
def form(monkeypatch):
    """Force the form of a tail after a cached prefix.  The form is read
    when a pass is traced, so the jitted apply is emptied on both sides."""
    def force(name):
        _apply.clear_cache()
        monkeypatch.setattr(P, "latent_prefill_form", lambda *a: name)
    yield force
    _apply.clear_cache()


def _prefill(model, v, cache, ids, start, bucket, backend):
    padded = np.zeros(bucket, np.int32)
    padded[:len(ids)] = ids
    return _apply(model, v, jnp.asarray(padded)[None],
                  positions=(start + jnp.arange(bucket))[None], cache=cache,
                  cache_index=jnp.int32(start), valid_len=len(ids),
                  attention_backend=backend)


# -- the description ---------------------------------------------------------------

def test_from_hf_builds_the_cut_and_refuses_what_it_cannot_honour(
        benchmark_config):
    c = benchmark_config
    cfg = program_config(c, dtype=jnp.bfloat16,
                         max_len=c["engine"]["max_len"])
    assert cfg.layer_kinds == ("latent_attention",) * 5
    assert cfg.ffn_kinds == ("dense",) + ("experts",) * 4
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert cfg.rope_style == "interleaved" and cfg.rms_norm_eps == 1e-6
    assert dict(cfg.rope_scaling)["factor"] == 32.0
    assert (cfg.num_experts, cfg.experts_first, cfg.experts_held_count,
            cfg.num_experts_per_tok) == (192, 0, 12, 8)
    assert (cfg.expert_groups, cfg.expert_groups_kept,
            cfg.routed_scaling_factor) == (8, 4, 2.5)
    assert cfg.num_shared_experts == 1 and cfg.norm_topk_prob
    assert cfg.expert_selection == "sigmoid" and not cfg.expert_selection_bias
    # the runner builds the same description from the file's own map
    from benchmark.runners import llm_serve
    assert llm_serve.build_model(c).cfg == cfg
    # what this description cannot honour is refused, whatever the family
    plain = {"vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 2,
             "num_attention_heads": 4, "intermediate_size": 64}
    for key, value in (("index_topk", 2048), ("hc_mult", 4),
                       ("enable_ihc", True), ("num_nextn_predict_layers", 1),
                       ("topk_method", "group_limited_greedy")):
        for base in (plain, c):
            with pytest.raises(ValueError, match=key):
                LlamaConfig.from_hf(dict(base, **{key: value}))
    with pytest.raises(ValueError, match="rope_scaling of type 'linear'"):
        LlamaConfig.from_hf(dict(plain, rope_scaling={"type": "linear",
                                                      "factor": 2.0}))
    with pytest.raises(ValueError, match="rope_scaling of type 'yarn'"):
        LlamaConfig.from_hf(dict(plain, rope_scaling=c["rope_scaling"]))
    with pytest.raises(ValueError, match="n_shared_experts"):
        LlamaConfig.from_hf(dict(c, n_shared_experts=2))
    with pytest.raises(ValueError, match="latent_attention layers alone"):
        LlamaConfig.tiny(rope_scaling=c["rope_scaling"])
    with pytest.raises(ValueError, match="kv_lora_rank"):
        LlamaConfig.tiny(layer_types=("latent_attention",) * 4)
    # a default rope_scaling is no scaling, as before
    assert LlamaConfig.from_hf(dict(plain, rope_scaling={
        "rope_type": "default"})).rope_scaling is None


def test_the_published_widths_the_cut_and_the_cache_by_hand(benchmark_config):
    c = benchmark_config
    from benchmark.runners import llm_serve
    cut = llm_serve.build_model(c).cfg
    shapes = jax.eval_shape(lambda: LlamaModel(cut).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])
    n = {k: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(v))
         for k, v in shapes.items()}
    attn = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 \
        + 64 * 128 * 7168
    assert attn == 101_122_048                       # 101.12 M
    norms = 1536 + 512 + 2 * 7168
    dense, expert = 3 * 7168 * 18432, 3 * 7168 * 2048
    assert (dense, expert) == (396_361_728, 44_040_192)
    assert n["layer_0"] == attn + norms + dense              # 497.5 M
    assert n["layer_1"] == attn + norms + 7168 * 192 + expert + 12 * expert
    assert 675.0e6 < n["layer_1"] < 675.1e6
    assert n["tok_embed"] == n["lm_head"] == 163840 * 7168   # not sliced
    total = sum(n.values())
    assert total == 5_546_466_304 and 2 * total == 11_092_932_608
    assert shapes["layer_1"]["attn"]["kv_b_proj"].value.shape == (512, 64, 256)
    # one latent row a position a layer, 576 values in 640 lanes
    cache = jax.eval_shape(lambda: init_cache(cut, 16, 17920))
    assert [set(e) for e in cache] == [{"latent"}] * 5
    assert cache[0]["latent"].shape == (16, 17920, 640)
    nbytes = sum(int(np.prod(a.shape)) * 2 for a in jax.tree.leaves(cache))
    assert nbytes == 5 * 16 * 17920 * 1280 == 1_835_008_000
    # a position's heads as a GQA layer would keep them: 64 x (192 + 128)
    # values, 35 times the latent's 576
    assert 64 * (192 + 128) / 576 == pytest.approx(35.6, abs=0.1)
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts"]


def test_yarn_frequencies_and_sigma_match_the_formula(benchmark_config, ref):
    c = benchmark_config
    inv, cs, factor = M.yarn_rope(64, 10000.0, c["rope_scaling"])
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    dim = lambda b: 64 * np.log(4096 / (2 * np.pi * b)) / (2 * np.log(1e4))  # noqa
    assert (np.floor(dim(32)), np.ceil(dim(1))) == (10, 23)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, base / 32 * ramp + base * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 32, rtol=1e-6)
    assert cs == 1.0 and factor == pytest.approx((0.1 * np.log(32) + 1) ** 2)
    cfg = program_config(c)
    assert M.LatentAttention.scale(cfg) == pytest.approx(0.13086, abs=1e-5)
    assert M.LatentAttention.scale(cfg) == pytest.approx(ref.yarn(c)[2])
    np.testing.assert_array_equal(ref.yarn(c)[0], inv)
    # the turn itself, past the original 4,096 positions: the program's
    # rotary embedding against the reference's, pairs (2i, 2i + 1)
    x = jax.random.normal(jax.random.PRNGKey(2), (5000, 1, 64))
    want = np.asarray(ref.rope_pairs(x, inv, 1.0))[4090:]
    pos = jnp.arange(4090, 5000)[None]
    got = np.asarray(M.apply_rope(x[4090:][None], pos, 10000.0,
                                  "interleaved", freqs=inv))[0]
    np.testing.assert_allclose(got, want, atol=2e-5)
    ang = 4999 * inv[30]
    np.testing.assert_allclose(
        got[-1, 0, 60:62], [np.cos(ang) * x[4999, 0, 60]
                            - np.sin(ang) * x[4999, 0, 61],
                            np.cos(ang) * x[4999, 0, 61]
                            + np.sin(ang) * x[4999, 0, 60]], atol=2e-5)


# -- the model against the reference ---------------------------------------------

def test_full_forward_matches_the_reference(small, ref, small_keys):
    cfg, model, variables = small
    ids = _prompt(64, 1)                    # four times the original 16
    tokens = jnp.asarray(ids)[None]
    want = _ref_logits(ref, small_keys, ids, np.arange(64))
    assert want.std() > 0.1
    np.testing.assert_allclose(np.asarray(_apply(model, variables, tokens))[0],
                               want, atol=LOGIT_TOL)
    # the faults this tolerance sees: YaRN left out is the reference's own
    # control; bfloat16 in place of float32 reads past it
    plain = LlamaModel(dataclasses.replace(cfg, rope_scaling=None))
    got = np.asarray(_apply(plain, variables, tokens))[0]
    assert np.abs(got - want).max() > 100 * LOGIT_TOL
    np.testing.assert_allclose(got, _ref_logits(ref, small_keys, ids,
                                                np.arange(64), "no_yarn"),
                               atol=LOGIT_TOL)
    low = LlamaModel(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    got = np.asarray(_apply(low, variables, tokens))[0]
    assert np.abs(got - want).max() > 10 * LOGIT_TOL


@pytest.mark.parametrize("backend", BACKENDS)
def test_prefill_then_decode_through_the_latent_cache(small, ref, small_keys,
                                                      backend):
    cfg, model, variables = small
    ids = _prompt(60, 2)
    want = _ref_logits(ref, small_keys, ids, np.arange(60))
    cache = init_cache(cfg, 2, MAX_LEN)
    assert cache[0]["latent"].shape == (2, MAX_LEN, 128)   # rows, not heads
    row = jax.tree.map(lambda a: a[:1], cache)
    lg, row = _prefill(model, variables, row, ids[:20], 0, 32, backend)
    np.testing.assert_allclose(np.asarray(lg)[0, :20], want[:20],
                               atol=LOGIT_TOL)
    cache = jax.tree.map(lambda a, r: a.at[:1].set(r), cache, row)
    active = jnp.asarray([True, False])
    for t in range(20, 60):       # past the original 16 positions threefold
        lengths = jnp.asarray([t + 1, 1], jnp.int32)
        lg, cache = _apply(model, variables,
                           jnp.asarray([ids[t], 0], jnp.int32)[:, None],
                           positions=(lengths - 1)[:, None], cache=cache,
                           cache_index=lengths - 1, slot_mask=active,
                           attention_backend=backend)
        np.testing.assert_allclose(np.asarray(lg)[0, 0], want[t],
                                   atol=LOGIT_TOL, err_msg=str(t))
    for layer in cache:                     # the idle slot wrote nothing
        assert not np.asarray(layer["latent"][1]).any()


def test_the_absorbed_decode_matches_the_expanded_form(small, form):
    """On one cache of 40 positions the 41st query three ways: the decode
    step (absorbed, per-slot positions), and a one-token pass at the offset
    in each form."""
    cfg, model, variables = small
    ids = _prompt(41, 3)
    _, row = _prefill(model, variables, init_cache(cfg, 1, MAX_LEN), ids[:40],
                      0, 64, "dense")
    step, _ = _apply(model, variables, jnp.asarray(ids[40:41])[None],
                     positions=jnp.asarray([[40]]), cache=row,
                     cache_index=jnp.asarray([40], jnp.int32),
                     attention_backend="dense")
    got = {}
    for name in ("expanded", "absorbed"):
        form(name)
        lg, _ = _prefill(model, variables, row, ids[40:41], 40, 1, "dense")
        got[name] = np.asarray(lg)[0, 0]
    np.testing.assert_allclose(np.asarray(step)[0, 0], got["expanded"],
                               atol=FORM_TOL)
    np.testing.assert_allclose(got["absorbed"], got["expanded"],
                               atol=FORM_TOL)


@pytest.mark.parametrize("backend", [
    pytest.param("dense", id="xla"),
    pytest.param("tiled", id="kernels-tiled", marks=pytest.mark.pallas)])
@pytest.mark.parametrize("name", ["expanded", "absorbed"])
def test_a_tail_over_a_reused_prefix_matches_a_cold_prefill(
        small, form, name, backend, request):
    cfg, model, variables = small
    if backend == "tiled":
        # through the prefill kernel: the expanded form at 4 K/V heads of 32,
        # the absorbed at one of 128 lanes for the 4 query heads
        request.getfixturevalue("every_prefill_tiled")
        backend = "interpret"
    form(name)
    ids = _prompt(64, 4)
    cold, _ = _prefill(model, variables, init_cache(cfg, 1, MAX_LEN), ids, 0,
                       64, backend)
    _, row = _prefill(model, variables, init_cache(cfg, 1, MAX_LEN), ids[:40],
                      0, 64, backend)
    tail, _ = _prefill(model, variables, row, ids[40:], 40, 32, backend)
    np.testing.assert_allclose(np.asarray(tail)[0, :24],
                               np.asarray(cold)[0, 40:], atol=LOGIT_TOL)


class Drive:
    """Admissions and steps by request name (as ``test_llm_mixed_kinds``
    drives its engine)."""

    def __init__(self, eng):
        self.eng, self.tokens, self.logits, self.paths = eng, {}, {}, {}
        self.by_slot = {}

    def admit(self, name, prompt, n):
        r = self.eng.admit(prompt, n)
        self.tokens[name], self.logits[name] = [r.token], r.logits
        self.paths[name] = (r.path, r.reused_tokens,
                            self.eng._prefill_attention_attrs()
                            .get("latent_prefill_form"))
        if not r.finished:
            self.by_slot[r.slot] = name

    def run(self):
        while self.eng.active.any():
            for ev in self.eng.step():
                self.tokens[self.by_slot[ev.slot]].append(ev.token)
                if ev.finished:
                    del self.by_slot[ev.slot]


@pytest.mark.parametrize("backend", BACKENDS)
def test_slot_engine_serves_the_references_logits(small, ref, small_keys,
                                                  backend, request):
    cfg, model, variables = small
    name = f"t-latent-{request.node.callspec.id}"
    eng = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                     attention_backend=backend, min_bucket=8, name=name)
    assert not eng.kv_by_position and [kc.latent for kc in eng._kinds] == \
        [True]
    count = get_registry().counter("llm_latent_prefill_total", "",
                                   ("engine", "form"))
    d = Drive(eng)
    pre = _prompt(40, 50)
    p = {"a": np.concatenate([pre, _prompt(6, 51)]),
         "b": np.concatenate([pre, _prompt(30, 52)]),     # a tail of 30
         "c": np.concatenate([pre, _prompt(3, 53)])}      # a tail of 3
    d.admit("a", p["a"], 20)
    d.admit("b", p["b"], 6)            # a's preamble, copied: a is active
    d.run()
    d.admit("c", p["c"], 8)            # in place: the slot holds the preamble
    d.run()
    assert d.paths["a"] == ("cold", 0, "cold")
    assert d.paths["b"][:2] == ("reuse", 40) and d.paths["c"][:2] == \
        ("reuse", 40)
    # the rule on products: a tail of 30 (bucket 32) over 128 rows expanded,
    # one of 3 (bucket 8) absorbed
    assert (d.paths["b"][2], d.paths["c"][2]) == ("expanded", "absorbed")
    assert [count.value(engine=name, form=f) for f in
            ("cold", "expanded", "absorbed")] == [1, 1, 1]
    for k in "abc":
        ids = list(p[k]) + d.tokens[k][:-1]
        lg = _ref_logits(ref, small_keys, ids,
                         np.arange(len(p[k]) - 1, len(ids)))
        tok = np.asarray(d.tokens[k])
        gap = float((lg.max(-1) - lg[np.arange(len(tok)), tok]).max())
        assert gap < LOGIT_TOL, (k, gap)
        np.testing.assert_allclose(d.logits[k], lg[0], atol=LOGIT_TOL,
                                   err_msg=k)
    # preempt and resume: the rows are copied back by position
    d.admit("e", p["b"], 12)
    for _ in range(5):
        for ev in eng.step():
            d.tokens["e"].append(ev.token)
    slot = [s for s, n in d.by_slot.items() if n == "e"][0]
    ticket = eng.preempt(slot)
    eng._flight = None
    d.by_slot = {eng.resume(ticket): "e"}
    d.run()
    ids = list(p["b"]) + d.tokens["e"][:-1]
    lg = _ref_logits(ref, small_keys, ids, np.arange(len(p["b"]) - 1,
                                                     len(ids)))
    tok = np.asarray(d.tokens["e"])
    assert len(tok) == 12
    assert float((lg.max(-1) - lg[np.arange(12), tok]).max()) < LOGIT_TOL


def test_the_spans_counters_and_gauges(small, tmp_path):
    from synapseml_tpu.telemetry import get_tracer
    cfg, model, variables = small
    name = "t-latent-count"
    eng = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                     attention_backend="interpret", min_bucket=8, name=name)
    reg = get_registry()

    def gauge(metric):
        return reg.gauge(metric, "", ("engine", "kind")).value(
            engine=name, kind="latent_attention")
    # reserved: 3 layers x 2 slots x 128 rows x 128 lanes x 4 B
    assert gauge("llm_kv_cache_bytes_reserved") == 3 * 2 * MAX_LEN * 128 * 4
    pre = _prompt(40, 60)
    jax.profiler.start_trace(str(tmp_path))         # step spans are live
    try:
        eng.admit(pre, 4)
        eng.step()
        eng.step()
        eng.admit(np.concatenate([pre, _prompt(20, 61)]), 2)
    finally:
        jax.profiler.stop_trace()
    step = [s for s in get_tracer().spans("engine.step")
            if "latent_tiles_walked" in s.attrs][-1]
    span = step.attrs["kv_span_sum"]
    # the latent bytes the walk needs: 48 values a row a layer, 4 B
    assert step.attrs["kv_bytes_latent_attention"] == 3 * span * 48 * 4
    tile = eng._kinds[0].geo.tile
    assert eng._paged_tile == tile == 64
    # both slots' first tile, three layers
    assert step.attrs["latent_tiles_walked"] == 3 * 2
    admits = [s for s in get_tracer().spans("engine.admit")
              if "latent_prefill_form" in s.attrs][-2:]
    assert [(s.attrs["latent_prefill_form"], s.attrs["latent_rows_expanded"])
            for s in admits] == [("cold", 3 * 64), ("expanded", 3 * MAX_LEN)]
    assert 0 < gauge("llm_kv_cache_bytes_in_use") < \
        gauge("llm_kv_cache_bytes_reserved")


def test_what_cannot_work_over_latent_rows_is_refused(small):
    from synapseml_tpu.serving.disagg import PrefillWorker
    cfg, model, variables = small
    kw = dict(n_slots=2, max_len=MAX_LEN, attention_backend="dense",
              min_bucket=8)
    with pytest.raises(ValueError, match="latent"):
        SlotEngine(model, variables, name="t-l-arena",
                   kv_arena=HostKVArena(max_bytes=1 << 20, name="t-l-arena"),
                   **kw)
    eng = SlotEngine(model, variables, name="t-l-worker", **kw)
    with pytest.raises(ValueError, match="not rows by position"):
        PrefillWorker(eng)


# -- grouped selection ---------------------------------------------------------------

def _share_outputs(groups, scale, shared, held=12, E=192):
    """The expert layer at 192 experts (8 a token) over hidden 32, width 16:
    the uncut layer and (``held`` given) its shares of ``held``, on the same
    tokens and the same weights (the shares' sliced from the uncut's)."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32, d_model=32, d_ff=16,
                           ffn="experts", num_experts=E, num_experts_per_tok=8,
                           num_shared_experts=shared, expert_selection="sigmoid",
                           norm_topk_prob=True, expert_groups=groups,
                           expert_groups_kept=groups // 2,
                           routed_scaling_factor=scale)
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 32, 32))
    valid = jnp.ones((1, 32), bool)
    uncut = X.ExpertFFN(cfg)
    import flax.linen as nn
    params = dict(nn.meta.unbox(uncut.init(jax.random.PRNGKey(6), h,
                                           valid)["params"]))
    # the scores spread, and the terms of order one
    params = {k: v * (40 if k == "router" else 10) if k.startswith(
        ("router", "experts")) else jax.tree.map(lambda a: 10 * a, v)
        for k, v in params.items()}
    whole = uncut.apply({"params": params}, h, valid)
    shares = []
    for first in range(0, E if held else 0, held or 1):
        part = X.ExpertFFN(dataclasses.replace(cfg, experts_first=first,
                                               experts_held=held))
        p = dict(params, **{k: params[k][first:first + held] for k in
                            ("experts_gate", "experts_up", "experts_down")})
        shares.append(part.apply({"params": p}, h, valid))
    return cfg, params, h, whole, shares


def test_sixteen_shares_and_the_shared_expert_once_add_up_to_the_layer():
    cfg, params, h, whole, shares = _share_outputs(8, 2.5, 1)
    # the shared expert, alone
    w = {k: params["shared_" + k]["kernel"] for k in ("gate", "up", "down")}
    shared = (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]
    total = sum(s - shared for s in shares) + shared
    # float32 over 16 partial sums of terms of order one
    np.testing.assert_allclose(total, whole, atol=1e-4)
    assert len(shares) == 16 and np.abs(np.asarray(whole - shared)).max() > 1
    assert np.abs(np.asarray(shared)).max() > 1
    # the scaling factor multiplies the routed terms alone
    _, _, _, one, _ = _share_outputs(8, 1.0, 1, held=None)
    np.testing.assert_allclose(whole - shared, 2.5 * (one - shared),
                               atol=1e-4)


def test_no_pair_falls_outside_the_kept_groups():
    s = jax.nn.sigmoid(3 * jax.random.normal(jax.random.PRNGKey(8), (500, 192)))
    sel = X._kept_groups(s, 8, 4)
    _, idx = jax.lax.top_k(sel, 8)
    g = s.reshape(500, 8, 24)
    score = np.asarray(jnp.sort(g, -1)[..., -2:].sum(-1))          # (500, 8)
    kept = np.argsort(-score, -1)[:, :4]
    in_kept = (np.asarray(idx)[:, :, None] // 24 == kept[:, None, :]).any(-1)
    assert in_kept.all()
    # and the choice differs from the ungrouped top 8 for most tokens: the
    # grouping is not a no-op here
    _, plain = jax.lax.top_k(s, 8)
    assert np.mean(np.any(np.sort(plain, -1) != np.sort(idx, -1), -1)) > 0.5
    # one group and a factor of 1 leave the parent's router, letter for letter
    cfg = LlamaConfig.tiny(ffn="experts", num_experts=16,
                           num_experts_per_tok=4, expert_selection="sigmoid")
    layer, h = X.ExpertFFN(cfg), jnp.zeros((1, 4, 128))
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), h, jnp.ones((1, 4), bool))["params"])
    text = str(jax.make_jaxpr(lambda p: layer.apply(
        {"params": p}, h, jnp.ones((1, 4), bool)))(params))
    assert text.count("top_k") == 1


# -- the kernel --------------------------------------------------------------------

def _latent_equations(q, rows, spans, rank, scale):
    """The absorbed form's attention one slot, query and head at a time."""
    B, S, H, _ = q.shape
    out = np.zeros((B, S, H, rank), np.float32)
    for b in range(B):
        for j in range(S):
            pos = int(spans[b]) - S + j
            kk = np.asarray(rows[b, :pos + 1], np.float64)
            for h in range(H):
                s = kk @ np.asarray(q[b, j, h], np.float64) * scale
                p = np.exp(s - s.max())
                out[b, j, h] = (p / p.sum()) @ kk[:, :rank]
    return out


@pytest.mark.pallas
@pytest.mark.parametrize("S", [1, 3])
def test_the_latent_kernel_matches_the_equations(S):
    B, H, T, lanes, rank, tile = 4, 8, 96, 128, 40, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    q = jax.random.normal(ks[0], (B, S, H, lanes))
    rows = jax.random.normal(ks[1], (B, T, lanes))
    spans = np.asarray([S, 17, 40, 96], np.int32)
    got = P.latent_decode_attention(q, rows, jnp.asarray(spans), tile=tile,
                                    rank=rank, scale=0.3, interpret=True)
    assert got.shape == (B, S, H, rank)
    np.testing.assert_allclose(np.asarray(got),
                               _latent_equations(q, rows, spans, rank, 0.3),
                               atol=2e-6)


def test_the_geometry_and_the_forms_at_the_published_widths():
    bf = jnp.bfloat16
    geo = P.paged_geometry(17920, 64, 1, 576, bf, latent=True)
    # a tile of 256 rows of 640 lanes: 320 KiB, keys and values at once
    assert (geo.tile, geo.total_tiles) == (256, 70)
    assert geo.vmem_bytes < 13 * 1024 * 1024
    # tails of the cell's buckets: 64 and 128 absorbed, 256 on expanded
    forms = [P.latent_prefill_form(s, 17920, 64, 512, 128, 64, 128, 640)
             for s in (64, 128, 256, 512, 1024)]
    assert forms == ["absorbed"] * 2 + ["expanded"] * 3
    # the absorbed form: one K/V head of 640 lanes for 64 query heads, the
    # key block narrowed to 256 where 512 leaves no query block room
    absorbed = P.prefill_geometry(128, 17920, 64, 1, 640, 512, bf)
    assert (absorbed.bq, absorbed.bk) == (16, 256)
    expanded = P.prefill_geometry(512, 17920, 64, 64, 192, 128, bf)
    assert (expanded.bq, expanded.bk) == (512, 512)
    # the accepted kinds' tiles are what they were
    assert P.prefill_geometry(2048, 2048, 32, 8, 128, 128, bf).bk == 512
    assert P.paged_geometry(2048, 32, 8, 128, bf).tile == 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_latent_kernels_compile_for_the_v5e_at_the_published_widths(
        one_chip):
    # (such a compile is written to the persistent cache and cannot be read
    # back without the chip: a later run warns and compiles again)
    bf = jnp.bfloat16

    def sds(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    jax.jit(lambda q, r, s: P.latent_decode_attention(
        q, r, s, tile=256, rank=512, scale=0.13)).lower(
            sds((16, 1, 64, 640)), sds((16, 17920, 640)),
            sds((16,), jnp.int32)).compile()
    geo = P.prefill_geometry(128, 17920, 64, 1, 640, 512, bf)
    jax.jit(lambda q, k, v: P.prefill_attention(
        q, k, v, 16384, 100, bq=geo.bq, bk=geo.bk, scale=0.13)).lower(
            sds((1, 128, 64, 640)), sds((1, 17920, 1, 640)),
            sds((1, 17920, 1, 512))).compile()
