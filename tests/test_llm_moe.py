"""A decoder with expert layers, a parallel block and window layers beside
full ones, served as ONE chip's share of a deployment.

Pinned here, at small sizes on the CPU (hidden 64, 8 heads of 16 over 2 K/V
heads, 16 experts with 4 a token and 2 shared, a window of 8 on contexts of
30 and more, the pattern sliding, sliding, sliding, full):

- the model (prefill, then decode through the cache, then ``SlotEngine``: a
  bucket's padding, a reused slot, prefix reuse across the window's edge, an
  inactive slot bit for bit) against the plain float32 reference the
  benchmark keeps for Command A+
  (``benchmark/references/command-a-plus-l4-e16.py``, which shares no code
  with the program), by LOGITS;
- the paged kernel with a window (interpret mode) against the dense
  equations, spans under, at and over the window; prefill attention in blocks
  over keys against the dense scores;
- the expert layer exact under the worst imbalance, and THE SHARES ADD UP:
  the routed parts of all the shares, with the shared experts and attention
  counted once, are the uncut layer of the reference;
- the uncut 32-layer pattern and its parameter counts from the published
  keys; each counter; what works unchanged over window layers (speculative
  verify, the host arena, a prefill worker).

Tolerances.  Program and reference both compute in float32 from the same
bfloat16-rounded weights and differ in summation order (XLA's CPU dot
against ``Precision.HIGHEST``, a grouped product against an expert over
every token, an online softmax against a whole row) over 4 layers and some
60 tokens: a few 1e-6 on logits of order 0.2.  ``LOGIT_TOL`` = 5e-5 leaves
an order of room and lies under what a fault reads here: the router's
product in bfloat16 1e-3 and more (the scores move; now and then a top-4
choice flips), a window ignored 0.4, half-split rotary pairs 5e-3 and more.
A padding token that were routed would change no real token's logits: the
counts catch it (``expert_pairs_held`` of a padded bucket, of an idle slot).
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
from synapseml_tpu.models.llm.pallas_attn import (  # noqa: E402
    cache_row_heads, paged_decode_attention, paged_geometry,
    paged_live_tiles, paged_read_bytes)
from synapseml_tpu.telemetry import get_registry  # noqa: E402

LOGIT_TOL = 5e-5
SEED = 33
MAX_LEN = 64
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "command-a-plus-l4-e16.json")
#: the published keys at toy sizes: this share holds experts 4..11 of 16
SMALL = {
    "model_type": "cohere2_moe", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "layer_norm_eps": 1e-5, "rms_norm_eps": None,
    "tie_word_embeddings": True, "use_parallel_block": True,
    "use_qk_norm": False, "position_embedding_type": "rope_gptj",
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": 8, "rope_theta": 50000, "logit_scale": 1,
    "num_experts_per_tok": 4, "num_shared_experts": 2,
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "max_position_embeddings": 200000,
    # the share: the reference's names
    "num_experts": 8, "router_experts": 16, "experts_first": 4}
BACKENDS = [pytest.param("dense", id="xla"),
            pytest.param("interpret", id="kernels", marks=pytest.mark.pallas)]


@pytest.fixture(scope="module")
def benchmark_config():
    with open(CONFIG_FILE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref(benchmark_config):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "references",
        benchmark_config["reference"] + ".py"))


def program_config(hc, **kw):
    """The program's description from the published keys, the share from the
    reference's three (``from_hf`` reads ``num_experts`` as the router's
    width, which is what it is in a ``config.json``)."""
    hf = dict(hc, num_experts=hc["router_experts"])
    return LlamaConfig.from_hf(
        hf, dtype=jnp.float32, max_len=MAX_LEN,
        experts_first=hc["experts_first"], experts_held=hc["num_experts"],
        **kw)


def lay_weights(ref, names, hc, seed=SEED):
    """The reference's weights in the program's tree, as the benchmark's
    runner lays them, in float32."""
    from benchmark.runners import llm_serve
    variables = llm_serve.build_variables(
        dict(hc, model={"params": names}), ref, seed)
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), variables)


@pytest.fixture(scope="module")
def small(ref, benchmark_config):
    """(cfg, model, variables): float32, the reference's seeded weights laid
    into the program's parameter tree by the configuration file's own map."""
    cfg = program_config(SMALL)
    return cfg, LlamaModel(cfg), lay_weights(
        ref, benchmark_config["model"]["params"], SMALL)


def _prompt(length, seed):
    return np.random.default_rng(seed).integers(
        1, SMALL["vocab_size"], length).astype(np.int32)


def _ref_logits(ref, ids, positions, hc=SMALL, quant=None):
    return ref.forward(hc, SEED, [np.asarray(ids, np.int32)],
                       [np.asarray(positions)], MAX_LEN, quant)[0]


def _gap(ref, prompt, served):
    ids = list(prompt) + list(served[:-1])
    lg = _ref_logits(ref, ids, np.arange(len(prompt) - 1, len(ids)))
    tok = np.asarray(served)
    return float((lg.max(-1) - lg[np.arange(len(tok)), tok]).max()), lg[0]


# -- the description -----------------------------------------------------------

def test_from_hf_reads_what_the_family_spells_in_its_own_words(small):
    cfg = small[0]
    assert (cfg.d_head, cfg.head_dim, cfg.d_model // cfg.num_heads) == (16, 16, 8)
    assert cfg.norm == "layer" and cfg.rms_norm_eps == 1e-5
    assert cfg.norm_order == "parallel" and cfg.rope_style == "interleaved"
    assert cfg.rope_layers == ("sliding_attention",) and not cfg.qk_norm
    assert cfg.sliding_window == 8 and cfg.logit_scale == 1.0
    assert (cfg.ffn, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.num_shared_experts) == ("experts", 16, 4, 2)
    assert cfg.expert_selection == "sigmoid" and cfg.norm_topk_prob
    assert (cfg.experts_first, cfg.experts_held_count) == (4, 8)
    assert cfg.num_attention_layers == 4 and cfg.num_window_layers == 3
    # a Llama config reads as it did
    plain = LlamaConfig.from_hf({
        "vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 64})
    assert (plain.ffn, plain.norm, plain.norm_order, plain.d_head,
            plain.sliding_window, plain.rope_layers) == \
        ("dense", "rms", "pre", 8, None, None)
    with pytest.raises(ValueError, match="sliding_window"):
        LlamaConfig.tiny(layer_types=("sliding_attention",) * 4)
    with pytest.raises(ValueError, match="not among the router's"):
        dataclasses.replace(cfg, experts_first=12)
    with pytest.raises(ValueError, match="ffn="):
        LlamaConfig.tiny(ffn="sparse")


def test_the_parameter_tree_is_the_configuration_files_map(small):
    _, model, variables = small
    import flax.linen as nn
    want = jax.tree.map(lambda a: a.shape, nn.meta.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))["params"])))
    got = jax.tree.map(lambda a: a.shape, variables["params"])
    assert got == want
    moe = got["layer_0"]["moe"]
    assert moe["router"] == (64, 16)            # the router keeps its width
    assert moe["experts_gate"] == (8, 64, 32)   # the experts held here
    assert moe["shared_gate"]["kernel"] == (64, 64)     # 2 side by side


def test_the_uncut_pattern_and_its_parameters_by_hand(benchmark_config):
    c = benchmark_config
    pub = dict(c, **c["published"])
    hf = {k: v for k, v in pub.items() if not isinstance(v, dict)
          or k == "rope_parameters"}
    cfg = LlamaConfig.from_hf(hf)
    period = ("sliding_attention",) * 3 + ("full_attention",)
    assert cfg.layer_kinds == period * 8 and cfg.num_layers == 32
    assert cfg.num_window_layers == 24 and cfg.sliding_window == 4096
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.d_head) == (128, 8, 128)
    assert cfg.num_heads * cfg.d_head == 16384 != cfg.d_model == 4096
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.num_shared_experts,
            cfg.experts_held_count) == (128, 8, 4, 128)
    assert cfg.vocab_size == 262144 and cfg.tie_embeddings
    # one layer by hand: attention, router, four shared experts; one expert
    attn = 2 * 4096 * 16384 + 2 * 4096 * 1024
    expert = 3 * 4096 * 4096
    assert attn == 142_606_336 and expert == 50_331_648
    outside = attn + 4096 * 128 + 4 * expert
    assert outside == 344_457_216                      # 344.4M beside
    assert 128 * expert == 6_442_450_944               # 128 x 50.3M
    # and the program's own tree at the cut's geometry (shapes only)
    cut = LlamaConfig.from_hf(
        {k: v for k, v in c.items() if not isinstance(v, dict)
         or k == "rope_parameters"} | {"num_experts": 128},
        experts_first=0, experts_held=16)
    shapes = jax.eval_shape(lambda: LlamaModel(cut).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])
    n = {k: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(v))
         for k, v in shapes.items()}
    assert n["layer_0"] == outside + 16 * expert + 4096     # + the norm
    assert n["tok_embed"] == 262144 * 4096                  # not sliced
    total = sum(n.values())
    assert total == 4 * (outside + 16 * expert + 4096) + 262144 * 4096 + 4096
    assert 11.34e9 < 2 * total < 11.35e9                    # 11.35 GB
    assert c["vocab_size"] == 262144 and "vocab_rows" not in c
    assert c["reduced"] == ["num_hidden_layers", "layer_types",
                            "num_experts"]


# -- the model against the reference ---------------------------------------------

def test_full_forward_matches_the_reference(small, ref):
    _, model, variables = small
    ids = _prompt(40, 1)                        # 40 tokens, window 8
    got = np.asarray(model.apply(variables, jnp.asarray(ids)[None]))[0]
    want = _ref_logits(ref, ids, np.arange(40))
    assert want.std() > 0.1
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)


@pytest.mark.parametrize("kind", ["experts", "llama"])
def test_logits_at_is_the_full_forwards_row_and_no_other(small, kind):
    """``logits_at``: the head over the one row a prefill reads (the last
    real token of a padded bucket), ``(B, 1, vocab)``; at the published
    vocabulary the bucket's logits would not fit the chip."""
    if kind == "experts":
        _, model, variables = small
        vocab = SMALL["vocab_size"]
    else:
        model = LlamaModel(LlamaConfig.tiny(dtype=jnp.float32))
        variables = model.init(jax.random.PRNGKey(3),
                               jnp.zeros((1, 4), jnp.int32))
        vocab = model.cfg.vocab_size
    ids = np.random.default_rng(5).integers(1, vocab, (2, 24)).astype(np.int32)
    full = np.asarray(model.apply(variables, jnp.asarray(ids)))
    at = jnp.asarray([19, 7], jnp.int32)
    got = np.asarray(model.apply(variables, jnp.asarray(ids), logits_at=at))
    assert got.shape == (2, 1, vocab)
    np.testing.assert_allclose(got[0, 0], full[0, 19], atol=1e-5)
    np.testing.assert_allclose(got[1, 0], full[1, 7], atol=1e-5)
    one = np.asarray(model.apply(variables, jnp.asarray(ids[:1]),
                                 logits_at=jnp.int32(23)))
    np.testing.assert_allclose(one[0, 0], full[0, 23], atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_prefill_then_decode_logits_match_the_reference(small, ref, backend):
    cfg, model, variables = small
    ids = _prompt(36, 2)
    want = _ref_logits(ref, ids, np.arange(36))
    cache = init_cache(cfg, 2, MAX_LEN)
    row = jax.tree.map(lambda c: c[:1], cache)
    # a padded prefill of 20 real tokens in a bucket of 32
    padded = np.zeros(32, np.int32)
    padded[:20] = ids[:20]
    lg, row = model.apply(variables, jnp.asarray(padded)[None],
                          positions=jnp.arange(32)[None], cache=row,
                          cache_index=0, valid_len=20,
                          attention_backend=backend)
    np.testing.assert_allclose(np.asarray(lg)[0, :20], want[:20],
                               atol=LOGIT_TOL)
    cache = jax.tree.map(lambda c, r: c.at[:1].set(r), cache, row)
    # then token by token through the vector path: slot 1 inactive
    active = jnp.asarray([True, False])
    for t in range(20, 36):                     # past the window's edge
        lengths = jnp.asarray([t + 1, 1], jnp.int32)
        tok = jnp.asarray([ids[t], 0], jnp.int32)
        lg, cache = model.apply(
            variables, tok[:, None], positions=(lengths - 1)[:, None],
            cache=cache, cache_index=lengths - 1, slot_mask=active,
            attention_backend=backend)
        np.testing.assert_allclose(np.asarray(lg)[0, 0], want[t],
                                   atol=LOGIT_TOL, err_msg=str(t))


def test_a_bfloat16_router_or_a_missing_window_would_be_seen(
        small, ref, monkeypatch):
    cfg, model, variables = small
    ids = _prompt(40, 3)
    want = _ref_logits(ref, ids, np.arange(40))
    # the window ignored (the reference's own control, and the program's)
    nw = _ref_logits(ref, ids, np.arange(40), quant="no_window")
    assert np.abs(nw - want).max() > 1000 * LOGIT_TOL
    wide = LlamaModel(dataclasses.replace(cfg, sliding_window=MAX_LEN))
    got = np.asarray(wide.apply(variables, jnp.asarray(ids)[None]))[0]
    assert np.abs(got - want).max() > 1000 * LOGIT_TOL
    np.testing.assert_allclose(got, nw, atol=LOGIT_TOL)
    # the router's product in bfloat16: the scores move and, now and
    # then, a top-4 choice flips
    many = np.stack([_prompt(40, 100 + i) for i in range(8)])
    want8 = np.stack([_ref_logits(ref, r, np.arange(40)) for r in many])
    got8 = np.asarray(model.apply(variables, jnp.asarray(many)))
    np.testing.assert_allclose(got8, want8, atol=LOGIT_TOL)
    monkeypatch.setattr(X, "_ROUTER_DTYPE", jnp.bfloat16)
    low8 = np.asarray(LlamaModel(cfg).apply(variables, jnp.asarray(many)))
    monkeypatch.undo()
    assert np.abs(low8 - want8).max() > 20 * LOGIT_TOL
    # the interleaved pairs read as half-split ones
    half = LlamaModel(dataclasses.replace(cfg, rope_style="half"))
    got = np.asarray(half.apply(variables, jnp.asarray(ids)[None]))[0]
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


# -- SlotEngine ------------------------------------------------------------------

class Drive:
    """What a serving loop keeps beside the engine."""

    def __init__(self, eng):
        self.eng = eng
        self.by_slot, self.tokens, self.logits, self.paths = {}, {}, {}, {}
        self.reused = {}

    def admit(self, name, prompt, max_new):
        res = self.eng.admit(prompt, max_new)
        assert res is not None
        self.tokens[name] = [res.token]
        self.logits[name] = res.logits
        self.paths[name] = res.path
        self.reused[name] = res.reused_tokens
        if not res.finished:
            self.by_slot[res.slot] = name
        return res.slot

    def step(self):
        events = self.eng.step()
        for ev in events:
            name = self.by_slot[ev.slot]
            self.tokens[name].append(ev.token)
            if ev.finished:
                del self.by_slot[ev.slot]
        return events

    def run(self):
        while self.eng.active.any():
            assert self.step()


def _kv_rows(eng, slot):
    return [np.asarray(layer[k][slot]) for layer in eng.cache
            for k in ("k", "v")]


@pytest.mark.parametrize("backend", BACKENDS)
def test_slot_engine_serves_the_references_logits_through_everything(
        small, ref, backend):
    cfg, model, variables = small
    eng = SlotEngine(model, variables, n_slots=3, max_len=MAX_LEN,
                     attention_backend=backend, min_bucket=8,
                     name=f"t-moe-mix-{backend}")
    assert eng.experts and not eng.recurrent
    d = Drive(eng)
    pre = _prompt(20, 50)               # a shared preamble past the window
    p = {"a": np.concatenate([pre, _prompt(3, 51)]),      # 23 of 32: padded
         "b": _prompt(9, 52),                             # 9 of 16
         "c": np.concatenate([pre, _prompt(7, 53)]),      # reuses a's 20
         "d": np.concatenate([pre, _prompt(5, 54)])}      # into a's slot
    d.admit("a", p["a"], 14)
    d.admit("b", p["b"], 4)
    while "b" in d.by_slot.values():
        d.step()
    freed = int(np.flatnonzero(~eng.active)[0])
    d.step()                            # the step in flight has run over it
    idle = _kv_rows(eng, freed)
    d.step()                            # an inactive slot beside an active
    for r0, r1 in zip(idle, _kv_rows(eng, freed)):
        np.testing.assert_array_equal(r0, r1)             # bit for bit
    # c copies a's preamble out of a's slot, 20 tokens: past the window's
    # edge (8), so its tail's window layers read copied keys
    d.admit("c", p["c"], 10)
    assert d.paths["c"] == "reuse" and d.reused["c"] == 20
    d.run()
    # d lands in a retired slot and reuses what it finds there or beside it
    d.admit("d", p["d"], 12)
    assert d.paths["d"] == "reuse" and d.reused["d"] >= 20
    d.run()
    assert eng.prefix_hits == 2 and eng._flight is None
    for k in "abcd":
        gap, first = _gap(ref, p[k], d.tokens[k])
        assert gap < LOGIT_TOL, (k, gap)
        np.testing.assert_allclose(d.logits[k], first, atol=LOGIT_TOL,
                                   err_msg=k)


def test_the_counters_and_spans_say_what_the_experts_cost(small, tmp_path):
    cfg, model, variables = small
    from synapseml_tpu.telemetry import get_tracer
    name = "t-moe-count"
    eng = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                     attention_backend="dense", min_bucket=8, name=name)
    reg = get_registry()

    def counter(metric):
        return reg.counter(metric, "", ("engine",)).value(engine=name)
    held = 4 * 8 * 3 * 64 * 32 * 4        # layers x experts x 3 x d x F x f32
    assert reg.gauge("llm_expert_weight_bytes_held", "", ("engine",)
                     ).value(engine=name) == held
    ids = _prompt(13, 60)
    jax.profiler.start_trace(str(tmp_path))         # step spans are live
    try:
        eng.admit(ids, 6)
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    # the admission's count against the reference's routing, by hand:
    # 13 real tokens of a 16-token bucket, pairs whose expert is 4..11
    ref_mod = harness.load_module(os.path.join(
        ROOT, "benchmark", "references", "command-a-plus-l4-e16.py"))
    x = ref_mod.outer_weights(SMALL, SEED)["embed"][jnp.asarray(ids)] \
        .astype(jnp.float32)
    w0 = ref_mod.layer_weights(SMALL, SEED, 0)
    idx, _ = ref_mod.route(ref_mod.layer_norm(x, w0["ln"], 1e-5),
                           w0["router"], k=4, quant=None)
    first_layer = int(((np.asarray(idx) >= 4) & (np.asarray(idx) < 12)).sum())
    admit = [s for s in get_tracer().spans("engine.admit")
             if "expert_pairs_held" in s.attrs][-1]
    assert first_layer <= admit.attrs["expert_pairs_held"] <= 13 * 4 * 4
    assert admit.attrs["expert_pairs_held"] > first_layer   # four layers
    steps = [s for s in get_tracer().spans("engine.step")
             if "experts_touched" in s.attrs][-2:]
    for s in steps:
        # one token, 4 pairs a layer at most, an expert a pair
        assert 0 <= s.attrs["expert_pairs_held"] <= 16
        assert s.attrs["experts_touched"] == s.attrs["expert_pairs_held"]
        assert s.attrs["kv_window_span_sum"] == 8 < s.attrs["kv_span_sum"]
        assert s.attrs["overlapped"] in (True, False)
    total = admit.attrs["expert_pairs_held"] + sum(
        s.attrs["expert_pairs_held"] for s in steps)
    assert counter("llm_expert_pairs_total") >= total > 0
    assert counter("llm_experts_touched_total") > 0
    # a dense model has none of it
    plain = LlamaConfig.tiny(dtype=jnp.float32)
    pm = LlamaModel(plain)
    pv = pm.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    pe = SlotEngine(pm, {"params": pv["params"]}, n_slots=2, max_len=64,
                    attention_backend="dense", name="t-moe-plain")
    assert not pe.experts and pe._no_prev.shape == (2,)
    pe.admit(np.arange(1, 9), 3)
    pe.step()
    assert pe._step_experts == {}


@pytest.mark.pallas
def test_the_byte_ledger_counts_a_window_layer_by_its_window(small):
    cfg, model, variables = small
    eng = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                     attention_backend="interpret", min_bucket=8,
                     name="t-moe-ledger")
    tile = eng._paged_geo.tile
    assert tile == 32                   # two tiles of a 64-position row
    eng.admit(_prompt(45, 70), 4)
    eng.step()
    eng.step()
    # the step accounted fed position 46 (span 47) beside an idle slot (1):
    # the full layer walks ceil(47/32) + 1 tiles, a window layer those from
    # floor((47 - 8) / 32) on: 1 + 1
    assert eng._step_tiles["paged_tiles_live"] == (2 + 1) + 3 * (1 + 1)
    assert paged_live_tiles([30, 1], 8) == 5
    assert paged_live_tiles([30, 1], 8, window=8) == 3
    assert paged_live_tiles([8, 9, 16, 17], 8, window=8) == 1 + 2 + 1 + 2
    assert paged_read_bytes([30, 1], 8, 2, 128, 4, 1, window=8) == \
        2 * 3 * 8 * 2 * 128 * 4


# -- what works unchanged over window layers and experts -----------------------------

def test_speculative_verify_works_over_window_layers_and_experts(small, ref):
    cfg, model, variables = small
    # a prompt that repeats itself, so the n-gram drafter proposes spans
    ids = np.tile(_prompt(6, 80), 5)[:28]
    plain = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                       attention_backend="dense", min_bucket=8,
                       name="t-moe-nospec")
    plain.admit(ids, 20)
    want = plain.run_to_completion()[0]
    spec = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                      attention_backend="dense", min_bucket=8,
                      spec_draft_len=4, name="t-moe-spec")
    spec.admit(ids, 20)
    got = spec.run_to_completion()[0]
    gap, _ = _gap(ref, ids, [int(t) for t in got])
    assert gap < LOGIT_TOL
    assert len(got) == len(want) == 20
    assert spec.spec_steps > 0 and spec._step_experts["expert_pairs_held"] >= 0


def test_the_host_arena_spills_and_restores_window_layers_rows(small, ref):
    cfg, model, variables = small
    arena = HostKVArena(max_bytes=1 << 24, name="t-moe-arena")
    eng = SlotEngine(model, variables, n_slots=1, max_len=MAX_LEN,
                     attention_backend="dense", min_bucket=8,
                     kv_arena=arena, name="t-moe-arena")
    d = Drive(eng)
    pre = _prompt(20, 90)
    d.admit("a", np.concatenate([pre, _prompt(4, 91)]), 5)
    d.run()
    d.admit("other", _prompt(30, 92), 3)        # the one slot is overwritten
    d.run()
    p = np.concatenate([pre, _prompt(6, 93)])
    d.admit("b", p, 8)                          # its preamble from the host
    assert d.paths["b"] == "restore" and d.reused["b"] == 20
    d.run()
    gap, first = _gap(ref, p, d.tokens["b"])
    assert gap < LOGIT_TOL
    np.testing.assert_allclose(d.logits["b"], first, atol=LOGIT_TOL)


def test_a_prefill_worker_serves_such_an_engine(small):
    from synapseml_tpu.serving.disagg import PrefillWorker
    cfg, model, variables = small
    eng = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                     attention_backend="dense", min_bucket=8,
                     name="t-moe-disagg")
    worker = PrefillWorker(eng)                 # no refusal: K/V by position
    assert worker is not None and not eng.recurrent


# -- the kernels and the blocks ----------------------------------------------------

def _dense_attention(q, k, v, spans, window):
    """The equations over every key, one slot and query at a time."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    out = np.zeros((B, S, H, D), np.float32)
    for b in range(B):
        for j in range(S):
            pos = int(spans[b]) - S + j
            lo = 0 if window is None else max(0, pos - window + 1)
            for h in range(H):
                kk = np.asarray(k[b, lo:pos + 1, h // (H // KV)], np.float64)
                vv = np.asarray(v[b, lo:pos + 1, h // (H // KV)], np.float64)
                s = kk @ np.asarray(q[b, j, h], np.float64) / np.sqrt(D)
                p = np.exp(s - s.max())
                out[b, j, h] = (p / p.sum()) @ vv
    return out


@pytest.mark.pallas
@pytest.mark.parametrize("S", [1, 3], ids=["decode", "verify"])
def test_paged_kernel_with_a_window_matches_the_dense_equations(S):
    B, T, H, KV, D, W, tile = 6, 64, 8, 2, 128, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, T, KV, D))
    v = jax.random.normal(ks[2], (B, T, KV, D))
    # under, at and over the window, on and off a tile's edge, the row's end
    spans = jnp.asarray([max(S, 5), 16, 17, 24 + S, 41, 64], jnp.int32)
    got = paged_decode_attention(q, k, v, spans, tile=tile, interpret=True,
                                 window=W)
    want = _dense_attention(q, k, v, np.asarray(spans), W)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)
    # and without one it is the kernel it was
    np.testing.assert_allclose(
        np.asarray(paged_decode_attention(q, k, v, spans, tile=tile,
                                          interpret=True)),
        _dense_attention(q, k, v, np.asarray(spans), None), atol=2e-6)
    assert np.abs(want - _dense_attention(q, k, v, np.asarray(spans),
                                          None)).max() > 1e-2


def test_the_geometry_at_128_query_heads_over_8():
    assert cache_row_heads(8, jnp.bfloat16) == 8
    geo = paged_geometry(5632, 128, 8, 128, jnp.bfloat16)
    # a K tile of 128 positions x 8 heads x 128 x 2 B = 256 KiB; 44 tiles
    assert (geo.tile, geo.total_tiles) == (128, 44)
    assert geo.vmem_bytes < 13 * 1024 * 1024
    # group 16: 128 query rows against a tile's 1,024 flat K/V rows
    wide = paged_geometry(5632, 128, 8, 128, jnp.bfloat16, max_query_span=4)
    assert wide is not None and wide.tile == 128


@pytest.mark.pallas
@pytest.mark.parametrize("window", [None, 24], ids=["full", "window"])
@pytest.mark.parametrize("start", [0, 40], ids=["cold", "tail"])
def test_prefill_attention_in_blocks_matches_the_dense_scores(window, start):
    """The prefill kernel at this configuration's group, in blocks of 16
    queries by 32 keys (``tests/test_llm_prefill_kernel.py`` has the other
    kinds' cases)."""
    from synapseml_tpu.models.llm.pallas_attn import prefill_attention
    B, S, T, H, KV, D = 1, 64, 128, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, T, KV, D))
    v = jax.random.normal(ks[2], (B, T, KV, D))
    pos = (start + jnp.arange(S))[None]
    got = prefill_attention(q, k, v, start, S, bq=16, bk=32, window=window,
                            interpret=True)
    qg = q.reshape(B, S, KV, H // KV, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k) / np.sqrt(D)
    see = jnp.arange(T)[None, None, :] <= pos[:, :, None]
    if window is not None:
        see &= jnp.arange(T)[None, None, :] > pos[:, :, None] - window
    p = jax.nn.softmax(jnp.where(see[:, None, None], s, -jnp.inf), -1)
    want = jnp.einsum("bkgst,btkd->bskgd", p, v).reshape(B, S, H * D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.pallas
def test_the_model_takes_the_kernel_where_the_geometry_has_a_tile(
        small, ref, every_prefill_tiled):
    """The same logits with the threshold at zero and the kernels' backend:
    every prefill pass, cold and behind a prefix, through the kernel; the
    pass without a cache (training) stays on the plain scores."""
    cfg, model, variables = small
    ids = _prompt(48, 6)
    want = _ref_logits(ref, ids, np.arange(48))
    cache = jax.tree.map(lambda c: c[:1], init_cache(cfg, 1, MAX_LEN))
    lg, cache = model.apply(variables, jnp.asarray(ids[:32])[None],
                            positions=jnp.arange(32)[None], cache=cache,
                            cache_index=0, valid_len=32,
                            attention_backend="interpret")
    np.testing.assert_allclose(np.asarray(lg)[0], want[:32], atol=LOGIT_TOL)
    lg, _ = model.apply(variables, jnp.asarray(ids[32:])[None],
                        positions=(32 + jnp.arange(16))[None], cache=cache,
                        cache_index=32, valid_len=16,
                        attention_backend="interpret")
    np.testing.assert_allclose(np.asarray(lg)[0], want[32:], atol=LOGIT_TOL)
    text = str(jax.make_jaxpr(lambda t: model.apply(
        variables, t, positions=jnp.arange(32)[None], cache=cache,
        cache_index=0, attention_backend="interpret"))(
            jnp.asarray(ids[:32])[None]))
    assert "prefill_attention" in text
    got = np.asarray(model.apply(variables, jnp.asarray(ids)[None]))[0]
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)


# -- the expert layer ---------------------------------------------------------------

def _layer_setup(ref, hc, seed=SEED):
    """(h, the reference's layer-0 weights) for a share ``hc``."""
    w = ref.layer_weights(hc, seed, 0)
    h = jax.random.normal(jax.random.PRNGKey(9), (48, hc["hidden_size"]))
    return h, w


def _program_experts(hc, w, h, backend, valid=None):
    cfg = program_config(hc)
    layer = X.ExpertFFN(cfg)
    params = {"router": w["router"], "experts_gate": w["experts_gate"],
              "experts_up": w["experts_up"], "experts_down": w["experts_down"],
              "shared_gate": {"kernel": w["shared_gate"]},
              "shared_up": {"kernel": w["shared_up"]},
              "shared_down": {"kernel": w["shared_down"]}}
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    valid = jnp.ones((1, h.shape[0]), bool) if valid is None else valid
    out, state = layer.apply({"params": params}, h[None], valid, backend,
                             mutable=["stats"])
    return np.asarray(out)[0], X.stats_totals(state["stats"])


@pytest.mark.parametrize("backend", BACKENDS)
def test_the_expert_layer_is_exact_under_the_worst_imbalance(ref, backend):
    """Every token to the same four experts, all held: each of them gets all
    48 tokens and the other four none.  No capacity, nothing dropped."""
    h, w = _layer_setup(ref, SMALL)
    bias = np.zeros((64, 16), np.float32)
    router = np.asarray(w["router"], np.float32) * 1e-3
    h = h.at[:, 0].set(40.0)            # one loud coordinate steers them all
    router[0, [4, 6, 7, 11]] = 1.0
    w = dict(w, router=jnp.asarray(router + bias))
    want = np.asarray(ref.experts(h, w, k=4, first=jnp.asarray(4), ns=2,
                                  quant=None))
    got, stats = _program_experts(SMALL, w, h, backend)
    # pairs, experts touched, tiles: 48 pairs an expert in tiles of 32 rows
    assert X._row_tile(48 * 4, 8) == 32
    assert list(np.asarray(stats)) == [48 * 4, 4, 4 * 2]
    np.testing.assert_allclose(got, want, atol=5e-5 * np.abs(want).max())
    # tokens that are not real route nowhere
    valid = (jnp.arange(48) < 10)[None]
    got, stats = _program_experts(SMALL, w, h, backend, valid)
    assert list(np.asarray(stats)) == [10 * 4, 4, 4]
    idle, stats = _program_experts(SMALL, w, h, backend,
                                   jnp.zeros((1, 48), bool))
    assert list(np.asarray(stats)) == [0, 0, 0]
    shared = np.asarray(ref.swiglu(h, w["shared_gate"], w["shared_up"],
                                   w["shared_down"], None)) / 2
    np.testing.assert_allclose(idle, shared, atol=5e-5 * np.abs(want).max())


#: a layer whose router selects by score plus bias and has no shared expert,
#: in the published keys of the family that has one (``mimo_v2``), at toy
#: sizes: layer 1 of two, the reference ``benchmark/references/
#: mimo-v2.5-l7-e16.py``
BIASED = {
    "model_type": "mimo_v2", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 8,
    "num_key_value_heads": 2, "swa_num_key_value_heads": 4, "head_dim": 64,
    "v_head_dim": 32, "layernorm_epsilon": 1e-5, "tie_word_embeddings": False,
    "hybrid_layer_pattern": [0, 1], "moe_layer_freq": [0, 1],
    "sliding_window": 8, "rope_theta": 10000000, "swa_rope_theta": 10000,
    "partial_rotary_factor": 0.25, "attention_value_scale": 0.707,
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "num_experts_per_tok": 4,
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "topk_method": "noaux_tc",
    "n_routed_experts": 16, "router_experts": 16, "experts_first": 0}


@pytest.fixture(scope="module")
def biased_ref():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "references", "mimo-v2.5-l7-e16.py"))


def _biased_experts(hc, w, h, backend):
    """The program's expert layer under the biased router, for a share."""
    cfg = LlamaConfig.from_hf(
        dict(hc, n_routed_experts=hc["router_experts"]), dtype=jnp.float32,
        max_len=MAX_LEN, experts_first=hc["experts_first"],
        experts_held=hc["n_routed_experts"])
    assert cfg.expert_selection_bias and cfg.num_shared_experts == 0
    params = {k: jnp.asarray(w[k], jnp.float32) for k in (
        "router", "router_bias", "experts_gate", "experts_up",
        "experts_down")}
    out, state = X.ExpertFFN(cfg).apply(
        {"params": params}, h[None], jnp.ones((1, h.shape[0]), bool), backend,
        mutable=["stats"])
    return np.asarray(out)[0], X.stats_totals(state["stats"])


@pytest.mark.parametrize("router", ["plain", "biased"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_the_shares_add_up_to_the_uncut_layer(ref, biased_ref, backend,
                                              router):
    """8 shares of 2 of 16 experts: their routed parts, with the shared
    experts counted once, are the reference's uncut layer; so is the
    program's own uncut layer.  ``biased``: 16 shares of ONE expert each
    under a router that selects by score plus bias and weighs by the score,
    no shared expert: the bias changes which experts a token takes, so a
    share that left it out, or weighed by it, would not add up."""
    if router == "biased":
        h = jax.random.normal(jax.random.PRNGKey(9), (48, 64))
        w_all = biased_ref.layer_weights(BIASED, SEED, 1)
        want = np.asarray(biased_ref.experts(
            h, w_all, k=4, first=jnp.asarray(0), quant=None)[0])
        scale = np.abs(want).max()
        got, stats = _biased_experts(BIASED, w_all, h, backend)
        assert int(stats[0]) == 48 * 4
        np.testing.assert_allclose(got, want, atol=5e-5 * scale)
        # the bias decides: without it other experts are selected
        plain_idx = jax.lax.top_k(jax.nn.sigmoid(
            h @ jnp.asarray(w_all["router"], jnp.float32)), 4)[1]
        idx = biased_ref.route(h, w_all["router"], w_all["router_bias"], k=4,
                               quant=None)[0]
        changed = np.mean(np.sort(np.asarray(plain_idx), -1)
                          != np.sort(np.asarray(idx), -1))
        assert changed > 0.1
        total, pairs = np.zeros_like(want), 0
        for s in range(16):
            share = dict(BIASED, n_routed_experts=1, experts_first=s)
            w = biased_ref.layer_weights(share, SEED, 1)
            np.testing.assert_array_equal(
                np.asarray(w["experts_gate"]),
                np.asarray(w_all["experts_gate"])[s:s + 1])
            np.testing.assert_array_equal(np.asarray(w["router_bias"]),
                                          np.asarray(w_all["router_bias"]))
            part, st = _biased_experts(share, w, h, backend)
            np.testing.assert_allclose(part, np.asarray(biased_ref.experts(
                h, w, k=4, first=jnp.asarray(s), quant=None)[0]),
                atol=5e-5 * scale)
            total += part
            pairs += int(st[0])
        assert pairs == 48 * 4                  # each pair on one chip
        np.testing.assert_allclose(total, want, atol=1e-4 * scale)
        return
    uncut = dict(SMALL, num_experts=16, experts_first=0)
    h, w_all = _layer_setup(ref, uncut)
    want = np.asarray(ref.experts(h, w_all, k=4, first=jnp.asarray(0), ns=2,
                                  quant=None))
    shared = np.asarray(ref.swiglu(h, w_all["shared_gate"],
                                   w_all["shared_up"], w_all["shared_down"],
                                   None)) / 2
    got, stats = _program_experts(uncut, w_all, h, backend)
    assert int(stats[0]) == 48 * 4              # every pair is computed
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=5e-5 * scale)
    total = np.zeros_like(want)
    pairs = 0
    for s in range(8):
        share = dict(SMALL, num_experts=2, experts_first=2 * s)
        _, w = _layer_setup(ref, share)
        # a share's experts are the uncut layer's own
        np.testing.assert_array_equal(np.asarray(w["experts_gate"]),
                                      np.asarray(w_all["experts_gate"])[
                                          2 * s:2 * s + 2])
        np.testing.assert_array_equal(np.asarray(w["router"]),
                                      np.asarray(w_all["router"]))
        part, st = _program_experts(share, w, h, backend)
        # the reference is given the same share and reads the same
        np.testing.assert_allclose(part, np.asarray(ref.experts(
            h, w, k=4, first=jnp.asarray(2 * s), ns=2, quant=None)),
            atol=5e-5 * scale)
        total += part - shared
        pairs += int(st[0])
    assert pairs == 48 * 4                      # each pair on one chip
    np.testing.assert_allclose(total + shared, want, atol=1e-4 * scale)
    assert np.abs(total).max() > 0.1 * scale    # the routed part is no noise
