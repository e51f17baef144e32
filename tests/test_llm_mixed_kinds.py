"""A decoder whose attention is described BY LAYER KIND (4 K/V heads on full
layers and 8 on window layers at real size; keys wider than values; rotary
embeddings on part of a head, with a theta a kind; a sink logit on window
layers; a scale on the values), a dense feed-forward before expert layers, a
router that selects by score plus bias, and window layers on a RING.

Pinned here, at small sizes on the CPU (hidden 64, 8 heads of 64 over 2 K/V
heads on full and 4 on window layers, values 32 wide, 16 rotary dims, thetas
1e7 and 1e4, a window of 8 whose ring is 16 rows with ``RING_BLOCK`` set to 8,
16 experts with 4 a token of which this share holds 8, the pattern full,
window, window, full with layer 0 dense):

- the model (full forward, prefill, decode through the cache and the ring to
  more than three rings, then ``SlotEngine``) against the plain float32
  reference the benchmark keeps for MiMo-V2.5
  (``benchmark/references/mimo-v2.5-l7-e16.py``, which shares no code with the
  program), by LOGITS, on the dense path and with the kernels in interpret
  mode;
- what a fault reads: bfloat16 in place of float32, the sink, the value scale,
  the selection bias, the second theta, the rotary share or the ring's wrap
  left out;
- the packed paged kernel (two heads a row, keys wider than values, the sink,
  ring addressing) against the equations one query at a time;
- what the engine does on a ring: a prefix reuse past the ring's spare rows is
  skipped and counted, one inside them is served; a drafter, a host arena and a
  prefill worker are refused at construction;
- the three other serving configurations trace to the decode-step and prefill
  programs recorded (a digest of the jaxpr's text: before this description
  existed, and again where a later change to shared code meant to change
  them).

Tolerances.  Program and reference both compute in float32 from the same
bfloat16-rounded weights and differ in summation order over 4 layers and a
hundred tokens: some 2e-7 on logits of spread 0.17.  ``LOGIT_TOL`` = 5e-5
leaves two orders of room and lies under every fault above.
"""

import dataclasses
import functools
import hashlib
import json
import os
import re
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
from synapseml_tpu.models.llm import slots as S  # noqa: E402
from synapseml_tpu.models.llm.pallas_attn import (  # noqa: E402
    paged_decode_attention, paged_geometry, paged_read_bytes)
from synapseml_tpu.telemetry import get_registry  # noqa: E402

LOGIT_TOL = 5e-5
SEED = 36
MAX_LEN = 128
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2.5-l7-e16.json")
#: the published keys at toy sizes: this share holds experts 4..11 of 16
SMALL = {
    "model_type": "mimo_v2", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 4, "num_attention_heads": 8,
    "swa_num_attention_heads": 8, "num_key_value_heads": 2,
    "swa_num_key_value_heads": 4, "head_dim": 64, "swa_head_dim": 64,
    "v_head_dim": 32, "swa_v_head_dim": 32, "layernorm_epsilon": 1e-5,
    "tie_word_embeddings": False, "hybrid_layer_pattern": [0, 1, 1, 0],
    "moe_layer_freq": [0, 1, 1, 1], "sliding_window": 8,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "partial_rotary_factor": 0.25, "attention_value_scale": 0.707,
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "num_experts_per_tok": 4,
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "n_shared_experts": None, "routed_scaling_factor": None,
    "max_position_embeddings": 1048576,
    # the share: the reference's names
    "n_routed_experts": 8, "router_experts": 16, "experts_first": 4}
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


@pytest.fixture
def ring8(monkeypatch):
    """A ring of 8 + 8 rows behind the window of 8 (at ``RING_BLOCK`` 128 a
    ring is 256 rows and more)."""
    monkeypatch.setattr(M, "RING_BLOCK", 8)


def program_config(hc, **kw):
    """The program's description from the published keys, the share from the
    reference's three (``from_hf`` reads ``n_routed_experts`` as the router's
    width, which is what it is in a ``config.json``)."""
    hf = dict(hc, n_routed_experts=hc["router_experts"])
    return LlamaConfig.from_hf(
        hf, dtype=jnp.float32, max_len=MAX_LEN,
        experts_first=hc["experts_first"],
        experts_held=hc["n_routed_experts"], **kw)


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


def _with_kind(cfg, kind, **changes):
    kinds = dict(cfg.attention_kinds)
    kinds[kind] = dataclasses.replace(kinds[kind], **changes)
    return dataclasses.replace(cfg, attention_kinds=kinds)


# -- the description -----------------------------------------------------------

def test_from_hf_reads_attention_by_kind_and_a_feed_forward_by_layer(small):
    cfg = small[0]
    full, win = cfg.attention("full_attention"), \
        cfg.attention("sliding_attention")
    assert (full.num_kv_heads, win.num_kv_heads) == (2, 4)
    assert (full.head_dim, full.v_head_dim, full.rotary_dim) == (64, 32, 16)
    assert (full.rope_theta, win.rope_theta) == (1e7, 1e4)
    assert (full.sink, win.sink) == (False, True)
    assert full.value_scale == win.value_scale == 0.707
    assert cfg.layer_kinds == ("full_attention", "sliding_attention",
                               "sliding_attention", "full_attention")
    assert cfg.ffn_kinds == ("dense", "experts", "experts", "experts")
    assert cfg.has_experts and cfg.num_expert_layers == 3
    assert (cfg.d_ff, cfg.expert_d_ff) == (96, 32)
    assert cfg.expert_selection == "sigmoid" and cfg.expert_selection_bias
    assert (cfg.num_experts, cfg.experts_first, cfg.experts_held_count) == \
        (16, 4, 8)
    assert cfg.packed("full_attention") and cfg.packed("sliding_attention")
    # the defaults every other configuration reads are what they were
    plain = LlamaConfig.tiny()
    a = plain.attention("full_attention")
    assert (a.num_kv_heads, a.head_dim, a.v_head_dim, a.rotary_dim,
            a.rope_theta, a.sink, a.value_scale) == \
        (4, 16, 16, 16, 500_000.0, False, 1.0)
    assert not plain.packed("full_attention") and plain.ffn_types is None
    assert plain.ffn_kinds == ("dense",) * 4 and not plain.has_experts
    # a kind without rotary embeddings stays without
    nope = LlamaConfig.tiny(rope_layers=("sliding_attention",),
                            sliding_window=8,
                            layer_types=("sliding_attention",) * 3
                            + ("full_attention",))
    assert nope.attention("full_attention").rope_theta is None
    assert nope.attention("sliding_attention").rope_theta == 500_000.0
    # one word for every layer keeps meaning what it means; first_k_dense
    hf = dict(SMALL, n_routed_experts=16, moe_layer_freq=None,
              first_k_dense_replace=2)
    assert LlamaConfig.from_hf(hf).ffn_kinds == \
        ("dense", "dense", "experts", "experts")
    hf = dict(hf, first_k_dense_replace=0)
    every = LlamaConfig.from_hf(hf)
    assert every.ffn == "experts" and every.ffn_types is None
    with pytest.raises(ValueError, match="not attention layer kinds"):
        LlamaConfig.tiny(attention_kinds={"linear_attention": {}})
    with pytest.raises(ValueError, match="ffn_types"):
        LlamaConfig.tiny(ffn_types=("dense",) * 3)
    # the router's grouping and scaling are read for every family
    assert LlamaConfig.from_hf(dict(hf, routed_scaling_factor=2.5)
                               ).routed_scaling_factor == 2.5
    grouped = LlamaConfig.from_hf(dict(hf, n_group=8, topk_group=2))
    assert (grouped.expert_groups, grouped.expert_groups_kept) == (8, 2)
    assert (every.expert_groups, every.routed_scaling_factor) == (1, 1.0)
    with pytest.raises(ValueError, match="hybrid_block_size"):
        LlamaConfig.from_hf(dict(hf, hybrid_block_size=4))


def test_the_parameter_tree_is_the_configuration_files_map(small):
    _, model, variables = small
    import flax.linen as nn
    want = jax.tree.map(lambda a: a.shape, nn.meta.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))["params"])))
    got = jax.tree.map(lambda a: a.shape, variables["params"])
    assert got == want
    assert got["layer_0"]["attn"]["k_proj"]["kernel"] == (64, 2 * 64)
    assert got["layer_1"]["attn"]["k_proj"]["kernel"] == (64, 4 * 64)
    assert got["layer_1"]["attn"]["v_proj"]["kernel"] == (64, 4 * 32)
    assert got["layer_1"]["attn"]["o_proj"]["kernel"] == (8 * 32, 64)
    assert got["layer_1"]["attn"]["sink"] == (8,)
    assert "sink" not in got["layer_0"]["attn"]
    assert got["layer_0"]["gate_proj"]["kernel"] == (64, 96)
    assert "moe" not in got["layer_0"] and "gate_proj" not in got["layer_1"]
    assert got["layer_1"]["moe"]["router"] == (64, 16)
    assert got["layer_1"]["moe"]["router_bias"] == (16,)
    assert got["layer_1"]["moe"]["experts_gate"] == (8, 64, 32)
    assert got["lm_head"]["kernel"] == (64, 256)        # untied


def test_the_published_widths_the_cut_and_the_cache_by_hand(benchmark_config):
    c = benchmark_config
    pub = {k: v for k, v in dict(c, **c["published"]).items()
           if not isinstance(v, dict) or k == "rope_scaling"}
    cfg = LlamaConfig.from_hf(pub)
    assert cfg.num_layers == 48 and cfg.num_window_layers == 39
    assert cfg.layer_kinds[:12] == ("full_attention",) \
        + ("sliding_attention",) * 4 + ("full_attention",) \
        + ("sliding_attention",) * 5 + ("full_attention",)
    assert cfg.ffn_kinds == ("dense",) + ("experts",) * 47
    full, win = cfg.attention("full_attention"), \
        cfg.attention("sliding_attention")
    assert (cfg.d_model, cfg.num_heads) == (4096, 64)
    assert (full.num_kv_heads, win.num_kv_heads) == (4, 8)
    assert (full.head_dim, full.v_head_dim, full.rotary_dim) == (192, 128, 64)
    assert (win.head_dim, win.v_head_dim, win.rotary_dim) == (192, 128, 64)
    assert (full.rope_theta, win.rope_theta) == (1e7, 1e4)
    assert (full.sink, win.sink) == (False, True)
    assert full.value_scale == 0.707 and cfg.sliding_window == 128
    assert (cfg.d_ff, cfg.expert_d_ff, cfg.num_experts,
            cfg.num_experts_per_tok) == (16384, 2048, 256, 8)
    assert cfg.expert_selection_bias and cfg.norm_topk_prob
    assert cfg.vocab_size == 152576 and not cfg.tie_embeddings
    # one layer by hand
    q, o = 4096 * 64 * 192, 64 * 128 * 4096
    full_mixer = q + 4096 * 4 * 192 + 4096 * 4 * 128 + o
    win_mixer = q + 4096 * 8 * 192 + 4096 * 8 * 128 + o
    assert (full_mixer, win_mixer) == (89_128_960, 94_371_840)
    expert, dense = 3 * 4096 * 2048, 3 * 4096 * 16384
    assert (expert, dense) == (25_165_824, 201_326_592)
    assert 256 * expert == 6_442_450_944
    # the program's own tree at the cut's geometry (shapes only)
    from benchmark.runners import llm_serve
    cut = llm_serve.build_model(c).cfg
    assert cut.layer_kinds == ("full_attention",) \
        + ("sliding_attention",) * 5 + ("full_attention",)
    assert cut.ffn_kinds == ("dense",) + ("experts",) * 6
    assert cut.attention_kinds == cfg.attention_kinds
    assert (cut.num_experts, cut.experts_held_count) == (256, 16)
    shapes = jax.eval_shape(lambda: LlamaModel(cut).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])
    n = {k: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(v))
         for k, v in shapes.items()}
    norms = 2 * 4096
    assert n["layer_0"] == full_mixer + dense + norms
    router = 4096 * 256 + 256
    assert n["layer_1"] == win_mixer + 64 + router + 16 * expert + norms
    assert n["layer_6"] == full_mixer + router + 16 * expert + norms
    assert n["tok_embed"] == n["lm_head"] == 152576 * 4096     # not sliced
    assert 9.04e9 < 2 * sum(n.values()) < 9.06e9               # 9.05 GB
    # the cache at 24 x 16,384: two full layers by position, five rings
    cache = jax.eval_shape(lambda: init_cache(cut, 24, 16384))
    assert cache[0]["k"].shape == (24, 16384 * 2, 384)     # two heads a row
    assert cache[0]["v"].shape == (24, 16384 * 2, 256)
    assert cache[1]["k"].shape == (24, 256 * 4, 384)       # a ring of 256
    nbytes = sum(int(np.prod(a.shape)) * 2 for a in jax.tree.leaves(cache))
    assert nbytes == 2 * 24 * 16384 * 2560 + 5 * 24 * 256 * 5120
    assert 2.1e9 < nbytes < 2.4e9
    # by position the five window layers would hold 16,384 rows a slot each
    assert 2 * 24 * 16384 * 2560 + 5 * 24 * 16384 * 5120 > 12.0e9
    assert c["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                            "moe_layer_freq", "n_routed_experts"]


def test_the_rule_on_sizes_beside_cache_entry():
    """A ring from two rings on; under that rows by position."""
    assert M.ring_rows(128) == 256 and M.ring_rows(4096) == 4224
    assert M.ring_rows(100) == 256 and M.ring_rows(129) == 384
    cfg = LlamaConfig.tiny(sliding_window=128, layer_types=(
        "sliding_attention",) * 3 + ("full_attention",))
    rows = M.SlidingAttention.cache_rows
    assert rows(cfg, 16384) == 256 and rows(cfg, 512) == 256
    assert rows(cfg, 511) == 511 and rows(cfg, 256) == 256
    # the other window configuration the benchmark serves: window 4,096 in
    # 5,632 rows is 1.3 rings, by position
    wide = dataclasses.replace(cfg, sliding_window=4096)
    assert rows(wide, 5632) == 5632 and rows(wide, 8448) == 4224
    assert M.CausalAttention.cache_rows(cfg, 777) == 777
    entry = M.SlidingAttention.cache_entry(cfg, 3, 1024)
    assert entry["k"].shape == (3, 256, 4, 16)         # not packed: 4-D rows
    assert M.kv_pack(192, 4) == 2 and M.kv_pack(192, 8) == 2
    assert M.kv_pack(128, 8) == 1 and M.kv_pack(64, 2) == 2
    assert M.kv_pack(192, 3) == 1 and M.kv_pack(16, 4) == 1


# -- the model against the reference ---------------------------------------------

def test_full_forward_matches_the_reference(small, ref):
    _, model, variables = small
    ids = _prompt(100, 1)
    got = np.asarray(model.apply(variables, jnp.asarray(ids)[None]))[0]
    want = _ref_logits(ref, ids, np.arange(100))
    assert want.std() > 0.1
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)


@pytest.mark.parametrize("backend", BACKENDS)
def test_prefill_then_decode_over_three_rings_matches_the_reference(
        small, ref, backend, ring8):
    cfg, model, variables = small
    ids = _prompt(100, 2)
    want = _ref_logits(ref, ids, np.arange(100))
    cache = init_cache(cfg, 2, MAX_LEN)
    assert cache[1]["k"].shape == (2, 16 * 2, 128)      # the ring, packed
    assert cache[0]["k"].shape == (2, MAX_LEN * 1, 128)
    row = jax.tree.map(lambda c: c[:1], cache)
    # a padded prefill of 20 real tokens in a bucket of 32: over a ring
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
    for t in range(20, 72):                 # 52 steps: over three rings
        lengths = jnp.asarray([t + 1, 1], jnp.int32)
        tok = jnp.asarray([ids[t], 0], jnp.int32)
        lg, cache = model.apply(
            variables, tok[:, None], positions=(lengths - 1)[:, None],
            cache=cache, cache_index=lengths - 1, slot_mask=active,
            attention_backend=backend)
        np.testing.assert_allclose(np.asarray(lg)[0, 0], want[t],
                                   atol=LOGIT_TOL, err_msg=str(t))
    for layer in cache:                     # the idle slot wrote nothing
        assert not np.asarray(layer["k"][1]).any()
    # a tail prefill that starts behind a wrapped ring reads its rows
    row = jax.tree.map(lambda c: c[:1], cache)
    lg, row = model.apply(variables, jnp.asarray(ids[72:88])[None],
                          positions=(72 + jnp.arange(16))[None], cache=row,
                          cache_index=72, valid_len=16,
                          attention_backend=backend)
    np.testing.assert_allclose(np.asarray(lg)[0], want[72:88],
                               atol=LOGIT_TOL)


def _fault(model_cfg, variables, ids):
    return np.asarray(LlamaModel(model_cfg).apply(
        variables, jnp.asarray(ids)[None]))[0]


def test_each_thing_left_out_would_be_seen(small, ref, monkeypatch):
    cfg, model, variables = small
    ids = _prompt(100, 3)
    want = _ref_logits(ref, ids, np.arange(100))
    np.testing.assert_allclose(_fault(cfg, variables, ids), want,
                               atol=LOGIT_TOL)

    def off(bad):
        return np.abs(_fault(bad, variables, ids) - want).max()
    # the sink: the program without it is the reference's own control
    no_sink = {"params": jax.tree.map(lambda a: a, variables["params"])}
    for i in (1, 2):
        no_sink["params"][f"layer_{i}"] = dict(
            variables["params"][f"layer_{i}"],
            attn={k: v for k, v in
                  variables["params"][f"layer_{i}"]["attn"].items()
                  if k != "sink"})
    got = np.asarray(LlamaModel(_with_kind(
        cfg, "sliding_attention", sink=False)).apply(
            no_sink, jnp.asarray(ids)[None]))[0]
    assert np.abs(got - want).max() > 100 * LOGIT_TOL
    np.testing.assert_allclose(
        got, _ref_logits(ref, ids, np.arange(100), quant="no_sink"),
        atol=LOGIT_TOL)
    # the value scale, on either kind
    for kind in ("full_attention", "sliding_attention"):
        assert off(_with_kind(cfg, kind, value_scale=1.0)) > 100 * LOGIT_TOL
    # the second theta: window layers turned by the full layers' (16 of 64
    # dims turn, over a window of 8 positions: the smallest fault here)
    assert off(_with_kind(cfg, "sliding_attention", rope_theta=1e7)) \
        > 5 * LOGIT_TOL
    # the rotary share: the whole head turned
    assert off(_with_kind(cfg, "full_attention", rotary_dim=64)) \
        > 100 * LOGIT_TOL
    # the selection bias left out, and weighing by it
    assert off(dataclasses.replace(cfg, expert_selection_bias=False)) \
        > 100 * LOGIT_TOL
    # the window ignored: the reference's control
    nw = _ref_logits(ref, ids, np.arange(100), quant="no_window")
    assert np.abs(nw - want).max() > 100 * LOGIT_TOL
    np.testing.assert_allclose(
        _fault(dataclasses.replace(cfg, sliding_window=MAX_LEN), variables,
               ids), nw, atol=LOGIT_TOL)
    # the dense layer's width taken for the experts'
    with pytest.raises(Exception):
        _fault(dataclasses.replace(cfg, expert_d_ff=None), variables, ids)


def test_a_rounded_down_program_fails_the_tolerance(small, ref):
    """bfloat16 in place of float32 (the nearest precision below the one
    this file compares in) reads two orders over ``LOGIT_TOL``."""
    cfg, model, variables = small
    ids = _prompt(100, 4)
    want = _ref_logits(ref, ids, np.arange(100))
    low = LlamaModel(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    got = np.asarray(low.apply(jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a, variables),
        jnp.asarray(ids)[None]))[0]
    assert np.abs(got - want).max() > 100 * LOGIT_TOL
    # and the reference's own lower precision fails it too
    fp8 = _ref_logits(ref, ids, np.arange(100), quant="fp8")
    assert np.abs(fp8 - want).max() > 100 * LOGIT_TOL


def test_a_ring_that_did_not_wrap_would_be_seen(small, ref, ring8,
                                                monkeypatch):
    """Decode past the ring with the write position left unwrapped: JAX
    drops a scatter past the end, the window reads stale rows."""
    cfg, model, variables = small
    ids = _prompt(40, 5)
    want = _ref_logits(ref, ids, np.arange(40))
    cache = init_cache(cfg, 1, MAX_LEN)
    lg, cache = model.apply(variables, jnp.asarray(ids[:12])[None],
                            positions=jnp.arange(12)[None], cache=cache,
                            cache_index=0, valid_len=12)
    np.testing.assert_allclose(np.asarray(lg)[0], want[:12], atol=LOGIT_TOL)
    monkeypatch.setattr(M, "ring_rows", lambda w: -1)   # no entry is a ring
    worst = 0.0
    for t in range(12, 40):
        lengths = jnp.asarray([t + 1], jnp.int32)
        lg, cache = model.apply(
            variables, jnp.asarray(ids[t:t + 1])[None],
            positions=(lengths - 1)[:, None], cache=cache,
            cache_index=lengths - 1, slot_mask=jnp.asarray([True]))
        worst = max(worst, float(np.abs(np.asarray(lg)[0, 0] - want[t]).max()))
    assert worst > 100 * LOGIT_TOL


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


@pytest.mark.parametrize("backend", BACKENDS + [pytest.param(
    "tiled", id="kernels-tiled-prefill", marks=pytest.mark.pallas)])
def test_slot_engine_serves_the_references_logits_over_a_ring(
        small, ref, backend, ring8, request):
    cfg, model, variables = small
    if backend == "tiled":
        # every prefill through the prefill kernel too: both head counts,
        # keys wider than values, the sink, the ring's rows before a pass
        request.getfixturevalue("every_prefill_tiled")
        backend = "interpret"
    eng = SlotEngine(model, variables, n_slots=3, max_len=MAX_LEN,
                     attention_backend=backend, min_bucket=8,
                     name=f"t-kinds-{request.node.callspec.id}")
    assert eng.experts and eng.ring and not eng.recurrent
    assert not eng.kv_by_position
    by = {kc.kind: kc for kc in eng._kinds}
    assert (by["sliding_attention"].rows, by["sliding_attention"].ring,
            by["full_attention"].rows) == (16, True, MAX_LEN)
    assert (by["sliding_attention"].pack, by["full_attention"].pack) == (2, 2)
    if backend == "interpret":
        # one geometry a kind: the ring's tile is a block at most
        assert eng._paged_tile == (("full_attention", 64),
                                   ("sliding_attention", 8))
    d = Drive(eng)
    pre = _prompt(20, 50)
    p = {"a": np.concatenate([pre, _prompt(3, 51)]),      # 23 of 32: padded
         "b": _prompt(9, 52),                             # 9 of 16
         "c": np.concatenate([pre, _prompt(7, 53)]),      # a's 20: in the ring
         "d": np.concatenate([pre, _prompt(5, 54)])}      # past the ring
    d.admit("a", p["a"], 60)            # decodes to 83: five rings
    d.admit("b", p["b"], 4)
    # c finds a's preamble while a has written 23 + 1 rows: 20 + 8 - 2 spare
    d.admit("c", p["c"], 30)
    assert d.paths["c"] == "reuse" and d.reused["c"] == 20
    d.run()
    # by now a's ring has wrapped over the preamble: skipped, counted
    before = eng.prefix_reuse_skipped
    d.admit("d", p["d"], 12)
    assert d.paths["d"] == "cold_ring" and d.reused["d"] == 0
    assert eng.prefix_reuse_skipped == before + 1
    assert get_registry().counter(
        "llm_prefix_reuse_skipped_total", "", ("engine", "reason")).value(
            engine=eng.name, reason="ring_overwritten") == 1
    d.run()
    assert eng.prefix_hits == 1 and eng._flight is None
    assert eng._prefill_attention_attrs()["prefill_attention"] == (
        "tiled" if request.node.callspec.id == "kernels-tiled-prefill"
        else "dense")
    for k in "abcd":
        gap, first = _gap(ref, p[k], d.tokens[k])
        assert gap < LOGIT_TOL, (k, gap)
        np.testing.assert_allclose(d.logits[k], first, atol=LOGIT_TOL,
                                   err_msg=k)
    # preempt and resume over a ring: rebuilt from the tokens
    d.admit("e", p["a"], 40)
    for _ in range(12):
        d.step()
    slot = [s for s, n in d.by_slot.items() if n == "e"][0]
    ticket = eng.preempt(slot)
    eng._flight = None
    new = eng.resume(ticket)
    d.by_slot = {new: "e"}
    d.run()
    gap, _ = _gap(ref, p["a"], d.tokens["e"])
    assert gap < LOGIT_TOL and len(d.tokens["e"]) == 40


def test_generate_decodes_over_the_ring_like_the_reference(small, ref, ring8):
    """``generate`` (one compiled scan of single-token steps at a scalar
    offset, its cache ``prompt + new`` rows: 52, over two rings of 16)."""
    from synapseml_tpu.models.llm import generate
    cfg, model, variables = small
    ids = _prompt(12, 90)
    out = np.asarray(generate(model, variables, ids[None], max_new_tokens=40))
    toks = [int(t) for t in out[0][-40:]]
    gap, _ = _gap(ref, ids, toks)
    assert gap < LOGIT_TOL


def test_what_cannot_work_on_a_ring_is_refused_at_construction(small, ring8):
    from synapseml_tpu.serving.disagg import PrefillWorker
    cfg, model, variables = small
    kw = dict(n_slots=2, max_len=MAX_LEN, attention_backend="dense",
              min_bucket=8)
    with pytest.raises(ValueError, match="ring"):
        SlotEngine(model, variables, spec_draft_len=4, name="t-k-spec", **kw)
    with pytest.raises(ValueError, match="not rows by position"):
        SlotEngine(model, variables, name="t-k-arena",
                   kv_arena=HostKVArena(max_bytes=1 << 20, name="t-k-arena"),
                   **kw)
    eng = SlotEngine(model, variables, name="t-k-worker", **kw)
    with pytest.raises(ValueError, match="not rows by position"):
        PrefillWorker(eng)


def test_packed_rows_by_position_serve_without_a_ring(small, ref):
    """At ``RING_BLOCK`` 128 the toy's window layers keep ``max_len`` rows
    like its full layers (128 rows are under two rings of 256): the packed
    layout by position, with a drafter's verify span over it."""
    cfg, model, variables = small
    eng = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                     attention_backend="dense", min_bucket=8,
                     spec_draft_len=4, name="t-kinds-flat")
    assert not eng.ring and not eng.kv_by_position
    assert eng.cache[1]["k"].shape == (2, MAX_LEN * 2, 128)
    ids = np.tile(_prompt(6, 80), 5)[:28]
    eng.admit(ids, 20)
    got = eng.run_to_completion()[0]
    gap, _ = _gap(ref, ids, [int(t) for t in got])
    assert gap < LOGIT_TOL and len(got) == 20 and eng.spec_steps > 0
    # and a reuse copies flat rows: two rows a position on window layers
    pre = _prompt(20, 81)
    d = Drive(eng)
    d.admit("a", np.concatenate([pre, _prompt(4, 82)]), 30)
    d.admit("b", np.concatenate([pre, _prompt(6, 83)]), 6)
    assert d.paths["b"] == "reuse" and d.reused["b"] == 20
    d.run()
    gap, first = _gap(ref, np.concatenate([pre, _prompt(6, 83)]),
                      d.tokens["b"])
    assert gap < LOGIT_TOL
    np.testing.assert_allclose(d.logits["b"], first, atol=LOGIT_TOL)


def test_the_counters_spans_and_gauges_by_kind(small, ring8, tmp_path):
    cfg, model, variables = small
    from synapseml_tpu.telemetry import get_tracer
    name = "t-kinds-count"
    eng = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                     attention_backend="dense", min_bucket=8, name=name)
    reg = get_registry()

    def gauge(metric, kind):
        return reg.gauge(metric, "", ("engine", "kind")).value(
            engine=name, kind=kind)
    # reserved: slots x rows x heads x (64 + 32) x 4 B, by kind
    assert gauge("llm_kv_cache_bytes_reserved", "full_attention") == \
        2 * 2 * MAX_LEN * 2 * 96 * 4
    assert gauge("llm_kv_cache_bytes_reserved", "sliding_attention") == \
        2 * 2 * 16 * 4 * 96 * 4
    # three expert layers of 8 held experts
    assert reg.gauge("llm_expert_weight_bytes_held", "", ("engine",)
                     ).value(engine=name) == 3 * 8 * 3 * 64 * 32 * 4
    jax.profiler.start_trace(str(tmp_path))         # step spans are live
    try:
        eng.admit(_prompt(29, 60), 6)
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    step = [s for s in get_tracer().spans("engine.step")
            if "kv_ring_rows" in s.attrs][-1]
    span = step.attrs["kv_span_sum"]
    assert step.attrs["kv_ring_rows"] == 16
    assert step.attrs["kv_window_span_sum"] == 8 < span
    assert step.attrs["kv_bytes_full_attention"] == 2 * span * 2 * 96 * 4
    assert step.attrs["kv_bytes_sliding_attention"] == 2 * 8 * 4 * 96 * 4
    assert 0 <= step.attrs["expert_pairs_held"] <= 3 * 4
    admit = [s for s in get_tracer().spans("engine.admit")
             if "expert_pairs_held" in s.attrs][-1]
    assert 0 < admit.attrs["expert_pairs_held"] <= 29 * 4 * 3
    # in use: a full layer's rows grow with the span, a ring's stop at 16
    assert gauge("llm_kv_cache_bytes_in_use", "sliding_attention") == \
        2 * 16 * 4 * 96 * 4
    assert 0 < gauge("llm_kv_cache_bytes_in_use", "full_attention") < \
        gauge("llm_kv_cache_bytes_reserved", "full_attention")


@pytest.mark.pallas
def test_the_byte_ledger_by_kind(small, ring8):
    cfg, model, variables = small
    eng = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                     attention_backend="interpret", min_bucket=8,
                     name="t-kinds-ledger")
    eng.admit(_prompt(45, 70), 4)
    eng.step()
    eng.step()
    # the step accounted fed position 46 (span 47) beside an idle slot (1).
    # full layers, tile 64: 1 + 1 tiles; window layers, tile 8: the tiles
    # from floor((47 - 8) / 8) = 4 to 5, and the idle slot's first
    assert eng._step_tiles["paged_tiles_live"] == 2 * 2 + 2 * (2 + 1)
    itemsize = 4
    full = paged_read_bytes([47, 1], 64, 2, 64, itemsize, 2, d_value=32,
                            pack=2)
    assert full == 2 * 2 * 64 * 1 * (128 + 128) * itemsize   # V padded to 128
    # at the real widths nothing is padded: 2 x 192 = 384, 2 x 128 = 256
    assert paged_read_bytes([300], 128, 4, 192, 2, 1, d_value=128, pack=2) \
        == 3 * 128 * 4 * (192 + 128) * 2
    assert paged_read_bytes([300], 64, 8, 192, 2, 1, window=128,
                            d_value=128, pack=2) == \
        (5 - 2) * 64 * 8 * (192 + 128) * 2
    # one key and value width, no packing: the ledger it was
    assert paged_read_bytes([30, 1], 8, 2, 128, 4, 1, window=8) == \
        2 * 3 * 8 * 2 * 128 * 4


# -- the kernel -----------------------------------------------------------------------

def _dense_attention(q, k, v, spans, window, sink, ring_rows=None):
    """The equations over every key, one slot and query at a time.  ``k``,
    ``v`` by position ``(B, T, KV, D)``."""
    B, S, H, D = q.shape
    KV, Dv = k.shape[2], v.shape[-1]
    out = np.zeros((B, S, H, Dv), np.float32)
    for b in range(B):
        for j in range(S):
            pos = int(spans[b]) - S + j
            lo = 0 if window is None else max(0, pos - window + 1)
            for h in range(H):
                kk = np.asarray(k[b, lo:pos + 1, h // (H // KV)], np.float64)
                vv = np.asarray(v[b, lo:pos + 1, h // (H // KV)], np.float64)
                s = kk @ np.asarray(q[b, j, h], np.float64) / np.sqrt(D)
                m = max(s.max(), sink[h]) if sink is not None else s.max()
                p = np.exp(s - m)
                z = p.sum() + (np.exp(sink[h] - m) if sink is not None else 0)
                out[b, j, h] = (p / z) @ vv
    return out


@pytest.mark.pallas
@pytest.mark.parametrize("case", ["full", "window-sink", "ring-sink"])
def test_the_packed_kernel_matches_the_dense_equations(case):
    B, H, KV, D, Dv, W, tile = 5, 8, 4, 64, 32, 16, 8
    T = 96
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    k = jax.random.normal(ks[1], (B, T, KV, D))
    v = jax.random.normal(ks[2], (B, T, KV, Dv))
    sink = None if case == "full" else \
        np.asarray(2.0 + jax.random.normal(ks[3], (H,)))
    window = None if case == "full" else W
    spans = np.asarray([1, 16, 17, 41, 96], np.int32)
    want = _dense_attention(q, k, v, spans, window, sink)
    rows = T
    kc, vc = k, v
    if case == "ring-sink":
        # a ring of 24 rows (16 + 8): position p in row p mod 24, each slot's
        # ring as its span left it
        rows = 24
        kc = np.zeros((B, rows, KV, D), np.float32)
        vc = np.zeros((B, rows, KV, Dv), np.float32)
        for b in range(B):
            for p in range(int(spans[b])):
                kc[b, p % rows], vc[b, p % rows] = k[b, p], v[b, p]
    packed_k = jnp.asarray(kc).reshape(B, rows * KV // 2, 2 * D)
    packed_v = jnp.asarray(vc).reshape(B, rows * KV // 2, 2 * Dv)
    got = paged_decode_attention(
        q, packed_k, packed_v, jnp.asarray(spans), tile=tile, kv_heads=KV,
        interpret=True, window=window, pack=2, ring=case == "ring-sink",
        sink=None if sink is None else jnp.asarray(sink))
    assert got.shape == (B, 1, H, Dv)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)
    if sink is not None:
        assert np.abs(want - _dense_attention(q, k, v, spans, window,
                                              None)).max() > 1e-2


def test_the_geometry_by_kind_at_the_published_widths():
    # full layers: 4 heads of 192 + 128 in rows of two heads: a K tile of
    # 128 positions x 2 rows x 384 lanes x 2 B = 192 KiB
    full = paged_geometry(16384, 64, 4, 192, jnp.bfloat16, d_value=128,
                          pack=2)
    assert (full.tile, full.total_tiles) == (128, 128)
    # window layers: a ring of 256 rows of 8 heads, the tile a block at most
    ring = paged_geometry(256, 64, 8, 192, jnp.bfloat16, d_value=128,
                          pack=2, most=M.RING_BLOCK)
    assert (ring.tile, ring.total_tiles) == (64, 4)
    assert ring.vmem_bytes < 13 * 1024 * 1024
    # walking a window of 128 through tiles of 64 touches three at most
    assert 64 + 128 - 1 <= 256
    # the geometry of one 128-wide key and value is what it was
    assert paged_geometry(2048, 32, 8, 128, jnp.bfloat16).tile == 128


# -- the other configurations' programs ---------------------------------------------

#: sha256 (first 16 hex) of the jaxpr text of the decode-step and prefill
#: programs of three toy configurations, recorded on the commit before this
#: description existed (cfbf84a): a change to shared model code that changes
#: another configuration's programs changes its compile-cache keys and its
#: set-up time on the chip (PERF.md section 6, PR 34).  PR 37: the prefill
#: digests stay as recorded: the prefill kernel engages from 128 MiB of
#: plain scores on (``pallas_attn._PREFILL_MIN_SCORE_BYTES``), never here.
#: All twelve re-recorded by the change that reads projection weights where
#: they lie (PERF.md section 6): an attention projection no longer folds its
#: split into heads (or o_proj the merge before it) into the product where
#: the pass has fewer rows than the projection's input width
#: (``model.projection_fold_cut``), which every toy program here has; the
#: numbers are the earlier programs' bit for bit
#: (``test_llm_projection_layout.py``).  The expert configurations' eight
#: re-recorded when the expert layer began to return a third count, the
#: grouped product's tiles (``experts.EXPERT_COUNTS``): their programs gain
#: that count's slice of each layer's tile total, its sum over the layers and
#: one more int32 in the output, and nothing else changes (the primitives of
#: the two jaxprs differ by those alone)
PARENT_PROGRAMS = {
    "mistral.dense.decode": "9df418197930cc84",
    "mistral.dense.prefill": "6c9b6188b7425e10",
    "mistral.interpret.decode": "1604f4dd6ccdc82a",
    "mistral.interpret.prefill": "6c9b6188b7425e10",
    "olmo.dense.decode": "68a7768112821080",
    "olmo.dense.prefill": "1717a26bccdb00bf",
    "olmo.interpret.decode": "d449403bbce79a33",
    "olmo.interpret.prefill": "d83960202535cb63",
    "command-a-plus.dense.decode": "1482454ce00e996e",
    "command-a-plus.dense.prefill": "f8308bdb18ea92a5",
    "command-a-plus.interpret.decode": "0aa81f5513b4f4ef",
    "command-a-plus.interpret.prefill": "70e09111e39ee1fe",
    # the toy MiMo description above at bfloat16, recorded on the commit
    # before latent attention existed (263d8a0)
    "mimo.dense.decode": "7107c730da701f85",
    "mimo.dense.prefill": "5a5afe0acf8e8ad2",
    "mimo.interpret.decode": "d3f0f48c87668693",
    "mimo.interpret.prefill": "9b74b1b0e21359e4",
}


def _toy_configurations():
    return {
        "mistral": LlamaConfig.tiny(dtype=jnp.bfloat16),
        "olmo": LlamaConfig.tiny(
            dtype=jnp.bfloat16, num_kv_heads=8, norm_order="post",
            qk_norm=True,
            layer_types=("linear_attention",) * 3 + ("full_attention",),
            linear_num_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=32),
        "command-a-plus": LlamaConfig.tiny(
            dtype=jnp.bfloat16, d_model=64, num_heads=8, num_kv_heads=2,
            head_dim=16, d_ff=32, norm_order="parallel", norm="layer",
            rope_style="interleaved", rope_layers=("sliding_attention",),
            sliding_window=8,
            layer_types=("sliding_attention",) * 3 + ("full_attention",),
            tie_embeddings=True, ffn="experts", num_experts=16,
            num_experts_per_tok=4, num_shared_experts=2,
            expert_selection="sigmoid", norm_topk_prob=True, experts_first=4,
            experts_held=8),
        "mimo": dataclasses.replace(program_config(SMALL),
                                    dtype=jnp.bfloat16)}


def _program_digests(cfg, backend):
    import flax.linen as nn
    model = LlamaModel(cfg)
    variables = nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))))
    eng = SlotEngine(
        model, jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), variables),
        n_slots=4, max_len=64, attention_backend=backend, min_bucket=8,
        name="t-kinds-digest")
    n = eng.n_slots
    kw, _ = eng._decode_step_args(np.ones(n, bool), np.full(n, 5))
    sds = jax.ShapeDtypeStruct
    cache = jax.tree.map(lambda a: sds(a.shape, a.dtype), eng.cache)
    texts = {
        "decode": jax.make_jaxpr(functools.partial(
            S._decode_step_jit.__wrapped__, model, temperature=0.0, top_k=0,
            top_p=1.0, **kw))(
                variables, cache, sds((n,), jnp.int32), sds((n,), jnp.int32),
                sds((n,), jnp.bool_), jax.random.PRNGKey(0),
                prev_nxt=sds(eng._no_prev.shape, jnp.int32),
                feed_host=sds((n,), jnp.bool_)),
        "prefill": jax.make_jaxpr(functools.partial(
            S._prefill_slot_jit.__wrapped__, model,
            attention_backend=eng.attention_backend))(
                variables, cache, sds((16,), jnp.int32), sds((), jnp.int32),
                sds((), jnp.int32), sds((), jnp.int32))}
    out = {}
    for prog, jaxpr in texts.items():
        text = re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))
        text = re.sub(r"/[^\s:\"']+\.py(:\d+)?", "FILE", text)
        out[prog] = hashlib.sha256(text.encode()).hexdigest()[:16]
    return out


@pytest.mark.parametrize("backend", ["dense", "interpret"])
@pytest.mark.parametrize("name", ["mistral", "olmo", "command-a-plus",
                                  "mimo"])
def test_the_other_configurations_trace_to_the_parents_programs(name,
                                                                backend):
    got = _program_digests(_toy_configurations()[name], backend)
    for prog, digest in got.items():
        assert digest == PARENT_PROGRAMS[f"{name}.{backend}.{prog}"], \
            (name, backend, prog)


def test_a_description_with_its_defaults_spelled_out_is_the_same_program():
    """The new fields at their defaults change no program: ``ffn_types``
    that repeats ``ffn``, and the paged tile as one number."""
    cfg = _toy_configurations()["mistral"]
    spelled = dataclasses.replace(cfg, ffn_types=("dense",) * 4,
                                  expert_selection_bias=False)
    assert _program_digests(spelled, "dense") == \
        _program_digests(cfg, "dense")


# -- an unpacked window layer on a ring ---------------------------------------------

def test_a_plain_window_layer_rides_the_same_ring(ring8):
    """The ring is a rule on sizes, whatever the kind's widths: a window
    model without an ``attention_kinds`` entry (rows ``(slots, rows, heads,
    d_head)``) decodes over its ring to the logits of its full forward."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32, sliding_window=8, max_len=MAX_LEN,
                           layer_types=("sliding_attention",) * 3
                           + ("full_attention",))
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 4), jnp.int32))
    ids = np.random.default_rng(7).integers(1, cfg.vocab_size, 60) \
        .astype(np.int32)
    want = np.asarray(model.apply(variables, jnp.asarray(ids)[None]))[0]
    for backend in ("dense", "interpret"):
        eng = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                         attention_backend=backend, min_bucket=8,
                         name=f"t-kinds-plain-{backend}")
        assert eng.ring and eng.cache[0]["k"].shape == (2, 16, 4, 16)
        res = eng.admit(ids[:21], 30)
        np.testing.assert_allclose(res.logits, want[20], atol=1e-4)
        toks = [res.token] + [ev.token for _ in range(29)
                              for ev in eng.step()]
        full = np.concatenate([ids[:21], toks[:-1]]).astype(np.int32)
        lg = np.asarray(model.apply(variables, jnp.asarray(full)[None]))[0]
        got = lg[20:, :]
        gaps = got.max(-1) - got[np.arange(len(toks)), np.asarray(toks)]
        assert gaps.max() < 1e-4, backend
