"""Qwen3-Next (``qwen3_next``): gated DeltaNet with fewer key heads than
value heads, gated softmax attention with q/k norms by head and rotary
embeddings on a quarter of a head, zero-centred norms, and small routed
experts beside a shared expert behind a sigmoid gate.

Pinned here, at small sizes on the CPU (hidden 64; three linear layers of 2
key heads for 4 value heads of 16, then one full layer of 4 query heads of 32
over 2 K/V heads, rotary on 8 of 32 dims; this share holding experts 4-11 of
16 with 4 a token; a shared expert of width 48 beside experts of 32):

- the model (full forward, prefill then decode through the cache, then
  ``SlotEngine``) against the plain float32 reference the benchmark keeps
  (``benchmark/references/qwen3-next-80b-a3b-l4-e256.py``, which shares no
  code with the program and computes in the checkpoint's fused layout), by
  LOGITS, on the dense path and with the kernels in interpret mode;
- each mechanism taken out reads past the tolerance: the output gate, the
  zero-centred norm, the shared expert's gate, the partial rotary embedding,
  and key heads tiled where they should repeat;
- the grouped gated-delta kernels against ``gated_delta_scan`` with value
  head ``j`` on key head ``j // r``;
- two shares of an expert layer, the gated shared expert counted once, add
  up to the uncut layer;
- ``from_hf`` on the catalog's row, the widths by hand, and what it refuses;
- the spans and counters: ``expert_tiles_active``, ``llm_expert_rows_total``
  and ``state_bytes`` by value and key heads.

Tolerances.  Program and reference both compute in float32 from the same
bfloat16-rounded weights and differ in summation order over four layers:
some 9e-7 on logits of spread 0.17.  ``LOGIT_TOL`` = 5e-5 leaves fifty times
that and lies two orders under every fault read below (the reference's three
controls 0.12-0.69, each mechanism taken out of the program 1e-2 or more).
The kernels against the scan: 2e-5 on outputs of order one (float32 in a
different order of the same sums).
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
from synapseml_tpu.models.llm import (LlamaConfig, LlamaModel,  # noqa: E402
                                      SlotEngine, init_cache)
from synapseml_tpu.models.llm import experts as X  # noqa: E402
from synapseml_tpu.models.llm import model as M  # noqa: E402
from synapseml_tpu.models.llm import pallas_attn as P  # noqa: E402
from synapseml_tpu.models.llm import pallas_gdn as G  # noqa: E402
from synapseml_tpu.telemetry import get_registry  # noqa: E402

LOGIT_TOL = 5e-5
KERNEL_TOL = 2e-5
SEED = 43
MAX_LEN = 128
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b-l4-e256.json")
TINY_FILE = os.path.join(ROOT, "tests", "benchmark_harness", "tiny_qwen3_next",
                         "configs", "tiny-qwen3-next.json")
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
    the reference's three (``from_hf`` reads ``num_experts`` as the router's
    width, which is what it is in a ``config.json``)."""
    hf = dict(hc, num_experts=hc["router_experts"])
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("max_len", MAX_LEN)
    return LlamaConfig.from_hf(hf, experts_first=hc["experts_first"],
                               experts_held=hc["num_experts"], **kw)


@pytest.fixture(scope="module")
def small(ref, small_keys):
    """(cfg, model, variables): float32, the reference's seeded weights laid
    into the program's parameter tree by the configuration file's own map."""
    from benchmark.runners import llm_serve
    cfg = program_config(small_keys)
    variables = llm_serve.build_variables(small_keys, ref, SEED)
    return cfg, LlamaModel(cfg), jax.tree.map(
        lambda a: jnp.asarray(a, jnp.float32), variables)


def _prompt(length, seed):
    return np.random.default_rng(seed).integers(1, 256, length).astype(np.int32)


def _ref_logits(ref, hc, ids, positions, quant=None):
    return ref.forward(hc, SEED, [np.asarray(ids, np.int32)],
                       [np.asarray(positions)], MAX_LEN, quant)[0]


_apply = jax.jit(lambda model, v, *a, **k: model.apply(v, *a, **k),
                 static_argnums=0, static_argnames=("attention_backend",))


def _prefill(model, v, cache, ids, start, bucket, backend):
    padded = np.zeros(bucket, np.int32)
    padded[:len(ids)] = ids
    return _apply(model, v, jnp.asarray(padded)[None],
                  positions=(start + jnp.arange(bucket))[None], cache=cache,
                  cache_index=jnp.int32(start), valid_len=len(ids),
                  attention_backend=backend)


# -- the description ---------------------------------------------------------------

def test_from_hf_reads_the_family_and_refuses_what_it_cannot_honour(
        benchmark_config):
    c = benchmark_config
    cfg = program_config(c, dtype=jnp.bfloat16,
                         max_len=c["engine"]["max_len"])
    assert cfg.layer_kinds == ("linear_attention",) * 3 + ("full_attention",)
    assert cfg.ffn_kinds == ("experts",) * 4
    a = cfg.attention("full_attention")
    assert (a.num_kv_heads, a.head_dim, a.v_head_dim, a.rotary_dim,
            a.rope_theta) == (2, 256, 256, 64, 1e7)
    assert cfg.num_heads == 16 and cfg.rope_style == "half"
    # packed K/V rows, by the shape rule and not by an entry of the family
    assert cfg.packed("full_attention") and cfg.attention_kinds is None
    assert (cfg.norm, cfg.qk_head_norm, cfg.attn_output_gate, cfg.qk_norm,
            cfg.norm_order) == ("zero_centred", True, True, False, "pre")
    assert (cfg.linear_num_heads, cfg.linear_key_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim, cfg.linear_allow_neg_eigval) == \
        (32, 16, 128, 128, 4, False)
    assert (cfg.num_experts, cfg.experts_first, cfg.experts_held_count,
            cfg.num_experts_per_tok, cfg.expert_d_ff) == (512, 0, 256, 10, 512)
    assert cfg.expert_selection == "softmax" and cfg.norm_topk_prob
    assert (cfg.num_shared_experts, cfg.shared_expert_d_ff,
            cfg.shared_expert_gate) == (1, 512, True)
    assert cfg.rms_norm_eps == 1e-6 and not cfg.tie_embeddings
    # the runner builds the same description from the file's own map
    from benchmark.runners import llm_serve
    assert llm_serve.build_model(c).cfg == cfg
    # decoder_sparse_step and mlp_only_layers: layer i has experts iff
    # (i + 1) % step == 0 and i is not listed
    hf = dict(c, num_experts=512)
    assert LlamaConfig.from_hf(dict(hf, decoder_sparse_step=2)).ffn_kinds == \
        ("dense", "experts") * 2
    assert LlamaConfig.from_hf(dict(hf, mlp_only_layers=[3])).ffn_kinds == \
        ("experts",) * 3 + ("dense",)
    # refused: a window on the full layers, value heads that are not a
    # multiple of the key heads
    with pytest.raises(ValueError, match="use_sliding_window"):
        LlamaConfig.from_hf(dict(hf, use_sliding_window=True))
    with pytest.raises(ValueError, match="not a multiple"):
        LlamaConfig.from_hf(dict(hf, linear_num_key_heads=12))
    with pytest.raises(ValueError, match="do not divide"):
        LlamaConfig.tiny(layer_types=("linear_attention",) * 4,
                         linear_num_heads=6, linear_num_key_heads=4,
                         linear_key_head_dim=16, linear_value_head_dim=16)
    with pytest.raises(ValueError, match="rope_scaling"):
        LlamaConfig.from_hf(dict(hf, rope_scaling={"rope_type": "linear",
                                                   "factor": 2.0}))
    # partial_rotary_factor is read for every family: a plain decoder that
    # carries it turns a quarter of each head, not all of it
    plain = {"vocab_size": 64, "hidden_size": 64, "num_hidden_layers": 2,
             "num_attention_heads": 4, "intermediate_size": 64}
    assert LlamaConfig.from_hf(plain).attention(
        "full_attention").rotary_dim == 16
    assert LlamaConfig.from_hf(dict(plain, partial_rotary_factor=0.25)
                               ).attention("full_attention").rotary_dim == 4
    # equal key and value heads keep the parent's description
    same = LlamaConfig.from_hf(dict(hf, linear_num_key_heads=32))
    assert same.linear_num_key_heads is None and same.linear_key_heads == 32


def test_the_published_widths_the_cut_and_the_cache_by_hand(benchmark_config):
    c = benchmark_config
    from benchmark.runners import llm_serve
    cut = llm_serve.build_model(c).cfg
    shapes = jax.eval_shape(lambda: LlamaModel(cut).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])
    n = {k: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(v))
         for k, v in shapes.items()}
    # a linear layer: qkvz 2,048 x 16 x (2 x 128 + 2 x 2 x 128), ba 2,048 x
    # 64, the convolution over 8,192 channels, out 4,096 x 2,048
    qkvz, ba, conv, out = 2048 * 16 * 768, 2048 * 64, 4 * 8192, 4096 * 2048
    assert (qkvz, conv) == (25_165_824, 32_768)
    linear = qkvz + ba + conv + out + 32 + 32 + 128
    assert 33.71e6 < linear < 33.72e6
    # the full layer: q with its gate 2,048 x 16 x 512, k and v 2 x 256
    full = 2048 * 16 * 512 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    assert 27.26e6 < full < 27.27e6
    moe = 2048 * 512 + 256 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048 + 2 * 2048
    assert 3 * 2048 * 512 == 3_145_728 and 256 * 3_145_728 == 805_306_368
    assert n["layer_0"] == n["layer_1"] == n["layer_2"] == linear + moe
    assert n["layer_3"] == full + moe
    assert 843.2e6 < n["layer_0"] < 843.3e6 and 836.7e6 < n["layer_3"] < 836.9e6
    assert n["tok_embed"] == n["lm_head"] == 151936 * 2048      # not sliced
    total = sum(n.values())
    assert 3.9887e9 < total < 3.9889e9                 # 7.98 GB at 2 bytes
    g = shapes["layer_0"]["gdn"]
    assert g["q_proj"]["kernel"].value.shape == (2048, 16 * 128)
    assert g["v_proj"]["kernel"].value.shape == (2048, 32 * 128)
    assert g["a_proj"]["kernel"].value.shape == (2048, 32)
    assert shapes["layer_3"]["attn"]["q_proj"]["kernel"].value.shape == \
        (2048, 16 * 512)
    assert shapes["layer_3"]["attn"]["q_norm"]["scale"].value.shape == (256,)
    assert shapes["layer_0"]["moe"]["shared_expert_gate"].value.shape == \
        (2048, 1)
    # the cache at 64 x 10,240: K/V of the one full layer in packed rows (a
    # position's 2 heads of 256 one after the other), 2,048 B a position; a
    # linear layer's state by value heads and window of 8,192
    assert cut.packed("full_attention")
    cache = jax.eval_shape(lambda: init_cache(cut, 64, 10240))
    assert cache[3]["k"].shape == cache[3]["v"].shape == (64, 10240 * 2, 256)
    assert cache[0]["state"].shape == (64, 32, 128, 128)
    assert cache[0]["conv"].shape == (64, 3, 8192)
    kv = 2 * 64 * 10240 * 2 * 256 * 2
    state = 3 * 64 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert (kv, state) == (1_342_177_280, 412_090_368)
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree.leaves(cache))
    assert nbytes == kv + state
    assert c["reduced"] == ["num_hidden_layers", "num_experts"]


def test_the_row_tile_at_the_cells_load():
    """64 slots, 10 experts a token over 512, 256 held: some 320 pairs over
    256 experts a layer, 1.25 an expert.  The tile is the smallest the MXU's
    bfloat16 operand takes, 16 rows; a doubled tile would double the rows
    computed and read nothing less."""
    assert X._row_tile(320, 256) == 16
    cfg = LlamaConfig.tiny(ffn="experts", num_experts=512,
                           num_experts_per_tok=10, experts_held=256)
    assert X.expert_row_tile(cfg, 64) == 16
    # a prefill of 2,048 tokens goes through in chunks of 1,024: 10,240
    # pairs of which some 5,120 held, tiles of 64
    assert X.expert_row_tile(cfg, 2048) == X._row_tile(10240, 256) == 64


@pytest.mark.parametrize("heads,width,relaid", [
    (2, 256, True), (4, 256, True), (4, 384, True),       # the rule's cases
    (1, 256, False), (8, 256, False), (16, 256, False),   # a head, whole tiles
    (2, 128, False), (8, 128, False), (32, 128, False),   # one lane tile
    (2, 64, False), (4, 192, False)])                     # left as they were
def test_packed_rows_by_shape(heads, width, relaid):
    """Whether a kind keeps packed rows is decided by the shape of an
    unpacked row, for any description: the Mistral, Command A+ and Olmo
    geometries (8 and 32 heads of 128) keep theirs, 2 heads of 256 pack
    without an ``attention_kinds`` entry."""
    assert P.row_relaid(heads, width) is relaid
    cfg = LlamaConfig(vocab_size=64, d_model=64, num_layers=1, num_heads=32,
                      num_kv_heads=heads, head_dim=width, d_ff=64)
    assert cfg.attention_kinds is None
    assert cfg.packed("full_attention") is relaid
    cache = jax.eval_shape(lambda: init_cache(cfg, 2, 8))
    assert cache[0]["k"].shape == ((2, 8 * heads, width) if relaid
                                   else (2, 8, cfg.kv_cache_heads, width))


# -- the model against the reference ---------------------------------------------

def test_full_forward_matches_the_reference(small, ref, small_keys):
    cfg, model, variables = small
    ids = _prompt(64, 1)
    tokens = jnp.asarray(ids)[None]
    want = _ref_logits(ref, small_keys, ids, np.arange(64))
    assert want.std() > 0.1
    np.testing.assert_allclose(np.asarray(_apply(model, variables, tokens))[0],
                               want, atol=LOGIT_TOL)
    # the reference's controls all read far past the tolerance, and
    # key_heads_tiled is the program's grouping done wrong
    for control in ref.CONTROLS:
        low = _ref_logits(ref, small_keys, ids, np.arange(64), control)
        assert np.abs(low - want).max() > 1000 * LOGIT_TOL, control

    def without(variables=variables, **change):
        m = LlamaModel(dataclasses.replace(cfg, **change))
        return np.asarray(_apply(m, variables, tokens))[0]
    # each mechanism taken out of the program
    for change in ({"norm": "rms"}, {"shared_expert_gate": False},
                  {"partial_rotary_factor": 1.0}, {"dtype": jnp.bfloat16}):
        got = without(**change)
        assert np.abs(got - want).max() > 100 * LOGIT_TOL, change
    # the output gate taken out: q_proj's query columns alone, and the
    # program then reads what the reference reads ungated
    p = variables["params"]["layer_3"]["attn"]["q_proj"]["kernel"]
    q_only = p.reshape(64, 4, 2, 32)[:, :, 0].reshape(64, 4 * 32)
    v = jax.tree.map(lambda a: a, variables)
    v["params"]["layer_3"]["attn"]["q_proj"]["kernel"] = q_only
    got = without(v, attn_output_gate=False)
    assert np.abs(got - want).max() > 100 * LOGIT_TOL
    np.testing.assert_allclose(got, _ref_logits(
        ref, small_keys, ids, np.arange(64), "no_output_gate"), atol=LOGIT_TOL)


@pytest.mark.parametrize("backend", BACKENDS)
def test_prefill_then_decode_through_the_cache(small, ref, small_keys,
                                               backend):
    cfg, model, variables = small
    ids = _prompt(60, 2)
    want = _ref_logits(ref, small_keys, ids, np.arange(60))
    cache = init_cache(cfg, 2, MAX_LEN)
    # the window: q and k by 2 key heads of 16, v by 4 value heads of 16
    assert cache[0]["conv"].shape == (2, 3, 2 * 2 * 16 + 4 * 16)
    row = jax.tree.map(lambda a: a[:1], cache)
    lg, row = _prefill(model, variables, row, ids[:20], 0, 32, backend)
    np.testing.assert_allclose(np.asarray(lg)[0, :20], want[:20],
                               atol=LOGIT_TOL)
    cache = jax.tree.map(lambda a, r: a.at[:1].set(r), cache, row)
    active = jnp.asarray([True, False])
    for t in range(20, 60):
        lengths = jnp.asarray([t + 1, 1], jnp.int32)
        lg, cache = _apply(model, variables,
                           jnp.asarray([ids[t], 0], jnp.int32)[:, None],
                           positions=(lengths - 1)[:, None], cache=cache,
                           cache_index=lengths - 1, slot_mask=active,
                           attention_backend=backend)
        np.testing.assert_allclose(np.asarray(lg)[0, 0], want[t],
                                   atol=LOGIT_TOL, err_msg=str(t))
    for layer in cache:                     # the idle slot wrote nothing
        assert not any(np.asarray(a[1]).any() for a in layer.values())


class Drive:
    """Admissions and steps by request name."""

    def __init__(self, eng):
        self.eng, self.tokens, self.logits, self.by_slot = eng, {}, {}, {}

    def admit(self, name, prompt, n):
        r = self.eng.admit(prompt, n)
        self.tokens[name], self.logits[name] = [r.token], r.logits
        if not r.finished:
            self.by_slot[r.slot] = name
        return r

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
    name = f"t-qwen3-next-{request.node.callspec.id}"
    eng = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                     attention_backend=backend, min_bucket=8, name=name)
    assert eng.recurrent and eng.experts
    d = Drive(eng)
    pre = _prompt(40, 50)
    p = {"a": np.concatenate([pre, _prompt(6, 51)]),
         "b": np.concatenate([pre, _prompt(30, 52)]),
         "c": _prompt(17, 53)}
    d.admit("a", p["a"], 24)
    # a's prefix is there and the recurrent state cannot be sliced: cold
    assert d.admit("b", p["b"], 6).path == "cold_recurrent"
    d.run()
    d.admit("c", p["c"], 10)
    d.run()
    for k in "abc":
        ids = list(p[k]) + d.tokens[k][:-1]
        lg = _ref_logits(ref, small_keys, ids,
                         np.arange(len(p[k]) - 1, len(ids)))
        tok = np.asarray(d.tokens[k])
        gap = float((lg.max(-1) - lg[np.arange(len(tok)), tok]).max())
        assert gap < LOGIT_TOL, (k, gap)
        np.testing.assert_allclose(d.logits[k], lg[0], atol=LOGIT_TOL,
                                   err_msg=k)


def test_the_spans_counters_and_state_bytes(small, tmp_path):
    from synapseml_tpu.telemetry import get_tracer
    cfg, model, variables = small
    name = "t-qwen3-next-count"
    eng = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                     attention_backend="interpret", min_bucket=8, name=name)
    # a slot's state by 4 value heads, its window's q and k by 2 key heads
    per_layer = 4 * 16 * 16 * 4 + 3 * (2 * 2 * 16 + 4 * 16) * 4
    assert eng.slot_state_bytes == 3 * per_layer
    rows = get_registry().counter("llm_expert_rows_total", "",
                                  ("engine", "rows"))
    pairs = get_registry().counter("llm_expert_pairs_total", "", ("engine",))
    jax.profiler.start_trace(str(tmp_path))         # step spans are live
    try:
        eng.admit(_prompt(20, 60), 4)
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    step = [s for s in get_tracer().spans("engine.step")
            if s.attrs.get("slots") == 1 and "expert_tiles_active" in s.attrs
            and s.attrs.get("state_bytes") == 2 * 3 * per_layer][-1]
    a = step.attrs
    # one token, 4 experts of 16 chosen, 8 held: at most 4 pairs a layer in
    # tiles of 16 rows (16 x 8 held covers 4 pairs), one tile an expert
    assert X.expert_row_tile(cfg, 2) == 16
    assert a["expert_tiles_active"] == a["experts_touched"] > 0
    assert a["expert_tile_rows"] == 16 * a["expert_tiles_active"]
    assert a["expert_pairs_held"] <= a["expert_tile_rows"]
    admit = [s for s in get_tracer().spans("engine.admit")
             if "expert_tiles_active" in s.attrs][-1].attrs
    assert admit["expert_tile_rows"] >= admit["expert_pairs_held"] > 0
    # the counter: every pass's tile rows, pairs and padding
    got = rows.value(engine=name, rows="pairs")
    assert got == pairs.value(engine=name) > 0
    assert rows.value(engine=name, rows="padding") > 0


# -- the kernels at grouped heads ------------------------------------------------

def _gdn_inputs(T, Hk, H, dk, dv, key=11):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    q = jax.random.normal(ks[0], (T, Hk, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = jax.random.normal(ks[1], (T, Hk, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (T, H, dv))
    alpha = jax.random.uniform(ks[3], (T, H), minval=0.5, maxval=1.0)
    beta = jax.random.uniform(ks[4], (T, H))
    return q, k, v, alpha, beta


def _scan(q, k, v, alpha, beta, state, r, tiled=False):
    """The plain recurrence with value head j on key head j // r (or, tiled,
    on j mod Hk): one slot, T tokens."""
    grow = (lambda x: jnp.tile(x, (1, r, 1))) if tiled else \
        (lambda x: jnp.repeat(x, r, axis=1))
    o, st = G.gated_delta_scan(grow(q)[None], grow(k)[None], v[None],
                               alpha[None], beta[None], state[None])
    return o[0], st[0]


@pytest.mark.pallas
@pytest.mark.parametrize("Hk,H,dv", [(2, 4, 128), (2, 4, 64)])
def test_the_grouped_gdn_kernels_match_the_scan(Hk, H, dv):
    """Decode (one token for 3 slots, one inactive) and prefill (a bucket of
    32 with 21 real tokens) against the scan, q and k by key head; at
    ``dv`` 64 two value heads share a lane tile (pack 2)."""
    dk, r = 16, H // Hk
    pack = G.gdn_pack(H, dv)
    assert pack == (1 if dv == 128 else 2)
    q, k, v, alpha, beta = _gdn_inputs(32, Hk, H, dk, dv)
    s0 = jax.random.normal(jax.random.PRNGKey(3), (H, dk, dv))
    want_o, want_s = _scan(q[:21], k[:21], v[:21], alpha[:21], beta[:21],
                           s0, r)
    st, o = G.gated_delta_prefill(G.pack_state(s0, pack), q, k, v, alpha,
                                  beta, jnp.int32(21), pack=pack,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(o[:21]), np.asarray(want_o),
                               atol=KERNEL_TOL)
    np.testing.assert_allclose(np.asarray(G.unpack_state(st, pack)),
                               np.asarray(want_s), atol=KERNEL_TOL)
    assert not np.asarray(o[21:]).any()
    # key heads tiled in place of repeated: the kernel is caught
    tiled_o, _ = _scan(q[:21], k[:21], v[:21], alpha[:21], beta[:21], s0, r,
                       tiled=True)
    assert np.abs(np.asarray(tiled_o) - np.asarray(o[:21])).max() > 0.1
    # decode: three slots, the middle one inactive
    states = jnp.stack([s0, 2 * s0, -s0])
    new, o = G.gated_delta_decode(
        G.pack_state(states, pack), q[:3], k[:3], v[:3], alpha[:3], beta[:3],
        jnp.asarray([True, False, True]), pack=pack, interpret=True)
    new = np.asarray(G.unpack_state(new, pack))
    for n in (0, 2):
        wo, ws = _scan(q[n:n + 1], k[n:n + 1], v[n:n + 1], alpha[n:n + 1],
                       beta[n:n + 1], states[n], r)
        np.testing.assert_allclose(np.asarray(o[n]), np.asarray(wo[0]),
                                   atol=KERNEL_TOL)
        np.testing.assert_allclose(new[n], np.asarray(ws), atol=KERNEL_TOL)
    np.testing.assert_array_equal(new[1], np.asarray(states[1]))
    assert not np.asarray(o[1]).any()


# -- the expert layer's shares ----------------------------------------------------

def test_two_shares_and_the_gated_shared_expert_once_add_up_to_the_layer():
    """The expert layer at 16 experts (4 a token, softmax, normalised) over
    hidden 32, experts of 16 and a shared expert of 24 behind its gate: the
    shares 0-7 and 8-15 less the shared term, plus the shared term once, are
    the uncut layer."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32, d_model=32, d_ff=16,
                           ffn="experts", num_experts=16, num_experts_per_tok=4,
                           num_shared_experts=1, shared_expert_d_ff=24,
                           shared_expert_gate=True, norm_topk_prob=True)
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 32, 32))
    valid = jnp.ones((1, 32), bool)
    import flax.linen as nn
    uncut = X.ExpertFFN(cfg)
    params = dict(nn.meta.unbox(uncut.init(jax.random.PRNGKey(6), h,
                                           valid)["params"]))
    # the scores spread, and the terms of order one
    params = jax.tree.map(lambda a: 20 * a, params)
    assert params["shared_gate"]["kernel"].shape == (32, 24)
    assert params["shared_expert_gate"].shape == (32, 1)
    whole = uncut.apply({"params": params}, h, valid)
    shares = []
    for first in (0, 8):
        part = X.ExpertFFN(dataclasses.replace(cfg, experts_first=first,
                                               experts_held=8))
        p = dict(params, **{k: params[k][first:first + 8] for k in
                            ("experts_gate", "experts_up", "experts_down")})
        shares.append(part.apply({"params": p}, h, valid))
    w = {k: params["shared_" + k]["kernel"] for k in ("gate", "up", "down")}
    shared = (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"] \
        * jax.nn.sigmoid(h @ params["shared_expert_gate"])
    np.testing.assert_allclose(sum(s - shared for s in shares) + shared,
                               whole, atol=1e-4)
    assert np.abs(np.asarray(whole - shared)).max() > 1
    assert np.abs(np.asarray(shared)).max() > 1
    # the gate is a token's own: it spreads, and without it the layer differs
    g = np.asarray(jax.nn.sigmoid(h @ params["shared_expert_gate"]))
    assert g.min() < 0.2 and g.max() > 0.8
    ungated = X.ExpertFFN(dataclasses.replace(cfg, shared_expert_gate=False))
    assert np.abs(np.asarray(ungated.apply({"params": params}, h, valid)
                             - whole)).max() > 1


# -- the v5e compiler at the published widths ---------------------------------------

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


def test_the_kernels_compile_for_the_v5e_at_the_published_widths(one_chip):
    """The grouped gated-delta kernels (32 value heads of 128 on 16 key
    heads), the paged decode kernel at head width 256 over 2 K/V heads, and
    the grouped expert product over 256 held experts of 512 at the decode
    step's tile.  (Such a compile is written to the persistent cache and
    cannot be read back without the chip: a later run compiles again.)"""
    bf, f32 = jnp.bfloat16, jnp.float32

    def sds(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    assert G.gdn_geometry(32, 128, 128) == 1
    jax.jit(lambda s, q, k, v, a, b, act: G.gated_delta_decode(
        s, q, k, v, a, b, act, pack=1)).lower(
            sds((64, 32, 128, 128)), sds((64, 16, 128)), sds((64, 16, 128)),
            sds((64, 32, 128)), sds((64, 32)), sds((64, 32)),
            sds((64,), jnp.bool_)).compile()
    jax.jit(lambda s, q, k, v, a, b: G.gated_delta_prefill(
        s, q, k, v, a, b, jnp.int32(700), pack=1)).lower(
            sds((32, 128, 128)), sds((1024, 16, 128)), sds((1024, 16, 128)),
            sds((1024, 32, 128)), sds((1024, 32)), sds((1024, 32))).compile()
    geo = P.paged_geometry(10240, 16, 2, 256, bf, pack=1, d_value=256)
    assert geo.tile == 256
    jax.jit(lambda q, k, v, s: P.paged_decode_attention(
        q, k, v, s, tile=geo.tile, kv_heads=2, pack=1)).lower(
            sds((64, 1, 16, 256), bf), sds((64, 10240 * 2, 256), bf),
            sds((64, 10240 * 2, 256), bf), sds((64,), jnp.int32)).compile()
    # the decode step of a description of 2 K/V heads of 256 with no
    # attention_kinds entry: packed rows by shape, no relay of the cache
    # (unpacked, the temporaries are the cache's size)
    from synapseml_tpu.models.llm import slots as S
    cfg = LlamaConfig(vocab_size=256, d_model=512, num_layers=1,
                      num_heads=16, num_kv_heads=2, head_dim=256, d_ff=256,
                      max_len=2048, dtype=bf)
    model = LlamaModel(cfg)
    var = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))))
    cache = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                         jax.eval_shape(lambda: init_cache(cfg, 16, 2048)))
    tile = P.paged_geometry(2048, 16, 2, 256, bf, pack=1, d_value=256).tile
    i32, on = sds((16,), jnp.int32), sds((16,), jnp.bool_)
    step = S._decode_step_jit.lower(
        model, var, cache, i32, i32, on, sds((2,), jnp.uint32),
        temperature=0.0, top_k=0, top_p=1.0, attention_backend="paged",
        paged_tile=tile, prev_nxt=i32, feed_host=on).compile()
    kv_bytes = 2 * 16 * 2048 * 2 * 256 * 2
    assert step.memory_analysis().temp_size_in_bytes < kv_bytes / 10
    tiles = -(-640 // 16) + 256
    jax.jit(lambda x, te, na, wg, wu: X.expert_ffn(
        x, te, na, wg, wu, tm=16)).lower(
            sds((tiles * 16, 2048), bf), sds((tiles,), jnp.int32),
            sds((1,), jnp.int32), sds((256, 2048, 512), bf),
            sds((256, 2048, 512), bf)).compile()
