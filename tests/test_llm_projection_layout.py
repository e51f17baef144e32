"""An attention projection's product kept apart from its split into heads
(``model.projection_fold_cut``, ``_fold_barrier``).

Folded, XLA on the TPU reads a projection's weight in the heads' order and
relays all of it there on every layer and step (PERF.md sections 6, 7);
behind ``lax.optimization_barrier`` the product reads the weight as it is
stored.  Pinned here on the CPU at toy sizes:

- the rule: cut where the pass has fewer rows than the projection's input
  width (every decode step, short prefills), kept where it has as many or
  more;
- an identity on numerics: greedy decode through ``SlotEngine``, for the three
  toy configurations of ``test_llm_mixed_kinds.py`` and MiMo's kinds, serves
  the same tokens and logits bit for bit with the fold cut and with it kept
  (the program without a barrier), and so does the full forward;
- the cache-less pass (training) differentiates through a cut;
- ``llm_projection_layout_total{proj, choice}`` counts each site once a
  trace, and every ``llm.warmup.program`` span says how many sites of its
  program were cut and kept.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from synapseml_tpu.models.llm import LlamaConfig, LlamaModel, SlotEngine  # noqa: E402
from synapseml_tpu.models.llm import model as M  # noqa: E402
from synapseml_tpu.models.llm import slots as S  # noqa: E402
from synapseml_tpu.telemetry import get_registry, get_tracer  # noqa: E402
from test_llm_mixed_kinds import (SMALL, _toy_configurations,  # noqa: E402
                                  program_config)


def _configurations():
    out = dict(_toy_configurations())
    out["mimo"] = program_config(SMALL)
    return out


@pytest.fixture
def fold(monkeypatch):
    """``fold(choice)``: every site cut, every site kept, or the rule
    (None); the jit caches are emptied so that no program traced under
    another choice is reused."""
    rule = M.projection_fold_cut

    def set_choice(choice):
        monkeypatch.setattr(
            M, "projection_fold_cut", rule if choice is None
            else (lambda rows, width: choice == "cut"))
        jax.clear_caches()
    yield set_choice
    jax.clear_caches()


def _counts():
    c = get_registry().counter("llm_projection_layout_total", "",
                               ("proj", "choice"))
    return {(p, ch): c.value(proj=p, choice=ch)
            for p in "qkvo" for ch in ("cut", "kept")}


# -- the rule --------------------------------------------------------------------

@pytest.mark.parametrize("rows,width,cut", [
    (24, 4096, True),         # a decode step of 24 slots
    (32, 4096, True),
    (1024, 4096, True),       # a prefill tail
    (2048, 4096, True),       # the document cell's bucket
    (4096, 4096, False),      # as many rows as the width: kept
    (8192, 4096, False),      # MiMo's long buckets: the weight is smaller
    (16384, 4096, False),
    (5632, 16384, True),      # Command A+'s o_proj: 128 heads of 128
])
def test_the_rule_cuts_where_the_activation_is_the_smaller(rows, width, cut):
    assert M.projection_fold_cut(rows, width) is cut


@pytest.mark.parametrize("name", ["mistral", "olmo", "command-a-plus", "mimo"])
def test_the_sites_a_pass_decides_at(name):
    cfg = _configurations()[name]
    attn = cfg.num_attention_layers
    sites = 4 * attn
    assert M.projection_layout(cfg, 4) == {"cut": sites, "kept": 0}
    assert M.projection_layout(cfg, 1 << 20) == {"cut": 0, "kept": sites}
    # a linear-attention layer's projections are never relayed: no site
    assert M.MIXERS["linear_attention"].projections(cfg) == ()
    # o reads the heads' values: its width is heads x the value's width
    kind = cfg.attention_layer_kinds[0]
    assert dict(M.MIXERS[kind].projections(cfg))["o"] == \
        cfg.num_heads * cfg.attention(kind).v_head_dim


# -- an identity on numerics -----------------------------------------------------

def _serve(model, variables, prompts, backend):
    eng = SlotEngine(model, variables, n_slots=3, max_len=64,
                     attention_backend=backend, min_bucket=8,
                     name=f"t-fold-{backend}")
    by_slot, tokens, logits = {}, {}, {}
    for i, p in enumerate(prompts):
        res = eng.admit(p, 6 + 2 * i)
        tokens[i], logits[i] = [res.token], [np.asarray(res.logits)]
        by_slot[res.slot] = i
    while eng.active.any():
        for ev in eng.step():
            tokens[by_slot[ev.slot]].append(ev.token)
    return tokens, logits


@pytest.mark.parametrize("name,backend", [
    ("mistral", "dense"), ("olmo", "dense"), ("command-a-plus", "dense"),
    ("mimo", "dense"),
    # the paged kernel's q: plain rows, and two heads a packed row
    pytest.param("mistral", "interpret", marks=pytest.mark.pallas),
    pytest.param("mimo", "interpret", marks=pytest.mark.pallas)])
def test_served_tokens_and_logits_are_the_same_bit_for_bit(name, backend,
                                                           fold):
    cfg = _configurations()[name]
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(7), jnp.zeros((1, 4), jnp.int32))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 13, 9)]
    ids = jnp.asarray(prompts[1])[None]
    served, forward = {}, {}
    for choice in ("kept", "cut"):
        fold(choice)
        before = _counts()
        served[choice] = _serve(model, variables, prompts, backend)
        forward[choice] = np.asarray(model.apply(variables, ids))
        traced = {k: v - before[k] for k, v in _counts().items()}
        # every site of every program took the forced choice
        assert sum(v for (_, ch), v in traced.items() if ch == choice) > 0
        assert sum(v for (_, ch), v in traced.items() if ch != choice) == 0
    (tok_k, lg_k), (tok_c, lg_c) = served["kept"], served["cut"]
    assert tok_k == tok_c
    for i in lg_k:
        np.testing.assert_array_equal(lg_c[i][0], lg_k[i][0])
    np.testing.assert_array_equal(forward["cut"], forward["kept"])


# -- training ----------------------------------------------------------------------

@pytest.mark.parametrize("kind,extra", [
    ("full_attention", {}),
    ("sliding_attention", {"sliding_window": 4}),
    ("full_attention", {"attention_kinds": {"full_attention": {
        "num_kv_heads": 2, "head_dim": 24, "v_head_dim": 16,
        "rotary_dim": 8, "sink": True, "value_scale": 0.707}}}),
], ids=["full", "window", "packed-wide-keys"])
def test_the_cache_less_pass_differentiates_through_a_cut(kind, extra, fold):
    cfg = LlamaConfig.tiny(dtype=jnp.float32, num_layers=1, layer_types=(kind,),
                           **extra)
    model = LlamaModel(cfg)
    ids = jnp.asarray(np.random.default_rng(3).integers(
        1, cfg.vocab_size, (2, 8)), jnp.int32)
    params = model.init(jax.random.PRNGKey(5), ids)

    def loss(p):
        return jnp.mean(jnp.square(model.apply(p, ids)))
    grads = {}
    for choice in ("kept", "cut"):
        fold(choice)
        before = _counts()
        grads[choice] = jax.grad(loss)(params)
        assert _counts()[("q", choice)] > before[("q", choice)]
    flat_c = jax.tree.leaves(grads["cut"])
    flat_k = jax.tree.leaves(grads["kept"])
    assert all(np.isfinite(np.asarray(g)).all() for g in flat_c)
    assert any(np.abs(np.asarray(g)).max() > 0 for g in flat_c)
    for gc, gk in zip(flat_c, flat_k):
        np.testing.assert_allclose(np.asarray(gc), np.asarray(gk),
                                   rtol=1e-6, atol=1e-9)


# -- the counter and the warm-up spans ---------------------------------------------

def test_a_decode_and_a_prefill_trace_count_each_site_once(fold):
    fold(None)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, max_len=256)      # width 128
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    eng = SlotEngine(model, variables, n_slots=4, max_len=256,
                     attention_backend="dense", min_bucket=8,
                     name="t-fold-count")
    sds = jax.ShapeDtypeStruct
    cache = jax.tree.map(lambda a: sds(a.shape, a.dtype), eng.cache)
    kw, _ = eng._decode_step_args(np.ones(4, bool), np.full(4, 5))
    layers = cfg.num_layers
    before = _counts()
    jax.make_jaxpr(functools.partial(
        S._decode_step_jit.__wrapped__, model, temperature=0.0, top_k=0,
        top_p=1.0, **kw))(
            variables, cache, sds((4,), jnp.int32), sds((4,), jnp.int32),
            sds((4,), jnp.bool_), jax.random.PRNGKey(0),
            prev_nxt=sds(eng._no_prev.shape, jnp.int32),
            feed_host=sds((4,), jnp.bool_))
    decode = {k: v - before[k] for k, v in _counts().items()}
    # 4 rows under every width: each site of each layer cut, once
    assert decode == {(p, ch): (layers if ch == "cut" else 0)
                      for p in "qkvo" for ch in ("cut", "kept")}
    before = _counts()
    jax.make_jaxpr(functools.partial(
        S._prefill_slot_jit.__wrapped__, model, attention_backend="dense"))(
            variables, cache, sds((256,), jnp.int32), sds((), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32))
    prefill = {k: v - before[k] for k, v in _counts().items()}
    # 256 rows over widths of 128: each site kept, once
    assert prefill == {(p, ch): (layers if ch == "kept" else 0)
                       for p in "qkvo" for ch in ("cut", "kept")}


def test_every_warmup_program_span_says_what_its_program_cut_and_kept():
    cfg = LlamaConfig.tiny(dtype=jnp.float32, max_len=128)      # width 128
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    tr = get_tracer()
    tr.reset()
    SlotEngine(model, variables, n_slots=2, max_len=128, min_bucket=32,
               warmup="sync", name="t-fold-spans")
    warm, = tr.spans("llm.warmup")
    by_key = {s.attrs["key"]: s.attrs for s in tr.children(warm)}
    sites = 4 * cfg.num_layers
    want = {"prefill_b32": (sites, 0), "prefill_b64": (sites, 0),
            "prefill_b128": (0, sites), "prefix_copy": (0, 0)}
    decode = [k for k in by_key if k.startswith("decode_")]
    assert len(decode) == 1
    want[decode[0]] = (sites, 0)
    assert {k: (a["projections_fold_cut"], a["projections_fold_kept"])
            for k, a in by_key.items()} == want
