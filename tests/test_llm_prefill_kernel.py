"""The prefill attention kernel (``pallas_attn.prefill_attention``) against the
plain equations, through the Pallas interpreter at small sizes.

- one parametrised comparison whose cases are the kernel's parameters by
  layer kind: the group (4, 16, 1 with a cache row padded 30 -> 32), a tail
  behind a reused prefix, ``plen`` short of the bucket, a window whose first
  visible block is masked inside, a sink, a key wider than the value, a
  ring's ``key_offset`` with rows at negative positions;
- a control: the same comparison is failed by operands rounded down and by
  a mask one key off, so the tolerance says something;
- ``prefill_geometry`` at the published shapes and where it answers None;
- the host's block count against a count by hand;
- ``SlotEngine`` serving each toy configuration's full-forward logits with
  every prefill through the kernel (MiMo's toy, with its ring, packed rows,
  sink and two head counts, rides ``tests/test_llm_mixed_kinds.py``'s
  engine test as its third backend).

Tolerance: float32 operands through two orders of summation agree to some
5e-7 on outputs near 1; ``TOL`` = 2e-6 as the paged kernel's tests have it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from synapseml_tpu.models.llm import LlamaConfig, LlamaModel, SlotEngine
from synapseml_tpu.models.llm import pallas_attn as P
from synapseml_tpu.telemetry import get_registry

pytestmark = pytest.mark.pallas

TOL = 2e-6


def plain(q, k, v, start, window=None, sink=None, key_offset=0):
    """The dense equations of ``CausalAttention``: float32 scores over every
    key, the mask, one softmax (a sink is one more column that carries no
    value), probabilities cast to the operands' dtype."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    s = jnp.einsum("bskgd,btkd->bkgst", q.reshape(B, S, KV, G, D), k,
                   preferred_element_type=jnp.float32) / np.sqrt(D)
    pos = start + jnp.arange(S)
    kpos = key_offset + jnp.arange(T)
    see = (kpos[None, :] <= pos[:, None]) & (kpos[None, :] >= 0)
    if window is not None:
        see &= kpos[None, :] > pos[:, None] - window
    s = jnp.where(see[None, None, None], s, jnp.finfo(jnp.float32).min)
    if sink is not None:
        col = jnp.broadcast_to(sink.reshape(1, KV, G, 1, 1), s.shape[:-1] + (1,))
        s = jnp.concatenate([s, col], -1)
    p = jax.nn.softmax(s, -1).astype(q.dtype)
    if sink is not None:
        p = p[..., :-1]
    return jnp.einsum("bkgst,btkd->bskgd", p, v).reshape(B, S, -1)


def operands(S, T, H, KV, D, Dv, row_heads=None, sink=False, seed=0,
             dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    rh = row_heads or KV
    q = jax.random.normal(ks[0], (1, S, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (1, T, rh, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (1, T, rh, Dv), jnp.float32).astype(dtype)
    return q, k, v, (jax.random.normal(ks[3], (H,)) if sink else None)


#: id -> (shape S T H KV D Dv, tile bq bk, the kernel's other arguments)
CASES = {
    "group4": ((32, 64, 8, 2, 16, 16), (8, 16), {}),
    "group16": ((32, 64, 16, 1, 16, 16), (8, 16), {}),
    "group1-rows-padded-30-to-32": ((32, 64, 30, 30, 16, 16), (16, 16),
                                    {"row_heads": 32}),
    "tail-behind-a-reused-prefix": ((16, 64, 8, 2, 16, 16), (8, 16),
                                    {"start": 40}),
    "plen-short-of-the-bucket": ((32, 64, 8, 2, 16, 16), (8, 16),
                                 {"start": 8, "plen": 19}),
    "plen-short-by-whole-blocks": ((32, 64, 8, 2, 16, 16), (8, 16),
                                   {"plen": 5}),
    "window-first-visible-block-masked": ((32, 64, 8, 2, 16, 16), (8, 8),
                                          {"start": 24, "window": 12}),
    "window-wider-than-the-keys": ((32, 64, 8, 2, 16, 16), (8, 16),
                                   {"window": 200}),
    "sink": ((32, 64, 8, 2, 16, 16), (8, 16), {"sink": True, "plen": 20}),
    "key-wider-than-the-value": ((32, 64, 8, 2, 24, 16), (8, 16),
                                 {"sink": True}),
    "ring-rows-at-negative-positions": (
        (32, 48, 8, 2, 16, 16), (8, 8),
        {"start": 5, "window": 6, "key_offset": -11, "plen": 27}),
    "ring-three-rings-on-with-a-sink": (
        (32, 48, 8, 2, 16, 16), (8, 8),
        {"start": 100, "window": 6, "key_offset": 84, "plen": 27,
         "sink": True}),
    "one-block-of-each": ((16, 16, 4, 4, 16, 16), (16, 16), {}),
}


def run_case(shape, tile, start=0, plen=None, window=None, sink=False,
             key_offset=None, row_heads=None, dtype=jnp.float32,
             kernel_window=None, kernel_start=None):
    """-> (the kernel's rows, the plain equations' rows, plen)."""
    S, T, H, KV, D, Dv = shape
    q, k, v, sk = operands(S, T, H, KV, D, Dv, row_heads, sink, dtype=dtype)
    plen = S if plen is None else plen
    got = P.prefill_attention(
        q, k, v, start if kernel_start is None else kernel_start, plen,
        bq=tile[0], bk=tile[1], kv_heads=KV,
        window=window if kernel_window is None else kernel_window, sink=sk,
        key_offset=key_offset, interpret=True)
    want = plain(q.astype(jnp.float32), k[:, :, :KV].astype(jnp.float32),
                 v[:, :, :KV].astype(jnp.float32), start, window, sk,
                 0 if key_offset is None else key_offset)
    return np.asarray(got, np.float32), np.asarray(want), plen


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_matches_the_plain_equations(case):
    shape, tile, kw = CASES[case]
    got, want, plen = run_case(shape, tile, **kw)
    assert got.shape == want.shape
    np.testing.assert_allclose(got[:, :plen], want[:, :plen], atol=TOL)
    # rows past ``plen`` are zeros, never what the scratch held
    assert np.all(got[:, plen:] == 0.0)
    assert np.abs(want[:, plen:]).max(initial=1.0) > 0.0


def test_a_rounded_down_or_mis_masked_kernel_would_be_seen():
    """The control: the comparison above fails for bfloat16 operands, for a
    window one key too wide and for queries placed one position late."""
    shape, tile = (32, 64, 8, 2, 16, 16), (8, 8)
    kw = {"start": 24, "window": 12}
    got, want, _ = run_case(shape, tile, **kw)
    assert np.abs(got - want).max() < TOL
    for name, fault in {"bfloat16": {"dtype": jnp.bfloat16},
                        "window": {"kernel_window": 13},
                        "start": {"kernel_start": 25}}.items():
        got, want, _ = run_case(shape, tile, **kw, **fault)
        assert np.abs(got - want).max() > 1e3 * TOL, name


#: the published shapes ``S, T, H, KV, D, Dv, window`` -> ``(bq, bk, steps)``
GEOMETRIES = {
    "mistral-bucket-2048": ((2048, 2048, 32, 8, 128, 128, None), (256, 512, 4)),
    "mistral-bucket-512": ((512, 2048, 32, 8, 128, 128, None), (256, 512, 4)),
    "mistral-bucket-256": ((256, 2048, 32, 8, 128, 128, None), None),
    "olmo-bucket-1536": ((1536, 1536, 30, 30, 128, 128, None), (512, 512, 3)),
    "olmo-bucket-512-group-1-loses": ((512, 1536, 30, 30, 128, 128, None),
                                      None),
    "command-a-window-1024": ((1024, 5632, 128, 8, 128, 128, 4096),
                              (64, 512, 10)),
    "command-a-full-64": ((64, 5632, 128, 8, 128, 128, None), (64, 512, 11)),
    "command-a-32-queries-lose": ((32, 5632, 128, 8, 128, 128, None), None),
    "mimo-full-16384": ((16384, 16384, 64, 4, 192, 128, None), (64, 512, 32)),
    "mimo-ring-16384": ((16384, 16640, 64, 8, 192, 128, 128), (128, 128, 3)),
    "mimo-ring-512": ((512, 768, 64, 8, 192, 128, 128), None),
    "no-block-divides-the-keys": ((2048, 2040, 32, 8, 128, 128, None), None),
    "no-block-divides-the-queries": ((2044, 2048, 32, 8, 128, 128, None),
                                     None),
    "heads-not-in-groups": ((2048, 2048, 32, 5, 128, 128, None), None),
}


@pytest.mark.parametrize("case", list(GEOMETRIES))
def test_the_geometry_follows_the_shape(case):
    (S, T, H, KV, D, Dv, window), want = GEOMETRIES[case]
    geo = P.prefill_geometry(S, T, H, KV, D, Dv, jnp.bfloat16, window)
    if want is None:
        assert geo is None
        return
    assert (geo.bq, geo.bk, geo.key_steps) == want
    assert geo.vmem_bytes <= P._VMEM_BUDGET
    assert S % geo.bq == 0 and T % geo.bk == 0


def test_the_geometry_halves_its_rows_until_vmem_holds_them():
    # 256 query heads over 1: even 16 positions are 4,096 score rows
    assert P.prefill_geometry(2048, 2048, 256, 1, 128, 128) is None
    geo = P.prefill_geometry(2048, 2048, 64, 1, 128, 128)
    assert (geo.bq, geo.bk) == (16, 512) and geo.vmem_bytes <= P._VMEM_BUDGET


@pytest.mark.parametrize("start,plen,window,off", [
    (0, 32, None, None), (0, 19, None, None), (40, 16, None, None),
    (24, 32, 12, None), (5, 27, 6, -11), (100, 27, 6, 84), (0, 1, None, None)])
def test_the_hosts_block_count_is_the_kernels_walk(start, plen, window, off):
    """``prefill_key_blocks`` against a count by hand: the (query block, key
    block) pairs in which a real query sees a key."""
    S, T, bq, bk = 32, 64 if off is None else 48, 8, 8
    geo = P.PrefillGeometry(bq, bk, P._prefill_key_steps(T, bq, bk, window), 0)
    pos = start + np.arange(S)
    kpos = (0 if off is None else off) + np.arange(T)
    see = (kpos[None, :] <= pos[:, None]) & (kpos[None, :] >= 0)
    if window is not None:
        see &= kpos[None, :] > pos[:, None] - window
    see[plen:] = False
    by_hand = see.reshape(S // bq, bq, T // bk, bk).any((1, 3))
    assert P.prefill_key_blocks(geo, S, start, plen, window, off) \
        == int(by_hand.sum())
    # and no query block needs more steps than the grid gives it
    assert by_hand.sum(1).max() <= geo.key_steps


# -- through the engine ---------------------------------------------------------------

def toy_configurations():
    """The benchmark's three configurations without ``attention_kinds`` at
    toy widths, float32 (``tests/test_llm_mixed_kinds.py`` pins the
    bfloat16 programs of the same three)."""
    return {
        "mistral": LlamaConfig.tiny(dtype=jnp.float32),
        "olmo": LlamaConfig.tiny(
            dtype=jnp.float32, num_kv_heads=8, norm_order="post",
            qk_norm=True,
            layer_types=("linear_attention",) * 3 + ("full_attention",),
            linear_num_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=32),
        "command-a-plus": LlamaConfig.tiny(
            dtype=jnp.float32, d_model=64, num_heads=8, num_kv_heads=2,
            head_dim=16, d_ff=32, norm_order="parallel", norm="layer",
            rope_style="interleaved", rope_layers=("sliding_attention",),
            sliding_window=8,
            layer_types=("sliding_attention",) * 3 + ("full_attention",),
            tie_embeddings=True, ffn="experts", num_experts=16,
            num_experts_per_tok=4, num_shared_experts=2,
            expert_selection="sigmoid", norm_topk_prob=True, experts_first=4,
            experts_held=8)}


@pytest.mark.parametrize("name", ["mistral", "olmo", "command-a-plus"])
def test_slot_engine_serves_the_full_forwards_logits_through_the_kernel(
        name, every_prefill_tiled):
    cfg = toy_configurations()[name]
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 4), jnp.int32))
    rng = np.random.default_rng(11)
    pre = rng.integers(1, cfg.vocab_size, 20).astype(np.int32)
    prompts = [np.concatenate([pre, rng.integers(1, cfg.vocab_size, 15)
                               .astype(np.int32)]),       # 35 of 64: padded
               rng.integers(1, cfg.vocab_size, 16).astype(np.int32),
               np.concatenate([pre, rng.integers(1, cfg.vocab_size, 9)
                               .astype(np.int32)])]       # a tail behind 20
    eng = SlotEngine(model, variables, n_slots=3, max_len=64,
                     attention_backend="interpret", min_bucket=8,
                     name=f"t-prefill-{name}")
    for i, ids in enumerate(prompts):
        want = np.asarray(model.apply(variables, jnp.asarray(ids)[None]))[0]
        res = eng.admit(ids, 3)
        np.testing.assert_allclose(res.logits, want[-1], atol=5e-5,
                                   err_msg=f"{name} prompt {i}")
        att = eng._prefill_attention_attrs()
        assert att["prefill_attention"] == "tiled"
        assert 0 < att["prefill_key_blocks_visited"] \
            <= att["prefill_key_blocks_bucket"]
        if i == 0:
            # 35 real tokens of a bucket of 64: fewer blocks than the bucket
            assert res.bucket == 64 and att["prefill_key_blocks_visited"] \
                < att["prefill_key_blocks_bucket"]
    # the third prompt's tail ran behind the reused prefix, where reuse is
    # served (a recurrent state keeps none)
    assert res.reused_tokens == (0 if eng.recurrent else 20)
    eng.run_to_completion()
    tiled = get_registry().counter(
        "llm_prefill_attention_total", "", ("engine", "path")).value(
            engine=eng.name, path="tiled")
    assert tiled == 3


def test_off_the_kernel_backends_and_under_the_threshold_the_plain_path_stays():
    """``dense`` never asks for a tile; ``interpret`` at a toy size is under
    ``_PREFILL_MIN_SCORE_BYTES`` and says so on its span."""
    cfg = toy_configurations()["mistral"]
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 4), jnp.int32))
    ids = np.arange(1, 20, dtype=np.int32)
    for backend in ("dense", "interpret"):
        eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                         attention_backend=backend, min_bucket=8,
                         name=f"t-prefill-plain-{backend}")
        eng.admit(ids, 2)
        assert eng._prefill_attention_attrs() == {
            "prefill_attention": "dense", "prefill_key_blocks_visited": 0,
            "prefill_key_blocks_bucket": 0}
