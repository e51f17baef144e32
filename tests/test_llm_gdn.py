"""Linear-attention layers (gated delta rule) beside full attention in one
decoder, and the recurrent state they keep beside the K/V cache.

Pinned here, at small sizes on the CPU:

- the two Pallas kernels of ``pallas_gdn`` (interpret mode) against the
  ``lax.scan`` recurrence: inactive slots, padded buckets, ``beta`` above 1;
- the model through ``SlotEngine`` (prefill, then decode) against the plain
  float32 reference the benchmark keeps for Olmo-Hybrid
  (``benchmark/references/olmo-hybrid-7b-l16.py``, which shares no code with
  the program), by LOGITS: the admission's own logits, and for every served
  token how far its logit lies under the reference's best.  With random
  weights the largest logit changes on rounding, so tokens are not compared;
- everything that can corrupt a state that no token position can slice: a
  prompt shorter than its bucket, a slot reused after a retire, an EOS and a
  cancel under the step in flight, an inactive slot beside active ones,
  ``preempt``/``resume``;
- each path that slices or rolls back by token position saying so: a drafter
  and a ``kv_arena`` are errors at construction, prefix reuse and ``resume``
  prefill from zero and count it;
- the paged decode kernel at 30 K/V heads in a cache row padded to 32;
- both kernels compiled for the v5e at the published geometry (no chip
  needed: the TPU compiler is installed; nothing runs); and, because such
  compiles belong in ONE test file (its worker holds the TPU library), the
  GBDT fused histogram pass at the boosting cell's shapes.

Tolerances.  Program and reference both compute in float32 from the same
bfloat16-rounded weights; they differ in summation order (XLA's CPU dot
against ``Precision.HIGHEST``, the kernel's one-pass form ``alpha S + k (beta
(v - alpha k^T S))^T`` against the two-step form of the equations) over 8
layers and some 40 tokens: a few 1e-6 relative on logits of order 1.
``LOGIT_TOL`` = 2e-4 leaves two orders of room and is two orders under the
gap a wrong state gives (a junk token in a state, or a state not zeroed,
reads 1e-2 and more here).
"""

import dataclasses
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
from synapseml_tpu.models.llm import (LlamaConfig, LlamaModel,  # noqa: E402
                                      SlotEngine, init_cache)
from synapseml_tpu.models.llm import pallas_gdn as G  # noqa: E402
from synapseml_tpu.models.llm.pallas_attn import (  # noqa: E402
    paged_decode_attention, paged_geometry)
from synapseml_tpu.telemetry import get_registry  # noqa: E402

LOGIT_TOL = 2e-4
KERNEL_TOL = 2e-6        # one token's float32 sums in another order
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b-l16.json")
BACKENDS = [pytest.param("dense", id="scan"),
            pytest.param("interpret", id="kernels", marks=pytest.mark.pallas)]


# -- the kernels against the scan ---------------------------------------------

def _inputs(B, S, H, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, S, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = jax.random.normal(ks[1], (B, S, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, H, dv))
    a = jax.random.uniform(ks[3], (B, S, H), minval=0.5, maxval=1.0)
    b = jax.random.uniform(ks[4], (B, S, H), minval=0.0, maxval=2.0)
    s = jax.random.normal(ks[5], (B, H, dk, dv))
    return q, k, v, a, b, s


def test_pack_puts_heads_side_by_side_on_the_lanes():
    assert G.gdn_pack(30, 192) == 2 and G.state_shape(30, 96, 192) == (15, 96, 384)
    assert G.gdn_pack(4, 32) == 4 and G.gdn_pack(8, 128) == 1
    assert G.gdn_pack(3, 100) == 1          # no divisor helps: unpacked
    s = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 8, 64))
    p = G.pack_state(s, 2)
    assert p.shape == (2, 3, 8, 128)
    # head 2g+e owns lanes [e*64, (e+1)*64) of group g
    np.testing.assert_array_equal(p[:, 1, :, 64:], s[:, 3])
    np.testing.assert_array_equal(G.unpack_state(p, 2), s)


def test_the_geometry_gate_refuses_and_names_the_reason():
    assert G.gdn_geometry(30, 96, 192) == 2         # the packing it runs with
    assert G.gdn_geometry(8, 64, 128) == 1
    assert G.gdn_geometry(4, 12, 32) is None        # d_k off the sublanes
    assert G.gdn_geometry(3, 96, 100) is None       # no lane-wide packing
    assert G.gdn_geometry(64, 256, 512) is None     # a slot's state > VMEM
    with pytest.raises(ValueError, match="no gated-delta kernel geometry"):
        G.resolve_recurrent_backend("paged", 3, 96, 100)
    # the scan takes any geometry; interpret runs the kernels anywhere
    assert G.resolve_recurrent_backend("dense", 3, 96, 100) == "dense"
    assert G.resolve_recurrent_backend("interpret", 4, 16, 32) == "interpret"


@pytest.mark.pallas
@pytest.mark.parametrize("active", [[1, 0, 1, 1, 0], [0, 0, 1, 0, 1],
                                    [0, 0, 0, 0, 0], [1, 1, 1, 1, 1]],
                         ids=lambda a: "".join(map(str, a)))
def test_decode_kernel_matches_the_scan_and_skips_inactive_slots(active):
    H, dk, dv = 4, 16, 32
    pack = G.gdn_pack(H, dv)
    q, k, v, a, b, s = _inputs(5, 1, H, dk, dv)
    assert float(b.max()) > 1.0                     # negative eigenvalues
    act = jnp.asarray(active, bool)
    o_ref, s_ref = G.gated_delta_scan(q, k, v, a, b, s, act[:, None])
    s_new, o = G.gated_delta_decode(
        G.pack_state(s, pack), q[:, 0], k[:, 0], v[:, 0], a[:, 0], b[:, 0],
        act, pack=pack, interpret=True)
    s_new = G.unpack_state(s_new, pack)
    np.testing.assert_allclose(s_new, s_ref, atol=KERNEL_TOL)
    on = np.asarray(act)
    np.testing.assert_allclose(np.asarray(o)[on], np.asarray(o_ref)[on, 0],
                               atol=KERNEL_TOL)
    # an inactive slot: its state bit for bit what it was, its output zero
    np.testing.assert_array_equal(np.asarray(s_new)[~on], np.asarray(s)[~on])
    assert not np.asarray(o)[~on].any()


@pytest.mark.pallas
@pytest.mark.parametrize("T,plen", [(32, 32), (32, 17), (32, 1), (8, 5),
                                    (64, 16), (32, 0)])
def test_prefill_kernel_matches_the_scan_and_skips_the_padding(T, plen):
    H, dk, dv = 4, 16, 32
    pack = G.gdn_pack(H, dv)
    q, k, v, a, b, s = _inputs(1, T, H, dk, dv, seed=T + plen)
    valid = (jnp.arange(T) < plen)[None]
    o_ref, s_ref = G.gated_delta_scan(q, k, v, a, b, s, valid)
    s_new, o = G.gated_delta_prefill(
        G.pack_state(s[0], pack), q[0], k[0], v[0], a[0], b[0], plen,
        pack=pack, interpret=True)
    np.testing.assert_allclose(G.unpack_state(s_new, pack), s_ref[0],
                               atol=T * KERNEL_TOL)
    np.testing.assert_allclose(o[:plen], o_ref[0, :plen], atol=T * KERNEL_TOL)
    assert not np.asarray(o[plen:]).any()           # padding rows: zero
    if plen == 0:
        np.testing.assert_array_equal(G.unpack_state(s_new, pack), s[0])


def test_a_slots_bytes_by_hand():
    # one slot, one layer at the published geometry: 30 x 96 x 192 float32
    # and three rows of the 11,520 convolution channels in bfloat16
    assert G.slot_state_bytes(30, 96, 192, 3, 11520) == 2_211_840 + 69_120
    assert G.slot_state_bytes(4, 16, 32, 3, 256, conv_itemsize=4) == \
        4 * 16 * 32 * 4 + 3 * 256 * 4


# -- the model against the plain reference --------------------------------------

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
SMALL = dict(model_type="olmo_hybrid", vocab_size=256, hidden_size=128,
             intermediate_size=256, num_hidden_layers=8,
             num_attention_heads=4, num_key_value_heads=4, head_dim=32,
             rms_norm_eps=1e-6, tie_word_embeddings=False,
             layer_types=PERIOD * 2, linear_num_key_heads=4,
             linear_num_value_heads=4, linear_key_head_dim=16,
             linear_value_head_dim=32, linear_conv_kernel_dim=4,
             linear_allow_neg_eigval=True,
             rope_parameters={"rope_theta": None},
             max_position_embeddings=96)
SEED = 2147483659          # past 2**31, as the driver's seeds are
MAX_LEN = 96


@pytest.fixture(scope="module")
def benchmark_config():
    with open(CONFIG_FILE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref(benchmark_config):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "references",
        benchmark_config["reference"] + ".py"))


@pytest.fixture(scope="module")
def small(ref, benchmark_config):
    """(cfg, model, variables): hidden 128, two periods, float32, with the
    reference's seeded weights laid into the program's parameter tree by the
    configuration file's own map."""
    cfg = LlamaConfig.from_hf(SMALL, dtype=jnp.float32, max_len=MAX_LEN)
    names = benchmark_config["model"]["params"]
    params = {}

    def put(path, arr):
        tree = params
        *parts, last = path.split("/")
        for p in parts:
            tree = tree.setdefault(p, {})
        tree[last] = jnp.asarray(arr, jnp.float32)
    for key, arr in ref.outer_weights(SMALL, SEED).items():
        put(names["outer"][key], arr)
    for i in range(SMALL["num_hidden_layers"]):
        for key, arr in ref.layer_weights(SMALL, SEED, i).items():
            put(names["layer_prefix"].format(i=i) + "/" + names["layer"][key],
                arr)
    return cfg, LlamaModel(cfg), {"params": params}


def _prompt(length, seed):
    return np.random.default_rng(seed).integers(
        1, SMALL["vocab_size"], length).astype(np.int32)


def _ref_logits(ref, ids, positions):
    return ref.forward(SMALL, SEED, [np.asarray(ids, np.int32)],
                       [np.asarray(positions)], MAX_LEN)[0]


def _gap(ref, prompt, served):
    """(widest gap of a served token's logit under the reference's best,
    the reference's logits at the prompt's last position)."""
    ids = list(prompt) + list(served[:-1])
    lg = _ref_logits(ref, ids, np.arange(len(prompt) - 1, len(ids)))
    tok = np.asarray(served)
    return float((lg.max(-1) - lg[np.arange(len(tok)), tok]).max()), lg[0]


def test_the_parameter_tree_is_the_configuration_files_map(small, ref):
    """Every parameter the model declares gets a weight of the reference
    and of its shape: none is left at its initial value, none is spare."""
    import flax.linen as nn
    cfg, model, variables = small
    assert cfg.layer_kinds == tuple(PERIOD * 2)
    assert cfg.norm_order == "post" and cfg.qk_norm and cfg.rope_theta is None
    init = nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"])

    def shapes(tree):
        return {jax.tree_util.keystr(path): leaf.shape for path, leaf
                in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert shapes(init) == shapes(variables["params"])


def test_full_forward_matches_the_reference(small, ref):
    cfg, model, variables = small
    ids = _prompt(21, seed=1)
    logits = model.apply(variables, jnp.asarray(ids)[None])[0]
    want = _ref_logits(ref, ids, np.arange(len(ids)))
    np.testing.assert_allclose(logits, want, atol=LOGIT_TOL)
    assert float(np.std(want)) > 0.1       # the tolerance is not the scale


@pytest.mark.parametrize("backend", BACKENDS)
def test_prefill_then_decode_logits_match_the_reference(small, ref, backend):
    """The model's own calls, as ``_prefill_slot_jit`` and
    ``_decode_step_jit`` make them: a bucket of 16 holding 11 real tokens
    into row 1, then per-slot decode steps with slot 0 inactive."""
    cfg, model, variables = small
    ids = _prompt(17, seed=2)
    want = _ref_logits(ref, ids, np.arange(len(ids)))
    cache = init_cache(cfg, 2, MAX_LEN)
    row = jax.tree.map(lambda c: c[1:2], cache)
    padded = np.full(16, 7, np.int32)
    padded[:11] = ids[:11]
    lg, row = model.apply(variables, jnp.asarray(padded)[None], cache=row,
                          cache_index=0, valid_len=11,
                          attention_backend=backend)
    np.testing.assert_allclose(lg[0, :11], want[:11], atol=LOGIT_TOL)
    cache = jax.tree.map(lambda c, r: c.at[1:2].set(r), cache, row)
    for t in range(11, 17):
        toks = jnp.asarray([[3], [ids[t]]], jnp.int32)
        lengths = jnp.asarray([1, t + 1])
        lg, cache = model.apply(
            variables, toks, positions=(lengths - 1)[:, None], cache=cache,
            cache_index=lengths - 1, slot_mask=jnp.asarray([False, True]),
            attention_backend=backend)
        np.testing.assert_allclose(lg[1, 0], want[t], atol=LOGIT_TOL)
    for layer, kind in zip(cache, cfg.layer_kinds):
        if kind == "linear_attention":      # slot 0 never took a token
            assert not np.asarray(layer["state"][0]).any()
            assert not np.asarray(layer["conv"][0]).any()
            assert np.asarray(layer["state"][1]).any()


def test_a_padded_token_or_a_stale_state_would_be_seen(small, ref):
    """What the gates are for, so that the tolerance above means something:
    the same prefill WITHOUT ``valid_len`` takes the bucket's padding into
    the state, and the next token's logits leave the reference by far more
    than ``LOGIT_TOL``."""
    cfg, model, variables = small
    ids = _prompt(12, seed=3)
    want = _ref_logits(ref, ids, [11])[0]
    padded = np.full(16, 7, np.int32)
    padded[:11] = ids[:11]

    def next_logits(valid_len):
        row = init_cache(cfg, 1, MAX_LEN)
        _, row = model.apply(variables, jnp.asarray(padded)[None], cache=row,
                             cache_index=0, valid_len=valid_len)
        lg, _ = model.apply(variables, jnp.asarray([[ids[11]]]),
                            positions=jnp.asarray([[11]]), cache=row,
                            cache_index=jnp.asarray([11]))
        return np.asarray(lg[0, 0])
    assert np.abs(next_logits(11) - want).max() < LOGIT_TOL
    assert np.abs(next_logits(None) - want).max() > 50 * LOGIT_TOL


class Drive:
    """What a serving loop keeps beside the engine: which request holds
    which slot, the tokens each was handed, its admission's logits."""

    def __init__(self, eng):
        self.eng = eng
        self.by_slot, self.tokens, self.reasons = {}, {}, {}
        self.logits, self.paths = {}, {}

    def admit(self, name, prompt, max_new):
        res = self.eng.admit(prompt, max_new)
        assert res is not None
        self.tokens[name] = [res.token]
        self.logits[name] = res.logits
        self.paths[name] = res.path
        if res.finished:
            self.reasons[name] = res.reason
        else:
            self.by_slot[res.slot] = name
        return res.slot

    def drop(self, name):
        slot, = [s for s, n in self.by_slot.items() if n == name]
        del self.by_slot[slot]
        return slot

    def step(self):
        events = self.eng.step()
        for ev in events:
            name = self.by_slot[ev.slot]
            self.tokens[name].append(ev.token)
            if ev.finished:
                self.reasons[name] = ev.reason
                del self.by_slot[ev.slot]
        return events

    def run(self):
        while self.eng.active.any():
            assert self.step()


def _recurrent_rows(eng, slot):
    return [(np.asarray(layer["state"][slot]), np.asarray(layer["conv"][slot]))
            for layer in eng.cache if "state" in layer]


@pytest.mark.parametrize("backend", BACKENDS)
def test_slot_engine_serves_the_references_logits_through_everything(
        small, ref, backend):
    cfg, model, variables = small
    lens = {"a": 11, "b": 9, "c": 5, "d": 7, "e": 6, "f": 13, "g": 5}
    p = {k: _prompt(n, seed=40 + i) for i, (k, n) in enumerate(lens.items())}
    budget = {"a": 24, "b": 3, "c": 12, "d": 6, "e": 5, "f": 9, "g": 4}
    name = f"t-gdn-mix-{backend}"
    # c's own continuation gives the EOS that stops it in mid-stream: a
    # token no other request produces (random weights repeat themselves)
    probe = Drive(SlotEngine(model, variables, n_slots=1, max_len=MAX_LEN,
                             attention_backend="dense", name=name + "-probe"))
    for k in p:
        probe.admit(k, p[k], budget[k])
        probe.run()
    others = {t for k in p if k != "c" for t in probe.tokens[k]}
    eos = next(t for i, t in enumerate(probe.tokens["c"]) if i >= 2
               and t not in probe.tokens["c"][:i] and t not in others)

    eng = SlotEngine(model, variables, n_slots=3, max_len=MAX_LEN, eos_id=eos,
                     attention_backend=backend, name=name)
    assert eng.recurrent and eng.attention_backend == backend
    d = Drive(eng)
    for k in "abc":                 # 11 of 16, 9 of 16, 5 of 8: all padded
        d.admit(k, p[k], budget[k])
    # b retires by length, c on its EOS (and rides the step in flight once
    # more, a junk token into its state); d takes the first slot freed
    while "b" not in d.reasons and "c" not in d.reasons:
        d.step()
    assert eng._flight is not None
    freed = int(np.flatnonzero(~eng.active)[0])
    d.step()                        # the step in flight has run over it
    idle_before = _recurrent_rows(eng, freed)
    d.step()                        # an inactive slot beside active ones:
    for (s0, c0), (s1, c1) in zip(idle_before, _recurrent_rows(eng, freed)):
        np.testing.assert_array_equal(s0, s1)       # bit for bit
        np.testing.assert_array_equal(c0, c1)
    d.admit("d", p["d"], budget["d"])               # a slot reused
    while len(d.reasons) < 2:
        d.step()
    # a is cancelled under a running step, its slot given to e at once
    d.admit("f", p["f"], budget["f"])
    assert eng._flight is not None
    slot_a = d.drop("a")
    eng.cancel(slot_a)
    assert d.admit("e", p["e"], budget["e"]) == slot_a
    d.step()
    d.step()
    # f is preempted under a running step and resumed: a cold prefill of
    # all it had, since no state after its span was kept
    skipped = eng.prefix_reuse_skipped
    ticket = eng.preempt(d.drop("f"))
    assert ticket is not None
    d.step()
    d.by_slot[eng.resume(ticket)] = "f"
    assert eng.prefix_reuse_skipped == skipped + 1
    d.run()
    d.admit("g", p["g"], budget["g"])
    d.run()
    assert eng._flight is None
    assert d.reasons["b"] == "length" and d.reasons["c"] == "eos"
    assert d.tokens["c"][-1] == eos and len(d.tokens["c"]) < budget["c"]
    assert len(d.tokens["a"]) < budget["a"]
    for k in "abcdefg":
        gap, first = _gap(ref, p[k], d.tokens[k])
        assert gap < LOGIT_TOL, (k, gap)
        np.testing.assert_allclose(d.logits[k], first, atol=LOGIT_TOL,
                                   err_msg=k)
    assert set(d.paths.values()) == {"cold"}        # nothing to reuse here


# -- the paths that slice or roll back by token position ---------------------------

def test_a_drafter_or_a_host_arena_is_refused_at_construction(small):
    cfg, model, variables = small
    with pytest.raises(ValueError, match="keeps no snapshot to return to"):
        SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                   spec_draft_len=2, attention_backend="dense")
    from synapseml_tpu.models.llm.kvtier import HostKVArena
    with pytest.raises(ValueError, match="snapshotted at a position, not "
                                         "sliced"):
        SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                   kv_arena=HostKVArena(1 << 20), attention_backend="dense")
    # the model itself refuses a multi-token step at per-slot positions
    cache = init_cache(cfg, 2, MAX_LEN)
    with pytest.raises(NotImplementedError, match="roll a recurrent state"):
        model.apply(variables, jnp.zeros((2, 3), jnp.int32),
                    positions=jnp.zeros((2, 3), jnp.int32), cache=cache,
                    cache_index=jnp.asarray([4, 5]))


def test_a_prefill_worker_over_such_an_engine_is_refused(small):
    from synapseml_tpu.serving.disagg import PrefillWorker
    cfg, model, variables = small
    eng = SlotEngine(model, variables, n_slots=1, max_len=MAX_LEN,
                     attention_backend="dense", name="t-gdn-disagg")
    with pytest.raises(ValueError, match="ships K/V rows by token position"):
        PrefillWorker(eng)


# -- the state's precision ---------------------------------------------------------
# The benchmark's ``served_logit_gap`` cannot tell a float32 state from one
# rounded to bfloat16 after every token (PERF.md section 7): these two hold
# the program to the configuration's stated float32 instead.

@pytest.mark.parametrize("backend", BACKENDS)
def test_the_served_state_is_the_references_float32_state(small, ref, backend):
    """Layer 0's state after a padded prefill and decode steps, against the
    reference's scan over the same tokens (its input is the embedding, so
    the reference's own pieces give it).  1e-5 of the state's largest
    entry: float32 sums of 16 terms in another order read 2e-7 of it; the
    same state rounded to bfloat16 after every token reads 4e-3 of it."""
    cfg, model, variables = small
    eng = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                     attention_backend=backend, name=f"t-gdn-state-{backend}")
    d = Drive(eng)
    prompt = _prompt(11, seed=90)                   # 11 of a bucket of 16
    slot = d.admit("a", prompt, 9)
    d.run()
    fed = np.concatenate([prompt, d.tokens["a"][:-1]])   # the last is not fed
    w = ref.layer_weights(SMALL, SEED, 0)
    x = jnp.asarray(ref.outer_weights(SMALL, SEED)["embed"],
                    jnp.float32)[jnp.asarray(fed)]
    lin = ref.linear_inputs(x, w, LH=4, dk=16, dv=32, neg=True)
    _, want = ref.gated_delta_rule(*lin)
    _, rounded = ref.gated_delta_rule(*lin, round_state=True)
    state = eng.cache[0]["state"]
    assert state.dtype == jnp.float32
    got = G.unpack_state(state[slot], G.gdn_pack(4, 32))
    scale = float(np.abs(np.asarray(want)).max())
    err = float(np.abs(np.asarray(got) - np.asarray(want)).max())
    low = float(np.abs(np.asarray(rounded) - np.asarray(want)).max())
    assert err < 1e-5 * scale
    assert low > 1e-3 * scale


def test_a_bfloat16_model_keeps_its_state_in_float32(small):
    """The served precision: weights, activations and the convolution
    window bfloat16, the state float32 through prefill and decode."""
    cfg, model, variables = small
    cfg16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    v16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16)
                                 if a.ndim > 1 else a, variables)
    eng = SlotEngine(LlamaModel(cfg16), v16, n_slots=2, max_len=MAX_LEN,
                     attention_backend="interpret", name="t-gdn-bf16")
    eng.admit(_prompt(7, seed=91), 4)
    eng.run_to_completion()
    for layer, kind in zip(eng.cache, cfg.layer_kinds):
        if kind == "linear_attention":
            assert layer["state"].dtype == jnp.float32
            assert layer["conv"].dtype == jnp.bfloat16
            assert np.abs(np.asarray(layer["state"][0])).max() > 0
        else:
            assert layer["k"].dtype == jnp.bfloat16


def test_prefix_reuse_is_skipped_counted_and_still_exact(small, ref):
    cfg, model, variables = small
    name = "t-gdn-reuse"
    eng = SlotEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                     attention_backend="dense", name=name, min_prefix=4)
    d = Drive(eng)
    first = _prompt(14, seed=60)
    second = np.concatenate([first[:10], _prompt(5, seed=61)])
    d.admit("first", first, 4)
    d.run()
    d.admit("second", second, 4)        # 10 tokens in common with a slot
    d.run()
    assert d.paths == {"first": "cold", "second": "cold_recurrent"}
    assert eng.prefix_hits == 0 and eng.prefix_tokens_reused == 0
    assert eng.prefix_reuse_skipped == 1
    reg = get_registry()
    assert reg.get("llm_prefix_reuse_skipped_total").value(
        engine=name, reason="recurrent_state") == 1.0
    assert reg.get("llm_recurrent_state_bytes").value(engine=name) == \
        2 * eng.slot_state_bytes
    # 6 linear layers x (4 x 16 x 32 float32 + 3 x 256 float32)
    assert eng.slot_state_bytes == 6 * (4 * 16 * 32 * 4 + 3 * 256 * 4)
    for k, prompt in (("first", first), ("second", second)):
        assert _gap(ref, prompt, d.tokens[k])[0] < LOGIT_TOL


def test_a_dense_model_keeps_its_reuse_and_counts_no_state():
    cfg = LlamaConfig.tiny(num_layers=2, max_len=64, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                     attention_backend="dense", name="t-gdn-dense",
                     min_prefix=4)
    assert not eng.recurrent and eng.slot_state_bytes == 0
    assert cfg.kv_cache_heads == cfg.num_kv_heads
    assert all(set(layer) == {"k", "v"} for layer in eng.cache)
    first = np.arange(1, 15, dtype=np.int32)
    eng.admit(first, 2)
    eng.run_to_completion()
    res = eng.admit(np.concatenate([first[:10], first[:3]]), 2)
    assert res.path == "reuse" and eng.prefix_reuse_skipped == 0
    assert get_registry().get("llm_recurrent_state_bytes").value(
        engine="t-gdn-dense") == 0.0


def test_the_step_and_admit_spans_say_what_the_state_cost(small, tmp_path):
    from jax.profiler import ProfileOptions
    from synapseml_tpu.telemetry import get_tracer
    cfg, model, variables = small
    eng = SlotEngine(model, variables, n_slots=3, max_len=MAX_LEN,
                     attention_backend="dense", name="t-gdn-span",
                     min_prefix=4)
    first = _prompt(9, seed=70)
    eng.admit(first, 4)
    tracer = get_tracer()
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:                            # step spans live under a session only
        eng.admit(np.concatenate([first[:6], _prompt(3, seed=71)]), 4)
        eng.step()
    finally:
        jax.profiler.stop_trace()
    assert tracer.spans("engine.admit")[-1].attrs["path"] == "cold_recurrent"
    # two slots in use: their state read once and written once
    assert tracer.spans("engine.step")[-1].attrs["state_bytes"] == \
        2 * 2 * eng.slot_state_bytes


# -- the published shape ---------------------------------------------------------

def test_the_uncut_pattern_builds_from_the_published_keys(benchmark_config):
    published = {k: v for k, v in benchmark_config.items()
                 if k in ("model_type", "vocab_size", "hidden_size",
                          "intermediate_size", "num_attention_heads",
                          "num_key_value_heads", "max_position_embeddings",
                          "rms_norm_eps", "tie_word_embeddings",
                          "linear_num_key_heads", "linear_num_value_heads",
                          "linear_key_head_dim", "linear_value_head_dim",
                          "linear_conv_kernel_dim", "linear_allow_neg_eigval",
                          "rope_parameters")}
    published.update(benchmark_config["published"])     # 32 layers, 8 periods
    cfg = LlamaConfig.from_hf(published)
    assert cfg.num_layers == 32 and cfg.num_recurrent_layers == 24 \
        and cfg.num_attention_layers == 8
    assert cfg.layer_kinds == tuple(PERIOD * 8)
    assert cfg.d_head == 128 and cfg.rope_theta is None and cfg.qk_norm
    assert cfg.kv_cache_heads == 32                  # 30, a row padded to 32
    shapes = jax.eval_shape(lambda: LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(x.shape))           # noqa: E731
                             for x in jax.tree.leaves(tree))
    h, F, V = 3840, 11008, 100352
    mlp = 3 * h * F + 2 * h
    linear = h * (2880 + 2880 + 5760 + 5760 + 5760) + 2 * h * 30 \
        + 4 * 11520 + 30 + 30 + 192
    full = 4 * h * h + 2 * h
    assert count(shapes["layer_0"]) == linear + mlp       # 215.5M
    assert count(shapes["layer_3"]) == full + mlp         # 185.8M
    assert count(shapes) == 24 * (linear + mlp) + 8 * (full + mlp) \
        + 2 * h * V + h
    # the cut file: the first 16 entries, four whole periods
    cut = LlamaConfig.from_hf({**published, **{
        k: benchmark_config[k] for k in benchmark_config["reduced"]}})
    assert cut.layer_kinds == tuple(PERIOD * 4)
    entry = init_cache(cut, 32, 1536)
    assert entry[0]["state"].shape == (32, 15, 96, 384)
    assert entry[0]["conv"].shape == (32, 3, 11520)
    assert entry[3]["k"].shape == (32, 1536, 32, 128)
    with pytest.raises(ValueError, match="layer_types names 32 layers"):
        LlamaConfig.from_hf({**published, "num_hidden_layers": 16})


# -- the paged kernel at 30 K/V heads -----------------------------------------------

@pytest.mark.pallas
def test_paged_kernel_reads_30_heads_of_a_row_padded_to_32():
    B, T, H, D, tile = 2, 64, 30, 128, 16
    geo = paged_geometry(1536, 30, 30, 128, jnp.bfloat16)
    # 30 counted as rows of 32: a K tile of 32 positions is 256 KiB
    assert geo is not None and geo.tile == 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, 32, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, 32, D), jnp.float32)
    spans = jnp.asarray([37, 5], jnp.int32)
    out = paged_decode_attention(q, k, v, spans, tile=tile,
                                 interpret=True, kv_heads=30)
    s = jnp.einsum("bhd,bthd->bht", q, k[:, :, :30]) / np.sqrt(D)
    s = jnp.where(jnp.arange(T)[None, None] < spans[:, None, None], s, -1e30)
    want = jnp.einsum("bht,bthd->bhd", jax.nn.softmax(s, -1), v[:, :, :30])
    # float32 online softmax against one softmax: summation order only
    np.testing.assert_allclose(out, want, atol=2e-5)


# -- the kernels through the TPU compiler (no chip) -----------------------------------

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_both_kernels_compile_for_the_v5e_at_the_published_geometry(one_chip):
    # (such a compile is written to the persistent cache and cannot be read
    # back without the chip: a later run warns and compiles again)
    H, dk, dv, N = 30, 96, 192, 32
    pack = G.gdn_pack(H, dv)
    g, _, w = G.state_shape(H, dk, dv)

    def sd(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = G.gated_delta_decode.lower(
        sd((N, g, dk, w)), sd((N, H, dk)), sd((N, H, dk)), sd((N, H, dv)),
        sd((N, H)), sd((N, H)), sd((N,), jnp.bool_), pack=pack
    ).compile().as_text()
    assert "tpu_custom_call" in text and "gated_delta_decode" in text
    for T in (1024, 8):             # the largest bucket in use, the smallest
        text = G.gated_delta_prefill.lower(
            sd((g, dk, w)), sd((T, H, dk)), sd((T, H, dk)), sd((T, H, dv)),
            sd((T, H)), sd((T, H)), sd((), jnp.int32), pack=pack
        ).compile().as_text()
        assert "tpu_custom_call" in text and "gated_delta_prefill" in text


@pytest.mark.parametrize("name,n,max_len,heads,kv,row_heads,span", [
    ("mistral", 32, 2048, 32, 8, 8, 1),        # group 4
    ("olmo", 32, 1536, 30, 30, 32, 1),         # group 1, rows padded to 32
    ("verify8", 32, 2048, 32, 8, 8, 8),        # the widest verify step
])
def test_paged_kernel_compiles_for_the_v5e_with_no_copy_of_the_cache(
        one_chip, name, n, max_len, heads, kv, row_heads, span):
    """At the cells' geometries the tile the gate picks fits the chip's
    VMEM (the compiler refuses what does not), and the flat-row view of
    the cache reaches the kernel as a bitcast: a copy of cache size
    would be 0.3-0.8 GB of temporaries a layer and step."""
    geo = paged_geometry(max_len, heads, kv, 128, jnp.bfloat16,
                         max_query_span=span)
    assert geo is not None

    def sd(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = sd((n, heads, 128) if span == 1 else (n, span, heads, 128))
    cache = sd((n, max_len, row_heads, 128))
    compiled = paged_decode_attention.lower(
        q, cache, cache, sd((n,), jnp.int32), tile=geo.tile,
        kv_heads=kv).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert f"bf16[{n},{max_len * row_heads},128]" in text     # flat rows ...
    assert not re.search(                                       # ... by bitcast
        rf"bf16\[{n},{max_len * row_heads},128\]\S* (copy|fusion)\(", text)


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
def test_paged_kernel_compiles_for_the_v5e_at_128_heads_over_8(one_chip,
                                                              window):
    """Command A+'s geometry: 24 slots of 5,632 positions, 128 query heads
    in groups of 16 over a row of 8 K/V heads, with and without the window's
    walk; the cache still reaches the kernel as a bitcast."""
    n, max_len, heads, kv = 24, 5632, 128, 8
    geo = paged_geometry(max_len, heads, kv, 128, jnp.bfloat16)
    assert geo is not None and geo.tile == 128

    def sd(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cache = sd((n, max_len, kv, 128))
    compiled = paged_decode_attention.lower(
        sd((n, heads, 128)), cache, cache, sd((n,), jnp.int32),
        tile=geo.tile, kv_heads=kv, window=window).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert not re.search(
        rf"bf16\[{n},{max_len * kv},128\]\S* (copy|fusion)\(", text)


@pytest.mark.parametrize("kind,rows,kv,most", [
    ("full", 16384, 4, None),       # rows by position, two heads a row
    ("ring", 256, 8, 128),          # a window layer's ring, sinks
])
def test_packed_paged_kernel_compiles_for_the_v5e_at_192_beside_128(
        one_chip, kind, rows, kv, most):
    """MiMo-V2.5's geometries: 24 slots, 64 query heads over 4 and 8 K/V
    heads, keys 192 wide beside values 128 wide, packed two heads a row (384
    and 256 lanes, no padding); the window layers' ring with the sink as one
    more operand.  The packed cache IS the kernel's view: nothing of cache
    size is copied or relaid."""
    n, heads, dk, dv = 24, 64, 192, 128
    geo = paged_geometry(rows, heads, kv, dk, jnp.bfloat16, d_value=dv,
                         pack=2, most=most)
    assert geo is not None and geo.tile == (128 if kind == "full" else 64)

    def sd(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    flat = rows * kv // 2
    extra = {} if kind == "full" else {
        "window": 128, "ring": True, "sink": sd((heads,), jnp.float32)}
    compiled = paged_decode_attention.lower(
        sd((n, heads, dk)), sd((n, flat, 2 * dk)), sd((n, flat, 2 * dv)),
        sd((n,), jnp.int32), tile=geo.tile, kv_heads=kv, pack=2,
        **extra).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert not re.search(
        rf"bf16\[{n},{flat},(384|256)\]\S* (copy|fusion)\(", text)


@pytest.mark.parametrize("name,S,T,H,KV,D,Dv,window,kind", [
    ("mistral-bucket-2048", 2048, 2048, 32, 8, 128, 128, None, ""),
    ("olmo-bucket-1536-rows-of-32", 1536, 1536, 30, 30, 128, 128, None, ""),
    ("command-a-window-tail-1024", 1024, 5632, 128, 8, 128, 128, 4096, ""),
    ("mimo-full-16384", 16384, 16384, 64, 4, 192, 128, None, "sink"),
    ("mimo-ring-16384", 16384, 16640, 64, 8, 192, 128, 128, "sink,offset"),
])
def test_prefill_kernel_compiles_for_the_v5e_at_the_published_shapes(
        one_chip, name, S, T, H, KV, D, Dv, window, kind):
    """The prefill attention kernel at the tile ``prefill_geometry`` picks
    for each serving configuration's largest bucket: group 4, group 1 over a
    cache row padded to 32 heads, 128 heads over 8 behind a window, and
    MiMo's two kinds (keys 192 wide padded to 256 lanes, a sink, a ring's
    rows before the pass).  No score array ever exists outside the kernel."""
    from synapseml_tpu.models.llm.pallas_attn import (prefill_attention,
                                                      prefill_geometry)
    geo = prefill_geometry(S, T, H, KV, D, Dv, jnp.bfloat16, window)
    assert geo is not None

    def sd(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    rows = 32 if KV == 30 else KV
    extra = {}
    if "sink" in kind:
        extra["sink"] = sd((H,), jnp.float32)
    if "offset" in kind:
        extra["key_offset"] = sd((), jnp.int32)
    compiled = prefill_attention.lower(
        sd((1, S, H, D)), sd((1, T, rows, D)), sd((1, T, rows, Dv)),
        sd((), jnp.int32), sd((), jnp.int32), bq=geo.bq, bk=geo.bk,
        kv_heads=KV, window=window, **extra).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "prefill_attention" in text
    # no (heads, S, T) float32 scores, whole or a key block wide
    assert not re.search(rf"f32\[[0-9,]*{S},({T}|{geo.bk})\]", text)


@pytest.mark.parametrize("pairs,tm", [(192, 16), (8192, 256)],
                         ids=["decode", "prefill-chunk"])
def test_expert_ffn_compiles_for_the_v5e_at_the_published_widths(
        one_chip, pairs, tm):
    """The expert layer's grouped product at 16 experts of 4,096 x 4,096:
    a decode step's 192 pairs in tiles of 16 rows, a prefill chunk's 8,192
    in tiles of 256; both its forms (gate and up in one, then down), the
    weights left where they are (no copy of 0.5 GB)."""
    from synapseml_tpu.models.llm import experts as X
    assert X._row_tile(pairs, 16) == tm
    tiles = -(-pairs // tm) + 16

    def sd(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    x, w = sd((tiles * tm, 4096)), sd((16, 4096, 4096))
    te, na = sd((tiles,), jnp.int32), sd((1,), jnp.int32)
    for args, kw in (((x, te, na, w, w), {}),
                     ((x, te, na, w), {"out_dtype": jnp.float32})):
        compiled = X.expert_ffn.lower(*args, tm=tm, **kw).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text and "expert_ffn" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("refine_k", [8, 0], ids=["wave", "root"])
def test_fused_histogram_pass_compiles_for_the_v5e_with_no_copy_of_the_bins(
        one_chip, refine_k):
    """At the boosting cell's shapes (12,001,280 x 28, 256 bins, shift 3,
    8 refined features, 16 slots) the tile `fused_geometry` picks fits the
    chip's VMEM (the compiler refuses what does not), and with one feature
    group the binned matrix reaches the kernel as a bitcast: the (4, 7, N)
    layout of the full-resolution pass is a 1.5 GB copy a tree."""
    from synapseml_tpu.models.gbdt import pallas_hist as ph
    N, F, B, S = 12_001_280, 28, 256, 16
    assert ph.fused_geometry(F, B, S, hist_shift=3,
                             refine_k=refine_k) == (28, 2048)

    def sd(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = ph.route_and_hist_pallas.lower(
        sd((F, N)), sd((N,)), sd((S,)), sd((S, N)), *[sd((S,))] * 6,
        sd((32, N), jnp.int8), sd((2,), jnp.float32), n_slots=S,
        total_bins=B, hist_shift=3,
        sel_k=sd((refine_k, N)) if refine_k else None).compile().as_text()
    assert "tpu_custom_call" in text and "route_and_hist_pallas" in text
    assert re.search(rf"s32\[1,{F},{N}\]\S* bitcast\(", text)
    assert not re.search(rf"s32\[\d+,\d+,{N}\]\S* (copy|fusion)\(", text)


def test_split_columns_are_read_by_index_on_the_v5e(one_chip):
    """At the boosting cell's shapes a wave's fused pass with its 16 split
    columns named by index, and the last wave's routing pass, compile for
    the chip with the binned matrix reaching each as a bitcast: no
    (16, N) matrix of split columns and no copy of the bins."""
    from synapseml_tpu.models.gbdt import pallas_hist as ph
    N, F, B, S = 12_001_280, 28, 256, 16

    def sd(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fused = ph.route_and_hist_pallas.lower(
        sd((F, N)), sd((N,)), sd((S,)), sd((S,)), *[sd((S,))] * 6,
        sd((32, N), jnp.int8), sd((2,), jnp.float32), n_slots=S,
        total_bins=B, hist_shift=3, sel_k=sd((8, N))).compile().as_text()
    route = ph.route_rows_pallas.lower(
        sd((F, N)), sd((N,)), *[sd((S,))] * 8).compile().as_text()
    for text, name in ((fused, "route_and_hist_pallas"),
                       (route, "route_rows_pallas")):
        assert "tpu_custom_call" in text and name in text
        assert re.search(rf"s32\[1,{F},{N}\]\S* bitcast\(", text)
        assert f"s32[{S},{N}]" not in text
        assert not re.search(rf"s32\[\d+,\d+,{N}\]\S* (copy|fusion)\(", text)


def test_refined_features_are_read_by_index_on_the_v5e(one_chip):
    """At the boosting cell's shapes a wave's fused pass with its 8
    refined features named by index (the coarse block building the other
    20) and the root's fine build by index compile for the chip with the
    binned matrix reaching each as a bitcast: no (8, N) matrix of refined
    columns and no copy of the bins."""
    from synapseml_tpu.models.gbdt import pallas_hist as ph
    N, F, B, S, K = 12_001_280, 28, 256, 16, 8

    def sd(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fused = ph.route_and_hist_pallas.lower(
        sd((F, N)), sd((N,)), sd((S,)), sd((S,)), *[sd((S,))] * 6,
        sd((32, N), jnp.int8), sd((2,), jnp.float32), n_slots=S,
        total_bins=B, hist_shift=3, sel_k=sd((K,))).compile().as_text()
    root = ph.build_hist_nodes_pallas.lower(
        sd((F, N)), sd((N,)), sd((32, N), jnp.int8), sd((2,), jnp.float32),
        n_slots=1, total_bins=B, feats=sd((K,))).compile().as_text()
    for text, name in ((fused, "route_and_hist_pallas"),
                       (root, "build_hist_nodes_pallas")):
        assert "tpu_custom_call" in text and name in text
        assert re.search(rf"s32\[1,{F},{N}\]\S* bitcast\(", text)
        assert f"s32[{K},{N}]" not in text
        assert not re.search(rf"s32\[\d+,\d+,{N}\]\S* (copy|fusion)\(", text)


@pytest.mark.parametrize("F,B", [
    pytest.param(256, 64, id="eight-groups-of-32"),
    pytest.param(84, 256, id="twelve-groups-of-7"),
    pytest.param(1580, 16, id="79-groups-of-20"),
])
def test_wide_matrices_route_by_index_on_the_v5e(one_chip, F, B):
    """Where the binned matrix has many feature groups, both kernels still
    compile for the chip at the histogram geometry they had when the split
    columns arrived as a (16, N) copy: routing reads the chunk's whole
    block, or a tile of 8 rows a slot where that is fewer, and the fused
    pass asks for those blocks beside its budget."""
    from synapseml_tpu.models.gbdt import pallas_hist as ph
    N, S = 1_048_576, 16

    def sd(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fused = ph.route_and_hist_pallas.lower(
        sd((F, N)), sd((N,)), sd((S,)), sd((S,)), *[sd((S,))] * 6,
        sd((32, N), jnp.int8), sd((2,), jnp.float32), n_slots=S,
        total_bins=B).compile().as_text()
    route = ph.route_rows_pallas.lower(
        sd((F, N)), sd((N,)), *[sd((S,))] * 8).compile().as_text()
    for text, name in ((fused, "route_and_hist_pallas"),
                       (route, "route_rows_pallas")):
        assert "tpu_custom_call" in text and name in text
        assert f"s32[{S},{N}]" not in text
