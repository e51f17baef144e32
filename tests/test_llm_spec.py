"""Speculative decoding fused into the SlotEngine (ISSUE 12).

The contract under test:

- greedy decode through a SPECULATIVE engine (n-gram self-drafts +
  multi-token verify) is TOKEN-EXACT vs the dense fused-scan
  ``generate`` path — including mid-flight admission, prefix reuse
  feeding the drafter's tables, EOS landing mid-span, and slot
  retirement truncating a committed span at the token budget;
- the paged (``interpret``) backend's S>1 verify step commits the SAME
  tokens as the dense verify and leaves the K/V cache BITWISE identical
  (the kernel only reads; the slot_mask-gated scatter owns every
  write);
- the :class:`~synapseml_tpu.models.llm.drafter.NgramDrafter` proposes
  the latest earlier occurrence's continuation, never self-matches the
  context tail, wraps periodic blocks, and falls back to the shorter
  n-gram table;
- per-slot acceptance EWMA adaptation shrinks a slot's draft cap under
  garbage drafts and the engine's ``tokens_per_step_estimate`` feeds
  the serving loop's spec-aware SLO projection
  (remaining-tokens ÷ accepted-tokens-per-step);
- spec telemetry (accepted-span histogram, draft hit/miss counters)
  lands in the process registry under the engine label.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from synapseml_tpu.models.llm import (LlamaConfig, LlamaModel,
                                      NgramDrafter, SlotEngine, generate)

pytestmark = pytest.mark.spec


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(num_layers=2, max_len=96, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 8), jnp.int32))
    return cfg, model, variables


def _prompts(cfg, n, length, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, (n, length)).astype(np.int32)


# ---------------------------------------------------------------------------
# the drafter
# ---------------------------------------------------------------------------

class TestNgramDrafter:
    def _ctx(self, ids):
        ctx = np.zeros(64, np.int32)
        ctx[:len(ids)] = ids
        return ctx, len(ids)

    def test_latest_earlier_occurrence_wins(self):
        d = NgramDrafter(1, ngram=3)
        # (1,2,3) occurs at 0..2 (continues 9...) and 5..7 (continues
        # 7...); the tail is a third occurrence — the LATEST EARLIER
        # one is the draft source, so the proposal is [7, 1, 2]
        ctx, n = self._ctx([1, 2, 3, 9, 4, 1, 2, 3, 7, 1, 2, 3])
        d.begin(0, ctx, n)
        out = d.draft(0, ctx, n, 3)
        np.testing.assert_array_equal(out, [7, 1, 2])

    def test_tail_self_match_excluded(self):
        d = NgramDrafter(1, ngram=3, min_ngram=3)
        ctx, n = self._ctx([5, 6, 7, 8, 9, 10])   # every 3-gram unique
        d.begin(0, ctx, n)
        assert len(d.draft(0, ctx, n, 4)) == 0    # tail only matches itself

    def test_periodic_wraparound_extrapolates(self):
        d = NgramDrafter(1, ngram=3)
        ctx, n = self._ctx([9, 4, 8, 4, 8, 4, 8])     # period-2 tail
        d.begin(0, ctx, n)
        out = d.draft(0, ctx, n, 6)
        # latest earlier (8,4,8) ends 2 back — the block wraps: 4 8 4 8...
        np.testing.assert_array_equal(out, [4, 8, 4, 8, 4, 8])

    def test_extend_registers_new_tokens(self):
        d = NgramDrafter(1, ngram=2)
        ctx, n = self._ctx([1, 2, 3, 4])
        d.begin(0, ctx, n)
        ctx[4:8] = [1, 2, 9, 1]
        d.extend(0, ctx, 4, 8)
        # tail (9, 1) has no earlier occurrence; tail (2, 9)→... check
        # a tail of (1, 2): latest earlier occurrence at 4..5 → next is 9
        ctx[8:10] = [1, 2]
        d.extend(0, ctx, 8, 10)
        out = d.draft(0, ctx, 10, 1)
        np.testing.assert_array_equal(out, [9])

    def test_fallback_to_shorter_ngram(self):
        d = NgramDrafter(1, ngram=3, min_ngram=2)
        #                  0  1  2  3  4  5
        ctx, n = self._ctx([7, 5, 6, 8, 5, 6])
        d.begin(0, ctx, n)
        # 3-gram (8,5,6) never occurred before; 2-gram (5,6) did at 1..2
        out = d.draft(0, ctx, n, 1)
        np.testing.assert_array_equal(out, [8])

    def test_begin_clears_previous_occupant(self):
        d = NgramDrafter(1, ngram=2)
        ctx, n = self._ctx([1, 2, 3, 1, 2])
        d.begin(0, ctx, n)
        assert len(d.draft(0, ctx, n, 2)) > 0
        ctx2, n2 = self._ctx([5, 6, 7, 8, 9])
        d.begin(0, ctx2, n2)
        # the old occupant's (1, 2) -> 3 mapping must be gone
        ctx3, n3 = self._ctx([5, 1, 2, 9, 1, 2])
        d.begin(0, ctx3, n3)
        out = d.draft(0, ctx3, n3, 1)
        np.testing.assert_array_equal(out, [9])


# ---------------------------------------------------------------------------
# token exactness: spec + continuous batching vs dense greedy
# ---------------------------------------------------------------------------

def _repeated_and_random(cfg):
    """Two rows of a 5-token block said twice (the drafter has a match
    from the first step) around one random row."""
    rng = np.random.default_rng(3)
    base = rng.integers(1, cfg.vocab_size, 5)
    ids = np.concatenate([base, base])[None, :].repeat(3, 0)
    ids[1] = rng.integers(1, cfg.vocab_size, 10)
    return ids.astype(np.int32)


class TestSpecExactness:
    @pytest.mark.parametrize("prompts,draft_len", [
        ("random", 7), ("repeated", 3), ("repeated", 7)])
    def test_spec_greedy_token_exact_vs_dense(self, tiny_model, prompts,
                                              draft_len):
        """The headline pin: a speculative engine's greedy output is
        token-identical to the dense fused-scan path — acceptance only
        ever commits the model's own argmax tokens — on random and on
        repetitive prompts, at several draft lengths."""
        cfg, model, variables = tiny_model
        ids = (_prompts(cfg, 3, 9) if prompts == "random"
               else _repeated_and_random(cfg))
        ref = generate(model, variables, ids, max_new_tokens=20)
        eng = SlotEngine(model, variables, n_slots=4, max_len=96,
                         spec_draft_len=draft_len)
        slots = {i: eng.admit(ids[i], 20).slot for i in range(3)}
        out = eng.run_to_completion()
        for i in range(3):
            np.testing.assert_array_equal(out[slots[i]], ref[i])
        # the workload actually speculated (cyclic greedy text drafts
        # well) — without this the pin could pass on plain steps alone
        assert eng.spec_steps > 0 and eng.spec_accepted > 0

    def test_mid_flight_admission_spec_exact(self, tiny_model):
        """A sequence admitted while a neighbor is mid-span decodes
        token-exact — heterogeneous accepted spans in one jitted
        verify step."""
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 2, 9, seed=1)
        ref_a = generate(model, variables, ids[0:1], max_new_tokens=18)[0]
        ref_b = generate(model, variables, ids[1:2], max_new_tokens=8)[0]
        eng = SlotEngine(model, variables, n_slots=4, max_len=96,
                         spec_draft_len=7)
        ra = eng.admit(ids[0], 18)
        for _ in range(3):
            eng.step()
        rb = eng.admit(ids[1], 8)          # admitted mid-flight
        assert eng.active_count == 2
        while eng.active.any():
            eng.step()
        np.testing.assert_array_equal(eng.generated_ids(ra.slot), ref_a)
        np.testing.assert_array_equal(eng.generated_ids(rb.slot), ref_b)

    @pytest.mark.parametrize("n,seed,max_new,eos_at", [
        (1, 7, 24, 12), (2, 5, 10, 3)])
    def test_eos_mid_span_truncates_exact(self, tiny_model, n, seed,
                                          max_new, eos_at):
        """EOS landing INSIDE an accepted span retires the slot at the
        eos token — where ``generate(eos_id=...)`` stops and starts
        padding, row by row."""
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, n, 8, seed=seed)
        probe = generate(model, variables, ids, max_new_tokens=max_new)
        # an eos that occurs mid-stream in row 0 (the greedy text is
        # cyclic, so any repeated token works)
        eos = int(probe[0, eos_at])
        ref = generate(model, variables, ids, max_new_tokens=max_new,
                       eos_id=eos, pad_id=0)
        eng = SlotEngine(model, variables, n_slots=2, max_len=96,
                         spec_draft_len=7, eos_id=eos)
        slots = [eng.admit(ids[i], max_new).slot for i in range(n)]
        while eng.active.any():
            eng.step()
        for i, slot in enumerate(slots):
            hits = np.flatnonzero(probe[i] == eos)
            stop = int(hits[0]) + 1 if len(hits) else max_new
            got = eng.generated_ids(slot)
            np.testing.assert_array_equal(got, ref[i, :stop])
            assert (ref[i, stop:] == 0).all()      # generate pads from there
        assert eng.generated_ids(slots[0])[-1] == eos

    def test_budget_truncates_committed_span(self, tiny_model):
        """Slot retirement mid-span: a token budget SMALLER than the
        accepted span commits exactly the budget, token-exact vs
        dense."""
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 1, 10, seed=3)
        ref = generate(model, variables, ids, max_new_tokens=3)[0]
        eng = SlotEngine(model, variables, n_slots=2, max_len=96,
                         spec_draft_len=7)
        res = eng.admit(ids[0], 3)
        while eng.active.any():
            eng.step()
        got = eng.generated_ids(res.slot)
        assert len(got) == 3
        np.testing.assert_array_equal(got, ref)

    def test_prefix_reuse_feeds_ngram_table(self, tiny_model):
        """An admission served from a REUSED prefix builds its draft
        tables from the full prompt ids (reuse skips prefill work, not
        table work) and still decodes token-exact."""
        cfg, model, variables = tiny_model
        rng = np.random.default_rng(11)
        shared = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)
        tail_a = rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
        tail_b = rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
        p1 = np.concatenate([shared, tail_a])
        p2 = np.concatenate([shared, tail_b])
        ref = generate(model, variables, p2[None, :], max_new_tokens=16)[0]
        eng = SlotEngine(model, variables, n_slots=3, max_len=96,
                         spec_draft_len=7, min_prefix=8)
        eng.admit(p1, 4)
        while eng.active.any():
            eng.step()
        res = eng.admit(p2, 16)
        assert res.reused_tokens >= 8       # the copy path actually ran
        while eng.active.any():
            eng.step()
        np.testing.assert_array_equal(eng.generated_ids(res.slot), ref)
        assert eng.spec_draft_hits > 0      # the table drafted post-reuse

    def test_spec_off_engine_unchanged(self, tiny_model):
        """spec_draft_len=0 (the default) never builds a drafter and
        never runs a verify step — the pre-spec engine exactly."""
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=64)
        assert eng._drafter is None
        ids = _prompts(cfg, 1, 8, seed=5)
        res = eng.admit(ids[0], 6)
        while eng.active.any():
            eng.step()
        assert eng.spec_steps == 0
        assert eng.steps_run > 0
        ref = generate(model, variables, ids, max_new_tokens=6)[0]
        np.testing.assert_array_equal(eng.generated_ids(res.slot), ref)

    def test_spec_requires_greedy(self, tiny_model):
        cfg, model, variables = tiny_model
        with pytest.raises(ValueError, match="greedy"):
            SlotEngine(model, variables, n_slots=2, max_len=64,
                       spec_draft_len=7, temperature=0.8)


# ---------------------------------------------------------------------------
# paged (interpret) backend verify step
# ---------------------------------------------------------------------------

class TestPagedVerify:
    def test_interpret_verify_matches_dense(self, tiny_model):
        """The paged kernel's S>1 verify step commits the SAME tokens
        as the dense verify, step for step.  Layer 0's K/V is BITWISE
        identical between backends (its inputs — embeddings + rope —
        never pass through an attention read, and the slot_mask-gated
        scatter is the same program both sides); deeper layers' K/V
        matches to ulp tolerance (their inputs ride the previous
        layers' attention outputs, where kernel-vs-dense reduction
        order differs by design)."""
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 3, 9, seed=2)

        def run(backend):
            eng = SlotEngine(model, variables, n_slots=4, max_len=96,
                             spec_draft_len=7, attention_backend=backend)
            slots = {i: eng.admit(ids[i], 14).slot for i in range(3)}
            while eng.active.any():
                eng.step()
            return eng, slots

        dense, dslots = run("dense")
        paged, pslots = run("interpret")
        assert paged.attention_backend == "interpret"
        assert dslots == pslots
        for i in range(3):
            np.testing.assert_array_equal(
                paged.generated_ids(pslots[i]),
                dense.generated_ids(dslots[i]))
        assert paged.spec_steps == dense.spec_steps > 0
        for key in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(dense.cache[0][key]),
                np.asarray(paged.cache[0][key]))
        for layer_d, layer_p in zip(dense.cache[1:], paged.cache[1:]):
            for key in ("k", "v"):
                np.testing.assert_allclose(
                    np.asarray(layer_d[key]), np.asarray(layer_p[key]),
                    rtol=1e-4, atol=1e-5)

    def test_kernel_s_gt1_parity_vs_reference(self):
        """Direct kernel check: S>1 queries with per-query causal
        limits inside the live span match a per-query dense softmax
        reference to f32 ulp tolerance, across span placements."""
        from synapseml_tpu.models.llm import paged_decode_attention

        rng = np.random.default_rng(0)
        B, S, H, KV, D, T, tile = 4, 4, 8, 4, 32, 64, 16
        q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, T, KV, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, T, KV, D)), jnp.float32)
        spans = jnp.asarray([4, 17, 33, 64], jnp.int32)
        got = np.asarray(paged_decode_attention(
            q, k, v, spans, tile=tile, interpret=True))
        group = H // KV
        for b in range(B):
            for j in range(S):
                lim = int(spans[b]) - (S - 1) + j
                for h in range(H):
                    kk = np.asarray(k[b, :lim, h // group], np.float32)
                    vv = np.asarray(v[b, :lim, h // group], np.float32)
                    logits = (np.asarray(q[b, j, h], np.float32) @ kk.T
                              / np.sqrt(D))
                    p = np.exp(logits - logits.max())
                    ref = (p / p.sum()) @ vv
                    np.testing.assert_allclose(got[b, j, h], ref,
                                               rtol=2e-5, atol=2e-5)

    def test_byte_ledger_prices_verify_span(self, tiny_model):
        """A verify step's DMA ledger prices ``lengths + S - 1`` spans
        — the keys the kernel's clamped grid actually reads."""
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=96,
                         spec_draft_len=7, attention_backend="interpret")
        ids = _prompts(cfg, 1, 9, seed=4)
        eng.admit(ids[0], 20)
        before = eng.decode_attn_bytes
        while eng.spec_steps == 0 and eng.active.any():
            eng.step()
        assert eng.decode_attn_bytes > before


# ---------------------------------------------------------------------------
# adaptation + serving-loop integration
# ---------------------------------------------------------------------------

class _BadDrafter:
    """Adversarial drafter: always proposes tokens the model will
    reject (vocab_size-1 repeated — greedy text here never emits it)."""

    def __init__(self, tok):
        self.tok = tok

    def begin(self, slot, ids, length):
        pass

    def extend(self, slot, ids, start, end):
        pass

    def forget(self, slot):
        pass

    def draft(self, slot, ids, length, max_draft):
        return np.full(max_draft, self.tok, np.int32)


class TestAdaptation:
    def test_acceptance_ewma_shrinks_draft_cap(self, tiny_model):
        """Garbage drafts drive a slot's acceptance EWMA down and its
        draft cap to 1 — the engine stops paying for wide verifies but
        keeps probing."""
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=96,
                         spec_draft_len=7)
        eng._drafter = _BadDrafter(cfg.vocab_size - 1)
        ids = _prompts(cfg, 1, 8, seed=9)
        ref = generate(model, variables, ids, max_new_tokens=20)[0]
        res = eng.admit(ids[0], 20)
        while eng.active.any():
            eng.step()
        # output exactness survives adversarial drafting...
        np.testing.assert_array_equal(eng.generated_ids(res.slot), ref)
        # ...and the cap collapsed to the 1-token probe
        assert eng._spec_k[res.slot] == 1
        assert eng._spec_ewma[res.slot] < 0.2
        assert eng.spec_acceptance_rate < 0.2

    def test_tokens_per_step_estimate_tracks_spec(self, tiny_model):
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=96,
                         spec_draft_len=7)
        assert eng.tokens_per_step_estimate() == 1.0   # before any step
        ids = _prompts(cfg, 1, 9, seed=6)
        eng.admit(ids[0], 30)
        while eng.active.any():
            eng.step()
        assert eng.tokens_per_step_estimate() > 1.2

    def test_slo_projection_divides_by_tokens_per_step(self):
        """The _DecodeLoop TTFT projection uses remaining-tokens ÷
        accepted-tokens-per-step: a 4x speculative engine projects a
        4x sooner slot release (no jax, pure duck-typing)."""
        from synapseml_tpu.serving.server import _DecodeLoop, _DecodeSeq

        class FakeReq:
            enqueued_at = time.monotonic()
            id = "r1"

        def fake_engine(tps):
            class E:
                n_slots = 4
                free_slot_count = 0
                active_count = 4

                def min_remaining_tokens(self):
                    return 40

                def tokens_per_step_estimate(self):
                    return tps
            return E()

        def project(engine):
            loop = _DecodeLoop.__new__(_DecodeLoop)
            loop.engine = engine
            loop._step_ewma = 0.01
            loop._retired_window = []
            return loop._projected_ttft(
                _DecodeSeq(FakeReq(), [1], 8, False), 0)

        plain = project(fake_engine(1.0))
        spec = project(fake_engine(4.0))
        assert spec < plain
        # waited ~0; plain ~ 40*0.01, spec ~ 10*0.01
        assert plain == pytest.approx(0.4, abs=0.05)
        assert spec == pytest.approx(0.1, abs=0.05)

    def test_reset_clears_drafter_state(self, tiny_model):
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=96,
                         spec_draft_len=7)
        ids = _prompts(cfg, 1, 8, seed=8)
        eng.admit(ids[0], 10)
        for _ in range(3):
            eng.step()
        eng._spec_ewma[:] = 0.0
        eng._spec_k[:] = 7
        eng.reset()
        assert not eng.active.any()
        assert (eng._spec_ewma == 1.0).all()
        assert (eng._spec_k == eng._spec_k0).all()


# ---------------------------------------------------------------------------
# telemetry + honest acceptance accounting
# ---------------------------------------------------------------------------

def test_spec_telemetry_exported(tiny_model):
    """The accepted-span histogram and draft hit/miss counters land in
    the process registry under the engine label."""
    from synapseml_tpu.telemetry import get_registry

    cfg, model, variables = tiny_model
    eng = SlotEngine(model, variables, n_slots=2, max_len=96,
                     spec_draft_len=7, name="spec-telemetry-probe")
    ids = _prompts(cfg, 1, 9, seed=12)
    eng.admit(ids[0], 24)
    while eng.active.any():
        eng.step()
    assert eng.spec_steps > 0
    reg = get_registry()
    stats = reg.get("llm_spec_accepted_span_size").stats(
        engine="spec-telemetry-probe")
    assert stats["count"] > 0
    hits = reg.get("llm_spec_draft_hit_total").value(
        engine="spec-telemetry-probe")
    misses = reg.get("llm_spec_draft_miss_total").value(
        engine="spec-telemetry-probe")
    assert hits == eng.spec_draft_hits > 0
    assert misses == eng.spec_draft_misses


def test_spec_acceptance_counts_real_drafts(tiny_model):
    """Acceptance divides by REAL drafted positions: a step on which
    the drafter had no match drafts nothing and dilutes nothing, and an
    accepted prefix can be no longer than its draft.  Tallied apart
    from the engine's counters, in the per-slot totals that
    ``trace_sink`` hands over at a slot's retirement."""
    cfg, model, variables = tiny_model
    rng = np.random.default_rng(0)
    base = rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
    prompt = np.concatenate([base] * 4)[None, :]
    ref = generate(model, variables, prompt, max_new_tokens=20)
    seen = []
    eng = SlotEngine(model, variables, n_slots=2, max_len=96,
                     spec_draft_len=7,
                     trace_sink=lambda slot, event, **a: seen.append(
                         (event, a)))
    res = eng.admit(prompt[0], 20)
    while eng.active.any():
        eng.step()
    np.testing.assert_array_equal(eng.generated_ids(res.slot), ref[0])
    assert [event for event, _ in seen] == ["decode", "retired"]
    total = seen[-1][1]
    assert total["verify_steps"] == eng.spec_steps > 0
    assert 0 <= total["accepted"] <= total["drafted"] \
        <= 7 * total["verify_steps"]
    assert eng.spec_drafted == total["drafted"]
    assert eng.spec_accepted == total["accepted"]
    assert total["steps"] == eng.steps_run and total["tokens"] == 20
    assert 0.0 <= eng.spec_acceptance_rate <= 1.0
    assert eng.spec_acceptance_rate == pytest.approx(
        eng.spec_accepted / eng.spec_drafted)
    # accepted tokens cannot exceed committed ones (but for a last
    # span the budget cut); every other token is the prefill's or a
    # step's own
    assert eng.spec_accepted <= 20 + 7
    assert 20 <= 1 + eng.steps_run + eng.spec_accepted
