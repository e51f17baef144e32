"""Pallas paged decode attention: interpret-mode correctness pins.

The contract under test (ISSUE 11 acceptance criteria):

- the kernel's online-softmax output is ulp-close to the dense masked-
  softmax math across spans and tiles — including span 1, the full
  ``max_len`` row, and the PR-8 repro shape (58 live tokens in a
  64-row cache);
- greedy decode through :class:`SlotEngine` with
  ``attention_backend='interpret'`` is TOKEN-EXACT vs the dense path,
  including mid-flight admission, prefix reuse, and spans that grow
  across tile and bucket boundaries;
- a retired slot's K/V survives a paged decode step BIT-identically
  (the kernel only reads; the ``slot_mask`` write gate still owns the
  scatter);
- ``resolve_attention_backend`` fails fast off-TPU for ``'paged'`` with
  an actionable message, and ``'auto'`` falls back to dense;
- the byte ledger (:func:`paged_read_bytes` / :func:`dense_read_bytes`)
  prices the paged read at ``sum(ceil(span/tile)*tile)`` tokens of K+V
  instead of ``n_slots * max_len``.

Everything here runs the kernel through the Pallas INTERPRETER on CPU
(the ``pallas_hist`` honesty pattern — speed is measured where the
hardware is); TPU-compiled coverage rides the same entry points when a
chip is present.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from synapseml_tpu.models.llm import (LlamaConfig, LlamaModel, SlotEngine,
                                      dense_read_bytes, generate,
                                      paged_decode_attention,
                                      paged_geometry, paged_read_bytes,
                                      resolve_attention_backend)
from synapseml_tpu.models.llm import pallas_attn

pytestmark = pytest.mark.pallas


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


def _dense_cache_dtype(q, k, v, spans):
    """The dense path's own arithmetic in the cache's dtype
    (``model.py`` ``CausalAttention``): operands as stored, float32
    logits and softmax, probabilities cast before the PV product.
    q (B, S, H, D); query j of slot b sits at ``spans[b]-S+j``."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) / np.sqrt(D)
    qpos = spans[:, None] - S + jnp.arange(S)[None, :]
    causal = jnp.arange(T)[None, None, :] <= qpos[:, :, None]    # (B, S, T)
    logits = jnp.where(causal[:, None, None], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v).reshape(B, S, H, D)


def _dense_reference(q, k, v, spans):
    """The same at S=1: full-row masked softmax; q (B, H, D)."""
    return _dense_cache_dtype(q[:, None], k, v, spans)[:, 0]


class TestKernelParity:
    """Direct kernel-vs-dense logits parity across spans and tiles."""

    B, T, KV, GROUP, D = 5, 96, 4, 2, 32

    def _operands(self, seed=0, T=None):
        rng = np.random.default_rng(seed)
        T = T or self.T
        H = self.KV * self.GROUP
        q = jnp.asarray(rng.normal(size=(self.B, H, self.D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(self.B, T, self.KV, self.D)),
                        jnp.float32)
        v = jnp.asarray(rng.normal(size=(self.B, T, self.KV, self.D)),
                        jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("spans", [
        [1, 1, 1, 1, 1],              # single-token spans
        [96, 96, 96, 96, 96],         # the full max_len row
        [1, 33, 96, 58, 7],           # ragged, tile-misaligned
        [32, 64, 96, 31, 65],         # exact tile boundaries +/- 1
    ])
    @pytest.mark.parametrize("tile", [32, 96])
    def test_matches_dense_softmax(self, spans, tile):
        q, k, v = self._operands()
        sp = jnp.asarray(spans, jnp.int32)
        ref = _dense_reference(q, k, v, sp)
        assert self.T % tile == 0
        out = paged_decode_attention(q, k, v, sp, tile=tile, interpret=True)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_one_program_serves_long_and_short_batches(self):
        """No span bucket: the same call covers a batch that runs to the
        last of twelve tiles and one whose every slot ends in its first
        (the ring's cursor crosses a slot boundary at every tile)."""
        q, k, v = self._operands(seed=1)
        for spans_np in ([5, 17, 40, 63, 96], [5, 3, 8, 1, 7]):
            sp = jnp.asarray(spans_np, jnp.int32)
            out = paged_decode_attention(q, k, v, sp, tile=8, interpret=True)
            np.testing.assert_allclose(out, _dense_reference(q, k, v, sp),
                                       rtol=1e-5, atol=1e-6)

    def test_pr8_repro_shape_58_at_64(self):
        """58 live tokens in a 64-row cache — the shape that exposed
        the PR-8 prefix-clamp bug rides the paged read exactly."""
        q, k, v = self._operands(seed=2, T=64)
        sp = jnp.asarray([58, 64, 1, 58, 33], jnp.int32)
        ref = _dense_reference(q, k, v, sp)
        out = paged_decode_attention(q, k, v, sp, tile=32, interpret=True)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


class TestCacheDtypeParity:
    """bfloat16 operands go to the products as stored, as in the dense
    path; every key past a live span, and every padding head of a cache
    row, holds POISON (a key aligned with the queries, a value of 100:
    one of them let through moves the output by tens)."""

    T, TILE, D = 64, 16, 32

    @pytest.mark.parametrize("S", [1, 4])
    @pytest.mark.parametrize("heads,kv,row_heads", [
        (32, 8, 8),            # group 4 over 8 K/V heads (Mistral's)
        (30, 30, 32),          # group 1, rows of 30 heads padded to 32
    ])
    def test_matches_the_dense_paths_bfloat16(self, heads, kv, row_heads, S):
        rng = np.random.default_rng(heads + S)
        # spans that end inside a tile, on a tile edge, one past it, and
        # at max_len; the shortest a verify step may see
        spans = np.asarray([S, 21, 32, 33, 63, 64])
        B, T, D = len(spans), self.T, self.D
        q = rng.normal(size=(B, S, heads, D)).astype(np.float32)
        k = rng.normal(size=(B, T, row_heads, D)).astype(np.float32)
        v = rng.normal(size=(B, T, row_heads, D)).astype(np.float32)
        dead = (np.arange(T)[None, :] >= spans[:, None])[:, :, None, None]
        pad = (np.arange(row_heads) >= kv)[None, None, :, None]
        aligned = 8.0 * q.mean((1, 2))[:, None, None, :]      # (B, 1, 1, D)
        k = np.where(dead | pad, aligned, k)
        v = np.where(dead | pad, 100.0, v)
        qb, kb, vb = (jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))
        sp = jnp.asarray(spans, jnp.int32)
        out = paged_decode_attention(qb[:, 0] if S == 1 else qb, kb, vb, sp,
                                     tile=self.TILE, kv_heads=kv,
                                     interpret=True)
        assert out.dtype == jnp.bfloat16
        out = np.asarray(out.astype(jnp.float32)).reshape(B, S, heads, D)
        ref = np.asarray(_dense_cache_dtype(
            qb, kb[:, :, :kv], vb[:, :, :kv], sp).astype(jnp.float32))
        # two roundings of a bfloat16 output of size about 1 apart
        np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)


class TestModelDispatch:
    def test_decode_step_logits_match_dense(self, tiny_model):
        """One vector-cache_index decode step through LlamaModel: the
        paged backend's logits are ulp-close to the dense backend's on
        the identical cache state."""
        from synapseml_tpu.models.llm import init_cache
        cfg, model, variables = tiny_model
        rng = np.random.default_rng(3)
        n, T = 3, cfg.max_len
        # ONE batched prefill builds every slot's K/V; the ragged
        # lengths then declare how much of each row is LIVE — both
        # backends mask (dense) or skip (paged) everything beyond a
        # slot's span, so the junk tail is never attended either way
        lengths = np.asarray([1, 37, 90], np.int64)
        ids = rng.integers(1, cfg.vocab_size, (n, 90))
        cache = init_cache(cfg, n, T)
        _, cache = model.apply(variables, jnp.asarray(ids, jnp.int32),
                               positions=jnp.broadcast_to(
                                   jnp.arange(90)[None, :], (n, 90)),
                               cache=cache, cache_index=0)
        toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (n, 1)),
                           jnp.int32)
        positions = jnp.asarray(lengths, jnp.int32)[:, None]
        out = {}
        for backend in ("dense", "interpret"):
            out[backend], _ = model.apply(
                variables, toks, positions=positions,
                cache=jax.tree.map(lambda x: x, cache),
                cache_index=jnp.asarray(lengths, jnp.int32),
                slot_mask=jnp.ones(n, bool), attention_backend=backend)
        np.testing.assert_allclose(out["interpret"], out["dense"],
                                   rtol=1e-5, atol=1e-5)

    def test_prefill_path_stays_dense_bitwise(self, tiny_model):
        """The backend switch governs ONLY the vector-index decode
        step: a scalar-index prefill under 'interpret' is the dense
        program, bit for bit."""
        from synapseml_tpu.models.llm import init_cache
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 2, 9, seed=4)
        outs = {}
        for backend in ("dense", "interpret"):
            cache = init_cache(cfg, 2, cfg.max_len)
            logits, _ = model.apply(
                variables, jnp.asarray(ids),
                positions=jnp.arange(9)[None, :].repeat(2, 0),
                cache=cache, cache_index=0, attention_backend=backend)
            outs[backend] = np.asarray(logits)
        np.testing.assert_array_equal(outs["interpret"], outs["dense"])


class TestEngineExactness:
    def test_greedy_token_exact_vs_dense(self, tiny_model):
        """The headline pin: paged greedy decode through the SlotEngine
        is token-identical to the dense fused-scan generate path."""
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 3, 7)
        ref = generate(model, variables, ids, max_new_tokens=10)
        eng = SlotEngine(model, variables, n_slots=4, max_len=64,
                         attention_backend="interpret")
        assert eng.attention_backend == "interpret"
        slots = {i: eng.admit(ids[i], 10).slot for i in range(3)}
        out = eng.run_to_completion()
        for i in range(3):
            np.testing.assert_array_equal(out[slots[i]], ref[i])

    def test_mid_flight_admission_token_exact(self, tiny_model):
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 2, 9, seed=1)
        ref_a = generate(model, variables, ids[0:1], max_new_tokens=14)[0]
        ref_b = generate(model, variables, ids[1:2], max_new_tokens=6)[0]
        eng = SlotEngine(model, variables, n_slots=4, max_len=64,
                         attention_backend="interpret")
        ra = eng.admit(ids[0], 14)
        for _ in range(5):
            eng.step()
        rb = eng.admit(ids[1], 6)          # admitted mid-flight
        while eng.active.any():
            eng.step()
        np.testing.assert_array_equal(eng.generated_ids(ra.slot), ref_a)
        np.testing.assert_array_equal(eng.generated_ids(rb.slot), ref_b)

    def test_prefix_reuse_token_exact(self, tiny_model):
        """Prefix-cache reuse composes with the paged read: a warm
        admit (LCP K/V copy + tail prefill) decodes the same tokens as
        a cold DENSE engine."""
        cfg, model, variables = tiny_model
        rng = np.random.default_rng(2)
        prefix = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)
        p1 = np.concatenate([prefix, rng.integers(1, cfg.vocab_size,
                                                  6).astype(np.int32)])
        p2 = np.concatenate([prefix, rng.integers(1, cfg.vocab_size,
                                                  6).astype(np.int32)])
        warm = SlotEngine(model, variables, n_slots=4, max_len=64,
                          min_prefix=8, attention_backend="interpret")
        warm.admit(p1, 4)
        warm.run_to_completion()
        r_warm = warm.admit(p2, 4)
        assert r_warm.reused_tokens == 16
        cold = SlotEngine(model, variables, n_slots=4, max_len=64,
                          min_prefix=8, attention_backend="dense")
        r_cold = cold.admit(p2, 4)
        # prefill is the dense program under both backends, but the warm
        # tail runs in a smaller bucket than the cold prompt: differently
        # shaped XLA:CPU programs reassociate (2-3 ulp under jax 0.9.0;
        # see test_llm_serving._assert_logits_match_cold) — tokens exact
        np.testing.assert_allclose(
            r_warm.logits, r_cold.logits, rtol=0,
            atol=16 * np.spacing(np.float32(np.abs(r_cold.logits).max())))
        warm.run_to_completion()
        cold.run_to_completion()
        np.testing.assert_array_equal(warm.generated_ids(r_warm.slot),
                                      cold.generated_ids(r_cold.slot))

    def test_span_growth_across_tile_boundaries(self, tiny_model):
        """A sequence decoding from span 30 to span 70 crosses the
        32-token tile boundary twice (one live tile, two, three); every
        token stays exactly greedy."""
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 1, 30, seed=7)
        ref = generate(model, variables, ids, max_new_tokens=40)[0]
        eng = SlotEngine(model, variables, n_slots=2, max_len=96,
                         attention_backend="interpret")
        assert eng._paged_geo.tile == 32
        r = eng.admit(ids[0], 40)
        eng.run_to_completion()
        np.testing.assert_array_equal(eng.generated_ids(r.slot), ref)

    def test_full_max_len_span_token_exact(self, tiny_model):
        """The span runs the cache to the last row: ceil rounds the
        paged read up to the full cache and output stays exact."""
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 1, 43, seed=8)
        ref = generate(model, variables, ids, max_new_tokens=20)[0]
        eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                         attention_backend="interpret")
        r = eng.admit(ids[0], 20)        # 43 + 20 + 1 == max_len
        eng.run_to_completion()
        np.testing.assert_array_equal(eng.generated_ids(r.slot), ref)

    def test_retired_slot_kv_survives_paged_steps_bitwise(self, tiny_model):
        """Neighbor-corruption pin: a retired slot's K/V rows are
        BIT-identical after many paged decode steps of an active
        neighbor — the kernel reads spans, the slot_mask write gate
        still owns every store."""
        cfg, model, variables = tiny_model
        rng = np.random.default_rng(9)
        p1 = rng.integers(1, cfg.vocab_size, 14).astype(np.int32)
        eng = SlotEngine(model, variables, n_slots=3, max_len=64,
                         min_prefix=8, attention_backend="interpret")
        r1 = eng.admit(p1, 3)
        eng.run_to_completion()                     # slot r1 retired
        before = [(np.asarray(c["k"][r1.slot]).copy(),
                   np.asarray(c["v"][r1.slot]).copy())
                  for c in eng.cache]
        eng.admit(_prompts(cfg, 1, 8, seed=10)[0], 20)
        eng.run_to_completion()                     # 20 paged steps
        for c, (k0, v0) in zip(eng.cache, before):
            np.testing.assert_array_equal(np.asarray(c["k"][r1.slot]), k0)
            np.testing.assert_array_equal(np.asarray(c["v"][r1.slot]), v0)


class TestResolveAndGeometry:
    def test_auto_falls_back_to_dense_off_tpu(self):
        assert resolve_attention_backend(
            "auto", max_len=256, num_heads=8, num_kv_heads=4,
            d_head=32, dtype=jnp.float32) == "dense"

    def test_paged_off_tpu_fails_fast_actionably(self):
        with pytest.raises(ValueError) as ei:
            resolve_attention_backend(
                "paged", max_len=256, num_heads=8, num_kv_heads=4,
                d_head=32, dtype=jnp.float32)
        msg = str(ei.value)
        assert "cpu" in msg and "interpret" in msg and "auto" in msg

    def test_engine_paged_off_tpu_fails_at_construction(self, tiny_model):
        cfg, model, variables = tiny_model
        with pytest.raises(ValueError, match="interpret"):
            SlotEngine(model, variables, n_slots=2, max_len=64,
                       attention_backend="paged")

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="must be one of"):
            resolve_attention_backend(
                "flash", max_len=256, num_heads=8, num_kv_heads=4,
                d_head=32)

    def test_geometry_gate(self):
        geo = paged_geometry(8192, 32, 8, 128, jnp.bfloat16)
        assert geo is not None
        assert 8192 % geo.tile == 0 and geo.tile <= 4096
        assert geo.tile % 16 == 0                 # bf16 sublane
        # the estimate counts PADDED lanes: a (KV=8, D=64) bf16 K/V row
        # is fetched at 128 lanes, the same bytes as (KV=8, D=128)
        small = paged_geometry(1024, 32, 8, 64, jnp.bfloat16)
        padded = paged_geometry(1024, 32, 8, 128, jnp.bfloat16)
        assert small.tile == padded.tile
        assert small.vmem_bytes == padded.vmem_bytes \
            >= 2 * pallas_attn._RING * small.tile * 8 * 128 * 2
        # a max_len no sublane-aligned tile divides: no geometry, and
        # the explicit backends refuse while auto falls back
        assert paged_geometry(100, 8, 4, 32, jnp.float32) is None
        with pytest.raises(ValueError, match="no paged geometry"):
            resolve_attention_backend("interpret", max_len=100,
                                      num_heads=8, num_kv_heads=4,
                                      d_head=32, dtype=jnp.float32)
        assert resolve_attention_backend(
            "auto", max_len=100, num_heads=8, num_kv_heads=4,
            d_head=32, dtype=jnp.float32) == "dense"

    @pytest.mark.parametrize("name,max_len,heads,kv,span", [
        ("mistral", 2048, 32, 8, 1), ("olmo", 1536, 30, 30, 1),
        ("verify8", 2048, 32, 8, 8)])
    def test_geometry_estimate_covers_the_kernels_buffers(
            self, name, max_len, heads, kv, span):
        """The VMEM gate prices what the kernel allocates: the ring of
        K and V tiles, the query rows, the softmax state and one chunk
        of logits and probabilities, all at the verify step's width."""
        geo = paged_geometry(max_len, heads, kv, 128, jnp.bfloat16,
                             max_query_span=span)
        assert geo is not None and max_len % geo.tile == 0
        row_heads = 32 if kv == 30 else kv        # a row of whole tiles
        q_rows = span * -(-heads // 8) * 8
        ring = 2 * pallas_attn._RING * geo.tile * row_heads * 128 * 2
        state = q_rows * 128 * (2 + 4 + 4 + 4)    # queries, acc, m, l
        chunk = pallas_attn._chunk_rows(geo.tile, row_heads, q_rows)
        assert chunk % row_heads == 0 and (geo.tile * row_heads) % chunk == 0
        assert geo.vmem_bytes >= ring + state + q_rows * chunk * 8
        assert geo.vmem_bytes <= pallas_attn._VMEM_BUDGET
        # the tile the ladder picks moves the K tile at or under its aim
        assert geo.tile * row_heads * 128 * 2 <= pallas_attn._TILE_BYTES \
            or geo.tile == 16


class TestByteLedger:
    def test_paged_under_dense_and_exact_formula(self):
        spans = np.asarray([1, 33, 96, 58, 7])
        tile, KV, D, item, L = 32, 4, 128, 4, 2
        paged = paged_read_bytes(spans, tile, KV, D, item, L)
        dense = dense_read_bytes(5, 96, KV, D, item, L)
        expect = L * 2 * int(np.ceil(spans / tile).sum()) * tile \
            * KV * D * item
        assert paged == expect
        assert paged < dense
        # all-full spans round to exactly the dense read
        assert paged_read_bytes([96] * 5, tile, KV, D, item, L) == dense

    @pytest.mark.parametrize("D", [128, 64])
    def test_read_bytes_equal_the_copies_the_kernel_starts(self, monkeypatch,
                                                           D):
        """``paged_read_bytes`` against the kernel itself: every DMA it
        starts is counted as it runs (interpret mode), for a ragged
        batch with an inactive slot (span 1) and padded cache rows; a
        head of 64 lanes is fetched, and priced, at 128."""
        B, T, heads, kv, row_heads, tile = 5, 64, 6, 6, 8, 16
        spans = np.asarray([1, 17, 64, 16, 33])        # slot 0 inactive
        started = []
        real = pallas_attn.pltpu.make_async_copy

        def counting(src, dst, sem):
            copy = real(src, dst, sem)
            nbytes = int(np.prod(dst.shape)) * np.dtype(dst.dtype).itemsize
            start = copy.start

            def start_and_count(*a, **kw):
                jax.debug.callback(lambda: started.append(nbytes))
                return start(*a, **kw)
            copy.start = start_and_count
            return copy

        monkeypatch.setattr(pallas_attn.pltpu, "make_async_copy", counting)
        paged_decode_attention.clear_cache()
        try:
            rng = np.random.default_rng(0)
            q = jnp.asarray(rng.normal(size=(B, heads, D)), jnp.bfloat16)
            kv_rows = jnp.asarray(rng.normal(size=(B, T, row_heads, D)),
                                  jnp.bfloat16)
            jax.block_until_ready(paged_decode_attention(
                q, kv_rows, kv_rows, jnp.asarray(spans, jnp.int32),
                tile=tile, kv_heads=kv, interpret=True))
            jax.effects_barrier()
        finally:
            paged_decode_attention.clear_cache()
        tiles = int(np.ceil(spans / tile).sum())
        assert tiles == pallas_attn.paged_live_tiles(spans, tile) == 11
        assert len(started) == 2 * tiles                # one of K, one of V
        assert sum(started) == paged_read_bytes(spans, tile, row_heads, D, 2)

    def test_engine_accounts_and_exports_bytes(self):
        from synapseml_tpu.telemetry import get_registry
        # heads of 128 lanes, as served models have: a narrower head is
        # fetched at 128 and the ledger prices that
        cfg = LlamaConfig.tiny(num_layers=1, d_model=256, num_heads=2,
                               num_kv_heads=1, max_len=64,
                               dtype=jnp.float32)
        model = LlamaModel(cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((2, 8), jnp.int32))
        eng = SlotEngine(model, variables, n_slots=4, max_len=64,
                         attention_backend="interpret", name="t-paged")
        dns = SlotEngine(model, variables, n_slots=4, max_len=64,
                         attention_backend="dense", name="t-dense")
        ids = _prompts(cfg, 2, 9, seed=13)
        for e in (eng, dns):
            e.admit(ids[0], 6)
            e.admit(ids[1], 6)
            e.run_to_completion()
        assert 0 < eng.decode_attn_bytes < dns.decode_attn_bytes
        g = get_registry().get("llm_decode_bytes_per_token")
        assert g.value(engine="t-paged", backend="interpret") > 0
        assert g.value(engine="t-dense", backend="dense") \
            > g.value(engine="t-paged", backend="interpret")
