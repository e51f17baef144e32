"""Continuous batching + slotted KV/prefix cache serving tests.

The contract under test (ISSUE 9 acceptance criteria):

- greedy decode through the slotted cache is TOKEN-EXACT vs the dense
  fused-scan ``generate`` path, including a sequence admitted mid-flight
  next to a longer-running neighbor;
- prefix reuse (LCP KV copy between slots) copies BIT-identical K/V rows
  and returns the cold prefill's logits to reassociation error (tokens
  exactly), and retired slots' caches survive their neighbors' decode
  traffic bit for bit (the ``slot_mask`` write gate);
- the ``_DecodeLoop`` serving loop admits every step, streams tokens,
  sheds past-SLO requests with 503 + ``Retry-After``, and ``drain()``
  keeps the zero-drop guarantee for in-flight sequences;
- ``ReplicaRouter`` session affinity pins multi-turn traffic to the
  replica holding its prefix cache and falls back cleanly across
  resizes;
- a server over a drafting engine exports its acceptance telemetry.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from synapseml_tpu.models.llm import (LlamaConfig, LlamaModel, SlotEngine,
                                      generate)

pytestmark = pytest.mark.llmserve


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


def _assert_logits_match_cold(warm_logits, cold_logits):
    """A warm admit prefills only its tail, in a smaller bucket than the
    cold prompt: two differently shaped XLA:CPU programs, which may
    reassociate the same row contraction.  Under jax 0.9.0 they do — the
    reused prefix K/V rows are bit-identical (asserted where a test has
    both caches), the tail rows and the logits differ by 2-3 ulp.  Bound:
    16 ulp of the largest logit; a wrong or clobbered K/V row moves
    logits by ~1e-1, five orders above."""
    np.testing.assert_allclose(
        warm_logits, cold_logits, rtol=0,
        atol=16 * np.spacing(np.float32(np.abs(cold_logits).max())))


class TestSlotEngineExactness:
    def test_greedy_token_exact_vs_dense_cache(self, tiny_model):
        """The headline pin: slotted-cache greedy decode is token-
        identical to the dense ``_generate_jit`` path for a batch of
        sequences sharing the same jitted step."""
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 3, 7)
        ref = generate(model, variables, ids, max_new_tokens=10)
        eng = SlotEngine(model, variables, n_slots=4, max_len=64)
        slots = {i: eng.admit(ids[i], 10).slot for i in range(3)}
        out = eng.run_to_completion()
        for i in range(3):
            np.testing.assert_array_equal(out[slots[i]], ref[i])

    def test_mid_flight_admission_token_exact(self, tiny_model):
        """A sequence admitted while a longer-running neighbor is mid-
        decode: BOTH outputs stay exactly greedy (heterogeneous lengths
        in one jitted step)."""
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 2, 9, seed=1)
        ref_a = generate(model, variables, ids[0:1], max_new_tokens=14)[0]
        ref_b = generate(model, variables, ids[1:2], max_new_tokens=6)[0]
        eng = SlotEngine(model, variables, n_slots=4, max_len=64)
        ra = eng.admit(ids[0], 14)
        for _ in range(5):
            eng.step()
        rb = eng.admit(ids[1], 6)          # admitted mid-flight
        assert eng.active_count == 2
        while eng.active.any():
            eng.step()
        np.testing.assert_array_equal(eng.generated_ids(ra.slot), ref_a)
        np.testing.assert_array_equal(eng.generated_ids(rb.slot), ref_b)

    def test_prefix_reuse_bit_identical_logits(self, tiny_model):
        """LCP KV copy + tail prefill: the copied prefix K/V rows are
        BIT-identical to a cold full prefill's, the next-token logits
        agree to reassociation error, the tokens exactly."""
        cfg, model, variables = tiny_model
        rng = np.random.default_rng(2)
        prefix = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)
        tail1 = rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
        tail2 = rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
        p1 = np.concatenate([prefix, tail1])
        p2 = np.concatenate([prefix, tail2])
        warm = SlotEngine(model, variables, n_slots=4, max_len=64,
                          min_prefix=8)
        warm.admit(p1, 4)
        warm.run_to_completion()
        r_warm = warm.admit(p2, 4)
        assert r_warm.reused_tokens == 16
        assert warm.prefix_hits == 1
        cold = SlotEngine(model, variables, n_slots=4, max_len=64,
                          min_prefix=8)
        r_cold = cold.admit(p2, 4)
        assert r_cold.reused_tokens == 0
        for lw, lc in zip(warm.cache, cold.cache):
            for kv in ("k", "v"):
                np.testing.assert_array_equal(
                    np.asarray(lw[kv][r_warm.slot, :16]),
                    np.asarray(lc[kv][r_cold.slot, :16]))
        _assert_logits_match_cold(r_warm.logits, r_cold.logits)
        warm.run_to_completion()
        cold.run_to_completion()
        np.testing.assert_array_equal(warm.generated_ids(r_warm.slot),
                                      cold.generated_ids(r_cold.slot))

    def test_retired_cache_survives_neighbor_decode(self, tiny_model):
        """The slot_mask pin: a retired slot's K/V is prefix-cache
        material and must survive many decode steps of an ACTIVE
        neighbor — without the write gate every step would scribble one
        junk row into it."""
        cfg, model, variables = tiny_model
        rng = np.random.default_rng(3)
        prefix = rng.integers(1, cfg.vocab_size, 12).astype(np.int32)
        p1 = np.concatenate([prefix,
                             rng.integers(1, cfg.vocab_size,
                                          4).astype(np.int32)])
        eng = SlotEngine(model, variables, n_slots=3, max_len=64,
                         min_prefix=8)
        retired = eng.admit(p1, 3).slot
        eng.run_to_completion()                       # slot now retired
        before = [{kv: np.asarray(layer[kv][retired, :12])
                   for kv in ("k", "v")} for layer in eng.cache]
        other = eng.admit(_prompts(cfg, 1, 8, seed=4)[0], 20)
        eng.run_to_completion()                       # 20 masked steps
        assert other is not None and other.slot != retired
        for layer, snap in zip(eng.cache, before):
            for kv in ("k", "v"):
                np.testing.assert_array_equal(
                    np.asarray(layer[kv][retired, :12]), snap[kv])
        p2 = np.concatenate([prefix,
                             rng.integers(1, cfg.vocab_size,
                                          5).astype(np.int32)])
        r_warm = eng.admit(p2, 4)
        assert r_warm.reused_tokens == 12
        cold = SlotEngine(model, variables, n_slots=3, max_len=64,
                          min_prefix=8)
        r_cold = cold.admit(p2, 4)
        _assert_logits_match_cold(r_warm.logits, r_cold.logits)

    def test_long_prefix_reuse_bucket_clamp_exact(self, tiny_model):
        """A reuse long enough that the tail's PADDED prefill bucket
        would run past max_len: the engine clamps the reused span so the
        write fits (an unclamped dynamic_update_slice silently shifts
        the write start and corrupts the prefix K/V) — output stays
        exactly cold-prefill."""
        cfg, model, variables = tiny_model
        rng = np.random.default_rng(11)
        p1 = rng.integers(1, cfg.vocab_size, 58).astype(np.int32)
        p2 = np.concatenate([p1, rng.integers(1, cfg.vocab_size,
                                              1).astype(np.int32)])
        warm = SlotEngine(model, variables, n_slots=2, max_len=64,
                          min_prefix=8)
        warm.admit(p1, 4)
        warm.run_to_completion()
        r_warm = warm.admit(p2, 4)               # lcp would be 58; 58+8>64
        assert 0 < r_warm.reused_tokens <= 64 - 8
        cold = SlotEngine(model, variables, n_slots=2, max_len=64,
                          min_prefix=8)
        r_cold = cold.admit(p2, 4)
        # ulp-level tolerance: the clamped tail prefills in a different
        # bucket size than the cold prompt, and XLA may tile the same
        # row contraction differently across shapes — the BUG this test
        # pins produced ~1e-1 divergence (corrupted K/V), five orders
        # above this bound
        np.testing.assert_allclose(r_warm.logits, r_cold.logits,
                                   rtol=1e-5, atol=1e-5)
        warm.run_to_completion()
        cold.run_to_completion()
        np.testing.assert_array_equal(warm.generated_ids(r_warm.slot),
                                      cold.generated_ids(r_cold.slot))

    def test_inplace_resume_reuses_own_slot(self, tiny_model):
        """n_slots=1 multi-turn: the reclaimed slot IS the prefix
        source — no copy, just a tail prefill from the cached span, and
        output stays exactly cold."""
        cfg, model, variables = tiny_model
        rng = np.random.default_rng(12)
        p1 = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)
        eng = SlotEngine(model, variables, n_slots=1, max_len=64,
                         min_prefix=8)
        r1 = eng.admit(p1, 3)
        eng.run_to_completion()
        turn2 = np.concatenate([p1, eng.generated_ids(r1.slot),
                                rng.integers(1, cfg.vocab_size,
                                             4).astype(np.int32)])
        r2 = eng.admit(turn2, 4)
        assert r2.reused_tokens >= 16            # own slot resumed
        assert eng.prefix_hits == 1
        cold = SlotEngine(model, variables, n_slots=1, max_len=64,
                          min_prefix=8)
        rc = cold.admit(turn2, 4)
        _assert_logits_match_cold(r2.logits, rc.logits)
        eng.run_to_completion()
        cold.run_to_completion()
        np.testing.assert_array_equal(eng.generated_ids(r2.slot),
                                      cold.generated_ids(rc.slot))

    def test_eos_retirement_matches_dense(self, tiny_model):
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 1, 6, seed=5)
        base = generate(model, variables, ids, max_new_tokens=10)[0]
        eos = int(base[3])                 # force a mid-stream stop
        ref = generate(model, variables, ids, max_new_tokens=10,
                       eos_id=eos, pad_id=0)[0]
        eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                         eos_id=eos)
        r = eng.admit(ids[0], 10)
        eng.run_to_completion()
        out = eng.generated_ids(r.slot)
        stop = list(ref).index(eos)
        np.testing.assert_array_equal(out, ref[:stop + 1])
        assert not eng.active[r.slot]
        assert eng.evictions == 1


class TestSlotEngineScheduling:
    def test_admit_full_returns_none_and_reclaim_is_lru(self, tiny_model):
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=64)
        ids = _prompts(cfg, 3, 6, seed=6)
        a = eng.admit(ids[0], 4)
        b = eng.admit(ids[1], 4)
        assert eng.admit(ids[2], 4) is None          # full
        eng.run_to_completion()
        # a retired first (same finish step, lower slot retires first in
        # event order but retirement times are monotonic within a step);
        # the next admit reclaims the LEAST recently retired slot
        c = eng.admit(ids[2], 4)
        assert c.slot in (a.slot, b.slot)
        assert c.slot == a.slot

    def test_prompt_too_long_raises(self, tiny_model):
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=32)
        with pytest.raises(ValueError, match="max_len"):
            eng.admit(_prompts(cfg, 1, 20, seed=7)[0], 20)

    def test_cancel_frees_slot(self, tiny_model):
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=1, max_len=64)
        r = eng.admit(_prompts(cfg, 1, 6, seed=8)[0], 30)
        assert eng.free_slot_count == 0
        eng.cancel(r.slot)
        assert eng.free_slot_count == 1
        assert eng.admit(_prompts(cfg, 1, 6, seed=9)[0], 4) is not None

    def test_min_remaining_tokens_floor(self, tiny_model):
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=64)
        assert eng.min_remaining_tokens() is None
        eng.admit(_prompts(cfg, 1, 6, seed=10)[0], 20)
        eng.admit(_prompts(cfg, 1, 6, seed=11)[0], 5)
        # one token of each budget was already produced by the prefill
        assert eng.min_remaining_tokens() == 4
        eng.step()
        assert eng.min_remaining_tokens() == 3

    def test_reset_recovers_donated_cache(self, tiny_model, monkeypatch):
        """The engine's jitted programs DONATE the cache: a failure
        raised after the call consumed the buffers leaves `cache`
        pointing at deleted arrays — reset() rebuilds it and the engine
        serves exactly again (what _DecodeLoop._fail_inflight relies
        on)."""
        import synapseml_tpu.models.llm.slots as slots_mod
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 2, 7, seed=13)
        eng = SlotEngine(model, variables, n_slots=2, max_len=64)
        eng.admit(ids[0], 10)
        real = slots_mod._decode_step_jit

        def post_donation_failure(*a, **kw):
            real(*a, **kw)          # consumes (donates) eng.cache
            raise RuntimeError("device fell over")
        monkeypatch.setattr(slots_mod, "_decode_step_jit",
                            post_donation_failure)
        with pytest.raises(RuntimeError, match="device fell over"):
            eng.step()
        monkeypatch.setattr(slots_mod, "_decode_step_jit", real)
        # the donated cache is dead: without reset the engine is bricked
        with pytest.raises(Exception):
            eng.admit(ids[1], 4)
        eng.reset()
        assert eng.active_count == 0
        r = eng.admit(ids[1], 6)
        eng.run_to_completion()
        ref = generate(model, variables, ids[1:2], max_new_tokens=6)[0]
        np.testing.assert_array_equal(eng.generated_ids(r.slot), ref)

    def test_occupancy_and_counters_exported(self, tiny_model):
        from synapseml_tpu.telemetry import get_registry
        cfg, model, variables = tiny_model
        eng = SlotEngine(model, variables, n_slots=2, max_len=64,
                         name="t-occ")
        eng.admit(_prompts(cfg, 1, 6, seed=12)[0], 3)
        g = get_registry().get("llm_slot_occupancy")
        assert g.value(engine="t-occ") == 0.5
        eng.run_to_completion()
        assert g.value(engine="t-occ") == 0.0
        assert get_registry().get("llm_admissions_total").value(
            engine="t-occ", tenant="default") == 1.0
        assert get_registry().get("llm_evictions_total").value(
            engine="t-occ", reason="length", tenant="default") == 1.0


def _post(url, payload, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read(), dict(r.headers)


class TestLLMServer:
    def test_http_roundtrip_token_exact(self, tiny_model):
        from synapseml_tpu.serving import LLMServer
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 1, 7, seed=20)
        ref = generate(model, variables, ids, max_new_tokens=8)[0]
        srv = LLMServer(model, variables, n_slots=2, max_len=64,
                        engine_kwargs={"name": "t-http"})
        try:
            status, body, _ = _post(srv.url, {
                "ids": [int(t) for t in ids[0]], "max_new_tokens": 8})
            assert status == 200
            assert json.loads(body)["ids"] == [int(t) for t in ref]
        finally:
            srv.close()

    def test_concurrent_requests_all_exact(self, tiny_model):
        """More requests than slots: the loop queues, admits as slots
        free, and every reply is exactly greedy."""
        from synapseml_tpu.serving import LLMServer
        cfg, model, variables = tiny_model
        n = 5
        ids = _prompts(cfg, n, 7, seed=21)
        refs = generate(model, variables, ids, max_new_tokens=6)
        srv = LLMServer(model, variables, n_slots=2, max_len=64,
                        engine_kwargs={"name": "t-conc"})
        results = {}

        def call(i):
            results[i] = _post(srv.url, {"ids": [int(t) for t in ids[i]],
                                         "max_new_tokens": 6})
        try:
            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            for i in range(n):
                status, body, _ = results[i]
                assert status == 200
                assert json.loads(body)["ids"] == [int(t) for t in refs[i]]
        finally:
            srv.close()

    def test_streaming_tokens_chunked(self, tiny_model):
        from synapseml_tpu.serving import LLMServer
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 1, 7, seed=22)
        ref = generate(model, variables, ids, max_new_tokens=6)[0]
        srv = LLMServer(model, variables, n_slots=2, max_len=64,
                        engine_kwargs={"name": "t-stream"})
        try:
            status, body, _ = _post(srv.url, {
                "ids": [int(t) for t in ids[0]], "max_new_tokens": 6,
                "stream": True})
            assert status == 200
            lines = [json.loads(ln) for ln in body.splitlines() if ln]
            toks = [ln["token"] for ln in lines if "token" in ln]
            assert toks == [int(t) for t in ref]
            done = lines[-1]
            assert done["done"] is True
            assert done["ids"] == [int(t) for t in ref]
        finally:
            srv.close()

    def test_token_stream_pull_takes_every_queued_chunk(self):
        """A pull joins what the decode loop queued since the last one, so
        a writer that fell behind pays one round trip for the lot; the end
        ends the stream after what came before it."""
        from synapseml_tpu.serving.server import _TokenStream
        s = _TokenStream()
        s.push(b'{"token": 1}\n')
        assert next(s) == b'{"token": 1}\n'
        for t in (2, 3, 4):
            s.push(b'{"token": %d}\n' % t)
        assert next(s) == b'{"token": 2}\n{"token": 3}\n{"token": 4}\n'
        s.push(b'{"token": 5}\n')
        s.push(b'{"done": true}\n')
        s.finish()
        s.push(b"after the end\n")
        assert list(s) == [b'{"token": 5}\n{"done": true}\n']
        with pytest.raises(StopIteration):
            next(s)

    def test_prompt_text_with_tokenizer(self, tiny_model):
        from synapseml_tpu.models.dl.tokenizer import WordTokenizer
        from synapseml_tpu.serving import LLMServer
        cfg, model, variables = tiny_model
        tok = WordTokenizer.fit(["the cat sat on the mat"] * 4,
                                vocab_size=cfg.vocab_size)
        srv = LLMServer(model, variables, tokenizer=tok, n_slots=2,
                        max_len=64, engine_kwargs={"name": "t-tok"})
        try:
            status, body, _ = _post(srv.url, {"prompt": "the cat",
                                              "max_new_tokens": 4})
            assert status == 200
            out = json.loads(body)
            assert len(out["ids"]) == 4
            assert isinstance(out["completion"], str)
        finally:
            srv.close()

    def test_unparseable_request_400_isolated(self, tiny_model):
        from synapseml_tpu.serving import LLMServer
        cfg, model, variables = tiny_model
        srv = LLMServer(model, variables, n_slots=2, max_len=64,
                        engine_kwargs={"name": "t-400"})
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(srv.url, {"nonsense": 1})
            assert exc.value.code == 400
            # the loop is still alive and serving
            ids = _prompts(cfg, 1, 7, seed=23)
            status, _, _ = _post(srv.url, {"ids": [int(t) for t in ids[0]],
                                           "max_new_tokens": 2})
            assert status == 200
        finally:
            srv.close()

    def test_slo_shed_503_with_retry_after(self, tiny_model):
        """One slot, one long-running sequence: a queued request whose
        projected TTFT exceeds the SLO answers 503 + Retry-After through
        the PR-2 queue-depth path."""
        from synapseml_tpu.serving import LLMServer
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 2, 7, seed=24)
        srv = LLMServer(model, variables, n_slots=1, max_len=96,
                        ttft_slo_s=0.01,
                        engine_kwargs={"name": "t-slo"})
        results = {}

        def long_call():
            results["long"] = _post(srv.url, {
                "ids": [int(t) for t in ids[0]], "max_new_tokens": 60})
        try:
            t = threading.Thread(target=long_call)
            t.start()
            # wait until the long request holds the only slot
            deadline = time.monotonic() + 10
            while (srv.engine.active_count == 0
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            assert srv.engine.active_count == 1
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(srv.url, {"ids": [int(t) for t in ids[1]],
                                "max_new_tokens": 4})
            assert exc.value.code == 503
            assert float(exc.value.headers["Retry-After"]) > 0
            t.join(timeout=30)
            assert results["long"][0] == 200      # in-flight unaffected
        finally:
            srv.close()

    def test_drain_zero_drop_and_new_work_shed(self, tiny_model):
        """The acceptance pin: drain() mid-decode lets the in-flight
        sequence run to completion (200, full output) while new work is
        shed with 503 + Retry-After."""
        from synapseml_tpu.serving import LLMServer
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 1, 7, seed=25)
        ref = generate(model, variables, ids, max_new_tokens=40)[0]
        srv = LLMServer(model, variables, n_slots=2, max_len=96,
                        engine_kwargs={"name": "t-drain"})
        results = {}

        def call():
            results["r"] = _post(srv.url, {
                "ids": [int(t) for t in ids[0]], "max_new_tokens": 40})
        t = threading.Thread(target=call)
        t.start()
        deadline = time.monotonic() + 10
        while srv.engine.active_count == 0 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert srv.engine.active_count == 1
        url = srv.url
        drained = srv.drain(timeout_s=30)
        t.join(timeout=30)
        assert drained is True
        status, body, _ = results["r"]
        assert status == 200
        assert json.loads(body)["ids"] == [int(t) for t in ref]
        # the listener is closed: new work cannot even connect
        with pytest.raises(Exception):
            _post(url, {"ids": [1, 2, 3]}, timeout=2)

    def test_stream_client_disconnect_frees_slot(self, tiny_model):
        """A streaming client that drops mid-decode must not hold its
        slot for the full token budget: the chunk writer flags the
        stream abandoned and the loop cancels the slot."""
        import socket
        import struct

        from synapseml_tpu.serving import LLMServer
        from synapseml_tpu.telemetry import get_registry
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 1, 7, seed=28)
        srv = LLMServer(model, variables, n_slots=1, max_len=96,
                        engine_kwargs={"name": "t-disc"})
        try:
            body = json.dumps({"ids": [int(t) for t in ids[0]],
                               "max_new_tokens": 80,
                               "stream": True}).encode()
            host, port = srv.server.address
            s = socket.create_connection((host, port), timeout=10)
            s.sendall((f"POST /generate HTTP/1.1\r\nHost: x\r\n"
                       f"Content-Length: {len(body)}\r\n\r\n"
                       ).encode() + body)
            s.recv(256)                     # stream is flowing
            # RST on close (SO_LINGER 0): the server's next chunk write
            # fails instead of buffering behind a FIN
            s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                         struct.pack("ii", 1, 0))
            s.close()
            deadline = time.monotonic() + 10
            while (srv.engine.active_count
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert srv.engine.active_count == 0
            assert get_registry().get("llm_evictions_total").value(
                engine="t-disc", reason="cancelled",
                tenant="default") == 1.0
        finally:
            srv.close()

    def test_engine_failure_does_not_kill_loop(self, tiny_model):
        """The _ApiLoop invariant holds for the decode loop: an engine
        step that raises fails the in-flight request with 500 and the
        loop keeps serving the next one."""
        from synapseml_tpu.serving import LLMServer
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 2, 7, seed=27)
        srv = LLMServer(model, variables, n_slots=2, max_len=64,
                        engine_kwargs={"name": "t-boom"})
        try:
            orig = srv.engine.step
            state = {"armed": True}

            def boom():
                if state["armed"]:
                    state["armed"] = False
                    raise RuntimeError("kaboom")
                return orig()
            srv.engine.step = boom
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(srv.url, {"ids": [int(t) for t in ids[0]],
                                "max_new_tokens": 5})
            assert exc.value.code == 500
            assert b"kaboom" in exc.value.read()
            ref = generate(model, variables, ids[1:2], max_new_tokens=4)[0]
            status, body, _ = _post(srv.url, {
                "ids": [int(t) for t in ids[1]], "max_new_tokens": 4})
            assert status == 200
            assert json.loads(body)["ids"] == [int(t) for t in ref]
        finally:
            srv.close()

    def test_expired_reply_window_cancels_slot(self, tiny_model):
        """A request whose reply window expired (client got its 504,
        exchange forgotten) must not decode to completion holding a
        slot — the loop cancels it, freeing capacity for requests
        someone is still waiting on."""
        from synapseml_tpu.serving import LLMServer
        from synapseml_tpu.telemetry import get_registry
        cfg, model, variables = tiny_model
        ids = _prompts(cfg, 1, 7, seed=26)
        srv = LLMServer(model, variables, n_slots=1, max_len=96,
                        reply_timeout_s=0.05,
                        engine_kwargs={"name": "t-exp"})
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(srv.url, {"ids": [int(t) for t in ids[0]],
                                "max_new_tokens": 80})
            assert exc.value.code == 504
            deadline = time.monotonic() + 5
            while (srv.engine.active_count
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert srv.engine.active_count == 0
            assert get_registry().get("llm_evictions_total").value(
                engine="t-exp", reason="cancelled",
                tenant="default") == 1.0
        finally:
            srv.close()

    def test_poll_and_get_batch_fast_path(self):
        from synapseml_tpu.serving.server import ApiHandle, ServingRequest
        api = ApiHandle("/x")
        t0 = time.perf_counter()
        assert api.poll() == []
        assert api.get_batch(timeout_s=0) == []
        assert api.get_batch(timeout_s=-1) == []
        assert time.perf_counter() - t0 < 0.05   # never blocks
        api.submit(ServingRequest(id="a", method="POST", path="/x",
                                  headers={}, body=b"{}"))
        out = api.poll()
        assert [r.id for r in out] == ["a"]
        assert api.poll() == []


_AFF_NAMES = iter(range(10_000))


class TestSessionAffinity:
    def _router(self, n=3, **kw):
        from synapseml_tpu.serving import ReplicaRouter
        table = [("127.0.0.1", 9000 + i) for i in range(n)]
        # unique router name per instance: replica breakers are keyed
        # process-wide by (name, host, port)
        return ReplicaRouter(table, name=f"t-aff-{next(_AFF_NAMES)}", **kw)

    def test_session_sticks_while_routable(self):
        r = self._router()
        rank0 = r.route(session="conv-1").rank
        for _ in range(5):
            assert r.route(session="conv-1").rank == rank0
        # unpinned traffic still round-robins over everyone
        seen = {r.route()[0] for _ in range(6)}
        assert seen == {0, 1, 2}

    def test_pinned_replica_down_falls_back_and_repins(self):
        from synapseml_tpu.serving.distributed import DEAD
        r = self._router()
        rank0 = r.route(session="conv-2").rank
        with r._lock:
            r._status[rank0] = DEAD
        rank1 = r.route(session="conv-2").rank
        assert rank1 != rank0
        assert r.route(session="conv-2")[0] == rank1     # re-pinned

    def test_resize_drops_departed_sessions(self):
        r = self._router()
        r.route(session="conv-3")
        # pin the session to the LAST replica, then shrink it away
        with r._lock:
            r._sessions[("default", "conv-3")] = ("127.0.0.1", 9002)
        r.refresh([("127.0.0.1", 9000), ("127.0.0.1", 9001)])
        assert ("default", "conv-3") not in r._sessions   # fell back cleanly
        rank = r.route(session="conv-3").rank        # never crashes
        assert rank in (0, 1)
        assert r._sessions[("default", "conv-3")] in r.table

    def test_session_cache_bounded_lru(self):
        r = self._router(session_cache_size=2)
        r.route(session="s1")
        r.route(session="s2")
        r.route(session="s3")
        assert ("default", "s1") not in r._sessions
        assert set(r._sessions) == {("default", "s2"), ("default", "s3")}


def test_speculative_metrics_exported(tiny_model):
    """A server whose engine drafts (``spec_draft_len``) answers exactly
    greedy, and its acceptance telemetry is on ``/metrics`` under the
    engine's label: draft quality can be watched live."""
    from synapseml_tpu.serving import LLMServer
    cfg, model, variables = tiny_model
    ids = _prompts(cfg, 1, 10, seed=30)   # its greedy text soon cycles
    ref = generate(model, variables, ids, max_new_tokens=32)[0]
    srv = LLMServer(model, variables, n_slots=2, max_len=64,
                    spec_draft_len=7, engine_kwargs={"name": "t-specm"})
    try:
        status, body, _ = _post(srv.url, {
            "ids": [int(t) for t in ids[0]], "max_new_tokens": 32})
        assert status == 200
        assert json.loads(body)["ids"] == [int(t) for t in ref]
        text = urllib.request.urlopen(
            srv.server.url_for("/metrics"), timeout=10).read().decode()
    finally:
        srv.close()
    assert srv.engine.spec_steps > 0
    assert 'llm_spec_accepted_span_size_count{engine="t-specm"}' in text
    hits = [ln for ln in text.splitlines()
            if ln.startswith('llm_spec_draft_hit_total{engine="t-specm"}')]
    assert float(hits[0].split()[-1]) == srv.engine.spec_draft_hits > 0
