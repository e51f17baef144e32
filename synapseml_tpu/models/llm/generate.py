"""Autoregressive generation: jitted prefill + decode loop with sampling.

The reference's only text-generation surface is the remote OpenAI
completion stage (reference: cognitive/.../openai/OpenAI.scala:246,
OpenAIPrompt.scala:172); this is the TPU-native local equivalent over
:class:`~synapseml_tpu.models.llm.model.LlamaModel`.  The whole decode
loop is ONE compiled XLA program: prefill writes the prompt's K/V into the
cache, then a ``lax.scan`` of single-token steps — each step one
dynamic-slice cache update and one sampled token; no host round-trips
until the finished (B, max_new) block returns.

Sampling: greedy (temperature=0), temperature, top-k, and nucleus
(top-p), composable in the usual k-then-p order.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .model import LlamaConfig, LlamaModel, init_cache


def sample_logits(logits: jnp.ndarray, key: jnp.ndarray,
                  temperature: float, top_k: int, top_p: float) -> jnp.ndarray:
    """Sample token ids from (B, V) logits.  temperature<=0 → argmax."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.float32(max(temperature, 1e-6))
    V = logits.shape[-1]
    if top_k and top_k < V:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        cum = jnp.cumsum(jax.nn.softmax(sorted_logits, axis=-1), axis=-1)
        # keep the smallest prefix with mass >= top_p (always >= 1 token)
        cutoff_idx = jnp.sum((cum < top_p).astype(jnp.int32), axis=-1,
                             keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "model", "max_new_tokens", "temperature", "top_k", "top_p", "eos_id",
    "pad_id"))
def _generate_jit(model: LlamaModel, variables: Any,
                  prompt_ids: jnp.ndarray, key: jnp.ndarray,
                  max_new_tokens: int, temperature: float, top_k: int,
                  top_p: float, eos_id: Optional[int], pad_id: int
                  ) -> jnp.ndarray:
    cfg = model.cfg
    B, P = prompt_ids.shape
    total = P + max_new_tokens
    cache = init_cache(cfg, B, total)

    # prefill: one batched pass over the prompt
    positions = jnp.broadcast_to(jnp.arange(P)[None, :], (B, P))
    logits, cache = model.apply(variables, prompt_ids, positions=positions,
                                cache=cache, cache_index=0)
    key, sub = jax.random.split(key)
    next_tok = sample_logits(logits[:, -1], sub, temperature, top_k, top_p)
    done = jnp.zeros(B, bool) if eos_id is None else (next_tok == eos_id)

    def step(carry, t):
        # t-th scan step feeds generated token #t, which sits at sequence
        # position P + t - 1 (prefill covered positions [0, P))
        cache, tok, done, key = carry
        ids = tok[:, None]
        pos = jnp.full((B, 1), P + t - 1, jnp.int32)
        logits, cache = model.apply(variables, ids, positions=pos,
                                    cache=cache, cache_index=P + t - 1)
        key, sub = jax.random.split(key)
        nxt = sample_logits(logits[:, -1], sub, temperature, top_k, top_p)
        nxt = jnp.where(done, pad_id, nxt)
        new_done = done if eos_id is None else (done | (nxt == eos_id))
        return (cache, nxt, new_done, key), tok

    (_, last, _, _), toks = lax.scan(
        step, (cache, next_tok, done, key),
        jnp.arange(max_new_tokens - 1) + 1)
    out = jnp.concatenate([jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)
    return out


def cast_params(variables: Any, dtype=jnp.bfloat16) -> Any:
    """Serving-precision cast of a param tree (float leaves only).

    Autoregressive decode is weight-bandwidth-bound: every token step
    streams the full parameter set from HBM, so f32-stored weights halve
    the achievable tokens/s against the same model held in bf16.  Compute
    already runs in ``cfg.dtype``; this aligns the STORED precision with
    it (measured on v5e, Llama-1B batch 8: 1.7k → 3.2k tokens/s/chip).
    Traverses ``nn.Partitioned`` wrappers, so TP shardings survive."""
    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree.map(cast, variables)


def quantize_int8(variables: Any) -> Any:
    """Weight-only int8 quantization of every Dense kernel (per-output-
    channel symmetric scales): the param tree for a model built with
    ``weight_quant="int8"``.

    Serving HBM halves again vs bf16 — Llama-3-8B drops from ~16 GB bf16
    to ~8.6 GB (int8 projections + bf16 embeddings/norms), which is what
    fits the 8B config on ONE 16 GB v5e chip with KV cache and activation
    headroom.  ``nn.Partitioned`` metadata carries over (scales shard on
    the kernel's output axis), so TP serving quantizes the same way.

    TIED models (no ``lm_head`` in the tree) additionally quantize the
    embedding table per vocab row for :class:`~.model.QuantEmbed` — the
    attend head streams the whole table every token, so on Llama-1B that
    is a third of the decode bandwidth."""
    import flax.linen as nn

    params = variables.get("params", variables)
    tied = isinstance(params, dict) and "lm_head" not in params

    def quant(w, axis, scale_names):
        """Symmetric int8 along ``axis`` → (q, scale), Partitioned-aware."""
        meta = None
        if isinstance(w, nn.Partitioned):
            meta, w = w.names, w.value
        absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis)
        scale = jnp.maximum(absmax / 127.0, 1e-12)
        s = jnp.expand_dims(scale, axis) if w.ndim > scale.ndim else scale
        q = jnp.clip(jnp.round(w.astype(jnp.float32) / s),
                     -127, 127).astype(jnp.int8)
        if meta is not None:
            q = nn.Partitioned(q, names=meta)
            scale = nn.Partitioned(scale, names=scale_names(meta))
        return q, scale

    def walk(d):
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                if tied and k == "tok_embed" and "embedding" in v:
                    # tied-embedding table -> QuantEmbed params: int8 with
                    # per-VOCAB-ROW scales (axis 1 is the contraction in
                    # attend, so the row scale commutes out columnwise).
                    # Non-tied models keep the bf16 table: its gather
                    # reads a handful of rows, not the whole tensor
                    q, scale = quant(v["embedding"], 1, lambda m: (m[0],))
                    out[k] = {"embedding_q": q, "scale": scale}
                elif "kernel" in v:
                    q, scale = quant(v["kernel"], 0, lambda m: (m[-1],))
                    rest = {kk: vv for kk, vv in v.items() if kk != "kernel"}
                    out[k] = {"kernel_q": q, "scale": scale, **walk(rest)}
                else:
                    out[k] = walk(v)
            else:
                out[k] = v
        return out

    return {k: (walk(v) if isinstance(v, dict) else v)
            for k, v in variables.items()}


def _ngram_draft(ctx: jnp.ndarray, cur_len: jnp.ndarray, draft_len: int,
                 ngram: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Prompt-lookup drafting: find the latest earlier occurrence of the
    last ``ngram`` tokens in the context and propose the tokens that
    followed it.  No draft model — the context itself is the draft source
    (strong on repetitive/structured text, harmless elsewhere because
    verification keeps greedy output exact).

    → ``(draft (B, draft_len) int32, vlen (B,) int32)`` where ``vlen``
    is how many draft positions came from a REAL known continuation —
    a row with no match (or a match whose continuation is shorter than
    ``draft_len``) pads with repeats of the last token, which can only
    be accepted by luck; counting those pads as "drafted" is the
    accounting bug that reported the old llama1b leg at 0.091
    acceptance (most of its "drafts" were never predictions at all).
    Acceptance telemetry divides by ``vlen``, not ``draft_len``."""
    B, L = ctx.shape
    iota_l = jnp.arange(L)[None, :]
    # gathers (take_along_axis) are the TPU pathology — every dynamic
    # read here is a one-hot contraction instead (measured: the gather
    # formulation cost several ms/step of the speculative loop's glue)
    gpos = jnp.maximum(cur_len[:, None] - ngram + jnp.arange(ngram), 0)
    tail = jnp.einsum("bjl,bl->bj",
                      (gpos[:, :, None] == iota_l[:, None, :])
                      .astype(jnp.int32), ctx)          # (B, n)
    # windows[b, p, j] = ctx[b, p + j] for p in [0, L - ngram]
    windows = jnp.stack([ctx[:, j:L - ngram + 1 + j] for j in range(ngram)],
                        axis=-1)                       # (B, L-n+1, n)
    match = jnp.all(windows == tail[:, None, :], axis=-1)
    p_idx = jnp.arange(L - ngram + 1)[None, :]
    # the match must END strictly before the tail and have at least one
    # known continuation token
    valid = match & (p_idx + ngram < cur_len[:, None])
    has = jnp.any(valid, axis=1)
    p_best = jnp.argmax(jnp.where(valid, p_idx, -1), axis=1)   # latest
    src = p_best[:, None] + ngram + jnp.arange(draft_len)      # (B, K)
    # clip unknown continuation positions to the last known token
    src = jnp.minimum(src, cur_len[:, None] - 1)
    oh = (src[:, :, None] == iota_l[:, None, :]).astype(jnp.int32)
    draft = jnp.einsum("bkl,bl->bk", oh, ctx)
    last = jnp.sum(jnp.where(iota_l == cur_len[:, None] - 1, ctx, 0),
                   axis=1, keepdims=True)
    vlen = jnp.where(
        has,
        jnp.clip(cur_len - (p_best + ngram), 0, draft_len),
        0).astype(jnp.int32)
    return jnp.where(has[:, None], draft,
                     jnp.broadcast_to(last, draft.shape)
                     ).astype(jnp.int32), vlen


@functools.partial(jax.jit, static_argnames=(
    "model", "max_new_tokens", "draft_len", "ngram", "eos_id", "pad_id"))
def _generate_spec_jit(model: LlamaModel, variables: Any,
                       prompt_ids: jnp.ndarray, max_new_tokens: int,
                       draft_len: int, ngram: int,
                       eos_id: Optional[int], pad_id: int):
    cfg = model.cfg
    B, P = prompt_ids.shape
    K = draft_len
    L = P + max_new_tokens + K + 2        # ctx/cache capacity with slack
    cache = init_cache(cfg, B, L)

    ctx = jnp.full((B, L), pad_id, jnp.int32).at[:, :P].set(prompt_ids)

    # prefill the prompt minus its last token (the last token is the first
    # verify block's "input 0" so its K/V lands there)
    positions = jnp.broadcast_to(jnp.arange(P - 1)[None, :], (B, P - 1))
    _, cache = model.apply(variables, prompt_ids[:, :-1],
                           positions=positions, cache=cache, cache_index=0)

    def cond(s):
        return (~jnp.all(s[2])) & (s[4] < max_new_tokens)

    def body(s):
        (ctx, cur_len, done, cache, steps, acc, row_steps, drafted,
         acc_valid) = s
        draft, vlen = _ngram_draft(ctx, cur_len, K, ngram)      # (B, K)
        last = jnp.sum(jnp.where(jnp.arange(L)[None, :]
                                 == cur_len[:, None] - 1, ctx, 0),
                       axis=1, keepdims=True)
        inputs = jnp.concatenate([last, draft], axis=1)         # (B, K+1)
        pos = (cur_len - 1)[:, None] + jnp.arange(K + 1)[None, :]
        logits, new_cache = model.apply(variables, inputs, positions=pos,
                                        cache=cache,
                                        cache_index=cur_len - 1)
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # (B, K+1)
        match = draft == g[:, :K]
        a = jnp.where(jnp.all(match, axis=1), K,
                      jnp.argmin(match.astype(jnp.int32), axis=1))  # (B,)
        n_new = a + 1                            # tokens g[:, 0..a]
        if eos_id is not None:
            is_eos = g == eos_id
            eos_pos = jnp.where(jnp.any(is_eos, axis=1),
                                jnp.argmax(is_eos, axis=1), K + 1)
            n_new = jnp.minimum(n_new, eos_pos + 1)
        n_new = jnp.where(done, 0, n_new)
        # scatter the accepted tokens g[:, i], i < n_new, at cur_len + i
        tpos = cur_len[:, None] + jnp.arange(K + 1)[None, :]    # (B, K+1)
        take = jnp.arange(K + 1)[None, :] < n_new[:, None]
        oh = (tpos[:, :, None] == jnp.arange(L)[None, None, :]) \
            & take[:, :, None]                                  # (B,K+1,L)
        ctx = jnp.where(jnp.any(oh, axis=1), jnp.einsum(
            "bsl,bs->bl", oh.astype(jnp.int32), g), ctx)
        if eos_id is not None:
            done = done | jnp.any((g == eos_id) & take, axis=1)
        acc = acc + n_new
        row_steps = row_steps + (n_new > 0).astype(jnp.int32)
        # honest acceptance accounting: only REAL draft positions
        # (known continuations, see _ngram_draft's vlen) count as
        # drafted, and an accepted prefix counts only up to vlen —
        # lucky matches on pad repeats are free tokens, not draft
        # skill.  n_new > 0 <=> the row entered this step live (a live
        # row always commits >= 1 token; a done row is zeroed above)
        live = (n_new > 0).astype(jnp.int32)
        drafted = drafted + vlen * live
        acc_valid = acc_valid + jnp.minimum(a, vlen) * live
        cur_len = cur_len + n_new
        # rows that reached their budget are done: keeping them in the
        # loop would burn full-model forwards and inflate the stats with
        # tokens the cropped output never shows
        done = done | (cur_len >= P + max_new_tokens)
        return (ctx, cur_len, done, new_cache, steps + 1, acc, row_steps,
                drafted, acc_valid)

    done0 = jnp.zeros(B, bool)
    state = (ctx, jnp.full((B,), P, jnp.int32), done0, cache,
             jnp.zeros((), jnp.int32), jnp.zeros((B,), jnp.int32),
             jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
             jnp.zeros((B,), jnp.int32))
    (ctx, cur_len, done, cache, steps, acc, row_steps, drafted,
     acc_valid) = lax.while_loop(cond, body, state)
    out = ctx[:, P:P + max_new_tokens]
    # pad everything past each sequence's end (eos freeze)
    keep = jnp.arange(max_new_tokens)[None, :] < (cur_len - P)[:, None]
    out = jnp.where(keep, out, pad_id)
    # pack tokens + stats into ONE array: one blocking host readback per
    # call instead of one per field
    packed = jnp.concatenate(
        [out, acc[:, None], row_steps[:, None],
         jnp.broadcast_to(steps, (B,))[:, None],
         drafted[:, None], acc_valid[:, None]], axis=1)
    return packed


def spec_unpack(packed, max_new_tokens: int, draft_len: int = 0):
    """Host-side unpack of a ``block=False`` speculative result →
    (tokens (B, max_new_tokens), stats dict) — same stats as the
    blocking path.  Publishes the acceptance telemetry (see
    :func:`_record_spec_stats`), so pipelined serving drains report the
    same metrics as blocking calls.  ``draft_len`` is unused (kept for
    call-site compatibility): the acceptance denominator is the REAL
    drafted count packed by the device loop, not the static k.

    ``acceptance_rate`` is accepted-over-DRAFTED: only draft positions
    backed by a real known continuation count (``_ngram_draft``'s
    ``vlen``) — the old definition divided committed tokens by the full
    static ``draft_len`` every step, so no-match steps (which draft
    nothing real) crushed the rate toward zero (0.091 on the llama1b
    leg) while saying nothing about draft quality."""
    packed = np.asarray(packed)
    out = packed[:, :max_new_tokens]
    acc = packed[:, max_new_tokens].astype(np.float64)
    row_steps = np.maximum(packed[:, max_new_tokens + 1].astype(np.float64),
                           1.0)
    drafted = packed[:, max_new_tokens + 3].astype(np.float64)
    acc_valid = packed[:, max_new_tokens + 4].astype(np.float64)
    tps = float(np.mean(acc / row_steps))
    stats = {"steps": int(packed[0, max_new_tokens + 2]),
             "accepted": int(acc.sum()),
             "drafted": int(drafted.sum()),
             "tokens_per_step": tps,
             "acceptance_rate": float(acc_valid.sum())
             / max(float(drafted.sum()), 1.0)}
    _record_spec_stats(stats)
    return out, stats


def _record_spec_stats(stats: dict) -> None:
    """Export speculative-decode acceptance as process metrics — the
    number ROADMAP item 3 tracks lived only inside bench.py before;
    with it on /metrics a serving fleet can watch draft quality decay
    live (e.g. after a model or tokenizer swap)."""
    from ...telemetry import get_registry
    reg = get_registry()
    reg.counter("llm_spec_accepted_tokens_total",
                "draft tokens accepted by speculative verification").inc(
        stats["accepted"])
    reg.counter("llm_spec_verify_steps_total",
                "speculative verify forwards executed").inc(stats["steps"])
    reg.gauge("llm_spec_tokens_per_step",
              "accepted tokens per verify step (last call)").set(
        stats["tokens_per_step"])
    reg.gauge("llm_spec_acceptance_rate",
              "fraction of drafted tokens accepted (last call)").set(
        stats["acceptance_rate"])


def generate_speculative(model: LlamaModel, variables: Any, prompt_ids,
                         max_new_tokens: int = 32, draft_len: int = 7,
                         ngram: int = 2, eos_id: Optional[int] = None,
                         pad_id: int = 0, block: bool = True):
    """Greedy decode with self-speculative (prompt-lookup) drafting.

    Each loop step verifies ``draft_len`` n-gram-drafted tokens in ONE
    forward of length draft_len+1.  At small batch the per-token matmuls
    use only B of the MXU's 128 rows, so a (B, K+1)-token verify costs the
    same as a single-token step — every accepted draft token is a free
    extra token.  Output is EXACTLY greedy decoding's (verification
    accepts a draft token only when it equals the model's argmax), so this
    is a pure serving-throughput lever, not an approximation.

    Returns (tokens (B, max_new_tokens) int32, stats dict with
    ``steps``/``accepted``/``tokens_per_step``).

    ``block=False`` instead returns the PACKED on-device
    (B, max_new_tokens + 5) array without the host readback — serving
    loops dispatch the next request while this one runs and recover
    (tokens, stats) later with :func:`spec_unpack`; the blocking
    readback is paid once per pipeline drain instead of once per call.
    """
    prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
    if prompt_ids.shape[1] < max(ngram, 2):
        raise ValueError("prompt must be at least ngram tokens long")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    packed = _generate_spec_jit(
        model, variables, prompt_ids, int(max_new_tokens), int(draft_len),
        int(ngram), eos_id, int(pad_id))
    if not block:
        # serving loops dispatch the next request while this one runs and
        # unpack later via :func:`spec_unpack` — the blocking readback
        # is paid once per pipeline drain, not once per call
        return packed
    # per-ROW stat averages (inside spec_unpack): rows finish at
    # different times, and a finished row must not dilute the rate of
    # rows still decoding.  ONE readback, not one per field
    return spec_unpack(packed, int(max_new_tokens), int(draft_len))


def generate(model: LlamaModel, variables: Any, prompt_ids,
             max_new_tokens: int = 32, temperature: float = 0.0,
             top_k: int = 0, top_p: float = 1.0,
             eos_id: Optional[int] = None, pad_id: int = 0,
             seed: int = 0, block: bool = True
             ) -> "np.ndarray | jax.Array":
    """Generate ``max_new_tokens`` continuations for a batch of
    equal-length prompts (B, P) → (B, max_new_tokens) int32.

    ``block=False`` returns the on-device array without the host
    readback: serving loops dispatch the next request's generate while
    the previous one still runs, so the host↔device round trip is paid
    once per pipeline drain instead of once per call."""
    prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    out = _generate_jit(model, variables, prompt_ids,
                        jax.random.PRNGKey(seed), int(max_new_tokens),
                        float(temperature), int(top_k), float(top_p),
                        eos_id, int(pad_id))
    return np.asarray(out) if block else out
