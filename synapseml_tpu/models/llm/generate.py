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
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .model import LlamaModel, init_cache


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
