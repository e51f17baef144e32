"""Minimal causal-LM fine-tuning for :class:`LlamaModel`.

The serving-side story (speculative decoding, int8 serving) needs models
whose greedy continuations are actually predictable — random-init
weights emit chaos, which is the measured reason prompt-lookup
acceptance stays near zero on synthetic benchmarks.  This trainer is the
in-image path to that regime: next-token cross-entropy with adamw on
token streams (zero egress blocks real checkpoints; structured corpora
are generated instead).

Reference frame: the reference fine-tunes its text models through
Horovod/pytorch-lightning (DeepTextClassifier.py:27-290); this is the
decoder-LM analogue of that training loop, collapsed to a jitted step.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .model import LlamaModel

__all__ = ["lm_loss_fn", "make_lm_train_step", "finetune_lm",
           "templated_log_corpus"]

#: default record template for :func:`templated_log_corpus` — 16 tokens,
#: two random field slots (-1), the rest fixed
_LOG_TEMPLATE = np.array([17, 18, 19, -1, 21, 22, 23, 24, 25, -1, 27, 28,
                          29, 30, 31, 32])


def templated_log_corpus(rng: np.random.Generator, n: int, n_rec: int,
                         template: Optional[np.ndarray] = None,
                         field_range: Tuple[int, int] = (64, 512)
                         ) -> np.ndarray:
    """(n, n_rec·len(template)) int32 sequences of templated "log
    records": fixed template tokens with random field tokens in the -1
    slots — the canonical predictable-text corpus for demonstrating
    speculative decoding's target regime (the tests and the example
    draw from this one generator)."""
    tpl = _LOG_TEMPLATE if template is None else np.asarray(template)
    rec_len = len(tpl)
    out = np.zeros((n, n_rec * rec_len), np.int32)
    n_fields = int((tpl == -1).sum())
    for i in range(n):
        for r in range(n_rec):
            rec = tpl.copy()
            rec[rec == -1] = rng.integers(*field_range, size=n_fields)
            out[i, r * rec_len:(r + 1) * rec_len] = rec
    return out


def lm_loss_fn(model: LlamaModel):
    """(variables, tokens (B, S) int32) → mean next-token CE (f32),
    through the module's shared :func:`causal_lm_loss`."""
    from .model import causal_lm_loss

    def loss(variables, tokens):
        logits = model.apply(variables, tokens).astype(jnp.float32)
        return causal_lm_loss(logits, tokens)
    return loss


def make_lm_train_step(model: LlamaModel, learning_rate: float = 3e-4,
                       weight_decay: float = 0.01):
    """→ (init_opt_state, jitted step(variables, opt_state, tokens) →
    (variables, opt_state, loss))."""
    tx = optax.adamw(learning_rate, weight_decay=weight_decay)
    loss = lm_loss_fn(model)

    @jax.jit
    def step(variables, opt_state, tokens):
        l, grads = jax.value_and_grad(loss)(variables, tokens)
        updates, opt_state = tx.update(grads, opt_state, variables)
        return optax.apply_updates(variables, updates), opt_state, l

    return tx.init, step


def finetune_lm(model: LlamaModel, variables: Any,
                batches: Iterable[np.ndarray],
                learning_rate: float = 3e-4,
                log_every: int = 0) -> Tuple[Any, float]:
    """Run the jitted CE step over ``batches`` of (B, S) int32 tokens;
    returns (trained variables, final loss)."""
    init_opt, step = make_lm_train_step(model, learning_rate)
    opt_state = init_opt(variables)
    l = None
    for i, toks in enumerate(batches):
        variables, opt_state, l = step(variables, opt_state,
                                       jnp.asarray(toks, jnp.int32))
        if log_every and (i + 1) % log_every == 0:
            print(f"  lm step {i + 1}: loss {float(l):.4f}")
    return variables, (float(l) if l is not None else float("nan"))
