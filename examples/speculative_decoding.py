"""Speculative decoding: prompt-lookup drafts, exact greedy output.

``SlotEngine(spec_draft_len=K)`` drafts up to K tokens per slot by n-gram
lookup in the slot's own context (``NgramDrafter``: host tables, nothing
drafted on a miss) and verifies every slot's draft in ONE multi-token
forward.  At small batch the verify matmuls use few of the MXU's rows, so
accepted draft tokens ride the same row-bound step for free — and because
a draft only survives when it equals the model's argmax, the output is
bit-identical to plain greedy decoding.
"""

import numpy as np

import jax
import jax.numpy as jnp

from synapseml_tpu.models.llm import (LlamaConfig, LlamaModel, SlotEngine,
                                      generate)


def spec_decode(model, variables, prompts, max_new_tokens, draft_len):
    """Decode every prompt in its own slot → (tokens (B, max_new_tokens),
    the engine)."""
    eng = SlotEngine(model, variables, n_slots=len(prompts),
                     max_len=model.cfg.max_len, spec_draft_len=draft_len)
    slots = [eng.admit(p, max_new_tokens).slot for p in prompts]
    eng.run_to_completion()
    return np.stack([eng.generated_ids(s) for s in slots]), eng


def main():
    cfg = LlamaConfig.tiny(num_layers=2, max_len=128, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    rng = np.random.default_rng(0)
    base = rng.integers(1, cfg.vocab_size, 6)
    prompt = np.concatenate([base, base, base])[None, :].repeat(2, 0)

    ref = generate(model, variables, prompt, max_new_tokens=24)
    out, eng = spec_decode(model, variables, prompt, 24, draft_len=5)
    assert np.array_equal(ref, out), "speculative decode must equal greedy"
    print(f"greedy-exact in {eng.steps_run} steps ({eng.spec_steps} of them "
          f"verify steps), acceptance {eng.spec_acceptance_rate:.2f}")


def target_regime():
    """The technique's TARGET regime: on PREDICTABLE text (here: a model
    fine-tuned on templated logs with finetune_lm — with network access,
    load a real checkpoint via llama_from_pretrained instead) acceptance
    jumps to several tokens per step while the output stays exactly
    greedy."""
    from synapseml_tpu.models.llm import finetune_lm, templated_log_corpus

    cfg = LlamaConfig.tiny(vocab_size=256, d_model=128, num_layers=2,
                           num_heads=4, num_kv_heads=2, max_len=160)
    model = LlamaModel(cfg)
    rng = np.random.default_rng(0)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    jnp.zeros((1, 8), jnp.int32))
    corpus = (templated_log_corpus(rng, 16, 6, field_range=(64, 256))
              for _ in range(120))
    variables, loss = finetune_lm(model, variables, corpus,
                                  learning_rate=1e-3)
    prompts = templated_log_corpus(rng, 4, 3, field_range=(64, 256))
    ref = generate(model, variables, prompts, max_new_tokens=32)
    out, eng = spec_decode(model, variables, prompts, 32, draft_len=7)
    assert np.array_equal(ref, out)
    print(f"fine-tuned (loss {loss:.2f}): {out.size} tokens in "
          f"{eng.steps_run} steps of 4 slots, still greedy-exact")


if __name__ == "__main__":
    main()
    target_regime()
    print("ok")
