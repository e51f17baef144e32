"""TP-sharded decoder-only LLM (the Llama-3 stretch config; the
reference's only LLM surface is remote OpenAI calls,
cognitive/.../openai/OpenAI.scala:246)."""

from .finetune import (finetune_lm, make_lm_train_step,
                       templated_log_corpus)
from .generate import (cast_params, generate, quantize_int8,
                       sample_logits)
from .experts import ExpertFFN, expert_ffn
from .model import (LLM_LOGICAL_RULES, CausalAttention, DecoderBlock,
                    LayerNorm, LlamaConfig, LlamaModel, RMSNorm,
                    SlidingAttention, apply_rope, causal_lm_loss, init_cache,
                    llama_from_pretrained, rope_frequencies)
from .drafter import NgramDrafter
from .kvtier import (KVTIER_METRICS, TRANSFER_MAGIC, ChecksumError,
                     HostKVArena, KVTransfer, RadixPrefixIndex,
                     SessionJournal, SessionState, kvtier_metrics,
                     pack_kv_transfer, token_prefix_hash,
                     unpack_kv_transfer)
from .pallas_attn import (ATTENTION_BACKENDS, PagedGeometry,
                          dense_read_bytes, paged_decode_attention,
                          paged_geometry, paged_read_bytes,
                          resolve_attention_backend)
from .slots import AdmitResult, SlotEngine, StepEvent
from .stage import LLMTransformer
from .warmup import (CompilePlane, ProgramSpec, engine_jit_cache_size,
                     program_lattice)

__all__ = [
    "ATTENTION_BACKENDS",
    "ChecksumError", "CompilePlane",
    "HostKVArena", "KVTIER_METRICS", "KVTransfer", "TRANSFER_MAGIC",
    "LLM_LOGICAL_RULES", "AdmitResult", "CausalAttention", "DecoderBlock",
    "ExpertFFN", "LLMTransformer", "LayerNorm", "SlidingAttention",
    "LlamaConfig", "LlamaModel", "NgramDrafter", "PagedGeometry",
    "ProgramSpec",
    "RMSNorm", "RadixPrefixIndex", "SessionJournal", "SessionState",
    "SlotEngine",
    "StepEvent",
    "kvtier_metrics", "pack_kv_transfer", "token_prefix_hash",
    "unpack_kv_transfer",
    "apply_rope", "causal_lm_loss",
    "cast_params", "dense_read_bytes", "engine_jit_cache_size",
    "expert_ffn",
    "finetune_lm", "generate",
    "init_cache", "llama_from_pretrained", "make_lm_train_step",
    "paged_decode_attention", "paged_geometry", "paged_read_bytes",
    "program_lattice",
    "quantize_int8",
    "resolve_attention_backend", "rope_frequencies", "sample_logits",
    "templated_log_corpus",
]
