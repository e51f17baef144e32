"""Decoder-only causal LLM: one model description, TP-sharded.

:class:`LlamaConfig` describes a decoder by its widths and a per-layer
pattern (``layer_types``, the key ``transformers`` configs use): each layer
kind is one mixer class that declares its own cache entry
(:data:`MIXERS`, :func:`init_cache`).  The default, every layer
``full_attention`` with rotary embeddings in pre-norm blocks, is the Llama-3
family; ``linear_attention`` layers (gated delta rule, a recurrent state in
place of K/V rows), the OLMo block order, query/key normalisation and a
decoder without rotary embeddings are rows of the same description
(:meth:`LlamaConfig.from_hf`).  So are ``sliding_attention`` layers (the
attention layer behind a window, its cache entry a ring where ``max_len``
holds two rings: :class:`SlidingAttention`), a feed-forward kind beside the
mixer kind, for every layer or layer by layer (:data:`FFNS`: dense SwiGLU, or
the expert layer of :mod:`~synapseml_tpu.models.llm.experts`), the parallel
block, LayerNorm, a head width that is not ``d_model / num_heads``,
interleaved rotary embeddings on some layer kinds and none on others, and
what ONE layer kind's attention overrides (:class:`AttentionKind`: its K/V
heads, a key wider than the value, rotary embeddings on part of a head with
the kind's own theta, a sink logit a head, a scale on the values).  So are
``latent_attention`` layers (:class:`LatentAttention`: multi-head latent
attention, whose cache entry is one latent row a position for all heads,
with YaRN's rotary embedding) and a router that selects among groups of
experts and scales their weights (``expert_groups``,
``routed_scaling_factor``).  So is the ``qwen3_next`` family: linear layers
with fewer key heads than value heads (``linear_num_key_heads``), a
sigmoid gate on attention's output (``attn_output_gate``), q/k norms by
head (``qk_head_norm``), zero-centred norms (``norm="zero_centred"``),
rotary embeddings on part of every head (``partial_rotary_factor``) and a
shared expert of its own width behind a sigmoid gate
(``shared_expert_d_ff``, ``shared_expert_gate``).

The reference has no LLM training/serving of its own — its OpenAI stages
call out to a remote service (reference: cognitive/.../openai/OpenAI.scala
:246).  This module is the TPU-native counterpart the stretch config
needs: RMSNorm, rotary embeddings, grouped-query attention, SwiGLU MLP —
with Megatron-style tensor-parallel layout expressed as flax logical
axes: QKV/gate/up shard column-wise on the ``model`` mesh axis, the
output/down projections row-wise, so each block incurs exactly one psum
(inserted by XLA from the shardings, not hand-written).

KV caches are explicit function state (a pytree threaded through
``apply``), shaped (B, max_len, n_kv_heads, d_head) and sharded on the
heads axis, so the whole decode loop stays inside one jitted program.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

#: logical→mesh rules for the decoder (kv heads shard with tp too)
LLM_LOGICAL_RULES = (
    ("batch", "data"),
    ("embed", None),
    ("heads", "model"),
    ("kv", "model"),
    ("mlp", "model"),
    ("vocab", "model"),
    ("seq", None),
)


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """What the attention of ONE layer kind (a ``layer_types`` entry) differs
    in from the description's defaults; ``None``: the default
    (``LlamaConfig.attention`` fills them in).  A kind that has such an entry
    keeps its K/V rows packed (:func:`kv_pack`), and so does one whose
    unpacked rows would be relaid (``LlamaConfig.packed``)."""
    #: K/V heads (default ``num_kv_heads``)
    num_kv_heads: Optional[int] = None
    #: width of a query and a key head (default ``d_head``)
    head_dim: Optional[int] = None
    #: width of a value head (default: the key's)
    v_head_dim: Optional[int] = None
    #: the FIRST dims of a query and key head that take the rotary
    #: embedding, the rest pass untouched (default: all of them)
    rotary_dim: Optional[int] = None
    #: default ``rope_theta`` (under ``rope_layers``)
    rope_theta: Optional[float] = None
    #: a learned logit a query head: one more column in the softmax that
    #: takes mass and contributes no value, whatever the mask
    sink: bool = False
    #: the values are multiplied by it before the probabilities meet them
    value_scale: float = 1.0


#: rows of one block of a window layer's ring (:meth:`SlidingAttention
#: .cache_rows`): the paged kernel's tile on a ring is at most this
RING_BLOCK = 128


def kv_pack(k_dim: int, kv_heads: int) -> int:
    """K/V heads that lie side by side in one packed cache row: as many as
    make the key row whole lane tiles (192 wide: 2, a row of 384 lanes and
    no padding), where the kind's heads divide into such groups; else 1."""
    f = 128 // np.gcd(int(k_dim), 128)
    return int(f) if f <= 2 and kv_heads % f == 0 else 1


@dataclasses.dataclass(unsafe_hash=True)
class LlamaConfig:
    vocab_size: int = 128_256
    d_model: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    d_ff: int = 14_336
    max_len: int = 8192
    #: None: no rotary embedding
    rope_theta: Optional[float] = 500_000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    #: "int8": Dense layers read int8 weights with per-output-channel
    #: scales (weight-only quantization; dequant AFTER the matmul, which
    #: commutes with the contraction) — halves serving HBM again vs bf16,
    #: the knob that fits 8B-class models on one 16 GB chip.  Pair with
    #: :func:`synapseml_tpu.models.llm.quantize_int8`
    weight_quant: str = "none"
    #: mixer kind of each layer (a key of :data:`MIXERS`), as
    #: ``transformers`` configs give it; None: every layer full attention
    layer_types: Optional[Tuple[str, ...]] = None
    #: "pre": ``x + mixer(norm(x))`` (Llama); "post": ``x + norm(mixer(x))``
    #: (OLMo 2 and 3), the same for the MLP
    norm_order: str = "pre"
    #: RMSNorm with a learned scale over the whole width of q and of k
    #: before the split into heads (OLMo 2 and 3)
    qk_norm: bool = False
    #: the model's norm (``norm``) over each head of q and of k after the
    #: split, one scale of the head's width for every head (Qwen3-Next)
    qk_head_norm: bool = False
    #: ``q_proj`` yields ``[q_h | g_h]`` a head, and the attention's output
    #: is multiplied by ``sigmoid(g)`` before ``o_proj`` (Qwen3-Next)
    attn_output_gate: bool = False
    #: share of a head's dims that take the rotary embedding, the first
    #: ones (``partial_rotary_factor``); a kind's ``rotary_dim`` overrides it
    partial_rotary_factor: float = 1.0
    # linear-attention layers (gated delta rule): value heads, key heads
    # (None: as many as value heads; fewer: value head ``j`` reads key head
    # ``j // (value heads / key heads)``), their key and value sizes, taps
    # of the causal depthwise convolution, and whether beta spans (0, 2)
    # (``linear_allow_neg_eigval``) or (0, 1)
    linear_num_heads: int = 0
    linear_num_key_heads: Optional[int] = None
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = False
    #: width of one attention head; None: ``d_model // num_heads``
    head_dim: Optional[int] = None
    #: "rms": RMSNorm; "layer": LayerNorm (mean subtracted, a learned scale,
    #: no bias), both with ``rms_norm_eps``; "zero_centred": RMSNorm whose
    #: scale is ``1 + w``, ``w`` initialised at 0 (Qwen3-Next)
    norm: str = "rms"
    #: "half": rotary pairs ``(i, i + d/2)`` (Llama, GPT-NeoX);
    #: "interleaved": pairs ``(2i, 2i + 1)`` (GPT-J)
    rope_style: str = "half"
    #: layer kinds that get the rotary embedding; None: every attention
    #: layer (where ``rope_theta`` is set at all)
    rope_layers: Optional[Tuple[str, ...]] = None
    #: keys a ``sliding_attention`` layer's query sees: key ``j`` is visible
    #: to query ``i`` iff ``i - sliding_window < j <= i``
    sliding_window: Optional[int] = None
    #: the logits are multiplied by it
    logit_scale: float = 1.0
    #: feed-forward kind of every layer (a key of :data:`FFNS`): "dense"
    #: (SwiGLU of width ``d_ff``) or "experts"
    #: (:class:`~synapseml_tpu.models.llm.experts.ExpertFFN`, of width
    #: ``expert_d_ff``); ``ffn_types`` says it layer by layer
    ffn: str = "dense"
    # expert layers: the router's width, experts a token selects, shared
    # experts every token takes (averaged), the width of one expert (None:
    # ``d_ff``), "sigmoid" or "softmax" selection scores, and whether the
    # selected scores are normalised to sum to one
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    expert_d_ff: Optional[int] = None
    #: width of the shared experts side by side (None: ``num_shared_experts
    #: * expert_d_ff``), and whether their sum is multiplied by
    #: ``sigmoid(x w_sg)``, a learned ``(d_model, 1)`` gate a token
    #: (Qwen2-MoE, Qwen3-Next: one shared expert of its own width)
    shared_expert_d_ff: Optional[int] = None
    shared_expert_gate: bool = False
    expert_selection: str = "softmax"
    norm_topk_prob: bool = False
    #: which routed experts THIS program holds: ``experts_held`` of them
    #: from ``experts_first`` on (None: all ``num_experts``).  The router
    #: keeps its width and every token its ``num_experts_per_tok``; the
    #: pairs of absent experts are left out of the layer's result
    experts_first: int = 0
    experts_held: Optional[int] = None
    #: what a layer kind's attention overrides: ``{kind: AttentionKind}``
    #: (or the dict of its fields); kept as a sorted tuple of pairs
    attention_kinds: Optional[Any] = None
    #: feed-forward kind of EACH layer (keys of :data:`FFNS`); None: ``ffn``
    #: for every layer
    ffn_types: Optional[Tuple[str, ...]] = None
    #: the router selects by ``score + bias`` (a learned float32 bias an
    #: expert) and weighs by ``score`` alone
    expert_selection_bias: bool = False
    #: group-limited selection: the router's experts lie in
    #: ``expert_groups`` equal groups by index, a group scores the sum of its
    #: two largest selection values, and a token selects among the experts
    #: of its ``expert_groups_kept`` best groups alone (1: no groups)
    expert_groups: int = 1
    expert_groups_kept: int = 1
    #: the routed experts' weights are multiplied by it, after any
    #: normalisation
    routed_scaling_factor: float = 1.0
    # latent attention (``latent_attention`` layers, :class:`LatentAttention`):
    # the query's latent width (None: q from the residual), the K/V
    # latent's, a head's part without and with the rotary embedding, and a
    # value head's width
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: scaled rotary embeddings of latent attention: None, or a ``yarn``
    #: mapping (``factor``, ``original_max_position_embeddings``,
    #: ``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``; kept as
    #: sorted pairs): :func:`yarn_rope`
    rope_scaling: Optional[Any] = None

    def __post_init__(self):
        if self.rope_scaling is not None:
            self.rope_scaling = tuple(sorted(dict(self.rope_scaling).items()))
            kind = dict(self.rope_scaling).get(
                "type", dict(self.rope_scaling).get("rope_type"))
            if kind != "yarn":
                raise ValueError(f"rope_scaling of type {kind!r}: only yarn "
                                 "is supported")
            if set(self.attention_layer_kinds) - {"latent_attention"}:
                raise ValueError("rope_scaling acts on latent_attention "
                                 "layers alone")
        if self.expert_groups_kept > self.expert_groups \
                or self.num_experts % max(1, self.expert_groups):
            raise ValueError(
                f"{self.num_experts} experts in {self.expert_groups} groups, "
                f"{self.expert_groups_kept} kept")
        if self.attention_kinds is not None:
            pairs = dict(self.attention_kinds)
            unknown = set(pairs) - {"full_attention", "sliding_attention"}
            if unknown:
                raise ValueError(f"attention_kinds names {sorted(unknown)}: "
                                 "not attention layer kinds")
            self.attention_kinds = tuple(sorted(
                (k, v if isinstance(v, AttentionKind) else AttentionKind(**v))
                for k, v in pairs.items()))
        if self.ffn_types is not None:
            self.ffn_types = tuple(self.ffn_types)
            if len(self.ffn_types) != self.num_layers:
                raise ValueError(
                    f"ffn_types names {len(self.ffn_types)} layers, "
                    f"num_layers is {self.num_layers}")
            unknown = set(self.ffn_types) - set(FFNS)
            if unknown:
                raise ValueError(f"ffn_types={sorted(unknown)}; the model "
                                 f"has {sorted(FFNS)}")
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)      # hashable
            if len(self.layer_types) != self.num_layers:
                raise ValueError(
                    f"layer_types names {len(self.layer_types)} layers, "
                    f"num_layers is {self.num_layers}")
            unknown = set(self.layer_types) - set(MIXERS)
            if unknown:
                raise ValueError(f"unknown layer kinds {sorted(unknown)}; "
                                 f"the model has {sorted(MIXERS)}")
        if self.rope_layers is not None:
            self.rope_layers = tuple(self.rope_layers)
        if self.norm_order not in ("pre", "post", "parallel"):
            raise ValueError(f"norm_order={self.norm_order!r}")
        if self.norm not in ("rms", "layer", "zero_centred"):
            raise ValueError(f"norm={self.norm!r}")
        if self.qk_norm and self.qk_head_norm:
            raise ValueError("qk_norm and qk_head_norm: q and k take one "
                             "norm, over the whole width or by head")
        if not 0.0 < self.partial_rotary_factor <= 1.0:
            raise ValueError(
                f"partial_rotary_factor={self.partial_rotary_factor}")
        if "linear_attention" in self.layer_kinds \
                and self.linear_num_heads % self.linear_key_heads:
            raise ValueError(
                f"{self.linear_num_heads} linear value heads do not divide "
                f"into {self.linear_key_heads} key heads")
        if self.rope_style not in ("half", "interleaved"):
            raise ValueError(f"rope_style={self.rope_style!r}")
        if self.ffn not in FFNS:
            raise ValueError(f"ffn={self.ffn!r}; the model has {sorted(FFNS)}")
        if "sliding_attention" in self.layer_kinds \
                and not self.sliding_window:
            raise ValueError("sliding_attention layers need sliding_window")
        for kind in self.attention_layer_kinds:
            if kind == "latent_attention":
                continue
            a = self.attention(kind)
            if self.num_heads % a.num_kv_heads:
                raise ValueError(
                    f"{kind}: {self.num_heads} query heads do not divide "
                    f"into {a.num_kv_heads} K/V heads")
            if a.rotary_dim % 2 or a.rotary_dim > a.head_dim:
                raise ValueError(
                    f"{kind}: rotary_dim={a.rotary_dim} of a head of "
                    f"{a.head_dim}")
        if self.has_experts:
            if self.expert_selection not in ("sigmoid", "softmax"):
                raise ValueError(
                    f"expert_selection={self.expert_selection!r}")
            if not 1 <= self.num_experts_per_tok <= self.num_experts:
                raise ValueError(
                    f"num_experts_per_tok={self.num_experts_per_tok} of "
                    f"num_experts={self.num_experts}")
            if self.experts_first < 0 or self.experts_first \
                    + self.experts_held_count > self.num_experts:
                raise ValueError(
                    f"experts {self.experts_first}..+{self.experts_held} "
                    f"are not among the router's {self.num_experts}")
            if self.weight_quant != "none":
                raise ValueError("expert layers have no int8 path")
        if "latent_attention" in self.layer_kinds:
            if not (self.kv_lora_rank and self.qk_rope_head_dim
                    and self.qk_nope_head_dim and self.v_head_dim):
                raise ValueError("latent_attention layers need kv_lora_rank, "
                                 "qk_nope_head_dim, qk_rope_head_dim and "
                                 "v_head_dim")
            if self.weight_quant != "none":
                raise ValueError("latent attention has no int8 path")

    @property
    def d_head(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def linear_key_heads(self) -> int:
        """Key heads of a linear-attention layer (value heads where none is
        named)."""
        return self.linear_num_key_heads or self.linear_num_heads

    @property
    def experts_held_count(self) -> int:
        """Routed experts this program holds (all where none is named)."""
        return self.num_experts if self.experts_held is None \
            else int(self.experts_held)

    @property
    def ffn_kinds(self) -> Tuple[str, ...]:
        return self.ffn_types or (self.ffn,) * self.num_layers

    @property
    def has_experts(self) -> bool:
        return "experts" in self.ffn_kinds

    @property
    def num_expert_layers(self) -> int:
        return self.ffn_kinds.count("experts")

    def packed(self, kind: str) -> bool:
        """Whether ``kind`` keeps its K/V rows packed: it has an entry in
        ``attention_kinds``, or an unpacked row of the description's heads
        would be relaid every step (:func:`~synapseml_tpu.models.llm
        .pallas_attn.row_relaid`: 2 K/V heads of 256)."""
        from .pallas_attn import row_relaid
        return kind in dict(self.attention_kinds or ()) \
            or row_relaid(self.kv_cache_heads, self.d_head)

    def attention(self, kind: str) -> AttentionKind:
        """The attention of layer kind ``kind`` with every default filled
        in (``rope_theta`` None: the kind takes no rotary embedding)."""
        o = dict(self.attention_kinds or ()).get(kind) or AttentionKind()
        d = o.head_dim or self.d_head
        roped = self.rope_theta is not None and (
            self.rope_layers is None or kind in self.rope_layers)
        theta = o.rope_theta if o.rope_theta is not None else \
            self.rope_theta if roped else None
        return AttentionKind(
            num_kv_heads=o.num_kv_heads or self.num_kv_heads, head_dim=d,
            v_head_dim=o.v_head_dim or d,
            rotary_dim=o.rotary_dim or int(d * self.partial_rotary_factor),
            rope_theta=theta, sink=o.sink, value_scale=o.value_scale)

    @property
    def attention_layer_kinds(self) -> Tuple[str, ...]:
        """The attention kinds the model has, each once, in layer order."""
        return tuple(dict.fromkeys(
            k for k in self.layer_kinds if k != "linear_attention"))

    @property
    def kv_cache_heads(self) -> int:
        """K/V heads a cache row holds: ``num_kv_heads``, rounded up to the
        cache dtype's sublane multiple where they pass one tile of it and
        do not fill their last (30 bfloat16 heads -> 32).  Unpadded, XLA
        lays such a cache out with positions minor for the step's scatter
        and copies all of it, every layer and step, into the row-major
        order the paged kernel reads (6 GB of temporaries at 30 heads,
        32 x 1536)."""
        from .pallas_attn import cache_row_heads
        return cache_row_heads(self.num_kv_heads, self.dtype)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return self.layer_types or ("full_attention",) * self.num_layers

    @property
    def num_attention_layers(self) -> int:
        """Layers that keep K/V rows by position (full, window, latent)."""
        return self.layer_kinds.count("full_attention") \
            + self.num_window_layers \
            + self.layer_kinds.count("latent_attention")

    @property
    def num_window_layers(self) -> int:
        return self.layer_kinds.count("sliding_attention")

    @property
    def num_recurrent_layers(self) -> int:
        return self.layer_kinds.count("linear_attention")

    @staticmethod
    def from_hf(hc: Dict[str, Any], **kw) -> "LlamaConfig":
        """The description from a ``transformers`` ``config.json``'s keys
        (``kw`` overrides: ``max_len``, ``dtype``...).  ``model_type``
        ``olmo_hybrid`` brings the family's block order and query/key
        norm, which its config does not spell out; ``cohere2_moe`` brings
        LayerNorm, rotary embeddings on the window layers alone, and what
        its keys say in the family's own words (``use_parallel_block``,
        ``position_embedding_type`` ``rope_gptj``, ``use_qk_norm``);
        ``mimo_v2`` brings attention by layer kind (``hybrid_layer_pattern``
        1: ``sliding_attention`` with the ``swa_*`` keys, 0:
        ``full_attention``), ``v_head_dim``, ``partial_rotary_factor``,
        ``attention_value_scale``, the sink logits and a feed-forward kind by
        layer (``moe_layer_freq``).  A config with ``kv_lora_rank`` (``axk1``,
        the DeepSeek-V3 family) is every layer ``latent_attention`` with the
        ``q_lora_rank``, ``qk_*_head_dim`` and ``v_head_dim`` keys, rotary
        pairs ``(2i, 2i + 1)`` and a ``yarn`` ``rope_scaling``.  The router's
        keys are read for every family: ``n_routed_experts`` or
        ``num_experts``, ``scoring_func``, ``n_group`` and ``topk_group``,
        ``routed_scaling_factor``, ``topk_method`` ``noaux_tc`` (the selection
        bias), ``decoder_sparse_step`` and ``mlp_only_layers``, and a shared
        expert of its own width behind a sigmoid gate
        (``shared_expert_intermediate_size``).  ``partial_rotary_factor`` is
        read for every family, and so is ``full_attention_interval`` (layer
        ``i`` full attention iff ``(i + 1) % interval == 0``, the others
        ``linear_attention``) where ``layer_types`` is not given.
        ``qwen3_next`` brings the family's zero-centred norms, q/k norms by
        head and the sigmoid gate on attention's output; its linear layers
        may have fewer key heads than value heads
        (``linear_num_key_heads``).  A key whose mechanism this description
        does not have is refused, whatever the family
        (:func:`_refuse_unhonoured`)."""
        _refuse_unhonoured(hc)
        if hc.get("model_type") == "mimo_v2":
            hc, kw = _mimo_v2_keys(hc), {**_mimo_v2_args(hc), **kw}
        rope = hc.get("rope_parameters") or {}
        theta = rope["rope_theta"] if "rope_theta" in rope \
            else hc.get("rope_theta", 10_000.0)   # HF's default (Llama-1/2)
        olmo = hc.get("model_type") == "olmo_hybrid"
        cohere = hc.get("model_type") == "cohere2_moe"
        qwen_next = hc.get("model_type") == "qwen3_next"
        if qwen_next and hc.get("use_sliding_window"):
            raise ValueError("use_sliding_window=True is not supported for "
                             "qwen3_next: its full-attention layers see "
                             "every position")
        latent = bool(hc.get("kv_lora_rank"))
        n = hc["num_hidden_layers"]
        layer_types = hc.get("layer_types")
        if layer_types is None and hc.get("full_attention_interval"):
            every = int(hc["full_attention_interval"])
            layer_types = ["linear_attention" if (i + 1) % every
                           else "full_attention" for i in range(n)]
        eps = hc.get("rms_norm_eps")
        layer_norm = eps is None and hc.get("layer_norm_eps") is not None
        if eps is None:
            eps = hc.get("layer_norm_eps", hc.get("layernorm_epsilon", 1e-5))
        args = dict(
            vocab_size=hc["vocab_size"], d_model=hc["hidden_size"],
            num_layers=n,
            num_heads=hc["num_attention_heads"],
            num_kv_heads=hc.get("num_key_value_heads",
                                hc["num_attention_heads"]),
            d_ff=hc["intermediate_size"],
            max_len=int(hc.get("max_position_embeddings", 8192)),
            rope_theta=None if theta is None else float(theta),
            rms_norm_eps=float(eps),
            tie_embeddings=bool(hc.get("tie_word_embeddings", False)),
            layer_types=layer_types,
            norm_order="post" if olmo else "parallel"
            if hc.get("use_parallel_block") else "pre",
            qk_norm=olmo or bool(hc.get("use_qk_norm", False)),
            qk_head_norm=qwen_next, attn_output_gate=qwen_next,
            partial_rotary_factor=float(
                hc.get("partial_rotary_factor") or 1.0),
            head_dim=hc.get("head_dim"),
            norm="layer" if layer_norm else "zero_centred" if qwen_next
            else "rms",
            rope_style="interleaved"
            if hc.get("position_embedding_type") == "rope_gptj" else "half",
            rope_layers=("sliding_attention",) if cohere else None,
            sliding_window=hc.get("sliding_window"),
            logit_scale=float(hc.get("logit_scale", 1.0)))
        if latent:
            args.update(
                layer_types=("latent_attention",) * n, rope_style="interleaved",
                q_lora_rank=hc.get("q_lora_rank"),
                kv_lora_rank=hc["kv_lora_rank"],
                qk_nope_head_dim=hc["qk_nope_head_dim"],
                qk_rope_head_dim=hc["qk_rope_head_dim"],
                v_head_dim=hc["v_head_dim"],
                rope_scaling=_rope_scaling(hc))
        experts = hc.get("num_experts") or hc.get("n_routed_experts")
        if experts:
            args.update(
                ffn="experts", num_experts=experts,
                num_experts_per_tok=hc["num_experts_per_tok"],
                num_shared_experts=hc.get("num_shared_experts",
                                          hc.get("n_shared_experts") or 0),
                expert_d_ff=hc.get("moe_intermediate_size"),
                expert_selection=hc.get("expert_selection_fn",
                                        hc.get("scoring_func", "softmax")),
                norm_topk_prob=bool(hc.get("norm_topk_prob", False)),
                expert_selection_bias=hc.get("topk_method") == "noaux_tc",
                expert_groups=int(hc.get("n_group") or 1),
                expert_groups_kept=int(hc.get("topk_group") or 1),
                routed_scaling_factor=float(
                    hc.get("routed_scaling_factor") or 1.0))
            if hc.get("shared_expert_intermediate_size"):
                args.update(num_shared_experts=1, shared_expert_gate=True,
                            shared_expert_d_ff=int(
                                hc["shared_expert_intermediate_size"]))
        if "linear_num_value_heads" in hc:
            nv = int(hc["linear_num_value_heads"])
            nk = int(hc.get("linear_num_key_heads") or nv)
            if nv % nk:
                raise ValueError(
                    f"linear_num_value_heads={nv} is not a multiple of "
                    f"linear_num_key_heads={nk}")
            args.update(
                linear_num_heads=nv,
                linear_num_key_heads=None if nk == nv else nk,
                linear_key_head_dim=hc["linear_key_head_dim"],
                linear_value_head_dim=hc["linear_value_head_dim"],
                linear_conv_kernel_dim=hc.get("linear_conv_kernel_dim", 4),
                linear_allow_neg_eigval=bool(
                    hc.get("linear_allow_neg_eigval", False)))
        dense = set(range(int(hc.get("first_k_dense_replace") or 0))) \
            | set(hc.get("mlp_only_layers") or ())
        freq = hc.get("moe_layer_freq")
        if isinstance(freq, (list, tuple)):
            dense |= {i for i, on in enumerate(freq) if not on}
        elif freq:                              # every freq-th layer
            dense |= {i for i in range(n) if i % int(freq)}
        step = int(hc.get("decoder_sparse_step") or 1)
        dense |= {i for i in range(n) if (i + 1) % step}
        if args.get("ffn") == "experts" and dense:
            args["ffn_types"] = tuple(
                "dense" if i in dense else "experts" for i in range(n))
        args.update(kw)
        return LlamaConfig(**args)

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama3_1b(**kw) -> "LlamaConfig":
        return LlamaConfig(d_model=2048, num_layers=16, num_heads=32,
                           num_kv_heads=8, d_ff=8192, tie_embeddings=True,
                           **kw)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Test config: byte vocab, 4 layers."""
        kw.setdefault("vocab_size", 512)
        kw.setdefault("d_model", 128)
        kw.setdefault("num_layers", 4)
        kw.setdefault("num_heads", 8)
        kw.setdefault("num_kv_heads", 4)
        kw.setdefault("d_ff", 256)
        kw.setdefault("max_len", 256)
        return LlamaConfig(**kw)


#: keys of mechanisms this description does not have, and the values
#: under which they say nothing: any other value is refused by
#: :meth:`LlamaConfig.from_hf`, whatever the family
_UNHONOURED = (
    ("index_topk", (None,)),                      # sparse selection of keys
    ("hc_mult", (None, 1)),                       # several residual streams
    ("enable_ihc", (None, False)),
    ("num_nextn_predict_layers", (None, 0)),      # multi-token prediction
    ("hybrid_block_size", (None,)),
    ("topk_method", (None, "none", "greedy", "noaux_tc")),
)


def _refuse_unhonoured(hc: Dict[str, Any]) -> None:
    """A ``ValueError`` for a key whose shape the description cannot
    honour, rather than a model that silently leaves its mechanism out."""
    for key, plain in _UNHONOURED:
        if hc.get(key) not in plain:
            raise ValueError(f"{key}={hc[key]!r} is not supported")
    if int(hc.get("n_shared_experts") or 0) > 1:
        raise ValueError(f"n_shared_experts={hc['n_shared_experts']}: shared "
                         "experts of this spelling are summed, ExpertFFN "
                         "averages them")
    for key in ("rope_scaling", "rope_parameters"):
        kind = (hc.get(key) or {}).get(
            "rope_type", (hc.get(key) or {}).get("type"))
        if kind not in (None, "default") and not (
                kind == "yarn" and hc.get("kv_lora_rank")):
            raise ValueError(f"{key} of type {kind!r} is not supported")


def _rope_scaling(hc: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """A ``yarn`` ``rope_scaling`` as :class:`LlamaConfig` keeps it."""
    rs = hc.get("rope_scaling") or {}
    if rs.get("type", rs.get("rope_type")) != "yarn":
        return None
    return {"type": "yarn", "factor": float(rs["factor"]),
            "original_max_position_embeddings":
                int(rs["original_max_position_embeddings"]),
            "beta_fast": float(rs.get("beta_fast", 32)),
            "beta_slow": float(rs.get("beta_slow", 1)),
            "mscale": float(rs.get("mscale", 1)),
            "mscale_all_dim": float(rs.get("mscale_all_dim", 0))}


def _mimo_v2_keys(hc: Dict[str, Any]) -> Dict[str, Any]:
    """A ``mimo_v2`` config's keys under the names :meth:`LlamaConfig.from_hf`
    reads from every family."""
    if hc.get("swa_num_attention_heads",
              hc["num_attention_heads"]) != hc["num_attention_heads"]:
        raise ValueError("window layers with another number of query heads "
                         "than full layers are not supported")
    return dict(
        hc, layer_types=["sliding_attention" if w else "full_attention"
                         for w in hc["hybrid_layer_pattern"]])


def _mimo_v2_args(hc: Dict[str, Any]) -> Dict[str, Any]:
    """What a ``mimo_v2`` config says of attention by layer kind, as
    :class:`LlamaConfig` arguments."""
    factor = float(hc.get("partial_rotary_factor", 1.0))
    scale = float(hc.get("attention_value_scale") or 1.0)

    def kind(pre, sink_key):
        d = hc.get(pre + "head_dim", hc["head_dim"])
        return AttentionKind(
            num_kv_heads=hc.get(pre + "num_key_value_heads",
                                hc["num_key_value_heads"]),
            head_dim=d, v_head_dim=hc.get(pre + "v_head_dim", d),
            rotary_dim=int(d * factor),
            rope_theta=float(hc.get(pre + "rope_theta", hc["rope_theta"])),
            sink=bool(hc.get(sink_key, False)), value_scale=scale)
    return dict(attention_kinds={
        "full_attention": kind("", "add_full_attention_sink_bias"),
        "sliding_attention": kind("swa_", "add_swa_attention_sink_bias")})


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale``; ``zero_centred``: ``* (1 +
    w)`` with ``w`` initialised at 0 (Qwen3-Next's form)."""
    eps: float
    dtype: Any
    zero_centred: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.with_partitioning(
            nn.initializers.zeros if self.zero_centred
            else nn.initializers.ones, ("embed",)), (x.shape[-1],))
        if self.zero_centred:
            scale = 1.0 + scale.astype(jnp.float32)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        normed = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
        return (normed * scale).astype(self.dtype)


class LayerNorm(nn.Module):
    """``(x - mean) / sqrt(var + eps) * scale``: no bias."""
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.with_partitioning(
            nn.initializers.ones, ("embed",)), (x.shape[-1],))
        x = x.astype(jnp.float32)
        x = x - jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x), -1, keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps) * scale).astype(self.dtype)


def _norm(cfg: "LlamaConfig", name: str):
    if cfg.norm == "layer":
        return LayerNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
    return RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                   zero_centred=cfg.norm == "zero_centred", name=name)


def rope_frequencies(d_head: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, d_head, 2, np.float32) / d_head))


def yarn_rope(d: int, theta: float, scaling: Dict[str, Any]
              ) -> Tuple[np.ndarray, float, float]:
    """YaRN's rotary embedding for heads of ``d`` rotated dims: ``(inverse
    frequencies (d/2,) float32, the scale of cos and sin, the factor on the
    softmax scale)``.  Pair ``i``'s base frequency ``f_i = theta^(-2i/d)``
    becomes ``f_i / factor * r_i + f_i * (1 - r_i)``, ``r_i`` the ramp
    ``clip((i - low) / (high - low), 0, 1)`` between the dims that turn
    ``beta_fast`` and ``beta_slow`` times over the original context; cos and
    sin are scaled by ``m(mscale) / m(mscale_all_dim)`` and the softmax
    scale by ``m(mscale_all_dim)^2``, ``m(a) = 0.1 a ln(factor) + 1``
    (DeepSeek-V3's ``yarn_get_mscale``; 1 where ``a`` is 0 or the factor at
    most 1)."""
    s = dict(scaling)
    factor, orig = float(s["factor"]), float(
        s["original_max_position_embeddings"])

    def dim_of(turns):              # the dim that turns ``turns`` times
        return d * np.log(orig / (turns * 2 * np.pi)) / (2 * np.log(theta))
    low = max(np.floor(dim_of(float(s.get("beta_fast", 32)))), 0)
    high = min(np.ceil(dim_of(float(s.get("beta_slow", 1)))), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    base = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inv = base / factor * ramp + base * (1 - ramp)

    def m(a):
        return 1.0 if factor <= 1 or not a else 0.1 * a * np.log(factor) + 1
    return (inv.astype(np.float32),
            m(float(s.get("mscale", 1))) / m(float(s.get("mscale_all_dim", 0))),
            m(float(s.get("mscale_all_dim", 0))) ** 2)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float, style: str = "half",
               rotary_dim: Optional[int] = None,
               freqs: Optional[np.ndarray] = None,
               mscale: float = 1.0) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) absolute token positions.
    ``style`` "half" rotates the pairs ``(i, i + D/2)``, "interleaved" the
    pairs ``(2i, 2i + 1)``; pair ``i`` turns by ``theta^(-2i/D)`` a
    position in both, or by ``freqs[i]`` where given, with cos and sin
    scaled by ``mscale`` (:func:`yarn_rope`).  ``rotary_dim`` under ``D``:
    the first ``rotary_dim`` dims are rotated as a head of that width, the
    rest pass untouched."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        turned = apply_rope(x[..., :rotary_dim], positions, theta, style)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    inv = jnp.asarray(rope_frequencies(d, theta) if freqs is None
                      else freqs)                      # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * inv   # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    if style == "interleaved":
        # out[2i] = x[2i] cos_i - x[2i+1] sin_i, out[2i+1] = x[2i+1] cos_i +
        # x[2i] sin_i, with each element's partner brought beside it by a
        # roll along the lanes: a reshape to (..., D/2, 2) would put 2 on
        # the minor dimension, and XLA then relays q_proj's whole weight
        # into that order every step
        xf = x.astype(jnp.float32)
        even = (jnp.arange(d) % 2 == 0)
        partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                            jnp.roll(xf, 1, axis=-1))
        out = xf * jnp.repeat(cos, 2, axis=-1) \
            + partner * jnp.repeat(sin, 2, axis=-1)
        return out.astype(x.dtype)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class QuantDense(nn.Module):
    """int8 weight-only Dense: per-output-channel scales applied AFTER the
    matmul (a per-column scale commutes with the contraction), so the MXU
    consumes the int8 weights cast to compute dtype tile-by-tile — no
    dequantized copy is ever materialized in HBM."""
    features: int
    axes: Tuple[str, ...]
    dtype: Any

    @nn.compact
    def __call__(self, x):
        kq = self.param("kernel_q", nn.with_partitioning(
            nn.initializers.zeros_init(), self.axes),
            (x.shape[-1], self.features), jnp.int8)
        scale = self.param("scale", nn.with_partitioning(
            nn.initializers.ones_init(), (self.axes[-1],)),
            (self.features,), jnp.float32)
        y = jax.lax.dot_general(x, kq.astype(self.dtype),
                                (((x.ndim - 1,), (0,)), ((), ())))
        return y * scale.astype(self.dtype)


class QuantEmbed(nn.Module):
    """int8 tied embedding: one (V, D) int8 table with per-VOCAB-ROW
    scales serves both the input gather (exact per-row dequant) and the
    output ``attend`` head (the per-row scale commutes out of the
    contraction over D, multiplying the logits columnwise).  Decode
    streams the table at half bf16 width — on Llama-1B the table is a
    third of all weight bytes, so this is the largest single-tensor
    bandwidth win the int8 path has."""
    vocab_size: int
    features: int
    dtype: Any

    def setup(self):
        self.embedding_q = self.param(
            "embedding_q", nn.with_partitioning(
                nn.initializers.zeros_init(), ("vocab", "embed")),
            (self.vocab_size, self.features), jnp.int8)
        self.scale = self.param(
            "scale", nn.with_partitioning(
                nn.initializers.ones_init(), ("vocab",)),
            (self.vocab_size,), jnp.float32)

    def __call__(self, ids):
        return (self.embedding_q[ids].astype(self.dtype)
                * self.scale[ids].astype(self.dtype)[..., None])

    def attend(self, x):
        logits = jax.lax.dot_general(
            x, self.embedding_q.astype(x.dtype),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return logits * self.scale


def _dense(features, axes, name, dtype, quant: str = "none"):
    if quant == "int8":
        return QuantDense(features, axes, dtype, name=name)
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name,
                    kernel_init=nn.with_partitioning(
                        nn.initializers.truncated_normal(0.02), axes))


def projection_fold_cut(rows: int, width: int) -> bool:
    """Whether a projection of ``rows`` rows (``B * S`` of the pass) from
    ``width`` input features is kept from folding its split into heads (or,
    at the output projection, the merge of heads before it) into the product.

    Folded, XLA reads the weight ``(width, heads * d)`` as ``[heads, d,
    width]`` and relays all of it into that order on every call; cut, the
    product reads the weight as it is stored and any relayout falls on the
    activation, ``rows x heads * d``.  So cut where the activation is the
    smaller, ``rows < width``: every decode step and the shorter prefills; a
    prefill of more rows than the width (16,384 tokens over 4,096) keeps the
    fold and relays the weight, the cheaper there."""
    return rows < width


def projection_layout(cfg: "LlamaConfig", rows: int) -> Dict[str, int]:
    """``{"cut": n, "kept": m}``: the projection sites of a pass of ``rows``
    rows over every layer of ``cfg``, by :func:`projection_fold_cut`."""
    out = {"cut": 0, "kept": 0}
    for kind in cfg.layer_kinds:
        for _, width in MIXERS[kind].projections(cfg):
            out["cut" if projection_fold_cut(rows, width) else "kept"] += 1
    return out


def _project(x, features, axes, name, cfg: "LlamaConfig", proj: str):
    """``_dense(features)(x)``, a product whose result is split into heads,
    through :func:`_fold_barrier`."""
    y = _dense(features, axes, name, cfg.dtype, cfg.weight_quant)(x)
    return _fold_barrier(y, x.shape[-1], proj)


def _fold_barrier(y, width: int, proj: str):
    """``y`` as it is, or behind ``lax.optimization_barrier`` (an identity
    XLA cannot fold a reshape through) where :func:`projection_fold_cut`
    says so for ``y``'s rows and ``width``; counted at trace time."""
    from ...telemetry import get_registry
    cut = projection_fold_cut(int(np.prod(y.shape[:-1])), int(width))
    get_registry().counter(
        "llm_projection_layout_total",
        "attention projection sites traced, by site (q, k, v, o) and by "
        "whether the product was kept from folding the split into heads "
        "(cut) or not (kept)", ("proj", "choice")).inc(
            proj=proj, choice="cut" if cut else "kept")
    return jax.lax.optimization_barrier(y) if cut else y


def init_cache(cfg: LlamaConfig, batch: int, max_len: int) -> List[Dict]:
    """Per-layer cache pytree: each layer's entry is what its mixer kind
    declares (``MIXERS[kind].cache_entry``): K/V rows by token position
    for full attention, a recurrent state and a convolution window,
    whatever ``max_len``, for linear attention.

    ``batch`` doubles as the SLOT axis for continuous-batching serving
    (:mod:`synapseml_tpu.models.llm.slots`): each row is one independent
    sequence slot, written at its own per-slot offset via the vector
    ``cache_index`` path and protected by ``slot_mask`` so retired slots
    keep their K/V intact as prefix-cache source material."""
    return [MIXERS[kind].cache_entry(cfg, batch, max_len)
            for kind in cfg.layer_kinds]


def _ring_pass(ring, new, start, n_valid):
    """One pass at a scalar offset over a ring of ``R`` rows (position
    ``p`` lies in row ``p mod R``).  ``ring (B, R, ...)``; ``new (B, S,
    ...)`` the rows of positions ``start .. start + S - 1`` of which the
    first ``n_valid`` are real.  -> ``(the ring after the pass, the rows of
    positions start - R .. start - 1 in that order)``.  Only real rows are
    written, the last ``R`` of them: a ring has no room beyond a slot's
    length where a bucket's padding could land unread."""
    B, R, S = ring.shape[0], ring.shape[1], new.shape[1]
    r = jnp.arange(R)
    ctx = jnp.take(ring, (start + r) % R, axis=1)
    n = S if n_valid is None else n_valid
    last = jnp.broadcast_to(jnp.asarray(start + n - 1, jnp.int32), (B,))
    pos = last[:, None] - (last[:, None] - r[None, :]) % R     # (B, R)
    src = jnp.clip(pos - start, 0, S - 1)
    tail = (1,) * (ring.ndim - 2)
    rows = jnp.take_along_axis(new, src.reshape((B, R) + tail), axis=1)
    return jnp.where((pos >= start).reshape((B, R) + tail), rows, ring), ctx


class CausalAttention(nn.Module):
    """Grouped-query softmax attention over K/V rows kept by position.
    :class:`SlidingAttention` is the same layer behind a window.

    What the layer's kind overrides (``LlamaConfig.attention``): K/V heads,
    the key's and the value's width, rotary dims and theta, a sink logit a
    head, a scale on the values.  A kind with an ``attention_kinds`` entry
    keeps its cache PACKED: flat rows ``(slots, rows * KV / f, f * width)``,
    ``f`` heads side by side (:func:`kv_pack`), the layout the paged kernel
    reads, so that a 192-wide key costs no lane of padding and the step
    relays nothing; so does a kind whose unpacked rows would be relaid
    (``LlamaConfig.packed``); every other kind keeps ``(slots, rows, KV,
    d_head)``."""
    cfg: LlamaConfig

    #: the layer kind (``layer_types`` entry) this class serves
    KIND = "full_attention"

    @property
    def window(self) -> Optional[int]:
        return None

    @classmethod
    def cache_rows(cls, cfg: LlamaConfig, max_len: int) -> int:
        """Rows a slot keeps in this kind's cache entry."""
        return max_len

    @classmethod
    def projections(cls, cfg: LlamaConfig) -> Tuple[Tuple[str, int], ...]:
        """``(site, input width)`` of each product that meets a split into
        heads or their merge: what :func:`_fold_barrier` decides at."""
        a = cfg.attention(cls.KIND)
        return (("q", cfg.d_model), ("k", cfg.d_model), ("v", cfg.d_model),
                ("o", cfg.num_heads * a.v_head_dim))

    @classmethod
    def cache_entry(cls, cfg: LlamaConfig, batch: int, max_len: int) -> Dict:
        rows = cls.cache_rows(cfg, max_len)
        if not cfg.packed(cls.KIND):
            shape = (batch, rows, cfg.kv_cache_heads, cfg.d_head)
            return {"k": jnp.zeros(shape, cfg.dtype),
                    "v": jnp.zeros(shape, cfg.dtype)}
        a = cfg.attention(cls.KIND)
        f = kv_pack(a.head_dim, a.num_kv_heads)
        n = rows * (a.num_kv_heads // f)
        return {"k": jnp.zeros((batch, n, f * a.head_dim), cfg.dtype),
                "v": jnp.zeros((batch, n, f * a.v_head_dim), cfg.dtype)}

    def _prefill_tile(self, attention_backend: str, cache, cache_index,
                      S: int, T: int):
        """The prefill kernel's tile for a pass of ``S`` queries over ``T``
        key rows, or None: the pass is no prefill on a kernel backend
        (no cache, per-slot offsets, the dense backend) or
        :func:`~synapseml_tpu.models.llm.pallas_attn.prefill_geometry` has
        none for the shape."""
        if attention_backend not in ("paged", "interpret") or cache is None \
                or jnp.ndim(cache_index) != 0:
            return None
        from .pallas_attn import prefill_geometry
        a = self.cfg.attention(self.KIND)
        return prefill_geometry(S, T, self.cfg.num_heads, a.num_kv_heads,
                                a.head_dim, a.v_head_dim, self.cfg.dtype,
                                self.window)

    @nn.compact
    def __call__(self, x, positions, cache: Optional[Dict],
                 cache_index: Optional[jnp.ndarray],
                 slot_mask: Optional[jnp.ndarray] = None,
                 attention_backend: str = "dense",
                 paged_tile: Optional[Any] = None,
                 valid_len: Optional[jnp.ndarray] = None):
        # ``valid_len`` is the recurrent mixer's and the ring's: by position
        # a padded row's K/V lands beyond the slot's length and is
        # overwritten before it is read
        cfg = self.cfg
        a = cfg.attention(self.KIND)
        packed = cfg.packed(self.KIND)
        B, S, _ = x.shape
        H, KV, D, Dv = (cfg.num_heads, a.num_kv_heads, a.head_dim,
                        a.v_head_dim)
        gate = None
        if cfg.attn_output_gate:
            # [q_h | g_h] a head: the gate is a query head's twin
            qg = _project(x, 2 * H * D, ("embed", "heads"), "q_proj", cfg,
                          "q").reshape(B, S, H, 2 * D)
            q, gate = qg[..., :D], qg[..., D:].reshape(B, S, H * D)
        else:
            q = _project(x, H * D, ("embed", "heads"), "q_proj", cfg, "q")
        k = _project(x, KV * D, ("embed", "kv"), "k_proj", cfg, "k")
        v = _project(x, KV * Dv, ("embed", "kv"), "v_proj", cfg, "v")
        if cfg.qk_norm:
            q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)
        q, k = q.reshape(B, S, H, D), k.reshape(B, S, KV, D)
        if cfg.qk_head_norm:
            q, k = _norm(cfg, "q_norm")(q), _norm(cfg, "k_norm")(k)
        if a.rope_theta is not None:
            q = apply_rope(q, positions, a.rope_theta, cfg.rope_style,
                           a.rotary_dim)
            k = apply_rope(k, positions, a.rope_theta, cfg.rope_style,
                           a.rotary_dim)
        v = v.reshape(B, S, KV, Dv)
        if a.value_scale != 1.0:
            v = v * jnp.asarray(a.value_scale, v.dtype)
        sink = self.param("sink", nn.initializers.zeros_init(), (H,),
                          jnp.float32) if a.sink else None
        window = self.window
        f = kv_pack(D, KV) if packed else 1
        rpp = KV // f                 # flat rows a position takes, packed

        new_cache = None
        ring = False
        key_offset = None             # position of row 0 where it is not 0
        if cache is not None:
            T = cache["k"].shape[1] // rpp if packed else cache["k"].shape[1]
            # a window layer whose entry has exactly the ring's rows is a
            # ring (by position such an entry would hold the same rows: no
            # position reaches its end)
            ring = window is not None and T == ring_rows(window)
            if not packed and cfg.kv_cache_heads != KV:   # the row's padding
                pad = ((0, 0), (0, 0), (0, cfg.kv_cache_heads - KV), (0, 0))
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            # this pass's rows as the entry lays them
            k_new, v_new = k, v
            if packed:
                k_new = k.reshape(B, S * rpp, f * D)
                v_new = v.reshape(B, S * rpp, f * Dv)
            if jnp.ndim(cache_index) == 0 and ring:
                def through(entry, new):
                    if not packed:
                        return _ring_pass(entry, new, cache_index, valid_len)
                    out, ctx = _ring_pass(
                        entry.reshape(B, T, rpp, -1),
                        new.reshape(B, S, rpp, -1), cache_index, valid_len)
                    return out.reshape(entry.shape), ctx
                k_all, k_ctx = through(cache["k"], k_new)
                v_all, v_ctx = through(cache["v"], v_new)
                # attend over [the ring's rows before this pass | this pass]
                k_att = jnp.concatenate(
                    [k_ctx.reshape(B, T, -1, D)[:, :, :KV], k[:, :, :KV]], 1)
                v_att = jnp.concatenate(
                    [v_ctx.reshape(B, T, -1, Dv)[:, :, :KV], v[:, :, :KV]], 1)
                key_offset = cache_index - T
                key_pos = (key_offset + jnp.arange(T + S))[None, :]
                T = T + S
            elif jnp.ndim(cache_index) == 0:
                # write this step's K/V at cache_index, attend over prefix
                at = (0, cache_index * rpp, 0) if packed else \
                    (0, cache_index, 0, 0)
                k_all = jax.lax.dynamic_update_slice(cache["k"], k_new, at)
                v_all = jax.lax.dynamic_update_slice(cache["v"], v_new, at)
            else:
                # PER-SEQUENCE write offsets (B,) — speculative decoding
                # accepts a different number of tokens per sequence and
                # the slotted serving cache advances every slot at its
                # own position, so each row writes its S-token block at
                # its own offset.  Batched ``.at[].set`` scatter: exact
                # (one writer per position) and updatable IN PLACE when
                # the caller donates the cache — the earlier one-hot
                # matmul formulation materialized the ENTIRE cache every
                # step, which made decode cost scale with slots x
                # max_len instead of with the tokens actually written
                wpos = cache_index[:, None] + jnp.arange(S)[None, :]
                if ring:
                    wpos = wpos % T
                if packed:
                    wpos = (wpos[:, :, None] * rpp
                            + jnp.arange(rpp)).reshape(B, S * rpp)
                bidx = jnp.arange(B)[:, None]
                k_w, v_w = k_new, v_new
                if slot_mask is not None:
                    # ACTIVE-SLOT gate (continuous-batching serving): a
                    # row whose slot is inactive must not write — a
                    # retired slot's K/V is live prefix-cache material,
                    # and one junk write per step would silently corrupt
                    # it.  Masking the PAYLOAD (write back the old
                    # values, gathered (B, S) rows only) keeps the
                    # scatter shape — and its in-place update — intact.
                    m = slot_mask.reshape((B,) + (1,) * (k_new.ndim - 1))
                    k_w = jnp.where(m, k_new, cache["k"][bidx, wpos])
                    v_w = jnp.where(m, v_new, cache["v"][bidx, wpos])
                k_all = cache["k"].at[bidx, wpos].set(k_w)
                v_all = cache["v"].at[bidx, wpos].set(v_w)
            new_cache = {"k": k_all, "v": v_all}
            if key_offset is None:
                if packed:
                    k_att = k_all.reshape(B, T, KV, D)
                    v_att = v_all.reshape(B, T, KV, Dv)
                else:
                    k_att, v_att = k_all[:, :, :KV], v_all[:, :, :KV]
                key_pos = jnp.arange(T)[None, :]                # (1, T)
                if ring:
                    # per-slot positions: row r holds the newest position
                    # congruent to r that the slot has written
                    last = positions[:, -1:]
                    key_pos = last - (last - key_pos) % T       # (B, T)
            qpos = positions[:, :, None]                        # (B, S, 1)
            causal = key_pos[:, None, :] <= qpos                # (B, S, T)
            if window is not None:
                causal &= key_pos[:, None, :] > qpos - window
            if ring:
                causal &= key_pos[:, None, :] >= 0
        else:
            k_att, v_att = k, v
            T = S
            causal = jnp.tril(jnp.ones((S, S), bool))[None]     # (1, S, S)
            if window is not None:
                causal &= ~jnp.tril(jnp.ones((S, S), bool), -window)[None]

        if (attention_backend in ("paged", "interpret")
                and cache is not None and jnp.ndim(cache_index) != 0):
            # paged decode read: each slot attends ONLY its live K/V
            # span through the Pallas online-softmax kernel — bytes
            # scale with live tokens, not cache capacity (the
            # vector-cache_index step is the serving hot loop: S == 1
            # plain decode, S > 1 the speculative-verify span whose S
            # queries amortize one span read; a prefill pass takes the
            # branch below, training the plain scores).
            # ``paged_tile`` is the engine-resolved geometry (the byte
            # ledger prices the same tile by construction; one tile, or a
            # tuple of (kind, tile) where the kinds' geometries differ);
            # absent it, re-derive — the direct-apply ergonomic path.
            from .pallas_attn import paged_decode_attention, \
                paged_geometry
            tile = paged_tile if paged_tile is None \
                or isinstance(paged_tile, int) \
                else dict(paged_tile)[self.KIND]
            if tile is None:
                kind = {}
                if packed:
                    kind.update(pack=f, d_value=Dv)
                if ring:
                    kind.update(most=RING_BLOCK)
                geo = paged_geometry(T, H, KV, D, cfg.dtype, **kind)
                if geo is None:
                    raise ValueError(
                        f"attention_backend={attention_backend!r}: no "
                        f"paged geometry for max_len={T}, "
                        f"kv_heads={KV}, d_head={D} — resolve the "
                        "backend via resolve_attention_backend first")
                tile = geo.tile
            extra = {}
            if packed:
                extra.update(pack=f)
            if ring:
                extra.update(ring=True)
            if sink is not None:
                extra.update(sink=sink)
            # the LAST query's key count; earlier queries mask one key
            # fewer each inside the kernel (the in-span causal mask)
            spans = positions[:, -1].astype(jnp.int32) + 1
            out = paged_decode_attention(
                q, k_all, v_all, spans, tile=tile, kv_heads=KV,
                interpret=(attention_backend == "interpret"),
                window=window, **extra).reshape(B, S, H * Dv)
        elif (tile := self._prefill_tile(attention_backend, cache,
                                         cache_index, S, T)) is not None:
            # a prefill pass on the engine's kernel backend, at a shape
            # ``prefill_geometry`` has a tile for: one tiled causal kernel
            # (query s sits at ``cache_index + s``, as the engine's
            # programs place it; under the shape's threshold and off the
            # TPU the plain scores below stay)
            from .pallas_attn import prefill_attention
            extra = {}
            if sink is not None:
                extra.update(sink=sink)
            if key_offset is not None:
                extra.update(key_offset=key_offset)
            out = prefill_attention(
                q, k_att, v_att, cache_index,
                S if valid_len is None else valid_len, bq=tile.bq,
                bk=tile.bk, window=window,
                interpret=(attention_backend == "interpret"), **extra)
        else:
            group = H // KV
            qg = q.reshape(B, S, KV, group, D)
            logits = jnp.einsum("bskgd,btkd->bkgst", qg, k_att,
                                preferred_element_type=jnp.float32)
            logits = logits / np.sqrt(D)
            mask = jnp.broadcast_to(causal[:, None, None, :, :],
                                    logits.shape)
            logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
            if sink is not None:
                # one more column: it takes mass and carries no value
                col = jnp.broadcast_to(sink.reshape(1, KV, group, 1, 1),
                                       (B, KV, group, S, 1))
                logits = jnp.concatenate([logits, col], axis=-1)
            probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
            if sink is not None:
                probs = probs[..., :-1]
            out = jnp.einsum("bkgst,btkd->bskgd", probs, v_att)
            out = out.reshape(B, S, H * Dv)
        if gate is not None:
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(cfg.dtype)
        out = _dense(cfg.d_model, ("heads", "embed"), "o_proj", cfg.dtype,
                     cfg.weight_quant)(_fold_barrier(out, H * Dv, "o"))
        return out, new_cache


def _l2norm(x):
    """FLA's: x / sqrt(sum x^2 + 1e-6), over the last axis."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


class GatedDeltaNet(nn.Module):
    """Linear-attention mixer: the gated delta rule (Yang et al., "Gated
    Delta Networks"; FLA's ``GatedDeltaNet``).  Per head a state ``S`` of
    ``(d_k, d_v)`` float32 takes each token as

        S <- alpha (S - beta k (k^T S)) + beta k v^T,    o = S^T q

    with q, k, v from a 4-tap causal depthwise convolution and SiLU, q and
    k L2-normalised, ``alpha = exp(-exp(A_log) softplus(x w_a + dt_bias))``
    and ``beta = sigmoid(x w_b)``, doubled where ``linear_allow_neg_eigval``;
    the output is RMS-normalised per head and gated by ``silu(x W_g)``.
    With fewer key heads than value heads (``linear_num_key_heads``, Qwen3-
    Next's 16 for 32) q and k are computed, convolved and normalised by key
    head, and value head ``j`` reads key head ``j // r``, ``r`` value heads
    a key head; the state, ``v``, the gates and the output are by value
    head.

    Its cache entry is the state (packed as
    :mod:`~synapseml_tpu.models.llm.pallas_gdn` lays it out) and the last
    ``taps - 1`` inputs of the convolution: a fixed size whatever the
    sequence length, which no token position can slice.  Every position
    that is not valid (``slot_mask`` false, or at or after ``valid_len``)
    leaves both exactly as they were, and a pass from position 0 starts
    from zeros, not from what the slot's last tenant left."""
    cfg: LlamaConfig

    @staticmethod
    def projections(cfg: LlamaConfig) -> Tuple[Tuple[str, int], ...]:
        """No site (:meth:`CausalAttention.projections`): in the programs
        the v5e compiler builds, none of this layer's projection weights is
        relayed, so none of its products is kept from folding."""
        return ()

    @staticmethod
    def cache_entry(cfg: LlamaConfig, batch: int, max_len: int) -> Dict:
        from .pallas_gdn import state_shape
        H, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        return {"state": jnp.zeros((batch,) + state_shape(H, dk, dv),
                                   jnp.float32),
                "conv": jnp.zeros((batch, cfg.linear_conv_kernel_dim - 1,
                                   linear_conv_channels(cfg)), cfg.dtype)}

    @nn.compact
    def __call__(self, x, positions, cache: Optional[Dict],
                 cache_index: Optional[jnp.ndarray],
                 slot_mask: Optional[jnp.ndarray] = None,
                 attention_backend: str = "dense",
                 paged_tile: Optional[int] = None,
                 valid_len: Optional[jnp.ndarray] = None):
        from . import pallas_gdn as gdn
        cfg = self.cfg
        B, S, _ = x.shape
        H, Hk, dk, dv = (cfg.linear_num_heads, cfg.linear_key_heads,
                         cfg.linear_key_head_dim, cfg.linear_value_head_dim)
        taps = cfg.linear_conv_kernel_dim
        pack = gdn.gdn_pack(H, dv)
        per_slot = cache is not None and jnp.ndim(cache_index) != 0
        if per_slot and S != 1:
            raise NotImplementedError(
                "a multi-token step at per-slot positions (speculative "
                "verify) would have to roll a recurrent state back over "
                "its rejected tokens; linear-attention layers keep no "
                "snapshot to roll back to")

        def proj(n, name, axes=("embed", "heads")):
            return _dense(n, axes, name, cfg.dtype, cfg.weight_quant)(x)
        mixed = jnp.concatenate([proj(Hk * dk, "q_proj"),
                                 proj(Hk * dk, "k_proj"),
                                 proj(H * dv, "v_proj")], axis=-1)
        C = mixed.shape[-1]

        # the tokens of this pass that are real: (B,) counts
        n_valid = jnp.full((B,), S, jnp.int32) if valid_len is None else \
            jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (B,))
        if slot_mask is not None:
            n_valid = jnp.where(slot_mask, n_valid, 0)
        if cache is None:
            window = jnp.zeros((B, taps - 1, C), mixed.dtype)
            state = jnp.zeros((B,) + gdn.state_shape(H, dk, dv), jnp.float32)
        else:
            window, state = cache["conv"], cache["state"]
            if not per_slot:
                fresh = jnp.asarray(cache_index) == 0
                window = jnp.where(fresh, jnp.zeros_like(window), window)
                state = jnp.where(fresh, jnp.zeros_like(state), state)

        # causal depthwise convolution over [window | this pass]
        w = self.param("conv", nn.with_partitioning(
            nn.initializers.normal(0.5), (None, "heads")),
            (taps, C)).astype(jnp.float32)
        ext = jnp.concatenate([window, mixed], axis=1)      # (B, taps-1+S, C)
        conv = sum(ext[:, j:j + S].astype(jnp.float32) * w[j]
                   for j in range(taps))
        conv = nn.silu(conv)
        new_window = jax.vmap(
            lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, taps - 1, 0)
        )(ext, n_valid)

        q, k, v = jnp.split(conv, [Hk * dk, 2 * Hk * dk], axis=-1)
        q = _l2norm(q.reshape(B, S, Hk, dk)) * (dk ** -0.5)
        k = _l2norm(k.reshape(B, S, Hk, dk))
        v = v.reshape(B, S, H, dv)
        a_log = self.param("A_log", nn.initializers.zeros_init(), (H,),
                           jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros_init(), (H,),
                             jnp.float32)
        alpha = jnp.exp(-jnp.exp(a_log) * jax.nn.softplus(
            proj(H, "a_proj").astype(jnp.float32) + dt_bias))
        beta = jax.nn.sigmoid(proj(H, "b_proj").astype(jnp.float32)) \
            * (2.0 if cfg.linear_allow_neg_eigval else 1.0)

        kernel = attention_backend in ("paged", "interpret") \
            and cache is not None
        interpret = attention_backend == "interpret"
        if kernel and per_slot:
            state, o = gdn.gated_delta_decode(
                state, q[:, 0], k[:, 0], v[:, 0], alpha[:, 0], beta[:, 0],
                n_valid > 0, pack=pack, interpret=interpret)
            o = o[:, None]
        elif kernel and B == 1:
            st, o = gdn.gated_delta_prefill(
                state[0], q[0], k[0], v[0], alpha[0], beta[0], n_valid[0],
                pack=pack, interpret=interpret)
            state, o = st[None], o[None]
        else:
            valid = jnp.arange(S)[None, :] < n_valid[:, None]
            if Hk != H:                   # value head j reads key head j // r
                q, k = (jnp.repeat(a, H // Hk, axis=2) for a in (q, k))
            o, st = gdn.gated_delta_scan(
                q, k, v, alpha, beta, gdn.unpack_state(state, pack), valid)
            state = gdn.pack_state(st, pack)

        # per-head RMSNorm over d_v with a learned scale, gated
        scale = self.param("o_norm", nn.initializers.ones_init(), (dv,),
                           jnp.float32)
        var = jnp.mean(jnp.square(o), -1, keepdims=True)
        o = o * jax.lax.rsqrt(var + cfg.rms_norm_eps) * scale
        gate = proj(H * dv, "g_proj").astype(jnp.float32)
        o = (o.reshape(B, S, H * dv) * nn.silu(gate)).astype(cfg.dtype)
        out = _dense(cfg.d_model, ("heads", "embed"), "o_proj", cfg.dtype,
                     cfg.weight_quant)(o)
        new_cache = None if cache is None else {"state": state,
                                                "conv": new_window}
        return out, new_cache


def linear_conv_channels(cfg: LlamaConfig) -> int:
    """Channels of a linear-attention layer's convolution: q and k by key
    head, v by value head (``2 x 16 x 128 + 32 x 128`` = 8,192 at
    Qwen3-Next's widths)."""
    return cfg.linear_key_heads * 2 * cfg.linear_key_head_dim \
        + cfg.linear_num_heads * cfg.linear_value_head_dim


def ring_rows(window: int) -> int:
    """Rows of a window layer's ring: the window rounded up to whole blocks
    of ``RING_BLOCK``, so that the window's keys are whole tiles of the paged
    kernel, and one block more, so that the (at most ``window / tile + 1``)
    position tiles a step walks, the newest of them part-written, are
    distinct tiles of the ring.  128 + 128 = 256 behind a window of 128."""
    return -(-int(window) // RING_BLOCK) * RING_BLOCK + RING_BLOCK


class SlidingAttention(CausalAttention):
    """:class:`CausalAttention` whose query ``i`` sees the keys
    ``i - sliding_window < j <= i``.

    Its cache entry is a RING of :func:`ring_rows` rows a slot whatever
    ``max_len``, position ``p`` in row ``p mod rows``, wherever ``max_len``
    holds two rings or more; under that it keeps ``max_len`` rows by
    position like a full layer (:meth:`cache_rows`: a rule on sizes).  By
    position a retired slot stays a prefix-reuse source at every length and
    costs rows no query reads again: the ring saves more than half of them
    from two rings on, and where ``max_len`` is 1.3 windows (a preamble as
    long as the window before a short tail) it would save a fifth and
    overwrite the preamble every request shares.  On a ring a decode step
    writes at ``position mod rows`` and the paged kernel walks the window's
    position tiles through the ring; a prefill writes only its real rows,
    the last ``rows`` of them, and attends over the ring's rows before it
    and its own (:func:`_ring_pass`); what the engine's by-position paths do
    on a ring is ``slots.py``'s to say.  The window acts in the masks, in
    the blocks prefill visits and in the tiles the paged kernel walks."""

    KIND = "sliding_attention"

    @property
    def window(self) -> Optional[int]:
        return int(self.cfg.sliding_window)

    @classmethod
    def cache_rows(cls, cfg: LlamaConfig, max_len: int) -> int:
        ring = ring_rows(cfg.sliding_window)
        return ring if max_len >= 2 * ring else max_len


def _plain_attention(q, k, v, qpos, kpos, scale: float, dtype):
    """Softmax attention by plain products: ``q (B, S, H, D)``, ``k (B, T,
    KV, D)``, ``v (B, T, KV, Dv)`` with ``H / KV`` query heads a K/V head;
    query at ``qpos (B or 1, S)`` sees key at ``kpos (B or 1, T)`` iff
    ``kpos <= qpos``.  Scores and softmax in float32, the probabilities
    cast to ``dtype`` before they meet the values, as the kernels do.
    -> ``(B, S, H, Dv)``."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    seen = (kpos[:, None, :] <= qpos[:, :, None])[:, None, None]
    logits = jnp.where(seen, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H, v.shape[-1])


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2 and V3's MLA; ``axk1``).
    ``h`` a token's normed residual, ``H`` heads::

        q = rms_q(h W_DQ) W_UQ         per head [q_nope (dn) | q_pe (dr)]
        [c (r) | k_pe (dr)] = h W_DKV,  c <- rms_kv(c)
        q_pe, k_pe turned by the rotary embedding (``rope_style``; YaRN where
            ``rope_scaling`` says so: :func:`yarn_rope`); ONE k_pe for all
            heads
        [k_nope_h (dn) | v_h (dv)] = c W_UKV,h,  k_h = [k_nope_h | k_pe]
        s_h,ij = (q_h,i . k_h,j) * sigma,  sigma = (dn + dr)^-0.5 times
            YaRN's factor;  causal
        out = concat_h(softmax_j(s_h) v_h) W_O

    The cache entry is the LATENT ``[c | k_pe]`` by position, one row of
    :func:`latent_lanes` lanes for all heads (``r + dr`` padded to whole lane
    tiles: 576 -> 640 at A.X-K1's widths), not heads.  Two forms compute the
    same attention from it:

    - EXPANDED: the rows are multiplied out to every head's ``k`` and ``v``
      and attended as ``H`` K/V heads (``prefill_attention`` at keys ``dn +
      dr`` wide, values ``dv``).  A prefill from position 0 expands its own
      rows (the training pass too); a tail after a cached prefix expands
      every row of the slot;
    - ABSORBED: ``W_UK`` folds into the query, ``q~_h = q_nope_h W_UK,h^T``
      (``r`` wide), the scores are ``[q~_h | q_pe_h] . [c_j | k_pe_j]``, the
      values the rows' first ``r`` lanes, and ``W_UV`` unfolds the result,
      ``o_h = (sum_j p_h,ij c_j) W_UV,h``: one K/V head of the row's width
      for all ``H`` query heads.  Every decode step runs it
      (:func:`~synapseml_tpu.models.llm.pallas_attn.latent_decode_attention`
      on the kernel backends); a tail after a cached prefix runs it where
      :func:`~synapseml_tpu.models.llm.pallas_attn.latent_prefill_form` says
      it costs less than expanding the prefix.

    A prefill pass at an offset holds both: ``lax.cond`` on the offset picks
    the first form at 0 and the tail's form after it, so a bucket is still
    one program."""
    cfg: LlamaConfig

    KIND = "latent_attention"

    @classmethod
    def cache_rows(cls, cfg: LlamaConfig, max_len: int) -> int:
        return max_len

    @classmethod
    def projections(cls, cfg: LlamaConfig) -> Tuple[Tuple[str, int], ...]:
        """The query's up-projection and the output projection: the two
        products that meet a split into heads or their merge."""
        return (("q", cfg.q_lora_rank or cfg.d_model),
                ("o", cfg.num_heads * cfg.v_head_dim))

    @classmethod
    def cache_entry(cls, cfg: LlamaConfig, batch: int, max_len: int) -> Dict:
        return {"latent": jnp.zeros((batch, max_len, latent_lanes(cfg)),
                                    cfg.dtype)}

    @staticmethod
    def scale(cfg: LlamaConfig) -> float:
        """``sigma``: ``(dn + dr)^-0.5``, times YaRN's factor where set."""
        s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
        if cfg.rope_scaling is not None:
            s *= yarn_rope(cfg.qk_rope_head_dim, cfg.rope_theta,
                           cfg.rope_scaling)[2]
        return float(s)

    @staticmethod
    def geometry(cfg: LlamaConfig, form: str, S: int, T: int):
        """The prefill kernel's tile for one form of a pass of ``S`` queries
        over ``T`` key rows (``prefill_geometry``; None: plain products)."""
        from .pallas_attn import prefill_geometry
        H = cfg.num_heads
        if form == "absorbed":
            return prefill_geometry(S, T, H, 1, latent_lanes(cfg),
                                    cfg.kv_lora_rank, cfg.dtype)
        return prefill_geometry(S, T, H, H, cfg.qk_nope_head_dim
                                + cfg.qk_rope_head_dim, cfg.v_head_dim,
                                cfg.dtype)

    @staticmethod
    def tail_form(cfg: LlamaConfig, S: int, T: int) -> str:
        """The form of a pass of ``S`` queries after a cached prefix in an
        entry of ``T`` rows."""
        from .pallas_attn import latent_prefill_form
        return latent_prefill_form(S, T, cfg.num_heads, cfg.kv_lora_rank,
                                   cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                                   cfg.v_head_dim, latent_lanes(cfg))

    @nn.compact
    def __call__(self, x, positions, cache: Optional[Dict],
                 cache_index: Optional[jnp.ndarray],
                 slot_mask: Optional[jnp.ndarray] = None,
                 attention_backend: str = "dense",
                 paged_tile: Optional[Any] = None,
                 valid_len: Optional[jnp.ndarray] = None):
        cfg = self.cfg
        B, S, _ = x.shape
        H, dn, dr, dv, r = (cfg.num_heads, cfg.qk_nope_head_dim,
                            cfg.qk_rope_head_dim, cfg.v_head_dim,
                            cfg.kv_lora_rank)
        L, dt = latent_lanes(cfg), cfg.dtype
        # the kernels serve passes over a cache; a pass without one
        # (training) takes plain products, as a full layer's does
        kernels = attention_backend in ("paged", "interpret") \
            and cache is not None
        interpret = attention_backend == "interpret"
        if cfg.q_lora_rank:
            cq = RMSNorm(cfg.rms_norm_eps, dt, name="q_a_norm")(_dense(
                cfg.q_lora_rank, ("embed", None), "q_a_proj", dt)(x))
            q = _project(cq, H * (dn + dr), (None, "heads"), "q_b_proj",
                         cfg, "q")
        else:
            q = _project(x, H * (dn + dr), ("embed", "heads"), "q_proj",
                         cfg, "q")
        q = q.reshape(B, S, H, dn + dr)
        kv = _dense(r + dr, ("embed", None), "kv_a_proj", dt)(x)
        c = RMSNorm(cfg.rms_norm_eps, dt, name="kv_a_norm")(kv[..., :r])
        freqs, mscale = None, 1.0
        if cfg.rope_scaling is not None:
            freqs, mscale, _ = yarn_rope(dr, cfg.rope_theta, cfg.rope_scaling)

        def turn(a):
            return apply_rope(a, positions, cfg.rope_theta, cfg.rope_style,
                              freqs=freqs, mscale=mscale)
        q_nope, q_pe = q[..., :dn], turn(q[..., dn:])
        k_pe = turn(kv[:, :, None, r:])[:, :, 0]
        w = self.param("kv_b_proj", nn.with_partitioning(
            nn.initializers.truncated_normal(0.02), (None, "heads", None)),
            (r, H, dn + dv)).astype(dt)
        w_uk, w_uv = w[..., :dn], w[..., dn:]
        scale = self.scale(cfg)
        # this pass's latent rows as the entry lays them
        row = jnp.concatenate(
            [c, k_pe, jnp.zeros((B, S, L - r - dr), dt)], -1).astype(dt)

        def expanded(rows, start, T):
            """Every head's k and v from ``rows (B, T, L)`` (key ``j`` at
            position ``j``), queries at ``start + s``."""
            k = jnp.einsum("btc,chd->bthd", rows[..., :r], w_uk)
            k = jnp.concatenate([k, jnp.broadcast_to(
                rows[:, :, None, r:r + dr], (B, T, H, dr))], -1)
            v = jnp.einsum("btc,chd->bthd", rows[..., :r], w_uv)
            qe = jnp.concatenate([q_nope, q_pe], -1)
            geo = self.geometry(cfg, "expanded", S, T) if kernels else None
            return self._attend(qe, k, v, start, T, geo, scale, valid_len,
                                interpret).reshape(B, S, H, dv)

        def folded():
            """``[q_nope W_UK^T | q_pe | zeros]``: a query as wide as a row."""
            return jnp.concatenate([
                jnp.einsum("bshd,chd->bshc", q_nope, w_uk).astype(dt), q_pe,
                jnp.zeros((B, S, H, L - r - dr), dt)], -1)

        def absorbed(rows, start, T):
            geo = self.geometry(cfg, "absorbed", S, T) if kernels else None
            u = self._attend(folded(), rows[:, :, None], rows[:, :, None, :r],
                             start, T, geo, scale, valid_len, interpret)
            return jnp.einsum("bshc,chd->bshd", u.reshape(B, S, H, r), w_uv)

        new_cache = None
        if cache is None:
            out = expanded(row, 0, S)
        elif jnp.ndim(cache_index) == 0:
            # a prefill pass at an offset: its rows land by position, then
            # the first pass attends over its own rows, a tail over the slot's
            rows = jax.lax.dynamic_update_slice(cache["latent"], row,
                                                (0, cache_index, 0))
            new_cache = {"latent": rows}
            T = rows.shape[1]
            tail = absorbed if self.tail_form(cfg, S, T) == "absorbed" \
                else expanded
            # a pass as long as the entry can only start at 0
            out = expanded(row, 0, S) if S >= T else jax.lax.cond(
                cache_index == 0, lambda: expanded(row, 0, S),
                lambda: tail(rows, cache_index, T))
        else:
            # per-slot positions (decode, a verify span): rows written by
            # the gated scatter a full layer uses, the absorbed form read
            wpos = cache_index[:, None] + jnp.arange(S)[None, :]
            bidx = jnp.arange(B)[:, None]
            w_row = row
            if slot_mask is not None:
                w_row = jnp.where(slot_mask[:, None, None], row,
                                  cache["latent"][bidx, wpos])
            rows = cache["latent"].at[bidx, wpos].set(w_row)
            new_cache = {"latent": rows}
            qa = folded()
            if kernels:
                from .pallas_attn import latent_decode_attention, \
                    paged_geometry
                tile = dict(paged_tile)[self.KIND] \
                    if isinstance(paged_tile, tuple) else paged_tile
                if tile is None:            # a direct apply: the default
                    tile = paged_geometry(rows.shape[1], H, 1, r + dr, dt,
                                          max_query_span=S, latent=True).tile
                # the last query's key count (``paged_decode_attention``'s)
                spans = positions[:, -1].astype(jnp.int32) + 1
                u = latent_decode_attention(
                    qa, rows, spans, tile=int(tile), rank=r, scale=scale,
                    interpret=interpret)
            else:
                T = rows.shape[1]
                u = _plain_attention(qa, rows[:, :, None],
                                     rows[:, :, None, :r], positions,
                                     jnp.arange(T)[None], scale, dt)
            out = jnp.einsum("bshc,chd->bshd", u, w_uv)
        out = out.reshape(B, S, H * dv).astype(dt)
        out = _dense(cfg.d_model, ("heads", "embed"), "o_proj", dt)(
            _fold_barrier(out, H * dv, "o"))
        return out, new_cache

    @staticmethod
    def _attend(q, k, v, start, T, geo, scale, valid_len, interpret):
        """One prefill pass's attention, queries at ``start + s`` over key
        rows ``0 .. T - 1``: the prefill kernel at ``geo``, else plain
        products.  -> ``(B, S, H * Dv)``."""
        B, S = q.shape[:2]
        if geo is not None:
            from .pallas_attn import prefill_attention
            return prefill_attention(
                q, k, v, start, S if valid_len is None else valid_len,
                bq=geo.bq, bk=geo.bk, scale=scale, interpret=interpret)
        qpos = (start + jnp.arange(S))[None]
        out = _plain_attention(q, k, v, qpos, jnp.arange(T)[None], scale,
                               q.dtype)
        return out.reshape(B, S, -1)


def latent_lanes(cfg: LlamaConfig) -> int:
    """Lanes of a latent cache row: ``kv_lora_rank + qk_rope_head_dim``
    rounded up to whole tiles of 128 (576 -> 640: the last 64 are zeros)."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


#: mixer kind (a ``layer_types`` entry) -> its class; each declares its
#: own cache entry and takes the same call
MIXERS = {"full_attention": CausalAttention,
          "sliding_attention": SlidingAttention,
          "linear_attention": GatedDeltaNet,
          "latent_attention": LatentAttention}
#: the name of a kind's parameters inside a block
_MIXER_NAME = {"full_attention": "attn", "sliding_attention": "attn",
               "linear_attention": "gdn", "latent_attention": "attn"}


def _swiglu(cfg: LlamaConfig, h, valid, backend):
    """The dense feed-forward: ``down(silu(gate h) * up h)``, its three
    matrices parameters of the block that calls it."""
    del valid, backend
    gate = _dense(cfg.d_ff, ("embed", "mlp"), "gate_proj", cfg.dtype,
                  cfg.weight_quant)(h)
    up = _dense(cfg.d_ff, ("embed", "mlp"), "up_proj", cfg.dtype,
                cfg.weight_quant)(h)
    return _dense(cfg.d_model, ("mlp", "embed"), "down_proj", cfg.dtype,
                  cfg.weight_quant)(nn.silu(gate) * up)              # SwiGLU


def _experts(cfg: LlamaConfig, h, valid, backend):
    from .experts import ExpertFFN
    return ExpertFFN(cfg, name="moe")(h, valid, backend)


#: feed-forward kind (``LlamaConfig.ffn``, or a layer's entry of
#: ``ffn_types``) -> ``f(cfg, h, valid, backend)``,
#: called inside the block's scope (its modules are the block's): ``h (B, S,
#: d)``, ``valid (B, S)`` the tokens that are real (an expert layer routes
#: the others nowhere), ``backend`` the engine's ``attention_backend``
FFNS = {"dense": _swiglu, "experts": _experts}


def _valid_tokens(B: int, S: int, slot_mask, valid_len):
    """(B, S) bool: not a bucket's padding, not an inactive slot's row."""
    valid = jnp.ones((B, S), bool)
    if valid_len is not None:
        n = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (B,))
        valid &= jnp.arange(S)[None, :] < n[:, None]
    if slot_mask is not None:
        valid &= slot_mask[:, None]
    return valid


class DecoderBlock(nn.Module):
    """One layer: a mixer (:data:`MIXERS`) and a feed-forward (:data:`FFNS`)
    around the residual, in ``cfg.norm_order``: "pre" ``x + f(norm(x))``
    twice, "post" ``x + norm(f(x))`` twice, "parallel" one norm and
    ``x + mixer(h) + ffn(h)``."""
    cfg: LlamaConfig
    kind: str = "full_attention"
    #: the layer's feed-forward kind (None: ``cfg.ffn``)
    ffn: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions, cache, cache_index, slot_mask=None,
                 attention_backend: str = "dense",
                 paged_tile: Optional[Any] = None,
                 valid_len: Optional[jnp.ndarray] = None):
        cfg = self.cfg
        mixer = MIXERS[self.kind](cfg, name=_MIXER_NAME[self.kind])
        ffn_kind = self.ffn or cfg.ffn
        ffn = FFNS[ffn_kind]
        valid = None if ffn_kind == "dense" else _valid_tokens(
            x.shape[0], x.shape[1], slot_mask, valid_len)
        ln_attn = _norm(cfg, "ln_attn")
        if cfg.norm_order == "parallel":
            h = ln_attn(x)
            a, new_cache = mixer(h, positions, cache, cache_index, slot_mask,
                                 attention_backend, paged_tile, valid_len)
            return x + a + ffn(cfg, h, valid, attention_backend), new_cache
        pre = cfg.norm_order == "pre"
        ln_mlp = _norm(cfg, "ln_mlp")
        a, new_cache = mixer(
            ln_attn(x) if pre else x, positions, cache, cache_index,
            slot_mask, attention_backend, paged_tile, valid_len)
        x = x + (a if pre else ln_attn(a))
        h = ffn(cfg, ln_mlp(x) if pre else x, valid, attention_backend)
        return x + (h if pre else ln_mlp(h)), new_cache


class LlamaModel(nn.Module):
    """Causal LM: ``__call__`` returns logits (B, S, vocab); pass a cache
    pytree + cache_index for incremental decode."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, cache=None,
                 cache_index=None, deterministic: bool = True,
                 slot_mask: Optional[jnp.ndarray] = None,
                 attention_backend: str = "dense",
                 paged_tile: Optional[Any] = None,
                 valid_len: Optional[jnp.ndarray] = None,
                 logits_at: Optional[jnp.ndarray] = None):
        """``valid_len`` (scalar or ``(B,)``): how many of the ``S`` tokens
        are real, the rest a bucket's padding; a recurrent layer must not
        take a padded token into its state (attention layers ignore it).
        ``logits_at`` (scalar or ``(B,)``): the one position whose logits
        the caller reads; the head then runs over that row alone and the
        logits are ``(B, 1, vocab)`` (a prefill reads its last real
        token's: ``S x vocab`` float32 is 5.9 GB at 5,632 x 262,144)."""
        cfg = self.cfg
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        if cfg.tie_embeddings and cfg.weight_quant == "int8":
            embed = QuantEmbed(cfg.vocab_size, cfg.d_model, cfg.dtype,
                               name="tok_embed")
        else:
            embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                             embedding_init=nn.with_partitioning(
                                 nn.initializers.truncated_normal(0.02),
                                 ("vocab", "embed")),
                             name="tok_embed")
        x = embed(input_ids)
        new_caches = []
        for i, (kind, ffn) in enumerate(zip(cfg.layer_kinds, cfg.ffn_kinds)):
            layer_cache = cache[i] if cache is not None else None
            x, nc = DecoderBlock(cfg, kind, ffn, name=f"layer_{i}")(
                x, positions, layer_cache, cache_index, slot_mask,
                attention_backend, paged_tile, valid_len)
            new_caches.append(nc)
        if logits_at is not None:
            # one-hot extraction: the position is traced, and a dynamic
            # gather is slow on the TPU
            at = jnp.broadcast_to(jnp.asarray(logits_at), (B,))
            x = jnp.sum(jnp.where(
                (jnp.arange(S)[None, :] == at[:, None])[..., None], x, 0),
                axis=1, keepdims=True).astype(x.dtype)
        x = _norm(cfg, "ln_final")(x)
        if cfg.tie_embeddings:
            if isinstance(embed, QuantEmbed):
                logits = embed.attend(x)      # f32 accumulation inside
            else:
                # ``nn.Embed.attend`` would round the logits to the
                # model's dtype; operands in it, float32 out
                logits = jax.lax.dot_general(
                    x, embed.embedding.astype(x.dtype),
                    (((x.ndim - 1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
        else:
            logits = _dense(cfg.vocab_size, ("embed", "vocab"), "lm_head",
                            jnp.float32, cfg.weight_quant)(x)
        logits = logits.astype(jnp.float32)
        if cfg.logit_scale != 1.0:
            logits = logits * cfg.logit_scale
        if cache is not None:
            return logits, new_caches
        return logits


def causal_lm_loss(logits: jnp.ndarray, input_ids: jnp.ndarray,
                   mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Next-token cross entropy over shifted targets."""
    import optax
    targets = input_ids[:, 1:]
    pred = logits[:, :-1]
    losses = optax.softmax_cross_entropy_with_integer_labels(pred, targets)
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
        return (losses * m).sum() / jnp.maximum(m.sum(), 1.0)
    return losses.mean()


def llama_from_pretrained(path: str, dtype: Any = jnp.bfloat16,
                          max_len: Optional[int] = None,
                          config: Optional[LlamaConfig] = None,
                          rng_seed: int = 0):
    """Build a LlamaModel + variables from an HF-format checkpoint.

    ``path``: HF model dir (config.json + safetensors/bin, possibly
    sharded) or a bare weights file (then ``config`` is required).  The
    weight import goes through the family mapping table in
    models/dl/checkpoints.py — torch (out, in) Linear layouts transpose to
    flax kernels, and HF's rotate-half RoPE arrangement matches
    ``apply_rope`` as-is.  Returns ``(model, {"params": ...})`` for
    LLMTransformer's bundle.
    """
    import json
    import os

    from ..dl.checkpoints import import_llama, read_checkpoint

    if config is None:
        cfg_path = os.path.join(path, "config.json") if os.path.isdir(path) \
            else os.path.join(os.path.dirname(path), "config.json")
        if not os.path.exists(cfg_path):
            raise ValueError(
                f"no config.json beside {path!r}; pass config= explicitly")
        with open(cfg_path) as f:
            hc = json.load(f)
        config = LlamaConfig.from_hf(hc, dtype=dtype)
        if max_len:
            config = dataclasses.replace(config, max_len=int(max_len))
    model = LlamaModel(config)
    probe = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(rng_seed), probe)["params"]
    hf = read_checkpoint(path)
    params = import_llama(params, hf, num_layers=config.num_layers,
                          tie_embeddings=config.tie_embeddings)
    return model, {"params": params}
