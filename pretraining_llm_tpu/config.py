"""Typed configuration for models, data, training, and the device mesh.

Replaces the reference's flat constants dict (`/root/reference/config/config.py:29-47`)
with validated dataclasses. The reference ships with five config keys that are
consumed but never defined (SURVEY.md Appendix B) — this module fails fast at
construction time instead: every field is typed, defaulted, and checked in
``__post_init__``/``validate``.

Presets cover the five BASELINE.json configs plus the reference's own default
3.16B shape (``reference-3b``) for parity accounting.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

_ACTIVATIONS = ("relu", "gelu", "swiglu", "relu2")
_NORMS = ("layernorm", "rmsnorm")
_POS_EMBEDS = ("learned", "rope", "none")
_ATTN_IMPLS = ("naive", "flash", "ring", "ulysses")
_REMAT_POLICIES = ("none", "full", "dots_saveable", "save_attn", "save_attn_res")

# Options and values taken out of the program, for configurations that arrive
# from outside it: a checkpoint's saved config (Config.from_json) and dotted
# overrides from a command line (Config.with_overrides). A retired field at
# the value the remaining path implements is dropped; anything else listed
# here raises and names what to use. The removed behaviour is not emulated.
_RETIRED_KEYS: Dict[str, Tuple[Optional[Tuple[Any, ...]], Any, str]] = {
    # section.key: (removed values, or None where the whole field went;
    #               the value the remaining path implements; removed in)
    "model.decode_cache_layout": (None, "unstacked", "PR 29"),
    "model.decode_unroll_layers": (None, False, "PR 29"),
    "model.scan_unroll": (None, 1, "PR 29"),
    "model.ce_impl": (("fused",), "chunked", "PR 29"),
    "model.remat": (("save_qkv_attn", "save_big"), "save_attn_res", "PR 29"),
    "model.paged_attention_impl": (None, "gather", "PR 47"),
    "model.ragged_kv_splits": (None, 1, "PR 47"),
    "model.ragged_amla": (None, False, "PR 47"),
    "model.flash_heads_major": (None, False, "PR 50"),
}


def _without_retired_keys(section: str, kw: Dict[str, Any]) -> Dict[str, Any]:
    """``kw`` for one config section without its retired fields; ValueError
    for a field value or an enumeration value whose path was removed."""
    out = {}
    for key, value in kw.items():
        removed, use, pr = _RETIRED_KEYS.get(f"{section}.{key}", ((), None, ""))
        if removed is None and value == use:
            continue
        if removed is None or value in removed:
            advice = "drop the key" if removed is None else f"use {key}={use!r}"
            raise ValueError(
                f"{section}.{key}={value!r} selects a path that was removed in {pr}; "
                f"the program implements {key}={use!r}: {advice}"
            )
        out[key] = value
    return out


def layers_from_pattern(pattern: str) -> Dict[str, Tuple[str, ...]]:
    """``layer_mixers`` and ``layer_ffns`` of a table of single sublayers from
    Nemotron-H's ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer alone, ``*``
    an attention layer alone, ``E`` an expert FFN alone. A loader of the family's
    configurations calls this (the benchmark's family file, the toy preset)."""
    kinds = {"M": ("mamba", "none"), "*": ("attn", "none"), "E": ("none", "moe")}
    if not pattern or set(pattern) - set(kinds):
        raise ValueError(f"a layer pattern is made of {''.join(kinds)}, got {pattern!r}")
    mixers, ffns = zip(*(kinds[c] for c in pattern))
    return {"layer_mixers": mixers, "layer_ffns": ffns}


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-magnitude correction, 0.1 * mscale * ln(factor) + 1."""
    import math

    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


@dataclass(frozen=True)
class ModelConfig:
    """Architecture of a decoder-only transformer.

    The pluggable knobs (``activation``, ``norm``, ``pos_embed``,
    ``use_output_proj``, ``tie_embeddings``) span the reference's exact
    architecture (SURVEY.md §2.5: pre-LN, learned-absolute positions, ReLU MLP,
    no attention output projection, untied biased lm_head) and the standard
    GPT-2 / Llama shapes required by BASELINE.json configs #1-#5.
    """

    vocab_size: int = 50304
    context_length: int = 1024
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_head: Optional[int] = None  # defaults to d_model // n_heads
    # Grouped-query attention: number of KV heads (None = n_heads, i.e. MHA;
    # 1 = MQA). Shrinks KV-cache memory and KV projection params by
    # n_heads/n_kv_heads.
    n_kv_heads: Optional[int] = None
    mlp_ratio: float = 4.0
    activation: str = "gelu"  # relu | gelu | swiglu | relu2 (ungated, relu(x)^2)
    norm: str = "layernorm"  # layernorm | rmsnorm
    pos_embed: str = "learned"  # learned | rope | none (no position of any kind)
    rope_theta: float = 10000.0
    use_output_proj: bool = True  # reference has none (attention.py:95)
    tie_embeddings: bool = True  # reference unties (transformer.py:37-38)
    lm_head_bias: bool = False  # reference has bias on lm_head
    qkv_bias: bool = False  # reference: biasless K/Q/V (attention.py:29-31)
    mlp_bias: bool = True  # reference: biases in MLP (mlp.py:24-26)
    norm_eps: float = 1e-5
    # Numerics
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # Attention implementation: naive einsum | pallas flash | ring (seq-parallel)
    attention_impl: str = "naive"
    # Sequence distribution for ring attention: "zigzag" pairs chunk i with
    # chunk 2n-1-i per device so causal work balances across the ring
    # (utilization ~1.0 vs (n+1)/2n contiguous); loss_fn applies the matching
    # token permutation automatically. "contiguous" keeps plain sharding.
    ring_layout: str = "zigzag"
    # Flash-attention block sizes (tuned for TPU MXU/VMEM; 0 = auto:
    # min(1024, T)). T <= 1024 is kept as ONE block a head, because every
    # further grid step costs more than it skips; the masked half of that
    # block is skipped inside it (ops/pallas_flash.py::causal_tiles), and at
    # a head size of 64 or a multiple of 128 those kernels read q, k, v and
    # write o where the projections leave them, (B, T, H*Dh), with no
    # transposing copy around the call (heads_in_place: read from the shapes,
    # no field selects it); a fused QKV projection's result, (B, 3, T, H*Dh),
    # goes to them whole where nothing stands between the two (no cache, no
    # rotation or norm of q and k: models/transformer.py::_qkv_stays_whole),
    # and d(qkv) comes back as one array. A size under T selects the grid's
    # kernels, which skip whole blocks and are handed the heads folded first.
    flash_block_q: int = 0
    flash_block_kv: int = 0
    # Rematerialization policy applied to each scanned block — see
    # ops/remat.py for what each saves.
    remat: str = "none"  # none | full | dots_saveable | save_attn | save_attn_res
    # CE head implementation: "chunked" scans token chunks and its backward
    # recomputes each chunk's logits (every preset and cell; handles a bias
    # and a vocab-sharded head); "dense" SAVES the compute-dtype logits so
    # backward recomputes nothing — S*V*2 bytes of head memory for zero
    # recompute FLOPs. No cell selects "dense"; tests use it as the
    # reference for "chunked".
    ce_impl: str = "chunked"  # chunked | dense
    # z-loss coefficient (PaLM/ST-MoE): adds z * mean(logsumexp(logits)^2)
    # to the training loss, pinning the softmax normalizer near 0 —
    # stabilizes large-scale bf16 training. 0 = off.
    z_loss_coef: float = 0.0
    # A cached forward over a per-layer cache (see make_kv_cache) with
    # Tq <= this runs a trace-time loop over layers, each cache leaf updated
    # in place (single-token decode steps, speculative verify rounds); a
    # larger Tq (prefill buckets start at 16) stacks the cache once and runs
    # the rolled depth scan, so the prefill program stays O(1) in depth.
    # Raise it if you run speculative decoding with spec_k >= this value.
    decode_loop_max_tokens: int = 8
    # Shard activations' sequence dim over the 'seq' mesh axis (Megatron-SP)
    sequence_parallel: bool = False
    # Sliding-window attention (Mistral-style): each query attends only the
    # last `sliding_window` positions (0 = full causal attention). The
    # flash kernel SKIPS blocks entirely outside the window (compute drops
    # from O(T^2) to O(T*W) at long context); cached decode masks old
    # slots. naive/flash paths; ring/ulysses rejected at validation.
    sliding_window: int = 0
    # Packed-document attention masking: >= 0 names the document-separator
    # token id (the EOT the preprocessor appends per document); attention
    # then never crosses a document boundary. Segment ids are derived
    # IN-MODEL from the token stream (exclusive running count of
    # separators) — no data-pipeline change. -1 = off (the reference, and
    # GPT-2/3-style packing, attend across document boundaries).
    # Training/eval only; naive + flash attention paths (ring/ulysses/
    # pipeline compositions are rejected at validation).
    doc_mask_token: int = -1
    # Mixture-of-experts MLP (0 = dense). Experts shard over the 'expert' mesh
    # axis; routing is dense einsum dispatch with a per-expert capacity bound.
    n_experts: int = 0
    experts_per_token: int = 2
    expert_capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # Tokens per routing group: capacity pools are per-group so dispatch
    # memory is O(S * k * C_group), linear in batch, not O(S^2). Group count
    # derives from the token count only (mesh-independent routing). 0 = one
    # global group (tiny-shape/testing escape hatch).
    moe_group_size: int = 2048
    # Routing. "capacity" (above) drops what overflows an expert's slots and
    # stays the training path. "dropless" sorts the (token, choice) pairs by
    # expert and runs one grouped matmul per projection: no slot, no drop, and
    # a token's output never depends on what shares its batch or its padded
    # bucket, so the paged engine and ragged generate accept it.
    moe_routing: str = "capacity"  # capacity | dropless
    # Dropless scoring: softmax, or sigmoid scores with a per-expert bias that
    # enters the SELECTION only ("noaux_tc"); the gates are the unbiased
    # scores of the selected experts, renormalised, times moe_routed_scale.
    moe_score: str = "softmax"  # softmax | sigmoid
    moe_score_bias: bool = False
    moe_norm_topk: bool = True
    moe_routed_scale: float = 1.0
    # Shared experts: one always-on SwiGLU of width n_shared_experts * d_expert
    # beside the routed ones. d_expert 0 = d_ff.
    n_shared_experts: int = 0
    d_expert: int = 0
    # Group-limited selection (DeepSeek-V3's noaux_tc): the experts are
    # moe_n_group groups of consecutive experts, a group scores the sum of its
    # two best biased scores, and the top-k is taken among the experts of the
    # moe_topk_group best groups. 1 group = no limit.
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # Experts whose weights this program holds, the router's first ones
    # (expert parallelism's share: moe.moe_mlp_dropless). 0 = all n_experts.
    n_experts_held: int = 0
    # SwiGLU clamps, one a layer (empty = none, 0 = none in that layer):
    # silu(min(gate, L)) * clip(up, -L, L) in the routed experts
    # (moe_swiglu_limits) and in the shared expert (moe_shared_swiglu_limits).
    moe_swiglu_limits: Tuple[float, ...] = ()
    moe_shared_swiglu_limits: Tuple[float, ...] = ()
    # Leading dense layers (width d_ff) before the expert layers: stored as
    # params["dense_blocks"], run as a group of their own ahead of
    # params["blocks"]. Caches and pools cover all n_layers.
    n_dense_layers: int = 0
    # Latent (MLA) attention, kv_lora_rank > 0: low-rank queries, one latent
    # of kv_lora_rank + qk_rope_head_dim values a token shared by all heads
    # (the only thing cached), keys and values expanded from it for prefill
    # and absorbed into the query and output for decode. d_head must be
    # qk_nope_head_dim + qk_rope_head_dim.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN: frequencies interpolated between the original context and
    # rope_factor times it; mscale_all_dim scales the softmax by its square.
    rope_scaling: str = "none"  # none | yarn
    rope_factor: float = 1.0
    rope_original_context: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # A sigmoid gate on the attention output, from the sublayer's input. Latent
    # attention: head-wise, o_h * sigmoid((x W_gate)_h), W_gate (D, H). Per-head
    # attention: element-wise, o * sigmoid(x W_gate), W_gate (D, H, Dh).
    attn_output_gate: bool = False
    # Per-layer attention kind of a per-head model, "window" (the last
    # sliding_window positions) or "full" (every earlier position), one a
    # layer; empty = every layer the one kind sliding_window implies. A stack
    # that mixes the two keeps two cache lifetimes when served: full layers a
    # row's pages for its whole length, window layers a pool of their own whose
    # pages are given back behind the window (generation/serving.py). The
    # stack is scanned as runs of like layers (layer_runs).
    attn_kinds: Tuple[str, ...] = ()
    # False: full layers of a mixed stack carry no position encoding (RoPE on
    # the window layers only).
    rope_full_layers: bool = True
    # RMSNorm with a learned weight over each head's queries and keys, before
    # RoPE (per-head attention; latent attention norms its own low-rank parts).
    qk_norm: bool = False
    # The same two norms over the WHOLE projected width instead, all heads'
    # queries (n_heads * d_head channels, one weight as wide) and all KV heads'
    # keys, before the heads are cut (OLMo 2, arXiv:2501.00656).
    qk_norm_whole: bool = False
    # Where a sublayer's one norm stands: "input", x + f(N(x)) (pre-norm), or
    # "output", x + N(f(x)) (OLMo 2: the sublayer reads the residual as it is
    # and its output is normed before it joins). The same two weights a layer,
    # ln1 and ln2, either way; the final norm is unchanged.
    norm_placement: str = "input"  # input | output
    # Two more norms a layer: x + N_post_attn(attn(N_in(x))), then
    # x + N_post_mlp(mlp(N_pre_mlp(x))).
    sandwich_norm: bool = False
    # The factor on the token embeddings (0 = none). True stands for
    # sqrt(d_model), muP's input scale, and is stored as that number.
    embed_scale: float = 0.0
    # Three more multipliers of the same family of models (Granite): each
    # sublayer's output times residual_multiplier before it joins the residual;
    # per-head attention scores times attention_multiplier in place of
    # 1/sqrt(head_dim) (0 = that); the logits divided by logits_scaling.
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # The mixer of every layer, one name a layer: "attn" (per-head or latent
    # attention, whichever the model has), "kda" (models/kda.py), "mamba"
    # (models/mamba.py) or "gdn" (models/gdn.py). Empty = every layer "attn",
    # or what layer_group_size, the shorthand for a period of KDA layers,
    # fills in. A stack with
    # recurrent layers is hybrid: stored and scanned as runs of like layers,
    # params["blocks"] its recurrent layers and params["attn_blocks"] its
    # attention layers (layer_runs); served, its attention layers alone keep
    # pages and each recurrent layer a fixed-size state a row
    # (transformer.make_paged_kv_pool). layer_kinds is the one table to ask.
    # "none": the layer has no mixer, it is its FFN alone under one norm.
    layer_mixers: Tuple[str, ...] = ()
    # The table's other column, the FFN of every layer: "dense", "moe" or
    # "none" (the layer is its mixer alone under one norm, x + f(N(x))). Empty
    # = every layer's FFN is what n_experts and n_dense_layers say. A table
    # is of whole layers (a mixer and an FFN each) or of single sublayers (a
    # mixer or an FFN each: Nemotron-H's pattern string), never both; a layer
    # of one sublayer keeps one norm, and only a mixer layer keeps a cache.
    layer_ffns: Tuple[str, ...] = ()
    # A Mamba-2 layer (arXiv:2405.21060): mamba_heads heads of mamba_head_dim
    # channels (the inner width, their product), a state of mamba_d_state a
    # channel, B and C shared by the heads of one of mamba_n_groups groups, a
    # causal depthwise convolution of mamba_conv_kernel taps on x, B and C, and
    # the chunked form's chunk of mamba_chunk_size tokens.
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_conv_kernel: int = 4
    mamba_chunk_size: int = 256
    # A Gated DeltaNet layer (arXiv:2412.06464): gdn_heads heads of gdn_key_dim
    # keys and gdn_value_dim values (a gdn_key_dim x gdn_value_dim float32
    # state a head), one scalar log-decay a head and token, a causal depthwise
    # convolution of gdn_conv_kernel taps on q, k and v, and beta in (0, 2)
    # instead of (0, 1) with gdn_allow_neg_eigval (arXiv:2411.12537).
    gdn_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv_kernel: int = 4
    gdn_allow_neg_eigval: bool = False
    # layer_group_size g > 0: layer i is a latent-attention
    # layer when (i + 1) % g == 0 and a KDA linear-attention layer otherwise
    # (models/kda.py; arXiv:2510.26692). A KDA layer has n_heads heads of
    # kda_head_dim keys and values, a causal depthwise convolution of
    # kda_conv_kernel taps on q, k and v, and a per-channel log-decay bounded
    # below by kda_gate_lower_bound a token. Its cache is a fixed-size state a
    # row, not pages.
    layer_group_size: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    kda_gate_lower_bound: float = -5.0
    # Multi-token prediction (DeepSeek-V3, arXiv:2412.19437 section 2.2): the
    # depth of the module kept beside the stack, params["mtp"]. 1 = one module:
    # an embedding norm, a hidden-state norm, a (2D, D) projection of the two
    # concatenated, one whole decoder block of the stack's last kind with a
    # cache layer of its own (index n_layers of every cache and pool), a final
    # norm; embedding and head are the stack's. Its logits at position p
    # predict token p + 2, which the serving engine takes as the draft of a
    # speculative round (models/mtp.py; spec_k without a draft model). The
    # training loss does not read it. 0 = none; the sources publish one.
    mtp_depth: int = 0
    # Manifold-constrained hyper-connections (arXiv:2512.24880): hc_mult > 1
    # residual streams a token, read, written and mixed per sublayer by
    # coefficients computed in float32 (models/hyper.py). 1 = plain residual.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    # Pipeline parallelism: split the layer stack into stages over the 'pipe'
    # mesh axis, GPipe microbatch schedule via ppermute. 1 = off.
    pipeline_stages: int = 1
    pipeline_microbatches: int = 4
    # Virtual stages per rank (Megatron-style interleaving): each rank hosts
    # this many round-robin depth chunks, shrinking the pipeline bubble by
    # the same factor. 1 = plain GPipe. Requires microbatches >= stages.
    pipeline_interleave: int = 1
    # KV-cache element type for decode: "compute" stores compute_dtype;
    # "int8" quantizes K/V per (token, head) with an fp32 amax scale —
    # halves persistent cache HBM vs bf16 (the serving memory term that
    # scales with L*B*T). Prefill attention always runs on the unquantized
    # local block; only decode-step reads dequantize.
    kv_cache_dtype: str = "compute"  # compute | int8

    def __post_init__(self) -> None:
        if self.kv_cache_dtype not in ("compute", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'compute' or 'int8', got "
                f"{self.kv_cache_dtype!r}"
            )
        if self.activation not in _ACTIVATIONS:
            raise ValueError(f"activation must be one of {_ACTIVATIONS}, got {self.activation!r}")
        if self.norm not in _NORMS:
            raise ValueError(f"norm must be one of {_NORMS}, got {self.norm!r}")
        if self.pos_embed not in _POS_EMBEDS:
            raise ValueError(f"pos_embed must be one of {_POS_EMBEDS}, got {self.pos_embed!r}")
        if self.attention_impl not in _ATTN_IMPLS:
            raise ValueError(
                f"attention_impl must be one of {_ATTN_IMPLS}, got {self.attention_impl!r}"
            )
        if self.remat not in _REMAT_POLICIES:
            raise ValueError(f"remat must be one of {_REMAT_POLICIES}, got {self.remat!r}")
        if self.decode_loop_max_tokens < 1:
            raise ValueError(
                f"decode_loop_max_tokens must be >= 1, got "
                f"{self.decode_loop_max_tokens}"
            )
        if self.ce_impl not in ("chunked", "dense"):
            raise ValueError(
                f"ce_impl must be 'chunked' or 'dense', got {self.ce_impl!r}"
            )
        if self.ring_layout not in ("contiguous", "zigzag"):
            raise ValueError(
                f"ring_layout must be 'contiguous' or 'zigzag', got {self.ring_layout!r}"
            )
        if self.d_model % self.n_heads != 0 and self.d_head is None:
            raise ValueError(
                f"d_model={self.d_model} not divisible by n_heads={self.n_heads}; set d_head"
            )
        if self.n_kv_heads is not None and (
            not 1 <= self.n_kv_heads <= self.n_heads
            or self.n_heads % self.n_kv_heads != 0
        ):
            raise ValueError(
                f"n_kv_heads={self.n_kv_heads} must divide n_heads={self.n_heads}"
            )
        if not self.use_output_proj and self.head_dim * self.n_heads != self.d_model:
            raise ValueError("use_output_proj=False requires n_heads*d_head == d_model")
        if self.tie_embeddings and self.lm_head_bias:
            raise ValueError("tie_embeddings is incompatible with lm_head_bias")
        if self.n_experts:
            if not 1 <= self.experts_per_token <= self.n_experts:
                raise ValueError(
                    f"experts_per_token={self.experts_per_token} must be in "
                    f"[1, n_experts={self.n_experts}]"
                )
            if self.expert_capacity_factor <= 0:
                raise ValueError("expert_capacity_factor must be positive")
            if self.moe_group_size < 0:
                raise ValueError("moe_group_size must be >= 0 (0 = one global group)")
            if self.moe_routing not in ("capacity", "dropless"):
                raise ValueError(
                    f"moe_routing must be 'capacity' or 'dropless', got {self.moe_routing!r}"
                )
            if self.moe_score not in ("softmax", "sigmoid"):
                raise ValueError(
                    f"moe_score must be 'softmax' or 'sigmoid', got {self.moe_score!r}"
                )
            if self.moe_routing == "dropless" and self.activation not in ("swiglu", "relu2"):
                raise ValueError(
                    "dropless experts are SwiGLU (activation='swiglu') or ungated relu^2 "
                    "(activation='relu2')"
                )
            if self.moe_routing == "capacity" and (
                self.moe_score != "softmax" or self.moe_score_bias
                or self.n_shared_experts or self.d_expert or self.moe_routed_scale != 1.0
            ):
                raise ValueError(
                    "sigmoid scores, a score bias, shared experts, d_expert and "
                    "moe_routed_scale need moe_routing='dropless'"
                )
        if self.n_experts and (
            self.n_experts % self.moe_n_group or not 1 <= self.moe_topk_group <= self.moe_n_group
            or self.experts_per_token > self.moe_topk_group * (self.n_experts // self.moe_n_group)
            or not 0 <= self.n_experts_held <= self.n_experts
        ):
            raise ValueError(
                "moe_n_group must divide n_experts, moe_topk_group groups must hold "
                "experts_per_token experts, and n_experts_held is at most n_experts"
            )
        if (self.moe_n_group > 1 or self.n_experts_held or self.moe_swiglu_limits
                or self.moe_shared_swiglu_limits) and not self.moe_dropless:
            raise ValueError(
                "moe_n_group, n_experts_held and the SwiGLU clamps need moe_routing='dropless'"
            )
        if (self.moe_swiglu_limits or self.moe_shared_swiglu_limits) and self.activation != "swiglu":
            raise ValueError("the SwiGLU clamps clamp a SwiGLU (activation='swiglu')")
        for name in ("moe_swiglu_limits", "moe_shared_swiglu_limits"):
            # a JSON round trip hands back a list; the config is a static (hashed) jit argument
            object.__setattr__(self, name, tuple(float(v) for v in getattr(self, name)))
        for limits in (self.moe_swiglu_limits, self.moe_shared_swiglu_limits):
            if limits and (len(limits) != self.n_layers or min(limits) < 0):
                raise ValueError("a SwiGLU clamp list has one limit >= 0 a layer")
        # a JSON round trip hands back a list; the config is a static (hashed) jit argument
        object.__setattr__(self, "layer_mixers", tuple(self.layer_mixers))
        object.__setattr__(self, "layer_ffns", tuple(self.layer_ffns))
        if self.embed_scale is True:
            object.__setattr__(self, "embed_scale", float(self.d_model) ** 0.5)
        if self.layer_mixers and (
            self.layer_group_size or len(self.layer_mixers) != self.n_layers
            or set(self.layer_mixers) - {"attn", "kda", "mamba", "gdn", "none"}
        ):
            raise ValueError(
                f"layer_mixers names 'attn', 'kda', 'mamba', 'gdn' or 'none' for each of "
                f"n_layers={self.n_layers} layers, and layer_group_size (the shorthand that fills "
                "it) is then left at 0"
            )
        if self.layer_ffns or "none" in self.layer_mixers:
            single = [(m == "none") != (f == "none") for m, f in self.layer_kinds]
            if (
                len(self.layer_ffns) not in (0, self.n_layers)
                or set(self.layer_ffns) - {"dense", "moe", "none"}
                or ("moe" in self.layer_ffns and not self.n_experts)
                or any(m == f == "none" for m, f in self.layer_kinds)
                or (any(single) and not all(single))
            ):
                raise ValueError(
                    f"layer_ffns names 'dense', 'moe' (an expert model's) or 'none' for each of "
                    f"n_layers={self.n_layers} layers; a layer has a mixer, an FFN or both, and a "
                    "table is of whole layers or of single sublayers, not a mix of the two"
                )
            if self.single_sublayers and (
                not self.hybrid or self.n_dense_layers or self.hc_mult > 1 or self.sandwich_norm
                or self.mtp_depth or self.pipeline_stages > 1 or self.moe_capacity
                or self.moe_swiglu_limits or self.moe_shared_swiglu_limits
            ):
                raise ValueError(
                    "a table of single sublayers is built beside recurrent layers (a hybrid "
                    "stack's per-layer caches; attention and FFN layers alone: ROADMAP) and runs on "
                    "the plain residual under one norm a layer: no n_dense_layers (the table says "
                    "it), residual streams, sandwich norms, multi-token-prediction module, pipeline, "
                    "capacity-routed experts or per-layer SwiGLU clamps"
                )
        if self.layer_group_size == 1 or self.layer_group_size < 0:
            raise ValueError("layer_group_size is a period of at least 2 layers")
        if self.hybrid:
            mixers = {mixer for mixer, _ in self.layer_kinds} - {"none"}
            if len(mixers) != 2 or "attn" not in mixers:
                raise ValueError(
                    "a hybrid stack has attention layers (their pages carry the block tables "
                    "the engine schedules by) and recurrent layers of one kind"
                )
            if "kda" in mixers and (not self.kv_lora_rank or self.kda_head_dim < 1):
                raise ValueError(
                    "a hybrid stack of KDA layers needs latent attention "
                    "(kv_lora_rank) for its attention layers and kda_head_dim"
                )
            if "kda" in mixers and (self.kda_conv_kernel < 2 or self.kda_gate_lower_bound >= 0):
                raise ValueError("kda_conv_kernel >= 2 and kda_gate_lower_bound < 0")
            if "mamba" in mixers and (
                min(self.mamba_heads, self.mamba_head_dim, self.mamba_d_state, self.mamba_chunk_size) < 1
                or self.mamba_conv_kernel < 2 or self.mamba_n_groups < 1
                or self.mamba_heads % self.mamba_n_groups or self.kv_lora_rank
            ):
                raise ValueError(
                    "a hybrid stack of Mamba-2 layers needs mamba_heads (a multiple of "
                    "mamba_n_groups), mamba_head_dim, mamba_d_state, mamba_conv_kernel >= 2 and "
                    "mamba_chunk_size, over per-head attention layers (no kv_lora_rank)"
                )
            if "gdn" in mixers and (
                min(self.gdn_heads, self.gdn_key_dim, self.gdn_value_dim) < 1
                or self.gdn_conv_kernel < 2 or self.kv_lora_rank
            ):
                raise ValueError(
                    "a hybrid stack of Gated DeltaNet layers needs gdn_heads, gdn_key_dim, "
                    "gdn_value_dim and gdn_conv_kernel >= 2, over per-head attention layers "
                    "(no kv_lora_rank)"
                )
            if self.hc_mult > 1 or self.pipeline_stages > 1 or self.attention_impl in ("ring", "ulysses"):
                raise ValueError(
                    "a hybrid stack runs with plain residuals, no pipeline and no "
                    "ring/ulysses attention"
                )
            if self.kv_cache_dtype != "compute" or self.doc_mask_token >= 0:
                raise ValueError("a hybrid stack has no int8 cache and no document mask")
        if self.residual_multiplier != 1.0 and (self.hc_mult > 1 or self.sandwich_norm):
            raise ValueError(
                "residual_multiplier scales what a sublayer adds to the plain residual: no "
                "residual streams, no sandwich norms"
            )
        if self.attention_multiplier < 0 or (self.attention_multiplier and self.kv_lora_rank):
            raise ValueError(
                "attention_multiplier (>= 0) is per-head attention's; latent attention "
                "scales by softmax_scale"
            )
        if self.logits_scaling <= 0 or (self.logits_scaling != 1.0 and (self.lm_head_bias or self.mtp_depth)):
            raise ValueError(
                "logits_scaling (> 0) divides the final hidden state: no head bias and no "
                "multi-token-prediction module's head of its own"
            )
        # a JSON round trip hands back a list; the config is a static (hashed) jit argument
        object.__setattr__(self, "attn_kinds", tuple(self.attn_kinds))
        if self.attn_kinds:
            if len(self.attn_kinds) != self.n_layers or set(self.attn_kinds) - {"window", "full"}:
                raise ValueError(
                    f"attn_kinds names 'window' or 'full' for each of n_layers={self.n_layers} layers"
                )
            if "window" in self.attn_kinds and self.sliding_window < 1:
                raise ValueError("a 'window' layer needs sliding_window")
            if (self.kv_lora_rank or self.hybrid or self.mtp_depth or self.hc_mult > 1
                    or self.pipeline_stages > 1 or self.kv_cache_dtype != "compute"):
                raise ValueError(
                    "attn_kinds is per-head attention's, over an unquantized cache: no latent "
                    "attention, hybrid stack, multi-token-prediction module, residual streams, "
                    "pipeline or int8 cache"
                )
        if not self.rope_full_layers and (self.pos_embed != "rope" or "full" not in self.attn_kinds):
            raise ValueError("rope_full_layers=False needs pos_embed='rope' and attn_kinds with full layers")
        if (self.qk_norm or self.sandwich_norm) and (self.kv_lora_rank or self.hybrid or self.hc_mult > 1):
            raise ValueError(
                "qk_norm and sandwich_norm are per-head attention's and the plain residual's: "
                "no latent attention, hybrid stack or residual streams"
            )
        if self.qk_norm_whole and (self.kv_lora_rank or self.qk_norm):
            raise ValueError(
                "qk_norm_whole norms per-head attention's whole projected width in place of "
                "qk_norm's heads: no latent attention, and one of the two"
            )
        if self.norm_placement not in ("input", "output"):
            raise ValueError(f"norm_placement must be 'input' or 'output', got {self.norm_placement!r}")
        if self.norm_placement == "output" and (
            self.kv_lora_rank or self.sandwich_norm or self.hc_mult > 1 or self.mtp_depth
            or self.state_mixer == "kda"
        ):
            raise ValueError(
                "norm_placement='output' is built for per-head attention, Mamba-2 and Gated "
                "DeltaNet layers on the plain residual: no latent attention (or KDA layers over "
                "it), sandwich norms, residual streams or multi-token-prediction module"
            )
        if self.mtp_depth not in (0, 1):
            raise ValueError(
                f"mtp_depth={self.mtp_depth}: one multi-token-prediction module is built "
                "(several chained: ROADMAP)"
            )
        if self.mtp_depth and (
            self.hybrid or self.hc_mult > 1 or self.pipeline_stages > 1
            or self.moe_capacity or self.moe_swiglu_limits or self.moe_shared_swiglu_limits
            or self.pos_embed != "rope"
        ):
            raise ValueError(
                "a multi-token-prediction module (mtp_depth) is one more block of a "
                "homogeneous stack under RoPE: no hybrid stack, residual streams, pipeline, "
                "capacity-routed experts, per-layer SwiGLU clamps or learned positions"
            )
        if not 0 <= self.n_dense_layers < max(self.n_layers, 1) or (
            self.n_dense_layers and not self.n_experts
        ):
            raise ValueError(
                f"n_dense_layers={self.n_dense_layers} needs an expert model with "
                f"more layers than that (n_layers={self.n_layers})"
            )
        if self.kv_lora_rank:
            if self.d_head != self.qk_nope_head_dim + self.qk_rope_head_dim or (
                min(self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim) <= 0
                or self.qk_rope_head_dim % 2
            ):
                raise ValueError(
                    "latent attention needs qk_nope_head_dim, an even qk_rope_head_dim "
                    "and v_head_dim, and d_head equal to the sum of the first two"
                )
            if self.pos_embed != "rope" or self.sliding_window or self.kv_cache_dtype != "compute":
                raise ValueError(
                    "latent attention runs with RoPE, no sliding window and an "
                    "unquantized cache (int8 latent pages: ROADMAP)"
                )
            if self.n_kv_heads not in (None, self.n_heads) or self.qkv_bias:
                raise ValueError("latent attention has no grouped KV heads and no QKV bias")
        if self.rope_scaling not in ("none", "yarn"):
            raise ValueError(f"rope_scaling must be 'none' or 'yarn', got {self.rope_scaling!r}")
        if self.rope_scaling == "yarn" and (self.rope_factor < 1 or self.rope_original_context < 1):
            raise ValueError("yarn needs rope_factor >= 1 and rope_original_context")
        if self.hc_mult < 1 or (self.hc_mult > 1 and self.pipeline_stages > 1):
            raise ValueError("hc_mult must be >= 1, and residual streams do not pipeline yet")
        if self.pipeline_stages < 1 or self.n_layers % self.pipeline_stages != 0:
            raise ValueError(
                f"pipeline_stages={self.pipeline_stages} must divide "
                f"n_layers={self.n_layers}"
            )
        if self.pipeline_microbatches < 1:
            raise ValueError("pipeline_microbatches must be >= 1")
        if self.pipeline_interleave < 1 or (
            self.n_layers % (self.pipeline_stages * self.pipeline_interleave) != 0
        ):
            raise ValueError(
                f"pipeline_interleave={self.pipeline_interleave} x "
                f"pipeline_stages={self.pipeline_stages} must divide "
                f"n_layers={self.n_layers}"
            )
        if self.pipeline_interleave > 1:
            if self.pipeline_stages == 1:
                raise ValueError(
                    "pipeline_interleave > 1 does nothing without "
                    "pipeline_stages > 1"
                )
            if self.pipeline_microbatches < self.pipeline_stages:
                raise ValueError(
                    "pipeline_interleave > 1 requires pipeline_microbatches >= "
                    f"pipeline_stages ({self.pipeline_microbatches} < "
                    f"{self.pipeline_stages})"
                )
        if self.pipeline_stages > 1 and (
            self.attention_impl in ("ring", "ulysses") or self.sequence_parallel
        ):
            raise ValueError(
                "pipeline parallelism does not compose with sequence/context "
                "parallelism (ring/ulysses attention or sequence_parallel)"
            )
        if self.z_loss_coef < 0:
            raise ValueError("z_loss_coef must be >= 0")
        if self.sliding_window < 0:
            raise ValueError("sliding_window must be >= 0 (0 = full causal)")
        if self.sliding_window > 0 and self.attention_impl in ("ring", "ulysses"):
            raise ValueError(
                "sliding_window is not supported by ring/ulysses attention "
                "(the rotating/all-to-all layouts assume full causal KV)"
            )
        if self.doc_mask_token >= 0:
            if self.attention_impl in ("ring", "ulysses"):
                raise ValueError(
                    "doc_mask_token (packed-document masking) is not "
                    "supported by ring/ulysses attention — segment ids are "
                    "not threaded through their collectives"
                )
            if self.pipeline_stages > 1:
                raise ValueError(
                    "doc_mask_token does not compose with pipeline "
                    "parallelism (segments are not threaded through the "
                    "pipelined block path)"
                )
            if self.doc_mask_token >= self.vocab_size:
                raise ValueError(
                    f"doc_mask_token={self.doc_mask_token} is outside the "
                    f"vocabulary (vocab_size={self.vocab_size})"
                )

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head is not None else self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    @property
    def d_ff(self) -> int:
        return int(self.mlp_ratio * self.d_model)

    @property
    def moe_dropless(self) -> bool:
        return bool(self.n_experts) and self.moe_routing == "dropless"

    @property
    def moe_capacity(self) -> bool:
        """Experts under a capacity bound: pad slots would compete with real
        tokens for it, so ragged and bucketed prefills refuse these."""
        return bool(self.n_experts) and self.moe_routing == "capacity"

    @property
    def expert_width(self) -> int:
        return self.d_expert or self.d_ff

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """The layer table, (mixer, ffn) of every layer: mixer ``"attn"``
        (per-head or latent attention, whichever the model has), ``"kda"``,
        ``"mamba"`` or ``"gdn"``, from ``layer_mixers`` (or ``layer_group_size``, the
        shorthand for a period of KDA layers closed by an attention layer); ffn
        ``"dense"`` or ``"moe"``, from ``layer_ffns`` or ``n_experts`` and
        ``n_dense_layers``. Either may be ``"none"``: the layer is then its other
        sublayer alone under one norm (``single_sublayers``)."""
        g = self.layer_group_size
        mixers = self.layer_mixers or tuple(
            "kda" if g and (i + 1) % g else "attn" for i in range(self.n_layers)
        )
        ffns = self.layer_ffns or tuple(
            "moe" if self.n_experts and i >= self.n_dense_layers else "dense"
            for i in range(self.n_layers)
        )
        return tuple(zip(mixers, ffns))

    @property
    def single_sublayers(self) -> bool:
        """A table whose every layer is one sublayer, a mixer or an FFN."""
        return any("none" in kind for kind in self.layer_kinds)

    @property
    def state_mixer(self) -> Optional[str]:
        """The kind of recurrent layer a hybrid stack has (``"kda"`` |
        ``"mamba"`` | ``"gdn"``): each keeps a fixed-size state a row where an attention
        layer keeps pages. None for a stack of attention layers alone."""
        return next((mixer for mixer, _ in self.layer_kinds if mixer not in ("attn", "none")), None)

    @property
    def hybrid(self) -> bool:
        return self.state_mixer is not None

    @property
    def layer_attn_kinds(self) -> Tuple[str, ...]:
        """"window" or "full" for every layer (a recurrent layer's entry says nothing)."""
        return self.attn_kinds or (("window" if self.sliding_window else "full",) * self.n_layers)

    @property
    def two_lifetimes(self) -> bool:
        """Window and full attention layers in one stack: two cache lifetimes."""
        return len(set(self.attn_kinds)) == 2

    @property
    def layer_runs(self) -> Tuple[Tuple[int, int], ...]:
        """The stack as runs of like layers, (first, past the last): what
        ``forward`` scans one at a time. A homogeneous model is one run, or two
        with leading dense layers; a stack of window and full attention layers
        splits where the attention kind changes too."""
        kinds, runs, start = tuple(zip(self.layer_kinds, self.layer_attn_kinds)), [], 0
        for i in range(1, self.n_layers + 1):
            if i == self.n_layers or kinds[i] != kinds[start]:
                runs.append((start, i))
                start = i
        return tuple(runs)

    @property
    def n_cache_layers(self) -> int:
        """Entries of a decode cache or page pool: the stack's layers (a layer
        with no mixer holds an empty one), then the multi-token-prediction
        module's block."""
        return self.n_layers + self.mtp_depth

    @property
    def n_kda_layers(self) -> int:
        return sum(mixer == "kda" for mixer, _ in self.layer_kinds)

    @property
    def n_state_layers(self) -> int:
        """Recurrent layers of any kind: the layers that keep a state a row."""
        return sum(mixer not in ("attn", "none") for mixer, _ in self.layer_kinds)

    @property
    def n_page_layers(self) -> int:
        """Layers that keep pages in a served pool: the stack's attention layers
        and the multi-token-prediction module's block."""
        return sum(mixer == "attn" for mixer, _ in self.layer_kinds) + self.mtp_depth

    @property
    def n_cacheless_layers(self) -> int:
        """Layers with no mixer: an FFN alone keeps nothing between calls, so
        its entry in a cache or a pool is empty."""
        return sum(mixer == "none" for mixer, _ in self.layer_kinds)

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the convolution runs over: x, then B and C of every group."""
        return self.mamba_d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def gdn_conv_dim(self) -> int:
        """Channels a Gated DeltaNet layer's convolution runs over: q, k, then v."""
        return self.gdn_heads * (2 * self.gdn_key_dim + self.gdn_value_dim)

    @property
    def latent_dim(self) -> int:
        """Values cached a token a layer by latent attention (0 = per-head K/V)."""
        return self.kv_lora_rank + self.qk_rope_head_dim if self.kv_lora_rank else 0

    @property
    def softmax_scale(self) -> float:
        """1/sqrt(head_dim), times YaRN's mscale(factor, mscale_all_dim) squared."""
        scale = self.head_dim ** -0.5
        if self.rope_scaling == "yarn" and self.rope_mscale_all_dim:
            scale *= yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2
        return scale

    @property
    def rope_yarn(self) -> Optional[Tuple[float, int, float, float, float]]:
        """What ``layers.rope_table`` needs for YaRN: (factor, original
        context, beta_fast, beta_slow, cos/sin scale), or None."""
        if self.rope_scaling != "yarn":
            return None
        scale = yarn_mscale(self.rope_factor, self.rope_mscale) / yarn_mscale(
            self.rope_factor, self.rope_mscale_all_dim
        )
        return (self.rope_factor, self.rope_original_context, self.rope_beta_fast,
                self.rope_beta_slow, scale)

    def num_params(self) -> int:
        """Analytic parameter count (matches init_params exactly; tested)."""
        d, v, t = self.d_model, self.vocab_size, self.context_length
        n = v * d  # token embedding
        if self.pos_embed == "learned":
            n += t * d
        # a sublayer's own: its norm (two under sandwich norms) and its hyper-connection
        norms = 4 if self.sandwich_norm else 2
        sub = norms // 2 * self._norm_params() + self._hc_params()
        shared = self._attn_params() + 2 * sub
        mixers = {
            "attn": self._attn_params, "kda": self._kda_params, "mamba": self._mamba_params,
            "gdn": self._gdn_params,
        }
        for mixer, ffn in self.layer_kinds:
            if mixer != "none":
                n += mixers[mixer]() + sub
            if ffn != "none":
                n += sub + (self._moe_params(self.experts_held) if ffn == "moe" else self._ffn_params(self.d_ff))
        n += self._norm_params()  # final norm
        # the module: a block of the stack's last kind, the (2D, D) projection, three norms
        ffn = self._moe_params(self.experts_held) if self.n_experts else self._ffn_params(self.d_ff)
        n += self.mtp_depth * (shared + ffn + 2 * d * d + 3 * self._norm_params())
        if not self.tie_embeddings:
            n += d * v
            if self.lm_head_bias:
                n += v
        return n

    def _attn_params(self) -> int:
        d, h, dh, g = self.d_model, self.n_heads, self.head_dim, self.kv_heads
        if self.kv_lora_rank:
            r, c = self.q_lora_rank, self.kv_lora_rank
            q = d * r + r + r * h * dh if r else d * h * dh  # down, its norm, up
            kv = d * self.latent_dim + c + c * h * (self.qk_nope_head_dim + self.v_head_dim)
            return q + kv + h * self.v_head_dim * d + (d * h if self.attn_output_gate else 0)
        n = d * h * dh + 2 * d * g * dh  # wqkv (or wq + wkv for GQA)
        if self.qkv_bias:
            n += h * dh + 2 * g * dh
        if self.use_output_proj:
            n += h * dh * d + d  # wo + bias
        if self.attn_output_gate:
            n += d * h * dh
        if self.qk_norm:
            n += 2 * dh
        if self.qk_norm_whole:
            n += (h + g) * dh
        return n

    def _kda_params(self) -> int:
        """A KDA mixer: q, k, v, decay, output gate and output projections, beta,
        the three convolutions, A_log, dt_bias and the head norm's scale."""
        d, w = self.d_model, self.n_heads * self.kda_head_dim
        return 6 * d * w + d * self.n_heads + 3 * w * self.kda_conv_kernel + (
            self.n_heads + w + self.kda_head_dim
        )

    def _mamba_params(self) -> int:
        """A Mamba-2 mixer: the input projection to [z | x B C | dt], the
        convolution's taps and bias, dt_bias, A_log and D a head, the gated
        norm's weight, the output projection."""
        d, w, c, h = self.d_model, self.mamba_d_inner, self.mamba_conv_dim, self.mamba_heads
        return d * (w + c + h) + c * (self.mamba_conv_kernel + 1) + 3 * h + w + w * d

    def _gdn_params(self) -> int:
        """A Gated DeltaNet mixer: the q, k and v projections and the taps of
        their convolution, the decay and beta projections, the output gate and
        the output projection, A_log and dt_bias a head, the head norm's weight."""
        d, h, v = self.d_model, self.gdn_heads, self.gdn_value_dim
        c = self.gdn_conv_dim
        return d * c + c * self.gdn_conv_kernel + 2 * d * h + 2 * d * h * v + 2 * h + v

    def _hc_params(self) -> int:
        """One sublayer's hyper-connection: phi, b and the three alphas."""
        n = self.hc_mult
        return (n * self.d_model + 1) * (n * n + 2 * n) + 3 if n > 1 else 0

    def _norm_params(self) -> int:
        return 2 * self.d_model if self.norm == "layernorm" else self.d_model

    def _ffn_params(self, f: int) -> int:
        """One FFN of width ``f``: the dense MLP, an expert, the shared expert."""
        d = self.d_model
        if self.activation == "swiglu":
            return d * 2 * f + f * d + ((2 * f + d) if self.mlp_bias else 0)
        # ungated (relu, gelu, relu2): two matrices
        return d * f + f * d + ((f + d) if self.mlp_bias else 0)

    def _per_expert_params(self) -> int:
        return self._ffn_params(self.expert_width)

    def _moe_params(self, n_routed: int) -> int:
        """An expert layer's FFN side with ``n_routed`` of its experts counted."""
        n = self.d_model * self.n_experts + n_routed * self._per_expert_params()
        if self.moe_score_bias:
            n += self.n_experts
        if self.n_shared_experts:
            n += self._ffn_params(self.n_shared_experts * self.expert_width)
        return n

    def num_active_params(self) -> int:
        """Params a single token's forward actually touches.

        Equal to num_params for dense models; for MoE only experts_per_token
        of the n_experts FFNs execute per token (the shared expert always
        does), so MFU/throughput math must not count the inactive experts'
        weights.
        """
        n = self.num_params()
        if self.n_experts:
            inactive = self.experts_held - self.experts_per_token
            n -= sum(ffn == "moe" for _, ffn in self.layer_kinds) * inactive * self._per_expert_params()
        if self.mtp_depth:
            # the training forward does not run the module
            n -= self.num_params() - dataclasses.replace(self, mtp_depth=0).num_params()
        return n

    def flops_per_token(self) -> int:
        """Forward+backward training FLOPs per token (6N_active + attention).

        Standard approximation used for MFU: 6 * active params for matmul
        parameters plus the attention score/value matmul term (the O(T^2)
        part). Per layer per token the QK^T and attn@V matmuls each cost
        2*T*(n_heads*d_head) forward FLOPs, x3 for fwd+bwd = 12*T*d_attn —
        note d_attn is the *query* attention width ``n_heads * d_head``
        (GQA shrinks KV projections, not the score matmuls), which differs
        from d_model whenever d_head is set explicitly. Causal attention
        computes only ~half the score matrix (and our flash kernel really
        does skip masked blocks), so the O(T^2) term carries a 1/2 factor —
        counting the full square would overstate MFU on long contexts.
        MoE counts only the experts_per_token experts a token executes.
        Latent attention's scores run over qk_nope + qk_rope and its values
        over v_head_dim, so d_attn is the mean of the two widths there (the
        expanded form; the absorbed decode form is not a training path).
        """
        d_attn = self.n_heads * self.head_dim
        if self.kv_lora_rank:
            d_attn = self.n_heads * (self.head_dim + self.v_head_dim) // 2
        return (
            6 * self.num_active_params()
            + 12 * (self.n_layers - self.n_cacheless_layers) * d_attn * self.context_length // 2
        )


# ---------------------------------------------------------------------------
# Mesh
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh: (data, fsdp, tensor, seq, expert, pipe) axes.

    Replaces the reference's DDP process-group bootstrap
    (`/root/reference/scripts/train_transformer.py:15-29`). One axis per
    parallelism strategy; axes of size 1 cost nothing. ``data=-1`` absorbs all
    remaining devices.
    """

    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    pipe: int = 1

    axis_names: Tuple[str, ...] = ("data", "fsdp", "tensor", "seq", "expert", "pipe")

    def sizes(self, n_devices: int) -> Tuple[int, ...]:
        fixed = self.fsdp * self.tensor * self.seq * self.expert * self.pipe
        data = self.data
        if data == -1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fsdp*tensor*seq*expert*pipe={fixed}"
                )
            data = n_devices // fixed
        if data * fixed != n_devices:
            raise ValueError(
                f"mesh {data}x{self.fsdp}x{self.tensor}x{self.seq}"
                f"x{self.expert}x{self.pipe} != {n_devices} devices"
            )
        return (data, self.fsdp, self.tensor, self.seq, self.expert, self.pipe)


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DataConfig:
    """Data pipeline config.

    Token files are flat uint16 memmaps — the same on-disk format as the
    reference's preprocessor output (`/root/reference/scripts/data_preprocess.py:47-62`)
    so existing datasets drop in unchanged.
    """

    train_path: str = "data/train.bin"
    val_path: str = "data/val.bin"
    dataset_name: str = "openwebtext"
    tokenizer_name: str = "gpt2"
    val_fraction: float = 0.0005
    split_seed: int = 42
    sample_seed: int = 1337  # reference uses unseeded torch.randint (Q1) — we seed
    prefetch: int = 2  # double-buffered device_put prefetch depth
    use_native_batcher: bool = True  # C++ batch gather when the extension is built


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

_LR_SCHEDULES = ("warmup_constant", "warmup_cosine", "warmup_stable_decay")


@dataclass(frozen=True)
class TrainConfig:
    batch_size: int = 32  # global batch (sequences per optimizer step)
    microbatches: int = 1  # gradient accumulation via lax.scan
    train_steps: int = 200_000
    eval_interval: int = 1000
    eval_iters: int = 250
    lr: float = 3e-4
    lr_schedule: str = "warmup_cosine"  # reference: 10% warmup then constant
    # "adamw" (reference behavior), "adafactor" (factored second moments,
    # ~0.3 bytes/param optimizer state vs Adam's 8 — fits 1B+ models on one
    # chip), or "muon" (momentum + Newton-Schulz orthogonalization for
    # hidden weight matrices, AdamW for embeddings/head/vectors — batched
    # matmul iterations, MXU-native; see training/optimizer.py).
    optimizer: str = "adamw"
    muon_momentum: float = 0.95  # muon only: nesterov momentum coefficient
    warmup_frac: float = 0.1
    min_lr_frac: float = 0.1  # cosine/decay floor as a fraction of lr
    # warmup_stable_decay (WSD) only: fraction of train_steps spent in the
    # final linear decay phase (warmup -> constant lr -> linear to
    # min_lr_frac*lr). The stable phase makes mid-run checkpoints
    # continuation-friendly (no cosine horizon baked in).
    decay_frac: float = 0.1
    weight_decay: float = 0.1
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    adam_eps: float = 1e-8
    grad_clip: float = 1.0  # 0 disables
    # Gradient STORAGE dtype. "float32" (default): the backward's output
    # tree materializes in fp32 — exact, but at 1B it is ~5 GB of the
    # 16 GB chip, the term that pins the batch knee at b8 when the
    # end-of-backward state is the peak. "bfloat16": each gradient leaf
    # is cast to bf16 as the backward produces it (XLA fuses the convert
    # into the producer), so the gradient tree and the microbatch
    # accumulator store 2 bytes/param; the fp32 cotangent chain is
    # unchanged — grads are the fp32-path values rounded once. Norm/clip
    # math and every optimizer update still reduce in fp32 per-leaf.
    # Precision note: bf16 grad storage shifts training numerics
    # slightly (Adafactor's RMS normalization absorbs most of it);
    # parity/golden runs keep float32. (Implementation note: the
    # alternative — differentiating a bf16 param VIEW — pins a full
    # bf16 param copy across the backward, AOT-measured +2.8 GiB at 1B,
    # cancelling the saving; this knob uses the cast-after-grad form.)
    grad_dtype: str = "float32"  # float32 | bfloat16
    # Exponential moving average of the params (0 = off): a fp32 shadow
    # updated after every optimizer step (ema = d*ema + (1-d)*params),
    # stored at state["ema"], checkpointed/sharded like the params.
    # Consume via `evaluate.py --ema`, `generate_text.py --ema`, or the
    # `--ema` flag on the torch/HF exporters. Typical d: 0.999-0.9999.
    ema_decay: float = 0.0
    seed: int = 0
    checkpoint_dir: str = "checkpoints"
    checkpoint_interval: int = 1000  # reference saves only once at the end
    keep_checkpoints: int = 3
    # Write checkpoint files on a background thread so the step loop never
    # stalls on disk IO (the device->host snapshot stays synchronous for
    # exactness). Single-process only: multi-host saves keep the internal
    # barrier on the main thread.
    checkpoint_async: bool = False
    # Write a final checkpoint when the run ends off a checkpoint boundary
    # (the reference's end-of-run save). False for throwaway runs —
    # benchmarks, smoke tests — that must not leave resumable state behind
    # or pay a synchronous full-state write inside a timed region.
    save_final: bool = True
    log_interval: int = 10
    metrics_path: str = ""  # JSONL sink; "" = stdout only
    debug_nans: bool = False  # op-level NaN detection (slow; debugging only)
    profile_dir: str = ""  # capture a profiler trace window into this dir
    profile_start: int = 10  # first step of the trace window
    profile_steps: int = 5  # trace window length

    def __post_init__(self) -> None:
        if self.lr_schedule not in _LR_SCHEDULES:
            raise ValueError(f"lr_schedule must be one of {_LR_SCHEDULES}")
        if self.optimizer not in ("adamw", "adafactor", "muon"):
            raise ValueError(
                "optimizer must be 'adamw', 'adafactor', or 'muon', "
                f"got {self.optimizer!r}"
            )
        if not 0.0 < self.decay_frac <= 1.0:
            raise ValueError(
                f"decay_frac must be in (0, 1], got {self.decay_frac}"
            )
        if not 0.0 <= self.ema_decay < 1.0:
            raise ValueError(
                f"ema_decay must be in [0, 1), got {self.ema_decay}"
            )
        if self.batch_size % self.microbatches != 0:
            raise ValueError(
                f"batch_size={self.batch_size} not divisible by microbatches={self.microbatches}"
            )
        if self.grad_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"grad_dtype must be 'float32' or 'bfloat16', got "
                f"{self.grad_dtype!r}"
            )


# ---------------------------------------------------------------------------
# Resilience
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ResilienceConfig:
    """Fault-tolerance knobs: anomaly detection, rollback, watchdog, faults.

    Everything here is host-side and off the hot path — the detector reads
    the metrics the trainer already fetched at log boundaries, the watchdog
    is one idle thread, and fault injection is a no-op unless ``faults`` is
    set. See resilience/ for the machinery and README "Fault tolerance" for
    the operational story (return codes, supervisor).
    """

    # --- anomaly detection (log-boundary metrics; free on the hot path) ----
    anomaly_detection: bool = False
    # Rolling window (in log-boundary samples) the spike baselines are
    # computed over. NaN/Inf detection needs no history and is always armed.
    anomaly_window: int = 32
    # Samples required before the relative-spike rules arm — an empty
    # baseline would flag ordinary early-training noise.
    anomaly_min_history: int = 5
    # loss > factor * rolling-median(loss) => anomaly ("loss_spike").
    loss_spike_factor: float = 3.0
    # grad_norm > factor * rolling-median(grad_norm) => anomaly ("grad_spike").
    grad_spike_factor: float = 10.0
    # --- rollback ----------------------------------------------------------
    # Max automatic checkpoint rollbacks per train() call; the next anomaly
    # past the budget ends the run with exit_reason="anomaly_budget"
    # (EXIT_ANOMALY, which the supervisor treats as fatal).
    rollback_budget: int = 3
    # Steps after a rollback during which new anomalies are suppressed
    # (logged, not acted on) while the detector rebuilds its baseline.
    cooldown_steps: int = 0
    # Extra batches to skip PAST the poison window on rollback. The window
    # itself (anomaly step - restored step batches) is always skipped; this
    # adds margin when the offending data region is wider than one window.
    skip_batches: int = 0
    # --- watchdog ----------------------------------------------------------
    # Host seconds without a completed step before the watchdog declares the
    # step wedged (stuck collective / hung chip), dumps all thread stacks,
    # attempts an emergency checkpoint, and exits EXIT_WEDGED. 0 = off.
    # Arms only after the first step completes, so compile time is excluded.
    watchdog_timeout_s: float = 0.0
    # --- fault injection (tests/drills only) -------------------------------
    # Deterministic fault plan, e.g. "nan@20,sigterm@50,hang@30,
    # ckpt_truncate@40": each entry fires once, right before the named step
    # executes. A resumed run does not re-fire faults at or below its start
    # step. "" = disabled.
    faults: str = ""

    def __post_init__(self) -> None:
        if self.anomaly_window < 2:
            raise ValueError(
                f"anomaly_window must be >= 2, got {self.anomaly_window}"
            )
        if self.anomaly_min_history < 1:
            raise ValueError(
                f"anomaly_min_history must be >= 1, got {self.anomaly_min_history}"
            )
        if self.loss_spike_factor <= 1.0 or self.grad_spike_factor <= 1.0:
            raise ValueError(
                "spike factors must be > 1 (a factor <= 1 flags every step): "
                f"loss={self.loss_spike_factor}, grad={self.grad_spike_factor}"
            )
        if self.rollback_budget < 0:
            raise ValueError(
                f"rollback_budget must be >= 0, got {self.rollback_budget}"
            )
        if self.cooldown_steps < 0 or self.skip_batches < 0:
            raise ValueError("cooldown_steps and skip_batches must be >= 0")
        if self.watchdog_timeout_s < 0:
            raise ValueError(
                f"watchdog_timeout_s must be >= 0, got {self.watchdog_timeout_s}"
            )
        if self.faults:
            # Fail fast on a malformed plan (lazy import: resilience.faults
            # has no config dependency, but config loads first in the
            # package import order).
            from pretraining_llm_tpu.resilience.faults import parse_faults

            parse_faults(self.faults)


# ---------------------------------------------------------------------------
# Observability
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ObservabilityConfig:
    """Run-wide telemetry knobs: events, spans, goodput export, HBM samples.

    The in-memory pieces (event bus, goodput accounting, compile counting)
    always run — they cost a few host-side dict updates per LOG BOUNDARY and
    nothing per step. The fields here gate the file sinks and samplers. See
    observability/ for the machinery and README "Observability" for usage.
    """

    # Run-event JSONL sink ("" = in-memory only). Events still reach the
    # goodput accountant and the metrics logger's `goodput` field without it;
    # the file is what scripts/obs_report.py and multi-run folds consume.
    events_path: str = ""
    # Chrome trace-event JSON of host-side spans, written at train() exit
    # ("" = off). Open in Perfetto alongside the --profile xplane dumps.
    spans_path: str = ""
    # Prometheus textfile (node-exporter textfile-collector format),
    # atomically rewritten at every log boundary and at run end ("" = off).
    prometheus_path: str = ""
    # Sample per-device HBM (Device.memory_stats) every N log boundaries
    # (0 = off). A host-side allocator query — no device sync.
    device_memory_interval: int = 0
    # Count backend compiles via jax.monitoring; compiles after the first
    # completed step become `recompile` events (a recompile storm shows up
    # in the stream instead of only as lost MFU).
    compile_telemetry: bool = True

    def __post_init__(self) -> None:
        if self.device_memory_interval < 0:
            raise ValueError(
                f"device_memory_interval must be >= 0, got "
                f"{self.device_memory_interval}"
            )


@dataclass(frozen=True)
class ServingConfig:
    """Decode-serving scheduler knobs (generation/serving.py).

    These gate host-side scheduling only — they never change emitted tokens
    (the greedy output contract in ServingEngine.run holds at every depth).
    """

    # In-flight decode-window queue depth for the pipelined scheduler:
    # how many dispatched-but-unreaped windows the engine keeps queued
    # before it blocks on the oldest. 1 reproduces the classic
    # double-buffered scheduler (reap window k-1 right after dispatching
    # window k); 2 lets the host reap/consume/admit a full window behind
    # the device, hiding the host work of one boundary entirely.
    pipeline_depth: int = 2
    # Cross-window admission batching: defer waiting prefills until at
    # least this many could be admitted in one batched prefill (0 or 1 =
    # admit eagerly every boundary). Deferral only happens while the
    # device still has active rows — an idle engine always admits
    # whatever fits, so batching can never deadlock the queue.
    admit_batch: int = 0
    # Cross-request prefix cache (generation/prefix_cache.py): finished
    # requests publish their full KV blocks into a content-addressed
    # index; new admissions map the longest cached block-aligned prefix
    # read-only and prefill only the uncached suffix. Cold cached blocks
    # are LRU-evicted under pool pressure, before any live preemption.
    # Off by default; greedy outputs are bit-identical either way.
    prefix_cache: bool = False
    # Shortest cached prefix (in blocks) worth mapping — below this the
    # table-sharing bookkeeping outweighs the prefill saved.
    prefix_cache_min_blocks: int = 1
    # Chunked prefill: split admitted prompts into chunks of at most this
    # many tokens and stream them in alongside decode windows instead of
    # running one monolithic prefill per admission. Caps how long any
    # single prefill dispatch can stall in-flight decode rows, which is
    # the dominant TTFT head-of-line term under long-prompt mixes. The
    # same budget bounds total chunk tokens per scheduler tick, so decode
    # TPOT is protected. 0 disables (monolithic prefill at admission);
    # greedy outputs are bit-identical either way.
    prefill_chunk_tokens: int = 0
    # KV-page integrity checksums (resilience/integrity.py): record a
    # digest of each published prefix-cache block's pool bytes and verify
    # it when a later request acquires the block — a corrupted shared page
    # is dropped and that request re-prefills privately instead of every
    # future hit inheriting the poison. Digests pull page bytes only at
    # publish/acquire boundaries, never per decode window. Off by default
    # (the zero-device-sync path).
    kv_checksum: bool = False
    # Quantized serving mode (models/quantize.py + the int8 KV pool):
    #   "none"    — bf16 weights, pool dtype per model.kv_cache_dtype.
    #   "int8"    — per-channel int8 block projections (attention + FFN;
    #               embeddings/lm_head/norms/biases stay bf16), dequantized
    #               at each use site with fp32 scales and bf16 accumulation.
    #   "int8-kv" — "int8" PLUS the int8 KV pool with bf16 scale pages:
    #               per-slot bytes drop from 2*Dh to Dh+2, so the pool
    #               holds ~1.94x (Dh=64) the blocks of a bf16 pool at the
    #               same HBM budget. Greedy outputs are deterministic
    #               run-to-run WITHIN the quantized graph (the integrity
    #               sentinel re-pins its golden probes there), but differ
    #               from the bf16 graph — don't mix quantized and exact
    #               replicas behind one sentinel.
    quantize: str = "none"  # none | int8 | int8-kv

    def __post_init__(self) -> None:
        if self.quantize not in ("none", "int8", "int8-kv"):
            raise ValueError(
                "serving.quantize must be 'none', 'int8' or 'int8-kv', "
                f"got {self.quantize!r}"
            )
        if self.pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {self.pipeline_depth}"
            )
        if self.admit_batch < 0:
            raise ValueError(f"admit_batch must be >= 0, got {self.admit_batch}")
        if self.prefix_cache_min_blocks < 1:
            raise ValueError(
                "prefix_cache_min_blocks must be >= 1, got "
                f"{self.prefix_cache_min_blocks}"
            )
        if self.prefill_chunk_tokens < 0:
            raise ValueError(
                "prefill_chunk_tokens must be >= 0, got "
                f"{self.prefill_chunk_tokens}"
            )


@dataclass(frozen=True)
class FrontendConfig:
    """Online serving gateway knobs (frontend/).

    All host-side: none of these change emitted tokens. They bound what the
    HTTP frontend ADMITS, not how the engine schedules what was admitted.
    """

    # Gateway bind address. Port 0 binds an ephemeral port (tests read it
    # back from ServingGateway.port).
    host: str = "127.0.0.1"
    port: int = 8000
    # Backpressure: max requests admitted and not yet terminal; excess gets
    # HTTP 429 + Retry-After instead of an unbounded queue wait.
    max_queue_depth: int = 64
    # Outstanding-token budget (sum of prompt + max_new over live
    # requests); 0 = unlimited. A depth bound alone cannot tell ten tiny
    # requests from one huge one.
    max_outstanding_tokens: int = 0
    # Retry-After hint (seconds) attached to 429 responses.
    retry_after_s: float = 1.0
    # Reject requests whose optimistic service estimate (decode-only TPOT
    # EWMA) already exceeds their deadline, instead of admitting them to
    # miss it (HTTP 504 at submit time).
    shed_infeasible: bool = True
    # Default per-request deadline applied when the client sends none;
    # 0 = no default deadline.
    default_deadline_s: float = 0.0
    # How long the idle engine-loop thread sleeps between inbox polls.
    idle_wait_s: float = 0.005
    # Per-request tracing: head-sampling fraction for requests without an
    # inbound ``traceparent`` (whose own sampled flag is honored). 0 =
    # tracing off — the default, and the zero-cost path.
    trace_sample: float = 0.0
    # Chrome-trace JSON export path, written at gateway shutdown when
    # tracing is on ("" = no export).
    trace_path: str = ""
    # /healthz returns 503 once the engine loop has gone this many
    # seconds without completing a scheduler turn. 0 disables — the
    # default, because a cold-start jit compile legitimately holds the
    # loop thread for minutes on slow hosts.
    healthz_stale_after_s: float = 0.0
    # Capacity observability ring size: per-window occupancy samples and
    # scheduler decision records kept live for /debug/* (the event-bus
    # JSONL keeps everything regardless). 0 disables the layer.
    capacity_ring: int = 512
    # ---- fleet (frontend/router.py); replicas=1 keeps the single
    # EngineLoop path with zero router overhead. -----------------------
    # Number of engine replicas behind the router tier.
    replicas: int = 1
    # Where each replica's engine lives: "inproc" (an EngineLoop thread
    # in the gateway process) or "process" (one worker subprocess per
    # replica — frontend/worker.py — behind a socket, so a kill -9 or a
    # dropped connection is a REAL fault domain, not a simulated one).
    # The router/sentinel/gateway contract is identical in both modes.
    replica_mode: str = "inproc"
    # Prefix-affinity routing: prompt tokens hashed for placement. 0
    # disables affinity (pure least-loaded).
    affinity_tokens: int = 32
    # Spill off the affinity choice when it carries this many more
    # in-system requests than the least-loaded replica.
    spill_margin: int = 4
    # Watchdog: eject a replica whose loop has active requests but has
    # not completed a scheduler turn for this long. 0 disables (same
    # cold-jit rationale as healthz_stale_after_s).
    wedged_after_s: float = 0.0
    # Relaunch backoff for ejected replicas: initial and cap (doubles).
    eject_backoff_s: float = 0.5
    eject_backoff_max_s: float = 8.0
    # Max failovers per request before it errors out (renamed from
    # ``redrive_max`` — see MIGRATION.md): a request that kills every
    # replica it lands on gets a clean terminal error after this many
    # attempts instead of fueling a redrive storm.
    redrive_max_attempts: int = 3
    # Brownout: when the healthy fraction of the fleet drops below this,
    # shed low-priority / long-deadline work with 429. 0 disables.
    brownout_min_healthy_frac: float = 0.0
    # Under brownout: shed requests with priority below this ...
    brownout_min_priority: int = 1
    # ... or deadline longer than this (0 = don't shed on deadline).
    brownout_max_deadline_s: float = 0.0
    # ---- multi-host fleet (replica_mode="process" only). -------------
    # Attach to pre-spawned workers (``worker.py --listen host:port``)
    # instead of spawning subprocesses: comma-separated "host:port" list,
    # one address per replica ("" = spawn locally). Attached workers are
    # detached (never killed) at teardown, and the stdin-orphan watch is
    # replaced by heartbeat leases.
    worker_attach: str = ""
    # Shared secret for the attach handshake: the first frame on a new
    # connection must be a hello carrying this token or the worker drops
    # the connection ("" = no auth; spawn mode ignores it).
    attach_token: str = ""
    # Heartbeat lease: a worker that hears nothing from its router for
    # this long stops admitting, drains, and parks; the router, hearing
    # nothing back, redrives the worker's in-flight work. 0 disables
    # (spawn mode's stdin-orphan + conn-EOF detection still applies).
    lease_s: float = 0.0
    # Write-ahead fleet journal (append-only JSONL): membership, fence
    # generations, and per-request committed frontiers, enough for a
    # restarted router to re-attach survivors, fence the old generation,
    # and redrive in-flight requests bit-identically ("" = no journal).
    journal_path: str = ""
    # Journal compaction threshold in MB: once the JSONL grows past this,
    # the journal rewrites itself down to its recovery_plan fold (fences,
    # live request frontiers, next_frid) via an atomic tmp+rename. 0
    # disables rotation (the journal grows without bound).
    journal_rotate_mb: float = 64.0
    # Serving-path fault plan, e.g. "replica_crash@req3:r0,slow_window@req5"
    # ("" = none). See resilience.faults.parse_serving_faults.
    serving_faults: str = ""
    # Retry-After jitter: 429/503 headers carry base * U[1, 1+frac],
    # drawn from a PRNG seeded with retry_jitter_seed (deterministic for
    # tests; decorrelates client retry herds in prod).
    retry_jitter_frac: float = 0.25
    retry_jitter_seed: int = 0
    # ---- output-integrity sentinel (resilience/integrity.py). All off
    # by default: probes, fingerprints, and checksums add zero device
    # work until a knob turns them on. ---------------------------------
    # Golden-probe period: every interval the router injects a pinned
    # greedy probe into each active replica at strict-lowest priority and
    # quarantines any replica whose output diverges from the reference
    # pinned at startup. 0 disables the sentinel entirely.
    probe_interval_s: float = 0.0
    # How many distinct probes to pin (round-robined across intervals).
    probe_count: int = 2
    # Tokens each probe decodes; longer probes catch subtler divergence
    # at proportionally higher (lowest-priority) cost.
    probe_max_new: int = 4
    # Per-replica weight fingerprint recompute period (computed on each
    # loop thread between scheduler turns; compared by the sentinel
    # against the value pinned at launch). 0 disables.
    weight_fingerprint_interval_s: float = 0.0

    def __post_init__(self) -> None:
        if not 0 <= self.port <= 65535:
            raise ValueError(f"port must be in [0, 65535], got {self.port}")
        if not 0.0 <= self.trace_sample <= 1.0:
            raise ValueError(
                f"trace_sample must be in [0, 1], got {self.trace_sample}"
            )
        if self.healthz_stale_after_s < 0:
            raise ValueError(
                f"healthz_stale_after_s must be >= 0, got "
                f"{self.healthz_stale_after_s}"
            )
        if self.max_queue_depth < 1:
            raise ValueError(
                f"max_queue_depth must be >= 1, got {self.max_queue_depth}"
            )
        if self.max_outstanding_tokens < 0:
            raise ValueError(
                f"max_outstanding_tokens must be >= 0, got "
                f"{self.max_outstanding_tokens}"
            )
        if self.retry_after_s <= 0:
            raise ValueError(
                f"retry_after_s must be > 0, got {self.retry_after_s}"
            )
        if self.default_deadline_s < 0:
            raise ValueError(
                f"default_deadline_s must be >= 0, got {self.default_deadline_s}"
            )
        if self.idle_wait_s <= 0:
            raise ValueError(f"idle_wait_s must be > 0, got {self.idle_wait_s}")
        if self.capacity_ring < 0:
            raise ValueError(
                f"capacity_ring must be >= 0 (0 disables), got "
                f"{self.capacity_ring}"
            )
        if self.replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {self.replicas}")
        if self.affinity_tokens < 0:
            raise ValueError(
                f"affinity_tokens must be >= 0, got {self.affinity_tokens}"
            )
        if self.spill_margin < 1:
            raise ValueError(
                f"spill_margin must be >= 1, got {self.spill_margin}"
            )
        if self.wedged_after_s < 0:
            raise ValueError(
                f"wedged_after_s must be >= 0, got {self.wedged_after_s}"
            )
        if self.eject_backoff_s <= 0:
            raise ValueError(
                f"eject_backoff_s must be > 0, got {self.eject_backoff_s}"
            )
        if self.eject_backoff_max_s < self.eject_backoff_s:
            raise ValueError(
                "eject_backoff_max_s must be >= eject_backoff_s, got "
                f"{self.eject_backoff_max_s} < {self.eject_backoff_s}"
            )
        if self.replica_mode not in ("inproc", "process"):
            raise ValueError(
                f"replica_mode must be 'inproc' or 'process', got "
                f"{self.replica_mode!r}"
            )
        if self.redrive_max_attempts < 0:
            raise ValueError(
                f"redrive_max_attempts must be >= 0, got "
                f"{self.redrive_max_attempts}"
            )
        if self.lease_s < 0:
            raise ValueError(f"lease_s must be >= 0, got {self.lease_s}")
        if self.journal_rotate_mb < 0:
            raise ValueError(
                f"journal_rotate_mb must be >= 0 (0 disables rotation), "
                f"got {self.journal_rotate_mb}"
            )
        if self.worker_attach:
            if self.replica_mode != "process":
                raise ValueError(
                    "worker_attach needs replica_mode='process', got "
                    f"{self.replica_mode!r}"
                )
            addrs = [a.strip() for a in self.worker_attach.split(",")]
            if len(addrs) != self.replicas:
                raise ValueError(
                    f"worker_attach lists {len(addrs)} addresses for "
                    f"{self.replicas} replicas"
                )
            for a in addrs:
                host, _, port_s = a.rpartition(":")
                if not host or not port_s.isdigit():
                    raise ValueError(
                        f"worker_attach address {a!r} is not host:port"
                    )
        if self.attach_token and not self.worker_attach:
            raise ValueError("attach_token needs worker_attach addresses")
        if not 0.0 <= self.brownout_min_healthy_frac <= 1.0:
            raise ValueError(
                "brownout_min_healthy_frac must be in [0, 1], got "
                f"{self.brownout_min_healthy_frac}"
            )
        if self.brownout_max_deadline_s < 0:
            raise ValueError(
                "brownout_max_deadline_s must be >= 0, got "
                f"{self.brownout_max_deadline_s}"
            )
        if not 0.0 <= self.retry_jitter_frac <= 1.0:
            raise ValueError(
                "retry_jitter_frac must be in [0, 1], got "
                f"{self.retry_jitter_frac}"
            )
        if self.probe_interval_s < 0:
            raise ValueError(
                f"probe_interval_s must be >= 0, got {self.probe_interval_s}"
            )
        if self.probe_count < 1:
            raise ValueError(
                f"probe_count must be >= 1, got {self.probe_count}"
            )
        if self.probe_max_new < 1:
            raise ValueError(
                f"probe_max_new must be >= 1, got {self.probe_max_new}"
            )
        if self.weight_fingerprint_interval_s < 0:
            raise ValueError(
                "weight_fingerprint_interval_s must be >= 0, got "
                f"{self.weight_fingerprint_interval_s}"
            )


# ---------------------------------------------------------------------------
# Top-level
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Config:
    model: ModelConfig = field(default_factory=ModelConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    data: DataConfig = field(default_factory=DataConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    obs: ObservabilityConfig = field(default_factory=ObservabilityConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    frontend: FrontendConfig = field(default_factory=FrontendConfig)
    name: str = "custom"

    # NOTE: pipeline stage assignment (P('pipe', ...) on the stacked layer
    # dim) COMPOSES with the per-weight expert/tensor/fsdp specs — no mesh-
    # combination restriction needed here (seq/ring composition is rejected
    # in ModelConfig).

    def replace(self, **sections: Any) -> "Config":
        return dataclasses.replace(self, **sections)

    def with_overrides(self, overrides: Dict[str, Any]) -> "Config":
        """Apply dotted-path overrides, e.g. {"model.n_layers": 4}.

        Unknown keys raise — the exact failure class the reference ships with
        (SURVEY.md Appendix B) is rejected at startup.
        """
        sections: Dict[str, Dict[str, Any]] = {}
        top: Dict[str, Any] = {}
        for key, value in overrides.items():
            if "." in key:
                section, fname = key.split(".", 1)
                if section not in ("model", "mesh", "data", "train", "resilience", "obs", "serving", "frontend"):
                    raise KeyError(f"unknown config section {section!r} in override {key!r}")
                sections.setdefault(section, {})[fname] = value
            else:
                if key != "name":
                    raise KeyError(f"unknown top-level config key {key!r}")
                top[key] = value
        new = self
        for section, kw in sections.items():
            kw = _without_retired_keys(section, kw)
            old = getattr(new, section)
            valid = {f.name for f in dataclasses.fields(old)}
            for k in kw:
                if k not in valid:
                    raise KeyError(f"unknown config key {section}.{k}")
            new = dataclasses.replace(new, **{section: dataclasses.replace(old, **kw)})
        if top:
            new = dataclasses.replace(new, **top)
        return new

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, sort_keys=True)

    @staticmethod
    def from_json(text: str) -> "Config":
        raw = json.loads(text)
        raw = {k: _without_retired_keys(k, v) if isinstance(v, dict) else v
               for k, v in raw.items()}
        return Config(
            model=ModelConfig(**raw["model"]),
            mesh=MeshConfig(**{k: tuple(v) if k == "axis_names" else v for k, v in raw["mesh"].items()}),
            data=DataConfig(**raw["data"]),
            train=TrainConfig(**raw["train"]),
            # Absent in checkpoints written before the resilience subsystem.
            resilience=ResilienceConfig(**raw.get("resilience", {})),
            # Absent in checkpoints written before the observability subsystem.
            obs=ObservabilityConfig(**raw.get("obs", {})),
            # Absent in checkpoints written before the serving scheduler knobs.
            serving=ServingConfig(**raw.get("serving", {})),
            # Absent in checkpoints written before the serving gateway.
            frontend=FrontendConfig(**raw.get("frontend", {})),
            name=raw.get("name", "custom"),
        )


# ---------------------------------------------------------------------------
# Presets — the 5 BASELINE.json configs + reference parity shape
# ---------------------------------------------------------------------------


def _gpt2_model(**kw: Any) -> ModelConfig:
    base = dict(
        vocab_size=50304,
        activation="gelu",
        norm="layernorm",
        pos_embed="learned",
        use_output_proj=True,
        tie_embeddings=True,
        qkv_bias=True,
        mlp_bias=True,
    )
    base.update(kw)
    return ModelConfig(**base)


def _llama_model(**kw: Any) -> ModelConfig:
    base = dict(
        activation="swiglu",
        norm="rmsnorm",
        pos_embed="rope",
        use_output_proj=True,
        tie_embeddings=False,
        lm_head_bias=False,
        qkv_bias=False,
        mlp_bias=False,
    )
    base.update(kw)
    return ModelConfig(**base)


_PRESETS: Dict[str, Config] = {}


def _register(name: str, cfg: Config) -> None:
    _PRESETS[name] = dataclasses.replace(cfg, name=name)


# BASELINE config #1: GPT-2 124M single-process (tiny-shakespeare, CPU ref)
_register(
    "gpt2-124m",
    Config(
        model=_gpt2_model(
            context_length=1024, d_model=768, n_heads=12, n_layers=12,
            attention_impl="flash",
        ),
        mesh=MeshConfig(),
        train=TrainConfig(batch_size=12, train_steps=5000, lr=6e-4, eval_interval=250, eval_iters=20),
    ),
)

# BASELINE config #2: GPT-2 350M data-parallel on v4-8 (psum grads only)
_register(
    "gpt2-350m-dp",
    Config(
        model=_gpt2_model(
            context_length=1024, d_model=1024, n_heads=16, n_layers=24,
            attention_impl="flash",
        ),
        mesh=MeshConfig(data=-1),
        train=TrainConfig(batch_size=32, lr=3e-4),
    ),
)

# BASELINE config #3: GPT-2 1.3B FSDP-style param/optimizer sharding on v4-32
_register(
    "gpt2-1p3b-fsdp",
    Config(
        model=_gpt2_model(
            context_length=1024, d_model=2048, n_heads=16, n_layers=24,
            remat="dots_saveable", attention_impl="flash",
        ),
        mesh=MeshConfig(data=-1, fsdp=8),
        train=TrainConfig(batch_size=64, lr=2e-4, microbatches=2),
    ),
)

# BASELINE config #4: Llama-style 1B (RoPE + SwiGLU + RMSNorm)
_register(
    "llama-1b",
    Config(
        model=_llama_model(
            vocab_size=32000,
            context_length=2048,
            d_model=2048,
            n_heads=16,
            n_layers=22,
            mlp_ratio=2.6875,  # d_ff = 5504, Llama-style 8/3 rounding
            remat="dots_saveable",
            attention_impl="flash",
        ),
        mesh=MeshConfig(data=-1, fsdp=4),
        train=TrainConfig(batch_size=32, lr=3e-4, weight_decay=0.1),
    ),
)

# BASELINE config #5: 8k-context pretraining, Pallas flash-attn + sequence
# parallel. remat=save_attn: the 2026-08-01 same-day on-chip comparison
# measured save_attn 24.2% vs dots_saveable 23.9% MFU at this preset
# (save_attn also won every gpt2-124m point across rounds).
_register(
    "gpt2-8k-sp",
    Config(
        model=_gpt2_model(
            context_length=8192,
            d_model=768,
            n_heads=12,
            n_layers=12,
            pos_embed="rope",  # learned-absolute does not extrapolate; 8k uses RoPE
            attention_impl="ring",
            sequence_parallel=True,
            remat="save_attn",
        ),
        mesh=MeshConfig(data=-1, seq=4),
        train=TrainConfig(batch_size=8, lr=3e-4),
    ),
)

# Beyond-parity: the 8k preset with grouped-query attention (12 query
# heads over 3 KV heads -> 4x less KV bandwidth). At long context the
# flash kernel's K/V streaming is the wall (8k measured 24.2% vs 43.8%
# at 1k on v5e, r4); G=4 quarters those bytes without touching the MXU
# work — the r5 long-context lever (VERDICT r4 #7) inside the proven
# kernel class (GQA flash/ring are gradient-tested, no block overrides).
_register(
    "gpt2-8k-gqa",
    Config(
        model=_gpt2_model(
            context_length=8192,
            d_model=768,
            n_heads=12,
            n_kv_heads=3,
            n_layers=12,
            pos_embed="rope",
            attention_impl="ring",
            sequence_parallel=True,
            remat="save_attn",
        ),
        mesh=MeshConfig(data=-1, seq=4),
        train=TrainConfig(batch_size=8, lr=3e-4),
    ),
)

# The reference's own default shape (config/config.py:4-8 + src/models/*):
# 3.16B params — vocab 50304, ctx 512, d 2048, 16 heads, 64 blocks, ReLU MLP,
# no attention output projection, untied biased lm_head, learned positions.
_register(
    "reference-3b",
    Config(
        model=ModelConfig(
            vocab_size=50304,
            context_length=512,
            d_model=2048,
            n_heads=16,
            n_layers=64,
            activation="relu",
            norm="layernorm",
            pos_embed="learned",
            use_output_proj=False,
            tie_embeddings=False,
            lm_head_bias=True,
            qkv_bias=False,
            mlp_bias=True,
            remat="dots_saveable",
            # Perf intent: flash. Parity experiments pin their own config
            # (scripts/parity_experiment.py builds it explicitly), so the
            # preset is free to use the fast kernel.
            attention_impl="flash",
        ),
        mesh=MeshConfig(data=-1, fsdp=4),
        train=TrainConfig(batch_size=32, train_steps=200_000, lr=1e-4, eval_interval=1000, eval_iters=250),
    ),
)

# Beyond-parity: Llama-3-style 1B with grouped-query attention (4 KV heads
# for 16 query heads -> 4x smaller KV cache at decode).
_register(
    "llama3-1b-gqa",
    Config(
        model=_llama_model(
            vocab_size=32000,
            context_length=2048,
            d_model=2048,
            n_heads=16,
            n_kv_heads=4,
            n_layers=22,
            mlp_ratio=2.6875,
            attention_impl="flash",
            remat="dots_saveable",
        ),
        mesh=MeshConfig(data=-1, fsdp=4),
        train=TrainConfig(batch_size=32, lr=3e-4, weight_decay=0.1),
    ),
)

# Beyond-parity: MoE with expert parallelism (SURVEY §2.2 lists EP as the one
# strategy the reference leaves open). 8 experts, top-2 routing, experts
# sharded over the 'expert' mesh axis.
_register(
    "moe-8x350m",
    Config(
        model=_gpt2_model(
            context_length=1024,
            d_model=1024,
            n_heads=16,
            n_layers=24,
            n_experts=8,
            experts_per_token=2,
            remat="dots_saveable",
            attention_impl="flash",
        ),
        mesh=MeshConfig(data=-1, expert=4),
        train=TrainConfig(batch_size=32, lr=3e-4),
    ),
)

# Every mechanism of the Xing4.0 family at a width a CPU smoke run holds:
# latent attention under YaRN, a leading dense layer, dropless sigmoid-routed
# experts with a shared one, four residual streams. The published widths are
# benchmark/configs/xing4.0-29b-a4b.json; this is for tests and serve.py.
_register(
    "xing-mini",
    Config(
        model=ModelConfig(
            vocab_size=256, context_length=256, d_model=64, n_heads=4, n_layers=3, d_head=24,
            mlp_ratio=2.5, activation="swiglu", norm="rmsnorm", pos_embed="rope",
            tie_embeddings=False, mlp_bias=False, norm_eps=1e-6,
            kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_scaling="yarn", rope_factor=64.0, rope_original_context=64, rope_mscale_all_dim=1.0,
            n_experts=8, experts_per_token=2, moe_routing="dropless", moe_score="sigmoid",
            moe_score_bias=True, moe_routed_scale=2.0, n_shared_experts=1, d_expert=32,
            n_dense_layers=1, hc_mult=4,
        ),
        mesh=MeshConfig(),
        data=DataConfig(tokenizer_name="byte"),
        train=TrainConfig(batch_size=8, train_steps=50, eval_interval=20, eval_iters=2, lr=1e-3),
    ),
)

# Every mechanism of the Ling-3.0 family at a width a CPU smoke run holds: two
# periods of 2 KDA linear-attention layers to 1 latent-attention layer with a
# head-wise gate, a leading dense layer, group-limited sigmoid routing over
# half of 16 experts with a shared one, a SwiGLU clamp in the last layers. The
# published widths are benchmark/configs/ling-3.0-flash.json; this is for the
# unit tests and serve.py.
_register(
    "ling-mini",
    Config(
        model=ModelConfig(
            vocab_size=256, context_length=256, d_model=64, n_heads=4, n_layers=6, d_head=24,
            mlp_ratio=2.5, activation="swiglu", norm="rmsnorm", pos_embed="rope",
            tie_embeddings=False, mlp_bias=False, norm_eps=1e-6,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            attn_output_gate=True, layer_group_size=3, kda_head_dim=16,
            n_experts=16, n_experts_held=8, experts_per_token=2, moe_routing="dropless",
            moe_score="sigmoid", moe_score_bias=True, moe_routed_scale=2.5, n_shared_experts=1,
            d_expert=32, n_dense_layers=1, moe_n_group=4, moe_topk_group=2,
            moe_swiglu_limits=(0.0, 0.0, 0.0, 0.0, 4.0, 4.0),
        ),
        mesh=MeshConfig(),
        data=DataConfig(tokenizer_name="byte"),
        train=TrainConfig(batch_size=8, train_steps=50, eval_interval=20, eval_iters=2, lr=1e-3),
    ),
)

# Every mechanism of the JoyAI-LLM-Flash family at a width a CPU smoke run
# holds: latent attention, a leading dense layer, sigmoid-routed dropless
# experts (half of them held) with a shared one, and the multi-token-prediction
# module that drafts for its own stack. The published widths are
# benchmark/configs/joyai-llm-flash.json; this is for the unit tests and
# serve.py (--spec-k 1 with no draft model).
_register(
    "joyai-mini",
    Config(
        model=ModelConfig(
            vocab_size=256, context_length=256, d_model=64, n_heads=4, n_layers=3, d_head=24,
            mlp_ratio=2.5, activation="swiglu", norm="rmsnorm", pos_embed="rope",
            tie_embeddings=False, mlp_bias=False, norm_eps=1e-6,
            kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_experts=16, n_experts_held=8, experts_per_token=2, moe_routing="dropless",
            moe_score="sigmoid", moe_score_bias=True, moe_routed_scale=2.5, n_shared_experts=1,
            d_expert=32, n_dense_layers=1, mtp_depth=1,
        ),
        mesh=MeshConfig(),
        data=DataConfig(tokenizer_name="byte"),
        train=TrainConfig(batch_size=8, train_steps=50, eval_interval=20, eval_iters=2, lr=1e-3),
    ),
)

# Every mechanism of the Trinity (afmoe) family at a width a CPU smoke run
# holds: a period of three window layers (16 positions) to one full layer with
# no position encoding, grouped-query attention with QK-norm and an
# element-wise output gate, four norms a layer, a scaled embedding, a leading
# dense layer, sigmoid-routed dropless experts with a shared one. Served, the
# window layers keep a page pool of their own (two cache lifetimes). The
# published widths are benchmark/configs/trinity-mini.json, the published
# model's own name; this preset is for the unit tests and serve.py.
_register(
    "trinity-toy",
    Config(
        model=ModelConfig(
            vocab_size=256, context_length=256, d_model=64, n_heads=4, n_kv_heads=2, n_layers=5,
            d_head=16, mlp_ratio=2.5, activation="swiglu", norm="rmsnorm", pos_embed="rope",
            tie_embeddings=False, mlp_bias=False, norm_eps=1e-5,
            sliding_window=16, attn_kinds=("window", "window", "window", "window", "full"),
            rope_full_layers=False, qk_norm=True, attn_output_gate=True, sandwich_norm=True,
            embed_scale=True,
            n_experts=8, experts_per_token=2, moe_routing="dropless", moe_score="sigmoid",
            moe_score_bias=True, moe_routed_scale=2.826, n_shared_experts=1, d_expert=32,
            n_dense_layers=1,
        ),
        mesh=MeshConfig(),
        data=DataConfig(tokenizer_name="byte"),
        train=TrainConfig(batch_size=8, train_steps=50, eval_interval=20, eval_iters=2, lr=1e-3),
    ),
)

# Every mechanism of the Granite-4.0-H (granitemoehybrid) family at a width a
# CPU smoke run holds: Mamba-2 layers (4 heads of 16 channels, a state of 16,
# chunks of 16 tokens) around one position-free grouped-query attention layer,
# the four multipliers (embedding, residual, attention scores, logits), tied
# embeddings, softmax-routed dropless experts (half of them held) with a
# shared one of twice their width. Served, a Mamba-2 layer keeps a state slot a
# row and the attention layer alone keeps pages. The published widths are
# benchmark/configs/granite-4.0-h-small.json; this is for the unit tests and
# serve.py.
_register(
    "granite-toy",
    Config(
        model=ModelConfig(
            vocab_size=256, context_length=256, d_model=32, n_heads=4, n_kv_heads=2, n_layers=5,
            mlp_ratio=1.0, activation="swiglu", norm="rmsnorm", pos_embed="none",
            tie_embeddings=True, mlp_bias=False, norm_eps=1e-5,
            layer_mixers=("mamba", "mamba", "attn", "mamba", "mamba"),
            mamba_heads=4, mamba_head_dim=16, mamba_d_state=16, mamba_chunk_size=16,
            embed_scale=12.0, residual_multiplier=0.22, attention_multiplier=0.125,
            logits_scaling=4.0,
            n_experts=8, n_experts_held=4, experts_per_token=3, moe_routing="dropless",
            moe_score="softmax", n_shared_experts=2, d_expert=16,
        ),
        mesh=MeshConfig(),
        data=DataConfig(tokenizer_name="byte"),
        train=TrainConfig(batch_size=8, train_steps=50, eval_interval=20, eval_iters=2, lr=1e-3),
    ),
)

# Every mechanism of the Olmo-Hybrid (olmo_hybrid) family at a width a CPU
# smoke run holds: two periods of three Gated DeltaNet layers (3 heads of 8 keys
# and 16 values: a rectangular state, beta in (0, 2)) to one position-free
# attention layer of 3 heads and 3 KV heads, RMSNorm over the whole width of q
# and of k, every sublayer normed on its output, a dense SwiGLU, an untied head.
# Served, a Gated DeltaNet layer keeps a state slot a row and the attention
# layers alone keep pages. The published widths are
# benchmark/configs/olmo-hybrid-7b.json; this is for the unit tests and serve.py.
_register(
    "olmo-hybrid-toy",
    Config(
        model=ModelConfig(
            vocab_size=256, context_length=256, d_model=48, n_heads=3, n_kv_heads=3, n_layers=8,
            mlp_ratio=2.0, activation="swiglu", norm="rmsnorm", pos_embed="none",
            tie_embeddings=False, mlp_bias=False, norm_eps=1e-6,
            layer_mixers=("gdn", "gdn", "gdn", "attn") * 2,
            gdn_heads=3, gdn_key_dim=8, gdn_value_dim=16, gdn_allow_neg_eigval=True,
            qk_norm_whole=True, norm_placement="output",
        ),
        mesh=MeshConfig(),
        data=DataConfig(tokenizer_name="byte"),
        train=TrainConfig(batch_size=8, train_steps=50, eval_interval=20, eval_iters=2, lr=1e-3),
    ),
)

# Every mechanism of the Nemotron-H (nemotron_h) family at a width a CPU smoke
# run holds: a table of single sublayers from the family's pattern string (M a
# Mamba-2 mixer alone, * an attention layer alone, E an expert FFN alone, each
# x + f(N(x)) under its one norm), Mamba-2 at 2 groups of 2 heads whose inner
# width (4 x 12) is not twice the hidden size, position-free attention whose
# query width (4 x 16) is not the hidden size either, ungated relu^2 experts of
# two matrices under a sigmoid router with a selection bias and a scale (half
# of them held) beside a shared one of twice their width, an untied head.
# Served, the Mamba-2 layers keep a state slot a row, the attention layer pages
# and the expert layers nothing. The published widths are
# benchmark/configs/nemotron-3-nano-30b-a3b.json; this is for the unit tests and
# serve.py.
_register(
    "nemotron-h-toy",
    Config(
        model=ModelConfig(
            vocab_size=256, context_length=256, d_model=32, n_heads=4, n_kv_heads=2, d_head=16,
            n_layers=7, mlp_ratio=0.75, activation="relu2", norm="rmsnorm", pos_embed="none",
            tie_embeddings=False, mlp_bias=False, norm_eps=1e-5,
            **layers_from_pattern("MEM*EME"),
            mamba_heads=4, mamba_head_dim=12, mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=16,
            n_experts=8, n_experts_held=4, experts_per_token=2, moe_routing="dropless",
            moe_score="sigmoid", moe_score_bias=True, moe_routed_scale=2.5,
            n_shared_experts=2, d_expert=24,
        ),
        mesh=MeshConfig(),
        data=DataConfig(tokenizer_name="byte"),
        train=TrainConfig(batch_size=8, train_steps=50, eval_interval=20, eval_iters=2, lr=1e-3),
    ),
)

# Tiny config for tests and smoke runs. Byte tokenizer: vocab 256 can't hold
# GPT-2 BPE ids, and byte-level needs no downloaded vocab files.
_register(
    "tiny",
    Config(
        model=_gpt2_model(vocab_size=256, context_length=64, d_model=32, n_heads=4, n_layers=2),
        mesh=MeshConfig(),
        data=DataConfig(tokenizer_name="byte"),
        train=TrainConfig(batch_size=8, train_steps=50, eval_interval=20, eval_iters=2, lr=1e-3),
    ),
)


def get_preset(name: str) -> Config:
    if name not in _PRESETS:
        raise KeyError(f"unknown preset {name!r}; available: {sorted(_PRESETS)}")
    return _PRESETS[name]


def list_presets() -> Tuple[str, ...]:
    return tuple(sorted(_PRESETS))
