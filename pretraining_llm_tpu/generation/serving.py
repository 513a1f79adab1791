"""Continuous-batching serving engine over the paged KV cache.

Offline generation (`generation.generate`) compiles one program per
(batch, bucket) and every row enters and leaves together. A serving
workload is the opposite: requests arrive whenever, finish whenever, and
the device must never idle waiting for the longest row. This engine keeps
ONE compiled lockstep decode program (`paged.paged_decode_step`, shape
(max_batch, max_blocks) fixed at construction) and mutates only host-side
int32 state between steps:

  admission   — a waiting request claims a free batch row + pool blocks,
                prefills its prompt into its pages, joins the next step;
  growth      — a row crossing a block boundary gets one more block;
  eviction    — a finished row frees its blocks and the row slot;
  preemption  — when the pool runs dry, the youngest running request is
                evicted and requeued (recompute-on-resume: its prompt +
                generated-so-far become the new prompt), so the oldest
                requests always run to completion — no deadlock.

TPU-first shape discipline: idle rows keep decoding into the reserved
scratch block (block 0) with their outputs ignored — a masked no-op is
cheaper than a recompile, and XLA sees a static (max_batch,) program
forever. The reference has no serving stack (batch-1 fixed-count
generate, /root/reference/src/models/transformer.py:96-114).

Speculative serving (``spec_k``): a round in place of a decode window, one to
``spec_k + 1`` tokens a row. The draft is a separate model with a pool of its
own (``draft_params`` + ``draft_cfg``: ``paged.paged_spec_round``, per-head
pools), or, with neither, the model's own multi-token-prediction module
(``cfg.mtp_depth``, ``spec_k=1``: ``paged.paged_mtp_round``), whose cache is
one more layer of the same pool under the same block tables; a row then
carries its pending draft (``drafts``) beside ``tokens`` and ``seq_lens``.

Deep pipelining: the run() scheduler keeps a depth-``pipeline_depth``
queue of dispatched-but-unreaped decode windows. Window k+1's input
tokens chain from window k's last column ON DEVICE, host ``seq_lens``
advance speculatively at dispatch, and the host reap/consume/admission
work for windows k-1, k-2, ... overlaps the device's execution of
window k. Speculation is reconciled by FLUSHING the queue (a synchronous
drain back to committed host state) whenever a decision needs exact
state — preemption and page reclaim — and replaying from there; events
the lag contract already absorbs (a row finishing early, a
sampling-dependent admission landing mid-queue) need no flush because
surplus tokens are discarded at reap by the snapshot identity check.
"""

from __future__ import annotations

import dataclasses
import logging
import time
import warnings
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.generation import paged, speculative
from pretraining_llm_tpu.generation import prefix_cache as prefix_cache_mod
from pretraining_llm_tpu.models import mla, moe, recurrent, transformer
from pretraining_llm_tpu.observability import spans as _spans
from pretraining_llm_tpu.observability import witness as _witness

_log = logging.getLogger("pretraining_llm_tpu.serving")

# The most padded prompt tokens one batched admission prefill program takes
# (ServingEngine._prefill_parts); a boundary that admits more runs several. An
# engine on a device that tells its memory starts from what fits
# (prefill_program_tokens below), every other from this.
PREFILL_PROGRAM_TOKENS = 32768

# What an expert layer's prefill holds at once, in copies of a token's
# experts_per_token rows of d_model: the sorted rows in, the experts' output, the
# combine's gather and its reshape (padded to whole tiles of 16 rows). The at-size
# compiles for a v5e read 2.6 of them in the combine alone and 232 KB a padded
# token in all on the state-space hybrid (top-10 of 4,096: 2.8 copies beside
# 1.8 GB that does not grow with the tokens); 3.75 puts its refused 16,384 and
# its accepted 8,192 tokens, and the linear-attention hybrid's accepted 32,768,
# each a factor 1.2 from the line (PERF.md section 7, "After PR 43" (5)).
# The same where the expert layer's prefill runs ops/pallas_moe.py's kernel and
# not the grouped matmuls (moe.prefill_form says which: ungated experts whose
# width is no whole number of lane tiles): beside the above it holds the sorted
# rows padded to whole row tiles, the visits' output blocks (7/6 of the rows)
# and the gather that brings the sorted order back. The at-size compiles for a
# v5e read 214 KB a padded token (6.6 copies of top-6 of 2,688) beside 0.53 GB
# that does not grow: 8 x 2,048 tokens refused by 0.52 GB, 8 x 1,024 taken with
# 1.47 GB to spare; 8.5 puts both a factor 1.2 and more from the line (PERF.md
# section 6, PR 58).
PREFILL_EXPERT_COPIES = {"grouped": 3.75, "kernel": 8.5}

# What a dense model's prefill holds at once for a padded token: its keys and
# values in every layer's staged pages, and the FFN's gate, up and hidden rows
# (160 KB on an 18-layer Mistral, whose at-size compiles read 151 KB: 0.62 GB
# for 4 x 1,024 tokens, 1.24 GB for 8 x 1,024), times this for what else is
# live and for a heap in pieces: with the serving layout's copy beside a stored
# tree its caller keeps, 1.28 GB are free, a program of 8,192 tokens found no
# room on the chip and 4 x 1,024 ran (PERF.md section 6, PR 45).
PREFILL_DENSE_MARGIN = 1.25


def prefill_program_tokens(cfg: ModelConfig, free_bytes: Optional[int], prefill_experts: str = "grouped") -> int:
    """The most padded tokens an admission prefill program of ``cfg`` may take
    beside what is resident: the largest power of two up to
    PREFILL_PROGRAM_TOKENS whose temporaries fit the ``free_bytes`` of the
    device, an expert model's by its expert layer (PREFILL_EXPERT_COPIES of the
    form a prefill's experts take, ``prefill_experts``: moe.prefill_form), a
    dense model's by its staged pages and FFN rows (PREFILL_DENSE_MARGIN).
    None (a device that does not tell, as a CPU) keeps the constant."""
    if free_bytes is None:
        return PREFILL_PROGRAM_TOKENS
    if cfg.n_experts:
        token_values = PREFILL_EXPERT_COPIES[prefill_experts] * cfg.experts_per_token * cfg.d_model
    else:
        token_values = PREFILL_DENSE_MARGIN * (
            cfg.n_layers * 2 * cfg.kv_heads * cfg.head_dim + 3 * cfg.d_ff
        )
    fit = max(1, int(free_bytes / (token_values * jnp.dtype(cfg.compute_dtype).itemsize)))
    return min(PREFILL_PROGRAM_TOKENS, 1 << (fit.bit_length() - 1))


# Where a scheduler turn's host time goes; stats["phase_s"] keeps one running
# total per phase. "other" is the turn's own bookkeeping between the rest.
PHASES = (
    "admit", "prefill_dispatch", "ensure_pages", "dispatch", "host_blocked",
    "commit", "other",
)


@dataclasses.dataclass
class _Request:
    rid: int
    prompt: List[int]
    max_new: int
    generated: List[int] = dataclasses.field(default_factory=list)
    # Tokens generated in earlier incarnations of a preempted request:
    # they were folded into `prompt` for recompute-on-resume, but they
    # belong to the OUTPUT (see _preempt/_finish).
    prefix: List[int] = dataclasses.field(default_factory=list)
    blocks: List[int] = dataclasses.field(default_factory=list)
    # Leading entries of ``blocks`` that are SHARED prefix-cache pages
    # (read-only; refcounted by the cache, never freed directly).
    n_shared: int = 0
    # Two cache lifetimes: the row's LIVE pages in the window layers' pool,
    # oldest first, and which page of the row the first one is; pages before
    # it lay wholly behind the window and went back to that pool.
    w_blocks: List[int] = dataclasses.field(default_factory=list)
    w_first: int = 0
    row: Optional[int] = None
    admit_order: int = -1  # monotonically increasing per admission
    preemptions: int = 0
    # Pipelined admission: the first sampled token stays ON DEVICE as
    # (batch_array, index) until the window it joined is reaped — the
    # engine never syncs just to learn it (see _resolve_first).
    pending_first: Optional[tuple] = None
    # Chunked prefill: the next prompt index to prefill. None = decode
    # phase (the whole prompt is resident — monolithic admission, or the
    # final chunk landed). While set, the row joins NO decode window/spec
    # round: its committed frontier is mid-prompt, and lockstep garbage
    # writes for it land at/above that frontier, overwritten by the next
    # chunk before any mask exposes them (slot-reuse discipline).
    prefill_pos: Optional[int] = None

    @property
    def n_generated(self) -> int:
        """Generated count INCLUDING a not-yet-materialized first token —
        the value scheduling math (max_new countdown, page horizons) must
        use so deferred resolution never changes allocation decisions."""
        return len(self.generated) + (1 if self.pending_first is not None else 0)


@dataclasses.dataclass
class _Window:
    """One dispatched-but-unreaped unit of device work in the in-flight
    queue. ``snapshot`` pins the (row, request) pairs the window was
    dispatched against: at reap, rows whose identity changed since (the
    request finished in an earlier reap, possibly re-admitted) are surplus
    by the lag contract and their tokens are discarded."""

    kind: str                       # "decode" | "spec"
    snapshot: List[tuple]           # [(row, _Request)] at dispatch time
    n: int                          # decode: window length; spec: k+1 bound
    toks: Any = None                # decode: (B, n) device tokens
    lp: Any = None                  # decode: ((B, n, k) values, ids) device
    emit: Any = None                # spec: (B, k+1) device emissions
    n_emit: Any = None              # spec: (B,) device per-row emit counts
    seq_dev: Any = None             # spec: (B,) device frontier at dispatch
    draft: Any = None               # spec, self-drafting: (B,) device next drafts
    moe: Any = None                 # dropless experts: the window's routing counters (device)
    t_dispatch: float = 0.0         # perf_counter at dispatch (trace spans)


class ServingEngine:
    """Continuous-batching text generation over a shared paged KV pool.

    Usage::

        eng = ServingEngine(params, cfg, max_batch=4, n_blocks=128)
        rid = eng.submit(prompt_ids, max_new_tokens=64)
        outputs = eng.run()        # {rid: [token, ...]}

    ``temperature=0`` (default) decodes greedily; sampling parameters are
    engine-global (per-request values would either recompile or pay a
    (B,)-vector mask per knob — the global default matches the common
    single-model deployment).
    """

    def __init__(
        self,
        params: Any,
        cfg: ModelConfig,
        *,
        max_batch: int = 8,
        n_blocks: int = 256,
        block_size: int = 64,
        max_seq: Optional[int] = None,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        min_p: Optional[float] = None,
        stop_token: Optional[int] = None,
        seed: int = 0,
        steps_per_sched: int = 1,
        pipeline_depth: int = 2,
        admit_batch: int = 0,
        prefill_chunk_tokens: int = 0,
        prefix_cache: bool = False,
        prefix_cache_min_blocks: int = 1,
        kv_checksum: bool = False,
        quantize: str = "none",
        mesh: Any = None,
        draft_params: Any = None,
        draft_cfg: Optional[ModelConfig] = None,
        spec_k: int = 0,
        fused_sampling: bool = True,
        logprobs_k: int = 0,
    ):
        if cfg.moe_capacity:
            # Same restriction as ragged generate: pad slots inside a
            # prefill bucket would compete for expert capacity. Dropless
            # routing has no capacity, and is served.
            raise ValueError(
                "paged serving does not support capacity-routed MoE models "
                "(moe_routing='dropless' is served)"
            )
        if cfg.hybrid:
            # State slots beside the pages (models/recurrent.py): a row's recurrent
            # state cannot be shared by prefix, shipped, digested or rolled back yet.
            refused = {
                "prefix_cache": prefix_cache, "kv_checksum": kv_checksum,
                "quantize": quantize != "none", "spec_k": bool(spec_k),
            }
            if any(refused.values()):
                raise ValueError(
                    f"a state-slot model ({cfg.state_mixer} layers) is served without "
                    + ", ".join(k for k, v in refused.items() if v)
                    + ": the prefix cache (and kv_transfer, which publishes into it) "
                    "and kv_checksum know pages only, int8 pages are not built on "
                    "its pools, and speculative decoding needs a state rollback"
                )
        elif cfg.kv_lora_rank:
            # A latent (MLA) page pool: what is not built on it yet (ROADMAP).
            refused = {
                "quantize": quantize != "none", "prefix_cache": prefix_cache,
                "kv_checksum": kv_checksum,
                "draft_params": draft_params is not None or draft_cfg is not None,
            }
            if any(refused.values()):
                raise ValueError(
                    "a latent-attention model is served without "
                    + ", ".join(k for k, v in refused.items() if v)
                    + ": int8 pages, the prefix cache (and kv_transfer, which "
                    "publishes into it) and a separate draft model's pool are not "
                    "built on the latent pool yet (spec_k with the model's own "
                    "multi-token-prediction module as the draft is)"
                )
        elif cfg.two_lifetimes:
            # Window and full attention layers in one stack: two block lists a
            # row, the window layers' pages given back behind the window.
            refused = {
                "prefix_cache": prefix_cache, "kv_checksum": kv_checksum,
                "quantize=int8-kv": quantize == "int8-kv", "spec_k": bool(spec_k),
                "prefill_chunk_tokens": bool(prefill_chunk_tokens),
            }
            if any(refused.values()):
                raise ValueError(
                    "a model of window and full attention layers (two cache lifetimes) is "
                    "served without "
                    + ", ".join(k for k, v in refused.items() if v)
                    + ": the prefix cache (and kv_transfer, which publishes into it) and "
                    "kv_checksum know one block list a row, int8 pages are not built on the "
                    "window pool, and the speculative verify and the chunk lane read a row's "
                    "pages through one table"
                )
        if cfg.doc_mask_token >= 0:
            # Decode sessions are single documents; forward() rejects the
            # combination with a cache (same sanitization as generate()).
            cfg = dataclasses.replace(cfg, doc_mask_token=-1)
        # Speculative serving: a draft proposes spec_k tokens per round, the
        # target verifies them in ONE multi-token paged forward. Greedy
        # output equals target-only serving; decode dispatches drop ~(k+1)x
        # at the draft's acceptance rate. Two draft sources: a separate draft
        # model with a pool of its own (draft_params + draft_cfg:
        # paged.paged_spec_round), or, with neither, the model's own
        # multi-token-prediction module (cfg.mtp_depth), whose cache is one
        # more layer of the SAME pool (self-drafting: paged.paged_mtp_round).
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.self_draft = bool(spec_k and cfg.mtp_depth and draft_params is None and draft_cfg is None)
        if self.self_draft:
            if spec_k > cfg.mtp_depth:
                raise ValueError(
                    f"spec_k={spec_k} exceeds the model's multi-token-prediction depth "
                    f"(mtp_depth={cfg.mtp_depth}): self-drafting proposes one token a module"
                )
            if prefill_chunk_tokens or prefix_cache:
                raise ValueError(
                    "self-drafting (spec_k with the model's own MTP module) is served "
                    "without prefill_chunk_tokens and prefix_cache: the chunk and suffix "
                    "lanes do not prefill the module"
                )
        elif (spec_k > 0) != (draft_params is not None and draft_cfg is not None):
            raise ValueError(
                "speculative serving needs all three of draft_params, "
                "draft_cfg and spec_k >= 1 (or none of them); without a draft "
                "model, spec_k needs a model with a multi-token-prediction "
                "module (mtp_depth) to draft for itself"
            )
        if cfg.mtp_depth and not self.self_draft:
            # nobody reads the module: serve the stack alone, without its pages
            cfg = dataclasses.replace(cfg, mtp_depth=0)
        # Decode-fused sampling (default): token selection runs INSIDE
        # the jitted decode window, so each window ships (B, n) token ids
        # (plus an optional (B, n, k) logprob sliver) back to the host
        # instead of per-step (B, V) logits. fused_sampling=False keeps
        # the unfused lane wired — forward-only program, a full logits
        # device->host round-trip, then a separate sampling dispatch per
        # step — as the measurement/bit-identity reference (greedy output
        # is identical by construction; tests pin it).
        self.fused_sampling = bool(fused_sampling)
        if logprobs_k < 0:
            raise ValueError(f"logprobs_k must be >= 0, got {logprobs_k}")
        if logprobs_k and not fused_sampling:
            raise ValueError(
                "logprobs_k requires fused_sampling (the logprob sliver "
                "rides the fused decode payload)"
            )
        if spec_k and (not fused_sampling or logprobs_k):
            raise ValueError(
                "speculative serving supports only the fused decode path "
                "without logprobs (spec rounds never materialize "
                "per-token logits host-side)"
            )
        self.logprobs_k = int(logprobs_k)
        # Per-request top-k logprobs, keyed by rid, one entry per OUTPUT
        # token in order: (values, token_ids) lists of length logprobs_k,
        # or None for tokens sampled inside prefill programs (each
        # request's first token, incl. post-preemption restarts) — those
        # programs don't compute the sliver. Populated only when
        # logprobs_k > 0; aligned with the finished[rid] token list.
        self.logprobs: Dict[int, List[Optional[tuple]]] = {}
        self.spec_k = int(spec_k)
        self.draft_params = draft_params
        self.draft_cfg: Optional[ModelConfig] = None
        if spec_k and (top_k or top_p or min_p):
            raise ValueError(
                "speculative serving supports temperature-only "
                "sampling (the accept/reject rule needs the raw "
                "draft/target distributions)"
            )
        if spec_k and not self.self_draft:
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab ({draft_cfg.vocab_size}) must equal "
                    f"target vocab ({cfg.vocab_size})"
                )
            if draft_cfg.n_experts:
                raise ValueError("draft model cannot be MoE (same rule)")
            if draft_cfg.doc_mask_token >= 0:
                draft_cfg = dataclasses.replace(draft_cfg, doc_mask_token=-1)
            self.draft_cfg = draft_cfg
        # Quantized serving (models/quantize.py): "int8" quantizes the
        # block projections (per-channel symmetric, dequantized at each
        # use site); "int8-kv" ALSO flips the KV pool to int8 codes with
        # bf16 scale pages — per-slot bytes Dh+2 vs 2*Dh, ~1.94x the
        # blocks of a bf16 pool at equal HBM (Dh=64). Greedy outputs are
        # deterministic run-to-run within the quantized graph but differ
        # from bf16 serving; the sentinel pins probes per-graph.
        if quantize not in ("none", "int8", "int8-kv"):
            raise ValueError(
                f"quantize must be 'none', 'int8' or 'int8-kv', got "
                f"{quantize!r}"
            )
        self.quantize = quantize
        if quantize != "none":
            from pretraining_llm_tpu.models import quantize as quantize_mod

            if quantize == "int8-kv" and cfg.kv_cache_dtype != "int8":
                # int8-kv implies the int8 pool — flip the model knob here
                # so callers set ONE serving-level switch.
                cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
                if (
                    self.draft_cfg is not None
                    and self.draft_cfg.kv_cache_dtype != "int8"
                ):
                    self.draft_cfg = dataclasses.replace(
                        self.draft_cfg, kv_cache_dtype="int8"
                    )
            # Pre-quantized params (serve.py quantizes BEFORE sharding so
            # scale leaves ride shard_params_for_inference) pass through;
            # raw bf16/fp32 trees are quantized here for direct callers.
            if not quantize_mod.is_quantized(params):
                params = quantize_mod.quantize_params_for_serving(params, cfg)
            if self.draft_cfg is not None and not quantize_mod.is_quantized(self.draft_params):
                self.draft_params = quantize_mod.quantize_params_for_serving(
                    self.draft_params, self.draft_cfg
                )
        # A dense SwiGLU's w1 laid out for the serving programs, once
        # (transformer.serving_layout; a serving mesh keeps the tree it sharded).
        # Every other leaf is the caller's own; layout_bytes is what the copies hold.
        self.params = params if mesh is not None else transformer.serving_layout(params, cfg)
        stored = {id(leaf) for leaf in jax.tree.leaves(params)}
        laid = {
            ".".join(str(k.key) for k in path): leaf.nbytes
            for path, leaf in jax.tree_util.tree_leaves_with_path(self.params)
            if id(leaf) not in stored
        }
        if laid:
            _log.info(
                "serving layout: %.2f GB laid out for the matmuls beside the stored tree: %s",
                sum(laid.values()) / 1e9,
                ", ".join(f"{name} {n / 1e9:.2f} GB" for name, n in laid.items()),
            )
        self.cfg = cfg
        # How a decode step reads the pool, fixed for the engine's programs:
        # what its input and the backend allow (models/mla.py::decode_form for
        # a latent pool, transformer.paged_attention_form for a per-head one).
        # A self-drafting round's verify and draft run spec_k + 1 queries a row:
        # a latent pool's kernel takes them as it takes one, the per-head one not yet.
        queries = self.spec_k + 1 if self.self_draft else 1
        self.decode_attention = (
            mla.decode_form(queries) if cfg.kv_lora_rank
            else transformer.paged_attention_form(
                cfg, queries, cfg.kv_cache_dtype == "int8", mesh=mesh
            )
        )
        # And how a decode step (max_batch rows, K choices each) runs a dropless
        # layer's experts (models/moe.py::experts_form); None without any.
        self.decode_experts = None
        prefill_experts = "grouped"  # and a prefill's, which sizes an admission program (prefill_program_tokens)
        if cfg.moe_dropless:
            experts = next(
                stack["mlp"]["experts"] for _, stack, _ in transformer.layer_groups(params, cfg)
                if "experts" in stack.get("mlp", {})  # a stack of mixers alone has no FFN
            )
            pairs = queries * int(max_batch) * cfg.experts_per_token
            self.decode_experts = moe.experts_form(pairs, cfg, experts, mesh=mesh)
            # the kernel's activation and tiles at that step, whether or not it runs
            self.decode_experts_plan = moe.experts_plan(pairs, cfg, experts)
            prefill_experts = moe.prefill_form(cfg, experts, mesh=mesh)
        # And how it steps a recurrent layer's state slots (the mixer's own
        # step_form, read from the pool's shape and dtype); None without any.
        self.decode_state = recurrent.step_form(cfg, int(max_batch) + 1, mesh=mesh)
        self.max_batch = int(max_batch)
        self.block_size = int(block_size)
        # Clamp max_seq so EVERY reachable prefill bucket fits the model
        # context: prefill pads prompts up to whole blocks, and a preempted
        # request can be readmitted with prompt+generated as its new prompt
        # — any p <= floor(ctx/bs)*bs then buckets within ctx, so
        # make_kv_cache can never blow up mid-serving on an accepted
        # request (block sizes that don't divide ctx are the trap).
        ctx_aligned = (cfg.context_length // self.block_size) * self.block_size
        self.max_seq = int(min(max_seq or cfg.context_length, ctx_aligned))
        # Table width: no row can ever hold more than the pool's usable
        # blocks, so clamping cuts the per-step gather/score width for
        # small pools (the attention kv_len is max_blocks * block_size).
        self.max_blocks = min(
            paged.required_blocks(self.max_seq, self.block_size), n_blocks - 1
        )
        self.temperature = temperature
        self.top_k, self.top_p, self.min_p = top_k, top_p, min_p
        self.stop_token = stop_token
        # Multi-step scheduling: decode windows of K steps per device
        # dispatch (one compiled scan), reaping/admitting only at window
        # boundaries — the lever against per-step host dispatch latency.
        # Rows finishing mid-window overrun into
        # their own pages (surplus discarded host-side).
        self.steps_per_sched = max(1, int(steps_per_sched))
        # Deep pipelining: how many dispatched-but-unreaped windows the
        # run() scheduler keeps queued before blocking on the oldest.
        # 1 = the classic double-buffered scheduler; 2 (default) hides a
        # full window of host reap/consume/admission work behind the
        # device. Purely host scheduling: greedy outputs are identical at
        # every depth (see run()).
        if pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got {pipeline_depth}")
        self.pipeline_depth = int(pipeline_depth)
        # Cross-window admission batching: defer waiting prefills until at
        # least this many could be admitted in ONE batched prefill (0/1 =
        # admit eagerly). Deferral only happens while rows are running —
        # an idle engine admits whatever fits, so no deadlock.
        if admit_batch < 0:
            raise ValueError(f"admit_batch must be >= 0, got {admit_batch}")
        self.admit_batch = int(admit_batch)
        # Chunked prefill: split each prompt into chunks of at most this
        # many tokens and interleave them between decode windows instead
        # of one monolithic prefill at admission — the token budget per
        # scheduler tick that protects decode TPOT while long prompts
        # stream in (0 = off, the historical monolithic behavior). The
        # budget is shared FCFS across all mid-prefill rows each tick;
        # rows past it wait (a `defer_prefill_chunk` decision). Greedy
        # outputs are bit-identical either way: chunks ride the SAME
        # multi-token paged forward as prefix-cache suffix prefill, and a
        # token's logits depend only on its own prompt prefix.
        if prefill_chunk_tokens < 0:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 0, got {prefill_chunk_tokens}"
            )
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        # KV integrity checksums (resilience/integrity.py): record a
        # content digest of every pool block the prefix cache publishes,
        # and re-verify it when a later admission acquires the block — a
        # corrupted shared page is dropped and re-prefilled privately
        # instead of poisoning every future hit. Off by default: digests
        # pull page bytes to the host, so the knob buys detection at
        # publish/acquire boundaries only (never inside decode windows).
        self.kv_checksum = bool(kv_checksum)

        # Sharded serving: params arrive pre-sharded
        # (generate.shard_params_for_inference); the KV pools shard their
        # kv_heads dim over the mesh's 'tensor' axis (each TP shard holds
        # its own heads' pages — the same head split as training TP), and
        # decode activations follow via the in-forward constraints.
        self.mesh = mesh

        # Two cache lifetimes: the window layers' pool is sized from what the
        # engine knows, every row at its most: the pages that hold a window,
        # one more where the window straddles a page boundary, and those a
        # decode window's writes can open (n_blocks stays the full layers').
        self.two_lifetimes = cfg.two_lifetimes
        self.window_blocks = 0
        if self.two_lifetimes:
            per_row = (
                paged.required_blocks(cfg.sliding_window, self.block_size) + 1
                + paged.required_blocks(self.steps_per_sched, self.block_size)
            )
            self.window_blocks = self.max_batch * per_row + 1

        def _build_pool(pool_cfg: ModelConfig):
            pools = transformer.make_paged_kv_pool(
                pool_cfg, n_blocks, block_size, window_blocks=self.window_blocks,
                # bf16 scale pages are what carry int8-kv past the 1.9x
                # block-capacity target; legacy int8 pools (kv_cache_dtype
                # set directly, quantize='none') keep fp32 scales for
                # bit-compatibility with the dense int8 cache.
                scale_dtype="bfloat16" if self.quantize == "int8-kv" else None,
                # a hybrid stack: one state slot a batch row beside the pages
                state_slots=self.max_batch if pool_cfg.hybrid else 0,
            )
            if mesh is None:
                return pools
            from jax.sharding import NamedSharding, PartitionSpec

            tp = mesh.shape.get("tensor", 1)
            if pool_cfg.kv_lora_rank:
                # one latent for all heads: nothing to split, every shard holds it
                from jax.sharding import NamedSharding, PartitionSpec

                return jax.tree.map(
                    lambda leaf: jax.device_put(leaf, NamedSharding(mesh, PartitionSpec())),
                    pools,
                )
            head_ax = (
                "tensor" if (tp > 1 and pool_cfg.kv_heads % tp == 0) else None
            )
            if tp > 1 and head_ax is None:
                # Same loudness convention as the flash blockwise fallback:
                # silent replication here multiplies KV HBM by the tensor
                # axis size on every shard.
                warnings.warn(
                    f"serving KV pool: kv_heads={pool_cfg.kv_heads} not "
                    f"divisible by tensor={tp}; pool REPLICATED over the "
                    f"tensor axis ({tp}x KV HBM per shard). Choose tp "
                    f"dividing kv_heads.",
                    stacklevel=2,
                )
            # Every pool leaf carries kv_heads at axis -2 (scale pools have
            # a trailing 1).
            return jax.tree.map(
                lambda leaf: jax.device_put(
                    leaf,
                    NamedSharding(
                        mesh,
                        PartitionSpec(
                            *([None] * (leaf.ndim - 2)), head_ax, None
                        ),
                    ),
                ),
                pools,
            )

        # A hybrid stack keeps a recurrent state a row beside its pages: row b
        # owns slot b of the state pools for as long as it owns the row.
        self.state_slots = cfg.hybrid
        self.pools = _build_pool(cfg)
        # Draft pools mirror the block structure exactly: SAME table/ids,
        # draft-model dims per block (paged_spec_round's shared-frontier
        # contract).
        self.d_pools = _build_pool(self.draft_cfg) if self.draft_cfg is not None else None
        # what _prefill_parts splits a boundary's admissions by: what fits beside the
        # weights and the pools just built, where the device tells (halved by _admit
        # should the compiler refuse a program all the same)
        held = next(iter(jax.tree.leaves(self.pools)[0].devices())).memory_stats() or {}
        free = held["bytes_limit"] - held["bytes_in_use"] if "bytes_limit" in held else None
        self.prefill_program_tokens = prefill_program_tokens(cfg, free, prefill_experts)
        if self.prefill_program_tokens < PREFILL_PROGRAM_TOKENS:
            _log.info("admission prefills hold at most %d padded tokens a program: %.2f GB free beside the "
                      "weights and pools", self.prefill_program_tokens, free / 1e9)
        self.n_blocks = int(n_blocks)
        self.alloc = paged.BlockAllocator(n_blocks)
        self.tables = np.zeros((self.max_batch, self.max_blocks), np.int32)
        # The window layers' allocator and table (PagedInfo.window_tables): as
        # wide as ``tables`` and indexed the same way, naming live pages only.
        self.w_alloc = paged.BlockAllocator(self.window_blocks) if self.two_lifetimes else None
        self.w_tables = np.zeros_like(self.tables) if self.two_lifetimes else None
        self.seq_lens = np.zeros((self.max_batch,), np.int32)
        self.tokens = np.zeros((self.max_batch,), np.int32)
        # Self-drafting: each row's pending draft of the token after
        # ``tokens[row]``, proposed by the module in the prefill or the round
        # that committed ``tokens[row]``; recomputed with the prompt when a
        # preempted request is admitted again.
        self.drafts = np.zeros((self.max_batch,), np.int32)
        self.rows: List[Optional[_Request]] = [None] * self.max_batch
        self.waiting: deque = deque()
        self.finished: Dict[int, List[int]] = {}
        # Requests aborted via cancel() — they never land in `finished`.
        self.cancelled: set = set()
        # Per-request lifecycle timestamps (monotonic seconds): submit_s,
        # admit_s (first row claim; preemption re-admits keep the first),
        # first_token_s (first COMMITTED output token), end_s. The online
        # frontend and the offline `serve.py --output` JSONL both read
        # these via timing_summary(); long-lived callers pop entries at
        # request end to bound growth.
        self.req_timing: Dict[int, Dict[str, float]] = {}
        self._now = time.monotonic
        # Streaming hooks (frontend/engine_loop.py): called synchronously
        # on the scheduling thread as tokens COMMIT (reap time in the
        # pipelined scheduler) and as requests finish. None = offline
        # batch mode.
        self.on_token: Optional[Callable[[int, int], None]] = None
        self.on_finish: Optional[Callable[[int, List[int]], None]] = None
        # Per-request traces (observability.tracing.RequestTrace), keyed
        # by rid — installed by the frontend via set_trace(). Empty when
        # tracing is off, and every recording site below guards on that
        # emptiness first, so the untraced hot path pays one dict truth
        # test. Recording itself is perf_counter reads + a list append:
        # no device syncs on any path.
        self.traces: Dict[int, Any] = {}
        # Optional latency histograms (observability.metrics.Histogram),
        # installed by the frontend: per-window wall duration and per-
        # window host-blocked readback seconds. Observed once per reaped
        # window — never per token.
        self.window_hist: Optional[Any] = None
        self.host_blocked_hist: Optional[Any] = None
        # Capacity observability (observability/capacity.py), installed by
        # the frontend like the histograms above: an occupancy sampler fed
        # once per reaped window (host ints the reap already holds — no
        # new device syncs), a scheduler decision log fed at the preempt/
        # evict/reclaim sites, and typed preemption counters. All None by
        # default; every producer site guards on that.
        self.capacity: Optional[Any] = None
        self.decisions: Optional[Any] = None
        self.preempt_counter: Optional[Any] = None
        self.preempt_tokens_counter: Optional[Any] = None
        # Chunked-prefill typed counters (bound by the frontend like the
        # preemption counters above): chunks dispatched, chunk tokens
        # prefilled, and ticks whose chunk program rode alongside a
        # decode window (interleaved) vs alone (dedicated).
        self.chunk_counter: Optional[Any] = None
        self.chunk_tokens_counter: Optional[Any] = None
        self.chunk_interleaved_counter: Optional[Any] = None
        self.chunk_dedicated_counter: Optional[Any] = None
        # Integrity typed counters (bound by the frontend like the rest):
        # out-of-vocab token ids caught at reap, and cached KV pages that
        # failed verify-on-acquire.
        self.invalid_token_counter: Optional[Any] = None
        self.kv_mismatch_counter: Optional[Any] = None
        self._key = jax.random.PRNGKey(seed)
        self._next_rid = 0
        self._admit_counter = 0
        # Pipelined scheduling state: the queue of in-flight windows
        # (tokens still on device, oldest first) and admission token
        # merges queued for the next dispatch — see _run_pipelined.
        self._inflight: deque = deque()
        self._pending_admit_merges: List[tuple] = []
        self.stats = {
            "steps": 0, "tokens": 0, "preemptions": 0, "admissions": 0,
            # Pipelined-scheduler telemetry: windows dispatched/reaped and
            # the host seconds spent blocked on a window's readback — the
            # quantity deep pipelining exists to shrink (host_blocked_s /
            # windows_reaped is the blocked time per window).
            "windows": 0, "windows_reaped": 0, "host_blocked_s": 0.0,
            "flushes": 0,
            # Prompt tokens actually prefilled (suffix-only for cache
            # hits) — with prefix_cache_hit_tokens this yields the
            # share of prompt tokens the prefix cache spared.
            "prefill_tokens": 0,
            # Chunked-prefill telemetry: chunk programs dispatched, chunk
            # tokens prefilled through them, and scheduler ticks whose
            # chunk dispatch shared the tick with a decode window
            # (interleaved) vs ran alone (dedicated) — the TPOT-protection
            # signal (interleaved ≫ dedicated under decode load).
            "prefill_chunks": 0, "prefill_chunk_tokens": 0,
            "chunk_windows_interleaved": 0, "chunk_windows_dedicated": 0,
            # Unfused-lane telemetry: bytes of raw (B, V) logits pulled
            # to the host per decode step. Stays 0 with fused sampling
            # (the default) — the transfer the fused path deletes.
            "logits_bytes_host": 0,
            # Always-on account of the scheduler's host time (PHASES), so an
            # untraced run can say which call stood still: running seconds
            # per phase ("host_blocked" is host_blocked_s), pipeline ticks
            # run, ticks the slow-tick rule logged, and the longest tick
            # with its own phase split (a reader that wants a fresh account
            # sets its "seconds" back to 0).
            "phase_s": {p: 0.0 for p in PHASES},
            # Pages the decode windows' attention had to read (those holding
            # a slot visible to an active row, summed over rows and steps)
            # and pages their block tables named (max_batch x max_blocks a
            # step): live / tabled is how much of the table's width the
            # traffic fills, which is what the in-place kernel saves over
            # the gather form. Logged whenever the engine runs empty.
            "attn_pages_live": 0, "attn_pages_tabled": 0,
            # Blocks of the pool owned by rows (or the prefix cache) after the
            # newest tick, and the most after any.
            "kv_blocks_in_use": 0, "kv_blocks_peak": 0,
            "ticks": 0, "slow_ticks": 0,
            "longest_tick": {"tick": 0, "seconds": 0.0, "phase_s": {}},
            # Bytes of the copies in the serving layout (0: none made).
            "layout_bytes": sum(laid.values()),
        }
        if self.two_lifetimes:
            # The second lifetime's own counters: the above then count a full
            # layer's pages; these a window layer's (read: inside the window;
            # tabled: the live pages its table names), its pool's blocks, and
            # the pages given back behind the window.
            self.stats.update(
                window_attn_pages_live=0, window_attn_pages_tabled=0,
                window_blocks_in_use=0, window_blocks_peak=0, window_pages_released=0,
            )
        self._clock = _spans.PhaseClock(self.stats["phase_s"])
        self._tick_hist: deque = deque(maxlen=_spans.SLOW_HISTORY)
        # Cross-request prefix cache: content-addressed page reuse over
        # the allocator (generation/prefix_cache.py). Off by default —
        # when on, greedy outputs stay bit-identical to cache-off runs
        # (the survivor-identity contract; tests/test_prefix_cache.py).
        self.prefix_cache: Optional[prefix_cache_mod.PrefixCache] = None
        if prefix_cache:
            self.prefix_cache = prefix_cache_mod.PrefixCache(
                self.alloc, self.block_size,
                min_blocks=prefix_cache_min_blocks, stats=self.stats,
            )
        _witness.ensure()  # the process's late-wake witness: what a slow tick's line asks

    # -- public API --------------------------------------------------------

    def pool_info(self) -> Dict[str, Any]:
        """KV-pool layout facts for /debug/engine, the capacity snapshot
        and the `pllm_kv_pool_bytes` gauge: element dtypes, bytes per
        block and total pool bytes — summed over ALL pool leaves (scale
        pages included), host-side shape math only (no device sync).
        Draft pools (speculative serving) are reported separately."""
        pools = self.pools
        # the first layer that has pages; a hybrid stack's recurrent layers keep
        # state slots, a layer with no mixer keeps nothing
        layer0 = next(f for f in pools["layers"] if f and "state_pool" not in f)
        state = int(sum(
            leaf.nbytes for f in pools["layers"] if "state_pool" in f
            for leaf in jax.tree.leaves(f)
        ))
        total = int(
            sum(leaf.nbytes for leaf in jax.tree.leaves(pools["layers"]))
        ) - state
        # the window layers' own pool (two cache lifetimes); the per-block and
        # per-token figures below are then the full layers'
        window_layers = paged.window_layers(self.cfg)
        window = int(sum(
            leaf.nbytes for i in window_layers for leaf in jax.tree.leaves(pools["layers"][i])
        ))
        total -= window
        info = {
            "quantize": self.quantize,
            "kv_dtype": str(next(iter(layer0.values())).dtype),
            "kv_scale_dtype": (
                str(layer0["k_scale_pool"].dtype)
                if "k_scale_pool" in layer0 else None
            ),
            "n_blocks": self.n_blocks,
            "block_size": self.block_size,
            "bytes_per_block": total // self.n_blocks,
            # over all layers: 2 * kv_heads * Dh elements a layer per head,
            # latent_dim elements a layer for a latent pool
            "bytes_per_token": total // (self.n_blocks * self.block_size),
            "pool_bytes": total + window,
            # "gather" | "kernel" (per head), "gather" | "latent_kernel" (latent)
            "decode_attention": self.decode_attention,
        }
        if "k_pool" in layer0:
            # the head axis the pages are stored with: the model's kv heads, or
            # more where the pool pads them (ops/pallas_paged.py::pool_kv_heads)
            info["pool_kv_heads"] = int(layer0["k_pool"].shape[-2])
        if self.decode_experts:
            info["decode_experts"] = self.decode_experts  # "kernel" | "grouped"
            info["decode_experts_plan"] = self.decode_experts_plan
        if self.two_lifetimes:
            info.update(
                full_pool_bytes=total, full_layers=self.cfg.n_layers - len(window_layers),
                window_pool_bytes=window, window_layers=len(window_layers),
                window_n_blocks=self.window_blocks, sliding_window=self.cfg.sliding_window,
            )
        if self.state_slots:
            # the other kind of cache: a fixed-size state a row, whatever its length
            info.update(
                state_slots=self.max_batch, state_bytes=state,
                bytes_per_slot=state // (self.max_batch + 1),
                decode_state=self.decode_state,  # "kernel" | "jnp"
                # the two kinds of layer: which mixer keeps the slots, in how
                # many layers, and how many layers the pages above are for
                # and how many keep no cache at all (an FFN alone: the layer table)
                state_mixer=self.cfg.state_mixer, state_layers=self.cfg.n_state_layers,
                page_layers=self.cfg.n_page_layers, cacheless_layers=self.cfg.n_cacheless_layers,
            )
        if self.self_draft:
            # the module's pages are one more layer of ``pools``, counted above
            info["draft"] = "mtp"
        if self.d_pools is not None:
            info["draft"] = "model"
            info["draft_pool_bytes"] = int(
                sum(leaf.nbytes for leaf in jax.tree.leaves(self.d_pools))
            )
        return info

    def health_gauges(self) -> Dict[str, Any]:
        """Point-in-time engine occupancy for the fleet health surface
        (worker ``health_pull`` replies and Router.fleet_health): row and
        KV-pool occupancy, queue depth, and the KV-migration counters.
        Host containers only — mutated between scheduler turns, each
        read an atomic snapshot — so gateway/worker threads may call it
        while the engine thread runs, at worst one turn stale. Block 0
        is reserved scratch, hence the ``- 1`` (same accounting as
        EngineLoop.debug_engine; the CI gate ties them out)."""
        pool_total = self.alloc.n_blocks - 1
        pool_free = self.alloc.available
        cache = self.prefix_cache
        pool_cold = cache.evictable if cache is not None else 0
        stats = dict(self.stats)
        return {
            "rows_active": sum(r is not None for r in list(self.rows)),
            "rows_capacity": self.max_batch,
            "waiting": len(self.waiting),
            "pool_total": pool_total,
            "pool_free": pool_free,
            "pool_cold": pool_cold,
            "pool_live": pool_total - pool_free - pool_cold,
            "kv_pages_adopted": int(stats.get("kv_pages_adopted", 0)),
            "kv_pages_rejected": int(stats.get("kv_pages_rejected", 0)),
            "preemptions": int(stats.get("preemptions", 0)),
        }

    def validate_request(
        self, prompt_ids: Sequence[int], max_new_tokens: Any
    ) -> int:
        """Everything submit() checks, without queueing anything — clear
        ``ValueError``s AT SUBMIT TIME (the gateway maps them to 400)
        instead of a shape/gather failure later inside dispatch. Reads
        only construction-time constants, so concurrent gateway threads
        may call it while the engine thread runs. Returns the normalized
        integer ``max_new_tokens``."""
        try:
            max_new = int(max_new_tokens)
        except (TypeError, ValueError):
            raise ValueError(
                f"max_new_tokens must be an integer, got "
                f"{type(max_new_tokens).__name__}"
            )
        if max_new != max_new_tokens:  # reject 2.5 -> 2 silent truncation
            raise ValueError(
                f"max_new_tokens must be an integer, got {max_new_tokens!r}"
            )
        p = len(prompt_ids)
        if p == 0:
            raise ValueError("empty prompt")
        ids = np.asarray(prompt_ids)
        if ids.ndim != 1:
            raise ValueError(
                f"prompt must be a flat list of token ids, got an array of "
                f"shape {ids.shape}"
            )
        if ids.dtype.kind not in "iu":
            raise ValueError(
                f"prompt must be integer token ids, got dtype {ids.dtype}"
            )
        lo, hi = int(ids.min()), int(ids.max())
        if lo < 0 or hi >= self.cfg.vocab_size:
            raise ValueError(
                f"prompt token ids must be in [0, {self.cfg.vocab_size}); "
                f"got range [{lo}, {hi}]"
            )
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        total = p + max_new
        if total > self.max_seq:
            raise ValueError(
                f"prompt({p}) + max_new({max_new}) = {total} exceeds "
                f"max_seq={self.max_seq}"
            )
        if paged.required_blocks(total, self.block_size) > self.alloc.n_blocks - 1:
            raise ValueError(
                f"request needs {paged.required_blocks(total, self.block_size)} "
                f"blocks; the pool only has {self.alloc.n_blocks - 1}"
            )
        return max_new

    def submit(self, prompt_ids: Sequence[int], max_new_tokens: int) -> int:
        """Queue a request; returns its id. Fails fast if the request can
        never fit (prompt + generation must fit max_seq AND the pool)."""
        max_new = self.validate_request(prompt_ids, max_new_tokens)
        rid = self._next_rid
        self._next_rid += 1
        self.req_timing[rid] = {"submit_s": self._now()}
        self.waiting.append(_Request(rid, [int(t) for t in prompt_ids], max_new))
        return rid

    def set_trace(self, rid: int, trace: Any) -> None:
        """Attach a RequestTrace to a submitted request; the scheduler
        records queue/prefill/window spans into it. ``None`` is a no-op
        (the unsampled case), so callers need no guard."""
        if trace is not None:
            self.traces[rid] = trace

    def pop_trace(self, rid: int) -> Any:
        """Detach (and return) a request's trace at terminal time; the
        caller owns finishing it."""
        return self.traces.pop(rid, None)

    def cancel(self, rid: int) -> bool:
        """Abort a live request, releasing its row and pool blocks
        immediately. A waiting request unlinks with no device work; a
        running one first FLUSHES the in-flight window queue — windows
        already dispatched keep writing K/V into the victim's pages on
        device, so freeing those blocks before the drain would hand
        live-written pages to the next admission — then releases the row.
        Tokens the flush commits still stream through ``on_token``; the
        caller owns the terminal notification. Returns False when the
        request is unknown or already finished (cancellation lost the
        race — its output is in ``finished``)."""
        for req in self.waiting:
            if req.rid == rid:
                self.waiting.remove(req)
                self._mark_cancelled(rid)
                return True
        req = next(
            (r for r in self.rows if r is not None and r.rid == rid), None
        )
        if req is None:
            return False
        self._flush_inflight()
        # The drain may have finished the request (its surviving tokens
        # were committed and streamed) — then there is nothing to cancel.
        if req.row is None or self.rows[req.row] is not req:
            return False
        # A victim admitted this very boundary may still hold its first
        # token on device; resolving it can itself finish the request.
        self._resolve_first(req)
        if req.row is None:
            return False
        self._release_row(req)
        self._mark_cancelled(rid)
        return True

    def _mark_cancelled(self, rid: int) -> None:
        self.cancelled.add(rid)
        self.stats["cancelled"] = self.stats.get("cancelled", 0) + 1
        t = self.req_timing.get(rid)
        if t is not None:
            t["end_s"] = self._now()

    def timing_summary(self, rid: int) -> Dict[str, float]:
        """Lifecycle latencies (seconds) for a request: ``queue_wait_s``
        (submit -> first row claim), ``ttft_s`` (submit -> first committed
        output token), ``e2e_s`` (submit -> finish/cancel). Only phases
        the request actually reached appear."""
        t = self.req_timing.get(rid)
        if not t:
            return {}
        out: Dict[str, float] = {}
        sub = t["submit_s"]
        if "admit_s" in t:
            out["queue_wait_s"] = t["admit_s"] - sub
        if "first_token_s" in t:
            out["ttft_s"] = t["first_token_s"] - sub
        if "end_s" in t:
            out["e2e_s"] = t["end_s"] - sub
        if "cached_tokens" in t:
            # Prompt tokens served from the prefix cache instead of
            # prefill, summed across admissions (a preemption resume that
            # re-hits its own published pages counts its savings too).
            out["cached_tokens"] = int(t["cached_tokens"])
        return out

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.rows)

    def has_work(self) -> bool:
        return bool(self.waiting) or self.n_active > 0

    def _window_len(self) -> int:
        """Effective decode-window length: ``steps_per_sched`` clamped by
        the active rows' token budget. When every row needs at most R more
        tokens, a full window wastes (sps - R) lockstep steps on rows that
        already finished — the tail-latency term at large windows. The
        clamp buckets UP to a power of two so the jit cache stays at
        log2(sps) window-program variants instead of one per residual
        length. (Pipelined mode sees n_generated one window stale: the
        clamp then OVERestimates the budget — never truncates a live
        row.)"""
        n = self.steps_per_sched
        if n <= 1:
            return max(1, n)
        rem = max(
            (req.max_new - req.n_generated for req in self.rows
             if req is not None),
            default=n,
        )
        if rem >= n:
            return n
        b = 1
        while b < max(1, rem):
            b <<= 1
        return min(b, n)

    def _n_decode_rows(self) -> int:
        """Rows eligible for decode windows/spec rounds: active AND past
        their prefill phase. Mid-chunk rows are excluded from dispatch
        snapshots — their frontier is mid-prompt."""
        return sum(
            1 for r in self.rows
            if r is not None and r.prefill_pos is None
        )

    def _note_chunk_window(self, decoded: bool) -> None:
        """Tick-level interleave accounting: a chunk program that shared
        its tick with a decode dispatch protected TPOT (interleaved);
        one that ran alone had the engine to itself (dedicated)."""
        if decoded:
            self.stats["chunk_windows_interleaved"] += 1
            if self.chunk_interleaved_counter is not None:
                self.chunk_interleaved_counter.inc()
        else:
            self.stats["chunk_windows_dedicated"] += 1
            if self.chunk_dedicated_counter is not None:
                self.chunk_dedicated_counter.inc()

    def step(self) -> None:
        """One scheduling round: admit -> prefill chunks (chunked mode)
        -> grow/preempt -> a window of ``steps_per_sched`` lockstep
        decode steps (clamped to the active rows' remaining-token
        budget, or ONE speculative round when spec_k is set) -> reap.
        A no-op when nothing is running or waiting."""
        self._admit()
        chunked = self._dispatch_prefill_chunks(defer=False)
        self._release_window_pages()
        decoded = self._step_decode() if self._n_decode_rows() else False
        if chunked:
            self._note_chunk_window(decoded)
        self._note_blocks_in_use()

    def _count_attention_pages(self, n: int) -> None:
        """``attn_pages_live`` / ``attn_pages_tabled`` of the ``n``-step
        decode window about to go out, from the decoding rows' committed
        lengths: step j of a row sees slots (seq + j - sliding_window,
        seq + j]."""
        bs = self.block_size
        seq = self.seq_lens[[
            i for i, r in enumerate(self.rows)
            if r is not None and r.prefill_pos is None
        ]]
        last = np.minimum(
            seq[:, None] + np.arange(n)[None, :], self.max_blocks * bs - 1
        )

        def live(window: int) -> int:
            first = np.maximum(last - window + 1, 0) // bs if window else 0
            return int(np.sum(last // bs - first + 1))

        window = self.cfg.sliding_window
        if self.two_lifetimes:
            # a window layer's pages beside a full layer's
            self.stats["window_attn_pages_live"] += live(window)
            self.stats["window_attn_pages_tabled"] += n * sum(
                len(r.w_blocks) for r in self.rows if r is not None and r.prefill_pos is None)
            window = 0
        self.stats["attn_pages_live"] += live(window)
        self.stats["attn_pages_tabled"] += n * self.max_batch * self.max_blocks

    def _decode_tables(self) -> np.ndarray:
        """The block tables a decode window goes out with. A state-slot model's
        decode step updates the slot of every row whose table names a page, so
        a row that rides no window (mid-prefill: its chunks are building its
        state) goes out with an empty table, like a free row."""
        if not (self.state_slots and self.prefill_chunk_tokens):
            return self.tables  # no row is ever mid-prefill without the chunk lane
        tables = self.tables.copy()
        tables[[i for i, r in enumerate(self.rows) if r is not None and r.prefill_pos is not None]] = 0
        return tables

    def _step_decode(self) -> bool:
        """The synchronous decode arm of step(); True when a decode
        window (or spec round) actually ran."""
        if self.spec_k:
            return self._spec_step()
        n = self._window_len()
        self._ensure_write_pages(horizon=n)
        if self._n_decode_rows() == 0:  # everyone got preempted (tiny pool)
            return False
        # Backstop for the PagedInfo capacity invariant (submit() bounds
        # every request structurally; this keeps scheduler bugs loud).
        # Multi-step windows may overshoot capacity mid-window — that is
        # handled by the model's scratch-redirect guard; the invariant
        # here is on the WINDOW-START state only.
        paged.check_paged_bounds(self.tables, self.seq_lens, self.block_size)
        self._count_attention_pages(n)
        self._key, sub = jax.random.split(self._key)
        toks, lp, moe = self._decode_window(
            jnp.asarray(self.tokens), jnp.asarray(self._decode_tables()),
            jnp.asarray(self.seq_lens), sub, n, raw_key_single=True,
        )
        window = np.asarray(toks)  # (B, n)
        lp_host = None
        if lp is not None:
            lp_host = (np.asarray(lp[0]), np.asarray(lp[1]))
        if moe is not None:
            self._count_moe(moe, n)
        self.stats["steps"] += n
        for row, req in enumerate(self.rows):
            if req is None or req.prefill_pos is not None:
                continue
            self._consume_tokens(
                req, row, window[row], advance_seq=True,
                lp=None if lp_host is None
                else (lp_host[0][row], lp_host[1][row]),
            )
        return True

    def _decode_window(self, base, tables_dev, seq_dev, key, n,
                       raw_key_single=False):
        """ONE definition of the decode-window device dispatch for the
        synchronous and pipelined schedulers. Returns ``(toks, lp, moe)``:
        ``toks`` a (B, n) DEVICE token array (the pipelined path chains
        its last column without a sync), ``lp`` None or the device
        ``((B, n, k) values, (B, n, k) ids)`` logprob sliver, ``moe`` None
        or a dropless expert model's device routing counters of the window
        (``paged.paged_decode_steps``; the other lanes do not count).

        Fused (default): sampling runs inside the jitted step program —
        the host payload per window is token ids (+ the optional
        sliver), never logits. Unfused: the measurement/reference lane —
        per step, a forward-only program returns full (B, V) logits,
        they cross device->host (counted in stats["logits_bytes_host"]),
        and a SEPARATE sampling dispatch picks the token. Greedy output
        is bit-identical between the two lanes by construction: same
        forward, same argmax, same key stream (``raw_key_single`` keeps
        the sync n==1 path on the raw key exactly like
        paged_decode_step)."""
        common = dict(
            cfg=self.cfg, temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p, min_p=self.min_p, mesh=self.mesh,
        )
        if self.two_lifetimes:
            # a copy: the CPU backend may alias a numpy buffer, and the next
            # turn's release zeroes entries that this window still reads
            common["window_tables"] = jnp.asarray(self.w_tables.copy())
        single = n == 1 and raw_key_single
        if not self.fused_sampling:
            skeys = [key] if single else list(jax.random.split(key, n))
            sample_kw = dict(
                temperature=self.temperature, top_k=self.top_k,
                top_p=self.top_p, min_p=self.min_p,
            )
            tok, seq, cols = base, seq_dev, []
            for sub in skeys:
                logits, self.pools = paged.paged_decode_logits(
                    self.params, self.pools, tok, tables_dev, seq,
                    cfg=self.cfg, mesh=self.mesh, window_tables=common.get("window_tables"),
                )
                # THE round-trip fused sampling deletes: every step pays
                # a (B, V) f32 device->host transfer + a second dispatch.
                logits_host = np.asarray(logits)
                self.stats["logits_bytes_host"] += logits_host.nbytes
                tok = paged.sample_tokens(
                    jnp.asarray(logits_host), sub, **sample_kw
                )
                cols.append(tok)
                seq = seq + 1
            return jnp.stack(cols, axis=1), None, None
        dev_args = (self.params, self.pools, base, tables_dev, seq_dev, key)
        if self.logprobs_k:
            if single:
                nxt, lpv, lpi, self.pools = paged.paged_decode_step_lp(
                    *dev_args, logprobs_k=self.logprobs_k, **common
                )
                return nxt[:, None], (lpv[:, None], lpi[:, None]), None
            toks, lpv, lpi, self.pools = paged.paged_decode_steps_lp(
                *dev_args, n_steps=n, logprobs_k=self.logprobs_k, **common
            )
            return toks, (lpv, lpi), None
        if single:
            nxt, self.pools = paged.paged_decode_step(*dev_args, **common)
            return nxt[:, None], None, None
        toks, self.pools = paged.paged_decode_steps(
            *dev_args, n_steps=n, **common
        )
        if self.cfg.moe_dropless:
            # the window's routing counters ride beside its tokens
            return toks[0], None, toks[1]
        return toks, None, None

    def _count_moe(self, counters: Any, n_steps: int, queries: int = 1) -> Dict[str, int]:
        """Add a reaped window's routing counters into the stats:
        ``moe_expert_tokens`` (expert layers, E) tokens routed to each expert,
        ``moe_experts_touched`` (expert layers,) experts that got a token,
        summed over steps, ``moe_visits`` the visits their groups take in the
        expert kernel (weight reads: equal to the touched where every group
        fits one visit) and ``moe_steps``. Idle rows route too (their
        tokens are discarded, their experts are read all the same). Returns
        the window's own totals, the ``serving.commit`` span's metadata:
        steps, expert layers, experts a layer (those held), experts touched,
        weight reads, pairs routed (every row's choices, wherever the expert lives), pairs
        that met an expert held here, and the busiest expert's pairs summed
        over layers. A self-drafting round is one step of ``queries`` tokens a
        row, the module's block one more expert layer (its experts touched
        also by themselves, ``mtp_touched``)."""
        tokens = np.asarray(counters["expert_tokens"], np.int64)
        touched = np.asarray(counters["experts_touched"], np.int64)
        visits = int(np.asarray(counters["expert_visits"], np.int64).sum())
        st = self.stats
        if "moe_steps" not in st:
            st["moe_expert_tokens"] = np.zeros_like(tokens)
            st["moe_experts_touched"] = np.zeros_like(touched)
            st["moe_visits"] = 0
            st["moe_steps"] = 0
        st["moe_expert_tokens"] += tokens
        st["moe_experts_touched"] += touched
        st["moe_visits"] += visits
        st["moe_steps"] += n_steps
        routed = n_steps * queries * self.max_batch * self.cfg.experts_per_token * tokens.shape[0]
        meta = dict(
            moe_steps=n_steps, moe_layers=tokens.shape[0], moe_experts=tokens.shape[1],
            moe_touched=int(touched.sum()), moe_visits=visits, moe_routed=routed, moe_routed_here=int(tokens.sum()),
            moe_busiest=int(tokens.max(axis=-1).sum()),
        )
        if self.self_draft:
            # the last expert layer counted is the module's block
            meta["mtp_touched"] = int(touched[-1])
        return meta

    def _spec_step(self) -> bool:
        """One speculative round for every active row: k draft proposals,
        one multi-token target verify, per-row ragged acceptance (1 to
        k+1 tokens emitted per row). The round writes slots
        [seq, seq + k] in BOTH pools, so the page horizon is spec_k + 1;
        rejected slots hold garbage above each row's new frontier and are
        overwritten by the next round (slot-reuse discipline)."""
        k = self.spec_k
        self._ensure_write_pages(horizon=k + 1)
        if self._n_decode_rows() == 0:  # everyone preempted (tiny pool)
            return False
        paged.check_paged_bounds(self.tables, self.seq_lens, self.block_size)
        self._count_attention_pages(k + 1)  # the verify's k + 1 queries a row
        self._key, sub = jax.random.split(self._key)
        emit, n_emit, draft, moe = self._spec_round(
            jnp.asarray(self.tokens), jnp.asarray(self.drafts), jnp.asarray(self.seq_lens), sub
        )
        emit = np.asarray(emit)  # (B, k+1)
        n_emit = np.asarray(n_emit)  # (B,)
        draft = None if draft is None else np.asarray(draft)
        if moe is not None:
            self._count_moe(moe, 1, queries=k + 1)
        self.stats["steps"] += 1
        self.stats["spec_rounds"] = self.stats.get("spec_rounds", 0) + 1
        self.stats["spec_proposed"] = (
            self.stats.get("spec_proposed", 0) + k * self._n_decode_rows()
        )
        for row, req in enumerate(self.rows):
            if req is None or req.prefill_pos is not None:
                continue
            self.stats["spec_accepted"] = (
                self.stats.get("spec_accepted", 0) + int(n_emit[row]) - 1
            )
            if draft is not None:
                self.drafts[row] = draft[row]
            self._consume_tokens(
                req, row, emit[row, : int(n_emit[row])], advance_seq=True
            )
        return True

    def _spec_round(self, base, draft_dev, seq_dev, key):
        """ONE definition of a speculative round's device dispatch for the
        synchronous and pipelined schedulers -> device ``(emit (B, k+1),
        n_emit (B,), next drafts (B,) or None, routing counters or None)``.
        Self-drafting runs ``paged.paged_mtp_round`` over the one pool (the
        model's MTP module proposes; ``draft_dev`` is each row's pending
        draft); a separate draft model runs ``paged.paged_spec_round`` over
        both pools."""
        with _spans.span(
            "serving.spec_round", k=self.spec_k, draft="mtp" if self.self_draft else "model",
            attention=self.decode_attention,
        ):
            tables = jnp.asarray(self.tables)
            if self.self_draft:
                emit, n_emit, draft, moe, self.pools = paged.paged_mtp_round(
                    self.params, self.pools, base, draft_dev, tables, seq_dev, key,
                    cfg=self.cfg, temperature=self.temperature, mesh=self.mesh,
                )
                return emit, n_emit, draft, moe
            emit, n_emit, self.pools, self.d_pools = paged.paged_spec_round(
                self.params, self.pools, self.d_pools, self.draft_params,
                base, tables, seq_dev, key, cfg_t=self.cfg, cfg_d=self.draft_cfg,
                k=self.spec_k, temperature=self.temperature, mesh=self.mesh,
            )
            return emit, n_emit, None, None

    def run(self, *, pipeline: bool = True) -> Dict[int, List[int]]:
        """Drive the engine until every submitted request has finished.

        ``pipeline=True`` (default) runs the deep-pipelined scheduler: a
        queue of up to ``pipeline_depth`` dispatched-but-unreaped windows.
        Window k+1's inputs chain from window k's last tokens ON DEVICE,
        so the host's reap/consume/admission work for older windows and
        their readback round trips overlap the device's execution instead
        of idling it — the device only drains when a decision needs exact
        host state (preemption, page reclaim), which flushes the queue
        and replays from committed state. The price is up to
        ``pipeline_depth`` windows of lag on finish detection (a finished
        row decodes surplus windows before its slot frees; surplus tokens
        are discarded at reap). Greedy outputs are IDENTICAL to
        pipeline=False at EVERY depth — per-row greedy decoding depends
        only on the row's own history, never on scheduling; with
        temperature > 0 the sampling key stream differs (window keys
        split in dispatch order, and deeper queues dispatch more surplus
        windows).

        Speculative serving (spec_k > 0) joins the same in-flight queue:
        round k+1 chains its seed tokens AND its frontier from round k's
        device-resident result (speculative.spec_next_inputs), so the
        data-dependent acceptance no longer forces a per-round host sync;
        the page horizon is pre-ensured for the worst-case (k+1) advance
        of every queued round. Committed host ``seq_lens`` advance at
        reap by the round's actual emit count.
        """
        if not pipeline:
            while self.has_work():
                self.step()
            return self.finished
        return self._run_pipelined()

    def _run_pipelined(self) -> Dict[int, List[int]]:
        assert not self._inflight, "re-entrant run()"
        while self.has_work() or self._inflight:
            self.pipeline_tick()
        return self.finished

    def pipeline_tick(self) -> bool:
        """One turn of the deep-pipelined scheduler: admit waiting
        requests, dispatch at most one window, reap windows beyond the
        queue depth. ``run(pipeline=True)`` is exactly this in a loop;
        the online frontend (frontend/engine_loop.py) calls it directly
        so submissions, cancellations and deadline checks can land
        BETWEEN scheduler turns of a long-lived engine. Returns True
        while device work remains dispatched or runnable (False = the
        engine is fully idle)."""
        before = dict(self.stats["phase_s"])
        with self._clock.span("other", "serving.tick") as tick:
            depth = self.pipeline_depth
            self._admit(defer=True)
            # Chunked prefill rides BEFORE the decode dispatch: its writes
            # are committed prompt data (earlier in device program order than
            # this tick's window), and the token budget bounds the prefill
            # work a decode window ever waits behind — the TPOT protection.
            chunked = self._dispatch_prefill_chunks(defer=True)
            self._release_window_pages()
            decoded = False
            if self._n_decode_rows():
                if self.spec_k:
                    # Worst case every queued round and the new one
                    # advance the device frontier by k+1 past the
                    # committed seq_lens — pre-ensure the whole horizon
                    # so no flush can land between dispatch and reap.
                    k = self.spec_k
                    self._ensure_write_pages(
                        horizon=(k + 1) * (len(self._inflight) + 1)
                    )
                    if self._n_decode_rows():
                        self._dispatch_spec_round()
                        decoded = True
                else:
                    n = self._window_len()
                    # ONE window length for both the page horizon and the
                    # dispatch: ensure_write_pages may flush/preempt
                    # (which only shrinks the remaining budget), and a
                    # dispatch longer than the ensured horizon would
                    # scratch-redirect live writes — computing n once
                    # makes that impossible by construction. ``prealloc``
                    # opportunistically extends rows toward the full
                    # in-flight horizon (n * depth slots) from the free
                    # list, so later dispatches rarely need new pages at
                    # all — a page flush between an already-dispatched
                    # window and its reap becomes the exception.
                    self._ensure_write_pages(
                        horizon=n, prealloc=n * (depth - 1)
                    )
                    if self._n_decode_rows():
                        self._dispatch_window(n)
                        decoded = True
            if chunked:
                self._note_chunk_window(decoded)
            # Reap the oldest window once the queue exceeds its depth —
            # by then it has had `depth` windows of device time to finish,
            # so the readback rarely blocks — and drain outright when
            # nothing is running (end of stream, or everyone preempted).
            while (len(self._inflight) > depth
                   or (self._inflight and not self.n_active)):
                self._reap_window(self._inflight.popleft())
            busy = bool(self._inflight) or self.has_work()
            self._note_blocks_in_use()
        self._account_tick(tick.t1 - tick.t0, before)
        return busy

    def _note_blocks_in_use(self) -> None:
        """Blocks out of each pool's free list after a scheduler turn, and the most so far."""
        pools = [("kv", self.alloc)] + ([("window", self.w_alloc)] if self.two_lifetimes else [])
        for name, alloc in pools:
            used = alloc.n_blocks - 1 - alloc.available
            self.stats[name + "_blocks_in_use"] = used
            if used > self.stats[name + "_blocks_peak"]:
                self.stats[name + "_blocks_peak"] = used

    def _release_window_pages(self) -> None:
        """Two cache lifetimes: give back every window-layer page that lies
        wholly behind its row's window as of the next dispatch. ``seq_lens`` is
        the dispatched frontier, so no window still to go out reads or writes
        such a page; one already in flight runs before whatever next writes the
        page (one device, programs in order), the discipline a finished row's
        pages rely on."""
        if not self.two_lifetimes:
            return
        released = 0
        with self._clock.span("ensure_pages", "serving.release_pages") as span:
            window, bs = self.cfg.sliding_window, self.block_size
            for row, req in enumerate(self.rows):
                if req is None or not req.w_blocks:
                    continue
                first = paged.window_first_block(int(self.seq_lens[row]), window, bs)
                n = min(first - req.w_first, len(req.w_blocks))
                if n <= 0:
                    continue
                self.w_alloc.free(req.w_blocks[:n])
                del req.w_blocks[:n]
                self.w_tables[row, req.w_first : req.w_first + n] = 0
                req.w_first += n
                released += n
            span.set(released=released)
        self.stats["window_pages_released"] += released

    def _grow_window_pages(self, req: _Request, row: int, need_pages: int) -> None:
        """Two cache lifetimes: the window layers' pages up to the row's page
        ``need_pages - 1``, from their own pool, which holds every row at its
        most by construction (``window_blocks``)."""
        while req.w_first + len(req.w_blocks) < need_pages:
            got = self.w_alloc.alloc(1)
            if got is None:
                raise RuntimeError(
                    f"the window layers' pool of {self.window_blocks} blocks ran dry at row {row} "
                    f"holding {len(req.w_blocks)}: its derived size no longer covers the scheduler"
                )
            self.w_tables[row, req.w_first + len(req.w_blocks)] = got[0]
            req.w_blocks.extend(got)

    def _account_tick(self, seconds: float, before: Dict[str, float]) -> None:
        """Keep the longest tick's phase split; log a tick that stood still."""
        st = self.stats
        st["ticks"] += 1
        longest = seconds > st["longest_tick"]["seconds"]
        slow = _spans.slow_factor(seconds, self._tick_hist)
        if longest or slow:
            split = {k: v - before[k] for k, v in st["phase_s"].items()}
            if longest:
                st["longest_tick"] = {
                    "tick": st["ticks"], "seconds": seconds, "phase_s": split,
                }
            if slow:
                st["slow_ticks"] += 1
                t1 = time.monotonic()  # the tick ended a few microseconds ago, on the witness's clock
                args = (st["ticks"], seconds, len(self._tick_hist), slow, _spans.format_split(split))
                # The witness thread writes the line, a period or two from now, once it knows the cause.
                _witness.when_settled(t1 - seconds, t1, lambda cause: _log.warning(
                    "slow tick %d: %.3f s, the last %d ticks' median times %.0f: %s; %s", *args, cause,
                ))
        self._tick_hist.append(seconds)

    def _dispatch_window(self, n: int) -> None:
        """Enqueue one ``steps_per_sched``-step decode window WITHOUT
        waiting for the queued ones: input tokens come from the youngest
        in-flight window's last column (still on device) merged with
        admission first-tokens (also on device); seq_lens advance
        host-side by the window length (every active row writes exactly
        that many slots, finished-or-not — surplus is discarded at reap).
        ``n`` is the window length the caller already ensured pages
        for."""
        capacity = self.max_blocks * self.block_size
        # Clamp: a finished-but-unreaped row may have written up to its
        # full allocation; feeding seq == capacity would trip the bounds
        # guard (and the model would clamp its page index onto a live
        # block). capacity-1 keeps its garbage writes inside its OWN last
        # block until it is reaped.
        seq_dispatch = np.minimum(self.seq_lens, capacity - 1)
        # Mid-prefill rows are NOT in the window: their lockstep writes
        # are garbage landing at/above their committed frontier (the next
        # chunk overwrites them before any mask exposes them), their seq
        # must not advance, and their tokens are never consumed.
        active = [
            i for i, r in enumerate(self.rows)
            if r is not None and r.prefill_pos is None
        ]
        paged.check_paged_bounds(
            self.tables[active], seq_dispatch[active], self.block_size
        )
        self._count_attention_pages(n)
        with self._clock.span(
            "dispatch", "serving.dispatch_window",
            steps=n, window=self.stats["windows"],
        ):
            if self._inflight:
                base = self._inflight[-1].toks[:, -1]  # (B,) device, no sync
            else:
                base = jnp.asarray(self.tokens)
            base = self._merge_admitted(base)
            self._key, sub = jax.random.split(self._key)
            toks, lp, moe = self._decode_window(
                base, jnp.asarray(self._decode_tables()), jnp.asarray(seq_dispatch),
                sub, n,
            )
        self.stats["steps"] += n
        self.stats["windows"] += 1
        snapshot = [(i, self.rows[i]) for i in active]
        for i in active:
            self.seq_lens[i] = min(int(self.seq_lens[i]) + n, capacity)
        self._inflight.append(
            _Window(kind="decode", snapshot=snapshot, n=n, toks=toks,
                    lp=lp, t_dispatch=time.perf_counter(), moe=moe)
        )

    def _dispatch_spec_round(self) -> None:
        """Enqueue one speculative round against the device-resident
        frontier: seed tokens and seq_lens chain from the youngest queued
        round via spec_next_inputs (no host sync); rows admitted since
        the last dispatch are spliced in from committed host state. With
        an empty queue (start, or right after a reconciliation flush)
        both come from committed host state — the replay path."""
        k = self.spec_k
        capacity = self.max_blocks * self.block_size
        seq_committed = np.minimum(self.seq_lens, capacity - 1)
        # Same exclusion as _dispatch_window: mid-prefill rows ride no
        # spec round (their chained seq_dev is reset to the committed
        # frontier by the chunk dispatch's merge entry, so their garbage
        # writes stay at/above it).
        active = [
            i for i, r in enumerate(self.rows)
            if r is not None and r.prefill_pos is None
        ]
        # The bounds invariant is checked on COMMITTED state (a lower
        # bound on the device frontier); in-flight advances stay inside
        # the pre-ensured horizon by construction.
        paged.check_paged_bounds(
            self.tables[active], seq_committed[active], self.block_size
        )
        self._count_attention_pages(k + 1)  # from the committed lengths
        with self._clock.span(
            "dispatch", "serving.dispatch_window",
            steps=k + 1, window=self.stats["windows"], kind="spec",
        ):
            if self._inflight:
                prev = self._inflight[-1]
                base, seq_dev = speculative.spec_next_inputs(
                    prev.emit, prev.n_emit, prev.seq_dev
                )
                draft_dev = prev.draft  # the module's proposal behind ``base``
            else:
                base = jnp.asarray(self.tokens)
                seq_dev = jnp.asarray(self.seq_lens)
                draft_dev = jnp.asarray(self.drafts) if self.self_draft else None
            base, seq_dev, draft_dev = self._merge_admitted(base, seq_dev, draft_dev)
            self._key, sub = jax.random.split(self._key)
            emit, n_emit, draft, moe = self._spec_round(base, draft_dev, seq_dev, sub)
        self.stats["steps"] += 1
        self.stats["windows"] += 1
        self.stats["spec_rounds"] = self.stats.get("spec_rounds", 0) + 1
        snapshot = [(i, self.rows[i]) for i in active]
        self._inflight.append(
            _Window(kind="spec", snapshot=snapshot, n=k + 1,
                    emit=emit, n_emit=n_emit, seq_dev=seq_dev, draft=draft,
                    moe=moe, t_dispatch=time.perf_counter())
        )

    def _merge_admitted(self, base, seq_dev=None, draft_dev=None):
        """Splice rows admitted since the last dispatch into the chained
        device inputs: their prefill-sampled first token, (spec mode)
        their committed frontier — a released row's stale chain values
        are otherwise garbage by design (zero tables scratch its writes),
        but a RE-ADMITTED row must restart from committed host state — and
        (self-drafting) the first draft their prefill proposed. The decode
        window takes ``base`` alone; a spec round all three (``draft_dev``
        None without self-drafting)."""
        for toks_dev, idxs, rows, drafts_dev in self._pending_admit_merges:
            r, i = jnp.asarray(rows, jnp.int32), jnp.asarray(idxs, jnp.int32)
            base = base.at[r].set(toks_dev[i])
            if seq_dev is not None:
                seq_dev = seq_dev.at[r].set(
                    jnp.asarray(self.seq_lens[np.asarray(rows)], jnp.int32)
                )
            if draft_dev is not None:
                draft_dev = draft_dev.at[r].set(drafts_dev[i])
        self._pending_admit_merges = []
        return base if seq_dev is None else (base, seq_dev, draft_dev)

    def _reap_window(self, w: _Window) -> None:
        """Materialize a window's tokens and do the lagged bookkeeping:
        resolve deferred first tokens, extend outputs, finish rows that
        hit stop/max_new (their surplus in-window tokens are discarded,
        exactly as in the synchronous path). The readback wait is the
        host-blocked time deep pipelining exists to hide — measured per
        window into stats and the span's trace args."""
        widx = self.stats["windows_reaped"]
        moe_meta: Dict[str, int] = {}
        with _spans.span("serving.reap_window", window=widx) as reap:
            with self._clock.span("host_blocked", "serving.host_blocked") as wait:
                if w.kind == "spec":
                    emit = np.asarray(w.emit)      # (B, k+1) — THE sync point
                    n_emit = np.asarray(w.n_emit)  # (B,)
                    draft = None if w.draft is None else np.asarray(w.draft)
                    if w.moe is not None:
                        moe_meta = self._count_moe(w.moe, 1, queries=w.n)
                else:
                    window = np.asarray(w.toks)    # (B, n) — THE sync point
                    lp_host = None
                    if w.lp is not None:
                        lp_host = (np.asarray(w.lp[0]), np.asarray(w.lp[1]))
                    if w.moe is not None:
                        moe_meta = self._count_moe(w.moe, w.n)
            t0, t_reaped = wait.t0, wait.t1
            blocked = t_reaped - t0
            reap.set(host_blocked_s=round(blocked, 6))
            self.stats["host_blocked_s"] += blocked
            self.stats["windows_reaped"] += 1
            if self.window_hist is not None and w.t_dispatch:
                self.window_hist.observe(t_reaped - w.t_dispatch)
            if self.host_blocked_hist is not None:
                self.host_blocked_hist.observe(blocked)
            capacity = self.max_blocks * self.block_size
            toks_before = self.stats["tokens"]
            if self.state_slots:
                # slots owned as the window commits: a row's slot is its row
                moe_meta["state_slots"] = self.n_active
            spec0 = (self.stats.get("spec_proposed", 0), self.stats.get("spec_accepted", 0))
            with self._clock.span(
                "commit", "serving.commit", rows=len(w.snapshot), **moe_meta
            ) as commit:
                for row, req in w.snapshot:
                    if req.row != row or self.rows[row] is not req:
                        # The row finished in an earlier reap and may have
                        # been re-admitted since; this window's tokens for it
                        # are surplus garbage by the lag contract. (Preemption
                        # can't land here: it flushes the queue first.)
                        continue
                    if self.traces:
                        tr = self.traces.get(req.rid)
                        if tr is not None and not tr.finished:
                            # One span per (request, window) it rode: dispatch
                            # -> reap. Under deep pipelining these intervals
                            # OVERLAP across windows; the SLO decomposition
                            # unions them into decode time. host_blocked_s is
                            # the whole window's readback wait — per request
                            # it reads as "this much of my window was the
                            # host, not the device".
                            tr.span(
                                "req.window",
                                w.t_dispatch or t0, t_reaped,
                                kind=w.kind, steps=w.n, window=widx,
                                host_blocked_s=round(blocked, 6),
                            )
                    self._resolve_first(req)
                    if req.row is None:  # first token alone finished it
                        continue
                    if w.kind == "spec":
                        # Commit the round's data-dependent advance. Proposal/
                        # acceptance telemetry counts here (not at dispatch)
                        # so surplus rounds for finished rows skew neither
                        # side of the hit rate.
                        ne = int(n_emit[row])
                        self.seq_lens[row] = min(
                            int(self.seq_lens[row]) + ne, capacity
                        )
                        self.stats["spec_proposed"] = (
                            self.stats.get("spec_proposed", 0) + self.spec_k
                        )
                        self.stats["spec_accepted"] = (
                            self.stats.get("spec_accepted", 0) + ne - 1
                        )
                        if draft is not None:
                            self.drafts[row] = draft[row]
                        self._consume_tokens(
                            req, row, emit[row, :ne], advance_seq=False
                        )
                    else:
                        self._consume_tokens(
                            req, row, window[row], advance_seq=False,
                            lp=None if lp_host is None
                            else (lp_host[0][row], lp_host[1][row]),
                        )
                if w.kind == "spec":
                    # the round's own counts, for the rows that were still live:
                    # drafts verified, drafts accepted, tokens committed
                    commit.set(
                        spec_proposed=self.stats.get("spec_proposed", 0) - spec0[0],
                        spec_accepted=self.stats.get("spec_accepted", 0) - spec0[1],
                        spec_emitted=self.stats["tokens"] - toks_before,
                    )
            if self.capacity is not None:
                # Occupancy sample AT the reap sync point: every value is
                # host state this method already touched (row snapshot,
                # committed-token delta, allocator free count, queue
                # depth) — no device access, so the asarray-spy contract
                # holds with sampling enabled.
                self.capacity.observe_window(
                    window=widx,
                    kind=w.kind,
                    t_dispatch_s=w.t_dispatch or t0,
                    t_reap_s=t_reaped,
                    steps=w.n,
                    rows=len(w.snapshot),
                    tokens_committed=self.stats["tokens"] - toks_before,
                    waiting=len(self.waiting),
                    pool_free=self.alloc.available,
                    pool_cold=(
                        self.prefix_cache.evictable
                        if self.prefix_cache is not None else 0
                    ),
                    host_blocked_s=blocked,
                    cum_tokens=self.stats["tokens"],
                    cum_prefill_tokens=self.stats["prefill_tokens"],
                    cum_rework_prefill_tokens=self.stats.get(
                        "preempted_tokens_recomputed", 0
                    ),
                    cum_preemptions=self.stats["preemptions"],
                )

    def _consume_tokens(self, req: _Request, row: int, toks,
                        advance_seq: bool, lp=None) -> None:
        """ONE definition of per-token reaping for all three schedulers
        (synchronous window, speculative round, pipelined reap): append
        to the output, update the row's pending token, finish on
        stop/max_new and DISCARD the surplus. ``advance_seq``: the
        synchronous and speculative paths advance the frontier here (the
        step that produced the token wrote its slot); the pipelined path
        already advanced it at dispatch. ``lp``: this row's
        ``((n, k) values, (n, k) ids)`` logprob slice — consumed in
        lockstep with the tokens, so surplus logprobs are discarded with
        their surplus tokens."""
        for i, tok in enumerate(int(t) for t in toks):
            if advance_seq:
                self.seq_lens[row] += 1
            self._check_token(req, tok)
            req.generated.append(tok)
            self._lp_append(
                req,
                None if lp is None
                else (lp[0][i].tolist(), lp[1][i].tolist()),
            )
            self._emit_token(req, tok)
            self.tokens[row] = tok
            self.stats["tokens"] += 1
            if tok == self.stop_token or len(req.generated) >= req.max_new:
                self._finish(req)
                break  # surplus tokens for this row are discarded

    def _lp_append(self, req: _Request, entry) -> None:
        """Record one output token's logprob entry (or its absence) —
        kept in lockstep with every ``generated.append`` so the per-rid
        list aligns with the final output across preemptions (prefix
        tokens keep the entries from their first incarnation)."""
        if not self.logprobs_k:
            return
        self.logprobs.setdefault(req.rid, []).append(entry)

    def _emit_token(self, req: _Request, tok: int) -> None:
        """Post-append commit hook: first-token timestamp + the streaming
        callback. The stop token is bookkeeping, not output (``_finish``
        strips it), so it is never streamed; across preemptions the
        concatenated stream equals the final ``prefix + generated``
        output exactly (preempted tokens streamed in their first
        incarnation, re-decoded ones arrive as prompt, not output)."""
        t = self.req_timing.get(req.rid)
        if t is not None and tok != self.stop_token:
            if "first_token_s" not in t:
                t["first_token_s"] = self._now()
                if self.traces:
                    tr = self.traces.get(req.rid)
                    if tr is not None:
                        # Zero-duration point on the waterfall; the TTFT
                        # histogram is observed at terminal time from
                        # req_timing, never here (per-token hot path).
                        tr.event("req.first_token")
        if self.on_token is not None and tok != self.stop_token:
            self.on_token(req.rid, tok)

    def _flush_inflight(self) -> None:
        """Reconciliation: synchronously drain EVERY in-flight window,
        oldest first, so host state is exact/committed — required before
        preemption decisions and speculative-page reclaim. The caller
        then replays from committed state (the next dispatch finds an
        empty queue and restarts the device chain from host tokens/
        seq_lens)."""
        if self._inflight:
            self.stats["flushes"] += 1
        while self._inflight:
            self._reap_window(self._inflight.popleft())

    def _check_token(self, req: _Request, tok: int) -> None:
        """In-band output sanity guard, applied to every token id at the
        moment it would COMMIT (the values are host ints the reap already
        materialized — no new device pulls). An out-of-vocab id can only
        come from corrupted state (weights, KV pages, a bad kernel —
        ``sample_logits`` maps non-finite sampling-path logits to -1 for
        exactly this reason), so the right move is to fail the engine
        loudly: the loop's failure path turns that into redrivable
        ``engine failure`` terminals instead of streaming garbage."""
        if 0 <= tok < self.cfg.vocab_size:
            return
        self.stats["invalid_tokens"] = self.stats.get("invalid_tokens", 0) + 1
        if self.invalid_token_counter is not None:
            self.invalid_token_counter.inc()
        from pretraining_llm_tpu.resilience.integrity import IntegrityError

        err = IntegrityError(
            f"invalid token id {tok} for rid {req.rid} (vocab size "
            f"{self.cfg.vocab_size}): refusing to stream corrupted output"
        )
        # Structured fields for the loop's integrity_invalid_token event.
        err.rid = req.rid
        err.token = int(tok)
        raise err

    def _verify_shared(
        self, req: _Request, cached_len: int, shared: List[int]
    ) -> Tuple[int, List[int]]:
        """Verify-on-acquire (``kv_checksum``): re-digest every shared
        block against the checksum recorded when it was published. On the
        first mismatch, keep only the verified prefix of the hit, release
        the rest, and DROP the corrupt block from the cache — this
        admission (and every future one) re-prefills those tokens
        privately, so one flipped page costs exactly one hit's worth of
        prefill instead of poisoning every request that shares it."""
        from pretraining_llm_tpu.resilience import integrity

        for j, b in enumerate(shared):
            expected = self.prefix_cache.checksum_of(b)
            if expected is None or (
                integrity.kv_block_digest(self.pools, b) == expected
            ):
                continue
            self.prefix_cache.release_shared(shared[j:])
            self.prefix_cache.drop_block(b)
            self.stats["kv_mismatches"] = (
                self.stats.get("kv_mismatches", 0) + 1
            )
            if self.kv_mismatch_counter is not None:
                self.kv_mismatch_counter.inc()
            if self.decisions is not None:
                tr = self.traces.get(req.rid)
                self.decisions.record(
                    "drop_corrupt_block",
                    rid=req.rid,
                    trace_id=getattr(tr, "trace_id", None),
                    block=b,
                    verified_blocks=j,
                )
                # The engine has no bus of its own; the loop's decision log
                # carries the (replica-labelled) one.
                if self.decisions.bus is not None:
                    self.decisions.bus.emit(
                        "integrity_kv_mismatch", rid=req.rid, block=b,
                        verified_blocks=j,
                    )
            keep = shared[:j]
            if len(keep) < self.prefix_cache.min_blocks:
                if keep:
                    self.prefix_cache.release_shared(keep)
                return 0, []
            return min(cached_len, len(keep) * self.block_size), keep
        return cached_len, shared

    def _resolve_first(self, req: _Request) -> None:
        """Materialize a deferred admission token (device is done with it
        by the time any caller needs the value)."""
        if req.pending_first is None:
            return
        arr, i = req.pending_first
        req.pending_first = None
        tok = int(np.asarray(arr)[i])
        self._check_token(req, tok)
        req.generated.append(tok)
        # Prefill programs sample but never compute the logprob sliver:
        # the first token's entry is an explicit None placeholder.
        self._lp_append(req, None)
        self._emit_token(req, tok)
        if req.row is not None:
            self.tokens[req.row] = tok
            if tok == self.stop_token or len(req.generated) >= req.max_new:
                self._finish(req)

    # -- scheduling internals ---------------------------------------------

    def _cache_available(self) -> int:
        """Blocks admission may count on: the free list plus cold cached
        blocks the LRU would hand back on demand."""
        avail = self.alloc.available
        if self.prefix_cache is not None:
            avail += self.prefix_cache.evictable
        return avail

    def _cache_alloc(self, n: int) -> Optional[List[int]]:
        """``alloc.alloc(n)``, evicting cold cached blocks first when the
        free list alone cannot cover the request."""
        if self.prefix_cache is not None and n > self.alloc.available:
            evicted = self.prefix_cache.evict(n - self.alloc.available)
            if evicted and self.decisions is not None:
                self.decisions.record(
                    "evict_cold", blocks=evicted, reason="admission",
                )
        return self.alloc.alloc(n)

    def reserve_migration_blocks(self, n: int) -> Optional[List[int]]:
        """Claim ``n`` pool blocks for adopted (migrated-in) KV pages, or
        None when serving pressure says no. Same watermark as admission:
        never take the pool below one spare block per active request —
        a migration is an optimization and must lose to live decode.
        Loop-thread only (callers come through EngineLoop.run_on_loop);
        the blocks are expected to be published into the prefix cache
        (where they become cold, i.e. reclaimable) or freed by the
        caller — they must not leak as unowned live blocks."""
        if n < 1:
            return None
        if self._cache_available() - n < self.n_active:
            return None
        return self._cache_alloc(n)

    def _prefill_parts(self, reqs: List[_Request]) -> List[List[_Request]]:
        """One boundary's admissions as the batched prefill programs they run
        in: in order, as many a program as keep its padded size (rows bucketed
        to a power of two x the longest prompt's page bucket) within
        ``self.prefill_program_tokens`` (``PREFILL_PROGRAM_TOKENS`` at first). A
        prefill's activations grow with that product (an expert layer sorts
        ``experts_per_token`` copies of every token), and eight 5,184-token
        rows of a 128-row engine do not fit a v5e beside 10.7 GB of weights and
        caches where two programs of four do."""
        parts: List[List[_Request]] = []
        for req in reqs:
            trial = (parts[-1] if parts else []) + [req]
            rows, pages = paged.prefill_bucket(
                self.cfg, len(trial),
                max(paged.required_blocks(len(r.prompt), self.block_size) for r in trial),
                self.block_size,
            )
            if parts and rows * pages * self.block_size <= self.prefill_program_tokens:
                parts[-1].append(req)
            else:
                parts.append([req])
        return parts

    def _admission_capacity(self) -> int:
        """How many queue heads could be admitted RIGHT NOW under the
        free-row + watermark rules, without committing anything — the
        ``admit_batch`` gate's lookahead. (With the prefix cache on this
        is conservative: cold blocks count as available, but each head is
        charged its FULL block need, ignoring possible hits.)"""
        free_rows = sum(r is None for r in self.rows)
        avail = self._cache_available()
        active = self.n_active
        count = 0
        for req in self.waiting:
            if count >= free_rows:
                break
            need = paged.required_blocks(len(req.prompt) + 1, self.block_size)
            if avail - need < active:
                break
            avail -= need
            active += 1
            count += 1
        return count

    def _admit(self, defer: bool = False) -> None:
        """FCFS admission: every queue head that fits claims a free row,
        then ALL claimed prompts prefill in ONE device program (batched
        admission — N arrivals used to pay N serialized prefill programs
        + N host-synced first-token samples, the dominant term of the
        measured 8x serving/decode gap at the window boundary).

        ``defer=True`` (pipelined run loop) keeps the sampled first
        tokens on device: bookkeeping that needs their VALUES (stop
        tokens, output lists) lags until the window they join is reaped,
        while scheduling math uses ``n_generated`` which already counts
        them.

        Cross-window admission batching (``admit_batch`` > 1, pipelined
        only): while the device has work, waiting prefills accumulate
        until one batched admission can take ``admit_batch`` of them —
        turning per-boundary dribble admissions (one prefill program
        each) into one larger prefill at the boundary where rows/pages
        free up. Greedy outputs are unaffected: a request's tokens depend
        only on its own prompt, never on when it was admitted."""
        if not self.waiting:
            return
        # The span covers every turn in which somebody waits: one that admits,
        # one that stalls at the watermark, one that defers.
        with self._clock.span("admit", "serving.admit") as span:
            if defer and self.admit_batch > 1 and self.waiting and self.n_active:
                goal = min(self.admit_batch, len(self.waiting), self.max_batch)
                if self._admission_capacity() < goal:
                    self.stats["admit_deferrals"] = (
                        self.stats.get("admit_deferrals", 0) + 1
                    )
                    return
                self.stats["admit_batches"] = (
                    self.stats.get("admit_batches", 0) + 1
                )
            admits: List[_Request] = []
            while self.waiting:
                free_rows = [i for i, r in enumerate(self.rows) if r is None]
                if not free_rows:
                    break
                req: _Request = self.waiting[0]
                p = len(req.prompt)
                # +1: the first decode step writes slot p — its page must exist.
                need = paged.required_blocks(p + 1, self.block_size)
                # Prefix-cache lookup: retain the longest cached block-aligned
                # prefix and charge admission only for the uncached remainder.
                cached_len = 0
                shared: List[int] = []
                t_lookup = t_hit = 0.0
                if self.prefix_cache is not None:
                    t_lookup = time.perf_counter()
                    cached_len, shared = self.prefix_cache.acquire(req.prompt)
                    if self.kv_checksum and shared:
                        cached_len, shared = self._verify_shared(
                            req, cached_len, shared
                        )
                    t_hit = time.perf_counter()
                need_new = need - len(shared)
                # Admission watermark — where head-of-line admission stalls:
                # keep one growth block of headroom per already-running row,
                # else a nearly-dry pool admits + pays a full prefill only for
                # the newcomer to be preempted at the next older-row block
                # boundary (prefill thrash). The stalled head waits for active
                # rows to finish and free blocks; preemption happens on growth.
                # Cold cached blocks count as available — the LRU hands them
                # back before any live request is preempted.
                if self._cache_available() - need_new < self.n_active:
                    if shared:
                        self.prefix_cache.release_shared(shared)
                    break
                blocks = self._cache_alloc(need_new)
                assert blocks is not None, "watermark guarantees coverage"
                self.waiting.popleft()
                row = free_rows[0]
                req.blocks = shared + blocks
                req.n_shared = len(shared)
                req.row = row
                if self.prefix_cache is not None:
                    # Counted only for COMMITTED admissions, so a stalled head
                    # retried at every boundary cannot inflate the hit rate.
                    if cached_len:
                        self.prefix_cache.note_hit(cached_len)
                    else:
                        self.prefix_cache.note_miss()
                req.admit_order = self._admit_counter
                self._admit_counter += 1
                self.stats["admissions"] += 1
                if not self.prefill_chunk_tokens:
                    # Chunked mode counts prefill (and recompute rework) at
                    # chunk DISPATCH — where the tokens are actually paid —
                    # so a mid-prefill cancellation never inflates either.
                    self.stats["prefill_tokens"] += p - cached_len
                    if req.preemptions > 0:
                        # Recompute-on-resume rework, counted where it is
                        # actually PAID: the re-admission's prefill (a cache
                        # hit on the victim's own published pages shrinks it).
                        self.stats["preempted_tokens_recomputed"] = (
                            self.stats.get("preempted_tokens_recomputed", 0)
                            + p - cached_len
                        )
                        if self.preempt_tokens_counter is not None:
                            self.preempt_tokens_counter.inc(p - cached_len)
                t = self.req_timing.get(req.rid)
                if t is not None:
                    # setdefault: a preempted request's re-admission must not
                    # move its queue-wait mark.
                    t.setdefault("admit_s", self._now())
                    if self.prefix_cache is not None:
                        # Accumulates: a preemption-resume hit on just-published
                        # pages adds its savings on top of the first admission's.
                        # Cache off -> key absent, so timing summaries (and the
                        # JSONL/body schemas built from them) are unchanged.
                        t["cached_tokens"] = t.get("cached_tokens", 0) + cached_len
                if self.traces:
                    tr = self.traces.get(req.rid)
                    if tr is not None:
                        if self.prefix_cache is not None:
                            # Recorded only for COMMITTED admissions (stalled
                            # heads would otherwise stack duplicate spans).
                            tr.span(
                                "prefix_cache.lookup", t_lookup, t_hit,
                                cached_tokens=cached_len, blocks=len(shared),
                            )
                        if "admit" not in tr.marks:
                            # Same setdefault rule: the queue span is submit ->
                            # FIRST row claim; preemption re-admissions keep it.
                            now_p = time.perf_counter()
                            tr.span(
                                "req.queue", tr.marks.get("submit", tr.t0), now_p,
                                n_prompt=p,
                            )
                            tr.marks["admit"] = now_p
                self.rows[row] = req  # claim now: n_active sees earlier admits
                if self.state_slots:
                    self.stats["state_slots_peak"] = max(
                        self.stats.get("state_slots_peak", 0), self.n_active
                    )
                self.tables[row, :] = 0
                self.tables[row, : len(req.blocks)] = req.blocks
                if self.two_lifetimes:
                    # the window layers keep the pages the first decode step can
                    # see; a longer prompt's earlier pages are never written there
                    req.w_first = paged.window_first_block(p, self.cfg.sliding_window, self.block_size)
                    self.w_tables[row, :] = 0
                    self._grow_window_pages(req, row, need)
                if self.prefill_chunk_tokens:
                    # Chunked admission: claim the row and ALL its blocks
                    # (same watermark math — the allocation is identical),
                    # but run NO prefill here. The committed frontier starts
                    # at the cached prefix; _dispatch_prefill_chunks streams
                    # the rest in budgeted chunks, cache hits riding the
                    # same lane with a head start.
                    req.prefill_pos = cached_len
                    self.seq_lens[row] = cached_len
                else:
                    self.seq_lens[row] = p
                admits.append(req)
            span.set(rows=len(admits), prompt_tokens=sum(len(r.prompt) for r in admits))
            if not admits:
                return
            if self.prefill_chunk_tokens:
                return  # prompts stream in via _dispatch_prefill_chunks
            # Cache hits prefill ONLY their uncached suffix (shared pages are
            # already in the table; PagedInfo seq = cached length), misses run
            # the full prefill — one batched program per non-empty group.
            miss = [r for r in admits if r.n_shared == 0]
            hits = [r for r in admits if r.n_shared > 0]
            if miss and self.quantize == "int8-kv":
                # Quantized-pool bit-identity: the monolithic lane's dense
                # flash-prefill shortcut attends the UNQUANTIZED local k/v,
                # while the suffix lane attends dequantized pool pages — the
                # two would commit DIFFERENT quantized bytes for the same
                # prompt, breaking identity across prefix-cache/chunked
                # configurations. Route every admission through the suffix
                # lane (cached_len 0 = full prompt) so page bytes are always
                # the same pure function of the token's prompt prefix.
                hits = miss + hits
                miss = []
            t_prefill = time.perf_counter()
            groups: List[Tuple[List[_Request], jax.Array]] = []
            with self._clock.span(
                "prefill_dispatch", "serving.prefill_dispatch",
                miss=len(miss), hits=len(hits),
            ):
                parts = self._prefill_parts(miss)
                while parts:
                    part = parts.pop(0)
                    self._key, sub = jax.random.split(self._key)
                    prompts = [r.prompt for r in part]
                    prefill_ids = [
                        r.blocks[: paged.required_blocks(len(r.prompt), self.block_size)]
                        for r in part
                    ]
                    try:
                        toks_dev, self.pools = paged.prefill_into_pool_batched(
                            self.params, self.cfg, self.pools, prompts, prefill_ids,
                            sub, temperature=self.temperature, top_k=self.top_k,
                            top_p=self.top_p, min_p=self.min_p, mesh=self.mesh,
                            # the state is written with the pages, into the row's own slot
                            slots=[r.row for r in part] if self.state_slots else None,
                            # self-drafting: the module's pages and each row's first draft too
                            with_draft=self.self_draft,
                            # two cache lifetimes: the window layers' live pages, 0 behind the window
                            rows_window_ids=[
                                self.w_tables[r.row, : len(ids)].tolist() for r, ids in zip(part, prefill_ids)
                            ] if self.two_lifetimes else None,
                        )
                    except jax.errors.JaxRuntimeError as err:
                        # The compiler knows what a program needs beside the weights and
                        # pools that are resident, and refuses one that does not fit before
                        # it runs: the pools it would have been given are untouched then,
                        # and only then is there anything to run again. Several rows: halve
                        # the split's figure and run what is left in smaller programs.
                        given_away = any(leaf.is_deleted() for leaf in jax.tree.leaves(self.pools))
                        if "RESOURCE_EXHAUSTED" not in str(err) or len(part) == 1 or given_away:
                            raise
                        rows, pages = paged.prefill_bucket(self.cfg, len(part), max(map(len, prefill_ids)), self.block_size)
                        self.prefill_program_tokens = rows * pages * self.block_size // 2
                        self.stats["prefill_program_tokens"] = self.prefill_program_tokens
                        _log.warning(
                            "no room for a prefill program of %d x %d tokens beside the weights and pools: "
                            "admission prefills now hold at most %d padded tokens a program",
                            rows, pages * self.block_size, self.prefill_program_tokens)
                        parts = self._prefill_parts(part + [r for rest in parts for r in rest])
                        continue
                    drafts_dev = None
                    if self.self_draft:
                        toks_dev, drafts_dev = toks_dev[:, 0], toks_dev[:, 1]
                    if self.d_pools is not None:
                        # The draft cache must cover the same pages (its sampled
                        # tokens are discarded — the target's first token above is
                        # the round seed either way).
                        _, self.d_pools = paged.prefill_into_pool_batched(
                            self.draft_params, self.draft_cfg, self.d_pools, prompts,
                            prefill_ids, sub, temperature=self.temperature,
                            mesh=self.mesh,
                        )
                    groups.append((part, toks_dev, drafts_dev))
                if hits:
                    self._key, sub = jax.random.split(self._key)
                    bs = self.block_size
                    suffixes = [r.prompt[r.n_shared * bs:] for r in hits]
                    tables_rows = self.tables[np.asarray([r.row for r in hits])]
                    cached_lens = [r.n_shared * bs for r in hits]
                    toks_dev, self.pools = paged.prefill_suffix_into_pool_batched(
                        self.params, self.cfg, self.pools, suffixes, tables_rows,
                        cached_lens, sub, temperature=self.temperature,
                        top_k=self.top_k, top_p=self.top_p, min_p=self.min_p,
                        mesh=self.mesh,
                    )
                    if self.d_pools is not None:
                        # Shared block ids index BOTH pools, so the draft's prefix
                        # KV is already resident too — suffix-only there as well.
                        _, self.d_pools = paged.prefill_suffix_into_pool_batched(
                            self.draft_params, self.draft_cfg, self.d_pools,
                            suffixes, tables_rows, cached_lens, sub,
                            temperature=self.temperature, mesh=self.mesh,
                        )
                    groups.append((hits, toks_dev, None))
            if self.traces:
                # Host-side prefill span (dispatch + any compile; the async
                # device compute itself overlaps the next windows). Batched
                # admissions share one interval — the per-request cost of a
                # shared program IS the shared wall time.
                t_prefill_end = time.perf_counter()
                for req in admits:
                    tr = self.traces.get(req.rid)
                    if tr is not None:
                        tr.span(
                            "req.prefill", t_prefill, t_prefill_end,
                            n_prompt=len(req.prompt), batch=len(admits),
                        )
            self.stats["tokens"] += len(admits)  # the prefill-sampled firsts
            if defer:
                for group, toks_dev, drafts_dev in groups:
                    for i, req in enumerate(group):
                        req.pending_first = (toks_dev, i)
                    # Next dispatch merges these device scalars into its input
                    # tokens (and drafts) without a host round trip.
                    self._pending_admit_merges.append(
                        (toks_dev, list(range(len(group))), [r.row for r in group], drafts_dev)
                    )
                return
            for group, toks_dev, drafts_dev in groups:
                toks = np.asarray(toks_dev)
                if drafts_dev is not None:
                    self.drafts[[r.row for r in group]] = np.asarray(drafts_dev)
                for i, req in enumerate(group):
                    tok = int(toks[i])
                    req.generated.append(tok)
                    self._lp_append(req, None)  # prefill-sampled: no sliver
                    self._emit_token(req, tok)
                    self.tokens[req.row] = tok
                    if tok == self.stop_token or len(req.generated) >= req.max_new:
                        self._finish(req)

    def _dispatch_prefill_chunks(self, defer: bool) -> bool:
        """Stream mid-prefill rows' next prompt chunks in ONE multi-token
        paged forward (the prefix-cache suffix lane with a PINNED token
        bucket), token-budgeted to ``prefill_chunk_tokens`` per tick so
        the decode window dispatched right after never waits behind more
        than one budget of prefill compute. FCFS by admission order;
        rows past the budget wait (a ``defer_prefill_chunk`` decision).
        A row's FINAL chunk samples its first output token from the last
        prompt position — exactly the monolithic prefill's sample — and
        the row joins the very next decode window. Returns True when a
        chunk program was dispatched (the interleave accounting hook).

        Commit discipline: a chunk is committed AT DISPATCH — its
        content is deterministic prompt data, not speculation — so
        ``seq_lens``/``prefill_pos`` advance immediately and a
        reconciliation flush never needs to rewind chunk state. In spec
        mode every chunked row also queues a merge entry: the next
        round's chained ``seq_dev`` must be reset to the committed
        frontier so the excluded row's lockstep garbage lands at/above
        it, never below."""
        if not self.prefill_chunk_tokens:
            return False
        pending = sorted(
            (r for r in self.rows
             if r is not None and r.prefill_pos is not None),
            key=lambda r: r.admit_order,
        )
        if not pending:
            return False
        budget = self.prefill_chunk_tokens
        group: List[_Request] = []
        chunks: List[List[int]] = []
        offsets: List[int] = []
        finals: List[bool] = []
        for req in pending:
            if budget <= 0:
                self.stats["chunk_deferrals"] = (
                    self.stats.get("chunk_deferrals", 0) + 1
                )
                if self.decisions is not None:
                    self.decisions.record(
                        "defer_prefill_chunk",
                        rid=req.rid,
                        trace_id=getattr(
                            self.traces.get(req.rid), "trace_id", None
                        ),
                        budget=self.prefill_chunk_tokens,
                        tokens_left=len(req.prompt) - req.prefill_pos,
                    )
                continue
            start = req.prefill_pos
            take = min(budget, len(req.prompt) - start)
            group.append(req)
            chunks.append(req.prompt[start:start + take])
            offsets.append(start)
            finals.append(start + take == len(req.prompt))
            budget -= take
        t_chunk = time.perf_counter()
        with self._clock.span(
            "prefill_dispatch", "serving.dispatch_chunks",
            rows=len(group), tokens=sum(len(c) for c in chunks),
        ):
            self._key, sub = jax.random.split(self._key)
            tables_rows = self.tables[np.asarray([r.row for r in group])]
            toks_dev, self.pools = paged.prefill_suffix_into_pool_batched(
                self.params, self.cfg, self.pools, chunks, tables_rows,
                offsets, sub, temperature=self.temperature,
                top_k=self.top_k, top_p=self.top_p, min_p=self.min_p,
                mesh=self.mesh, t_bucket=self.prefill_chunk_tokens,
                # each chunk starts from the state its row's slot holds
                slots=[r.row for r in group] if self.state_slots else None,
            )
            if self.d_pools is not None:
                # The draft pool must hold the same chunk K/V (shared
                # block ids index both pools); its sampled tokens are
                # discarded — the target's final-chunk token seeds the
                # round either way.
                _, self.d_pools = paged.prefill_suffix_into_pool_batched(
                    self.draft_params, self.draft_cfg, self.d_pools,
                    chunks, tables_rows, offsets, sub,
                    temperature=self.temperature, mesh=self.mesh,
                    t_bucket=self.prefill_chunk_tokens,
                )
        t_chunk_end = time.perf_counter()
        final_idxs: List[int] = []
        for i, req in enumerate(group):
            take = len(chunks[i])
            req.prefill_pos = None if finals[i] else offsets[i] + take
            self.seq_lens[req.row] = offsets[i] + take
            self.stats["prefill_chunks"] += 1
            self.stats["prefill_chunk_tokens"] += take
            self.stats["prefill_tokens"] += take
            if req.preemptions > 0:
                # Every chunk of a preemption resume is recompute rework
                # (its prompt IS the prior incarnation's prompt+output).
                self.stats["preempted_tokens_recomputed"] = (
                    self.stats.get("preempted_tokens_recomputed", 0) + take
                )
                if self.preempt_tokens_counter is not None:
                    self.preempt_tokens_counter.inc(take)
            if self.chunk_counter is not None:
                self.chunk_counter.inc()
            if self.chunk_tokens_counter is not None:
                self.chunk_tokens_counter.inc(take)
            if self.traces:
                tr = self.traces.get(req.rid)
                if tr is not None:
                    # One span per (request, chunk); batched groups share
                    # the host interval, like req.prefill. The request's
                    # decode windows all start after its final chunk, so
                    # these never overlap its req.window spans — the
                    # waterfall's sum-to-e2e invariant survives.
                    tr.span(
                        "req.prefill_chunk", t_chunk, t_chunk_end,
                        offset=offsets[i], chunk_tokens=take,
                        final=finals[i], batch=len(group),
                    )
            if finals[i]:
                final_idxs.append(i)
        if final_idxs:
            self.stats["tokens"] += len(final_idxs)  # prefill-sampled firsts
        if defer:
            for i in final_idxs:
                group[i].pending_first = (toks_dev, i)
            if self.spec_k:
                # ALL chunked rows merge: finals contribute their real
                # first token; non-finals just pin seq_dev back to the
                # committed frontier (their base token is garbage and
                # never consumed — the row is outside every snapshot).
                self._pending_admit_merges.append(
                    (toks_dev, list(range(len(group))),
                     [r.row for r in group], None)
                )
            elif final_idxs:
                self._pending_admit_merges.append(
                    (toks_dev, final_idxs,
                     [group[i].row for i in final_idxs], None)
                )
        else:
            toks = np.asarray(toks_dev)
            for i in final_idxs:
                req = group[i]
                tok = int(toks[i])
                req.generated.append(tok)
                self._lp_append(req, None)  # prefill-sampled: no sliver
                self._emit_token(req, tok)
                self.tokens[req.row] = tok
                if tok == self.stop_token or len(req.generated) >= req.max_new:
                    self._finish(req)
        return True

    def _ensure_write_pages(self, horizon: int = 1, prealloc: int = 0) -> None:
        """Every active row's next ``horizon`` write slots must have
        allocated pages (writes landing in a surviving row's unallocated
        page would silently fall through to the scratch block and LOSE
        that token's K/V); when the pool is dry, drain the in-flight
        queue, then roll back other rows' speculative page grants, and
        only then preempt youngest-first (recompute-on-resume) so the
        oldest admitted requests always make progress. Slots a row cannot
        reach before finishing (remaining < horizon) or that exceed table
        capacity don't need pages — those surplus writes are
        scratch-redirected and discarded by design.

        ``prealloc`` extends the target a further N slots
        OPPORTUNISTICALLY: extra pages come from the free list only
        (never a flush, never a preemption) and keep one headroom block
        per active row so admission's watermark is untouched. The
        pipelined scheduler uses it to cover the full in-flight horizon
        (window * depth), making a mid-queue page flush the exception;
        over-grants are speculative and rolled back at release,
        preemption, or by _reclaim_spec_pages under pressure."""
        with self._clock.span("ensure_pages", "serving.ensure_pages"):
            capacity = self.max_blocks * self.block_size
            for row in range(self.max_batch):
                req = self.rows[row]
                if req is None:
                    continue
                # n_generated may lag the device by the in-flight queue
                # (pipelined mode): remaining is then an OVERestimate, so the
                # horizon only ever covers extra slots — writes stay inside
                # allocated (or scratch-redirected) pages either way.
                remaining = req.max_new - req.n_generated
                last_write = min(
                    int(self.seq_lens[row]) + min(horizon, remaining) - 1,
                    capacity - 1,
                )
                need_pages = last_write // self.block_size + 1
                while len(req.blocks) < need_pages:
                    got = self.alloc.alloc(1)
                    if got is not None:
                        req.blocks.extend(got)
                        self.tables[row, len(req.blocks) - 1] = got[0]
                        continue
                    if self._inflight:
                        # Pool dry with windows in flight: drain them first —
                        # their finished rows may free blocks, and preemption
                        # bookkeeping (prompt+generated) must be exact.
                        self._flush_inflight()
                        if self.rows[row] is not req:
                            break  # this row finished in the flush
                        continue  # retry allocation against the fresh state
                    if self._reclaim_spec_pages(horizon):
                        continue  # speculative grants rolled back; retry
                    if (
                        self.prefix_cache is not None
                        and self.prefix_cache.evict(1)
                    ):
                        if self.decisions is not None:
                            self.decisions.record(
                                "evict_cold", blocks=1, reason="growth",
                                rid=req.rid,
                                trace_id=getattr(
                                    self.traces.get(req.rid), "trace_id", None
                                ),
                            )
                        continue  # cold cache evicted BEFORE any preemption
                    victim = max(
                        (r for r in self.rows if r is not None),
                        key=lambda r: r.admit_order,
                    )
                    self._preempt(victim)
                    if victim is req or self.rows[row] is not req:
                        break  # this row is gone; nothing more to grow
                if self.two_lifetimes and self.rows[row] is req:
                    self._grow_window_pages(req, row, need_pages)
            if prealloc > 0:
                self._prealloc_write_pages(horizon + prealloc)

    def _prealloc_write_pages(self, horizon: int) -> None:
        """Best-effort page growth toward ``horizon`` write slots per live
        row — free-list only, stopping at one headroom block per active
        row (the same constant admission's watermark protects)."""
        capacity = self.max_blocks * self.block_size
        for row in range(self.max_batch):
            req = self.rows[row]
            if req is None:
                continue
            remaining = req.max_new - req.n_generated
            last_write = min(
                int(self.seq_lens[row]) + min(horizon, remaining) - 1,
                capacity - 1,
            )
            need_pages = last_write // self.block_size + 1
            want = need_pages - len(req.blocks)
            spare = self.alloc.available - self.n_active
            if want <= 0 or spare <= 0:
                continue
            got = self.alloc.alloc_upto(min(want, spare))
            for b in got:
                req.blocks.append(b)
                self.tables[row, len(req.blocks) - 1] = b
            if got:
                self.stats["page_preallocs"] = (
                    self.stats.get("page_preallocs", 0) + len(got)
                )
            if len(got) < want:
                return  # pool has no spare pages this boundary

    def _reclaim_spec_pages(self, horizon: int) -> int:
        """Roll back speculative page grants: free every live row's
        blocks beyond its committed ``horizon`` coverage. Only legal with
        an empty in-flight queue (callers flush first) — then no write
        can target the reclaimed pages, and all live K/V sits below the
        committed frontier, which the kept coverage strictly contains.
        Returns the number of blocks returned to the pool."""
        assert not self._inflight, "reclaim needs committed state"
        capacity = self.max_blocks * self.block_size
        freed = 0
        for row in range(self.max_batch):
            req = self.rows[row]
            if req is None:
                continue
            remaining = req.max_new - req.n_generated
            last_write = min(
                int(self.seq_lens[row]) + min(horizon, remaining) - 1,
                capacity - 1,
            )
            need_pages = last_write // self.block_size + 1
            if len(req.blocks) > need_pages:
                surplus = req.blocks[need_pages:]
                del req.blocks[need_pages:]
                self.tables[row, need_pages:] = 0
                self.alloc.free(surplus)
                freed += len(surplus)
        if freed:
            self.stats["page_reclaims"] = (
                self.stats.get("page_reclaims", 0) + freed
            )
            if self.decisions is not None:
                self.decisions.record(
                    "reclaim_spec", blocks=freed, horizon=horizon,
                )
        return freed

    def _preempt(self, req: _Request) -> None:
        """Evict a running request: free its memory, requeue it at the
        FRONT with prompt+generated as the new prompt (vLLM-style recompute
        recovery — cheap for short generations, and the only option that
        frees ALL its blocks)."""
        # A victim admitted this very boundary may still hold its first
        # token on device; resolve it so the resumed prompt is exact.
        # Resolution can itself FINISH the request (stop token /
        # max_new=1) — then its blocks are already freed and there is
        # nothing to preempt.
        self._resolve_first(req)
        if req.row is None:
            return
        row = req.row
        self.stats["preemptions"] += 1
        if self.preempt_counter is not None:
            self.preempt_counter.inc()
        new_prompt = req.prompt + req.generated
        remaining = req.max_new - len(req.generated)
        assert remaining >= 1, "finished requests are reaped, not preempted"
        if self.decisions is not None:
            tr = self.traces.get(req.rid)
            self.decisions.record(
                "preempt",
                rid=req.rid,
                trace_id=getattr(tr, "trace_id", None),
                row=row,
                # Why this victim: youngest-first by admission order, so
                # the oldest admitted requests always make progress.
                victim_admit_order=req.admit_order,
                blocks_reclaimed=len(req.blocks),
                tokens_to_recompute=len(req.generated),
                preemption_n=req.preemptions + 1,
            )
        self._release_row(req)
        fresh = _Request(
            req.rid, new_prompt, remaining,
            prefix=req.prefix + req.generated,
            preemptions=req.preemptions + 1,
        )
        self.waiting.appendleft(fresh)

    def _finish(self, req: _Request) -> None:
        out = req.prefix + req.generated
        if self.stop_token is not None and out and out[-1] == self.stop_token:
            out = out[:-1]
        self.finished[req.rid] = out
        if self.logprobs_k:
            # Stop-token stripping above must strip its entry too: keep
            # the per-rid list exactly aligned with the output tokens.
            lps = self.logprobs.get(req.rid)
            if lps is not None and len(lps) > len(out):
                self.logprobs[req.rid] = lps[: len(out)]
        t = self.req_timing.get(req.rid)
        if t is not None:
            t["end_s"] = self._now()
        self._release_row(req)
        if self.on_finish is not None:
            self.on_finish(req.rid, out)

    def _release_row(self, req: _Request) -> None:
        row = req.row
        assert row is not None
        if self.prefix_cache is not None:
            # Publish the row's committed full blocks back to the cache
            # (and deref its shared ones). Only slots strictly below
            # p + g - 1 are guaranteed written — the LAST sampled token
            # may never have been fed — and any surplus in-flight window
            # writes at or above that frontier, so publishing below it is
            # safe even mid-pipeline.
            g = len(req.generated)
            p = len(req.prompt)
            if req.prefill_pos is not None:
                # Mid-prefill release (chunked cancellation/preemption):
                # only chunks below prefill_pos ever landed — publish
                # exactly those. A resume then re-acquires its OWN
                # partial prefix from the cache, so the rework shrinks
                # to the unprefilled remainder.
                publish_len = req.prefill_pos
            else:
                publish_len = p + g - 1 if g else p
            published = self.prefix_cache.release_row(
                req.prompt + req.generated, req.blocks, req.n_shared,
                publish_len,
            )
            if self.kv_checksum and published:
                # Record content digests AT publish — the pages below the
                # committed frontier are final (shared pages are read-only
                # and a row only ever writes ahead of it), so the digest
                # taken here is the truth every later acquire verifies.
                from pretraining_llm_tpu.resilience import integrity

                for b in published:
                    self.prefix_cache.set_checksum(
                        b, integrity.kv_block_digest(self.pools, b)
                    )
        else:
            self.alloc.free(req.blocks)
        if self.two_lifetimes:
            self.w_alloc.free(req.w_blocks)
            req.w_blocks, req.w_first = [], 0
            self.w_tables[row, :] = 0
        req.blocks = []
        req.n_shared = 0
        req.row = None
        self.rows[row] = None
        self.tables[row, :] = 0
        self.seq_lens[row] = 0
        self.tokens[row] = 0
        self.drafts[row] = 0
        if not self.has_work():
            st = self.stats
            routing = ""
            if "moe_steps" in st:
                routing = "; experts (%s) took %d pairs, %d touched, %d weight reads over %d steps (%s)" % (
                    self.decode_experts, st["moe_expert_tokens"].sum(),
                    st["moe_experts_touched"].sum(), st["moe_visits"], st["moe_steps"], self.decode_experts_plan,
                )
            if self.state_slots:
                routing += "; state slots stepped as %s (%d state, %d page, %d cacheless layers)" % (
                    self.decode_state, self.cfg.n_state_layers, self.cfg.n_page_layers,
                    self.cfg.n_cacheless_layers,
                )
            if self.spec_k:
                routing += "; %d speculative rounds (draft: %s) proposed %d, accepted %d" % (
                    st.get("spec_rounds", 0), "mtp" if self.self_draft else "model",
                    st.get("spec_proposed", 0), st.get("spec_accepted", 0),
                )
            _log.info(
                "engine empty after %d ticks, %d decode steps: attention read %d "
                "live pages of %d tabled (%.4f)%s; %s",
                st["ticks"], st["steps"], st["attn_pages_live"], st["attn_pages_tabled"],
                st["attn_pages_live"] / max(1, st["attn_pages_tabled"]), routing,
                _witness.summary(),
            )
