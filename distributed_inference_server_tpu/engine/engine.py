"""Continuous-batching inference engine over the paged KV cache.

This is the TPU-native realization of the reference's inference-execution
layer (``InferenceWorker``/``KVCacheManager``/decode loop, stubs at
``crates/inference/src/worker.rs:1``; spec ``design.md:315-412,660-674``),
redesigned for XLA's compilation model:

- **Continuous batching at decode-step granularity** replaces the spec's
  static pad-to-max batches (``design.md:244-248`` [spec]): a fixed pool of
  ``max_batch`` decode slots; requests join/leave between steps. The 50ms/32
  windowed batcher survives as the *admission* layer (engine/batcher.py), so
  the reference's batching properties still hold at the boundary.
- **Static shapes everywhere**: decode always runs the full [max_batch]
  program (inactive slots masked by dropping their page writes); prefill
  lengths snap to a small set of buckets. One compiled program per bucket,
  warm-compiled at startup, instead of XLA recompiling per request mix.
- **On-device sampling** fused into the decode step (temperature/top-p per
  slot) so tokens — not logits — cross the host boundary each step.
- **Block decode + pipelining**: decode runs as compiled K-step blocks
  (``lax.scan`` with on-device EOS/length masking and carried device state)
  and the host consumes block N-1's tokens while block N executes — one
  [K, B] token download per block instead of the per-token blocking sync
  the reference's host-driven loop implies (design.md:660-674 [spec]).
- **Prefix reuse + LRU** via the PageAllocator (Properties 9-11), with
  on-demand page allocation during decode and preemption (youngest slot
  returns to the queue, pages released) when the pool runs dry.
- **Per-request failure isolation** (Property 22, design.md:812-816): host-
  side processing of each slot is fenced; a poisoned request errors out
  alone.

Threading: the engine is synchronous and single-owner (one step() caller);
the serving layer runs it on a dedicated thread and bridges to asyncio.
"""

from __future__ import annotations

import functools
import logging
import os
import time
from collections import deque
from dataclasses import dataclass, field, replace as dc_replace
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distributed_inference_server_tpu.core.errors import (
    CacheDeserializationError,
    CacheFull,
)
from distributed_inference_server_tpu.core.models import FinishReason, Usage
from distributed_inference_server_tpu.core.types import RequestId
from distributed_inference_server_tpu.engine.kv_cache import (
    _KIND_LATENT,
    _KIND_QPOOL,
    _KIND_WIRE8,
    _encode_group,
    _scatter_payload,
    chunk_crc,
    DIGEST_DEPTH,
    HostTier,
    KvChunk,
    KvImportSession,
    LATENT_QUANTS,
    LatentCodec,
    PageAllocator,
    PagedCacheConfig,
    PagedKVState,
    QuantPool,
    default_latent_rank,
    deserialize_into_allocator,
    deserialize_kv,
    encoded_page_fraction,
    gather_kv_parts,
    iter_chain_hashes,
    payload_kind,
    serialize_kv,
    serialize_kv_chunks,
    start_host_copies,
)
from distributed_inference_server_tpu.engine.speculative import (
    PatternTrackers,
    SpecConfig,
    _probs as spec_probs,
    accept_and_resample as spec_accept_resample,
    spec_signature,
)
from distributed_inference_server_tpu.ops.sampling import (
    nucleus_probs as spec_nucleus,
)
from distributed_inference_server_tpu.models import llama
from distributed_inference_server_tpu.models.configs import ModelConfig
from distributed_inference_server_tpu.models.tokenizer import Tokenizer
from distributed_inference_server_tpu.ops.sampling import sample_tokens

logger = logging.getLogger(__name__)


def _chosen_logprob(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """log softmax(logits)[token] per row: [B, V] x [B] -> [B] f32 (the
    model-distribution log-probability of each sampled token).

    Computed as logits[token] - logsumexp(logits): two [B, V] reductions
    with no [B, V] intermediate, where log_softmax-then-take would write
    (and read back) the full 33 MB log-probability matrix per decode
    step at the 128k-vocab bench geometry."""
    x = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(x, axis=-1)
    chosen = jnp.take_along_axis(
        x, jnp.maximum(tokens, 0)[:, None], axis=-1
    )[:, 0]
    return chosen - lse


def _device_append_pages(block_tables, bt_counts, free_pages, n_free,
                         free_used, needed, rows, sub_rounds):
    """Grow row block tables from the device-held page free-list inside
    a looped decode block (kernel looping, docs/PERF.md). ``needed`` is
    each row's target page count for its next write(s) (0 for rows that
    must not grow); up to ``sub_rounds`` statically-unrolled passes each
    assign at most one page per row, in row order, via a cumsum rank
    over the rows still short. ``free_used`` indexes into
    ``free_pages`` (sentinel-padded past ``n_free``); assignment order
    is deterministic, so the host can replay it from the returned
    tables alone. Rows the list cannot cover come back ``starved`` —
    the loop freezes them with exit reason 'pages' and the host
    re-stages them after reconciling the draw."""
    P = block_tables.shape[1]
    for _ in range(sub_rounds):
        need = (bt_counts < needed) & (bt_counts < P)
        rank = jnp.cumsum(need.astype(jnp.int32)) - 1
        draw_idx = free_used + rank
        got = need & (draw_idx < n_free)
        new_page = free_pages[
            jnp.minimum(draw_idx, free_pages.shape[0] - 1)
        ]
        col = jnp.minimum(bt_counts, P - 1)
        cur = block_tables[rows, col]
        block_tables = block_tables.at[rows, col].set(
            jnp.where(got, new_page, cur)
        )
        bt_counts = bt_counts + got.astype(jnp.int32)
        free_used = free_used + jnp.sum(got.astype(jnp.int32))
    starved = bt_counts < needed
    return block_tables, bt_counts, free_used, starved


def _make_allocator(pcfg: PagedCacheConfig, force: Optional[bool],
                    need_offload_hook: bool = False):
    """Pick the page-allocator tier: the native C++ implementation
    (native/allocator.cpp — the reference's serving layer is native, ours
    matches) when available, the canonical Python one otherwise.
    ``need_offload_hook`` (host-tier prefix cache) requires the Python
    tier — the native allocator has no eviction callback surface."""
    if need_offload_hook:
        if force is True:
            raise RuntimeError(
                "native_allocator=True is incompatible with the host-tier "
                "prefix cache (host_tier_bytes > 0): the native allocator "
                "has no offload hook"
            )
        return PageAllocator(pcfg)
    if force is not False:
        try:
            from distributed_inference_server_tpu import native

            if native.available():
                return native.NativePageAllocator(pcfg)
        except Exception as e:  # noqa: BLE001 — toolchain missing etc.
            logging.getLogger(__name__).info(
                "native allocator unavailable (%s); using the Python tier",
                e,
            )
        if force is True:
            raise RuntimeError(
                "native_allocator=True but the native library is unavailable"
            )
    return PageAllocator(pcfg)


@dataclass(frozen=True)
class SamplingParams:
    max_tokens: int = 256
    temperature: float = 1.0
    top_p: float = 1.0
    stop_sequences: Tuple[str, ...] = ()


@dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 8
    prefill_buckets: Tuple[int, ...] = (32, 128, 512)
    paged: PagedCacheConfig = field(default_factory=PagedCacheConfig)
    seed: int = 0
    # decode attention: "auto" = Pallas ragged paged-attention kernel on
    # TPU, XLA gather path elsewhere; or force "pallas" / "xla"
    attention_impl: str = "auto"
    # host-side page allocator: None = native C++ (native/allocator.cpp)
    # when the library builds, Python fallback otherwise; True/False force
    native_allocator: Optional[bool] = None
    # decode steps per compiled block: the host pays one device round-trip
    # per block, not per token (the reference's per-token host loop,
    # design.md:660-674 [spec], is hot-path poison on TPU — each sync costs
    # a full host<->device round trip). EOS/length stopping is masked
    # on-device inside the block.
    decode_block_size: int = 8
    # blocks kept in flight beyond the one being processed: with depth 1
    # the host consumes block N-1's tokens while the device runs block N,
    # hiding the round-trip entirely. 0 = synchronous (fetch each block
    # right after launch).
    pipeline_depth: int = 1
    # prompts prefill in one batched program per bucket instead of
    # sequential B=1 calls: up to prefill_batch waiting rows share a chunk
    # forward (padded rows' writes are dropped)
    prefill_batch: int = 4
    # max prefill tokens (batch rows x bucket) processed per engine step:
    # long prompts prefill in budgeted quanta interleaved with decode
    # blocks, so seated sequences keep decoding while a long prompt loads
    # (at least one chunk always runs, so progress is guaranteed)
    prefill_token_budget: int = 2048
    # ragged mixed-batch stepping (ISSUE 12): > 0 enables the MIXED step
    # — ONE jitted dispatch per engine iteration consuming a packed
    # token-budgeted batch of decode rows (1 token each, from the device
    # carry) plus prefill-chunk rows (PackInfer-style back-to-back, no
    # bucket padding), attended by the ragged paged-attention kernel
    # (ops/pallas/paged_attention.py paged_attention_ragged; XLA ragged
    # reference off-TPU). Prefill no longer runs as separate quantum
    # programs that stall every in-flight decode for their duration: TBT
    # stays flat under prompt bursts on a unified replica. The value is
    # the TOTAL packed width (decode slots + prefill budget) and must
    # exceed max_batch. 0 = the quantum-interleave path (baseline).
    # Does not compose with speculative decoding or stage/seq/data mesh
    # axes (rejected at construction).
    mixed_step_tokens: int = 0
    # run-to-completion decode blocks (Kernel Looping, docs/PERF.md;
    # arxiv 2410.23668): decode blocks carry an on-device page free-list
    # and run to the stop condition (EOS / budget / free-list
    # exhaustion / loop_max_steps) inside ONE compiled lax.while_loop
    # instead of stopping at a host-chosen decode_block_size. The host
    # PageAllocator draws pages into a DEVICE-HELD state at launch and
    # reconciles the device's block-table appends afterwards. Also
    # folds the mixed step into K-block form (one ragged dispatch
    # advances decode_block_size decode tokens per iteration while
    # prefill chunks pack the remainder) and lifts the
    # mixed-vs-speculation exclusion (draft+verify compose inside the
    # same looped program). Greedy tokens are bit-identical to the
    # fixed-K path (tests/test_engine_loop.py).
    loop_to_completion: bool = False
    # per-launch iteration cap for looped blocks so a runaway row cannot
    # starve admission or hold the device carry forever: a block that
    # hits the cap simply resumes at the next engine step. Degradation
    # rungs shrink the effective cap (set_loop_cap_frac) like they
    # shrink the mixed prefill frac.
    loop_max_steps: int = 256
    # GPipe microbatches per forward when the mesh has a stage axis
    # (pipeline parallelism, parallel/pp.py); must divide max_batch and
    # prefill_batch
    pp_microbatches: int = 1
    # context-parallel prefill (parallel/cp.py): when the mesh has a
    # ``seq`` axis, prompts at least this long prefill via sequence-
    # parallel attention sharded over it, landing straight in the page
    # pool. None = auto (one past the largest prefill bucket). Ignored
    # without a seq axis.
    cp_min_tokens: Optional[int] = None
    # sequence-parallel attention flavor for that path: "ring" (KV
    # rotation over ICI, any axis size) or "ulysses" (all-to-all head
    # scatter, axis must divide the query- and KV-head counts)
    sp_impl: str = "ring"
    # compile every serving program at startup (engine.warmup()) so the
    # first real request doesn't pay tracing + XLA compile (~20-40s on
    # TPU). Off by default — tests build many engines; the server and
    # hot-swap paths turn it on (serving config engine.warmup_compile).
    warmup_compile: bool = False
    # KV cache quantization: "int8" stores pools as per-vector-absmax
    # int8 codes + f32 scales (engine/kv_cache.py QuantPool) — half the
    # KV HBM traffic per decode step and double the context capacity.
    # Forces the XLA attention path (the Pallas kernels DMA raw pages)
    # and is not supported under stage/seq mesh axes.
    kv_quant: str = "none"
    # host-RAM second tier of the prefix cache (engine/kv_cache.py
    # HostTier; ISSUE 5): LRU-evicted refcount-0 prefix pages demote to a
    # bounded host pool instead of dropping, and prefix matching falls
    # through HBM misses into it. 0 = off. Requires the Python allocator
    # tier (the native one has no eviction hook).
    host_tier_bytes: int = 0
    # host-tier storage encoding for FLOAT pools: "int8" stores demoted
    # pages as per-vector absmax codes + f32 scales (4x smaller for f32
    # pools, lossy like the disagg wire quant); "latent"/"latent_int8"
    # store rank-r latent codes (needs latent_rank > 0); quantized
    # pools always store their native codes exactly.
    host_tier_quant: str = "none"
    # latent page codec (TPLA stage (a), docs/CACHING.md "Latent KV
    # pages"): rank of the per-(layer, kv-head) projection pairs the
    # engine calibrates at construction. 0 = off (no codec; latent
    # wire/tier settings degrade to "none"). Float pools only — gated
    # off for quantized pools and speculative engines like the host
    # tier is.
    latent_rank: int = 0
    # chain depth covered by the published routing digest (config
    # cache.digest_depth): first-K page hashes per cached chain. Deeper
    # digests let the fleet cost model (serving/scheduler.py plan_route)
    # see — and peer-fetch — deep matches that a shallow digest would
    # flatten to "identical past page K"; the price is a bigger
    # EngineStatus snapshot per replica.
    digest_depth: int = DIGEST_DEPTH


@dataclass
class SequenceExport:
    """A live sequence lifted off its engine for KV handoff (disaggregated
    prefill/decode serving, serving/disagg.py): everything a receiving
    engine needs to resume decoding exactly where the source stopped —
    paged K/V bytes (serialize_kv format, Property 12), the host text /
    emission state, and the sampling params. Token-identical resumption
    is tested in tests/test_disagg.py."""

    request_id: RequestId
    token_ids: List[int]  # tokens whose K/V is resident (prompt so far)
    prompt_len: int
    seq_len: int  # == len(token_ids) for a completed prefill
    next_token: int  # sampled, not yet decoded (the migration point)
    params: SamplingParams
    output_text: str
    emitted_upto: int
    emitted_tokens: int
    pending_ids: List[int]
    kv: bytes
    draft_kv: Optional[bytes] = None
    source_engine: str = ""
    # streamed handoff (export_handoff_begin/finish): page-group chunks
    # replace the monolithic ``kv`` payload; ``wire_quant`` names the
    # per-chunk wire encoding (kv_cache.WIRE_QUANTS). ``stalled_at`` is the
    # host-local monotonic instant the sequence stopped decoding on the
    # source (drives kv_handoff_stall_seconds; never on the wire).
    kv_chunks: Optional[List[KvChunk]] = None
    wire_quant: str = "none"
    stalled_at: float = 0.0

    def kv_bytes(self) -> int:
        n = len(self.kv) + len(self.draft_kv or b"")
        if self.kv_chunks is not None:
            n += sum(len(c.payload) for c in self.kv_chunks)
        return n


@dataclass
class HandoffExportSession:
    """State of one streamed (decode-overlapped) handoff export, owned by
    the engine thread: the immutable full-page prefix snapshot taken at
    export_handoff_begin, the chunks serialized so far, and liveness.
    ``dead`` means the migration is off (request aborted, finished in
    place, or preempted) — the caller drops the job; the request itself
    is unaffected."""

    seq: "_Seq"
    prefix_pages: List[int]
    chunk_pages: int
    wire_quant: str
    chunks: List[KvChunk] = field(default_factory=list)
    prefix_done: bool = False
    dead: bool = False

    @property
    def request_id(self) -> RequestId:
        return self.seq.request_id


@dataclass
class StepOutput:
    """One event emitted by step(): a token delta and/or completion."""

    request_id: RequestId
    token_id: Optional[int] = None
    text: str = ""  # detokenized delta safe to emit now
    token_index: int = 0
    # log-probability of token_id under the model distribution (raw-logit
    # log-softmax; temperature/top-p-independent — matches the reference's
    # optional TokenEvent logprob, models.rs:272-277)
    logprob: Optional[float] = None
    finished: bool = False
    finish_reason: Optional[FinishReason] = None
    usage: Optional[Usage] = None
    error: Optional[str] = None


# distlint: thread-confined — sequences live inside their engine, which is
# single-owner on the runner thread (see LLMEngine below)
class _Seq:
    """Host-side state of one in-flight request."""

    __slots__ = (
        "request_id", "token_ids", "prompt_len", "block_table",
        "seq_len", "next_token", "params", "output_text", "emitted_upto",
        "emitted_tokens", "dev_pos", "dev_steps_left", "freed_upto",
        "pending_ids", "prefill_only", "exporting",
    )

    def __init__(self, request_id: RequestId, prompt_ids: List[int],
                 params: SamplingParams):
        self.request_id = request_id
        self.token_ids: List[int] = list(prompt_ids)
        self.prompt_len = len(prompt_ids)
        self.block_table: List[int] = []
        self.seq_len = 0  # tokens with K/V resident in pages
        self.next_token: Optional[int] = None  # sampled, not yet decoded
        self.params = params
        self.output_text = ""
        self.emitted_upto = 0
        self.emitted_tokens = 0
        # device-side projections (host view lags by the in-flight blocks):
        # upper bound on the device row's position, and launch budget left
        self.dev_pos = 0
        self.dev_steps_left = 0
        # sliding-window reclaim watermark: table entries below this are
        # freed (sentinel) — pages fully behind the attention window
        self.freed_upto = 0
        # incremental-detokenization holdback: token ids whose text is an
        # incomplete UTF-8 / byte-fallback sequence (decodes to U+FFFD)
        self.pending_ids: List[int] = []
        # disaggregated serving (serving/disagg.py): stop after the first
        # sampled token and park in the handoff-ready set instead of
        # seating for decode — the KV migrates to a decode engine
        self.prefill_only = False
        # streamed handoff in flight (export_handoff_begin): the sequence
        # decodes in place while its immutable prefix pages serialize;
        # window reclaim must not free pages mid-stream
        self.exporting = False

    def num_output_tokens(self) -> int:
        return len(self.token_ids) - self.prompt_len


class _EmbedState:
    """Accumulator for an incremental embeddings computation (see
    LLMEngine.embed_start/embed_step/embed_finish)."""

    __slots__ = ("work", "sums", "counts", "idx")

    def __init__(self, work, sums, counts):
        self.work = work
        self.sums = sums
        self.counts = counts
        self.idx = 0


# distlint: thread-confined — the engine is single-owner by contract: every
# interaction goes through EngineRunner's inbox and runs on the runner
# thread (serving/runner.py module docstring); DL008's cross-thread write
# analysis does not apply inside it
class LLMEngine:
    """Single-model continuous-batching engine (one replica = one "worker"
    in the reference's terms, ``design.md:335-342`` [spec])."""

    def __init__(
        self,
        params: llama.Params,
        cfg: ModelConfig,
        tokenizer: Tokenizer,
        engine_cfg: Optional[EngineConfig] = None,
        dtype=jnp.bfloat16,
        mesh=None,
        draft_params: Optional[llama.Params] = None,
        draft_cfg: Optional[ModelConfig] = None,
        spec: Optional[SpecConfig] = None,
        device=None,
    ):
        """``mesh``: optional ``jax.sharding.Mesh`` (parallel/mesh.py) for
        intra-replica tensor parallelism — weights and the paged KV pool are
        sharded over the ``tensor`` axis (parallel/tp.py layout) and every
        jitted step runs SPMD with XLA-inserted ICI collectives. Without a
        mesh, single-device execution (the reference's worker model) — on
        ``device`` when given: weights and pools are COMMITTED there, so
        every jitted step follows them and N one-chip replicas in one
        process use N chips (uncommitted, they would all land on
        ``jax.devices()[0]``).

        ``draft_params``/``draft_cfg``: optional draft model enabling
        speculative decoding inside the continuous-batching step (Req 12,
        requirements.md:164-170 [spec]): the draft gets its own page pool
        addressed by the SAME block tables as the target (pages are
        allocated once and hold both models' K/V for the same tokens, so
        prefix-cache sharing carries the draft cache along for free), and
        decode blocks run speculative rounds — draft proposes gamma
        tokens, target verifies them in one T=gamma+1 forward, rejection
        sampling accepts a prefix. Acceptance is tracked and speculation
        auto-disables below ``spec.disable_threshold`` (Req 12.5), falling
        back to plain decode blocks."""
        self.params = params
        self.cfg = cfg
        self.tok = tokenizer
        self.ecfg = engine_cfg or EngineConfig()
        self.pcfg = self.ecfg.paged
        self.dtype = dtype
        self.mesh = mesh
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        self.spec = spec or SpecConfig()
        self.spec_trackers = (
            PatternTrackers(self.spec) if draft_params is not None else None
        )
        kvq = self.ecfg.kv_quant
        if kvq != "none":
            # (value validation itself lives in PagedKVState.create)
            if self.ecfg.attention_impl == "pallas":
                raise ValueError(
                    "kv_quant='int8' serves on the XLA attention path: "
                    "Mosaic rejects the int8-pool decode kernel "
                    "(ops/pallas/paged_attention.py; tools/"
                    "kernel_probe.py) and the prefill kernel has no "
                    "int8 variant"
                )
            # stage axes: QuantPool pools thread through pp_paged_forward
            # as pytrees with per-member stage specs (parallel/pp.py);
            # seq axes: ring/Ulysses prefill quantizes at the pool
            # scatter (parallel/cp.py:_scatter_pool). VERDICT r4 #4.
        if self.ecfg.mixed_step_tokens:
            if self.ecfg.mixed_step_tokens <= self.ecfg.max_batch:
                raise ValueError(
                    f"mixed_step_tokens ({self.ecfg.mixed_step_tokens}) "
                    f"must exceed max_batch ({self.ecfg.max_batch}): the "
                    "packed width holds every decode slot plus at least "
                    "one prefill token"
                )
            if draft_params is not None and not self.ecfg.loop_to_completion:
                # under loop_to_completion the exclusion lifts: mixed
                # iterations advance decode rows one PLAIN token while a
                # prompt backlog exists (greedy spec ≡ greedy plain, so
                # token identity holds), and once the backlog drains the
                # looped spec block owns the carry gamma+1 at a time
                raise ValueError(
                    "mixed_step_tokens does not compose with speculative "
                    "decoding: the mixed step owns the decode carry one "
                    "token at a time, the spec block gamma+1 at a time "
                    "(set engine.loop_to_completion to compose them)"
                )
            if mesh is not None and (
                mesh.shape.get("stage", 1) > 1
                or mesh.shape.get("seq", 1) > 1
                or mesh.shape.get("data", 1) > 1
            ):
                raise ValueError(
                    "mixed_step_tokens supports single-device and "
                    "tensor-axis meshes only (the ragged attend shards "
                    "heads; stage/seq/data axes take the quantum path)"
                )
        if self.ecfg.loop_to_completion and self.ecfg.loop_max_steps < 1:
            raise ValueError(
                f"loop_max_steps must be >= 1, got "
                f"{self.ecfg.loop_max_steps}"
            )
        self.draft_state = (
            PagedKVState.create(draft_cfg, self.pcfg, dtype=dtype,
                                kv_quant=kvq)
            if draft_params is not None
            else None
        )

        self.state = PagedKVState.create(cfg, self.pcfg, dtype=dtype,
                                         kv_quant=kvq)
        if mesh is not None and device is not None:
            raise ValueError("pass a mesh or a device, not both")
        if device is not None:
            # (a missing draft is None, an empty pytree: passes through)
            self.params, self.draft_params = jax.device_put(
                (self.params, self.draft_params), device)
            for st in filter(None, (self.state, self.draft_state)):
                st.k, st.v = jax.device_put((st.k, st.v), device)
        if mesh is not None:
            from jax.sharding import NamedSharding

            from distributed_inference_server_tpu.parallel import tp as tp_rules

            pp = mesh.shape.get("stage", 1)
            stage_axis = "stage" if pp > 1 else None
            if self.ecfg.sp_impl not in ("ring", "ulysses"):
                raise ValueError(
                    f"sp_impl must be 'ring' or 'ulysses', got "
                    f"{self.ecfg.sp_impl!r}"
                )
            sp_ax = mesh.shape.get("seq", 1)
            if sp_ax > 1 and self.ecfg.sp_impl == "ulysses":
                tp_sz = mesh.shape.get("tensor", 1)
                if (cfg.num_heads // tp_sz) % sp_ax or (
                    cfg.num_kv_heads // tp_sz
                ) % sp_ax:
                    raise ValueError(
                        f"Ulysses seq axis {sp_ax} must divide the per-"
                        f"tensor-shard head counts "
                        f"({cfg.num_heads // tp_sz} q / "
                        f"{cfg.num_kv_heads // tp_sz} kv); use sp_impl="
                        "'ring' for larger axes"
                    )
            tp_rules.validate_tp(cfg, mesh.shape.get("tensor", 1))
            if stage_axis is not None:
                from distributed_inference_server_tpu.parallel.pp import (
                    validate_pp,
                )

                validate_pp(cfg, pp, self.ecfg.max_batch,
                            self.ecfg.pp_microbatches)
                validate_pp(cfg, pp, self.ecfg.prefill_batch,
                            self.ecfg.pp_microbatches)
                if draft_params is not None:
                    # the draft pipelines over the same stage axis: its
                    # layer stack must split the same way
                    validate_pp(draft_cfg, pp, self.ecfg.max_batch,
                                self.ecfg.pp_microbatches)
            self.params = tp_rules.shard_params(params, mesh, cfg,
                                                stage_axis=stage_axis)
            pool_sharding = NamedSharding(
                mesh, tp_rules.kv_pool_spec(stage_axis)
            )

            def put_pool(pool):
                if isinstance(pool, QuantPool):
                    # scale [L, slots, KV] shards on KV heads like the
                    # codes, layers on the stage axis under PP
                    from jax.sharding import PartitionSpec as P

                    scale_sh = NamedSharding(
                        mesh, P(stage_axis, None, "tensor")
                    )
                    return QuantPool(
                        jax.device_put(pool.data, pool_sharding),
                        jax.device_put(pool.scale, scale_sh),
                    )
                return jax.device_put(pool, pool_sharding)

            self.state.k = put_pool(self.state.k)
            self.state.v = put_pool(self.state.v)
            if self.draft_params is not None:
                tp_rules.validate_tp(draft_cfg, mesh.shape.get("tensor", 1))
                self.draft_params = tp_rules.shard_params(
                    self.draft_params, mesh, draft_cfg,
                    stage_axis=stage_axis,
                )
                self.draft_state.k = put_pool(self.draft_state.k)
                self.draft_state.v = put_pool(self.draft_state.v)
        if self._moe_impl() == "ep":
            # Serving is drop-free: per-expert load never exceeds N (top-k
            # experts are distinct per token), so a capacity factor of E/k
            # guarantees no assignment is dropped — unlike the training-
            # oriented 1.25 default, which silently zeroes overflow tokens.
            dropless = self.cfg.num_experts / self.cfg.num_experts_per_tok
            if self.cfg.moe_capacity_factor < dropless:
                self.cfg = self.cfg.with_overrides(
                    moe_capacity_factor=dropless
                )
        self.allocator = _make_allocator(
            self.pcfg, self.ecfg.native_allocator,
            # draft_state check mirrors the host-tier gate below: a
            # speculative engine never gets a tier, so it must neither
            # reject the native allocator nor silently downgrade to the
            # Python one for a hook nobody will install
            need_offload_hook=(self.ecfg.host_tier_bytes > 0
                               and self.draft_state is None),
        )
        # host-RAM second tier of the prefix cache (ISSUE 5): LRU-evicted
        # refcount-0 pages demote here via the allocator's offload hook;
        # _start_prefill falls through HBM misses into it
        self.host_tier: Optional[HostTier] = None
        if self.ecfg.host_tier_bytes > 0:
            if self.draft_state is not None:
                # a speculative engine's shared pages cover BOTH pools;
                # demoting only the target pool would re-seat prefixes
                # whose draft KV is garbage (silent acceptance collapse)
                logger.warning(
                    "host-tier prefix cache disabled: speculative engines "
                    "would re-seat prefixes with a stale draft KV pool"
                )
            else:
                self.host_tier = HostTier(
                    self.ecfg.host_tier_bytes,
                    quant=self.ecfg.host_tier_quant,
                    # one full gather bucket stays in flight; bursts
                    # larger than the window span several offer() calls
                    # (new_burst=False continuations) and never drain
                    # their own still-in-flight copies
                    inflight_window=self._OFFLOAD_BUCKETS[-1],
                )
                self.allocator.offload_hook = self._offload_pages
                # bucketed page-group pull as a single compiled program:
                # an eviction burst dispatches one cached executable per
                # ≤32-page group instead of an op-by-op eager chain per
                # page (gather + quant can be 6-8 dispatches eagerly —
                # the dominant term of the allocate path that triggers
                # the demotions). quant is static arg 0.
                self._offload_pull = jax.jit(gather_kv_parts,
                                             static_argnums=0)
        # host-tier traffic counters (runner._report_cache_deltas turns
        # them into kv_prefix_hits_total{tier=host} etc.): engine-thread
        # writes, racy-but-atomic int reads from the status path
        self._host_hit_pages = 0
        self._host_reload_durations: List[float] = []
        self.waiting: Deque[_Seq] = deque()
        # prefill_only sequences whose first token has been emitted: pages
        # held, waiting for the serving layer to export_handoff() them
        self._handoff_ready: Dict[RequestId, _Seq] = {}
        self.slots: List[Optional[_Seq]] = [None] * self.ecfg.max_batch
        self._by_id: Dict[RequestId, _Seq] = {}
        self._rng = jax.random.PRNGKey(self.ecfg.seed)
        self._num_slots_flat = self.pcfg.num_pages * self.pcfg.page_size
        self._smax = self.pcfg.max_pages_per_seq * self.pcfg.page_size

        # --- decode-block state ---
        # Host mirror of per-slot block tables / sampling params, uploaded
        # at each block launch (tiny arrays; uploads are async, unlike the
        # per-token download the r1 loop blocked on).
        B = self.ecfg.max_batch
        self._bt = np.zeros((B, self.pcfg.max_pages_per_seq), np.int32)
        self._bt_pages = np.zeros((B,), np.int32)
        self._temp = np.ones((B,), np.float32)
        self._topp = np.ones((B,), np.float32)
        # slot -> (active, token, position, steps) overrides merged into the
        # device carry at the next launch (admissions and deactivations)
        self._slot_updates: Dict[int, Tuple[bool, int, int, int]] = {}
        # device-carried decode state: (tokens, positions, steps_left,
        # active, rng) — created at first launch, never fetched to host
        self._carry = None
        # launched-but-unprocessed blocks: (out_tokens [K, B] device array,
        # [(slot, seq)] snapshot at launch)
        self._pending: Deque[Tuple[jnp.ndarray, List[Tuple[int, _Seq]]]] = deque()

        # step-scoped device-trace capture (utils/profiler.py):
        # (n, base_dir, event, holder) armed by profile_steps(); active
        # capture is [steps_left, TraceSession, event, holder]
        self._prof_req = None
        self._prof_active = None

        # jit caches
        # "auto" probe result: (decode_impl, prefill_impl) once resolved
        self._auto_impl: Optional[Tuple[str, str]] = None
        # kernel name -> first line of Mosaic's message, for every kernel
        # the "auto" probes rejected (placement() reports them; a
        # rejection must be visible, not a quiet XLA server)
        self._probe_rejected: Dict[str, str] = {}
        # experimental int8-pool Pallas decode opt-in, captured ONCE at
        # construction: re-reading the env per resolution call could flip
        # the attention impl mid-serving after blocks were already built
        self._kv_quant_pallas = (
            os.environ.get("DIS_TPU_KV_QUANT_PALLAS") == "1"
        )
        # ragged mixed-batch step (EngineConfig.mixed_step_tokens): one
        # compiled program, built lazily at the first mixed launch (the
        # "auto" ragged-kernel probe runs then); host-side share/traffic
        # accounting feeds engine_mixed_step_tokens{kind} + the density
        # gauge via mixed_stats()
        self._mixed_fn: Optional[Callable] = None
        self._mixed_impl: Optional[str] = None
        self._mixed_prefill_frac = 1.0
        self._mixed_steps = 0
        self._mixed_prefill_tokens = 0
        self._mixed_decode_tokens = 0
        self._mixed_density_sum = 0.0
        # run-to-completion looped blocks (EngineConfig.loop_to_completion;
        # kernel looping): compiled per effective iteration cap (the
        # degradation ladder shrinks it), spec variants per (use_topp,
        # cap). Host-side counters feed engine_loop_steps_total /
        # engine_loop_exit_total via loop_stats() — the runner
        # delta-reports them like the mixed block.
        self._loop_fns: Dict[int, Callable] = {}
        self._spec_loop_fns: Dict[Tuple[bool, int], Callable] = {}
        self._loop_cap_frac = 1.0
        self._loop_blocks = 0
        self._loop_steps = 0
        self._loop_decode_tokens = 0
        self._loop_exits = {"eos": 0, "budget": 0, "pages": 0, "cap": 0}
        # engine step clock (docs/OBSERVABILITY.md "Performance
        # telemetry"): host-side wall time, dispatch counts, tokens and
        # batch rows per dispatch kind, plus step-loop pressure events.
        # HOST timestamps only — time.monotonic around the host sections
        # of each dispatch path, never a device sync (DL007-safe); the
        # runner delta-reports these cumulative counters like the mixed
        # block, and drains _sc_samples into the windowed digests.
        self._sc_kinds: Dict[str, Dict[str, float]] = {
            k: {"dispatches": 0, "wall_s": 0.0, "tokens": 0, "rows": 0}
            for k in ("prefill", "decode_block", "mixed", "loop")
        }
        self._sc_events: Dict[str, int] = {
            "cache_full": 0, "preempt": 0, "reclaim": 0, "retrace": 0,
        }
        self._sc_samples: List[Tuple[str, float]] = []
        # warmup() compiles every serving program up front — those are
        # boot cost, not the mid-serving "retrace" pressure event
        self._in_warmup = False
        self._fwd = self._make_fwd()
        self._prefill_fns: Dict[Tuple[int, int], Callable] = {}
        self._cp_fns: Dict[int, Callable] = {}
        self._block_fn = self._build_decode_block()
        # speculative block variants keyed by use_topp: the nucleus-aware
        # verify pays full-vocab sorts per round, so all-greedy/top_p=1
        # launches dispatch a variant compiled without them
        self._spec_block_fns: Dict[bool, Callable] = {}
        if draft_params is not None:
            self._spec_block_fns[False] = self._build_spec_block(False)

        # per-kind encoded payload byte counters (runner delta-reports
        # them into kv_payload_bytes_total{kind}; docs/OBSERVABILITY.md)
        # + the raw-equivalent bytes latent encodes stood in for (the
        # /server/stats cache block's savings figure). Initialized
        # BEFORE codec calibration — its prefill pass can demote pages.
        self._payload_bytes: Dict[str, int] = {
            k: 0 for k in ("raw", "int8", "qpool", "latent", "latent_int8")
        }
        self._latent_raw_equiv_bytes = 0
        # latent page codec (TPLA stage (a)): per-(layer, kv-head)
        # rank-r projections, calibrated over a short deterministic
        # prefill pass at construction (or loaded when the model config
        # ships them). Gated like the host tier: float pools only, no
        # speculative engines (the draft pool would need its own codec
        # and bit-exactness for the acceptance law).
        self.latent_codec: Optional[LatentCodec] = None
        self._warned_latent_off = False
        if self.ecfg.latent_rank > 0:
            if self.draft_state is not None or isinstance(
                self.state.k, QuantPool
            ):
                logger.warning(
                    "latent KV codec disabled: %s",
                    "speculative engines need the draft pool bit-exact"
                    if self.draft_state is not None
                    else "quantized pools ship native codes exactly",
                )
            else:
                self.latent_codec = self._calibrate_latent(
                    self.ecfg.latent_rank
                )

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def add_request(
        self,
        request_id: RequestId,
        prompt_ids: List[int],
        params: SamplingParams,
        prefill_only: bool = False,
    ) -> None:
        """Queue a tokenized request for execution. ``prefill_only``
        (disaggregated serving): emit the first sampled token, then park
        the sequence for KV handoff instead of decoding here."""
        seq = _Seq(request_id, prompt_ids, params)
        seq.prefill_only = prefill_only
        self._by_id[request_id] = seq
        self.waiting.append(seq)

    def abort(self, request_id: RequestId) -> bool:
        """Abort a queued or running request (client disconnect,
        Req 5.4 requirements.md:85). Returns True if found.

        Pages are released immediately; an in-flight decode block may still
        write into them, but that is safe: a reader only ever gathers slots
        its own sequence has already written (positions < kv_valid), and
        the new owner's prefill is enqueued after the in-flight block."""
        seq = self._by_id.pop(request_id, None)
        if seq is None:
            return False
        self._handoff_ready.pop(request_id, None)
        if seq in self.waiting:
            self.waiting.remove(seq)
        for i, s in enumerate(self.slots):
            if s is seq:
                self.slots[i] = None
                self._deact_slot(i)
        self._release_seq(seq)
        return True

    def has_work(self) -> bool:
        return bool(self._by_id)

    def num_active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def num_waiting(self) -> int:
        return len(self.waiting)

    def step(self) -> List[StepOutput]:
        """One engine iteration: admit waiting requests into free slots
        (prefill + first sampled token), launch a decode block (K on-device
        steps, async), and consume the oldest pending block's tokens once
        the pipeline is full (or nothing new was launched). Token events
        therefore arrive in bursts of up to ``decode_block_size`` per
        sequence, ``pipeline_depth`` blocks behind the device.

        With ``mixed_step_tokens`` set and prefill work pending, the
        quantum+block pair is replaced by ONE ragged mixed dispatch:
        every seated decode row advances one token while the prefill
        backlog consumes the packed budget's remainder — a long prompt
        no longer stalls in-flight decodes for a full quantum. With no
        prefill backlog, decode runs the K-step block path unchanged.

        With ``loop_to_completion`` set, pure-decode iterations run as
        run-to-completion looped blocks instead of fixed-K blocks: ONE
        dispatch per launch that keeps stepping on-device — growing row
        block tables from a device-held page free-list — until every
        row hits EOS / its budget / free-list exhaustion or the
        iteration cap. Looped blocks do not pipeline (the loop already
        amortizes the host round-trip over its whole run); they are
        processed synchronously right after the dispatch returns."""
        outputs: List[StepOutput] = []
        self._prof_begin()
        self._admit(outputs)
        if self.ecfg.mixed_step_tokens and any(
            s is not None and s.next_token is None
            and s.seq_len < len(s.token_ids)
            for s in self.slots
        ):
            launched = self._mixed_step(outputs)
        elif self.ecfg.loop_to_completion:
            self._prefill_quantum(outputs)
            launched = self._loop_step(outputs)
        else:
            self._prefill_quantum(outputs)
            launched = self._maybe_launch(outputs)
        if self._pending and (
            len(self._pending) > self.ecfg.pipeline_depth or not launched
        ):
            self._process_block(outputs)
        self._prof_end_step()
        return outputs

    def profile_steps(self, n: int, base_dir: Optional[str] = None):
        """Arm a device-trace capture (utils/profiler.py) spanning the next
        ``n`` engine steps — the SURVEY §5 "trace per decode step" bar.
        Returns (event, holder): the event is set when the capture
        finalizes and ``holder`` then carries the trace summary (or an
        ``error`` key). Capture begins at the next step() call, so an idle
        engine captures nothing until work arrives."""
        import threading as _threading

        ev = _threading.Event()
        holder: Dict[str, object] = {}
        self._prof_req = (max(1, int(n)), base_dir, ev, holder)
        return ev, holder

    def cancel_profile(self, holder) -> None:
        """Disarm a not-yet-started capture (timed-out waiter): a trace
        nobody consumes must not start later and hold the global profiler
        lock. Already-active captures run to completion."""
        if self._prof_req is not None and self._prof_req[3] is holder:
            self._prof_req = None

    def _prof_begin(self) -> None:
        if self._prof_req is None or self._prof_active is not None:
            return
        n, base_dir, ev, holder = self._prof_req
        self._prof_req = None
        try:
            from distributed_inference_server_tpu.utils.profiler import (
                TraceSession,
            )

            session = TraceSession(base_dir)
        except Exception as e:  # noqa: BLE001 — e.g. capture in progress
            holder["error"] = str(e)
            ev.set()
            return
        self._prof_active = [n, session, ev, holder]

    def _prof_end_step(self) -> None:
        if self._prof_active is None:
            return
        self._prof_active[0] -= 1
        if self._prof_active[0] > 0:
            return
        _, session, ev, holder = self._prof_active
        self._prof_active = None
        try:
            holder.update(session.stop())
            holder["mode"] = "steps"
        except Exception as e:  # noqa: BLE001 — profiler teardown failure
            holder["error"] = str(e)
        ev.set()

    def cache_stats(self):
        return self.allocator.stats()

    def audit_pages(self, extra_pages: Sequence[int] = ()) -> List[str]:
        """KV-page conservation audit (docs/RESILIENCE.md): collect every
        page id a live sequence holds — waiting, active, handoff-ready,
        and mid-export sequences are all in ``_by_id``; sliding-window
        sentinels are not pages — plus ``extra_pages`` (the runner passes
        its open import sessions' reservations), and prove against the
        allocator that every page is exactly one of free / cached /
        live-held with matching refcounts. Engine-thread only (the
        allocator is single-owner). Returns inconsistency strings; the
        native allocator tier has no audit surface and reports clean."""
        if not isinstance(self.allocator, PageAllocator):
            return []
        sentinel = self.pcfg.num_pages
        live: List[int] = [
            p
            for s in self._by_id.values()
            for p in s.block_table
            if p != sentinel
        ]
        live.extend(extra_pages)
        return self.allocator.audit(live)

    # ------------------------------------------------------------------
    # host-tier prefix cache (engine/kv_cache.py HostTier; ISSUE 5)
    # ------------------------------------------------------------------

    #: demotion gather geometry: bursts split into ≤32-page groups, each
    #: padded up to a bucket so the jitted pull compiles once per bucket
    #: size instead of once per burst size
    _OFFLOAD_BUCKETS = (1, 2, 4, 8, 16, 32)

    def _offload_pages(self, victims) -> None:
        """Allocator offload hook: demote a batch of LRU-evicted
        refcount-0 pages to the host tier. Each ≤32-page group is gathered
        (plus optional on-device int8 quantization) in ONE jitted program
        and its device→host copies STARTED here — before the page ids are
        recycled, so the gather reads the old content — but nothing
        blocks: the HostTier's in-flight window materializes pages
        asynchronously behind the decode loop. Batched because eviction
        bursts ride inside allocate() on the request path: per-page pulls
        cost one dispatch per victim, which profiles as the dominant term
        of a tiered reload."""
        tier = self.host_tier
        if tier is None:
            return
        victims = [v for v in victims if not tier.has(v.hash)]
        ps = self.pcfg.page_size
        cap = self._OFFLOAD_BUCKETS[-1]
        quant = self._effective_wire_quant(tier.quant)
        kind = payload_kind(self.state.k, quant)
        for start in range(0, len(victims), cap):
            group = victims[start:start + cap]
            bucket = next(b for b in self._OFFLOAD_BUCKETS
                          if b >= len(group))
            # pad by repeating the last victim: the extra slots gather
            # real (identical) content and the tier ignores them
            padded = group + [group[-1]] * (bucket - len(group))
            slots = jnp.asarray(np.concatenate(
                [np.arange(v.page_id * ps, (v.page_id + 1) * ps)
                 for v in padded]
            ))
            if kind == _KIND_QPOOL:
                # quant normalized to "none": 5 QuantPool args must
                # never dispatch gather's latent (also 5-arg) form
                arrs = self._offload_pull(
                    "none", self.state.k.data, self.state.k.scale,
                    self.state.v.data, self.state.v.scale, slots,
                )
            elif kind == _KIND_LATENT:
                kp, vp = self.latent_codec.device_projs()
                arrs = self._offload_pull(quant, self.state.k,
                                          self.state.v, slots, kp, vp)
            else:
                arrs = self._offload_pull(quant, self.state.k,
                                          self.state.v, slots)
            start_host_copies(arrs)
            # encoded-bytes accounting: the bucket gathers padded slots,
            # the tier keeps len(group) pages of them
            nbytes = sum(int(a.nbytes) for a in arrs)
            self._note_payload(kind, quant,
                               nbytes * len(group) // bucket)
            # groups past the first are burst continuations: the window
            # must not drain this very burst's still-in-flight copies
            tier.offer([(v.hash, v.depth, v.root) for v in group], kind,
                       arrs, ps, new_burst=(start == 0))

    def _host_tier_reload(self, seq: "_Seq", prompt: List[int]) -> None:
        """Prefix-match fallthrough (ISSUE 5): continue the content-hash
        chain past the HBM match into the host tier, re-seat every
        matched page into freshly allocated HBM pages with ONE batched
        device scatter (the same ``_scatter_payload`` the streamed-import
        ``KvImportSession`` uses), and content-address them so the next
        prompt hits them in HBM directly. The scatter is dispatched
        async — it overlaps the remaining prefill chunks' compute rather
        than serializing before them."""
        tier = self.host_tier
        ps = self.pcfg.page_size
        n = len(prompt)
        start = len(seq.block_table)  # pages already shared from HBM
        if tier.empty or (start + 1) * ps >= n:
            # cold tier / HBM match already covers every matchable page:
            # skip the hash walk entirely
            return
        # lazy hash chain: the walk below stops at its first tier miss,
        # so hashing costs O(HBM match + tier match + 1) pages, not
        # O(prompt) — a long cold prompt pays one probe, not a full walk
        hash_it = iter_chain_hashes(prompt, ps)
        for _ in range(start):  # skip the hashes the HBM match covered
            next(hash_it)
        entries = []
        idx = start
        # always leave >= 1 token to compute (same contract as the HBM
        # match above)
        while (idx + 1) * ps < n:
            h = next(hash_it, None)
            if h is None:
                break
            e = tier.get(h)
            if e is None or (entries and e.kind != entries[0].kind):
                break
            entries.append(e)
            idx += 1
        if not entries:
            return
        t0 = time.monotonic()
        try:
            pages = self.allocator.allocate(len(entries))
        except CacheFull:
            return  # pool too tight to re-seat; prefill recomputes instead
        try:
            slots = np.concatenate(
                [np.arange(p * ps, (p + 1) * ps) for p in pages]
            )
            kind = entries[0].kind
            merged = tuple(
                np.concatenate([e.parts[m] for e in entries], axis=1)
                for m in range(len(entries[0].parts))
            )
            if kind == _KIND_WIRE8:
                # int8 host tier into a float pool: upload the codes+scales
                # (4x fewer bytes over PCIe than dequantized values) and
                # dequantize on device
                k_q, v_q, k_s, v_s = merged
                dt = self.state.k.dtype
                k = (jnp.asarray(k_q, jnp.float32)
                     * jnp.asarray(k_s)[..., None]).astype(dt)
                v = (jnp.asarray(v_q, jnp.float32)
                     * jnp.asarray(v_s)[..., None]).astype(dt)
                parts = (k, v)
            elif kind == _KIND_LATENT:
                # latent host tier into a float pool: upload the rank-r
                # codes (the smallest PCIe transfer of any encoding) and
                # reconstruct on device against the codec projections
                if self.latent_codec is None:
                    raise CacheDeserializationError(
                        "host tier holds latent pages but the engine "
                        "has no codec"
                    )
                dt = self.state.k.dtype
                if len(merged) == 4:  # latent_int8: dequant codes first
                    k_q, v_q, k_s, v_s = merged
                    k_codes = (jnp.asarray(k_q, jnp.float32)
                               * jnp.asarray(k_s)[..., None])
                    v_codes = (jnp.asarray(v_q, jnp.float32)
                               * jnp.asarray(v_s)[..., None])
                else:
                    k_codes = jnp.asarray(merged[0])
                    v_codes = jnp.asarray(merged[1])
                k, v = self.latent_codec.decode_device(k_codes, v_codes)
                parts = (k.astype(dt), v.astype(dt))
            else:
                # _KIND_RAW into a float pool / _KIND_QPOOL into a QuantPool
                parts = merged
            self.state = _scatter_payload(self.state, slots, parts)
        except Exception as e:  # noqa: BLE001 — reload is best-effort
            # the pages are not yet in seq.block_table and carry no
            # content address, so release() returns them straight to the
            # free list; the prefill recomputes the prefix instead
            self.allocator.release(pages)
            logger.warning("host-tier reload of %d pages failed: %s",
                           len(entries), e)
            return
        seq.block_table.extend(pages)
        seq.seq_len = (start + len(entries)) * ps
        # content-address the re-seated pages: the next prompt sharing
        # this prefix hits them in HBM (and the routing digest sees them)
        self.allocator.publish(prompt[: seq.seq_len], seq.block_table)
        self._host_hit_pages += len(entries)
        self._host_reload_durations.append(time.monotonic() - t0)
        if len(self._host_reload_durations) > 1024:
            # nobody draining (no metrics collector): keep the tail only
            del self._host_reload_durations[:-1024]

    def evict_cache(self, target_frac: float,
                    drop_host_tier: bool = False) -> None:
        """Degradation-ladder hook (serving/degradation.py): reclaim
        cached pages down to ``target_frac``, DEMOTING them to the host
        tier on the way out; ``drop_host_tier`` (the most severe rung)
        skips demotion and clears the host tier outright."""
        if self.host_tier is not None:
            self.allocator.evict_below(target_frac,
                                       demote=not drop_host_tier)
            if drop_host_tier:
                self.host_tier.clear()
            else:
                # a ladder demotion can exceed the in-flight window in
                # ONE burst, and nothing may arrive later to drain it —
                # leaving the gathered device arrays (HBM this eviction
                # just tried to free) pinned. We're off the decode hot
                # path here: materialize the overshoot now.
                self.host_tier.drain_to_window()
        else:
            self.allocator.evict_below(target_frac)

    def prefix_digest(self, max_depth: Optional[int] = None) -> frozenset:
        """Compact rolling digest of this engine's cached prefix chains
        (first-``max_depth`` page hashes per chain, HBM + host tier) for
        cache-aware routing; ``None`` = the configured
        ``ecfg.digest_depth``. Engine-thread only; the runner snapshots
        it into EngineStatus. Empty under the native allocator (no
        digest surface) — the router then falls back to least-loaded."""
        if max_depth is None:
            max_depth = self.ecfg.digest_depth
        dig = getattr(self.allocator, "prefix_digest", None)
        out = dig(max_depth) if dig is not None else frozenset()
        if self.host_tier is not None:
            out = frozenset(out) | frozenset(
                self.host_tier.digest_hashes(max_depth)
            )
        return out

    def host_tier_stats(self) -> Optional[Dict[str, int]]:
        """Host-tier occupancy/traffic snapshot for metrics and
        /server/stats; None when the tier is off."""
        if self.host_tier is None:
            return None
        s = self.host_tier.stats()
        return {
            "budget_bytes": s.budget_bytes,
            "bytes": s.bytes_used,
            "pages": s.pages,
            "hits": s.hits,
            "hit_pages": self._host_hit_pages,
            "offloads": s.offloads,
            "evictions": s.evictions,
        }

    def drain_reload_durations(self) -> List[float]:
        """Hand the accumulated host-tier reload durations to the caller
        (runner thread — the same thread that appends them)."""
        out, self._host_reload_durations = self._host_reload_durations, []
        return out

    # ------------------------------------------------------------------
    # latent page codec (TPLA stage (a); docs/CACHING.md "Latent KV pages")
    # ------------------------------------------------------------------

    def _calibrate_latent(self, rank: int) -> Optional[LatentCodec]:
        """Fit the per-(layer, kv-head) projection pairs by SVD over a
        short DETERMINISTIC calibration pass: a couple of seeded prompts
        prefill through the normal request path, the touched pool slots
        are harvested as activation samples, and the engine is reset to
        pristine (fresh allocator, zeroed pools, reset step clock) so
        calibration pages and counters never leak into serving state.
        Same weights + same seed ⇒ bit-identical projections on every
        engine of a homogeneous fleet, so codecs agree without ever
        shipping a basis on the wire. A checkpoint-shipped codec
        (``model config latent_codec_path``) skips the pass entirely."""
        path = getattr(self.cfg, "latent_codec_path", None) or None
        if path:
            codec = LatentCodec.load(path)
            if codec.rank != rank:
                raise ValueError(
                    f"model-shipped latent codec has rank {codec.rank}, "
                    f"config asks for {rank}"
                )
            return codec
        head_dim = self.cfg.head_dim
        if not 0 < rank <= head_dim:
            raise ValueError(
                f"latent_rank must be in (0, head_dim={head_dim}], "
                f"got {rank}"
            )
        # ~2 prompts of >= 2*head_dim tokens give the per-head SVDs an
        # overdetermined sample matrix; clamp to what the pool can seat
        cap = self.pcfg.max_seq_len - 2
        n_tok = min(max(2 * head_dim, 32), cap)
        rng = np.random.default_rng(0x7A7E)
        vocab = max(2, self.cfg.vocab_size - 1)
        greedy = SamplingParams(max_tokens=1, temperature=0.0)
        for i in range(2):
            prompt = [1 + int(t) for t in rng.integers(0, vocab, n_tok)]
            self.add_request(f"__latent_calib_{i}", prompt, greedy)
            while self.has_work():
                self.step()
        k = np.asarray(self.state.k, np.float32)
        v = np.asarray(self.state.v, np.float32)
        used = np.any(k != 0.0, axis=(0, 2, 3)) | np.any(
            v != 0.0, axis=(0, 2, 3))
        if int(used.sum()) < 2:
            logger.warning(
                "latent KV codec disabled: calibration pass touched "
                "%d pool slots", int(used.sum()),
            )
            codec = None
        else:
            codec = LatentCodec.calibrate(k[:, used], v[:, used], rank)
        # reset to pristine: calibration pages, content addresses, and
        # step-clock samples must not outlive the pass
        self.state = PagedKVState(jnp.zeros_like(self.state.k),
                                  jnp.zeros_like(self.state.v))
        self.allocator = _make_allocator(
            self.pcfg, self.ecfg.native_allocator,
            need_offload_hook=(self.ecfg.host_tier_bytes > 0
                               and self.draft_state is None),
        )
        if self.host_tier is not None:
            self.host_tier.clear()
            self.allocator.offload_hook = self._offload_pages
        self._by_id.clear()
        self.waiting.clear()
        self.slots = [None] * self.ecfg.max_batch
        self._slot_updates.clear()
        self._carry = None
        self._pending.clear()
        self._rng = jax.random.PRNGKey(self.ecfg.seed)
        for d in self._sc_kinds.values():
            d.update(dispatches=0, wall_s=0.0, tokens=0, rows=0)
        self._sc_events = {k: 0 for k in self._sc_events}
        self._sc_samples.clear()
        self._host_hit_pages = 0
        self._host_reload_durations.clear()
        self._payload_bytes = {k: 0 for k in self._payload_bytes}
        # a calibration-time offload legitimately sees no codec yet;
        # re-arm the one-shot warning for real serving-time degrades
        self._warned_latent_off = False
        self._latent_raw_equiv_bytes = 0
        return codec

    def _effective_wire_quant(self, wire_quant: str) -> str:
        """Degrade a latent wire request to "none" when this engine has
        no codec (latent_rank=0, spec engine, calibration declined) and
        the pool is float — QuantPool exports pass native codes through
        whatever the wire setting, so they keep it. One warning, not one
        per export."""
        if (wire_quant in LATENT_QUANTS and self.latent_codec is None
                and not isinstance(self.state.k, QuantPool)):
            if not self._warned_latent_off:
                self._warned_latent_off = True
                logger.warning(
                    "wire_quant %r degraded to \"none\": engine has no "
                    "latent codec (cache.latent_rank unset or codec "
                    "gated off)", wire_quant,
                )
            return "none"
        return wire_quant

    def _payload_label(self, kind: int, wire_quant: str) -> str:
        if kind == _KIND_QPOOL:
            return "qpool"
        if kind == _KIND_LATENT:
            return ("latent_int8" if wire_quant == "latent_int8"
                    else "latent")
        return "int8" if kind == _KIND_WIRE8 else "raw"

    def _note_payload(self, kind: int, wire_quant: str, nbytes: int) -> None:
        """Account encoded payload bytes by kind (every encode site:
        handoff, streamed chunks, prefix export, host-tier offload) —
        the runner delta-reports into kv_payload_bytes_total{kind}."""
        label = self._payload_label(kind, wire_quant)
        self._payload_bytes[label] += int(nbytes)
        if kind == _KIND_LATENT and self.latent_codec is not None:
            # latent payloads only come off float pools
            frac = encoded_page_fraction(
                wire_quant, self.state.k.dtype.itemsize,
                self.cfg.head_dim, self.latent_codec.rank,
            )
            if frac > 0:
                self._latent_raw_equiv_bytes += int(nbytes / frac)

    def payload_byte_counters(self) -> Dict[str, int]:
        """Cumulative encoded-bytes-by-kind snapshot (runner thread
        delta-reports it; plain int reads are atomic)."""
        return dict(self._payload_bytes)

    def latent_stats(self) -> Optional[Dict[str, int]]:
        """/server/stats cache block ``latent`` entry: codec rank plus
        encoded vs raw-equivalent byte totals; None when no codec."""
        if self.latent_codec is None:
            return None
        encoded = (self._payload_bytes["latent"]
                   + self._payload_bytes["latent_int8"])
        return {
            "rank": self.latent_codec.rank,
            "encoded_bytes": encoded,
            "saved_bytes": max(0, self._latent_raw_equiv_bytes - encoded),
        }

    # ------------------------------------------------------------------
    # KV handoff (disaggregated prefill/decode serving, serving/disagg.py)
    # ------------------------------------------------------------------

    def handoff_ready_ids(self) -> List[RequestId]:
        """Requests whose prefill finished under ``prefill_only`` and are
        parked for export (pages held, first token already emitted)."""
        return list(self._handoff_ready)

    def export_handoff(self, request_id: RequestId,
                       wire_quant: str = "none"
                       ) -> Optional[SequenceExport]:
        """Lift a handoff-ready sequence off this engine: serialize its
        paged K/V (and the draft pool's, when speculating) plus the host
        emission state, publish the prompt's full pages so this engine's
        prefix cache stays warm for future prompts sharing it, then
        release the pages. ``wire_quant="int8"`` applies the lossy wire
        encoding to float pools (draft pools excluded — speculation
        needs the draft cache bit-exact to keep its acceptance law).
        Returns None if the request is unknown (e.g. aborted between
        readiness and export)."""
        seq = self._handoff_ready.pop(request_id, None)
        if seq is None or self._by_id.get(request_id) is not seq:
            return None
        if seq.freed_upto or self.pcfg.num_pages in seq.block_table:
            # never reached (window reclaim skips prefill_only), but a
            # sentinel-holed table must not serialize neighboring
            # sequences' KV — fail the export loudly; the runner aborts
            # the request rather than migrating corruption
            self._handoff_ready[request_id] = seq
            raise RuntimeError(
                "handoff candidate has window-reclaimed pages"
            )
        ps = self.pcfg.page_size
        wire_quant = self._effective_wire_quant(wire_quant)
        kv = serialize_kv(self.state, seq.block_table, ps, seq.seq_len,
                          wire_quant=wire_quant, codec=self.latent_codec)
        self._note_payload(payload_kind(self.state.k, wire_quant),
                           wire_quant, len(kv))
        draft_kv = (
            serialize_kv(self.draft_state, seq.block_table, ps, seq.seq_len)
            if self.draft_state is not None
            else None
        )
        exp = SequenceExport(
            request_id=seq.request_id,
            token_ids=list(seq.token_ids),
            prompt_len=seq.prompt_len,
            seq_len=seq.seq_len,
            next_token=int(seq.next_token),
            params=seq.params,
            output_text=seq.output_text,
            emitted_upto=seq.emitted_upto,
            emitted_tokens=seq.emitted_tokens,
            pending_ids=list(seq.pending_ids),
            kv=kv,
            draft_kv=draft_kv,
            wire_quant=wire_quant,
        )
        self._by_id.pop(request_id, None)
        if seq.freed_upto == 0:
            self.allocator.publish(seq.token_ids, seq.block_table)
        self._release_seq(seq)
        return exp

    # -- streamed (decode-overlapped) export ----------------------------

    def export_handoff_begin(
        self, request_id: RequestId, chunk_pages: int = 8,
        wire_quant: str = "none",
    ) -> Optional["HandoffExportSession"]:
        """Start a STREAMED handoff export: the sequence's full prefix
        pages are immutable (decode only appends at new positions), so
        they can serialize while the sequence RESUMES DECODING IN PLACE
        — the decode pause shrinks from O(seq_len) to O(tail). The
        parked sequence is re-queued for a decode seat (the imported-
        sequence admission branch seats it straight into the carry) and
        a session covering the immutable full-page prefix is returned;
        the caller pumps it (export_handoff_pump) between steps and
        switches over with export_handoff_finish.

        Returns None — caller should use the monolithic export_handoff —
        when streaming cannot pay for itself: the prompt has no full
        page to stream, or the remaining token budget is too small to
        cover the overlap window (the sequence would finish in place
        before the switchover, turning the migration into a no-op).
        Raises like export_handoff on a window-reclaimed candidate."""
        seq = self._handoff_ready.get(request_id)
        if seq is None or self._by_id.get(request_id) is not seq:
            return None
        if seq.freed_upto or self.pcfg.num_pages in seq.block_table:
            raise RuntimeError(
                "handoff candidate has window-reclaimed pages"
            )
        n_full = seq.seq_len // self.pcfg.page_size
        # overlap window ~ 3 decode blocks (serialize + target open span
        # a couple of runner iterations, each decoding one block, plus
        # the block draining at switchover); a budget that would finish
        # inside the window decodes to completion in place instead —
        # cheaper than any migration
        overlap = 3 * self.ecfg.decode_block_size
        if n_full == 0 or (
            seq.params.max_tokens - seq.emitted_tokens <= overlap + 2
        ):
            return None
        self._handoff_ready.pop(request_id, None)
        session = HandoffExportSession(
            seq=seq,
            prefix_pages=list(seq.block_table[:n_full]),
            chunk_pages=max(1, chunk_pages),
            wire_quant=self._effective_wire_quant(wire_quant),
        )
        seq.exporting = True
        seq.prefill_only = False
        self.waiting.append(seq)  # decode resumes here during the stream
        return session

    def _session_alive(self, session: "HandoffExportSession") -> bool:
        seq = session.seq
        return (
            self._by_id.get(seq.request_id) is seq
            and seq.seq_len > 0
            and seq.freed_upto == 0
            and seq.block_table[: len(session.prefix_pages)]
            == session.prefix_pages
        )

    def export_handoff_pump(self, session: "HandoffExportSession") -> bool:
        """Serialize the session's immutable prefix (double-buffered
        device→host pulls, kv_cache.serialize_kv_chunks) while the
        sequence keeps decoding — called between steps on the engine
        thread. Returns True once the prefix is done (or the session
        died: aborted, finished in place, or preempted — the caller
        drops the migration; the request is unaffected)."""
        if session.prefix_done or session.dead:
            return True
        if not self._session_alive(session):
            session.dead = True
            session.seq.exporting = False
            return True
        new_chunks = list(serialize_kv_chunks(
            self.state, session.prefix_pages, self.pcfg.page_size,
            chunk_pages=session.chunk_pages,
            wire_quant=session.wire_quant,
            codec=self.latent_codec,
        ))
        kind = payload_kind(self.state.k, session.wire_quant)
        for c in new_chunks:
            self._note_payload(kind, session.wire_quant, len(c.payload))
        session.chunks.extend(new_chunks)
        session.prefix_done = True
        return True

    def export_handoff_cancel(self, session: "HandoffExportSession") -> None:
        """Abandon a streamed export: the sequence (if still live) simply
        keeps decoding in place — only the exporting flag is lifted so
        window reclaim can resume. Serialized chunks are host bytes and
        just get dropped."""
        session.dead = True
        seq = session.seq
        if self._by_id.get(seq.request_id) is seq:
            seq.exporting = False

    def export_handoff_finish(
        self, session: "HandoffExportSession"
    ) -> Tuple[Optional[SequenceExport], List[StepOutput]]:
        """Switch over: drain the decode pipeline (host view exact), stop
        the sequence, serialize the TAIL pages written during the overlap
        window as the final delta chunks, and lift the host state off the
        engine — publish + release exactly like export_handoff. Returns
        (None, outputs) when the sequence finished or died during the
        overlap (the drained outputs still carry its token/done events);
        the request then needs no migration."""
        outputs: List[StepOutput] = []
        seq = session.seq
        if session.dead:
            return None, outputs
        self._drain_pending(outputs)
        if not self._session_alive(session):
            session.dead = True
            seq.exporting = False
            return None, outputs
        stalled_at = time.monotonic()
        for i, s in enumerate(self.slots):
            if s is seq:
                self.slots[i] = None
                self._deact_slot(i)
        if seq in self.waiting:  # switchover before a seat opened
            self.waiting.remove(seq)
        n_prefix = len(session.prefix_pages)
        chunks = list(session.chunks)
        tail_pages = seq.block_table[n_prefix:]
        if tail_pages:
            tail_chunks = list(serialize_kv_chunks(
                self.state, tail_pages, self.pcfg.page_size,
                chunk_pages=session.chunk_pages,
                wire_quant=session.wire_quant,
                first_chunk_index=len(chunks),
                first_page_index=n_prefix,
                codec=self.latent_codec,
            ))
            kind = payload_kind(self.state.k, session.wire_quant)
            for c in tail_chunks:
                self._note_payload(kind, session.wire_quant, len(c.payload))
            chunks.extend(tail_chunks)
        total = len(chunks)
        chunks = [dc_replace(c, total=total) for c in chunks]
        exp = SequenceExport(
            request_id=seq.request_id,
            token_ids=list(seq.token_ids),
            prompt_len=seq.prompt_len,
            seq_len=seq.seq_len,
            next_token=int(seq.next_token),
            params=seq.params,
            output_text=seq.output_text,
            emitted_upto=seq.emitted_upto,
            emitted_tokens=seq.emitted_tokens,
            pending_ids=list(seq.pending_ids),
            kv=b"",
            kv_chunks=chunks,
            wire_quant=session.wire_quant,
            stalled_at=stalled_at,
        )
        self._by_id.pop(seq.request_id, None)
        if seq.freed_upto == 0:
            self.allocator.publish(seq.token_ids, seq.block_table)
        self._release_seq(seq)
        seq.exporting = False
        session.dead = True
        return exp, outputs

    def import_sequence(self, exp: SequenceExport) -> None:
        """Resume an exported sequence on this engine: allocate pages,
        restore the serialized K/V with prefix-cache registration
        (kv_cache.deserialize_into_allocator for the monolithic payload,
        an incremental KvImportSession for streamed chunks — pages
        reserved up front, published only on a validated final chunk),
        and queue the sequence for an immediate decode seat — no prefill
        recomputation. Raises CacheFull / CacheDeserializationError with
        the engine unchanged (modulo garbage in freed pages, which is
        never gathered)."""
        n = exp.seq_len
        ps = self.pcfg.page_size
        self._validate_import(exp)
        if (exp.draft_kv is None) != (self.draft_params is None):
            raise CacheDeserializationError(
                "draft-model topology mismatch between source and target "
                "engines (speculation must match across a handoff)"
            )
        if exp.kv_chunks is not None:
            # streamed import, one-shot form: pages reserved up front,
            # every chunk validated (crc/range/shape), publish only on a
            # complete stream; any failure releases everything
            # (KvImportSession). The phased form used by the serving
            # path is import_stream_open/add/commit.
            session = KvImportSession(self.state, self.allocator, ps,
                                      codec=self.latent_codec)
            try:
                session.reserve(-(-n // ps))
                for chunk in exp.kv_chunks:
                    session.add_chunk(chunk)
                self.state, pages = session.finish(self.state, exp.token_ids)
            except Exception as e:
                session.abort()
                if isinstance(e, (CacheDeserializationError, CacheFull)):
                    raise
                raise CacheDeserializationError(str(e)) from None
        elif exp.draft_kv is None:
            self.state, pages = deserialize_into_allocator(
                self.state, self.allocator, exp.kv, exp.token_ids, ps,
                codec=self.latent_codec,
            )
        else:
            # both pools restore into the SAME pages (shared block
            # tables); publish only once both succeed, so the prefix
            # cache never addresses pages with a torn draft half
            pages = self.allocator.allocate(-(-n // ps))
            try:
                self.state, tc = deserialize_kv(self.state, exp.kv, pages, ps)
                if tc != n:
                    raise CacheDeserializationError(
                        f"payload carries {tc} tokens, expected {n}"
                    )
                self.draft_state, dtc = deserialize_kv(
                    self.draft_state, exp.draft_kv, pages, ps
                )
                if dtc != n:
                    raise CacheDeserializationError(
                        f"draft payload carries {dtc} tokens, expected {n}"
                    )
            except Exception:
                self.allocator.release(pages)
                raise
            self.allocator.publish(exp.token_ids, pages)
        self._seat_imported(exp, pages)

    def _validate_import(self, exp: SequenceExport) -> None:
        """Shared import preconditions (import_sequence and
        import_stream_commit must accept exactly the same exports)."""
        n = exp.seq_len
        if n != len(exp.token_ids) or exp.next_token is None:
            raise CacheDeserializationError(
                "export is not at a decode boundary (seq_len != resident "
                "tokens or no sampled token)"
            )
        if n + 1 > self.pcfg.max_seq_len:
            raise CacheDeserializationError(
                f"sequence of {n} tokens exceeds this engine's capacity "
                f"({self.pcfg.max_seq_len} tokens)"
            )
        if exp.request_id in self._by_id:
            raise CacheDeserializationError(
                f"request {exp.request_id} is already live on this engine"
            )

    def _seat_imported(self, exp: SequenceExport, pages: List[int]) -> None:
        seq = _Seq(exp.request_id, list(exp.token_ids), exp.params)
        seq.prompt_len = exp.prompt_len  # ctor set it to len(token_ids)
        seq.block_table = list(pages)
        seq.seq_len = exp.seq_len
        seq.next_token = int(exp.next_token)
        seq.output_text = exp.output_text
        seq.emitted_upto = int(exp.emitted_upto)
        seq.emitted_tokens = int(exp.emitted_tokens)
        seq.pending_ids = list(exp.pending_ids)
        self._by_id[seq.request_id] = seq
        self.waiting.append(seq)

    # -- phased (decode-overlapped) import ------------------------------

    def import_stream_open(self, request_id: RequestId,
                           prefix_pages: int) -> KvImportSession:
        """Open an incremental import for a streamed handoff: reserve the
        immutable-prefix pages UP FRONT (a CacheFull surfaces here, while
        the source sequence is still decoding in place and the migration
        can be abandoned for free) and return the session the runner
        feeds via import_stream_add. Raises CacheDeserializationError /
        CacheFull with the engine unchanged."""
        if request_id in self._by_id:
            raise CacheDeserializationError(
                f"request {request_id} is already live on this engine"
            )
        if self.draft_params is not None:
            raise CacheDeserializationError(
                "streamed handoff carries no draft pool; this engine "
                "speculates (topology must match across a handoff)"
            )
        if prefix_pages > self.pcfg.max_pages_per_seq:
            raise CacheDeserializationError(
                f"prefix of {prefix_pages} pages exceeds this engine's "
                f"per-sequence capacity ({self.pcfg.max_pages_per_seq})"
            )
        session = KvImportSession(self.state, self.allocator,
                                  self.pcfg.page_size,
                                  codec=self.latent_codec)
        try:
            session.reserve(prefix_pages)
        except Exception:
            session.abort()
            raise
        return session

    def import_stream_add(self, session: KvImportSession,
                          chunks: List[KvChunk]) -> None:
        """Absorb arrived chunks: validate and WRITE them into the pool
        now (reserved pages; invisible to prefix matching until commit).
        This is the work the overlap window hides — by commit time only
        the tail delta remains."""
        for chunk in chunks:
            session.add_chunk(chunk)
        self.state = session.apply_ready(self.state)

    def import_stream_commit(self, session: KvImportSession,
                             exp: SequenceExport) -> None:
        """Switchover on the import side: absorb the final delta chunks,
        validate the stream complete, publish, and seat the sequence for
        an immediate decode resume. On ANY failure the session is
        aborted (every reserved page released) and the error propagates
        — the controller falls back to an in-place resume on the
        source."""
        try:
            self._validate_import(exp)
            for chunk in exp.kv_chunks or []:
                session.add_chunk(chunk)
            self.state, pages = session.finish(self.state, exp.token_ids)
        except Exception as e:
            session.abort()
            if isinstance(e, (CacheDeserializationError, CacheFull)):
                raise
            raise CacheDeserializationError(str(e)) from None
        self._seat_imported(exp, pages)

    def import_stream_abort(self, session: KvImportSession) -> None:
        """Drop a phased import (source cancelled / client disconnect):
        every reserved page is released; nothing was published."""
        session.abort()

    # -- fleet peer-fetch of a cached prefix (serving/disagg.py) ---------

    def export_prefix_chunks(
        self, hashes: Sequence[int], chunk_pages: int = 8,
        wire_quant: str = "none",
    ) -> Tuple[int, List[KvChunk]]:
        """Fleet peer-fetch export (PrefixFetcher, docs/CACHING.md): walk
        ``hashes`` — a request's content-hash chain — consecutively from
        the head through this engine's prefix tiers (HBM first, host-tier
        fallthrough) and serialize every matched page as self-describing
        KvChunks — the same framing the streamed handoff puts on the
        wire. Returns ``(depth, chunks)``: depth is the consecutive
        pages served; depth < len(hashes) means the chain was (partly)
        evicted since the routing digest was snapshotted — the caller
        imports what it got or falls back to recompute. HBM pages pull
        through the double-buffered ``serialize_kv_chunks`` path
        (``wire_quant`` applies); host-tier pages ship in their stored
        encoding (already int8 when the tier quantizes — re-encoding
        would cost a decode for zero wire savings). Full pages are
        immutable, so live (refcount>0) pages export safely.
        Engine-thread only; mutates nothing beyond host-tier access
        clocks — a peer-fetched chain is re-used traffic and earns its
        chain protection."""
        ps = self.pcfg.page_size
        wire_quant = self._effective_wire_quant(wire_quant)
        lookup = getattr(self.allocator, "cached_page", None)
        # ("hbm", page_id) | ("host", _HostPage), consecutive from head
        entries: List[Tuple[str, object]] = []
        for h in hashes:
            pid = lookup(h) if lookup is not None else None
            if pid is not None:
                entries.append(("hbm", pid))
                continue
            hp = (self.host_tier.get(h)
                  if self.host_tier is not None else None)
            if hp is None:
                break
            entries.append(("host", hp))
        chunks: List[KvChunk] = []
        chunk_pages = max(1, chunk_pages)
        i = 0
        while i < len(entries):
            src = entries[i][0]
            j = i + 1
            if src == "hbm":
                while j < len(entries) and entries[j][0] == "hbm":
                    j += 1
                hbm_chunks = list(serialize_kv_chunks(
                    self.state, [p for _, p in entries[i:j]], ps,
                    chunk_pages=chunk_pages, wire_quant=wire_quant,
                    first_chunk_index=len(chunks), first_page_index=i,
                    codec=self.latent_codec,
                ))
                hbm_kind = payload_kind(self.state.k, wire_quant)
                for c in hbm_chunks:
                    self._note_payload(hbm_kind, wire_quant, len(c.payload))
                chunks.extend(hbm_chunks)
            else:
                kind = entries[i][1].kind
                while (j < len(entries) and entries[j][0] == "host"
                       and entries[j][1].kind == kind
                       and j - i < chunk_pages):
                    j += 1
                group = [e for _, e in entries[i:j]]
                merged = tuple(
                    np.concatenate([g.parts[m] for g in group], axis=1)
                    for m in range(len(group[0].parts))
                )
                # the ONE payload encoder the handoff wire uses — the
                # peer-fetch wire must never diverge from it. Host-tier
                # pages ship in their STORED encoding (kind 3 when the
                # tier is latent — _encode_group derives the int8 flag
                # from the part count).
                payload = _encode_group(self.state, kind, merged, 0)
                tier_quant = (self.host_tier.quant
                              if self.host_tier is not None else "none")
                self._note_payload(kind, tier_quant, len(payload))
                chunks.append(KvChunk(
                    index=len(chunks), total=0, page_start=i,
                    page_count=len(group), payload=payload,
                    crc32=chunk_crc(payload),
                ))
            i = j
        return len(entries), chunks

    def import_prefix(self, tokens: Sequence[int],
                      chunks: Sequence[KvChunk]) -> int:
        """Fleet peer-fetch import: seat a peer's exported prefix pages
        into this engine's prefix cache so the pending request's own
        prefill matches them instead of recomputing. Goes through the
        same ``KvImportSession`` validate-and-scatter path as the
        streamed handoff (pages reserved up front, every chunk
        crc/range/shape-checked, publish only on a complete tiling), so
        a torn fetch leaves the engine semantically unchanged — then the
        pages are RELEASED: refcount-0 content-addressed pages are
        exactly the CACHED state ``match_prefix`` shares from, and LRU
        reclaims them if nothing arrives. ``tokens`` must be the whole-
        page prefix the chunks cover (the fetcher slices the request's
        prompt by the served depth). Returns pages seated. Raises
        CacheFull / CacheDeserializationError with nothing leaked."""
        ps = self.pcfg.page_size
        n = len(tokens)
        if n <= 0 or n % ps != 0:
            raise CacheDeserializationError(
                f"prefix import must cover whole pages "
                f"(got {n} tokens, page_size {ps})"
            )
        if self.draft_params is not None:
            raise CacheDeserializationError(
                "peer-fetched prefix carries no draft pool; seating it "
                "on a speculative engine would publish pages whose "
                "draft KV is garbage"
            )
        session = KvImportSession(self.state, self.allocator, ps,
                                  codec=self.latent_codec)
        try:
            session.reserve(n // ps)
            for chunk in chunks:
                session.add_chunk(chunk)
            self.state, pages = session.finish(self.state, list(tokens))
        except Exception as e:
            session.abort()
            if isinstance(e, (CacheDeserializationError, CacheFull)):
                raise
            raise CacheDeserializationError(str(e)) from None
        self.allocator.release(pages)
        return len(pages)

    def warmup(self) -> None:
        """Compile every serving program before traffic arrives: one
        throwaway request per prefill bucket (compiles that bucket's
        batched-prefill program), decoded through at least one full block
        (compiles the decode — or speculative — block), plus the ring-
        prefill program when a seq axis is configured. Without this the
        first real request pays tracing + XLA compile (~20-40s on TPU)
        inside its TTFT.

        Decode gather windows are bucketed by live page count
        (_pages_bucket), so contexts growing past the warmed lengths
        still pay one compile per new power-of-two bucket — amortized by
        the persistent XLA compile cache across restarts."""
        steps = self.ecfg.decode_block_size + 1
        lengths = [
            min(b, self.pcfg.max_seq_len - steps - 2)
            for b in self.ecfg.prefill_buckets
        ]
        # one max-length request walks decode up to the CAP gather bucket
        # (intermediate power-of-two buckets still compile lazily, at most
        # log2(max_pages_per_seq) times over a server's lifetime)
        full = self.pcfg.max_seq_len - steps - 2
        if full > max(lengths, default=0):
            lengths.append(full)
        thr = self._cp_threshold()
        if thr is not None:
            lengths.append(min(self._cp_bucket(thr),
                               self.pcfg.max_seq_len - steps - 2))
        # boot-time compiles are not the "retrace" pressure signal (a
        # new geometry compiled MID-SERVING); gate the event so every
        # clean warmup boot doesn't read as N retraces
        self._in_warmup = True
        try:
            for i, n in enumerate(lengths):
                if n < 1:
                    continue
                # distinct leading token per warmup: prefix reuse
                # against an earlier warmup would shrink the chunk into
                # a smaller bucket's program and leave this one cold
                tok_id = 1 + i % max(1, self.cfg.vocab_size - 1)
                self.add_request(
                    f"__warmup_{i}", [tok_id] * n,
                    SamplingParams(max_tokens=steps, temperature=0.0),
                )
                # drain one at a time: co-seated warmups would share
                # the largest bucket's program and leave the others cold
                while self.has_work():
                    self.step()  # outputs discarded
        finally:
            self._in_warmup = False

    # ------------------------------------------------------------------
    # admission / prefill
    # ------------------------------------------------------------------

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _admit(self, outputs: List[StepOutput]) -> None:
        while self.waiting:
            slot = self._free_slot()
            if slot is None:
                return
            seq = self.waiting[0]
            n = len(seq.token_ids)
            needed_pages = -(-(n + 1) // self.pcfg.page_size)
            if n + 1 > self.pcfg.max_seq_len or needed_pages > self.pcfg.num_pages:
                self.waiting.popleft()
                self._by_id.pop(seq.request_id, None)
                outputs.append(StepOutput(
                    request_id=seq.request_id, finished=True,
                    error=f"prompt of {n} tokens exceeds the engine "
                          f"capacity ({self.pcfg.max_seq_len} tokens)",
                ))
                continue
            if (
                seq.next_token is not None
                and seq.block_table
                and seq.seq_len >= len(seq.token_ids)
            ):
                # imported via KV handoff (import_sequence): K/V already
                # resident in this engine's pages — seat straight into
                # the decode carry, no prefill
                self.waiting.popleft()
                self.slots[slot] = seq
                self._stage_seat(slot, seq)
                continue
            try:
                self._start_prefill(seq)
            except CacheFull:
                self._event("cache_full")
                return  # no pages; retry next step
            except Exception as e:  # failure isolation (Property 22)
                self.waiting.popleft()
                self._by_id.pop(seq.request_id, None)
                self._release_seq(seq)
                outputs.append(StepOutput(
                    request_id=seq.request_id, finished=True, error=str(e)))
                continue
            self.waiting.popleft()
            self.slots[slot] = seq  # seated, prefilling (next_token None)

    def _start_prefill(self, seq: _Seq) -> None:
        """Claim pages for the whole prompt (prefix-shared where possible)
        and mark the sequence as prefilling. The actual compute happens in
        budgeted quanta (_prefill_quantum) so decode is never starved."""
        ps = self.pcfg.page_size
        self._release_seq(seq)  # defensive: drop any stale pages
        prompt = seq.token_ids  # on re-admission after preemption this
        # includes previously generated tokens; their logits are recomputed
        # only past the cached prefix.
        n = len(prompt)

        # prefix reuse (Property 9) — match against prompt[:-1] so a
        # fully-cached prompt still leaves >= 1 token to compute and the
        # hit counters never count a page that would be released right back
        shared_pages, shared_tokens = self.allocator.match_prefix(
            prompt[: n - 1])
        seq.block_table = list(shared_pages)
        seq.seq_len = shared_tokens
        seq.next_token = None

        # host-tier fallthrough (ISSUE 5): HBM misses may still be warm
        # in host RAM — re-seat them instead of recomputing the prefill
        if self.host_tier is not None:
            self._host_tier_reload(seq, prompt)

        # allocate the remaining pages for the prompt
        pages_needed = -(-n // ps) - len(seq.block_table)
        if pages_needed > 0:
            try:
                seq.block_table.extend(self.allocator.allocate(pages_needed))
            except CacheFull:
                self._release_seq(seq)
                raise

    def _prefill_quantum(self, outputs: List[StepOutput]) -> None:
        """Run up to ``prefill_token_budget`` prefill tokens: waiting chunks
        of up to ``prefill_batch`` sequences share one compiled program per
        length bucket (the spec's pad-to-batch-max batching, design.md:
        244-246 [spec], applied to prefill). Sequences whose prompts
        complete sample their first token (batched, on-device) and are
        staged into the decode carry."""
        budget = self.ecfg.prefill_token_budget
        Bp = self.ecfg.prefill_batch
        sc_t0 = time.monotonic()  # step clock: host wall only
        sc_tokens = sc_rows = sc_disp = 0
        thr = self._cp_threshold()
        if thr is not None:
            # at most ONE ring prefill per step, and it consumes the whole
            # step's prefill budget: seated sequences get a decode block
            # between long-prompt admissions instead of starving behind
            # them (the budget's decode-starvation guarantee)
            for slot, s in list(enumerate(self.slots)):
                if (
                    s is not None and s.next_token is None
                    and len(s.token_ids) >= thr
                ):
                    remaining = len(s.token_ids) - s.seq_len
                    try:
                        self._cp_prefill_seq(slot, s, outputs)
                        sc_tokens += remaining
                        sc_rows += 1
                        sc_disp += 1
                    except Exception as e:  # failure isolation (Property 22)
                        self.slots[slot] = None
                        self._by_id.pop(s.request_id, None)
                        self._release_seq(s)
                        outputs.append(StepOutput(
                            request_id=s.request_id, finished=True,
                            error=str(e)))
                    budget = 0
                    break
        # Phase 1 — dispatch: launch every chunk program in the quantum
        # back-to-back WITHOUT touching device results; the first-token
        # fetch of group N would otherwise serialize group N+1's upload
        # behind a full host<->device round trip (the r1 per-step sync
        # bug in miniature, one per prefill group).
        dispatched: List[
            Tuple[object, object, List[Tuple[int, _Seq]], List[bool]]
        ] = []
        while budget > 0:
            group = [
                (i, s) for i, s in enumerate(self.slots)
                if s is not None and s.next_token is None
                and s.seq_len < len(s.token_ids)  # not yet reaped below
            ][:Bp]
            if not group:
                break
            bucket = self._pick_bucket(max(
                len(s.token_ids) - s.seq_len for _, s in group
            ))
            ids = np.zeros((Bp, bucket), np.int32)
            positions = np.zeros((Bp, bucket), np.int32)
            write_slots = np.full((Bp, bucket), self._num_slots_flat, np.int32)
            # prefill gathers are always full width — see _gather_pages
            # for why (one shape per admitted chunk, exact warmup cover)
            gpages = self._gather_pages(0, prefill=True)
            gather = np.zeros((Bp, gpages * self.pcfg.page_size), np.int32)
            gather[: len(group)] = self._gather_slots(
                [s.block_table for _, s in group], gpages
            )
            kv_valid = np.zeros((Bp,), np.int32)
            last_idx = np.zeros((Bp,), np.int32)
            temp = np.ones((Bp,), np.float32)
            top_p = np.ones((Bp,), np.float32)
            chunk_lens: List[int] = []
            for j, (_, s) in enumerate(group):
                start = s.seq_len
                t = min(len(s.token_ids) - start, bucket)
                chunk_lens.append(t)
                ids[j, :t] = s.token_ids[start : start + t]
                positions[j] = np.arange(start, start + bucket, dtype=np.int32)
                write_slots[j] = self._slots_for_positions(
                    s.block_table, positions[j : j + 1], t
                )[0]
                kv_valid[j] = start + t
                last_idx[j] = t - 1
                temp[j] = s.params.temperature
                top_p[j] = s.params.top_p

            fn = self._get_prefill_fn(Bp, bucket)
            self._rng, sub = jax.random.split(self._rng)
            args = (
                jnp.asarray(ids),
                jnp.asarray(positions),
                self.state.k,
                self.state.v,
                jnp.asarray(write_slots),
                jnp.asarray(gather),
                jnp.asarray(kv_valid),
                jnp.asarray(last_idx),
                jnp.asarray(temp),
                jnp.asarray(top_p),
                sub,
            )
            if self.draft_params is not None:
                # the draft model prefills the same chunk into its own
                # pool (same slots) so speculative rounds can attend the
                # full prompt
                (toks, lps, self.state.k, self.state.v,
                 self.draft_state.k, self.draft_state.v) = fn(
                    self.params, self.draft_params,
                    self.draft_state.k, self.draft_state.v, *args,
                )
            else:
                toks, lps, self.state.k, self.state.v = fn(
                    self.params, *args
                )
            budget -= Bp * bucket
            sc_tokens += sum(chunk_lens)
            sc_rows += len(group)
            sc_disp += 1
            done: List[bool] = []
            for j, (_, s) in enumerate(group):
                s.seq_len += chunk_lens[j]  # host view advances now so the
                # next while-iteration groups the remaining chunks
                done.append(s.seq_len >= len(s.token_ids))
            dispatched.append((toks, lps, list(group), done))

        # Phase 2 — reap: fetch each group's first-token batch (the device
        # has been crunching the later groups meanwhile) and seat finished
        # prompts into the decode carry. ``done`` marks rows whose FINAL
        # prompt chunk ran in that group — only there is toks[j] the real
        # first sampled token.
        for toks, lps, group, done in dispatched:
            toks_np: Optional[np.ndarray] = None
            for j, (slot, s) in enumerate(group):
                if not done[j]:
                    continue  # mid-prompt chunk (or finished elsewhere)
                if self._by_id.get(s.request_id) is not s:
                    continue  # aborted between dispatch and reap
                if toks_np is None:
                    toks_np = np.asarray(toks)
                    lps_np = np.asarray(lps)
                try:
                    self._emit_token(s, int(toks_np[j]), outputs,
                                     float(lps_np[j]))
                except Exception as e:  # failure isolation (Property 22)
                    self.slots[slot] = None
                    self._by_id.pop(s.request_id, None)
                    self._release_seq(s)
                    outputs.append(StepOutput(
                        request_id=s.request_id, finished=True, error=str(e)))
                    continue
                if self._by_id.get(s.request_id) is s:
                    if s.prefill_only:
                        # disaggregated handoff point: first token is out;
                        # free the slot but keep the pages — the serving
                        # layer exports the sequence to a decode engine
                        self.slots[slot] = None
                        self._handoff_ready[s.request_id] = s
                    else:
                        self._stage_seat(slot, s)
                # else: finished during its very first token (EOS or
                # max_tokens=1) — _finish already cleared the slot
        if sc_disp:
            self._clock("prefill", time.monotonic() - sc_t0,
                        tokens=sc_tokens, rows=sc_rows, dispatches=sc_disp)

    def _pick_bucket(self, remaining: int) -> int:
        for b in self.ecfg.prefill_buckets:
            if remaining <= b:
                return b
        return self.ecfg.prefill_buckets[-1]

    # ------------------------------------------------------------------
    # ragged mixed-batch step (EngineConfig.mixed_step_tokens; ISSUE 12)
    # ------------------------------------------------------------------

    def set_mixed_prefill_frac(self, frac: float) -> None:
        """Degradation-ladder hook (serving/degradation.py): shrink the
        prefill share of the mixed step's packed budget under memory
        pressure — decode rows keep their slots; prompt loading slows
        instead of decode stalling. Engine-thread only (the runner posts
        it); floor 0.05 so prefill always progresses."""
        self._mixed_prefill_frac = min(1.0, max(0.05, float(frac)))

    def mixed_stats(self) -> Optional[Dict[str, object]]:
        """Mixed-step traffic snapshot for /metrics and the
        /server/stats engine block; None when the mixed step is off.
        ``batch_density`` is the rolling mean of (real packed tokens) /
        mixed_step_tokens — how full the MXU tiles actually ran."""
        if not self.ecfg.mixed_step_tokens:
            return None
        steps = self._mixed_steps
        return {
            "steps": steps,
            "prefill_tokens": self._mixed_prefill_tokens,
            "decode_tokens": self._mixed_decode_tokens,
            "batch_density": round(
                self._mixed_density_sum / steps, 4) if steps else 0.0,
            "prefill_frac": self._mixed_prefill_frac,
        }

    def set_loop_cap_frac(self, frac: float) -> None:
        """Degradation-ladder hook (serving/degradation.py): shrink the
        looped block's iteration cap under memory pressure so page draws
        stay small and admission gets the device back sooner — the loop
        analogue of set_mixed_prefill_frac. Engine-thread only (the
        runner posts it); floor 0.05 so decode always progresses."""
        self._loop_cap_frac = min(1.0, max(0.05, float(frac)))

    def _loop_cap(self) -> int:
        """Effective iteration cap for the next looped block: the
        configured loop_max_steps scaled by the degradation ladder's
        fraction, never below one step."""
        return max(1, int(self.ecfg.loop_max_steps * self._loop_cap_frac))

    def loop_stats(self) -> Optional[Dict[str, object]]:
        """Looped-block traffic snapshot for /metrics and the
        /server/stats engine block; None when loop_to_completion is off.
        ``steps`` counts device loop iterations (the dispatch-amortized
        unit the fixed-K path pays one host round-trip per block for);
        ``exits`` counts per-row stop reasons at block reconcile."""
        if not self.ecfg.loop_to_completion:
            return None
        return {
            "blocks": self._loop_blocks,
            "steps": self._loop_steps,
            "decode_tokens": self._loop_decode_tokens,
            "exits": dict(self._loop_exits),
            "cap": self._loop_cap(),
            "cap_frac": self._loop_cap_frac,
        }

    # ------------------------------------------------------------------
    # engine step clock (docs/OBSERVABILITY.md "Performance telemetry")
    # ------------------------------------------------------------------

    def _clock(self, kind: str, wall_s: float, tokens: int = 0,
               rows: int = 0, dispatches: int = 0) -> None:
        """Attribute one host-side wall-time segment to a dispatch kind.
        Engine-thread only; pure dict bumps (no device work, DL007-safe
        in every hot set)."""
        c = self._sc_kinds[kind]
        c["dispatches"] += dispatches
        c["wall_s"] += wall_s
        c["tokens"] += tokens
        c["rows"] += rows
        self._sc_samples.append((kind, wall_s))
        if len(self._sc_samples) > 4096:
            # the runner drains every loop; a headless engine (tests,
            # bench) must still stay bounded
            del self._sc_samples[:-2048]

    def _event(self, name: str, n: int = 1) -> None:
        if name == "retrace" and self._in_warmup:
            return  # boot-time compile, not a mid-serving retrace
        self._sc_events[name] = self._sc_events.get(name, 0) + n

    def step_clock_stats(self) -> Dict[str, Dict[str, float]]:
        """Cumulative step-clock counters (engine-thread writes; the
        runner's status path reads copies — delta-reporting like
        mixed_stats)."""
        return {
            "kinds": {k: dict(v) for k, v in self._sc_kinds.items()},
            "events": dict(self._sc_events),
        }

    def drain_step_samples(self) -> List[Tuple[str, float]]:
        """Per-segment (kind, wall_s) samples since the last drain —
        the runner feeds them into the step_ms.<kind> windowed digests."""
        out, self._sc_samples = self._sc_samples, []
        return out

    def _resolved_mixed_impl(self) -> str:
        """Attention impl for the mixed step's ragged attend: the ragged
        Pallas kernel on TPU when its AOT probe passes (same judge-is-
        Mosaic policy as _resolved_impl, same single builder
        ``llama.make_ragged_attend`` as serving), the XLA ragged
        reference otherwise. Quantized pools always serve on XLA (no
        int8 ragged kernel)."""
        if self.ecfg.kv_quant != "none":
            return "xla"
        impl = self.ecfg.attention_impl
        if impl == "xla":
            return "xla"
        if self._mixed_impl is None:
            if jax.default_backend() != "tpu":
                self._mixed_impl = "xla"
            elif impl == "pallas":
                self._mixed_impl = "pallas"  # explicit pin wins
            else:
                self._mixed_impl = (
                    "pallas" if self._probe_ragged() else "xla"
                )
        return self._mixed_impl

    def _probe_ragged(self) -> bool:
        """AOT-compile the ragged mixed-batch kernel at this engine's
        exact mixed geometry (packed width, row count, page shapes —
        sharded form under a tensor axis) so a Mosaic rejection
        downgrades to the XLA ragged path instead of crashing the first
        mixed launch."""
        from distributed_inference_server_tpu.models.llama import (
            make_ragged_attend,
            shard_ragged_attend,
        )

        pcfg = self.pcfg
        S = self.ecfg.mixed_step_tokens
        B = self.ecfg.max_batch
        Bm = B + min(self.ecfg.prefill_batch, S - B)
        tp = self.mesh.shape.get("tensor", 1) if self.mesh is not None else 1
        sm = self.mesh is not None and tp > 1
        if sm:
            kv, heads = self.cfg.num_kv_heads, self.cfg.num_heads
        else:
            kv = max(1, self.cfg.num_kv_heads // tp)
            heads = max(1, self.cfg.num_heads // tp)
        slots = pcfg.num_pages * pcfg.page_size
        pool = jax.ShapeDtypeStruct((slots, kv, self.cfg.head_dim),
                                    self.dtype)
        fn = make_ragged_attend(
            pcfg.page_size, self.cfg.attn_logit_softcap or 0.0,
            interpret=False,
        )
        if sm:
            fn = shard_ragged_attend(fn, self.mesh)
        try:
            jax.jit(fn).lower(
                jax.ShapeDtypeStruct((S, heads, self.cfg.head_dim),
                                     self.dtype),
                pool, pool,
                jax.ShapeDtypeStruct((Bm, pcfg.max_pages_per_seq),
                                     jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.int32),
                jax.ShapeDtypeStruct((Bm,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
            ).compile()
            return True
        except Exception as e:  # Mosaic rejection or backend failure
            first = str(e).split("\n")[0]
            self._probe_rejected["ragged"] = first
            logger.warning(
                "Pallas ragged mixed-batch kernel unavailable for this "
                "geometry (mixed step -> xla ragged path): %s", first,
            )
            return False

    def _mixed_block_k(self) -> int:
        """Decode tokens one mixed dispatch advances: decode_block_size
        under loop_to_completion (K-block fusion — the mixed path's
        dispatch count per decode token drops K×), 1 otherwise (the
        original per-token mixed step)."""
        return (self.ecfg.decode_block_size
                if self.ecfg.loop_to_completion else 1)

    def _get_mixed_fn(self) -> Callable:
        if self._mixed_fn is None:
            self._event("retrace")
            self._mixed_fn = self._build_mixed_step()
        return self._mixed_fn

    def _build_mixed_step(self) -> Callable:
        """Compile the ragged mixed step: ONE program that (a) merges the
        host's slot overrides into the decode carry, (b) runs one packed
        ragged forward over [decode rows | prefill chunks] with KV
        writes staying single scatters on the carried pools (the
        pool-carry scan contract, docs/PERF.md), (c) samples on-device
        ONLY the rows that produced a next token — every active decode
        row plus each prefill row's chunk-final position — and (d)
        advances the decode carry one token with the block path's exact
        EOS/budget freeze law. The host sees [1, B] decode ids (the same
        pending-block framing as the K-step path) plus [Bp] first-token
        candidates it reaps only for prompts that completed.

        Under ``loop_to_completion`` the mixed step runs in K-BLOCK form
        (kernel looping, docs/PERF.md): after the packed ragged forward,
        K-1 additional plain decode steps (the fixed block's exact
        one_step math) advance the decode carry inside the SAME program,
        so the mixed path pays one dispatch per K decode tokens instead
        of one per token. The host sees [K, B] ids on the same pending
        frame; prefill chunks still land once per dispatch."""
        cfg = self.cfg
        impl = self._resolved_mixed_impl()
        ps = self.pcfg.page_size
        S = self.ecfg.mixed_step_tokens
        B = self.ecfg.max_batch
        Bp = min(self.ecfg.prefill_batch, S - B)
        K = self._mixed_block_k()
        num_slots = self._num_slots_flat
        moe_impl = self._moe_impl()
        impl_blk = self._resolved_impl()
        fwd = self._fwd
        mesh = self.mesh
        eos = jnp.asarray(sorted(self.tok.eos_ids), jnp.int32)

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4, 5, 6, 10))
        def mixed(params, pool_k, pool_v, tokens, positions, steps_left,
                  active, block_tables, temp, top_p, rng,
                  set_mask, set_active, set_tokens, set_positions,
                  set_steps, p_ids, p_pos, p_row, p_write, p_valid,
                  p_last, p_temp, p_topp, sample_mode):
            # merge host overrides (admissions / deactivations) into carry
            tokens = jnp.where(set_mask, set_tokens, tokens)
            positions = jnp.where(set_mask, set_positions, positions)
            steps_left = jnp.where(set_mask, set_steps, steps_left)
            active = jnp.where(set_mask, set_active, active)

            rows = jnp.arange(B, dtype=jnp.int32)
            page = block_tables[rows, positions // ps]
            d_write = jnp.where(
                active, page * ps + positions % ps, num_slots
            )
            # packed layout: decode slots 0..B-1 (their row ids ARE their
            # packed indices), prefill chunks back-to-back after them
            ids = jnp.concatenate([tokens, p_ids])
            pos = jnp.concatenate([positions, p_pos])
            tok_row = jnp.concatenate(
                [jnp.where(active, rows, -1), p_row]
            )
            write = jnp.concatenate([d_write, p_write])
            kv_valid = jnp.concatenate(
                [jnp.where(active, positions + 1, 0), p_valid]
            )
            offs = jnp.arange(block_tables.shape[1] * ps, dtype=jnp.int32)
            gather = block_tables[:, offs // ps] * ps + offs % ps
            logits, pool_k, pool_v = llama.ragged_paged_forward(
                params, cfg, ids[None], pos[None], pool_k, pool_v,
                write[None], tok_row, gather, kv_valid,
                attention_impl=impl, page_size=ps, moe_impl=moe_impl,
                mesh=mesh,
                logits_idx=jnp.concatenate([rows, p_last]),
            )  # [B + Bp, V]
            rng, sub = jax.random.split(rng)
            all_temp = jnp.concatenate([temp, p_temp])
            all_topp = jnp.concatenate([top_p, p_topp])
            # same 3-way runtime sampler switch as the decode block
            nxt = lax.switch(
                sample_mode,
                [
                    lambda a: jnp.argmax(a[1], -1).astype(jnp.int32),
                    lambda a: sample_tokens(a[0], a[1], a[2], a[3],
                                            use_topp=False),
                    lambda a: sample_tokens(a[0], a[1], a[2], a[3],
                                            use_topp=True),
                ],
                (sub, logits, all_temp, all_topp),
            )
            lp = _chosen_logprob(logits, nxt)
            d_next, p_next = nxt[:B], nxt[B:]
            d_lp, p_lp = lp[:B], lp[B:]
            out = jnp.where(active, d_next, -1)
            is_eos = (
                (d_next[:, None] == eos[None, :]).any(-1)
                if eos.size
                else jnp.zeros_like(active)
            )
            positions = jnp.where(active, positions + 1, positions)
            steps_left = jnp.where(active, steps_left - 1, steps_left)
            tokens = jnp.where(active, d_next, tokens)
            active = active & ~is_eos & (steps_left > 0)
            outs_all = out[None]
            lps_all = d_lp[None]
            if K > 1:
                # K-block fusion (loop_to_completion): K-1 extra plain
                # decode steps on the decode rows — the fixed block's
                # one_step verbatim, over the [:B] slice of the packed
                # tables — inside this same dispatch
                gather_d = gather[:B]

                def one_step(carry, _):
                    (tokens, positions, steps_left, active,
                     pool_k, pool_v, rng) = carry
                    page = block_tables[rows, positions // ps]
                    write = jnp.where(
                        active, page * ps + positions % ps, num_slots
                    )[:, None]
                    kv_valid = jnp.where(active, positions + 1, 0)
                    logits, pool_k, pool_v = fwd(
                        params, cfg, tokens[:, None], positions[:, None],
                        pool_k, pool_v, write, gather_d, kv_valid,
                        impl_blk, moe_impl,
                    )
                    rng, sub = jax.random.split(rng)
                    nxt2 = lax.switch(
                        sample_mode,
                        [
                            lambda a: jnp.argmax(a[1], -1).astype(
                                jnp.int32),
                            lambda a: sample_tokens(a[0], a[1], a[2],
                                                    a[3], use_topp=False),
                            lambda a: sample_tokens(a[0], a[1], a[2],
                                                    a[3], use_topp=True),
                        ],
                        (sub, logits[:, 0], temp, top_p),
                    )
                    lp2 = _chosen_logprob(logits[:, 0], nxt2)
                    out2 = jnp.where(active, nxt2, -1)
                    is_eos2 = (
                        (nxt2[:, None] == eos[None, :]).any(-1)
                        if eos.size
                        else jnp.zeros_like(active)
                    )
                    positions = jnp.where(active, positions + 1,
                                          positions)
                    steps_left = jnp.where(active, steps_left - 1,
                                           steps_left)
                    tokens = jnp.where(active, nxt2, tokens)
                    active = active & ~is_eos2 & (steps_left > 0)
                    return (tokens, positions, steps_left, active,
                            pool_k, pool_v, rng), (out2, lp2)

                carry, (outs_rest, lps_rest) = lax.scan(
                    one_step,
                    (tokens, positions, steps_left, active,
                     pool_k, pool_v, rng),
                    None, length=K - 1,
                )
                (tokens, positions, steps_left, active,
                 pool_k, pool_v, rng) = carry
                outs_all = jnp.concatenate([outs_all, outs_rest], 0)
                lps_all = jnp.concatenate([lps_all, lps_rest], 0)
            return (outs_all, lps_all, p_next, p_lp, tokens,
                    positions, steps_left, active, pool_k, pool_v, rng)

        return self._with_mesh(mixed)

    def _mixed_step(self, outputs: List[StepOutput]) -> bool:
        """Launch one ragged mixed dispatch: decode rows advance a single
        token from the carry (the [1, B] result rides the SAME pending-
        block pipeline as K-step blocks) while the prefill backlog packs
        chunks into the budget's remainder — no bucket padding, chunk
        lengths exactly what fits (PackInfer). Page pressure drains the
        pipeline then preempts, exactly like _maybe_launch."""
        sc_t0 = time.monotonic()  # step clock: host wall only
        sc_excl = 0.0  # drained-frame seconds (clocked by their frames)
        S = self.ecfg.mixed_step_tokens
        B = self.ecfg.max_batch
        Sp = S - B
        Bp = min(self.ecfg.prefill_batch, Sp)
        ps = self.pcfg.page_size
        P = self.pcfg.max_pages_per_seq
        K = self._mixed_block_k()

        def mid_prefill(s: _Seq) -> bool:
            return s.next_token is None and s.seq_len < len(s.token_ids)

        while True:
            decode_seated = [
                (i, s) for i, s in enumerate(self.slots)
                if s is not None and not mid_prefill(s)
            ]
            # sliding-window reclaim for every seated row, exactly like
            # _maybe_launch: a sustained prompt backlog keeps the engine
            # on the mixed path, which must not suspend the O(window)
            # KV bound
            for i, s in enumerate(self.slots):
                if s is not None:
                    self._reclaim_window_pages(s)
            # K-block fusion (loop_to_completion): each dispatch advances
            # up to K decode tokens per row; pages are pre-allocated for
            # the full advance (exact for active rows — plain steps emit
            # what they assume unless frozen, and frozen rows stop
            # writing)
            advs = {
                id(s): min(K, max(0, s.dev_steps_left))
                for _, s in decode_seated
            }
            try:
                for _, s in decode_seated:
                    self._ensure_block_pages(s, advs[id(s)])
                break
            except CacheFull:
                self._event("cache_full")
                if self._pending:
                    # drained frames clock their own processing —
                    # exclude it from this dispatch's window
                    drain_t0 = time.monotonic()
                    self._drain_pending(outputs)
                    sc_excl += time.monotonic() - drain_t0
                    continue
                if decode_seated:
                    self._preempt_youngest(outputs)
                    continue
                break  # prefill rows already hold their prompt pages

        # compose the prefill share: up to Bp mid-prefill rows packed
        # back-to-back under the (pressure-shrinkable) budget
        group = [
            (i, s) for i, s in enumerate(self.slots)
            if s is not None and mid_prefill(s)
        ][:Bp]
        budget = max(1, min(Sp, int(Sp * self._mixed_prefill_frac)))
        p_ids = np.zeros((Sp,), np.int32)
        p_pos = np.zeros((Sp,), np.int32)
        p_row = np.full((Sp,), -1, np.int32)
        p_write = np.full((Sp,), self._num_slots_flat, np.int32)
        p_valid = np.zeros((Bp,), np.int32)
        p_last = np.zeros((Bp,), np.int32)
        p_temp = np.ones((Bp,), np.float32)
        p_topp = np.ones((Bp,), np.float32)
        chunk_lens: List[int] = []
        off = 0
        for j, (_, s) in enumerate(group):
            start = s.seq_len
            t = min(len(s.token_ids) - start, budget - off)
            if t <= 0:
                chunk_lens.append(0)
                continue
            p_ids[off:off + t] = s.token_ids[start:start + t]
            p_pos[off:off + t] = np.arange(start, start + t, dtype=np.int32)
            flat = np.arange(start, start + t, dtype=np.int32)
            table = np.asarray(s.block_table, np.int32)
            p_write[off:off + t] = table[flat // ps] * ps + flat % ps
            p_row[off:off + t] = B + j
            p_valid[j] = start + t
            p_last[j] = B + off + t - 1
            p_temp[j] = s.params.temperature
            p_topp[j] = s.params.top_p
            chunk_lens.append(t)
            off += t

        for i, s in decode_seated:
            if self._bt_pages[i] != len(s.block_table):
                self._refresh_bt_row(i, s)
        tables = np.zeros((B + Bp, P), np.int32)
        tables[:B] = self._bt
        for j, (_, s) in enumerate(group):
            tb = s.block_table[:P]
            tables[B + j, :len(tb)] = tb

        injects = self._drain_slot_updates()
        tokens, positions, steps_left, active, rng = self._carry
        use_topp = any(
            s.params.top_p < 1.0 and s.params.temperature > 0.0
            for _, s in decode_seated + group
        )
        any_temp = any(
            s.params.temperature > 0.0 for _, s in decode_seated + group
        )
        sample_mode = 2 if use_topp else (1 if any_temp else 0)

        (outs, lps, p_toks, p_lps, tokens, positions, steps_left, active,
         self.state.k, self.state.v, rng) = self._get_mixed_fn()(
            self.params, self.state.k, self.state.v,
            tokens, positions, steps_left, active,
            jnp.asarray(tables), jnp.asarray(self._temp),
            jnp.asarray(self._topp), rng, *injects,
            jnp.asarray(p_ids), jnp.asarray(p_pos), jnp.asarray(p_row),
            jnp.asarray(p_write), jnp.asarray(p_valid),
            jnp.asarray(p_last), jnp.asarray(p_temp),
            jnp.asarray(p_topp), jnp.asarray(sample_mode, jnp.int32),
        )
        self._carry = (tokens, positions, steps_left, active, rng)
        snapshot = [(i, s) for i, s in decode_seated]
        self._pending.append(
            (outs, lps, None, None, None,
             [(i, s, advs[id(s)]) for i, s in snapshot], "mixed")
        )
        for _, s in decode_seated:
            adv = advs[id(s)]
            s.dev_pos += adv
            s.dev_steps_left -= adv

        prefill_tokens = sum(chunk_lens)
        decode_tokens = sum(advs.values())
        self._mixed_steps += 1
        self._mixed_prefill_tokens += prefill_tokens
        self._mixed_decode_tokens += decode_tokens
        self._mixed_density_sum += (prefill_tokens + decode_tokens) / S
        for j, (_, s) in enumerate(group):
            s.seq_len += chunk_lens[j]
        self._reap_mixed_prefill(group, chunk_lens, p_toks, p_lps, outputs)
        # step clock: packed tokens/rows counted at dispatch (the [1, B]
        # pending frame's reconcile adds its wall time under this kind
        # too, but never re-counts the tokens)
        self._clock("mixed",
                    max(0.0, time.monotonic() - sc_t0 - sc_excl),
                    tokens=prefill_tokens + decode_tokens,
                    rows=len(decode_seated)
                    + sum(1 for t in chunk_lens if t),
                    dispatches=1)
        return True

    def _reap_mixed_prefill(self, group, chunk_lens, p_toks, p_lps,
                            outputs: List[StepOutput]) -> None:
        """Emit first tokens for prompts the mixed dispatch COMPLETED and
        seat them for decode (or park them handoff-ready) — the mixed
        step's analogue of the quantum path's reap. The single
        np.asarray below is the block-boundary device read: nothing else
        here may touch the device (distlint DL007 polices this function
        exactly like the decode loop)."""
        toks_np = lps_np = None
        for j, (slot, s) in enumerate(group):
            if not chunk_lens[j] or s.seq_len < len(s.token_ids):
                continue  # mid-prompt chunk; later mixed steps finish it
            if self._by_id.get(s.request_id) is not s:
                continue  # aborted while the dispatch ran
            if toks_np is None:
                toks_np = np.asarray(p_toks)
                lps_np = np.asarray(p_lps)
            try:
                self._emit_token(s, int(toks_np[j]), outputs,
                                 float(lps_np[j]))
            except Exception as e:  # failure isolation (Property 22)
                self.slots[slot] = None
                self._by_id.pop(s.request_id, None)
                self._release_seq(s)
                outputs.append(StepOutput(
                    request_id=s.request_id, finished=True, error=str(e)))
                continue
            if self._by_id.get(s.request_id) is s:
                if s.prefill_only:
                    # disaggregated handoff point (same as the quantum
                    # path): pages held, serving layer exports the seq
                    self.slots[slot] = None
                    self._handoff_ready[s.request_id] = s
                else:
                    self._stage_seat(slot, s)

    # ------------------------------------------------------------------
    # context-parallel (ring attention) prefill — the long-prompt path
    # ------------------------------------------------------------------

    def _cp_threshold(self) -> Optional[int]:
        """Prompt length from which ring prefill over the ``seq`` mesh axis
        kicks in (VERDICT r1: long-context serving must be reachable from
        the engine, not a standalone demo). None = CP unavailable.

        CP x PP composition (VERDICT r4 #5): on a seq x stage mesh the
        RING path runs through ``parallel/cp.py:cp_pp_prefill`` — one
        partial-manual shard_map spanning BOTH axes with the GPipe tick
        loop inside and the per-shard ring body as the attend, so every
        device issues the seq- and stage-axis collectives in the same
        static order. (Nesting ring's own shard_map under the stage
        loop DEADLOCKED XLA's collective scheduling on the r4-window
        jax, and current jax rejects the nesting at trace time —
        tools/nested_shardmap_repro.py keeps the minimal repro.)
        Ulysses is seq-only: its all-to-all head scatter does not
        compose with the stage loop, so ulysses + stage falls back to
        the PP-capable batched CHUNKED prefill path (same O(T^2)
        attention FLOPs spread over the stage group; context bounded by
        the page pool, not one chip's dense-ring buffer). Tested
        end-to-end in tests/test_cp_engine.py and dryrun 'CP-PP'."""
        if self.mesh is None or self.mesh.shape.get("seq", 1) <= 1:
            return None
        if (
            self.mesh.shape.get("stage", 1) > 1
            and self.ecfg.sp_impl != "ring"
        ):
            return None  # chunked-prefill fallback (see docstring)
        if self.ecfg.cp_min_tokens is not None:
            return self.ecfg.cp_min_tokens
        return self.ecfg.prefill_buckets[-1] + 1

    def _cp_bucket(self, n: int) -> int:
        """Prompt-buffer bucket for ring prefill: power-of-two growth
        bounds recompiles; the buffer must divide by the seq-axis size.
        Clamped to the pool's max sequence length (seq-axis-rounded) so
        the dense ring K/V intermediate never overshoots the longest
        admissible prompt by ~2x."""
        seq_ax = self.mesh.shape.get("seq", 1)
        cap = -(-self.pcfg.max_seq_len // seq_ax) * seq_ax
        b = max(16, seq_ax)
        while b < n:
            b *= 2
        if b % seq_ax:  # non-power-of-two seq axis: exact multiple
            b = -(-n // seq_ax) * seq_ax
        return min(b, max(cap, -(-n // seq_ax) * seq_ax))

    def _get_cp_fn(self, T: int) -> Callable:
        """Compiled sequence-parallel prefill program keyed on the
        prompt-buffer length: cp_paged_prefill (ring or Ulysses attention
        over ``seq`` per EngineConfig.sp_impl, K/V scattered into the page
        pool) fused with first-token sampling. With a draft model, the
        draft's pool is prefilled in the same program (same slots) so
        speculative rounds can attend the full prompt."""
        fn = self._cp_fns.get(T)
        if fn is None:
            self._event("retrace")
            from distributed_inference_server_tpu.parallel.cp import (
                cp_paged_prefill_any,
            )

            cfg, mesh = self.cfg, self.mesh
            sp = self.ecfg.sp_impl
            if self.draft_params is not None:
                dcfg = self.draft_cfg

                @functools.partial(jax.jit, donate_argnums=(2, 3, 6, 7))
                def cp_spec(params, dparams, dpool_k, dpool_v, ids, valid,
                            pool_k, pool_v, write_slots, temp, top_p, rng):
                    logits, pool_k, pool_v = cp_paged_prefill_any(
                        params, cfg, mesh, ids, valid, pool_k, pool_v,
                        write_slots, sp_impl=sp,
                    )
                    _, dpool_k, dpool_v = cp_paged_prefill_any(
                        dparams, dcfg, mesh, ids, valid, dpool_k, dpool_v,
                        write_slots, sp_impl=sp,
                    )
                    toks = sample_tokens(rng, logits, temp, top_p)
                    return (toks, _chosen_logprob(logits, toks),
                            pool_k, pool_v, dpool_k, dpool_v)

                fn = self._cp_fns[T] = self._with_mesh(cp_spec)
            else:

                @functools.partial(jax.jit, donate_argnums=(3, 4))
                def cp(params, ids, valid, pool_k, pool_v, write_slots,
                       temp, top_p, rng):
                    logits, pool_k, pool_v = cp_paged_prefill_any(
                        params, cfg, mesh, ids, valid, pool_k, pool_v,
                        write_slots, sp_impl=sp,
                    )
                    toks = sample_tokens(rng, logits, temp, top_p)
                    return toks, _chosen_logprob(logits, toks), pool_k, pool_v

                fn = self._cp_fns[T] = self._with_mesh(cp)
        return fn

    def _cp_prefill_seq(self, slot: int, s: _Seq,
                        outputs: List[StepOutput]) -> None:
        """Prefill one long prompt via ring attention and seat it for
        decode. The whole prompt is recomputed from position 0 (ring
        attention runs full self-attention of the chunk; prefix-shared
        pages are rewritten with identical contents, which is safe — the
        K/V of a prefix depends only on the prefix)."""
        n = len(s.token_ids)
        T = self._cp_bucket(n)
        ids = np.zeros((1, T), np.int32)
        ids[0, :n] = s.token_ids
        positions = np.arange(T, dtype=np.int32)[None]
        write_slots = self._slots_for_positions(s.block_table, positions, n)
        fn = self._get_cp_fn(T)
        self._rng, sub = jax.random.split(self._rng)
        temp = np.array([s.params.temperature], np.float32)
        topp = np.array([s.params.top_p], np.float32)
        valid = np.array([n], np.int32)
        if self.draft_params is not None:
            (toks, lps, self.state.k, self.state.v,
             self.draft_state.k, self.draft_state.v) = fn(
                self.params, self.draft_params,
                self.draft_state.k, self.draft_state.v,
                jnp.asarray(ids), jnp.asarray(valid),
                self.state.k, self.state.v, jnp.asarray(write_slots),
                jnp.asarray(temp), jnp.asarray(topp), sub,
            )
        else:
            toks, lps, self.state.k, self.state.v = fn(
                self.params, jnp.asarray(ids), jnp.asarray(valid),
                self.state.k, self.state.v, jnp.asarray(write_slots),
                jnp.asarray(temp), jnp.asarray(topp), sub,
            )
        s.seq_len = n
        self._emit_token(s, int(np.asarray(toks)[0]), outputs,
                         float(np.asarray(lps)[0]))
        if self._by_id.get(s.request_id) is s:
            if s.prefill_only:
                self.slots[slot] = None
                self._handoff_ready[s.request_id] = s
            else:
                self._stage_seat(slot, s)

    def _with_mesh(self, fn: Callable) -> Callable:
        """Run a jitted step inside the mesh context (PartitionSpec-based
        sharding constraints, e.g. the MoE all-to-all boundary, need it)."""
        if self.mesh is None:
            return fn
        mesh = self.mesh

        def wrapped(*args):
            with mesh:
                return fn(*args)

        return wrapped

    def _make_fwd(self) -> Callable:
        """Central paged-forward router for every compiled program (decode
        blocks, speculative rounds, prefill chunks): single-device / TP
        execution via ``llama.paged_forward``, or the stage-axis pipeline
        (``parallel/pp.py:pp_paged_forward``) when the mesh has one — the
        70B TP x PP serving path over the SAME paged pool and host
        machinery."""
        mesh = self.mesh
        ps = self.pcfg.page_size
        if mesh is not None and mesh.shape.get("stage", 1) > 1:
            from distributed_inference_server_tpu.parallel.pp import (
                pp_paged_forward,
            )

            M = self.ecfg.pp_microbatches

            def fwd(params, cfg, ids, positions, pk, pv, ws, gs, kvv,
                    impl, moe_impl, logits_idx=None):
                return pp_paged_forward(
                    mesh, params, cfg, ids, positions, pk, pv, ws, gs,
                    kvv, num_microbatches=M, page_size=ps,
                    logits_idx=logits_idx,
                )

            return fwd

        def fwd(params, cfg, ids, positions, pk, pv, ws, gs, kvv, impl,
                moe_impl, logits_idx=None):
            return llama.paged_forward(
                params, cfg, ids, positions, pk, pv, ws, gs, kvv,
                attention_impl=impl, page_size=ps, moe_impl=moe_impl,
                mesh=mesh, logits_idx=logits_idx,
            )

        return fwd

    def _moe_impl(self) -> str:
        """MoE execution path: capacity-based EP dispatch (ops/moe.py) when
        an expert mesh axis exists — the Mixtral-scale path; dense-compute
        otherwise (exact, no capacity drops — right for single-device
        test-scale models, where the E/k FLOP overhead is irrelevant)."""
        if (
            self.cfg.is_moe
            and self.mesh is not None
            and self.mesh.shape.get("expert", 1) > 1
        ):
            return "ep"
        return "dense"

    def _resolved_impl(self):
        """The decode/prefill attention implementation after "auto"
        resolution: a ``(decode_impl, prefill_impl)`` pair consumed by
        ``llama.paged_forward`` per call site — the Pallas paged-attention
        kernels on TPU when they compile for this model's geometry, the
        XLA gather path otherwise.

        Mosaic's tiling/alignment rules vary with head_dim, head counts,
        and toolchain version, so "auto" PROBES each kernel with an AOT
        compile at this engine's real per-shard shapes the first time it
        resolves (cached; the persistent XLA compile cache makes repeats
        cheap). A rejected kernel downgrades to the XLA path with a
        warning instead of poisoning every serving program (round-1
        verdict: "auto" must never ship a slower-or-broken path) — and
        independently per kernel, so a prefill-only rejection keeps the
        decode hot loop on Pallas."""
        impl = self.ecfg.attention_impl
        if self.ecfg.kv_quant != "none":
            # quantized pools serve on the XLA gather path, EXCEPT the
            # experimental opt-in: with attention_impl='auto' (an
            # explicit 'xla' pin always wins),
            # DIS_TPU_KV_QUANT_PALLAS=1 lets the auto probe judge the
            # int8-pool decode kernel with QuantPool-shaped pools —
            # including under a tensor axis, where shard_pallas_attend
            # carries per-leaf QuantPool specs (codes on KV heads,
            # scales alongside). Prefill stays XLA either way — no int8
            # prefill kernel. Explicit 'pallas' was rejected at
            # construction.
            if impl == "auto" and self._kv_quant_pallas:
                if self._auto_impl is None:
                    if jax.default_backend() != "tpu":
                        self._auto_impl = ("xla", "xla")
                    else:
                        ok_decode, _ = self._probe_pallas()
                        self._auto_impl = (
                            "pallas" if ok_decode else "xla", "xla"
                        )
                return self._auto_impl
            return "xla"
        if impl != "auto":
            return impl
        if self._auto_impl is None:
            if jax.default_backend() != "tpu":
                self._auto_impl = ("xla", "xla")
            else:
                # each kernel serves where Mosaic accepts it. Prefill is
                # not optional at serving widths: the XLA form gathers a
                # dense [B, S_max] window and materializes f32 scores
                # [B, H, T, S_max] — 2 x 8 GB at the default geometry
                # (16 rows x 512 tokens x 8192 slots), which a 16 GB chip
                # cannot compile — while the kernel reads only the pages
                # a query tile can causally see.
                ok_decode, ok_prefill = self._probe_pallas()
                self._auto_impl = (
                    "pallas" if ok_decode else "xla",
                    "pallas" if ok_prefill else "xla",
                )
        return self._auto_impl

    def placement(self) -> Dict[str, object]:
        """Where this replica lives and what serves its attention: the
        devices holding its KV pool (with the bytes each has in use,
        where the backend reports them), the resolved (decode, prefill)
        attention pair, and Mosaic's first line for any kernel the
        "auto" probe rejected. ``/health`` reports it per engine. Reads
        cached state only — the pair was resolved at construction."""
        k = self.state.k
        # .sharding, not .devices(): the pool is donated into every step,
        # and a status read from another thread may catch the stale handle
        devs = sorted(
            (k.data if isinstance(k, QuantPool) else k).sharding.device_set,
            key=lambda d: d.id,
        )
        impl = self._resolved_impl()
        decode, prefill = (impl, impl) if isinstance(impl, str) else impl
        return {
            "device_ids": [d.id for d in devs],
            "device_bytes_in_use": [
                (d.memory_stats() or {}).get("bytes_in_use") for d in devs
            ],
            "attention": {"decode": decode, "prefill": prefill},
            "attention_rejected": dict(self._probe_rejected),
        }

    def _probe_pallas(self) -> Tuple[bool, bool]:
        """AOT-compile the Pallas paged-attention kernels (decode, chunked
        prefill) at every geometry this engine will actually launch them
        at — target AND draft model head shapes, every prefill bucket,
        and the speculative verify width (gamma+1) — returning per-kernel
        success. Runs on the real backend so Mosaic itself is the judge;
        one never-probed shape crashing at first launch is exactly the
        failure mode this probe exists to prevent. The probed callables
        come from ``llama.make_pallas_attend`` — the same builder the
        serving path launches — so probe and serving cannot drift."""
        from distributed_inference_server_tpu.models.llama import (
            make_pallas_attend,
            shard_pallas_attend,
        )

        pcfg = self.pcfg
        tp = self.mesh.shape.get("tensor", 1) if self.mesh is not None else 1
        dp = self.mesh.shape.get("data", 1) if self.mesh is not None else 1
        Bd = max(1, self.ecfg.max_batch // dp)  # decode / spec-verify rows
        Bp = max(1, self.ecfg.prefill_batch // dp)  # batched-prefill rows
        P = pcfg.max_pages_per_seq
        slots = pcfg.num_pages * pcfg.page_size
        # per-geometry (rows, chunk width) prefill-kernel launch sites:
        # bucketed admission chunks run for BOTH models (the draft
        # prefills the same chunks into its own pool), but the gamma+1
        # speculative verify forward exists only for the TARGET — probing
        # a never-launched draft shape could spuriously demote everything
        buckets = [
            (Bp, T) for T in sorted(set(self.ecfg.prefill_buckets))
        ]
        geometries = [(self.cfg, list(buckets))]
        if self.draft_cfg is not None:
            geometries[0][1].append((Bd, self.spec.num_draft_tokens + 1))
            geometries.append((self.draft_cfg, list(buckets)))

        def try_compile(name, lower_thunk):
            # the thunk runs BOTH lowering and compile inside the try:
            # Mosaic rejects misaligned kernels at lowering time too
            try:
                lower_thunk().compile()
                return True
            except Exception as e:  # Mosaic rejection or backend failure
                first = str(e).split("\n")[0]
                self._probe_rejected[name] = first
                logger.warning(
                    "Pallas %s kernel unavailable for this geometry "
                    "(auto -> xla gather path): %s", name, first,
                )
                return False

        def tv(B):
            return (
                jax.ShapeDtypeStruct((B, P), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32),
            )

        # Under a tensor mesh the serving path launches the kernels INSIDE
        # shard_map (llama.shard_pallas_attend) — probe that exact program
        # at global shapes rather than the standalone per-shard lowering,
        # whose Mosaic acceptance could in principle diverge (ADVICE r2).
        sm = self.mesh is not None and tp > 1

        ok_decode = ok_prefill = True
        for cfg, launches in geometries:
            softcap = cfg.attn_logit_softcap or 0.0
            if sm:  # global shapes: shard_map's specs do the splitting
                kv, heads = cfg.num_kv_heads, cfg.num_heads
            else:
                kv = max(1, cfg.num_kv_heads // tp)
                heads = max(1, cfg.num_heads // tp)
            pool = jax.ShapeDtypeStruct(
                (slots, kv, cfg.head_dim), self.dtype
            )
            if self.ecfg.kv_quant == "int8":
                # probe with QuantPool-shaped pools so Mosaic judges the
                # int8 kernel variant serving would launch; the prefill
                # lowering raises (no int8 prefill kernel) and resolves
                # to the XLA path via the same try_compile catch
                pool = QuantPool(
                    jax.ShapeDtypeStruct(
                        (slots, kv, cfg.head_dim), jnp.int8
                    ),
                    jax.ShapeDtypeStruct((slots, kv), jnp.float32),
                )

            def lower_kernel(decode_step, q_shape, B):
                tables, valid = tv(B)
                q = jax.ShapeDtypeStruct(q_shape, self.dtype)
                w = jax.ShapeDtypeStruct((), jnp.int32)
                fn = make_pallas_attend(
                    pcfg.page_size, softcap, decode_step, interpret=False
                )
                if sm:
                    fn = shard_pallas_attend(
                        fn, self.mesh, decode_step,
                        kv_quantized=self.ecfg.kv_quant == "int8",
                    )
                if decode_step:
                    return jax.jit(fn).lower(q, pool, pool, tables, valid, w)
                # q_start shares kv_valid_len's [B] i32 shape
                return jax.jit(fn).lower(
                    q, pool, pool, tables, valid, valid, w
                )

            Bd_g = Bd * dp if sm else Bd
            ok_decode = ok_decode and try_compile(
                "paged-decode",
                lambda: lower_kernel(True, (Bd_g, heads, cfg.head_dim), Bd_g),
            )
            for B, T in launches:
                B_g = B * dp if sm else B
                ok_prefill = ok_prefill and try_compile(
                    "chunked-prefill",
                    lambda: lower_kernel(
                        False, (B_g, T, heads, cfg.head_dim), B_g
                    ),
                )
                if not ok_prefill:
                    break
        return ok_decode, ok_prefill

    def _get_prefill_fn(self, batch: int, bucket: int) -> Callable:
        """Compiled batched-prefill chunk program keyed on (rows, bucket):
        one paged forward over [batch, bucket] new tokens with per-row
        positions/write-slots, plus fused first-token sampling at each
        row's last valid index. Chunk positions are contiguous per row, so
        the Pallas chunked-prefill kernel applies when selected."""
        key = (batch, bucket)
        fn = self._prefill_fns.get(key)
        if fn is None:
            self._event("retrace")
            cfg = self.cfg
            moe_impl = self._moe_impl()
            impl = self._resolved_impl()
            fwd = self._fwd

            if self.draft_params is not None:
                dcfg = self.draft_cfg

                @functools.partial(jax.jit, donate_argnums=(2, 3, 6, 7))
                def prefill_spec(params, dparams, dpool_k, dpool_v, ids,
                                 positions, pool_k, pool_v, write_slots,
                                 gather_slots, kv_valid_len, last_idx,
                                 temp, top_p, rng):
                    logits, k, v = fwd(
                        params, cfg, ids, positions, pool_k, pool_v,
                        write_slots, gather_slots, kv_valid_len,
                        impl, moe_impl, logits_idx=last_idx,
                    )
                    # draft logits are never read (only dk/dv are kept);
                    # logits_idx shrinks its unembed to one position
                    # rather than trusting XLA DCE to drop the [B, T, V]
                    # projection
                    _, dk, dv = fwd(
                        dparams, dcfg, ids, positions, dpool_k, dpool_v,
                        write_slots, gather_slots, kv_valid_len,
                        impl, "dense", logits_idx=last_idx,
                    )
                    last = logits[:, 0]
                    toks = sample_tokens(rng, last, temp, top_p)
                    return toks, _chosen_logprob(last, toks), k, v, dk, dv

                fn = self._prefill_fns[key] = self._with_mesh(prefill_spec)
                return fn

            @functools.partial(jax.jit, donate_argnums=(3, 4))
            def prefill(params, ids, positions, pool_k, pool_v, write_slots,
                        gather_slots, kv_valid_len, last_idx, temp, top_p,
                        rng):
                logits, k, v = fwd(
                    params, cfg, ids, positions, pool_k, pool_v,
                    write_slots, gather_slots, kv_valid_len, impl, moe_impl,
                    logits_idx=last_idx,
                )
                last = logits[:, 0]
                toks = sample_tokens(rng, last, temp, top_p)
                return toks, _chosen_logprob(last, toks), k, v

            fn = self._prefill_fns[key] = self._with_mesh(prefill)
        return fn

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------

    def _build_decode_block(self) -> Callable:
        """Compile the K-step decode block.

        The whole continuous-batching decode inner loop lives on device: a
        ``lax.scan`` of K model steps with on-device sampling, EOS masking,
        per-row length budgets, and block-table slot arithmetic. The host
        contributes only tiny async uploads (block tables, sampling params,
        admission injections) and one token download of [K, B] ids per
        block — the r1 design's per-step blocking ``np.asarray`` (measured
        at 72-107 ms/step of pure host sync on the real chip) is gone."""
        cfg = self.cfg
        impl = self.ecfg.attention_impl
        if impl not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"attention_impl must be 'auto', 'pallas' or 'xla', "
                f"got {impl!r}"
            )
        if self.ecfg.decode_block_size < 1:
            raise ValueError(
                f"decode_block_size must be >= 1, got "
                f"{self.ecfg.decode_block_size}"
            )
        if self.ecfg.pipeline_depth < 0:
            raise ValueError(
                f"pipeline_depth must be >= 0, got "
                f"{self.ecfg.pipeline_depth}"
            )
        impl = self._resolved_impl()
        ps = self.pcfg.page_size
        K = self.ecfg.decode_block_size
        num_slots = self._num_slots_flat
        moe_impl = self._moe_impl()
        fwd = self._fwd
        eos = jnp.asarray(sorted(self.tok.eos_ids), jnp.int32)

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4, 5, 6, 10))
        def block(params, pool_k, pool_v, tokens, positions, steps_left,
                  active, block_tables, temp, top_p, rng,
                  set_mask, set_active, set_tokens, set_positions, set_steps,
                  sample_mode):
            # merge host overrides (admissions / deactivations) into carry
            tokens = jnp.where(set_mask, set_tokens, tokens)
            positions = jnp.where(set_mask, set_positions, positions)
            steps_left = jnp.where(set_mask, set_steps, steps_left)
            active = jnp.where(set_mask, set_active, active)

            # gather rows from the block tables — tables are frozen for
            # the duration of the block (pages pre-allocated at launch).
            # The width comes from the UPLOADED table shape: the launcher
            # slices to the live bucket, and jit specializes per bucket.
            offs = jnp.arange(block_tables.shape[1] * ps, dtype=jnp.int32)
            gather = block_tables[:, offs // ps] * ps + offs % ps
            rows = jnp.arange(block_tables.shape[0])

            def one_step(carry, _):
                tokens, positions, steps_left, active, pool_k, pool_v, rng = carry
                page = block_tables[rows, positions // ps]
                write = jnp.where(
                    active, page * ps + positions % ps, num_slots
                )[:, None]
                kv_valid = jnp.where(active, positions + 1, 0)
                logits, pool_k, pool_v = fwd(
                    params, cfg, tokens[:, None], positions[:, None],
                    pool_k, pool_v, write, gather, kv_valid, impl, moe_impl,
                )
                rng, sub = jax.random.split(rng)
                # runtime 3-way branch, not static variants: one compiled
                # program per gather bucket (warmup coverage unchanged),
                # with the launcher picking the cheapest sampler the
                # seated mix needs. XLA lowers lax.switch on a scalar to
                # real control flow on TPU, so only the taken branch
                # executes:
                #   0 all-greedy (the bench path): pure argmax — no
                #     nucleus passes AND no [B, V] Gumbel noise, which
                #     the temperature>0 select cannot DCE away since
                #     temperature is a runtime tensor;
                #   1 sampled, all top_p==1: categorical without the
                #     nucleus softmax + threshold search;
                #   2 nucleus rows present: the full machinery.
                nxt = lax.switch(
                    sample_mode,
                    [
                        lambda a: jnp.argmax(a[1], -1).astype(jnp.int32),
                        lambda a: sample_tokens(a[0], a[1], a[2], a[3],
                                                use_topp=False),
                        lambda a: sample_tokens(a[0], a[1], a[2], a[3],
                                                use_topp=True),
                    ],
                    (sub, logits[:, 0], temp, top_p),
                )
                lp = _chosen_logprob(logits[:, 0], nxt)
                out = jnp.where(active, nxt, -1)
                is_eos = (
                    (nxt[:, None] == eos[None, :]).any(-1)
                    if eos.size
                    else jnp.zeros_like(active)
                )
                positions = jnp.where(active, positions + 1, positions)
                steps_left = jnp.where(active, steps_left - 1, steps_left)
                tokens = jnp.where(active, nxt, tokens)
                active = active & ~is_eos & (steps_left > 0)
                return (tokens, positions, steps_left, active,
                        pool_k, pool_v, rng), (out, lp)

            carry, (outs, lps) = lax.scan(
                one_step,
                (tokens, positions, steps_left, active, pool_k, pool_v, rng),
                None, length=K,
            )
            tokens, positions, steps_left, active, pool_k, pool_v, rng = carry
            return (outs, lps, tokens, positions, steps_left, active,
                    pool_k, pool_v, rng)

        return self._with_mesh(block)

    # ------------------------------------------------------------------
    # run-to-completion looped blocks (EngineConfig.loop_to_completion;
    # Kernel Looping, docs/PERF.md)
    # ------------------------------------------------------------------

    def _get_loop_fn(self, cap: int) -> Callable:
        fn = self._loop_fns.get(cap)
        if fn is None:
            self._event("retrace")
            fn = self._build_loop_block(cap)
            self._loop_fns[cap] = fn
        return fn

    def _get_spec_loop_fn(self, use_topp: bool, cap: int) -> Callable:
        fn = self._spec_loop_fns.get((use_topp, cap))
        if fn is None:
            self._event("retrace")
            fn = self._build_spec_loop_block(use_topp, cap)
            self._spec_loop_fns[(use_topp, cap)] = fn
        return fn

    def _build_loop_block(self, cap: int) -> Callable:
        """Compile the run-to-completion decode block: a ``lax.while_loop``
        whose body is EXACTLY the fixed-K block's per-step math (same
        gather/write/kv_valid arithmetic, same sampler switch, same
        ``active & ~is_eos & (steps_left > 0)`` freeze law — greedy
        tokens are bit-identical, tests/test_engine_loop.py), prefixed
        by an on-device page append: rows whose next write crosses a
        page boundary take the next page off the device-held free list
        and grow their block table inside the loop, so no host-chosen K
        bounds the run. The loop exits when every row froze (EOS /
        budget / free-list exhaustion) or after ``cap`` iterations; a
        per-row exit code (1=eos 2=budget 3=pages 4=cap) and the final
        tables come back for host reconcile. Output buffers are
        preallocated [cap, B] with the fixed path's -1 freeze sentinel."""
        cfg = self.cfg
        impl = self._resolved_impl()
        ps = self.pcfg.page_size
        num_slots = self._num_slots_flat
        moe_impl = self._moe_impl()
        fwd = self._fwd
        eos = jnp.asarray(sorted(self.tok.eos_ids), jnp.int32)

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4, 5, 6, 10))
        def loop_block(params, pool_k, pool_v, tokens, positions,
                       steps_left, active, block_tables, temp, top_p, rng,
                       set_mask, set_active, set_tokens, set_positions,
                       set_steps, bt_counts, free_pages, n_free,
                       sample_mode):
            # merge host overrides (admissions / deactivations) into carry
            tokens = jnp.where(set_mask, set_tokens, tokens)
            positions = jnp.where(set_mask, set_positions, positions)
            steps_left = jnp.where(set_mask, set_steps, steps_left)
            active = jnp.where(set_mask, set_active, active)

            B = tokens.shape[0]
            rows = jnp.arange(B)
            offs = jnp.arange(block_tables.shape[1] * ps, dtype=jnp.int32)

            def cond(st):
                return (st[0] < cap) & st[4].any()

            def body(st):
                (k, tokens, positions, steps_left, active, block_tables,
                 bt_counts, free_used, exit_code, outs, lps_buf,
                 pool_k, pool_v, rng) = st
                # --- on-device page append: a row whose write position
                # entered an unallocated page takes the next free-list
                # page; rows the list cannot cover freeze (reason 3) ---
                needed = jnp.where(active, positions // ps + 1, 0)
                (block_tables, bt_counts, free_used,
                 starved) = _device_append_pages(
                    block_tables, bt_counts, free_pages, n_free,
                    free_used, needed, rows, 1,
                )
                exit_code = jnp.where(
                    starved & (exit_code == 0), 3, exit_code
                )
                active = active & ~starved

                # --- one decode step: the fixed block's exact math
                # (gather recomputed per iteration because the tables
                # grow; entries past kv_valid are never attended, so
                # the numerics match the fixed path bit-for-bit) ---
                gather = block_tables[:, offs // ps] * ps + offs % ps
                page = block_tables[rows, positions // ps]
                write = jnp.where(
                    active, page * ps + positions % ps, num_slots
                )[:, None]
                kv_valid = jnp.where(active, positions + 1, 0)
                logits, pool_k, pool_v = fwd(
                    params, cfg, tokens[:, None], positions[:, None],
                    pool_k, pool_v, write, gather, kv_valid, impl,
                    moe_impl,
                )
                rng, sub = jax.random.split(rng)
                nxt = lax.switch(
                    sample_mode,
                    [
                        lambda a: jnp.argmax(a[1], -1).astype(jnp.int32),
                        lambda a: sample_tokens(a[0], a[1], a[2], a[3],
                                                use_topp=False),
                        lambda a: sample_tokens(a[0], a[1], a[2], a[3],
                                                use_topp=True),
                    ],
                    (sub, logits[:, 0], temp, top_p),
                )
                lp = _chosen_logprob(logits[:, 0], nxt)
                out = jnp.where(active, nxt, -1)
                is_eos = (
                    (nxt[:, None] == eos[None, :]).any(-1)
                    if eos.size
                    else jnp.zeros_like(active)
                )
                positions = jnp.where(active, positions + 1, positions)
                steps_left = jnp.where(active, steps_left - 1, steps_left)
                tokens = jnp.where(active, nxt, tokens)
                was_active = active
                active = active & ~is_eos & (steps_left > 0)
                froze = was_active & ~active
                exit_code = jnp.where(
                    froze & is_eos & (exit_code == 0), 1, exit_code
                )
                exit_code = jnp.where(
                    froze & ~is_eos & (exit_code == 0), 2, exit_code
                )
                outs = lax.dynamic_update_index_in_dim(outs, out, k, 0)
                lps_buf = lax.dynamic_update_index_in_dim(lps_buf, lp, k, 0)
                return (k + 1, tokens, positions, steps_left, active,
                        block_tables, bt_counts, free_used, exit_code,
                        outs, lps_buf, pool_k, pool_v, rng)

            st = lax.while_loop(cond, body, (
                jnp.asarray(0, jnp.int32), tokens, positions, steps_left,
                active, block_tables, bt_counts,
                jnp.asarray(0, jnp.int32), jnp.zeros((B,), jnp.int32),
                jnp.full((cap, B), -1, jnp.int32),
                jnp.zeros((cap, B), jnp.float32),
                pool_k, pool_v, rng,
            ))
            (n_steps, tokens, positions, steps_left, active, block_tables,
             bt_counts, free_used, exit_code, outs, lps_buf,
             pool_k, pool_v, rng) = st
            exit_code = jnp.where(active & (exit_code == 0), 4, exit_code)
            return (outs, lps_buf, exit_code, n_steps, block_tables,
                    bt_counts, tokens, positions, steps_left, active,
                    pool_k, pool_v, rng)

        return self._with_mesh(loop_block)

    def _build_spec_loop_block(self, use_topp: bool, cap: int) -> Callable:
        """Compile the speculative run-to-completion block: draft+verify
        rounds (the fixed spec block's exact round body — draft gamma
        proposals, ONE gamma+1 verify forward, shared rejection
        sampling) inside a ``lax.while_loop``, with the same on-device
        page append as the plain loop block growing each row's table to
        cover the round's gamma+1 writes before they happen. One
        compiled program replaces the fixed path's two-dispatches-per-
        round; ``cap`` device steps round up to ceil(cap / (gamma+1))
        rounds. Greedy rows stay bit-identical to plain decoding (the
        accept law is exact-match and key-independent under argmax)."""
        cfg, dcfg = self.cfg, self.draft_cfg
        impl = self._resolved_impl()
        ps = self.pcfg.page_size
        gamma = self.spec.num_draft_tokens
        W = gamma + 1
        rounds = max(1, -(-cap // W))
        # pages one round can demand beyond a row's table: its W writes
        # span at most W//ps + 1 pages, +1 covers a mid-page start
        sub_rounds = W // ps + 2
        smax = self._smax
        num_slots = self._num_slots_flat
        moe_impl = self._moe_impl()
        fwd = self._fwd
        eos = jnp.asarray(sorted(self.tok.eos_ids), jnp.int32)

        @functools.partial(
            jax.jit, donate_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 14)
        )
        def loop_block(params, dparams, pool_k, pool_v, dpool_k, dpool_v,
                       tokens, positions, steps_left, active, block_tables,
                       temp, top_p, spec_ok, rng,
                       set_mask, set_active, set_tokens, set_positions,
                       set_steps, bt_counts, free_pages, n_free, any_temp):
            tokens = jnp.where(set_mask, set_tokens, tokens)
            positions = jnp.where(set_mask, set_positions, positions)
            steps_left = jnp.where(set_mask, set_steps, steps_left)
            active = jnp.where(set_mask, set_active, active)

            B = tokens.shape[0]
            rows = jnp.arange(B)
            offs = jnp.arange(block_tables.shape[1] * ps, dtype=jnp.int32)
            max_pages = block_tables.shape[1]

            def cond(st):
                return (st[0] < rounds) & st[4].any()

            def body(st):
                (k, tokens, positions, steps_left, active, block_tables,
                 bt_counts, free_used, exit_code, toks_buf, lps_buf,
                 counts_buf, acc_buf, prop_buf,
                 pool_k, pool_v, dpool_k, dpool_v, rng) = st
                # --- page append covering this round's W writes ---
                last_pos = jnp.minimum(positions + W - 1, smax - 1)
                needed = jnp.where(active, last_pos // ps + 1, 0)
                (block_tables, bt_counts, free_used,
                 starved) = _device_append_pages(
                    block_tables, bt_counts, free_pages, n_free,
                    free_used, needed, rows, sub_rounds,
                )
                exit_code = jnp.where(
                    starved & (exit_code == 0), 3, exit_code
                )
                active = active & ~starved

                gather = block_tables[:, offs // ps] * ps + offs % ps

                def flat_slot(pos):
                    page = block_tables[
                        rows, jnp.minimum(pos // ps, max_pages - 1)
                    ]
                    return page * ps + pos % ps

                rng, sub = jax.random.split(rng)
                keys = jax.random.split(sub, gamma + 3)

                def dstep(c, key):
                    dpk, dpv, tok, pos = c
                    ok = active & (pos < smax)
                    write = jnp.where(
                        ok, flat_slot(pos), num_slots
                    )[:, None]
                    kv_valid = jnp.where(active, pos + 1, 0)
                    logits, dpk, dpv = fwd(
                        dparams, dcfg, tok[:, None], pos[:, None],
                        dpk, dpv, write, gather, kv_valid, impl, "dense",
                    )
                    q = spec_probs(logits[:, 0], temp)
                    if use_topp:
                        q = spec_nucleus(q, top_p)
                    nxt = lax.cond(
                        any_temp,
                        lambda a: jax.random.categorical(
                            a[0], jnp.log(a[1] + 1e-30), axis=-1
                        ).astype(jnp.int32),
                        lambda a: jnp.argmax(a[1], -1).astype(jnp.int32),
                        (key, q),
                    )
                    return (dpk, dpv, nxt, pos + 1), (nxt, q)

                (dpool_k, dpool_v, _, _), (dtoks, dqs) = lax.scan(
                    dstep, (dpool_k, dpool_v, tokens, positions),
                    keys[: gamma + 1],
                )
                dtoks = dtoks.T[:, :gamma]
                dqs = jnp.moveaxis(dqs, 0, 1)[:, :gamma]

                ver_tokens = jnp.concatenate([tokens[:, None], dtoks], 1)
                ver_pos = positions[:, None] + jnp.arange(W)[None]
                ok = active[:, None] & (ver_pos < smax)
                vpage = block_tables[
                    rows[:, None],
                    jnp.minimum(ver_pos // ps, max_pages - 1),
                ]
                write = jnp.where(ok, vpage * ps + ver_pos % ps, num_slots)
                kv_valid = jnp.where(active, positions + W, 0)
                logits, pool_k, pool_v = fwd(
                    params, cfg, ver_tokens, ver_pos, pool_k, pool_v,
                    write, gather, kv_valid, impl, moe_impl,
                )
                tps = spec_probs(logits, temp[:, None])
                x32 = logits.astype(jnp.float32)
                lse = jax.scipy.special.logsumexp(x32, axis=-1)

                toks_out, num_accepted = spec_accept_resample(
                    tps, dtoks, dqs, keys[gamma + 1], keys[gamma + 2],
                    spec_ok=spec_ok,
                    top_p=top_p if use_topp else None,
                    greedy_only=~any_temp,
                )
                idx = jnp.arange(W)[None]
                base = num_accepted + 1
                is_eos = (
                    (toks_out[..., None] == eos[None, None, :]).any(-1)
                    if eos.size
                    else jnp.zeros(toks_out.shape, bool)
                ) & (idx < base[:, None])
                has_eos = is_eos.any(-1)
                first_eos = jnp.argmax(is_eos, axis=-1)
                emitted = jnp.where(
                    has_eos, jnp.minimum(base, first_eos + 1), base
                )
                emitted = jnp.where(active, emitted, 0)
                acc_out = jnp.where(active & spec_ok, num_accepted, 0)
                prop_out = jnp.where(active & spec_ok, gamma, 0)
                toks_out = jnp.where(
                    (idx < emitted[:, None]) & active[:, None],
                    toks_out, -1,
                )
                lp_out = jnp.take_along_axis(
                    x32, jnp.maximum(toks_out, 0)[..., None], axis=-1
                )[..., 0] - lse
                new_last = toks_out[rows, jnp.maximum(emitted, 1) - 1]
                tokens = jnp.where(
                    active & (emitted > 0), new_last, tokens
                )
                positions = positions + emitted
                steps_left = steps_left - emitted
                was_active = active
                active = active & ~has_eos & (steps_left > 0)
                froze = was_active & ~active
                exit_code = jnp.where(
                    froze & has_eos & (exit_code == 0), 1, exit_code
                )
                exit_code = jnp.where(
                    froze & ~has_eos & (exit_code == 0), 2, exit_code
                )
                toks_buf = lax.dynamic_update_index_in_dim(
                    toks_buf, toks_out, k, 0)
                lps_buf = lax.dynamic_update_index_in_dim(
                    lps_buf, lp_out, k, 0)
                counts_buf = lax.dynamic_update_index_in_dim(
                    counts_buf, emitted, k, 0)
                acc_buf = lax.dynamic_update_index_in_dim(
                    acc_buf, acc_out, k, 0)
                prop_buf = lax.dynamic_update_index_in_dim(
                    prop_buf, prop_out, k, 0)
                return (k + 1, tokens, positions, steps_left, active,
                        block_tables, bt_counts, free_used, exit_code,
                        toks_buf, lps_buf, counts_buf, acc_buf, prop_buf,
                        pool_k, pool_v, dpool_k, dpool_v, rng)

            st = lax.while_loop(cond, body, (
                jnp.asarray(0, jnp.int32), tokens, positions, steps_left,
                active, block_tables, bt_counts,
                jnp.asarray(0, jnp.int32), jnp.zeros((B,), jnp.int32),
                jnp.full((rounds, B, W), -1, jnp.int32),
                jnp.zeros((rounds, B, W), jnp.float32),
                jnp.zeros((rounds, B), jnp.int32),
                jnp.zeros((rounds, B), jnp.int32),
                jnp.zeros((rounds, B), jnp.int32),
                pool_k, pool_v, dpool_k, dpool_v, rng,
            ))
            (n_rounds, tokens, positions, steps_left, active, block_tables,
             bt_counts, free_used, exit_code, toks_buf, lps_buf,
             counts_buf, acc_buf, prop_buf,
             pool_k, pool_v, dpool_k, dpool_v, rng) = st
            exit_code = jnp.where(active & (exit_code == 0), 4, exit_code)
            return (toks_buf, lps_buf, counts_buf, acc_buf, prop_buf,
                    exit_code, n_rounds, block_tables, bt_counts,
                    tokens, positions, steps_left, active,
                    pool_k, pool_v, dpool_k, dpool_v, rng)

        return self._with_mesh(loop_block)

    def _loop_step(self, outputs: List[StepOutput]) -> bool:
        """Launch ONE run-to-completion block and reconcile it
        synchronously (looped blocks do not pipeline: the loop itself
        amortizes the host round-trip over its whole run, and processing
        immediately keeps the host view exact for admission/preemption).
        Page pressure drains/preempts exactly like _maybe_launch; the
        host guarantees only each row's FIRST write host-side (the
        livelock guard — every launched row advances at least one step),
        then sizes a device free-list draw for the worst-case remainder
        and reconciles claimed/returned pages with the allocator
        afterwards."""
        if self._pending:
            # fixed/mixed frames from earlier iterations reconcile first
            # so slots, dev_pos and the carry projection are exact
            self._drain_pending(outputs)
        sc_t0 = time.monotonic()
        sc_excl = 0.0
        cap = self._loop_cap()
        use_spec = False
        while True:
            seated = [(i, s) for i, s in enumerate(self.slots)
                      if s is not None]
            if not any(u[0] for u in self._slot_updates.values()) and not any(
                s.dev_steps_left > 0 for _, s in seated
            ):
                return False
            use_spec, spec_ok = self._spec_plan(seated)
            for _, s in seated:
                self._reclaim_window_pages(s)
            W = self.spec.num_draft_tokens + 1 if use_spec else 1
            try:
                for _, s in seated:
                    if s.dev_steps_left > 0:
                        self._ensure_block_pages(s, W)
                break
            except CacheFull:
                self._event("cache_full")
                if self._pending:
                    drain_t0 = time.monotonic()
                    self._drain_pending(outputs)
                    sc_excl += time.monotonic() - drain_t0
                    continue
                if seated:
                    self._preempt_youngest(outputs)
                    continue
                return False
        ps = self.pcfg.page_size
        P = self.pcfg.max_pages_per_seq
        gamma = self.spec.num_draft_tokens if use_spec else 0
        advs: Dict[int, int] = {}
        want = 0
        for i, s in seated:
            if s.dev_steps_left <= 0:
                advs[id(s)] = 0
                continue
            if use_spec:
                adv = min(max(1, -(-cap // W)) * W, s.dev_steps_left + gamma)
            else:
                adv = min(cap, s.dev_steps_left)
            advs[id(s)] = adv
            needed = min((s.dev_pos + adv - 1) // ps + 1, P)
            want += max(0, needed - len(s.block_table))
        drawn = self.allocator.draw_device(want) if want > 0 else []
        free_arr = np.full((self.pcfg.num_pages,), self.pcfg.num_pages,
                           np.int32)
        free_arr[: len(drawn)] = drawn
        for i, s in seated:
            if self._bt_pages[i] != len(s.block_table):
                self._refresh_bt_row(i, s)
        # snapshot records each row's table length at launch so the
        # reconcile can read the device's appends off the returned table
        snapshot = [(i, s, advs[id(s)], len(s.block_table))
                    for i, s in seated]
        injects = self._drain_slot_updates()
        tokens, positions, steps_left, active, rng = self._carry
        # the loop appends pages at ANY index, so the uploaded table
        # keeps full capacity width (no gather bucketing; attention is
        # kv_valid-masked either way)
        uploads = (
            jnp.asarray(np.ascontiguousarray(self._bt)),
            jnp.asarray(self._temp),
            jnp.asarray(self._topp),
        )
        use_topp = any(
            s.params.top_p < 1.0 and s.params.temperature > 0.0
            for _, s in seated
        )
        any_temp = any(s.params.temperature > 0.0 for _, s in seated)
        sample_mode = 2 if use_topp else (1 if any_temp else 0)
        loop_extras = (
            jnp.asarray(self._bt_pages), jnp.asarray(free_arr),
            jnp.asarray(len(drawn), jnp.int32),
        )
        if use_spec:
            ok_arr = np.zeros((self.ecfg.max_batch,), bool)
            for i, _ in seated:
                ok_arr[i] = spec_ok is None or spec_ok.get(i, True)
            (toks, lps, counts, acc, prop, codes, n_steps, tbl, cnt,
             tokens, positions, steps_left, active,
             self.state.k, self.state.v,
             self.draft_state.k, self.draft_state.v,
             rng) = self._get_spec_loop_fn(use_topp, cap)(
                self.params, self.draft_params,
                self.state.k, self.state.v,
                self.draft_state.k, self.draft_state.v,
                tokens, positions, steps_left, active,
                *uploads, jnp.asarray(ok_arr), rng, *injects,
                *loop_extras, jnp.asarray(any_temp),
            )
        else:
            (toks, lps, codes, n_steps, tbl, cnt,
             tokens, positions, steps_left, active,
             self.state.k, self.state.v, rng) = self._get_loop_fn(cap)(
                self.params, self.state.k, self.state.v,
                tokens, positions, steps_left, active,
                *uploads, rng, *injects, *loop_extras,
                jnp.asarray(sample_mode, jnp.int32),
            )
            counts = acc = prop = None
        self._carry = (tokens, positions, steps_left, active, rng)
        for _, s in seated:
            adv = advs[id(s)]
            s.dev_pos += adv
            s.dev_steps_left -= adv
        emitted = self._process_loop_block(
            toks, lps, counts, acc, prop, codes, n_steps, tbl, cnt,
            snapshot, drawn, outputs,
        )
        self._clock("loop",
                    max(0.0, time.monotonic() - sc_t0 - sc_excl),
                    tokens=emitted, rows=len(seated), dispatches=1)
        return True

    def _process_loop_block(self, toks_d, lps_d, counts_d, acc_d, prop_d,
                            codes_d, steps_d, tbl_d, cnt_d, snapshot,
                            drawn: List[int],
                            outputs: List[StepOutput]) -> int:
        """Reconcile one looped block. Page settlement comes FIRST:
        device-appended pages join live rows' block tables (so a row the
        emission walk finishes releases them through _finish ->
        _release_seq like any other page), appends on rows aborted
        mid-flight are orphans, and orphans plus the draw's unused tail
        go back to the allocator via reconcile_device — audit()
        conservation holds again the moment this returns. Then the
        fixed path's emission walk runs unchanged (freeze sentinels,
        spec counts, failure isolation, assumed-vs-emitted reconcile),
        rows frozen for pages (exit 3) re-stage for the next launch,
        and the per-row exit codes feed engine_loop_exit_total. The
        np.asarray calls below are the block-boundary device reads;
        nothing else here may touch the device (distlint DL007)."""
        toks = np.asarray(toks_d)
        lps = np.asarray(lps_d)
        codes = np.asarray(codes_d)
        n_steps = int(np.asarray(steps_d))
        tbl = np.asarray(tbl_d)
        cnt = np.asarray(cnt_d)
        # --- page settlement (before the walk: _finish must see the
        # device-grown tables to free them) ---
        claimed: List[int] = []
        for slot, seq, _, n0 in snapshot:
            n1 = int(cnt[slot])
            if n1 <= n0:
                continue
            pages = [int(p) for p in tbl[slot, n0:n1]]
            if self._by_id.get(seq.request_id) is seq:
                claimed.extend(pages)
                seq.block_table.extend(pages)
            # aborted rows' appends fall through to the returned list:
            # their KV is garbage (same safety argument as abort's
            # in-flight block writes) and the pages go straight back
        claimed_set = set(claimed)
        returned = [p for p in drawn if p not in claimed_set]
        if drawn:
            self.allocator.reconcile_device(claimed, returned)
        if counts_d is None:
            toks3 = toks[:, :, None]
            lps3 = lps[:, :, None]
            counts = (toks >= 0).astype(np.int32)
        else:
            toks3 = toks
            lps3 = lps
            counts = np.asarray(counts_d)
            if self.spec_trackers is not None:
                prop_arr = np.asarray(prop_d)
                acc_arr = np.asarray(acc_d)
                agg: Dict[tuple, list] = {}
                for slot, seq, _, _ in snapshot:
                    p = int(prop_arr[:, slot].sum())
                    if p <= 0:
                        continue
                    a = agg.setdefault(spec_signature(seq.params),
                                       [0, 0, 0])
                    a[0] += int(acc_arr[:, slot].sum())
                    a[1] += p
                    a[2] += int((prop_arr[:, slot] > 0).sum())
                for sig, (acc_n, prop_n, rows_n) in agg.items():
                    self.spec_trackers.update(
                        sig, acc_n, prop_n, rows=rows_n
                    )
        R = toks3.shape[0]
        sc_emitted = 0
        for slot, seq, assumed, _ in snapshot:
            if self._by_id.get(seq.request_id) is not seq:
                continue  # finished or aborted while the block ran
            emitted_here = 0
            try:
                done = False
                for k in range(R):
                    c = int(counts[k, slot])
                    if c <= 0:
                        break  # row froze on-device before this round
                    for w in range(c):
                        t = int(toks3[k, slot, w])
                        if t < 0:
                            break
                        seq.token_ids.append(seq.next_token)
                        seq.seq_len += 1
                        emitted_here += 1
                        self._emit_token(seq, t, outputs,
                                         float(lps3[k, slot, w]))
                        if self._by_id.get(seq.request_id) is not seq:
                            self._deact_slot(slot)
                            done = True
                            break
                    if done:
                        break
            except Exception as e:  # failure isolation (Property 22)
                if self.slots[slot] is seq:
                    self.slots[slot] = None
                self._deact_slot(slot)
                self._by_id.pop(seq.request_id, None)
                self._release_seq(seq)
                outputs.append(StepOutput(
                    request_id=seq.request_id, finished=True, error=str(e)))
                continue
            sc_emitted += emitted_here
            if self._by_id.get(seq.request_id) is seq:
                delta = assumed - emitted_here
                seq.dev_pos -= delta
                seq.dev_steps_left += delta
        # rows the free list starved (exit 3) froze on-device but are
        # still live on the host: re-stage them so the next launch
        # re-injects the carry row (host pages guaranteed then)
        _REASONS = ("", "eos", "budget", "pages", "cap")
        for slot, seq, _, _ in snapshot:
            c = int(codes[slot])
            if c:
                self._loop_exits[_REASONS[c]] += 1
            if (c == 3 and self._by_id.get(seq.request_id) is seq
                    and self.slots[slot] is seq):
                self._stage_seat(slot, seq)
        self._loop_blocks += 1
        self._loop_steps += n_steps
        self._loop_decode_tokens += sc_emitted
        return sc_emitted

    def _get_spec_block(self, use_topp: bool) -> Callable:
        """Speculative block variant for this launch: the use_topp=True
        variant (nucleus-aware verify) compiles lazily on the first
        launch that seats a top_p<1 row."""
        fn = self._spec_block_fns.get(use_topp)
        if fn is None:
            fn = self._build_spec_block(use_topp)
            self._spec_block_fns[use_topp] = fn
        return fn

    def _build_spec_block(self, use_topp: bool) -> Callable:
        """Compile the speculative decode block (Req 12): R rounds of
        (draft proposes gamma tokens over its own page pool -> target
        verifies all of them in ONE T=gamma+1 paged forward -> rejection
        sampling accepts a prefix + resamples/bonus), all on-device in one
        program. Per round a row emits 1..gamma+1 tokens.

        Temperature-0 rows accept by exact greedy match (bit-identical to
        plain decoding, tested); top-p rows are verified NUCLEUS-AWARE —
        the draft samples from its top-p-filtered q̃ and the verifier
        scores against the filtered target p̃, so they keep full
        multi-token acceptance and their output law is exactly nucleus
        sampling from the target (tested for distribution exactness).
        The nucleus machinery costs full-vocab sorts per round, so it is
        compiled in only when ``use_topp`` — launches whose seated rows
        are all top_p=1 dispatch the variant without it (see
        ``_get_spec_block``). EOS truncates a row's emissions and freezes
        it on-device.
        Writes past the row's capacity are dropped (speculative overshoot
        near max_seq_len)."""
        cfg, dcfg = self.cfg, self.draft_cfg
        impl = self._resolved_impl()
        ps = self.pcfg.page_size
        R = self.ecfg.decode_block_size
        gamma = self.spec.num_draft_tokens
        W = gamma + 1
        smax = self._smax
        num_slots = self._num_slots_flat
        moe_impl = self._moe_impl()
        fwd = self._fwd
        eos = jnp.asarray(sorted(self.tok.eos_ids), jnp.int32)

        @functools.partial(
            jax.jit, donate_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 14)
        )
        def block(params, dparams, pool_k, pool_v, dpool_k, dpool_v,
                  tokens, positions, steps_left, active, block_tables,
                  temp, top_p, spec_ok, rng,
                  set_mask, set_active, set_tokens, set_positions,
                  set_steps, any_temp):
            tokens = jnp.where(set_mask, set_tokens, tokens)
            positions = jnp.where(set_mask, set_positions, positions)
            steps_left = jnp.where(set_mask, set_steps, steps_left)
            active = jnp.where(set_mask, set_active, active)

            B = tokens.shape[0]
            # gather width = uploaded (bucketed) table shape; smax stays
            # the full CAPACITY bound for the write-drop checks below
            offs = jnp.arange(block_tables.shape[1] * ps, dtype=jnp.int32)
            gather = block_tables[:, offs // ps] * ps + offs % ps
            rows = jnp.arange(B)
            max_pages = block_tables.shape[1]

            def flat_slot(pos):  # [B] absolute positions -> flat slots
                page = block_tables[
                    rows, jnp.minimum(pos // ps, max_pages - 1)
                ]
                return page * ps + pos % ps

            def one_round(carry, keys):
                (tokens, positions, steps_left, active,
                 pool_k, pool_v, dpool_k, dpool_v) = carry

                # ---- draft: gamma+1 sequential T=1 proposals (the last
                # step ingests the final proposal's K/V; its sample is
                # discarded) over the draft page pool ----
                def dstep(c, key):
                    dpk, dpv, tok, pos = c
                    ok = active & (pos < smax)
                    write = jnp.where(ok, flat_slot(pos), num_slots)[:, None]
                    kv_valid = jnp.where(active, pos + 1, 0)
                    logits, dpk, dpv = fwd(
                        dparams, dcfg, tok[:, None], pos[:, None],
                        dpk, dpv, write, gather, kv_valid, impl, "dense",
                    )
                    # proposals MUST be sampled from the same nucleus-
                    # filtered q̃ the verifier scores against (top_p=1
                    # rows: identity, so the sorts are compiled out)
                    q = spec_probs(logits[:, 0], temp)
                    if use_topp:
                        q = spec_nucleus(q, top_p)
                    # all-greedy launches (runtime branch): q rows are
                    # one-hots, so argmax(q) IS the draw — skip the
                    # [B, V] Gumbel noise per draft step
                    nxt = lax.cond(
                        any_temp,
                        lambda a: jax.random.categorical(
                            a[0], jnp.log(a[1] + 1e-30), axis=-1
                        ).astype(jnp.int32),
                        lambda a: jnp.argmax(a[1], -1).astype(jnp.int32),
                        (key, q),
                    )
                    return (dpk, dpv, nxt, pos + 1), (nxt, q)

                (dpool_k, dpool_v, _, _), (dtoks, dqs) = lax.scan(
                    dstep, (dpool_k, dpool_v, tokens, positions),
                    keys[: gamma + 1],
                )
                dtoks = dtoks.T[:, :gamma]  # [B, gamma]
                dqs = jnp.moveaxis(dqs, 0, 1)[:, :gamma]  # [B, gamma, V]

                # ---- target: one verify forward over [last, d_1..d_g] ----
                # (positions are contiguous per row, so the Pallas
                # chunked-prefill kernel applies when selected)
                ver_tokens = jnp.concatenate([tokens[:, None], dtoks], 1)
                ver_pos = positions[:, None] + jnp.arange(W)[None]
                ok = active[:, None] & (ver_pos < smax)
                vpage = block_tables[
                    rows[:, None], jnp.minimum(ver_pos // ps, max_pages - 1)
                ]
                write = jnp.where(ok, vpage * ps + ver_pos % ps, num_slots)
                kv_valid = jnp.where(active, positions + W, 0)
                logits, pool_k, pool_v = fwd(
                    params, cfg, ver_tokens, ver_pos, pool_k, pool_v,
                    write, gather, kv_valid, impl, moe_impl,
                )
                tps = spec_probs(logits, temp[:, None])  # [B, W, V]
                # model-distribution logprobs of whatever gets emitted
                # (raw logits, matching the plain decode path): computed
                # as logits[token] - logsumexp, no [B, W, V] log-softmax
                # intermediate
                x32 = logits.astype(jnp.float32)
                lse = jax.scipy.special.logsumexp(x32, axis=-1)  # [B, W]

                # ---- rejection sampling (shared speculative.py core) ----
                # nucleus-aware: the core filters BOTH sides to each row's
                # top-p nucleus (the draft sampled from that same q̃
                # above), so top-p rows keep full multi-token acceptance
                # spec_ok=False rows (pattern on probation, Req 12.5)
                # force-reject at 0 and draw their one token from the
                # (filtered) target — plain decoding law at one
                # token/round, no draft-quality dependence
                toks_out, num_accepted = spec_accept_resample(
                    tps, dtoks, dqs, keys[gamma + 1], keys[gamma + 2],
                    spec_ok=spec_ok,
                    top_p=top_p if use_topp else None,
                    greedy_only=~any_temp,
                )
                idx = jnp.arange(W)[None]
                base = num_accepted + 1
                is_eos = (
                    (toks_out[..., None] == eos[None, None, :]).any(-1)
                    if eos.size
                    else jnp.zeros(toks_out.shape, bool)
                ) & (idx < base[:, None])
                has_eos = is_eos.any(-1)
                first_eos = jnp.argmax(is_eos, axis=-1)
                emitted = jnp.where(
                    has_eos, jnp.minimum(base, first_eos + 1), base
                )
                emitted = jnp.where(active, emitted, 0)
                # masked rows contribute nothing to acceptance stats
                acc_out = jnp.where(active & spec_ok, num_accepted, 0)
                prop_out = jnp.where(active & spec_ok, gamma, 0)
                toks_out = jnp.where(
                    (idx < emitted[:, None]) & active[:, None], toks_out, -1
                )
                lp_out = jnp.take_along_axis(
                    x32, jnp.maximum(toks_out, 0)[..., None], axis=-1
                )[..., 0] - lse
                new_last = toks_out[rows, jnp.maximum(emitted, 1) - 1]
                tokens = jnp.where(active & (emitted > 0), new_last, tokens)
                positions = positions + emitted
                steps_left = steps_left - emitted
                active = active & ~has_eos & (steps_left > 0)
                return (
                    (tokens, positions, steps_left, active,
                     pool_k, pool_v, dpool_k, dpool_v),
                    (toks_out, lp_out, emitted, acc_out, prop_out),
                )

            rng, sub = jax.random.split(rng)
            keys = jax.random.split(sub, R * (gamma + 3))
            keys = keys.reshape((R, gamma + 3) + keys.shape[1:])
            carry, (toks, lps, counts, acc, prop) = lax.scan(
                one_round,
                (tokens, positions, steps_left, active,
                 pool_k, pool_v, dpool_k, dpool_v),
                keys,
            )
            (tokens, positions, steps_left, active,
             pool_k, pool_v, dpool_k, dpool_v) = carry
            return (toks, lps, counts, acc, prop, tokens, positions,
                    steps_left, active, pool_k, pool_v, dpool_k, dpool_v,
                    rng)

        return self._with_mesh(block)

    def _spec_plan(self, seated):
        """Per-launch speculation plan (Req 12.5 per-pattern disable):
        ``(use_spec, ok_by_slot)`` where a seated row speculates iff its
        request pattern's tracker is enabled. A launch whose rows are ALL
        on disabled patterns takes the plain block; a mixed launch runs
        the spec block with the disabled rows masked via ``spec_ok``
        (they emit one target-sampled token per round — plain decoding
        law — and contribute nothing to acceptance statistics). Runs on
        the engine thread, so it owns the probation re-enable (stats
        readers see the pure ``enabled`` view)."""
        if self.draft_params is None or self.spec_trackers is None:
            return False, None
        ok: Dict[int, bool] = {}
        any_ok = False
        for i, s in seated:
            en = self.spec_trackers.consume_probation(
                spec_signature(s.params)
            )
            ok[i] = en
            any_ok = any_ok or en
        return any_ok, ok

    def spec_stats(self) -> Optional[dict]:
        """Speculation metrics for /server/stats and /metrics (Req 12.4),
        aggregate plus per-pattern breakdown; None when no draft model is
        configured."""
        if self.spec_trackers is None:
            return None
        out = self.spec_trackers.stats()
        out["num_draft_tokens"] = self.spec.num_draft_tokens
        return out

    def _stage_seat(self, slot: int, seq: _Seq) -> None:
        """Stage a freshly prefetched sequence into a decode slot: its first
        sampled token, position, and on-device step budget are injected into
        the carry at the next block launch."""
        budget = max(0, min(
            seq.params.max_tokens - seq.emitted_tokens,
            self.pcfg.max_seq_len - 1 - seq.seq_len,
        ))
        seq.dev_pos = seq.seq_len
        seq.dev_steps_left = budget
        self._slot_updates[slot] = (True, int(seq.next_token), seq.seq_len,
                                    budget)
        self._temp[slot] = seq.params.temperature
        self._topp[slot] = seq.params.top_p
        self._bt_pages[slot] = 0
        self._refresh_bt_row(slot, seq)

    def _deact_slot(self, slot: int) -> None:
        self._slot_updates[slot] = (False, 0, 0, 0)

    def _refresh_bt_row(self, slot: int, seq: _Seq) -> None:
        table = seq.block_table[: self.pcfg.max_pages_per_seq]
        start = int(self._bt_pages[slot])
        if start > len(table):
            start = 0
        for p in range(start, len(table)):
            self._bt[slot, p] = table[p]
        self._bt_pages[slot] = len(table)

    def _assumed_adv(self, seq: _Seq, use_spec: bool) -> int:
        """Upper bound on tokens this sequence can emit in one block: the
        page-preallocation and budget-projection unit. Speculative rounds
        may overshoot the budget by up to gamma tokens before the device
        freeze triggers.

        With blocks in flight the projection (dev_pos, dev_steps_left) is
        an upper bound on the device row's position but only a LOWER bound
        on its remaining steps (speculative rounds emit fewer tokens than
        assumed whenever acceptance < 100%; the reconcile in
        _process_block restores exactness). The sum dev_pos +
        dev_steps_left is conserved across launches and reconciles, so the
        worst-case write position of the next block is
        min(dev_pos + block_cap, dev_pos + dev_steps_left + gamma) - 1 —
        the advance below must NOT floor at dev_steps_left <= 0 while a
        block is pending, or a still-active device row decodes past its
        ensured pages into other sequences' KV."""
        if use_spec:
            if seq.dev_steps_left <= 0 and not self._pending:
                return 0  # host view exact: row is frozen
            gamma = self.spec.num_draft_tokens
            return max(0, min(
                self.ecfg.decode_block_size * (gamma + 1),
                seq.dev_steps_left + gamma,
            ))
        if seq.dev_steps_left <= 0:
            return 0
        return min(self.ecfg.decode_block_size, seq.dev_steps_left)

    def _ensure_block_pages(self, seq: _Seq, steps: int) -> None:
        """Pre-allocate pages covering the next block's writes for this
        sequence (positions dev_pos .. dev_pos+steps-1). Raises CacheFull."""
        if steps <= 0:
            return
        needed = (seq.dev_pos + steps - 1) // self.pcfg.page_size + 1
        missing = min(needed, self.pcfg.max_pages_per_seq) - len(seq.block_table)
        if missing > 0:
            seq.block_table.extend(self.allocator.allocate(missing))

    def _maybe_launch(self, outputs: List[StepOutput]) -> bool:
        """Launch one decode block if any seated row has budget left or a
        host override is staged. Handles page pressure by draining the
        pipeline (finished rows release pages) and then preempting the
        youngest sequence, exactly once per launch attempt."""
        sc_t0 = time.monotonic()  # step clock: host wall only
        sc_excl = 0.0  # drained-frame seconds (clocked by their frames)
        use_spec = False
        while True:
            seated = [(i, s) for i, s in enumerate(self.slots)
                      if s is not None]
            # launch only if some row will actually decode; deact-only
            # updates stay staged until the next real launch
            if not any(u[0] for u in self._slot_updates.values()) and not any(
                s.dev_steps_left > 0 for _, s in seated
            ):
                return False
            use_spec, spec_ok = self._spec_plan(seated)
            for _, s in seated:
                self._reclaim_window_pages(s)
            # spec_ok=False rows in a spec launch still use the spec
            # advance bound: the verify forward WRITES gamma+1 positions
            # per round for every row, so their pages must cover the
            # same worst-case write position
            advs = {id(s): self._assumed_adv(s, use_spec) for _, s in seated}
            try:
                for _, s in seated:
                    self._ensure_block_pages(s, advs[id(s)])
                break
            except CacheFull:
                self._event("cache_full")
                if self._pending:
                    # the drained frames clock their own processing
                    # under their kinds — exclude it here or those
                    # seconds count twice across kinds
                    drain_t0 = time.monotonic()
                    self._drain_pending(outputs)
                    sc_excl += time.monotonic() - drain_t0
                    continue  # finished rows may have released pages
                if seated:
                    self._preempt_youngest(outputs)
                    continue
                return False
        for i, s in seated:
            if self._bt_pages[i] != len(s.block_table):
                self._refresh_bt_row(i, s)
        self._launch(seated, advs, use_spec, spec_ok)
        for _, s in seated:
            adv = advs[id(s)]
            # no floor: negatives reconcile exactly when blocks complete
            s.dev_pos += adv
            s.dev_steps_left -= adv
        self._clock("decode_block",
                    max(0.0, time.monotonic() - sc_t0 - sc_excl),
                    rows=len(seated), dispatches=1)
        return True

    def _drain_slot_updates(self) -> Tuple[jnp.ndarray, ...]:
        """Drain the staged host overrides (admissions / deactivations)
        into the inject arrays every carry-consuming launch merges, and
        lazily create the device carry — shared by the decode block
        (_launch) and the mixed step (_mixed_step) so the two paths'
        staged-update encoding and carry layout cannot drift."""
        B = self.ecfg.max_batch
        set_mask = np.zeros((B,), bool)
        set_active = np.zeros((B,), bool)
        set_tokens = np.zeros((B,), np.int32)
        set_pos = np.zeros((B,), np.int32)
        set_steps = np.zeros((B,), np.int32)
        for slot, (act, tok, pos, steps) in self._slot_updates.items():
            set_mask[slot] = True
            set_active[slot] = act
            set_tokens[slot] = tok
            set_pos[slot] = pos
            set_steps[slot] = steps
        self._slot_updates.clear()
        if self._carry is None:
            self._carry = (
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), bool),
                jax.random.PRNGKey(self.ecfg.seed + 1),
            )
        return (
            jnp.asarray(set_mask), jnp.asarray(set_active),
            jnp.asarray(set_tokens), jnp.asarray(set_pos),
            jnp.asarray(set_steps),
        )

    def _launch(self, seated: List[Tuple[int, _Seq]],
                advs: Dict[int, int], use_spec: bool,
                spec_ok: Optional[Dict[int, bool]] = None) -> None:
        injects = self._drain_slot_updates()
        tokens, positions, steps_left, active, rng = self._carry
        live_pages = max(
            [len(s.block_table) for _, s in seated], default=1
        )
        bucket = self._gather_pages(live_pages, prefill=False)
        uploads = (
            jnp.asarray(np.ascontiguousarray(self._bt[:, :bucket])),
            jnp.asarray(self._temp),
            jnp.asarray(self._topp),
        )
        snapshot = [(i, s, advs[id(s)]) for i, s in seated]
        # sampling machinery only as heavy as a seated row actually
        # needs: greedy rows (temperature 0) sample a one-hot, for which
        # nucleus filtering is a no-op — and an all-greedy launch needs
        # neither the full-vocab nucleus passes nor categorical's [B, V]
        # Gumbel noise (sample_mode 0/1/2, decoded in the block)
        use_topp = any(
            s.params.top_p < 1.0 and s.params.temperature > 0.0
            for _, s in seated
        )
        any_temp = any(s.params.temperature > 0.0 for _, s in seated)
        sample_mode = 2 if use_topp else (1 if any_temp else 0)
        if use_spec:
            ok_arr = np.zeros((self.ecfg.max_batch,), bool)
            for i, _ in seated:
                ok_arr[i] = spec_ok is None or spec_ok.get(i, True)
            (toks, lps, counts, acc, prop, tokens, positions, steps_left,
             active, self.state.k, self.state.v,
             self.draft_state.k, self.draft_state.v,
             rng) = self._get_spec_block(use_topp)(
                self.params, self.draft_params,
                self.state.k, self.state.v,
                self.draft_state.k, self.draft_state.v,
                tokens, positions, steps_left, active,
                *uploads, jnp.asarray(ok_arr), rng, *injects,
                jnp.asarray(any_temp),
            )
            self._pending.append((toks, lps, counts, acc, prop, snapshot,
                                  "decode_block"))
        else:
            (outs, lps, tokens, positions, steps_left, active,
             self.state.k, self.state.v, rng) = self._block_fn(
                self.params, self.state.k, self.state.v,
                tokens, positions, steps_left, active,
                *uploads, rng, *injects,
                jnp.asarray(sample_mode, jnp.int32),
            )
            self._pending.append((outs, lps, None, None, None, snapshot,
                                  "decode_block"))
        self._carry = (tokens, positions, steps_left, active, rng)

    def _drain_pending(self, outputs: List[StepOutput]) -> None:
        """Process every in-flight block. Afterwards the host view is exact
        (device position == seq.seq_len, carry token == seq.next_token for
        every live row), which preemption requires."""
        while self._pending:
            self._process_block(outputs)

    def _process_block(self, outputs: List[StepOutput]) -> None:
        """Consume the oldest pending block: walk each row's sampled tokens
        through the same emission path as r1's per-step loop (EOS / stop-
        sequence / length finishing, streaming deltas, failure isolation).

        Normal blocks carry [K, B] tokens with -1 freeze sentinels;
        speculative blocks carry [R, B, W] tokens plus per-round emission
        counts and acceptance stats. Live sequences reconcile the launch's
        assumed advance against what was actually emitted (speculative
        rounds emit a variable number of tokens)."""
        sc_t0 = time.monotonic()  # step clock: host wall incl. the read
        (toks_d, lps_d, counts_d, acc_d, prop_d,
         snapshot, sc_kind) = self._pending.popleft()
        # the block's two blocking device reads (token ids + their
        # logprobs; the logprob tensor is [K, B] f32 — trivial next to
        # the step compute, and computed on-device by one fused
        # log-softmax over logits the step already produced)
        toks = np.asarray(toks_d)
        lps = np.asarray(lps_d)
        if counts_d is None:
            toks3 = toks[:, :, None]
            lps3 = lps[:, :, None]
            counts = (toks >= 0).astype(np.int32)
        else:
            toks3 = toks
            lps3 = lps
            counts = np.asarray(counts_d)
            if self.spec_trackers is not None:
                # per-PATTERN attribution (Req 12.5): each seated row's
                # accept/propose counts update its own request pattern's
                # tracker, so a badly speculating pattern disables alone.
                # prop/acc are [R(ounds), B]; spec_ok-masked and inactive
                # rows carry prop 0 and drop out here.
                prop_arr = np.asarray(prop_d)
                acc_arr = np.asarray(acc_d)
                agg: Dict[tuple, list] = {}
                for slot, seq, _ in snapshot:
                    p = int(prop_arr[:, slot].sum())
                    if p <= 0:
                        continue
                    a = agg.setdefault(spec_signature(seq.params),
                                       [0, 0, 0])
                    a[0] += int(acc_arr[:, slot].sum())
                    a[1] += p
                    a[2] += int((prop_arr[:, slot] > 0).sum())
                for sig, (acc_n, prop_n, rows_n) in agg.items():
                    self.spec_trackers.update(
                        sig, acc_n, prop_n, rows=rows_n
                    )
        R = toks3.shape[0]
        sc_emitted = 0
        for slot, seq, assumed in snapshot:
            if self._by_id.get(seq.request_id) is not seq:
                continue  # finished or aborted while the block was in flight
            emitted_here = 0
            try:
                done = False
                for k in range(R):
                    c = int(counts[k, slot])
                    if c <= 0:
                        break  # row was frozen on-device before this round
                    for w in range(c):
                        t = int(toks3[k, slot, w])
                        if t < 0:
                            break
                        seq.token_ids.append(seq.next_token)
                        seq.seq_len += 1
                        emitted_here += 1
                        self._emit_token(seq, t, outputs,
                                         float(lps3[k, slot, w]))
                        if self._by_id.get(seq.request_id) is not seq:
                            # finished (EOS/stop/length): the device row
                            # may still be live (stop sequences are host-
                            # only) — deactivate it at the next launch
                            self._deact_slot(slot)
                            done = True
                            break
                    if done:
                        break
            except Exception as e:  # failure isolation (Property 22)
                if self.slots[slot] is seq:
                    self.slots[slot] = None
                self._deact_slot(slot)
                self._by_id.pop(seq.request_id, None)
                self._release_seq(seq)
                outputs.append(StepOutput(
                    request_id=seq.request_id, finished=True, error=str(e)))
                continue
            sc_emitted += emitted_here
            if self._by_id.get(seq.request_id) is seq:
                delta = assumed - emitted_here
                seq.dev_pos -= delta
                seq.dev_steps_left += delta
        # reconcile wall time lands under the LAUNCHING kind; tokens
        # for mixed frames and rows for BOTH kinds were counted at
        # dispatch — re-counting here would double rows-per-dispatch
        self._clock(sc_kind, time.monotonic() - sc_t0,
                    tokens=sc_emitted if sc_kind == "decode_block" else 0)

    # ------------------------------------------------------------------
    # token emission & completion
    # ------------------------------------------------------------------

    def _decode_piece(self, seq: _Seq, token_id: int) -> str:
        """Incremental detokenization: a token whose isolated text decodes
        to U+FFFD is (almost always) a fragment of a multi-token UTF-8
        character — a raw byte from ByteTokenizer or a byte-fallback BPE
        piece. Hold such tokens back and decode them TOGETHER with their
        successors, emitting the completed character once the joint decode
        is clean (previously every fragment streamed as a literal '�').
        A genuinely undecodable run flushes after 8 tokens (a UTF-8
        character is at most 4 bytes) so output cannot stall; _finish
        flushes any remainder."""
        if seq.pending_ids:
            seq.pending_ids.append(token_id)
            text = self.tok.decode(seq.pending_ids)
            if text.endswith("�") and len(seq.pending_ids) < 8:
                return ""
            seq.pending_ids = []
            return text
        piece = self.tok.decode_token(token_id)
        # only a TRAILING replacement char signals an incomplete multi-byte
        # sequence; a vocab entry that legitimately decodes to U+FFFD
        # mid-string would otherwise be delayed and merged into the next
        # delta for no reason
        if piece.endswith("�"):
            seq.pending_ids = [token_id]
            return ""
        return piece

    def _flush_pending_text(self, seq: _Seq) -> None:
        """Decode and append any held-back fragment ids (request is
        terminating — emit what exists, replacement chars included)."""
        if seq.pending_ids:
            seq.output_text += self.tok.decode(seq.pending_ids)
            seq.pending_ids = []

    def _emit_token(self, seq: _Seq, token_id: int,
                    outputs: List[StepOutput],
                    logprob: Optional[float] = None) -> None:
        """Process one sampled token: EOS / length / stop-sequence handling
        and the streaming text delta with stop-sequence holdback."""
        p = seq.params
        if token_id in self.tok.eos_ids:
            self._finish(seq, FinishReason.STOP, outputs)
            return

        seq.next_token = token_id
        seq.emitted_tokens += 1
        piece = self._decode_piece(seq, token_id)
        seq.output_text += piece

        # stop sequences: scan the un-emitted tail
        if p.stop_sequences:
            earliest = -1
            for stop in p.stop_sequences:
                idx = seq.output_text.find(stop, max(0, seq.emitted_upto - len(stop)))
                if idx >= 0 and (earliest < 0 or idx < earliest):
                    earliest = idx
            if earliest >= 0:
                seq.output_text = seq.output_text[:earliest]
                # defensive: pending_ids is provably empty here (a held
                # fragment leaves output_text unchanged, so no new stop
                # match can appear while one is pending) — cleared anyway
                # so _finish can never flush text past a stop truncation
                seq.pending_ids = []
                self._finish(seq, FinishReason.STOP_SEQUENCE, outputs)
                return

        if (
            seq.emitted_tokens >= p.max_tokens
            or seq.seq_len + 1 >= self.pcfg.max_seq_len
        ):
            # final token: emit its id, then the completion (which flushes
            # all held-back text)
            outputs.append(StepOutput(
                request_id=seq.request_id,
                token_id=token_id,
                text="",
                token_index=seq.emitted_tokens - 1,
                logprob=logprob,
            ))
            self._finish(seq, FinishReason.LENGTH, outputs)
            return

        # emit the delta, holding back a possible stop-sequence prefix
        hold = max((len(s) for s in p.stop_sequences), default=1) - 1
        safe_upto = max(seq.emitted_upto, len(seq.output_text) - hold)
        delta = seq.output_text[seq.emitted_upto : safe_upto]
        seq.emitted_upto = safe_upto
        outputs.append(StepOutput(
            request_id=seq.request_id,
            token_id=token_id,
            text=delta,
            token_index=seq.emitted_tokens - 1,
            logprob=logprob,
        ))

    def _finish(self, seq: _Seq, reason: FinishReason,
                outputs: List[StepOutput]) -> None:
        # flush held-back text; index it as the last emitted token's
        self._flush_pending_text(seq)
        delta = seq.output_text[seq.emitted_upto :]
        usage = Usage.of(seq.prompt_len, seq.emitted_tokens)
        outputs.append(StepOutput(
            request_id=seq.request_id,
            text=delta,
            token_index=max(0, seq.emitted_tokens - 1),
            finished=True,
            finish_reason=reason,
            usage=usage,
        ))
        for i, s in enumerate(self.slots):
            if s is seq:
                self.slots[i] = None
        self._by_id.pop(seq.request_id, None)
        # publish full pages for prefix reuse, then drop our references;
        # window-reclaimed tables hold sentinels (K/V gone) — not reusable
        if seq.freed_upto == 0:
            self.allocator.publish(seq.token_ids, seq.block_table)
        self._release_seq(seq)

    def _release_seq(self, seq: _Seq) -> None:
        if seq.block_table:
            sentinel = self.pcfg.num_pages
            live = [p for p in seq.block_table if p != sentinel]
            if live:
                self.allocator.release(live)
            seq.block_table = []
            seq.freed_upto = 0

    def _reclaim_window_pages(self, seq: _Seq) -> None:
        """Sliding-window KV reclaim: pages whose positions are entirely
        behind every future query's window (position <= seq_len - W, with
        seq_len the exact resident count — a lower bound on the device
        position) are released and their table entries set to the
        out-of-range sentinel. Freed slots are never attended again: the
        Pallas kernels skip whole blocks below the window, and the XLA
        gather clamps + masks. Re-prefill after preemption never writes
        through a sentinel (flat slot lands out of range -> dropped).
        Turns per-sequence KV from O(length) into O(window)."""
        W = self.cfg.sliding_window
        if not W or not seq.block_table:
            return
        if seq.prefill_only or seq.exporting:
            # a handoff candidate must keep EVERY page serializable:
            # sentinel-holed tables cannot migrate (and the import-side
            # prefix registration would content-address garbage pages);
            # the same holds while a streamed export is in flight
            return
        if self.cfg.sliding_window_pattern:
            # Gemma-2-style alternating layers: the GLOBAL layers still
            # attend the full history, so no page is ever dead
            return
        ps = self.pcfg.page_size
        sentinel = self.pcfg.num_pages
        limit = seq.seq_len - W + 1  # positions < limit are dead
        freed: List[int] = []
        j = seq.freed_upto
        while j < len(seq.block_table) and (j + 1) * ps <= limit:
            page = seq.block_table[j]
            if page != sentinel:
                freed.append(page)
                seq.block_table[j] = sentinel
            j += 1
        seq.freed_upto = j
        if freed:
            self._event("reclaim", len(freed))
            self.allocator.release(freed)

    # ------------------------------------------------------------------
    # paging helpers
    # ------------------------------------------------------------------

    def _preempt_youngest(self, outputs: List[StepOutput]) -> None:
        """Release the youngest active sequence back to the waiting queue
        (its pages freed) to relieve page pressure."""
        youngest: Optional[_Seq] = None
        for s in self.slots:
            if s is not None and (
                youngest is None or s.num_output_tokens() < youngest.num_output_tokens()
            ):
                youngest = s
        if youngest is not None:
            self._preempt(youngest, outputs)

    def _preempt(self, seq: _Seq, outputs: List[StepOutput]) -> None:
        # only called with the pipeline drained (_maybe_launch), so the host
        # state below is exact, not a lagging projection
        self._event("preempt")
        for i, s in enumerate(self.slots):
            if s is seq:
                self.slots[i] = None
                self._deact_slot(i)
        self._release_seq(seq)
        seq.seq_len = 0
        seq.dev_pos = 0
        seq.dev_steps_left = 0
        # between steps the sampled-but-undecoded token is never in
        # token_ids; fold it in so re-prefill resumes exactly where we left
        if seq.next_token is not None:
            seq.token_ids.append(seq.next_token)
            seq.next_token = None
        self.waiting.appendleft(seq)

    def _slots_for_positions(
        self, table: List[int], positions: np.ndarray, valid: int
    ) -> np.ndarray:
        ps = self.pcfg.page_size
        out = np.full_like(positions, self._num_slots_flat)
        flat = positions[0]
        for j in range(valid):
            pos = int(flat[j])
            page = pos // ps
            if page < len(table):
                out[0, j] = table[page] * ps + pos % ps
        return out

    def _gather_slots(
        self, tables: List[List[int]], width_pages: Optional[int] = None
    ) -> np.ndarray:
        """[B, width_pages * page_size] flat slots covering each row's
        block table (padded with slot 0; masked by kv_valid_len).
        ``width_pages`` defaults to the full per-sequence capacity; the
        prefill quantum passes the live bucket instead so short contexts
        never gather (or pay attention HBM traffic for) S_max slots."""
        ps = self.pcfg.page_size
        B = max(len(tables), 1)
        W = width_pages or self.pcfg.max_pages_per_seq
        out = np.zeros((B, W * ps), np.int32)
        offs = np.arange(ps, dtype=np.int32)
        for b, table in enumerate(tables):
            for p, page in enumerate(table[:W]):
                out[b, p * ps : (p + 1) * ps] = page * ps + offs
        return out

    def _pages_bucket(self, pages: int) -> int:
        """Power-of-two page-count bucket (min 8) for the gather width:
        compiled programs are keyed on the bucketed block-table shape, so
        growth costs at most log2(max_pages_per_seq) compiles while the
        per-step gather/attention window tracks the LIVE maximum context
        instead of the configured capacity (8192 slots at serving
        defaults — paying that per decode step regardless of actual
        lengths was the XLA path's scalability flaw)."""
        cap = self.pcfg.max_pages_per_seq
        b = 8
        while b < pages:
            b *= 2
        return min(b, cap)

    def _gather_pages(self, live_pages: int, prefill: bool) -> int:
        """Block-table width to upload for a launch. Bucketing only pays
        on the XLA gather path (it bounds the dense [B, S] materialization
        + attention window); the Pallas kernels read exactly the valid
        pages whatever the table width, and the "auto" probe validates
        them ONLY at full capacity — so any launch that can reach a
        Pallas kernel keeps the probed full-width shape. That includes
        decode launches under a MIXED resolution (decode=xla,
        prefill=pallas): the speculative block's gamma+1 verify forward
        inside a decode launch dispatches by T to the prefill kernel.
        Prefill launches also stay full width: their gather materializes
        once per admitted chunk (not per decode step), and a single
        shape keeps warmup coverage exact."""
        if prefill:
            return self.pcfg.max_pages_per_seq
        impl = self._resolved_impl()
        impls = (impl,) if isinstance(impl, str) else impl
        if "pallas" in impls:
            return self.pcfg.max_pages_per_seq
        return self._pages_bucket(live_pages)

    # ------------------------------------------------------------------
    # embeddings (the /embeddings endpoint's compute)
    # ------------------------------------------------------------------

    def embed_start(self, ids_list: List[List[int]]) -> "_EmbedState":
        """Begin an incremental embeddings computation: inputs longer than
        the largest prefill bucket split into bucket-sized chunks, all
        chunks form a flat work list processed ``max_batch`` rows per
        ``embed_step`` call. The serving runner interleaves steps with
        decode so a large embeddings batch never stalls generation
        (VERDICT r1: embeddings ran whole on the engine thread)."""
        max_bucket = self.ecfg.prefill_buckets[-1]
        work: List[Tuple[int, List[int]]] = []
        for b, row in enumerate(ids_list):
            for start in range(0, len(row), max_bucket):
                work.append((b, row[start : start + max_bucket]))
        return _EmbedState(
            work=work,
            sums=np.zeros((len(ids_list), self.cfg.hidden_size), np.float32),
            counts=np.zeros((len(ids_list),), np.float32),
        )

    def embed_step(self, state: "_EmbedState") -> bool:
        """Process one device batch of the work list; True when done."""
        if state.idx >= len(state.work):
            return True
        batch = state.work[state.idx : state.idx + self.ecfg.max_batch]
        state.idx += len(batch)
        bucket = self._pick_bucket(max(len(c) for _, c in batch))
        B = len(batch)
        ids = np.zeros((B, bucket), np.int32)
        lens = np.zeros((B,), np.int32)
        for j, (_, chunk) in enumerate(batch):
            ids[j, : len(chunk)] = chunk
            lens[j] = len(chunk)
        h = llama.hidden_states(
            self.params,
            self.cfg,
            jnp.asarray(ids),
            jnp.broadcast_to(jnp.arange(bucket), (B, bucket)),
            jnp.asarray(lens),
        )
        h = np.asarray(h)
        mask = (np.arange(bucket)[None, :] < lens[:, None]).astype(np.float32)
        for j, (b, _) in enumerate(batch):
            state.sums[b] += (h[j] * mask[j][:, None]).sum(0)
            state.counts[b] += mask[j].sum()
        return state.idx >= len(state.work)

    def embed_finish(self, state: "_EmbedState") -> np.ndarray:
        pooled = state.sums / np.maximum(state.counts, 1.0)[:, None]
        norms = np.linalg.norm(pooled, axis=-1, keepdims=True)
        return pooled / np.maximum(norms, 1e-9)

    def embed_ids(self, ids_list: List[List[int]]) -> np.ndarray:
        """Mean-pooled, L2-normalized final hidden states per input —
        the one-shot convenience form of the incremental API above."""
        state = self.embed_start(ids_list)
        while not self.embed_step(state):
            pass
        return self.embed_finish(state)
