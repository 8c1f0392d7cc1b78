"""Benchmark: continuous-batching decode throughput on one chip.

Measures BASELINE.md config 2 (single-chip continuous batching) with a
Llama-3.2-1B-shaped model (random bf16 weights — the environment has no
network egress, so no checkpoints; throughput is weight-content-independent).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/s", "vs_baseline": N/2000}
vs_baseline is against the north-star 2000 output tok/s/chip target
(BASELINE.json; the reference itself publishes no numbers — BASELINE.md).

Env knobs: BENCH_BATCH (64), BENCH_PROMPT (128), BENCH_NEW (128),
BENCH_BLOCK (64 burst / 16 when BENCH_RATE_RPS>0, decode steps per
device block), BENCH_PIPELINE (1,
blocks in flight), BENCH_PREFILL_BATCH (16, rows per batched prefill
program), BENCH_PREFILL_BUDGET (8192, prefill tokens per engine step),
BENCH_RATE_RPS (0; >0 switches to steady-state serving mode — requests
arrive at this rate and TTFT is measured from arrival, the number the
p50<200ms target is about), BENCH_IMPL (auto|pallas|xla decode attention),
BENCH_COMPARE (default 1 on hardware: measure BOTH attention impls,
report the better with both numbers in the line; 0 = single BENCH_IMPL
run), BENCH_FORCE_CPU=1 (tiny-model smoke mode), BENCH_CPU_FULL=1
(BASELINE.md config 1: the REAL BENCH_MODEL on the CPU backend, batch 1,
greedy single-request decode, f32 — the CPU-backend baseline config is
measurable with no TPU at all; defaults clamp to prompt 64 / 32 new
tokens so a 1-core run finishes in minutes).
The hardware path refuses to run on the CPU backend (exit 2): a CPU
number never carries the hardware metric name.

Scale knobs (BASELINE.json's metric is tok/s/chip AT 8B — measure it):
BENCH_MODEL (any models/configs.py preset; default llama-3.2-1b),
BENCH_QUANT (none|int8|int4 — weight-only; int8 fits 8B on one v5e:
  BENCH_MODEL=llama-3-8b BENCH_QUANT=int8 BENCH_BATCH=32 python bench.py),
(the roofline estimate printed alongside every hardware run — roofline
tok/s = batch * BW / weight bytes, the weight-read bound a decode step
cannot beat — takes BW from the device_kind-keyed table below; an
unknown device kind is an error),
BENCH_SHARED_PREFIX (0; >0 = first K prompt tokens identical across
  requests, so later requests reuse the prefix pages — the TTFT delta vs
  0 measures the prefix cache, and records carry the allocator hit rate),
BENCH_DRAFT (none|same|self-int8|self-int4 — speculative decoding with a
  draft sharing the target's weights ("same": acceptance 1.0 ceiling) or a
  quantized copy of them ("self-int*": honest sub-1.0 acceptance from
  quantization disagreement, a real self-speculation config),
BENCH_GAMMA (4, draft tokens per speculation round),
BENCH_MEASURE_WARMUP=1 (measure cold first-request TTFT vs a warmed
engine's first request vs steady-state — quantifies engine.warmup()'s
compile amortization instead of asserting it).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

# Peak HBM bandwidth in GB/s by ``jax.devices()[0].device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (819 GB/s per chip). A device
# that is not in the table is an error, not a default.
_PEAK_HBM_GBPS = {"TPU v5 lite": 819.0}  # what jax 0.9 calls a v5e chip


@functools.lru_cache(maxsize=1)
def _git_rev() -> str:
    """Short commit id stamped into every record so a number can always
    be traced to the exact tree that produced it; empty when git is
    unavailable (the record must never fail over provenance). Cached —
    the rev cannot change within a run, and a wedged git must not stall
    every emission."""
    try:
        import subprocess

        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            text=True, stderr=subprocess.DEVNULL, timeout=5,
        ).strip()
    except Exception:
        return ""


def _emit(obj) -> None:
    rev = _git_rev()
    if rev:
        obj.setdefault("rev", rev)
    print(json.dumps(obj), flush=True)


_MODEL_SLUGS = {
    "llama-3.2-1b": "llama1b",
    "llama-3-8b": "llama8b",
    "llama-3-70b": "llama70b",
    "mistral-7b": "mistral7b",
    "qwen2-7b": "qwen7b",
    "gemma2-9b": "gemma9b",
    "mixtral-8x7b": "mixtral",
}


def bench_handoff() -> None:
    """KV-handoff microbench (BENCH_HANDOFF=1; ISSUE 4): sweep sequence
    length x channel x wire_quant x export mode on the tiny CPU fixture,
    emitting one JSON line per config with the STALL (decode pause the
    migrated sequence observes: switchover -> import seated) split from
    the END-TO-END handoff time (which the streamed export mostly
    overlaps with decoding), plus post-quantization bytes moved.

    Engine-level on purpose: two LLMEngine instances and the real
    channel/export/import code paths, no HTTP jitter — the serving-path
    rerun lives in `tools/disagg_smoke.py --bench`.

    Knobs: BENCH_HANDOFF_LENS ("128,400,1024" token sequence lengths),
    BENCH_HANDOFF_REPS (5)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from distributed_inference_server_tpu.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )
    from distributed_inference_server_tpu.engine.kv_cache import (
        PagedCacheConfig,
    )
    from distributed_inference_server_tpu.models import llama
    from distributed_inference_server_tpu.models.configs import TINY
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.serving.disagg import make_channel

    import jax.numpy as jnp

    lens = [int(x) for x in os.environ.get(
        "BENCH_HANDOFF_LENS", "128,400,1024").split(",") if x.strip()]
    reps = int(os.environ.get("BENCH_HANDOFF_REPS", "5"))
    ps = 8
    max_pages = -(-(max(lens) + 256) // ps)
    paged = PagedCacheConfig(num_pages=2 * max_pages + 64, page_size=ps,
                             max_pages_per_seq=max_pages)
    params = llama.init_params(jax.random.PRNGKey(0), TINY,
                               dtype=jnp.float32)

    def mk():
        return LLMEngine(
            params, TINY, ByteTokenizer(),
            EngineConfig(max_batch=4, prefill_buckets=(64, 256), paged=paged),
            dtype=jnp.float32,
        )

    rng = np.random.default_rng(0)

    def prefill(engine, rid, n, budget=512):
        ids = rng.integers(1, min(TINY.vocab_size, 250), size=n).tolist()
        engine.add_request(rid, ids, SamplingParams(
            max_tokens=budget, temperature=0.0), prefill_only=True)
        while not engine.handoff_ready_ids():
            engine.step()

    def one_monolithic(src, dst, chan, rid, n, wq):
        prefill(src, rid, n)
        t0 = time.monotonic()
        exp = src.export_handoff(rid, wire_quant=wq)
        # stall == e2e for the stop-the-world export
        wired = chan.transfer(exp)
        dst.import_sequence(wired)
        t1 = time.monotonic()
        dst.abort(rid)
        return {"stall_s": t1 - t0, "e2e_s": t1 - t0,
                "bytes": exp.kv_bytes(), "chunks": 0}

    def one_streamed(src, dst, chan, rid, n, wq):
        # the serving pipeline's two-phase flow, inline: prefix
        # serializes AND imports on the target during the overlap
        # window; the stall is only the switchover delta
        prefill(src, rid, n)
        t_begin = time.monotonic()
        session = src.export_handoff_begin(rid, chunk_pages=8, wire_quant=wq)
        assert session is not None, "streamed export refused"
        src.step()  # the overlap window: the sequence decodes a block
        src.export_handoff_pump(session)
        wired_prefix = chan.transfer_chunks(rid, wq, session.chunks)
        isess = dst.import_stream_open(rid, len(session.prefix_pages))
        dst.import_stream_add(isess, wired_prefix)
        src.step()  # more overlap while the target absorbs the prefix
        exp, _outputs = src.export_handoff_finish(session)
        assert exp is not None, "sequence resolved in place mid-bench"
        tail = exp.kv_chunks[len(session.chunks):]
        wired = chan.transfer_commit(exp, tail)
        dst.import_stream_commit(isess, wired)
        t1 = time.monotonic()
        dst.abort(rid)
        return {"stall_s": t1 - exp.stalled_at, "e2e_s": t1 - t_begin,
                "bytes": exp.kv_bytes(), "chunks": len(exp.kv_chunks or [])}

    for n in lens:
        src, dst = mk(), mk()
        seq = 0
        for chan_name in ("inproc", "protowire"):
            chan = make_channel(chan_name)
            for wq in ("none", "int8"):
                for mode, fn in (("monolithic", one_monolithic),
                                 ("streamed", one_streamed)):
                    stalls, e2es, rec = [], [], None
                    for r in range(reps + 1):
                        seq += 1
                        rec = fn(src, dst, chan, f"h{seq}", n, wq)
                        if r:  # rep 0 warms compile caches
                            stalls.append(rec["stall_s"])
                            e2es.append(rec["e2e_s"])
                    _emit({
                        "metric": "kv_handoff_stall_ms_tiny_cpu",
                        "value": round(float(np.median(stalls)) * 1e3, 3),
                        "unit": "ms",
                        "vs_baseline": 0.0,
                        "seq_len": n,
                        "channel": chan_name,
                        "wire_quant": wq,
                        "mode": mode,
                        "e2e_ms": round(float(np.median(e2es)) * 1e3, 3),
                        "bytes": rec["bytes"],
                        "chunks": rec["chunks"],
                        "reps": reps,
                    })


def bench_prefix() -> None:
    """Tiered prefix-cache microbench (BENCH_PREFIX=1; ISSUE 5): a
    repeated-prefix workload (one long shared system prefix + unique
    tails, interleaved with short unique "churn" traffic that cycles the
    HBM page pool) measured AFTER an eviction cycle — the regime where
    the HBM-only prefix cache is worthless because the pool already
    recycled the shared pages.

    Per swept config it emits one JSON line with the probe request's
    median TTFT and prefill-tokens-recomputed (prompt length minus the
    pages matched in either tier):

    - mode "cold": never-seen prefix (full prefill — the floor);
    - mode "hbm_only": host_tier_bytes=0 — after churn the prefix pages
      are gone, so this re-pays ~full prefill;
    - mode "tiered": host tier on, swept over budget (generous: holds
      the whole working set / tight: forces front-biased partial
      retention) x storage quant (none | int8).

    Engine-level on purpose (two tiers + the real match/reload path, no
    HTTP jitter). Knobs: BENCH_PREFIX_REPS (5), BENCH_PREFIX_PAGES (24
    shared-prefix pages), BENCH_PREFIX_CHURN (10 unique churn prompts
    per rep)."""
    import gc

    # single-threaded XLA CPU: the thread pool's scheduling jitter on a
    # small host is ±2x PER REP on identical work, drowning the
    # tiered-vs-HBM-only TTFT deltas; one thread is slower but tight
    # (must be set before jax initializes)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_multi_thread_eigen=false"
        + " intra_op_parallelism_threads=1"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import jax.numpy as jnp

    from distributed_inference_server_tpu.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )
    from distributed_inference_server_tpu.engine.kv_cache import (
        PagedCacheConfig,
    )
    from distributed_inference_server_tpu.models import llama
    from distributed_inference_server_tpu.models.configs import TINY
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer

    reps = int(os.environ.get("BENCH_PREFIX_REPS", "5"))
    prefix_pages = int(os.environ.get("BENCH_PREFIX_PAGES", "24"))
    churn_n = int(os.environ.get("BENCH_PREFIX_CHURN", "10"))
    # ~8x TINY's prefill compute (4x layers, 2x width) with only 4x the
    # KV bytes: on TINY itself dispatch noise is the same order as the
    # whole prefill, so the recompute savings the tier buys would drown
    # in jitter; at this scale compute dominates and TTFT separates
    # cleanly while the bench stays CI-runnable on CPU
    mcfg = TINY.with_overrides(
        name="tiny-4l", hidden_size=128, intermediate_size=512,
        num_layers=4, num_heads=8, num_kv_heads=4, head_dim=16,
    )
    ps = 8
    churn_pages = 4
    tail = ps  # unique tail tokens after the shared prefix
    prompt_len = prefix_pages * ps + tail
    # pool sized so one churn phase cycles it past the shared prefix:
    # barely larger than the longest prompt, as a loaded server runs
    paged = PagedCacheConfig(
        num_pages=prefix_pages + 8,
        page_size=ps,
        max_pages_per_seq=prefix_pages + 4,
    )
    params = llama.init_params(jax.random.PRNGKey(0), mcfg,
                               dtype=jnp.float32)
    # page bytes in the f32 pool (k+v), for budget sweeps in page units
    page_bytes = (mcfg.num_layers * ps * mcfg.num_kv_heads * mcfg.head_dim
                  * 4 * 2)
    budgets = {
        # holds the shared prefix AND the churn heads comfortably
        "generous": (prefix_pages + churn_pages * churn_n + 8) * page_bytes,
        # smaller than the shared prefix itself: front-biased retention
        # keeps the chain HEAD, so the probe still skips half the prefill
        "tight": (prefix_pages // 2) * page_bytes,
    }
    rng = np.random.default_rng(7)
    hi = min(mcfg.vocab_size, 250)

    def mk(host_bytes=0, quant="none"):
        return LLMEngine(
            params, mcfg, ByteTokenizer(),
            EngineConfig(max_batch=2, prefill_buckets=(64, 128, 256),
                         paged=paged, host_tier_bytes=host_bytes,
                         host_tier_quant=quant),
            dtype=jnp.float32,
        )

    seq = [0]

    def run(engine, ids, max_tokens=2):
        """Submit one request, drain it, return TTFT seconds."""
        seq[0] += 1
        rid = f"p{seq[0]}"
        t0 = time.perf_counter()
        engine.add_request(rid, ids, SamplingParams(
            max_tokens=max_tokens, temperature=0.0))
        ttft = None
        while engine.has_work():
            for out in engine.step():
                if ttft is None and out.token_id is not None:
                    ttft = time.perf_counter() - t0
        assert ttft is not None
        return ttft

    def compile_warm(engine):
        """Walk every prefill bucket + decode so no measured rep pays
        XLA compile, then drop every cache the warmers left behind."""
        run(engine, rng.integers(1, hi, size=prompt_len).tolist())
        run(engine, rng.integers(1, hi, size=prompt_len // 2).tolist())
        run(engine, rng.integers(1, hi, size=churn_pages * ps).tolist())
        engine.evict_cache(0.0, drop_host_tier=True)

    def probe(engine, prefix_ids):
        """One measured repeated-prefix request after a churn cycle (GC
        held off so a collection pause cannot land inside the TTFT)."""
        s0 = engine.cache_stats()
        host0 = engine.host_tier_stats() or {"hit_pages": 0}
        ids = prefix_ids + rng.integers(1, hi, size=tail).tolist()
        gc.collect()
        gc.disable()
        try:
            ttft = run(engine, ids)
        finally:
            gc.enable()
        s1 = engine.cache_stats()
        host1 = engine.host_tier_stats() or {"hit_pages": 0}
        hbm_pages = s1.hits - s0.hits
        host_pages = host1["hit_pages"] - host0["hit_pages"]
        reloads = engine.drain_reload_durations()
        return {
            "ttft_s": ttft,
            "recompute_tokens": len(ids) - (hbm_pages + host_pages) * ps,
            "hbm_pages": hbm_pages,
            "host_pages": host_pages,
            "reload_ms": round(sum(reloads) * 1e3, 3),
        }

    def churn(engine):
        for _ in range(churn_n):
            run(engine, rng.integers(
                1, hi, size=churn_pages * ps - 2).tolist())

    def measure(mode, host_bytes=0, quant="none", budget_name=None):
        engine = mk(host_bytes=host_bytes, quant=quant)
        compile_warm(engine)
        prefix_ids = rng.integers(1, hi, size=prefix_pages * ps).tolist()
        recs = []
        if mode == "cold":
            for _ in range(reps):
                # never-repeated prefix: every probe is a full prefill
                fresh = rng.integers(1, hi, size=prefix_pages * ps).tolist()
                churn(engine)
                recs.append(probe(engine, fresh))
        else:
            run(engine, prefix_ids
                + rng.integers(1, hi, size=tail).tolist())  # warm
            # one unmeasured cycle: the tier's chain protection needs a
            # first match to mark the prefix chain as re-used traffic
            # (steady state is what repeated-prefix serving runs in)
            churn(engine)
            probe(engine, prefix_ids)
            for _ in range(reps):
                churn(engine)  # cycle the pool: HBM prefix evicted
                recs.append(probe(engine, prefix_ids))
        s = engine.cache_stats()
        host = engine.host_tier_stats()
        _emit({
            "metric": "prefix_probe_ttft_ms_cpu",
            "value": round(
                float(np.median([r["ttft_s"] for r in recs])) * 1e3, 3),
            "unit": "ms",
            "vs_baseline": 0.0,
            "mode": mode,
            **({"host_budget": budget_name,
                "host_budget_bytes": host_bytes,
                "host_quant": quant} if host_bytes else {}),
            "prompt_len": prompt_len,
            "recompute_tokens": int(np.median(
                [r["recompute_tokens"] for r in recs])),
            "matched_hbm_pages": int(np.median(
                [r["hbm_pages"] for r in recs])),
            "matched_host_pages": int(np.median(
                [r["host_pages"] for r in recs])),
            "reload_ms": float(np.median(
                [r["reload_ms"] for r in recs])),
            "evictions": s.evictions,
            **({"host_tier_pages": host["pages"],
                "host_tier_bytes": host["bytes"],
                "host_offloads": host["offloads"],
                "host_evictions": host["evictions"]}
               if host is not None else {}),
            "reps": reps,
        })

    measure("cold")
    measure("hbm_only")
    for budget_name, budget in budgets.items():
        for quant in ("none", "int8"):
            measure("tiered", host_bytes=budget, quant=quant,
                    budget_name=budget_name)


def bench_peerfetch() -> None:
    """Fleet peer-fetch microbench (BENCH_PEERFETCH=1; ISSUE 8): a
    repeated-prefix request lands on a COLD replica while a warm peer
    holds the matched chain. Per swept config — prefix depth (pages) x
    wire quant — the probe's TTFT is measured under each of the cost
    model's three options (docs/CACHING.md "Fleet-wide prefix
    sharing"):

    - mode "recompute": the cold replica prefills the whole prompt (the
      floor the fetch must beat);
    - mode "fetch": the cold replica peer-fetches the chain from the
      warm peer (export -> protowire channel -> import_prefix) and
      prefills only the tail; TTFT INCLUDES the whole fetch;
    - mode "route_warm": the warm replica serves it in place (HBM
      prefix hit — the ceiling fetch cannot beat).

    Engine-level on purpose (the real export/channel/import code paths,
    no HTTP jitter), single-threaded XLA + GC held off and the tiny-4l
    model, exactly like BENCH_PREFIX — at TINY scale dispatch noise
    drowns the prefill-recompute savings being measured. Knobs:
    BENCH_PEERFETCH_REPS (5), BENCH_PEERFETCH_DEPTHS ("8,16,24")."""
    import gc

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_multi_thread_eigen=false"
        + " intra_op_parallelism_threads=1"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import jax.numpy as jnp

    from distributed_inference_server_tpu.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )
    from distributed_inference_server_tpu.engine.kv_cache import (
        PagedCacheConfig,
        chain_hashes,
    )
    from distributed_inference_server_tpu.models import llama
    from distributed_inference_server_tpu.models.configs import TINY
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.serving.disagg import make_channel

    reps = int(os.environ.get("BENCH_PEERFETCH_REPS", "5"))
    depths = [int(x) for x in os.environ.get(
        "BENCH_PEERFETCH_DEPTHS", "8,16,24").split(",") if x.strip()]
    mcfg = TINY.with_overrides(
        name="tiny-4l", hidden_size=128, intermediate_size=512,
        num_layers=4, num_heads=8, num_kv_heads=4, head_dim=16,
    )
    ps = 8
    tail = ps
    max_depth = max(depths)
    paged = PagedCacheConfig(
        num_pages=2 * max_depth + 16,
        page_size=ps,
        max_pages_per_seq=max_depth + 4,
    )
    params = llama.init_params(jax.random.PRNGKey(0), mcfg,
                               dtype=jnp.float32)
    rng = np.random.default_rng(11)
    hi = min(mcfg.vocab_size, 250)
    chan = make_channel("protowire")

    def mk():
        return LLMEngine(
            params, mcfg, ByteTokenizer(),
            EngineConfig(
                max_batch=2,
                prefill_buckets=(16, 64, 128, 256),
                paged=paged, native_allocator=False,
                # the whole swept chain must be visible to the fetch
                digest_depth=max_depth,
            ),
            dtype=jnp.float32,
        )

    seq = [0]

    def run(engine, ids, max_tokens=2):
        seq[0] += 1
        rid = f"pf{seq[0]}"
        t0 = time.perf_counter()
        engine.add_request(rid, ids, SamplingParams(
            max_tokens=max_tokens, temperature=0.0))
        ttft = None
        while engine.has_work():
            for out in engine.step():
                if ttft is None and out.token_id is not None:
                    ttft = time.perf_counter() - t0
        assert ttft is not None
        return ttft

    def compile_warm(engine):
        for n in (max_depth * ps + tail, 2 * ps, tail + ps):
            run(engine, rng.integers(1, hi, size=n).tolist())
        engine.evict_cache(0.0)

    for depth in depths:
        prefix_ids = rng.integers(1, hi, size=depth * ps).tolist()
        warm, cold = mk(), mk()
        compile_warm(warm)
        compile_warm(cold)
        run(warm, prefix_ids + rng.integers(1, hi, size=tail).tolist())
        for wq in ("none", "int8"):
            recs = {"recompute": [], "fetch": [], "route_warm": []}
            fetch_ms, fetch_bytes = [], 0
            for r in range(reps + 1):
                probe = prefix_ids + rng.integers(1, hi,
                                                  size=tail).tolist()
                hashes = chain_hashes(probe, ps,
                                      max_pages=(len(probe) - 1) // ps)
                gc.collect()
                gc.disable()
                try:
                    # recompute floor: the cold replica starts empty
                    cold.evict_cache(0.0)
                    t_rec = run(cold, probe)
                    # fetch: export -> wire -> import -> prefill tail;
                    # TTFT includes the whole fetch
                    cold.evict_cache(0.0)
                    t0 = time.perf_counter()
                    served, chunks = warm.export_prefix_chunks(
                        hashes, chunk_pages=8, wire_quant=wq)
                    wired = chan.transfer_chunks(f"b{seq[0]}", wq, chunks)
                    cold.import_prefix(probe[: served * ps], wired)
                    t_fetch_done = time.perf_counter() - t0
                    t_fet = t_fetch_done + run(cold, probe)
                    # warm ceiling: the peer serves it in place
                    t_warm = run(warm, probe)
                finally:
                    gc.enable()
                if r:  # rep 0 warms compile caches
                    recs["recompute"].append(t_rec)
                    recs["fetch"].append(t_fet)
                    recs["route_warm"].append(t_warm)
                    fetch_ms.append(t_fetch_done * 1e3)
                    fetch_bytes = sum(len(c.payload) for c in wired)
                assert served == (len(probe) - 1) // ps, served
            for mode in ("recompute", "fetch", "route_warm"):
                _emit({
                    "metric": "peerfetch_ttft_ms_cpu",
                    "value": round(
                        float(np.median(recs[mode])) * 1e3, 3),
                    "unit": "ms",
                    "vs_baseline": 0.0,
                    "mode": mode,
                    "prefix_pages": depth,
                    "prompt_len": depth * ps + tail,
                    "wire_quant": wq,
                    **({"fetch_ms": round(float(np.median(fetch_ms)), 3),
                        "fetch_bytes": fetch_bytes}
                       if mode == "fetch" else {}),
                    "reps": reps,
                })


def bench_mixed() -> None:
    """Ragged mixed-batch step microbench (BENCH_MIXED=1; ISSUE 12): a
    mixed long-prompt/chat workload on ONE unified engine — chat rows
    decode continuously while a burst of long prompts arrives — measured
    under the MIXED step (engine.mixed_step_tokens > 0: one ragged
    dispatch per iteration serving decode rows + prefill chunks) vs the
    QUANTUM-INTERLEAVE baseline it replaces (prefill quanta dispatched
    between decode blocks, stalling every in-flight decode for their
    duration).

    Per swept config it emits one JSON line per mode with the chat rows'
    TBT max/p99 observed DURING the prompt burst (the number the mixed
    step exists to flatten), overall tokens/s at the fixed geometry, and
    ``tokens_identical`` — whether the two modes emitted bit-identical
    token streams (greedy workload; the acceptance criterion).

    Engine-level on purpose (no HTTP jitter), single-threaded XLA + the
    tiny-4l model exactly like BENCH_PREFIX — at TINY scale dispatch
    noise drowns the stall being measured. Knobs: BENCH_MIXED_REPS (3),
    BENCH_MIXED_PROMPTS ("64,128" burst prompt lengths),
    BENCH_MIXED_TOKENS (24, the packed width)."""
    import gc

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_multi_thread_eigen=false"
        + " intra_op_parallelism_threads=1"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import jax.numpy as jnp

    from distributed_inference_server_tpu.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )
    from distributed_inference_server_tpu.engine.kv_cache import (
        PagedCacheConfig,
    )
    from distributed_inference_server_tpu.models import llama
    from distributed_inference_server_tpu.models.configs import TINY
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer

    reps = int(os.environ.get("BENCH_MIXED_REPS", "3"))
    prompt_lens = [int(x) for x in os.environ.get(
        "BENCH_MIXED_PROMPTS", "128,256").split(",") if x.strip()]
    mixed_tokens = int(os.environ.get("BENCH_MIXED_TOKENS", "24"))
    n_burst = int(os.environ.get("BENCH_MIXED_BURST", "4"))
    mcfg = TINY.with_overrides(
        name="tiny-4l", hidden_size=128, intermediate_size=512,
        num_layers=4, num_heads=8, num_kv_heads=4, head_dim=16,
    )
    ps = 8
    n_chat = 3
    chat_len, chat_tokens = ps, 64
    max_pages = -(-(max(prompt_lens) + 64) // ps)
    paged = PagedCacheConfig(
        num_pages=(n_chat + n_burst + 2) * max_pages, page_size=ps,
        max_pages_per_seq=max_pages,
    )
    params = llama.init_params(jax.random.PRNGKey(0), mcfg,
                               dtype=jnp.float32)
    rng = np.random.default_rng(23)
    hi = min(mcfg.vocab_size, 250)

    def mk(mixed: bool):
        return LLMEngine(
            params, mcfg, ByteTokenizer(),
            EngineConfig(
                max_batch=n_chat + n_burst,
                prefill_buckets=(32, 64, 128, 256),
                paged=paged, decode_block_size=4, pipeline_depth=1,
                mixed_step_tokens=mixed_tokens if mixed else 0,
            ),
            dtype=jnp.float32,
        )

    def run_once(engine, chats, prompts):
        """Seat the chat rows, fire the prompt burst, record every chat
        token's wall-clock instant until the burst's prompts finish and
        the chats hit their budget. Returns (events, toks, elapsed)."""
        toks = {}
        times = {f"c{i}": [] for i in range(n_chat)}
        for i, ids in enumerate(chats):
            engine.add_request(f"c{i}", ids, SamplingParams(
                max_tokens=chat_tokens, temperature=0.0))
        # chats seated and decoding before the burst lands
        while not all(times[r] for r in times):
            for out in engine.step():
                if out.token_id is not None:
                    toks.setdefault(out.request_id, []).append(out.token_id)
                    if out.request_id in times:
                        times[out.request_id].append(time.perf_counter())
        t0 = time.perf_counter()
        for i, ids in enumerate(prompts):
            engine.add_request(f"p{i}", ids, SamplingParams(
                max_tokens=4, temperature=0.0))
        produced = 0
        while engine.has_work():
            for out in engine.step():
                if out.token_id is not None:
                    produced += 1
                    toks.setdefault(out.request_id, []).append(out.token_id)
                    if out.request_id in times:
                        times[out.request_id].append(time.perf_counter())
        elapsed = time.perf_counter() - t0
        # TBT of the in-flight chats across the burst window: gaps
        # between consecutive observed tokens from the burst's landing
        # on — anchored at each chat's LAST pre-burst token, so the gap
        # that spans the prompt admission (the stall the mixed step
        # exists to flatten) is measured, not dropped
        tbts = []
        for r, ts in times.items():
            before = [t for t in ts if t < t0]
            after = [t for t in ts if t >= t0]
            anchored = before[-1:] + after
            tbts.extend(np.diff(anchored).tolist())
        return tbts, toks, produced / elapsed

    for n in prompt_lens:
        chats = [rng.integers(1, hi, size=chat_len).tolist()
                 for _ in range(n_chat)]
        prompts = [rng.integers(1, hi, size=n).tolist()
                   for _ in range(n_burst)]
        results = {}
        for mode, mixed in (("quantum", False), ("mixed", True)):
            engine = mk(mixed)
            all_tbts, toks, tput = [], None, []
            for r in range(reps + 1):
                gc.collect()
                gc.disable()
                try:
                    tbts, toks, tp = run_once(engine, chats, prompts)
                finally:
                    gc.enable()
                for rid in list(toks):
                    engine.abort(rid)
                # drop the prefix cache: a warm repeat would skip the
                # very prefill whose stall is being measured
                engine.evict_cache(0.0, drop_host_tier=True)
                if r:  # rep 0 warms compile caches
                    all_tbts.extend(tbts)
                    tput.append(tp)
            results[mode] = {
                "tbt_max_ms": float(np.max(all_tbts)) * 1e3,
                "tbt_p99_ms": float(np.percentile(all_tbts, 99)) * 1e3,
                "tokens_per_sec": float(np.median(tput)),
                "toks": toks,
            }
        identical = results["mixed"]["toks"] == results["quantum"]["toks"]
        for mode in ("quantum", "mixed"):
            r = results[mode]
            _emit({
                "metric": "mixed_step_tbt_p99_ms_cpu",
                "value": round(r["tbt_p99_ms"], 3),
                "unit": "ms",
                "vs_baseline": 0.0,
                "mode": mode,
                "prompt_len": n,
                "burst_prompts": n_burst,
                "chat_rows": n_chat,
                "mixed_step_tokens": mixed_tokens if mode == "mixed" else 0,
                "tbt_max_ms": round(r["tbt_max_ms"], 3),
                "tokens_per_sec": round(r["tokens_per_sec"], 2),
                "tokens_identical": identical,
                "reps": reps,
            })
        if not identical:
            print("BENCH_MIXED: token streams DIVERGED between modes",
                  file=sys.stderr)
            sys.exit(3)


def bench_loop() -> None:
    """Run-to-completion looped decode microbench (BENCH_LOOP=1; ISSUE
    19): a mixed long-prompt/chat workload on ONE unified engine, swept
    over {fixed-K, loop_to_completion} x {plain decode, mixed step at
    K-block fusion}. Per config it emits one JSON line per mode with

    - ``dispatches_per_decode_token`` on the mode's decode-serving path
      (the acceptance number: at K=8 the fused looped mixed step must
      spend >= 4x fewer mixed dispatches per decode token than the
      per-token fixed mixed step),
    - overall tokens/s at the fixed geometry, and
    - ``tokens_identical`` — greedy streams bit-identical to the
      fixed-path baseline of the same workload.

    Engine-level on purpose (no HTTP jitter), single-threaded XLA + the
    tiny-4l model exactly like BENCH_MIXED — at TINY scale a dispatch
    boundary costs more than the flops it frames, which is precisely the
    host-sync overhead kernel looping removes. Knobs: BENCH_LOOP_REPS
    (3), BENCH_LOOP_K (8, decode_block_size = the fusion width),
    BENCH_LOOP_PROMPTS ("128" burst prompt lengths),
    BENCH_LOOP_TOKENS (24, the packed mixed width)."""
    import gc

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_multi_thread_eigen=false"
        + " intra_op_parallelism_threads=1"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import jax.numpy as jnp

    from distributed_inference_server_tpu.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )
    from distributed_inference_server_tpu.engine.kv_cache import (
        PagedCacheConfig,
    )
    from distributed_inference_server_tpu.models import llama
    from distributed_inference_server_tpu.models.configs import TINY
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer

    reps = int(os.environ.get("BENCH_LOOP_REPS", "3"))
    k_block = int(os.environ.get("BENCH_LOOP_K", "8"))
    prompt_lens = [int(x) for x in os.environ.get(
        "BENCH_LOOP_PROMPTS", "128").split(",") if x.strip()]
    mixed_tokens = int(os.environ.get("BENCH_LOOP_TOKENS", "24"))
    n_burst = 4
    mcfg = TINY.with_overrides(
        name="tiny-4l", hidden_size=128, intermediate_size=512,
        num_layers=4, num_heads=8, num_kv_heads=4, head_dim=16,
    )
    ps = 8
    n_chat = 3
    # the chat budget must OUTLIVE the prompt-loading window in the
    # fused mode (K decode tokens per dispatch): a chat that runs dry
    # mid-burst leaves later mixed dispatches with no decode rows,
    # muddying the per-path dispatch ratio being measured; the prompt
    # rows themselves stop after 4 tokens so the mixed window stays
    # dominated by the long-lived chats
    chat_len, chat_tokens = ps, 256
    max_pages = -(-(max(prompt_lens) + chat_tokens + 8) // ps)
    paged = PagedCacheConfig(
        num_pages=(n_chat + n_burst + 2) * max_pages, page_size=ps,
        max_pages_per_seq=max_pages,
    )
    params = llama.init_params(jax.random.PRNGKey(0), mcfg,
                               dtype=jnp.float32)
    rng = np.random.default_rng(23)
    hi = min(mcfg.vocab_size, 250)

    def mk(loop: bool, mixed: bool):
        return LLMEngine(
            params, mcfg, ByteTokenizer(),
            EngineConfig(
                max_batch=n_chat + n_burst,
                prefill_buckets=(32, 64, 128, 256),
                paged=paged, decode_block_size=k_block, pipeline_depth=1,
                mixed_step_tokens=mixed_tokens if mixed else 0,
                loop_to_completion=loop, loop_max_steps=256,
            ),
            dtype=jnp.float32,
        )

    def run_once(engine, chats, prompts):
        """Seat the chats, fire the prompt burst, drain. Returns
        (toks, decode_tokens/s, decode-path dispatches, decode tokens)
        for the burst window on."""
        sc0 = engine.step_clock_stats()["kinds"]
        d0 = {k: v["dispatches"] for k, v in sc0.items()}
        toks = {}
        n_req = len(chats) + len(prompts)
        for i, ids in enumerate(chats):
            engine.add_request(f"c{i}", ids, SamplingParams(
                max_tokens=chat_tokens, temperature=0.0))
        for i, ids in enumerate(prompts):
            engine.add_request(f"p{i}", ids, SamplingParams(
                max_tokens=4, temperature=0.0))
        t0 = time.perf_counter()
        produced = 0
        while engine.has_work():
            for out in engine.step():
                if out.token_id is not None:
                    produced += 1
                    toks.setdefault(out.request_id, []).append(out.token_id)
        elapsed = time.perf_counter() - t0
        sc = engine.step_clock_stats()["kinds"]
        # dispatches on the decode-serving path: every launch that
        # advanced decode rows (prefill-only launches excluded)
        decode_kinds = ("decode_block", "mixed", "loop")
        disp = sum(sc[k]["dispatches"] - d0.get(k, 0)
                   for k in decode_kinds if k in sc)
        decode_toks = produced - n_req  # prefill samples each first token
        ms = engine.mixed_stats()
        return toks, produced / elapsed, disp, decode_toks, ms

    for n in prompt_lens:
        chats = [rng.integers(1, hi, size=chat_len).tolist()
                 for _ in range(n_chat)]
        prompts = [rng.integers(1, hi, size=n).tolist()
                   for _ in range(n_burst)]
        results = {}
        modes = (
            ("fixed", False, False),
            ("loop", True, False),
            ("fixed+mixed", False, True),
            ("loop+mixed", True, True),
        )
        for mode, loop, mixed in modes:
            engine = mk(loop, mixed)
            tput, last = [], None
            for r in range(reps + 1):
                gc.collect()
                gc.disable()
                try:
                    last = run_once(engine, chats, prompts)
                finally:
                    gc.enable()
                toks, tp, disp, decode_toks, ms = last
                for rid in list(toks):
                    engine.abort(rid)
                engine.evict_cache(0.0, drop_host_tier=True)
                if r:  # rep 0 warms compile caches
                    tput.append(tp)
            toks, _, disp, decode_toks, ms = last
            results[mode] = {
                "toks": toks,
                "tokens_per_sec": float(np.median(tput)),
                "dispatches_per_decode_token": disp / max(1, decode_toks),
                "decode_tokens": decode_toks,
                # the acceptance ratio: mixed dispatches per decode
                # token ADVANCED BY THE MIXED PATH (cumulative over the
                # reps — every rep runs the identical workload)
                "mixed_dispatches_per_decode_token": (
                    ms["steps"] / max(1, ms["decode_tokens"])
                    if ms else None),
            }
        ok = True
        for mode in ("loop", "fixed+mixed", "loop+mixed"):
            if results[mode]["toks"] != results["fixed"]["toks"]:
                ok = False
        for mode, loop, mixed in modes:
            r = results[mode]
            _emit({
                "metric": "loop_dispatches_per_decode_token_cpu",
                "value": round(r["dispatches_per_decode_token"], 4),
                "unit": "dispatches/token",
                "vs_baseline": 0.0,
                "mode": mode,
                "k_block": k_block,
                "prompt_len": n,
                "burst_prompts": n_burst,
                "chat_rows": n_chat,
                "mixed_step_tokens": mixed_tokens if mixed else 0,
                "decode_tokens": r["decode_tokens"],
                "tokens_per_sec": round(r["tokens_per_sec"], 2),
                "mixed_dispatches_per_decode_token": (
                    round(r["mixed_dispatches_per_decode_token"], 4)
                    if r["mixed_dispatches_per_decode_token"] is not None
                    else None),
                "tokens_identical": ok,
                "reps": reps,
            })
        if not ok:
            print("BENCH_LOOP: token streams DIVERGED between modes",
                  file=sys.stderr)
            sys.exit(3)
        fused = results["loop+mixed"]["mixed_dispatches_per_decode_token"]
        base = results["fixed+mixed"]["mixed_dispatches_per_decode_token"]
        if fused > base / 4.0:
            print(
                "BENCH_LOOP: mixed-path dispatch collapse below 4x "
                f"({base:.3f} -> {fused:.3f} per decode token)",
                file=sys.stderr)
            sys.exit(4)


def bench_telem() -> None:
    """Telemetry-overhead microbench (BENCH_TELEM=1; ISSUE 14): decode
    tokens/s through a REAL EngineRunner with the performance-telemetry
    plane ON — MetricsCollector (step-clock delta reports + windowed
    digests) plus FlightRecorder with an armed SLO — vs OFF (metrics
    and recorder both None, the identity-check fast path). CPU anchor
    like the other microbenches (single-threaded XLA, tiny-4l, greedy);
    at TINY scale the host-side per-step cost is a LARGER share of the
    step than on real silicon, so the measured overhead upper-bounds
    production. Acceptance: <= 2% decode tokens/s cost.

    Knobs: BENCH_TELEM_REPS (5), BENCH_TELEM_ROWS (4 concurrent
    requests), BENCH_TELEM_TOKENS (192 decode tokens per request)."""
    import gc
    import threading

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_multi_thread_eigen=false"
        + " intra_op_parallelism_threads=1"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import jax.numpy as jnp

    from distributed_inference_server_tpu.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )
    from distributed_inference_server_tpu.engine.kv_cache import (
        PagedCacheConfig,
    )
    from distributed_inference_server_tpu.models import llama
    from distributed_inference_server_tpu.models.configs import TINY
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.serving.flightrec import (
        FlightRecorder,
    )
    from distributed_inference_server_tpu.serving.metrics import (
        MetricsCollector,
    )
    from distributed_inference_server_tpu.serving.runner import (
        EngineRunner,
        ServerRequest,
    )
    from distributed_inference_server_tpu.serving.teledigest import (
        SloSettings,
    )

    reps = int(os.environ.get("BENCH_TELEM_REPS", "5"))
    rows = int(os.environ.get("BENCH_TELEM_ROWS", "4"))
    tokens = int(os.environ.get("BENCH_TELEM_TOKENS", "192"))
    mcfg = TINY.with_overrides(
        name="tiny-4l", hidden_size=128, intermediate_size=512,
        num_layers=4, num_heads=8, num_kv_heads=4, head_dim=16,
    )
    ps = 8
    max_pages = -(-(16 + tokens + ps) // ps)
    paged = PagedCacheConfig(num_pages=(rows + 2) * max_pages,
                             page_size=ps, max_pages_per_seq=max_pages)
    params = llama.init_params(jax.random.PRNGKey(0), mcfg,
                               dtype=jnp.float32)
    rng = np.random.default_rng(14)
    hi = min(mcfg.vocab_size, 250)
    prompts = [[int(t) for t in rng.integers(1, hi, size=16)]
               for _ in range(rows)]

    def factory():
        return LLMEngine(
            params, mcfg, ByteTokenizer(),
            EngineConfig(max_batch=rows, prefill_buckets=(16, 32),
                         paged=paged, decode_block_size=8,
                         warmup_compile=False),
            dtype=jnp.float32,
        )

    class _Sink:
        def __init__(self):
            self.tokens = 0
            self.ev = threading.Event()

        def on_token(self, token_id, text, token_index, logprob=None):
            if token_id is not None:
                self.tokens += 1

        def on_done(self, finish_reason, usage):
            self.ev.set()

        def on_error(self, message, code):
            self.ev.set()

    def run_batch(runner, tag: str) -> float:
        sinks = []
        reqs = []
        for i, prompt in enumerate(prompts):
            sink = _Sink()
            sinks.append(sink)
            reqs.append(ServerRequest(
                f"{tag}-{i}", list(prompt),
                SamplingParams(max_tokens=tokens, temperature=0.0),
                sink))
        t0 = time.perf_counter()
        runner.submit(reqs)
        for sink in sinks:
            assert sink.ev.wait(300.0), "bench request wedged"
        wall = time.perf_counter() - t0
        emitted = sum(s.tokens for s in sinks)
        assert emitted >= rows * (tokens - 1), emitted
        return emitted / wall

    results = {"off": [], "on": []}
    runners = {}
    metrics_on = MetricsCollector()
    recorder_on = FlightRecorder(
        metrics=metrics_on,
        slo=SloSettings(ttft_ms=60_000.0, tbt_p99_ms=60_000.0))
    runners["off"] = EngineRunner("bench-off", factory, None)
    runners["on"] = EngineRunner("bench-on", factory, metrics_on,
                                 recorder=recorder_on)
    try:
        for mode, runner in runners.items():
            runner.start(wait_ready=True)
            run_batch(runner, f"warm-{mode}")  # compile + warm path
        gc.disable()
        try:
            for rep in range(reps):
                # alternate order so drift penalizes neither mode
                order = (["off", "on"] if rep % 2 == 0
                         else ["on", "off"])
                for mode in order:
                    results[mode].append(
                        run_batch(runners[mode], f"r{rep}-{mode}"))
        finally:
            gc.enable()
    finally:
        for runner in runners.values():
            runner.shutdown()

    med_off = sorted(results["off"])[reps // 2]
    med_on = sorted(results["on"])[reps // 2]
    overhead = (med_off - med_on) / med_off * 100.0
    for mode in ("off", "on"):
        print(json.dumps({
            "bench": "telem_overhead", "mode": mode,
            "decode_tokens_per_sec_median": round(
                sorted(results[mode])[reps // 2], 1),
            "runs": [round(x, 1) for x in results[mode]],
            "rows": rows, "tokens": tokens, "reps": reps,
        }))
    print(json.dumps({
        "bench": "telem_overhead", "mode": "summary",
        "overhead_pct": round(overhead, 2),
        "budget_pct": 2.0,
        "within_budget": overhead <= 2.0,
    }))
    # sanity: the ON plane actually recorded — a vacuously fast
    # telemetry path that records nothing would be a broken bench
    perf = metrics_on.perf.wire_digests()
    assert "step_ms.decode_block" in perf, sorted(perf)
    assert "ttft_ms" in perf
    counts, _ = metrics_on.slo_counts()
    assert sum(counts.get("default", {}).values()) >= rows * reps


def bench_latent() -> None:
    """Latent-KV codec microbench (BENCH_LATENT=1; ISSUE 20, TPLA
    stage (a)): sweep rank x wire encoding (none/int8/latent/
    latent_int8) over the three KV byte paths on the tiny CPU fixture —

    - disagg handoff: monolithic export -> import; stall + payload bytes;
    - peer prefix fetch: export_prefix_chunks bytes for a warm chain;
    - host-tier reload: churn the prefix into the tier, re-prefill, and
      read the engine's reload timer + stored tier bytes;

    each emitting one JSON line with ``tokens_identical`` — greedy
    decode of the moved sequence must match the never-moved reference
    at the swept rank (the acceptance tolerance harness; a latent rank
    that flips a token shows up as tokens_identical=false, not a
    silently worse number).

    Knobs: BENCH_LATENT_RANKS ("4,8"; rank sweep for the latent wires —
    none/int8 are rank-independent and run once at rank 0),
    BENCH_LATENT_REPS (3)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp

    from distributed_inference_server_tpu.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )
    from distributed_inference_server_tpu.engine.kv_cache import (
        PagedCacheConfig,
        chain_hashes,
    )
    from distributed_inference_server_tpu.models import llama
    from distributed_inference_server_tpu.models.configs import TINY
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer

    ranks = [int(x) for x in os.environ.get(
        "BENCH_LATENT_RANKS", "4,8").split(",") if x.strip()]
    reps = int(os.environ.get("BENCH_LATENT_REPS", "3"))
    ps = 4
    params = llama.init_params(jax.random.PRNGKey(0), TINY,
                               dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompt = [1 + int(t) for t in rng.integers(0, 200, 88)]  # 22 pages
    hashes = chain_hashes(prompt, ps, max_pages=(len(prompt) - 1) // ps)

    def mk(rank, num_pages=96, **over):
        return LLMEngine(
            params, TINY, ByteTokenizer(),
            EngineConfig(max_batch=4, prefill_buckets=(8, 128),
                         paged=PagedCacheConfig(num_pages=num_pages,
                                                page_size=ps,
                                                max_pages_per_seq=32),
                         latent_rank=rank, native_allocator=False, **over),
            dtype=jnp.float32,
        )

    def run(engine, rid, ids, max_tokens=8):
        engine.add_request(rid, ids, SamplingParams(
            max_tokens=max_tokens, temperature=0.0))
        toks = []
        while engine.has_work():
            for o in engine.step():
                if o.token_id is not None:
                    toks.append(o.token_id)
        return toks

    ref = mk(0)
    want = run(ref, "ref", prompt)
    sweep = [("none", 0), ("int8", 0)] + [
        (wq, r) for r in ranks for wq in ("latent", "latent_int8")]

    for wq, rank in sweep:
        # path 1: handoff (stall = export -> import seated)
        stalls, nbytes, identical = [], 0, True
        src, dst = mk(rank), mk(rank)
        for rep in range(reps + 1):
            rid = f"{wq}{rank}h{rep}"
            got = []
            src.add_request(rid, prompt, SamplingParams(
                max_tokens=8, temperature=0.0), prefill_only=True)
            while src.has_work() and not src.handoff_ready_ids():
                for o in src.step():
                    if o.token_id is not None:
                        got.append(o.token_id)
            t0 = time.monotonic()
            exp = src.export_handoff(rid, wire_quant=wq)
            dst.import_sequence(exp)
            t1 = time.monotonic()
            while dst.has_work():
                for o in dst.step():
                    if o.token_id is not None:
                        got.append(o.token_id)
            identical &= got == want
            nbytes = len(exp.kv)
            if rep:  # rep 0 warms compile caches
                stalls.append(t1 - t0)
        _emit({
            "metric": "kv_latent_handoff_stall_ms_tiny_cpu",
            "value": round(float(np.median(stalls)) * 1e3, 3),
            "unit": "ms", "vs_baseline": 0.0, "wire_quant": wq,
            "rank": rank, "bytes": nbytes, "tokens_identical": identical,
            "reps": reps,
        })

        # path 2: peer prefix fetch (bytes on the wire + token identity)
        warm = mk(rank)
        run(warm, "warm", prompt)
        depth, chunks = warm.export_prefix_chunks(hashes, chunk_pages=2,
                                                  wire_quant=wq)
        target = mk(rank)
        target.import_prefix(prompt[: depth * ps], chunks)
        _emit({
            "metric": "kv_latent_fetch_bytes_tiny_cpu",
            "value": sum(len(c.payload) for c in chunks),
            "unit": "bytes", "vs_baseline": 0.0, "wire_quant": wq,
            "rank": rank, "pages": depth,
            "tokens_identical": run(target, "probe", prompt) == want,
        })

        # path 3: host-tier reload (stored tier encoding = the wire);
        # the pool holds ONE resident sequence (22-page prompt + decode)
        # plus a little headroom, so churn demotes the warm prefix
        tier = mk(rank, num_pages=30, host_tier_bytes=1 << 22,
                  host_tier_quant=wq)
        run(tier, "seed", prompt)
        for i in range(6):  # churn the 12-page pool: the prefix demotes
            run(tier, f"churn{i}",
                rng.integers(100, 200, size=7).tolist(), max_tokens=2)
        tier.host_tier.flush()
        tier.drain_reload_durations()
        got = run(tier, "probe", prompt)
        reloads = tier.drain_reload_durations()
        st = tier.host_tier_stats() or {}
        _emit({
            "metric": "kv_latent_hosttier_reload_ms_tiny_cpu",
            "value": round(sum(reloads) * 1e3, 3),
            "unit": "ms", "vs_baseline": 0.0, "wire_quant": wq,
            "rank": rank, "tier_bytes": st.get("bytes", 0),
            "tier_pages": st.get("pages", 0),
            "hit_pages": st.get("hit_pages", 0),
            "tokens_identical": got == want,
        })


def main() -> None:
    if os.environ.get("BENCH_HANDOFF") == "1":
        bench_handoff()
        return
    if os.environ.get("BENCH_LATENT") == "1":
        bench_latent()
        return
    if os.environ.get("BENCH_TELEM") == "1":
        bench_telem()
        return
    if os.environ.get("BENCH_MIXED") == "1":
        bench_mixed()
        return
    if os.environ.get("BENCH_LOOP") == "1":
        bench_loop()
        return
    if os.environ.get("BENCH_PREFIX") == "1":
        bench_prefix()
        return
    if os.environ.get("BENCH_PEERFETCH") == "1":
        bench_peerfetch()
        return
    force_cpu = os.environ.get("BENCH_FORCE_CPU") == "1"
    cpu_full = os.environ.get("BENCH_CPU_FULL") == "1"
    model_name = os.environ.get("BENCH_MODEL", "llama-3.2-1b")
    quant = os.environ.get("BENCH_QUANT", "none")
    slug = _MODEL_SLUGS.get(
        model_name, "".join(c for c in model_name if c.isalnum())
    )
    if force_cpu:
        metric = "decode_tokens_per_sec_tiny_cpu"
    elif cpu_full:
        # BASELINE.md config 1: real model, CPU backend, single request
        metric = f"decode_tokens_per_sec_{slug}_f32_cpu_single"
    else:
        metric = "decode_tokens_per_sec_%s_%s" % (
            slug, quant if quant != "none" else "bf16"
        )
    batch = int(os.environ.get("BENCH_BATCH", "1" if cpu_full else "64"))
    prompt_len = int(os.environ.get(
        "BENCH_PROMPT", "64" if cpu_full else "128"
    ))
    new_tokens = int(os.environ.get(
        "BENCH_NEW", "32" if cpu_full else "128"
    ))
    rate_rps = float(os.environ.get("BENCH_RATE_RPS", "0"))
    # burst mode runs long blocks (which block length is best is not
    # measured on current code); in steady-state rate mode the host
    # blocks a full fixed-length device block per _process_block, so a
    # large block quantum would dominate the TTFT being measured — rate
    # mode keeps the small block unless overridden
    block = int(os.environ.get(
        "BENCH_BLOCK", "16" if rate_rps > 0 else "64"
    ))
    pipeline = int(os.environ.get("BENCH_PIPELINE", "1"))
    prefill_batch = int(os.environ.get("BENCH_PREFILL_BATCH", "16"))
    prefill_budget = int(os.environ.get("BENCH_PREFILL_BUDGET", "8192"))
    impl = os.environ.get("BENCH_IMPL", "auto")
    # speculative decoding: "same" shares the target's weight arrays
    # (acceptance 1.0 — mechanism proof / ceiling), "self-int8"/"self-int4"
    # draft with a quantized copy of the SAME weights — a genuinely
    # cheaper forward whose argmax mostly-but-not-always agrees with the
    # bf16 target, i.e. an honest sub-1.0 acceptance measurable with
    # random weights (no checkpoint download exists in this environment)
    draft_mode = os.environ.get("BENCH_DRAFT", "none")
    gamma = int(os.environ.get("BENCH_GAMMA", "4"))
    kv_quant = os.environ.get("BENCH_KV_QUANT", "none")
    # shared-prefix mode: every request's first K prompt tokens are
    # identical, so requests after the first reuse the prefix pages
    # (content-addressed page sharing — reference Req 4.1/Property 9);
    # the TTFT delta vs BENCH_SHARED_PREFIX=0 is the prefix cache's
    # measured value, and the record carries the allocator's hit rate
    shared_prefix = int(os.environ.get("BENCH_SHARED_PREFIX", "0"))
    if force_cpu and cpu_full:
        _emit({
            "metric": metric, "value": 0.0, "unit": "tokens/s",
            "vs_baseline": 0.0,
            "error": "BENCH_FORCE_CPU and BENCH_CPU_FULL are mutually "
                     "exclusive (tiny smoke vs real-model CPU baseline)",
        })
        sys.exit(2)
    if cpu_full and batch != 1:
        # the metric name says _single; a batched run under it would lie
        _emit({
            "metric": metric, "value": 0.0, "unit": "tokens/s",
            "vs_baseline": 0.0,
            "error": "BENCH_CPU_FULL is the single-request baseline "
                     f"(config 1); BENCH_BATCH must be 1, got {batch}",
        })
        sys.exit(2)
    if cpu_full and quant != "none":
        # BASELINE config 1 is the f32 CPU baseline; a quantized run
        # under the _f32_cpu_single metric name would lie
        _emit({
            "metric": metric, "value": 0.0, "unit": "tokens/s",
            "vs_baseline": 0.0,
            "error": "BENCH_CPU_FULL is the f32 CPU baseline (config 1); "
                     "BENCH_QUANT must be none",
        })
        sys.exit(2)
    if shared_prefix < 0:
        _emit({
            "metric": metric, "value": 0.0, "unit": "tokens/s",
            "vs_baseline": 0.0,
            "error": f"BENCH_SHARED_PREFIX must be >= 0, got {shared_prefix}",
        })
        sys.exit(2)
    if shared_prefix > 0 and os.environ.get("BENCH_MEASURE_WARMUP") == "1":
        # the warmup path builds its own unshared prompts; a record
        # labelled _prefixK for a run that shared nothing would lie
        _emit({
            "metric": metric, "value": 0.0, "unit": "tokens/s",
            "vs_baseline": 0.0,
            "error": "BENCH_SHARED_PREFIX and BENCH_MEASURE_WARMUP are "
                     "mutually exclusive (warmup prompts are unshared)",
        })
        sys.exit(2)
    # validation happens here (fail in milliseconds, before weight init)
    if kv_quant not in ("none", "int8"):
        _emit({
            "metric": metric, "value": 0.0, "unit": "tokens/s",
            "vs_baseline": 0.0,
            "error": f"unknown BENCH_KV_QUANT {kv_quant!r}; known: none|int8",
        })
        sys.exit(2)
    # suffixes attach immediately after their own validation so every
    # later error record (unknown draft, no accelerator, bad model)
    # carries the already-validated config it was measuring;
    # kv/spec are clamp-INDEPENDENT (force_cpu never alters them) —
    # only _prefixK waits for the post-clamp prompt_len/page_size
    if kv_quant != "none":
        metric += "_kv" + kv_quant
    if draft_mode not in ("none", "same", "self-int8", "self-int4"):
        # validate at parse time: an unknown value must fail in
        # milliseconds, not after minutes of 8B weight init
        _emit({
            "metric": metric, "value": 0.0, "unit": "tokens/s",
            "vs_baseline": 0.0,
            "error": f"unknown BENCH_DRAFT {draft_mode!r}; "
                     "known: none|same|self-int8|self-int4",
        })
        sys.exit(2)
    if draft_mode != "none":
        metric += "_spec_" + draft_mode.replace("self-", "self")

    import jax

    if force_cpu or cpu_full:
        jax.config.update("jax_platforms", "cpu")
    from distributed_inference_server_tpu.utils.compile_cache import (
        setup_compile_cache,
    )

    setup_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    device_kind = devices[0].device_kind
    if platform == "cpu" and not (force_cpu or cpu_full):
        # the hardware metric name must never carry a CPU number: jax
        # falls back to the CPU silently when it finds no accelerator
        _emit({
            "metric": metric, "value": 0.0, "unit": "tokens/s",
            "vs_baseline": 0.0,
            "error": "no accelerator: jax fell back to the CPU backend "
                     "(BENCH_FORCE_CPU=1 / BENCH_CPU_FULL=1 are the CPU "
                     "modes, under their own metric names)",
        })
        sys.exit(2)
    if platform != "cpu" and device_kind not in _PEAK_HBM_GBPS:
        _emit({
            "metric": metric, "value": 0.0, "unit": "tokens/s",
            "vs_baseline": 0.0,
            "error": f"no peak-bandwidth entry for device_kind "
                     f"{device_kind!r}; known: {sorted(_PEAK_HBM_GBPS)}",
        })
        sys.exit(2)

    import jax.numpy as jnp
    import numpy as np

    from distributed_inference_server_tpu.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )
    from distributed_inference_server_tpu.engine.kv_cache import PagedCacheConfig
    from distributed_inference_server_tpu.models import llama
    from distributed_inference_server_tpu.models.configs import TINY, get_config
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.ops.quant import (
        init_random_quantized,
    )

    if force_cpu:
        cfg, dtype = TINY, jnp.float32
        prompt_len, new_tokens = min(prompt_len, 16), min(new_tokens, 16)
        # clamp the block too: warmup() needs max_seq_len (64 here) to
        # cover block+1 steps, or every warmup request is skipped and the
        # smoke mode silently stops exercising the warmup machinery
        block = min(block, 8)
        paged = PagedCacheConfig(num_pages=64, page_size=8, max_pages_per_seq=8)
        buckets = (32, 64)
    else:
        try:
            cfg = get_config(model_name)
        except KeyError as e:
            # keep the always-emit-JSON contract of the other error paths
            _emit({
                "metric": metric, "value": 0.0, "unit": "tokens/s",
                "vs_baseline": 0.0, "error": str(e),
            })
            sys.exit(2)
        # CPU-backend baseline (config 1) runs f32 — oneDNN's fast path;
        # bf16 matmuls take a slow emulation route on CPU
        dtype = jnp.float32 if cpu_full else jnp.bfloat16
        pages_per_seq = -(-(prompt_len + new_tokens + 16) // 16)
        paged = PagedCacheConfig(
            num_pages=(batch + 2) * pages_per_seq + 16,
            page_size=16,
            max_pages_per_seq=pages_per_seq,
        )
        buckets = (prompt_len, max(256, prompt_len))

    shared_prefix = min(shared_prefix, prompt_len)
    if shared_prefix > 0:
        metric += f"_prefix{shared_prefix}"
        # the post-prefix residual chunk needs its OWN prefill bucket:
        # without it the residual pads up to the full prompt bucket and
        # runs the exact same device program as an unshared prompt,
        # reducing the measured "prefix cache benefit" to host-side page
        # bookkeeping noise. Prefix matching shares whole PAGES only, so
        # the real residual is prompt_len minus the matched full pages —
        # and when every page would match, the engine holds one back
        # (the divergence page), leaving a one-page residual.
        matched = (shared_prefix // paged.page_size) * paged.page_size
        resid = prompt_len - matched
        if resid <= 0:
            resid = paged.page_size
        buckets = tuple(sorted(set(buckets) | {resid}))

    if quant != "none":
        # quantized leaves are created directly (no dense intermediate):
        # 8B bf16 (~16 GB) would not fit one v5e chip, 8B int8 (~8 GB) does
        params = init_random_quantized(
            jax.random.PRNGKey(0), cfg, quant, dtype=dtype
        )
    else:
        params = llama.init_params(jax.random.PRNGKey(0), cfg, dtype=dtype)
    jax.block_until_ready(params)

    draft_params = None
    if draft_mode == "same":
        draft_params = params  # shared arrays: no extra weight HBM
    elif draft_mode in ("self-int8", "self-int4"):
        if quant != "none":
            _emit({
                "metric": metric, "value": 0.0, "unit": "tokens/s",
                "vs_baseline": 0.0,
                "error": "BENCH_DRAFT=self-int* requires BENCH_QUANT=none "
                         "(the draft is quantized FROM the bf16 target)",
            })
            sys.exit(2)
        from distributed_inference_server_tpu.ops.quant import (
            quantize_params,
        )
        draft_params = quantize_params(params, draft_mode[len("self-"):])
        jax.block_until_ready(draft_params)

    # HBM roofline: every decode step reads every weight byte once, so
    # steps/s <= BW / weight_bytes and tok/s <= batch * steps/s
    weight_bytes = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(params)
    )
    hbm_gbps = _PEAK_HBM_GBPS.get(device_kind, 0.0)  # 0: CPU rows, unused
    roofline = batch * hbm_gbps * 1e9 / max(1, weight_bytes)
    rng = np.random.default_rng(0)

    def mk_engine(use_impl: str) -> "LLMEngine":
        # single construction site: warmup mode and throughput mode must
        # measure the SAME engine configuration
        kw = {}
        if draft_params is not None:
            from distributed_inference_server_tpu.engine.speculative import (
                SpecConfig,
            )

            kw = dict(
                draft_params=draft_params, draft_cfg=cfg,
                spec=SpecConfig(num_draft_tokens=gamma),
            )
        return LLMEngine(
            params, cfg, ByteTokenizer(),
            EngineConfig(
                max_batch=batch, prefill_buckets=buckets, paged=paged,
                attention_impl=use_impl, decode_block_size=block,
                pipeline_depth=pipeline, prefill_batch=prefill_batch,
                prefill_token_budget=prefill_budget, kv_quant=kv_quant,
            ),
            dtype=dtype,
            **kw,
        )

    warmup_metric = metric.replace(
        "decode_tokens_per_sec", "warmup_first_request_ttft"
    )
    if os.environ.get("BENCH_MEASURE_WARMUP") == "1":
        # Quantify the warmup machinery (engine.warmup docstring claims
        # first-request compile ~20-40s on TPU; VERDICT r2 weak #9 — the
        # benefit was never measured): cold first-request TTFT (pays
        # tracing + XLA compile) vs the same engine's second request vs
        # a warmed engine's FIRST request. No persistent compile cache is
        # set here, so each engine's compiles are honest.
        seq = [0]

        def first_ttft(engine) -> float:
            seq[0] += 1
            ids = rng.integers(
                1, min(cfg.vocab_size, 250), size=prompt_len
            ).tolist()
            t0 = time.perf_counter()
            engine.add_request(
                f"wu{seq[0]}", ids,
                SamplingParams(max_tokens=8, temperature=0.0),
            )
            ttft = None
            while engine.has_work():
                for out in engine.step():
                    if ttft is None and out.token_id is not None:
                        ttft = time.perf_counter() - t0
            assert ttft is not None
            return ttft

        try:
            cold_engine = mk_engine(impl)
            cold = first_ttft(cold_engine)
            steady = first_ttft(cold_engine)
            # release the first engine's KV pool + executables before
            # building the second: at 8B-int8 two live engines would
            # overshoot one chip's HBM
            del cold_engine
            warmed_engine = mk_engine(impl)
            t0 = time.perf_counter()
            warmed_engine.warmup()
            warmup_s = time.perf_counter() - t0
            warmed = first_ttft(warmed_engine)
        except Exception as e:  # same always-emit contract as run paths
            _emit({
                "metric": warmup_metric, "value": 0.0, "unit": "s",
                "vs_baseline": 0.0, "attention_impl": impl,
                "error": str(e).split("\n")[0][:200],
            })
            sys.exit(3)
        _emit({
            "metric": warmup_metric,
            "value": round(warmed, 4),
            "unit": "s",
            # >= 1 means the <200ms first-token target is met (matching
            # the throughput emissions' higher-is-better convention)
            "vs_baseline": round(0.2 / max(warmed, 1e-9), 4),
            "platform": platform,
            "model": cfg.name,
            **({"quant": quant} if quant != "none" else {}),
            "cold_first_ttft_s": round(cold, 4),
            "steady_ttft_s": round(steady, 4),
            "warmup_duration_s": round(warmup_s, 4),
            "compile_cost_amortized_s": round(cold - warmed, 4),
        })
        return

    def run_once(use_impl: str) -> dict:
        engine = mk_engine(use_impl)

        hi = min(cfg.vocab_size, 250)
        prefix_ids = rng.integers(
            1, hi, size=min(shared_prefix, prompt_len)
        ).tolist()

        def add(rid: str, n_new: int):
            ids = prefix_ids + rng.integers(
                1, hi, size=prompt_len - len(prefix_ids)
            ).tolist()
            engine.add_request(rid, ids, SamplingParams(
                max_tokens=n_new, temperature=0.0, top_p=1.0))

        def drain(t_start=None, first_token_at=None):
            tokens = 0
            while engine.has_work():
                for out in engine.step():
                    if out.token_id is not None:
                        tokens += 1
                        if first_token_at is not None and \
                                out.request_id not in first_token_at:
                            first_token_at[out.request_id] = (
                                time.perf_counter() - t_start)
            return tokens

        # warm-up at FULL length: decode gather windows are bucketed by
        # live page count, so a full-length generation walks (and
        # compiles) every bucket the timed run will hit
        add("warmup", new_tokens)
        drain()

        ttfts = {}
        if rate_rps > 0.0:
            # steady-state serving mode: requests arrive at rate_rps
            # (uniform spacing) and TTFT is measured from each request's
            # ARRIVAL — the continuous-batching number the p50<200ms
            # north star is about, not the all-at-once cold burst below
            total = batch * 2  # enough arrivals to reach steady state
            arrival_at = {f"r{i}": i / rate_rps for i in range(total)}
            pending = sorted(arrival_at, key=arrival_at.get)
            produced = 0
            t0 = time.perf_counter()
            while pending or engine.has_work():
                now = time.perf_counter() - t0
                while pending and arrival_at[pending[0]] <= now:
                    add(pending.pop(0), new_tokens)
                outs = engine.step()
                for out in outs:
                    if out.token_id is not None:
                        produced += 1
                        rid = out.request_id
                        if rid not in ttfts:
                            ttfts[rid] = (
                                time.perf_counter() - t0 - arrival_at[rid]
                            )
                if not outs:
                    # nothing surfaced this pass — sleep toward the next
                    # arrival instead of hot-spinning the host between
                    # events (the spin perturbs the TTFT being measured);
                    # a device block may still be in flight, so cap the
                    # nap well under a block's service time
                    wait = (
                        arrival_at[pending[0]] - (time.perf_counter() - t0)
                        if pending else 0.005
                    )
                    if engine.has_work():
                        wait = min(wait, 0.001)
                    if wait > 0:
                        time.sleep(min(0.005, wait))
            elapsed = time.perf_counter() - t0
        else:
            for i in range(batch):
                add(f"r{i}", new_tokens)
            t0 = time.perf_counter()
            produced = drain(t0, ttfts)
            elapsed = time.perf_counter() - t0
        ttft_sorted = sorted(ttfts.values())
        cache = None
        if shared_prefix > 0:
            cs = engine.cache_stats()
            cache = {
                "hits": cs.hits,
                "misses": cs.misses,
                "hit_rate": round(
                    cs.hits / max(1, cs.hits + cs.misses), 4
                ),
            }
        spec = None
        ss = engine.spec_stats()
        if ss is not None:
            spec = {
                "gamma": ss["num_draft_tokens"],
                "acceptance_rate": ss["acceptance_rate"],
                # emitted tokens per TARGET forward (incl. the bonus
                # token) — the speculative speedup factor
                "tokens_per_target_forward": ss["estimated_speedup"],
                "enabled": ss["enabled"],
            }
        return {
            "tput": produced / elapsed,
            "total_tokens": produced,
            "spec": spec,
            "cache": cache,
            "elapsed_s": round(elapsed, 3),
            "p50_ttft_s": round(
                ttft_sorted[len(ttft_sorted) // 2], 3
            ) if ttft_sorted else 0.0,
            "p99_ttft_s": round(
                ttft_sorted[min(len(ttft_sorted) - 1,
                                int(0.99 * len(ttft_sorted)))], 3,
            ) if ttft_sorted else 0.0,
        }

    extra = {}
    # compare defaults ON for hardware runs — but an explicit BENCH_IMPL
    # means "measure exactly this path", so it turns compare off unless
    # BENCH_COMPARE=1 is also explicit
    compare = os.environ.get(
        "BENCH_COMPARE",
        "0" if force_cpu or cpu_full or "BENCH_IMPL" in os.environ
        else "1",
    )
    if compare == "1":
        # measure BOTH attention impls (default on hardware); report the
        # better one and carry the comparison in the same line (VERDICT
        # r1: "auto" must be justified by a number). A failing impl —
        # e.g. a Mosaic rejection on a forced Pallas path — records 0
        # with its error instead of sinking the whole bench.
        results = {}
        for i in ("xla", "pallas"):
            try:
                results[i] = run_once(i)
            except Exception as e:
                results[i] = {"tput": 0.0, "total_tokens": 0,
                              "elapsed_s": 0.0, "p50_ttft_s": 0.0,
                              "p99_ttft_s": 0.0}
                extra[f"{i}_error"] = str(e).split("\n")[0][:200]
        impl = max(results, key=lambda i: results[i]["tput"])
        r = results[impl]
        extra.update({
            "xla_tokens_per_sec": round(results["xla"]["tput"], 2),
            "pallas_tokens_per_sec": round(results["pallas"]["tput"], 2),
        })
        if all(res["tput"] == 0.0 for res in results.values()):
            # both paths died: emit an explicit error record and exit
            # nonzero
            _emit({
                "metric": metric,
                "value": 0.0, "unit": "tokens/s", "vs_baseline": 0.0,
                "error": "both attention impls failed", **extra,
            })
            sys.exit(3)
        # one leg died: the surviving leg's record is still emitted
        # below, but the run as a whole failed
        exit_code = 3 if any(f"{i}_error" in extra for i in results) else 0
    else:
        exit_code = 0
        try:
            r = run_once(impl)
        except Exception as e:
            # same structured-error contract as the both-failed path:
            # always emit a JSON record
            _emit({
                "metric": metric,
                "value": 0.0, "unit": "tokens/s", "vs_baseline": 0.0,
                "attention_impl": impl,
                "error": str(e).split("\n")[0][:200],
            })
            sys.exit(3)

    tput = r["tput"]
    _emit({
        # steady-state (arrival-limited) runs get their own metric name:
        # their throughput reflects offered load, not engine capacity,
        # and must not be trended against the burst-mode number
        "metric": metric + ("_steady" if rate_rps > 0 else ""),
        "value": round(tput, 2),
        "unit": "tokens/s",
        "vs_baseline": round(tput / 2000.0, 4),
        "platform": platform,
        "model": cfg.name,
        **({"quant": quant} if quant != "none" else {}),
        **({"kv_quant": kv_quant} if kv_quant != "none" else {}),
        **({"shared_prefix": shared_prefix, "prefix_cache": r["cache"]}
           if r.get("cache") else {}),
        **({"draft": draft_mode, "spec": r["spec"]}
           if r.get("spec") else {}),
        "weight_bytes": weight_bytes,
        # the roofline is an HBM-bandwidth bound — meaningless for CPU
        # rows (smoke/config-1), where emitting it would hand consumers
        # a nonsense value/roofline ratio
        **({"roofline_tokens_per_sec": round(roofline, 1)}
           if platform != "cpu" else {}),
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "decode_block": block,
        "pipeline_depth": pipeline,
        "attention_impl": impl,
        "total_tokens": r["total_tokens"],
        "elapsed_s": r["elapsed_s"],
        "p50_ttft_s": r["p50_ttft_s"],
        "p99_ttft_s": r["p99_ttft_s"],
        **({"rate_rps": rate_rps} if rate_rps > 0 else {}),
        **extra,
    })
    if exit_code:
        sys.exit(exit_code)


if __name__ == "__main__":
    main()
