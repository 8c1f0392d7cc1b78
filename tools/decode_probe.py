"""Pure-device decode-block probe: the model forward, minus the engine.

Measures a jitted lax.scan of `block` decode steps over llama.paged_forward
at bench geometry (greedy argmax feeding back), with no engine machinery,
no host uploads inside the loop, and no sampling tail beyond argmax. The
delta between this and bench.py's tok/s is, by construction, the cost of
everything the engine adds (host loop, uploads, logprob reads, nucleus
sampling, detok). hbm_probe.py bounds this number from above.

Usage (on the chip, alone — one process per chip):
    python tools/decode_probe.py [batch] [ctx] [block]
Prints one JSON line per attention impl; exits 1 if either impl failed,
2 when there is no accelerator (a CPU time is not a device number).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main() -> int:
    from distributed_inference_server_tpu.utils.compile_cache import (
        setup_compile_cache,
    )

    setup_compile_cache()
    if jax.devices()[0].platform == "cpu":
        print(json.dumps({"probe": "decode_block",
                          "error": "no accelerator: jax reports cpu"}),
              flush=True)
        return 2
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    ctx = int(sys.argv[2]) if len(sys.argv) > 2 else 272
    block = int(sys.argv[3]) if len(sys.argv) > 3 else 64

    from distributed_inference_server_tpu.models import llama
    from distributed_inference_server_tpu.models.configs import get_config

    cfg = get_config(os.environ.get("DP_MODEL", "llama-3.2-1b"))
    dtype = jnp.bfloat16
    params = llama.init_params(jax.random.PRNGKey(0), cfg, dtype=dtype)
    jax.block_until_ready(params)
    weight_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))

    page = 16
    pages_per_seq = (ctx + block + page - 1) // page + 1
    num_pages = batch * pages_per_seq + 1
    slots = num_pages * page
    L = cfg.num_layers
    pool_k = jnp.zeros((L, slots, cfg.num_kv_heads, cfg.head_dim), dtype)
    pool_v = jnp.zeros((L, slots, cfg.num_kv_heads, cfg.head_dim), dtype)
    # row b owns pages [b*pps, (b+1)*pps): contiguous, non-overlapping
    gather = np.zeros((batch, pages_per_seq * page), np.int32)
    for b in range(batch):
        gather[b] = b * pages_per_seq * page + np.arange(pages_per_seq * page)
    gather_j = jnp.asarray(gather)

    @functools.partial(jax.jit, static_argnames=("impl",))
    def decode_block(params, pool_k, pool_v, tokens, start_pos, impl):
        def body(carry, _):
            pool_k, pool_v, tokens, pos = carry
            write = gather_j[jnp.arange(batch), pos][:, None]
            logits, pool_k, pool_v = llama.paged_forward(
                params, cfg, tokens[:, None], pos[:, None],
                pool_k, pool_v, write, gather_j, pos + 1,
                attention_impl=impl, page_size=page,
            )
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            return (pool_k, pool_v, nxt, pos + 1), nxt

        (pool_k, pool_v, tokens, pos), outs = jax.lax.scan(
            body, (pool_k, pool_v, tokens, start_pos), None, length=block
        )
        return pool_k, pool_v, tokens, pos, outs

    tokens = jnp.ones((batch,), jnp.int32)
    start = jnp.full((batch,), ctx, jnp.int32)

    ok = True
    for impl in ("xla", "pallas"):
        try:
            t0 = time.perf_counter()
            r = decode_block(params, pool_k, pool_v, tokens, start, impl)
            jax.block_until_ready(r)
            compile_s = time.perf_counter() - t0
            reps = 3
            t0 = time.perf_counter()
            for _ in range(reps):
                r = decode_block(params, pool_k, pool_v, tokens, start, impl)
            jax.block_until_ready(r)
            dt = (time.perf_counter() - t0) / reps
            # DP_TRACE=1: capture a device trace of ONE extra block (to
            # name the per-step cost on the chip, op by op). Deliberately
            # OUTSIDE the timed reps, so step_ms is an untraced number.
            # The trace lands under chiprun_out/, the one directory the
            # chip tool brings back.
            if os.environ.get("DP_TRACE") == "1":
                trace_dir = os.path.join(
                    os.path.dirname(__file__), "..", "chiprun_out",
                    "traces", f"decode_probe_{impl}_b{batch}_ctx{ctx}",
                )
                jax.profiler.start_trace(trace_dir)
                try:
                    jax.block_until_ready(decode_block(
                        params, pool_k, pool_v, tokens, start, impl
                    ))
                finally:
                    jax.profiler.stop_trace()
            step_ms = dt / block * 1e3
            print(json.dumps({
                "probe": "decode_block", "impl": impl, "batch": batch,
                "ctx": ctx, "block": block,
                "compile_s": round(compile_s, 1),
                "block_ms": round(dt * 1e3, 2),
                "step_ms": round(step_ms, 3),
                "tok_per_s": round(batch / (step_ms / 1e3), 1),
                "eff_hbm_gbps": round(weight_bytes / (step_ms / 1e3) / 1e9, 1),
            }), flush=True)
        except Exception as e:
            ok = False
            print(json.dumps({"probe": "decode_block", "impl": impl,
                              "error": str(e).split("\n")[0][:200]}),
                  flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
