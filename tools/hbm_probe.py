"""Measure achievable HBM bandwidth + MXU throughput on the real chip.

Two probes that bound what any decode step can do:
  1. weight-stream: lax.scan over L stacked [N,N] bf16 weights doing
     x @ W_l — models batched decode (read every weight byte once per
     step). GB/s = L*N*N*2 / t_step.
  2. big matmul: one [M,N]x[N,N] bf16 matmul — MXU TFLOP/s.

Usage (on the chip, alone — one process per chip):
    python tools/hbm_probe.py [batch]
Prints one JSON line per probe; exits 2 when there is no accelerator (a
CPU time is not a device number).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main() -> int:
    from distributed_inference_server_tpu.utils.compile_cache import (
        setup_compile_cache,
    )

    setup_compile_cache()
    if jax.devices()[0].platform == "cpu":
        print(json.dumps({"probe": "hbm",
                          "error": "no accelerator: jax reports cpu"}),
              flush=True)
        return 2
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    N = int(os.environ.get("HP_N", "4096"))
    L = int(os.environ.get("HP_L", "16"))  # 16 * 4096*4096*2B = 512 MiB
    key = jax.random.PRNGKey(0)
    W = jax.random.normal(key, (L, N, N), dtype=jnp.bfloat16)
    x = jax.random.normal(key, (batch, N), dtype=jnp.bfloat16)

    @jax.jit
    def stream(x, W):
        def body(h, w):
            return jnp.tanh(h @ w), ()
        h, _ = jax.lax.scan(body, x, W)
        return h

    stream(x, W).block_until_ready()
    t0 = time.perf_counter()
    reps = 10
    for _ in range(reps):
        out = stream(x, W)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / reps
    gbs = L * N * N * 2 / dt / 1e9
    print(json.dumps({"probe": "weight_stream", "batch": batch, "L": L,
                      "N": N, "t_ms": round(dt * 1e3, 3),
                      "hbm_gbps": round(gbs, 1)}), flush=True)

    M = N
    A = jax.random.normal(key, (M, N), dtype=jnp.bfloat16)
    B = jax.random.normal(key, (N, N), dtype=jnp.bfloat16)

    @jax.jit
    def mm(a, b):
        return a @ b

    mm(A, B).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = mm(A, B)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / reps
    tf = 2 * M * N * N / dt / 1e12
    print(json.dumps({"probe": "matmul", "M": M, "N": N,
                      "t_ms": round(dt * 1e3, 3),
                      "tflops": round(tf, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
