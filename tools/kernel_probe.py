"""Pallas kernel probe: does every kernel the repo ships compile on the
chip, and does what it computes there agree with the XLA reference?

For each geometry — the Llama-3.2-1B serving geometry (32/8 heads,
head_dim 64) and one head_dim-128 geometry (Mistral/Qwen2: 32/8, D=128),
both at the default engine sizes from ``serving/config.py`` (max_batch
64, page_size 16, num_pages 2048, max_pages_per_seq 512, prefill buckets
32/128/512 at prefill_batch 16) — lower and compile with
``interpret=False``, run, and compare against the XLA reference on the
same inputs:

- ``paged_attention_decode`` (bf16 pool, and the int8 ``QuantPool``
  variant),
- ``paged_attention_prefill`` at each prefill bucket,
- ``paged_attention_ragged`` at a 512-token mixed-step width,
- ``ops/pallas/fused.py``: RMSNorm, RoPE, int8 and int4 dequant-matmul.

Interpret mode cannot stand in for this: it accepts shapes Mosaic
rejects, and its clamping gathers hide out-of-bounds scalar reads.

Run on the chip, alone (one process per chip):
    python tools/kernel_probe.py
Prints one JSON line per (geometry, kernel) — ``compiled``, and then
either ``mosaic_error`` (Mosaic's message, verbatim) or ``max_abs_err``,
``agrees`` and the blocking time of one call — and writes the same lines
to ``chiprun_out/kernel_probe.jsonl``. Exits 1 on any rejection or
mismatch, 2 when there is no TPU.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

GEOMETRIES = {
    "llama-3.2-1b": dict(H=32, KV=8, D=64, hidden=2048),
    "d128-32/8": dict(H=32, KV=8, D=128, hidden=4096),
}
MIXED_WIDTH = 512  # packed tokens per mixed step (decode rows + chunks)
# bf16 operands, f32 accumulation: the tolerance tests/ use for bf16
# kernel-vs-reference comparisons (test_pallas_paged_attention.py)
TOL = 5e-2


def _time_ms(fn, n: int = 10) -> float:
    import jax

    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn())
    return round((time.perf_counter() - t0) / n * 1e3, 3)


def probe_geometry(name: str, geo: dict, eng: dict, interpret: bool = False,
                   mixed_width: int = MIXED_WIDTH):
    """Yield one record per kernel at this geometry. ``interpret`` and
    ``mixed_width`` exist so tests/test_chip_smoke.py can exercise the
    probe's own logic off-chip at a tiny size; ``main`` uses the
    defaults."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_inference_server_tpu.models import llama
    from distributed_inference_server_tpu.ops.attention import (
        gqa_attention,
        ragged_gqa_attention,
    )
    from distributed_inference_server_tpu.ops.norms import rms_norm
    from distributed_inference_server_tpu.ops.pallas import (
        apply_rope_pallas,
        paged_attention_decode,
        paged_attention_prefill,
        paged_attention_ragged,
        quant_matmul_pallas,
        rms_norm_pallas,
    )
    from distributed_inference_server_tpu.ops.quant import (
        QuantPool,
        dequantize,
        dequantize_kv,
        quantize_int4,
        quantize_int8,
        quantize_kv,
    )
    from distributed_inference_server_tpu.ops.rotary import (
        apply_rope,
        rope_frequencies,
    )

    H, KV, D, hidden = geo["H"], geo["KV"], geo["D"], geo["hidden"]
    B, ps, P = eng["max_batch"], eng["page_size"], eng["max_pages_per_seq"]
    num_pages, Bp = eng["num_pages"], eng["prefill_batch"]
    smax = P * ps
    dtype = jnp.bfloat16
    dpb, ppb, qb = llama.pallas_tuning()  # what serving launches
    rng = np.random.default_rng(0)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    pool_k, pool_v = normal(num_pages * ps, KV, D), normal(num_pages * ps,
                                                           KV, D)

    def tables_for(rows: int):
        # rows share pages (64 x 512 slots > 2048 pages): attention only
        # reads, so aliasing is harmless
        return jnp.asarray(rng.integers(0, num_pages, (rows, P)), jnp.int32)

    def gather(pool, tables, pages):
        slots = (tables[:, :pages, None] * ps
                 + jnp.arange(ps)[None, None, :]).reshape(
                     tables.shape[0], pages * ps)
        return pool[slots]

    def record(kernel, run, ref, **shape):
        """Compile + run ``run()``; compare with ``ref()``."""
        rec = {"geometry": name, "kernel": kernel, "H": H, "KV": KV, "D": D,
               **shape}
        try:
            got = jax.block_until_ready(run())
        except Exception as e:  # noqa: BLE001 — Mosaic's text is the result
            rec.update(compiled=False, mosaic_error=str(e))
            return rec
        rec["compiled"] = True
        try:
            want = ref()
        except Exception as e:  # noqa: BLE001 — e.g. reference OOM
            rec.update(agrees=False, xla_error=str(e).split("\n")[0][:300])
            return rec
        err = float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32))))
        rec.update(max_abs_err=round(err, 5), agrees=bool(err <= TOL),
                   finite=bool(jnp.isfinite(got.astype(jnp.float32)).all()),
                   pallas_ms=_time_ms(run))
        return rec

    # ---- decode: ragged lengths incl. a full-length row and a 1-token row
    tables = tables_for(B)
    valid_np = rng.integers(1, smax + 1, B)
    valid_np[0], valid_np[1] = smax, 1
    valid = jnp.asarray(valid_np, jnp.int32)
    q1 = normal(B, H, D)
    ref_decode = jax.jit(lambda k, v: gqa_attention(
        q1[:, None], gather(k, tables, P), gather(v, tables, P),
        (valid - 1)[:, None], valid)[:, 0])
    yield record(
        "decode",
        lambda: paged_attention_decode(
            q1, pool_k, pool_v, tables, valid, page_size=ps,
            pages_per_block=dpb, interpret=interpret),
        lambda: ref_decode(pool_k, pool_v), B=B, P=P,
    )

    kq, ks = quantize_kv(pool_k)
    vq, vs = quantize_kv(pool_v)
    yield record(
        "decode_int8_pool",
        lambda: paged_attention_decode(
            q1, QuantPool(kq, ks), QuantPool(vq, vs), tables, valid,
            page_size=ps, pages_per_block=dpb, interpret=interpret),
        lambda: ref_decode(dequantize_kv(kq, ks, dtype),
                           dequantize_kv(vq, vs, dtype)), B=B, P=P,
    )

    # ---- chunked prefill at each bucket; reference row by row (the
    # [B, H, T, S_max] f32 score tensor of one call would not fit)
    ptables = tables_for(Bp)
    for T in eng["prefill_buckets"]:
        pvalid_np = rng.integers(T, smax + 1, Bp)
        pvalid_np[0], pvalid_np[1] = smax, T
        pvalid = jnp.asarray(pvalid_np, jnp.int32)
        qstart = pvalid - T
        qT = normal(Bp, T, H, D)

        @jax.jit
        def ref_row(q, tab, vl, qs):
            pos = qs[:, None] + jnp.arange(q.shape[1])[None]
            return gqa_attention(q, gather(pool_k, tab, P),
                                 gather(pool_v, tab, P), pos, vl)

        yield record(
            f"prefill_T{T}",
            lambda: paged_attention_prefill(
                qT, pool_k, pool_v, ptables, qstart, pvalid, page_size=ps,
                q_block=qb, pages_per_block=ppb, interpret=interpret),
            lambda: jnp.concatenate([
                ref_row(qT[b:b + 1], ptables[b:b + 1], pvalid[b:b + 1],
                        qstart[b:b + 1]) for b in range(Bp)]),
            B=Bp, T=T, P=P,
        )

    # ---- ragged mixed step: B decode rows + prefill chunks packed to S.
    # Contexts stay <= ref_pages pages so the reference's per-token
    # gather fits; the kernel still gets the full-width [Bm, P] table.
    S = mixed_width
    n_chunks = min(Bp, S - B)
    Bm = B + n_chunks
    ref_pages = min(P, 64)
    chunk = (S - B) // n_chunks
    q_lens = [1] * B + [chunk] * n_chunks
    rvalid_np = np.array(
        [rng.integers(ql, ref_pages * ps + 1) for ql in q_lens])
    tok_row_np = np.full((S,), -1, np.int64)
    q_pos_np = np.zeros((S,), np.int64)
    off = 0
    for r, ql in enumerate(q_lens):
        tok_row_np[off:off + ql] = r
        q_pos_np[off:off + ql] = rvalid_np[r] - ql + np.arange(ql)
        off += ql
    rtables = tables_for(Bm)
    tok_row = jnp.asarray(tok_row_np, jnp.int32)
    q_pos = jnp.asarray(q_pos_np, jnp.int32)
    rvalid = jnp.asarray(rvalid_np, jnp.int32)
    qS = normal(S, H, D)
    live = tok_row_np >= 0  # padding tokens' outputs are garbage by contract
    ref_ragged = jax.jit(lambda: ragged_gqa_attention(
        qS, gather(pool_k, rtables, ref_pages),
        gather(pool_v, rtables, ref_pages), tok_row, q_pos, rvalid))
    yield record(
        "ragged",
        lambda: paged_attention_ragged(
            qS, pool_k, pool_v, rtables, tok_row, q_pos, rvalid,
            page_size=ps, q_block=qb, pages_per_block=ppb,
            interpret=interpret)[live],
        lambda: ref_ragged()[live], S=S, Bm=Bm, P=P,
    )

    # ---- fused non-attention kernels at decode-row shapes
    x2 = normal(B, hidden)
    wn = jnp.asarray(rng.standard_normal((hidden,), np.float32))
    yield record(
        "rms_norm",
        lambda: rms_norm_pallas(x2, wn, 1e-5, interpret=interpret),
        jax.jit(lambda: rms_norm(x2, wn, 1e-5)), M=B, hidden=hidden,
    )
    q4 = normal(B, 1, H, D)
    posd = jnp.asarray(rng.integers(0, smax, (B, 1)), jnp.int32)
    inv = rope_frequencies(D, theta=500000.0)
    yield record(
        "rope",
        lambda: apply_rope_pallas(q4, posd, inv, interpret=interpret),
        jax.jit(lambda: apply_rope(q4, posd, inv)), M=B * H,
    )
    w = jnp.asarray(rng.standard_normal((hidden, hidden), np.float32)
                    * hidden ** -0.5)
    for kname, wq, packed in (
        ("q8_matmul", quantize_int8(w), False),
        ("q4_matmul", quantize_int4(w), True),
    ):
        group = hidden // wq.s.shape[-2]
        yield record(
            kname,
            lambda: quant_matmul_pallas(x2, wq.q, wq.s, group=group,
                                        packed=packed, interpret=interpret),
            jax.jit(lambda: x2 @ dequantize(wq, dtype)), M=B, K=hidden,
            N=hidden,
        )


def engine_defaults() -> dict:
    """The default engine geometry the server starts with."""
    from distributed_inference_server_tpu.serving.config import ServerConfig

    cfg = ServerConfig.load(cli_args=[])
    return {k: cfg.get("engine", k) for k in (
        "max_batch", "page_size", "num_pages", "max_pages_per_seq",
        "prefill_batch", "prefill_buckets")}


def main() -> int:
    import jax

    from distributed_inference_server_tpu.utils.compile_cache import (
        setup_compile_cache,
    )

    setup_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"kernel_probe needs a TPU; jax reports "
                                   f"{dev.platform}"}), flush=True)
        return 2
    # the fused kernels' XLA comparators go through norms.rms_norm /
    # rotary.apply_rope, whose dispatch would route to the Pallas kernels
    # if the opt-in flag were set in this shell — comparing Pallas with
    # itself
    os.environ["DIS_TPU_PALLAS_FUSED"] = "0"
    eng = engine_defaults()
    out_dir = os.path.join(os.path.dirname(__file__), "..", "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    ok = True
    with open(os.path.join(out_dir, "kernel_probe.jsonl"), "w") as f:
        for name, geo in GEOMETRIES.items():
            for rec in probe_geometry(name, geo, eng):
                rec.update(platform=dev.platform, device_kind=dev.device_kind)
                line = json.dumps(rec)
                print(line, flush=True)
                f.write(line + "\n")
                ok = ok and rec["compiled"] and rec["agrees"] \
                    and rec["finite"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
