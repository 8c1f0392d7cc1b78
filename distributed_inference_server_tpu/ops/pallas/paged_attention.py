"""Ragged paged-attention decode kernel (Pallas / Mosaic TPU), v2.

The serving hot loop's attention: one new query token per sequence attends
to that sequence's KV pages scattered through the HBM page pool. The
pure-XLA path (``models/llama.py:paged_forward``) first gathers every
sequence's pages into a dense ``[B, S_max, KV, D]`` buffer and then runs
dense attention — materializing S_max slots per row in HBM each step and
paying the write+read round trip. This kernel reads pages straight from
the pool instead.

Decode kernel v3 design (v1 drowned in grid overhead — B x P grid steps
of one page each; v2 blocked the DMA but sliced 64-wide per-head lane
windows, which Mosaic rejects for head_dim-64 models — "slice shape must
be aligned to tiling (128)"):

- **Grid = (B,)**: one grid step per sequence; the page loop runs inside
  the kernel as a ``fori_loop`` with a *dynamic* trip count covering only
  the row's valid pages — rows attend exactly as far as they are long
  (the ragged contract), and short rows cost proportionally less.
- **Manual double-buffered DMA**: the page pools stay in HBM
  (``memory_space=ANY``); each loop iteration copies a *block* of
  ``pages_per_block`` pages (chosen by the scalar-prefetched block table)
  into one of two VMEM buffers with ``make_async_copy`` while the MXU
  works on the previous block — the classic overlap pattern, with
  per-page semaphores because the pages are scattered.
- **Block-diagonal GQA**: pages are DMA'd with heads folded into lanes
  ([page_size, KV*D] — always 128-aligned for serving geometries), and
  the query enters pre-expanded to a block-diagonal [H, KV*D] so the
  whole batch of heads is TWO aligned MXU dots per KV block: scores
  [H,KV*D]x[T,KV*D]^T and values [H,T]x[T,KV*D]. No per-head slicing
  anywhere in the kernel; the wrapper extracts each head's diagonal
  lane block afterwards. The KV-fold multiplies attention FLOPs by KV,
  which is free in practice: decode attention is HBM-DMA-bound and the
  tiny per-head matmuls of v2 were far below MXU tile size anyway.
- **bf16 on the MXU**: q/k/v enter the dots in their native dtype with
  ``preferred_element_type=f32`` accumulation.
- Online-softmax accumulation (flash-attention style) across blocks in
  f32 VMEM scratch; causal masking implied by the ragged ``kv_valid_len``
  (the query IS the last valid token — decode only).

Replaces the reference's planned llama.cpp attention (design.md:7 [spec])
as the native tier; same contract as ops/attention.py:gqa_attention.
Kernel shape follows the ragged-paged-attention recipe (PAPERS.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128  # VPU lane width; scratch statistics are broadcast across lanes


def _decode_kernel(
    # scalar-prefetch refs (SMEM)
    tables_ref,  # [B, P] page id per (row, page-slot)
    valid_ref,  # [B] valid token count per row
    window_ref,  # [1] sliding window (0 = full causal; runtime so Gemma-2
    #              per-layer windows flow through one compiled program)
    # tensor refs, then scratch — layout depends on `quantized`:
    #   dense:  qbd, k_hbm, v_hbm, out,
    #           k_buf, v_buf, sem_k, sem_v, m, l, acc
    #   int8:   qbd, k_hbm, v_hbm, ks_hbm, vs_hbm, out,
    #           k_buf, v_buf, ks_buf, vs_buf,
    #           sem_k, sem_v, sem_ks, sem_vs, m, l, acc
    # where ks/vs are the QuantPool scale pages [num_pages, ps, KV] f32
    # and k/v carry int8 codes (engine/kv_cache.py QuantPool layout)
    *refs,
    page_size: int,
    pages_per_block: int,
    num_page_slots: int,
    head_dim: int,
    attn_softcap: float = 0.0,
    quantized: bool = False,
):
    """v3 body: block-diagonal GQA — every shape Mosaic-tile-aligned.

    The query arrives pre-expanded (host XLA) to [H, KV*D], row h = kv*G+g
    holding q_h in lanes [kv*D, (kv+1)*D) and zeros elsewhere. One
    [H, KV*D] x [KV*D, T] MXU dot then yields exactly the per-head scores
    (zero lanes null the cross-head terms) without slicing the KV/head
    dimension anywhere — the per-head lane slices of v2 were 64-wide for
    head_dim-64 models, which Mosaic rejects (tiling is 128). The extra
    FLOPs (contraction over KV*D instead of D) are irrelevant: decode
    attention is DMA-bound, the MXU idles either way.

    Int8 mode (``quantized``): K/V pages carry int8 codes and separate
    per-(token, head) f32 scale pages ride their own (much smaller) DMAs —
    HALF the attention DMA bytes, the bound this kernel lives under. The
    codes are cast to bf16 for the MXU and the scales are folded in
    WITHOUT any lane-crossing reshape: score[h, t] needs k_scale[t, kv(h)]
    and the PV accumulation needs probs[h, t] * v_scale[t, kv(h)], both
    of which are one [H, KV] x [KV, T] one-hot MXU dot per block (the
    head->kv map) multiplied elementwise into the score/prob matrix.
    Cross-head lanes of the accumulator pick up wrongly-scaled garbage —
    exactly the lanes the wrapper already discards."""
    if quantized:
        (qbd_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, out_ref,
         k_buf, v_buf, ks_buf, vs_buf, sem_k, sem_v, sem_ks, sem_vs,
         m_ref, l_ref, acc_ref) = refs
    else:
        (qbd_ref, k_hbm, v_hbm, out_ref,
         k_buf, v_buf, sem_k, sem_v, m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    PB = pages_per_block
    blk_tokens = PB * page_size

    valid = valid_ref[b]
    num_blocks = lax.div(valid + blk_tokens - 1, blk_tokens)
    # sliding window: the decode query sits at position valid-1, so only
    # tokens >= valid - window are attended; skip whole blocks below it.
    # win_lo stays 0 for full-causal layers, making the mask a no-op.
    w = window_ref[0]
    win_lo = jnp.where(w > 0, jnp.maximum(valid - w, 0), 0)
    first_block = lax.div(win_lo, blk_tokens)

    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def start_block(slot, blk):
        # PB scattered pages -> PB independent DMAs into adjacent buffer
        # rows; page ids come from the scalar-prefetched table (clamped by
        # the driver, so entries past the row's last page are in-range and
        # merely masked at compute time)
        for i in range(PB):
            page = tables_ref[b, jnp.minimum(blk * PB + i,
                                             num_page_slots - 1)]
            pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[slot, i], sem_k.at[slot, i]
            ).start()
            pltpu.make_async_copy(
                v_hbm.at[page], v_buf.at[slot, i], sem_v.at[slot, i]
            ).start()
            if quantized:
                pltpu.make_async_copy(
                    ks_hbm.at[page], ks_buf.at[slot, i], sem_ks.at[slot, i]
                ).start()
                pltpu.make_async_copy(
                    vs_hbm.at[page], vs_buf.at[slot, i], sem_vs.at[slot, i]
                ).start()

    def wait_block(slot, blk):
        for i in range(PB):
            page = tables_ref[b, jnp.minimum(blk * PB + i,
                                             num_page_slots - 1)]
            pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[slot, i], sem_k.at[slot, i]
            ).wait()
            pltpu.make_async_copy(
                v_hbm.at[page], v_buf.at[slot, i], sem_v.at[slot, i]
            ).wait()
            if quantized:
                pltpu.make_async_copy(
                    ks_hbm.at[page], ks_buf.at[slot, i], sem_ks.at[slot, i]
                ).wait()
                pltpu.make_async_copy(
                    vs_hbm.at[page], vs_buf.at[slot, i], sem_vs.at[slot, i]
                ).wait()

    @pl.when(num_blocks > first_block)
    def _run():
        qbd = qbd_ref[0] * (1.0 / (head_dim**0.5))  # [H, KV*D]
        if quantized:
            # head -> kv-head map as a one-hot [H, KV] (static iota
            # compare): row h = kv*G + g selects column kv
            H, CD = qbd_ref.shape[1], qbd_ref.shape[2]
            KV = CD // head_dim
            G = H // KV
            head_onehot = (
                lax.broadcasted_iota(jnp.int32, (H, KV), 0) // G
                == lax.broadcasted_iota(jnp.int32, (H, KV), 1)
            ).astype(jnp.float32)
        start_block(lax.rem(first_block, 2), first_block)

        def loop(blk, _):
            slot = lax.rem(blk, 2)

            @pl.when(blk + 1 < num_blocks)
            def _prefetch():
                start_block(lax.rem(blk + 1, 2), blk + 1)

            wait_block(slot, blk)
            start = blk * blk_tokens

            k = k_buf[slot].reshape(blk_tokens, -1)  # [T, KV*D]
            v = v_buf[slot].reshape(blk_tokens, -1)
            if quantized:
                k = k.astype(jnp.bfloat16)
                v = v.astype(jnp.bfloat16)

            # [H, T] scores in ONE MXU dot; block-diagonal q rows contract
            # only their own head's lanes
            s = lax.dot_general(
                qbd.astype(k.dtype), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if quantized:
                # fold k scales in: score[h, t] *= k_scale[t, kv(h)],
                # realized as onehot[H, KV] @ kscale[T, KV]^T — one tiny
                # MXU dot, no lane-crossing reshape
                ksc = ks_buf[slot].reshape(blk_tokens, -1)  # [T, KV]
                s = s * lax.dot_general(
                    head_onehot, ksc, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            if attn_softcap:
                s = jnp.tanh(s * (1.0 / attn_softcap)) * attn_softcap
            token_ids = start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            ok = (token_ids < valid) & (token_ids >= win_lo)
            s = jnp.where(ok, s, _NEG_INF)

            m_prev = m_ref[:, :1]  # [H, 1]
            l_prev = l_ref[:, :1]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            probs = jnp.exp(s - m_new)  # [H, T] f32
            l_new = l_prev * alpha + jnp.sum(probs, -1, keepdims=True)
            if quantized:
                # fold v scales into the probabilities: row h's own-head
                # lanes then accumulate sum(p * v_scale * codes) exactly;
                # cross-head lanes get wrongly-scaled garbage the wrapper
                # discards anyway
                vsc = vs_buf[slot].reshape(blk_tokens, -1)  # [T, KV]
                probs = probs * lax.dot_general(
                    head_onehot, vsc, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            # [H, KV*D]: row h accumulates its own head's V in the diagonal
            # lane block (other lanes carry cross-head garbage the wrapper
            # discards)
            acc_ref[:] = acc_ref[:] * alpha + lax.dot_general(
                probs.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
            return 0

        lax.fori_loop(first_block, num_blocks, loop, 0)

    l = jnp.maximum(l_ref[:, :1], 1e-30)  # rows with valid=0 emit zeros
    out_ref[0] = (acc_ref[:] / l).astype(out_ref.dtype)


def _prefill_kernel(
    # scalar-prefetch refs (SMEM)
    tables_ref,  # [B, P] page id per (row, page-slot)
    valid_ref,  # [B] valid token count per row (incl. this chunk)
    qstart_ref,  # [B] global position of the chunk's first query
    window_ref,  # [1] sliding window (0 = full causal; runtime scalar)
    # tensor refs
    qbd_ref,  # [1, 1, R, CD] this (row, head-chunk, q-block)'s
    #           block-diagonal query tile (VMEM); R = TQ*C*G
    k_hbm,  # [num_pages, page_size, KV*D] full K pool (HBM)
    v_hbm,  # [num_pages, page_size, KV*D] full V pool (HBM)
    out_ref,  # [1, 1, R, CD] (VMEM; per-head diagonal lanes valid)
    # scratch
    k_buf,  # [2, PB, page_size, CD] double-buffered K page lane-chunks
    v_buf,
    sem_k,  # DMA semaphores [2, PB]
    sem_v,
    *,
    page_size: int,
    pages_per_block: int,
    num_page_slots: int,
    heads_per_chunk: int,
    groups: int,
    head_dim: int,
    attn_softcap: float = 0.0,
):
    """v3 body: like the decode kernel, every shape is tile-aligned by
    folding heads into 128-lane chunks (C = 128/D heads per chunk; C = 1
    for head_dim >= 128). Grid = (B, KV/C, T/TQ); each step DMAs only its
    chunk's lane window of each page (128-aligned dynamic lane slice) and
    runs the whole chunk as two MXU dots over block-diagonal queries —
    the per-head 64-wide lane slices Mosaic rejects never appear."""
    b = pl.program_id(0)
    c = pl.program_id(1)
    qb = pl.program_id(2)
    R, CD = qbd_ref.shape[2], qbd_ref.shape[3]
    C, G, D = heads_per_chunk, groups, head_dim
    TQ = R // (C * G)
    PB = pages_per_block
    blk_tokens = PB * page_size

    valid = valid_ref[b]
    qstart = qstart_ref[b]
    q_base = qstart + qb * TQ  # global position of this tile's first query
    # causal upper bound for the whole tile: the last query's position + 1,
    # clamped by the row's valid length — the KV loop never reads past it
    kv_upper = jnp.minimum(valid, q_base + TQ)
    num_blocks = lax.div(kv_upper + blk_tokens - 1, blk_tokens)
    # sliding window: no query in this tile sees anything before
    # q_base - window + 1, so whole blocks below it are skipped. The
    # window is a runtime scalar (0 = full causal -> first_block 0 and an
    # effectively-infinite mask window).
    w = window_ref[0]
    first_block = lax.div(
        jnp.where(w > 0, jnp.maximum(q_base - w + 1, 0), 0), blk_tokens
    )
    eff_w = jnp.where(w > 0, w, jnp.int32(2**30))

    lane_lo = c * CD  # this head-chunk's 128-aligned lane window

    def start_block(slot, blk):
        for i in range(PB):
            page = tables_ref[b, jnp.minimum(blk * PB + i,
                                             num_page_slots - 1)]
            pltpu.make_async_copy(
                k_hbm.at[page, :, pl.ds(lane_lo, CD)],
                k_buf.at[slot, i], sem_k.at[slot, i]
            ).start()
            pltpu.make_async_copy(
                v_hbm.at[page, :, pl.ds(lane_lo, CD)],
                v_buf.at[slot, i], sem_v.at[slot, i]
            ).start()

    def wait_block(slot, blk):
        for i in range(PB):
            page = tables_ref[b, jnp.minimum(blk * PB + i,
                                             num_page_slots - 1)]
            pltpu.make_async_copy(
                k_hbm.at[page, :, pl.ds(lane_lo, CD)],
                k_buf.at[slot, i], sem_k.at[slot, i]
            ).wait()
            pltpu.make_async_copy(
                v_hbm.at[page, :, pl.ds(lane_lo, CD)],
                v_buf.at[slot, i], sem_v.at[slot, i]
            ).wait()

    # per-row global query position: row r = (t*C + cl)*G + g
    q_pos = q_base + lax.broadcasted_iota(
        jnp.int32, (R, 1), 0
    ) // (C * G)

    m0 = jnp.full((R, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((R, 1), jnp.float32)
    acc0 = jnp.zeros((R, CD), jnp.float32)
    qbd = qbd_ref[0, 0] * (1.0 / (D**0.5))  # [R, CD]

    def loop(blk, carry):
        m, l, acc = carry
        slot = lax.rem(blk, 2)

        @pl.when(blk + 1 < num_blocks)
        def _prefetch():
            start_block(lax.rem(blk + 1, 2), blk + 1)

        wait_block(slot, blk)
        start = blk * blk_tokens
        kv_idx = start + lax.broadcasted_iota(
            jnp.int32, (R, blk_tokens), 1
        )
        mask = (kv_idx <= q_pos) & (kv_idx < valid)
        mask &= kv_idx > q_pos - eff_w

        k = k_buf[slot].reshape(blk_tokens, CD)
        v = v_buf[slot].reshape(blk_tokens, CD)
        # [R, T] scores in ONE MXU dot; block-diagonal q rows contract
        # only their own head's lanes
        s = lax.dot_general(
            qbd.astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if attn_softcap:
            s = jnp.tanh(s * (1.0 / attn_softcap)) * attn_softcap
        s = jnp.where(mask, s, _NEG_INF)

        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        # masked-everything rows: exp(s - m_new) with m_new still -inf
        # would be exp(0); force explicit zeros
        probs = jnp.where(s > _NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
        l_new = l * alpha + jnp.sum(probs, -1, keepdims=True)
        acc_new = acc * alpha + lax.dot_general(
            probs.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new)

    def run():
        start_block(lax.rem(first_block, 2), first_block)
        return lax.fori_loop(first_block, num_blocks, loop, (m0, l0, acc0))

    _, l, acc = lax.cond(
        num_blocks > first_block, run, lambda: (m0, l0, acc0)
    )
    out_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "q_block", "pages_per_block", "interpret",
                     "attn_softcap"),
)
def paged_attention_prefill(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    page_tables: jnp.ndarray,
    q_start: jnp.ndarray,
    kv_valid_len: jnp.ndarray,
    *,
    page_size: int,
    q_block: int = 128,
    pages_per_block: int = 8,
    interpret: bool | None = None,
    sliding_window=0,
    attn_softcap: float = 0.0,
) -> jnp.ndarray:
    """Chunked-prefill paged GQA attention against the flat page pool.

    The XLA prefill path gathers every row's pages into a dense
    ``[B, S_max, KV, D]`` buffer per layer (``models/llama.py``
    ``paged_forward``) — S_max slots materialized in HBM per row however
    short the row. This kernel reads only the pages a query tile can
    causally see, with the same double-buffered scattered-page DMA as the
    decode kernel (VERDICT r1: "no prefill/chunked-prefill kernel").

    Contract: queries are a CONTIGUOUS chunk of positions per row —
    query t of row b sits at global position ``q_start[b] + t`` (the
    engine's chunked/batched prefill layout). K/V for the chunk must
    already be written to the pool (same ordering as ops/attention.py).

    Args:
      q: [B, T, H, D] query chunk (T >= 1, bucket-padded; padding rows'
        outputs are garbage and discarded by the caller).
      pool_k, pool_v: [num_slots, KV, D] one layer's flat page pool.
      page_tables: [B, P] page ids per row.
      q_start: [B] global position of each row's first query.
      kv_valid_len: [B] valid tokens per row INCLUDING this chunk.
      page_size: tokens per page.
      q_block: queries per grid tile (VMEM residency unit).
      pages_per_block: pages DMA'd per inner-loop step.
      interpret: force Pallas interpret mode; defaults to True off-TPU.

    Returns: [B, T, H, D] attention outputs in q.dtype.
    """
    B, T, H, D = q.shape
    num_slots, KV, _ = pool_k.shape
    G = H // KV
    num_pages = num_slots // page_size
    P = page_tables.shape[1]
    PB = min(pages_per_block, P)
    TQ = min(q_block, T)
    while T % TQ:
        TQ //= 2  # buckets are powers of two; degenerate T still divides
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # heads per 128-lane chunk: pack small heads (D=64) in pairs so every
    # DMA lane window and MXU operand is tile-aligned; D >= 128 chunks are
    # a single head (no block-diagonal FLOP overhead at all). For
    # geometries that cannot align (tiny test models, odd head counts) we
    # still build the kernel — interpret mode runs anything, and on real
    # TPU the engine's "auto" probe rejects what Mosaic rejects.
    C = max(1, min(_LANES // D, KV))
    while KV % C:
        C -= 1
    KVc = KV // C
    CD = C * D
    R = TQ * C * G  # rows per tile: (query t, chunk-local head cl, group g)

    # Block-diagonal query expansion within each head chunk (plain XLA):
    # row (t, cl, g) carries q[t, c*C+cl, g] in lanes [cl*D, (cl+1)*D).
    eye = jnp.eye(C, dtype=q.dtype)
    qbd = jnp.einsum(
        "btkugd,uj->btkugjd",
        q.reshape(B, T, KVc, C, G, D), eye,
    )  # [B, T, KVc, C, G, C, D]
    qbd = qbd.transpose(0, 2, 1, 3, 4, 5, 6).reshape(B, KVc, T * C * G, CD)
    k_pages = pool_k.reshape(num_pages, page_size, KV * D)
    v_pages = pool_v.reshape(num_pages, page_size, KV * D)
    tables = jnp.clip(page_tables.astype(jnp.int32), 0, num_pages - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, KVc, T // TQ),
        in_specs=[
            pl.BlockSpec((1, 1, R, CD),
                         lambda b, c, qb, t, vl, qs, w: (b, c, qb, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, R, CD),
                               lambda b, c, qb, t, vl, qs, w: (b, c, qb, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, PB, page_size, CD), pool_k.dtype),
            pltpu.VMEM((2, PB, page_size, CD), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, PB)),
            pltpu.SemaphoreType.DMA((2, PB)),
        ],
    )

    out_big = pl.pallas_call(
        functools.partial(
            _prefill_kernel,
            page_size=page_size,
            pages_per_block=PB,
            num_page_slots=P,
            heads_per_chunk=C,
            groups=G,
            head_dim=D,
            attn_softcap=attn_softcap,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVc, T * C * G, CD), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * T * P * page_size * CD,
            bytes_accessed=2 * B * KV * P * page_size * D
            * pool_k.dtype.itemsize,
            transcendentals=B * H * T * P * page_size,
        ),
    )(
        tables, kv_valid_len.astype(jnp.int32), q_start.astype(jnp.int32),
        jnp.asarray(sliding_window, jnp.int32).reshape(1),
        qbd, k_pages, v_pages,
    )
    # extract each head's diagonal lane block
    out = jnp.einsum(
        "bktugjd,uj->btkugd",
        out_big.reshape(B, KVc, T, C, G, C, D), eye,
    )
    return out.reshape(B, T, H, D)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "pages_per_block", "interpret",
                     "attn_softcap"),
)
def paged_attention_decode(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    page_tables: jnp.ndarray,
    kv_valid_len: jnp.ndarray,
    *,
    page_size: int,
    pages_per_block: int = 8,
    interpret: bool | None = None,
    sliding_window=0,
    attn_softcap: float = 0.0,
) -> jnp.ndarray:
    """Decode-step paged GQA attention against the flat page pool.

    Args:
      q: [B, H, D] one query per row (the token being decoded).
      pool_k, pool_v: [num_slots, KV, D] one layer's flat page pool
        (num_slots = num_pages * page_size — engine/kv_cache.py layout),
        or ``ops.quant.QuantPool`` (int8 codes + f32 per-vector scales):
        the kernel then DMAs HALF the attention bytes and folds the
        scales into the score/probability matrices on the fly.
        CAVEAT (quantized mode): the scale VMEM scratch and DMA tiles are
        [page_size, KV] with KV far below the 128-lane Mosaic tile, and
        Mosaic REJECTS the kernel on the chip for it ("Slice shape along
        dimension 2 must be aligned to tiling (128), but is 8" at KV=8,
        D=64 and D=128, jax 0.9.0 on a v5e; tools/kernel_probe.py). It
        runs in interpret mode only. Serving gates it behind
        DIS_TPU_KV_QUANT_PALLAS=1 plus the AOT probe, which today always
        resolves to the XLA path; the repair is a scale layout with the
        token axis in lanes (ROADMAP S6).
      page_tables: [B, P] page ids per row (entries past the row's last
        page may be any value; they are clamped to the pool and masked).
      kv_valid_len: [B] valid tokens per row, INCLUDING the just-written
        query token (the query is causal-last by construction).
      page_size: tokens per page.
      pages_per_block: pages DMA'd and processed per inner-loop step (the
        double-buffered block size; tune for DMA/compute overlap).
      interpret: force Pallas interpret mode; defaults to True off-TPU so
        tests run on the CPU backend.
      sliding_window: attend only the last N positions (0 = full causal).
        May be a TRACED scalar — Gemma-2's per-layer windows flow through
        one compiled program via scalar prefetch.
      attn_softcap: Gemma-2 score soft-capping tanh(s/cap)*cap (0 = off).

    Returns: [B, H, D] attention outputs in q.dtype.
    """
    from distributed_inference_server_tpu.ops.quant import QuantPool

    quantized = isinstance(pool_k, QuantPool)
    k_arr = pool_k.data if quantized else pool_k
    v_arr = pool_v.data if quantized else pool_v
    B, H, D = q.shape
    num_slots, KV, _ = k_arr.shape
    G = H // KV
    CD = KV * D
    num_pages = num_slots // page_size
    P = page_tables.shape[1]
    PB = min(pages_per_block, P)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # Block-diagonal query expansion (plain XLA — no Mosaic layout rules):
    # qbd[b, kv*G+g, kv*D+d] = q[b, kv*G+g, d], zeros off the diagonal.
    # This is what lets the kernel contract [H, KV*D] x [T, KV*D] in one
    # aligned MXU dot instead of slicing 64-wide per-head lane windows.
    eye = jnp.eye(KV, dtype=q.dtype)
    qbd = jnp.einsum(
        "bkgd,kj->bkgjd", q.reshape(B, KV, G, D), eye
    ).reshape(B, H, CD)
    if quantized:
        k_pages = pool_k.data.reshape(num_pages, page_size, CD)
        v_pages = pool_v.data.reshape(num_pages, page_size, CD)
        ks_pages = pool_k.scale.reshape(num_pages, page_size, KV)
        vs_pages = pool_v.scale.reshape(num_pages, page_size, KV)
        extra_in = [ks_pages, vs_pages]
        extra_in_specs = [
            pl.BlockSpec(memory_space=pl.ANY),  # K scales stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),  # V scales stay in HBM
        ]
        extra_scratch = [
            pltpu.VMEM((2, PB, page_size, KV), jnp.float32),
            pltpu.VMEM((2, PB, page_size, KV), jnp.float32),
        ]
        extra_sems = [
            pltpu.SemaphoreType.DMA((2, PB)),
            pltpu.SemaphoreType.DMA((2, PB)),
        ]
    else:
        k_pages = pool_k.reshape(num_pages, page_size, CD)
        v_pages = pool_v.reshape(num_pages, page_size, CD)
        extra_in, extra_in_specs, extra_scratch, extra_sems = [], [], [], []
    tables = jnp.clip(page_tables.astype(jnp.int32), 0, num_pages - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, CD), lambda b, t, vl, w: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # K pool stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),  # V pool stays in HBM
            *extra_in_specs,
        ],
        out_specs=pl.BlockSpec((1, H, CD), lambda b, t, vl, w: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, PB, page_size, CD), k_arr.dtype),
            pltpu.VMEM((2, PB, page_size, CD), v_arr.dtype),
            *extra_scratch,
            pltpu.SemaphoreType.DMA((2, PB)),
            pltpu.SemaphoreType.DMA((2, PB)),
            *extra_sems,
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, CD), jnp.float32),
        ],
    )

    out_big = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            page_size=page_size,
            pages_per_block=PB,
            num_page_slots=P,
            head_dim=D,
            attn_softcap=attn_softcap,
            quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, CD), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # rows are independent — scratch state is reset per grid step
            # — so let megacore split the batch
            dimension_semantics=("parallel",),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * P * page_size * CD,
            bytes_accessed=2 * B * KV * P * page_size * D
            * k_arr.dtype.itemsize,
            transcendentals=B * H * P * page_size,
        ),
    )(tables, kv_valid_len.astype(jnp.int32),
      jnp.asarray(sliding_window, jnp.int32).reshape(1),
      qbd, k_pages, v_pages, *extra_in)
    # extract each head's diagonal lane block (the rest is cross-head
    # garbage by construction)
    out = jnp.einsum(
        "bkgjd,kj->bkgd", out_big.reshape(B, KV, G, KV, D), eye
    )
    return out.reshape(B, H, D)


def _ragged_kernel(
    # scalar-prefetch refs (SMEM)
    tables_ref,  # [Bm, P] page id per (row, page-slot)
    valid_ref,  # [Bm] valid token count per row (incl. its new tokens)
    wrow_ref,  # [W] work-item row (-1 = padding item)
    wwin_ref,  # [W] work-item packed-query window
    wfirst_ref,  # [W] 1 = first work item of its window (init the out block)
    window_ref,  # [1] sliding window (0 = full causal)
    # tensor refs
    qbd_ref,  # [1, 1, R, CD] this (window, head-chunk)'s block-diagonal
    #           query tile; R = TQ*C*G
    posr_ref,  # [1, R, 1] per-q-row absolute position (token-expanded)
    rowr_ref,  # [1, R, 1] per-q-row owning batch row (-1 = padding token)
    k_hbm,  # [num_pages, page_size, KV*D] full K pool (HBM)
    v_hbm,  # [num_pages, page_size, KV*D] full V pool (HBM)
    out_ref,  # [1, 1, R, CD] (VMEM; revisited by every segment of the window)
    # scratch
    k_buf,  # [2, PB, page_size, CD]
    v_buf,
    sem_k,
    sem_v,
    *,
    page_size: int,
    pages_per_block: int,
    num_page_slots: int,
    head_dim: int,
    attn_softcap: float = 0.0,
):
    """Ragged mixed-batch body: each grid step is one (window, row)
    SEGMENT — the tokens of one batch row that fall inside one TQ-wide
    window of the packed query axis. Rows are packed back-to-back
    (PackInfer-style), so a window can hold many decode rows (q_len 1
    each) next to a prefill chunk's tail; segments of the same window run
    as consecutive grid steps and read-modify-write the shared out block
    (the first one zero-initializes it). The KV loop covers only the
    segment's row, exactly like the decode/prefill kernels' per-row loop
    — ragged per-row trip counts are the whole point."""
    c = pl.program_id(0)
    i = pl.program_id(1)
    R, CD = qbd_ref.shape[2], qbd_ref.shape[3]
    PB = pages_per_block
    blk_tokens = PB * page_size
    lane_lo = c * CD  # this head-chunk's 128-aligned lane window

    b = wrow_ref[i]
    bb = jnp.maximum(b, 0)
    valid = jnp.where(b >= 0, valid_ref[bb], 0)
    pos_r = posr_ref[0]  # [R, 1]
    row_r = rowr_ref[0]
    belongs = (row_r == b) & (b >= 0)

    # the segment's query-position span bounds the KV loop: nothing past
    # the last query's causal horizon (or the row's valid length) is read
    seg_hi = jnp.max(jnp.where(belongs, pos_r, -1)) + 1
    kv_upper = jnp.minimum(valid, seg_hi)
    num_blocks = lax.div(kv_upper + blk_tokens - 1, blk_tokens)
    w = window_ref[0]
    seg_lo = jnp.min(jnp.where(belongs, pos_r, jnp.int32(2**30)))
    first_block = lax.div(
        jnp.where(w > 0, jnp.maximum(seg_lo - w + 1, 0), 0), blk_tokens
    )
    eff_w = jnp.where(w > 0, w, jnp.int32(2**30))

    @pl.when(wfirst_ref[i] != 0)
    def _init():
        out_ref[0, 0] = jnp.zeros((R, CD), out_ref.dtype)

    def start_block(slot, blk):
        for j in range(PB):
            page = tables_ref[bb, jnp.minimum(blk * PB + j,
                                              num_page_slots - 1)]
            pltpu.make_async_copy(
                k_hbm.at[page, :, pl.ds(lane_lo, CD)],
                k_buf.at[slot, j], sem_k.at[slot, j]
            ).start()
            pltpu.make_async_copy(
                v_hbm.at[page, :, pl.ds(lane_lo, CD)],
                v_buf.at[slot, j], sem_v.at[slot, j]
            ).start()

    def wait_block(slot, blk):
        for j in range(PB):
            page = tables_ref[bb, jnp.minimum(blk * PB + j,
                                              num_page_slots - 1)]
            pltpu.make_async_copy(
                k_hbm.at[page, :, pl.ds(lane_lo, CD)],
                k_buf.at[slot, j], sem_k.at[slot, j]
            ).wait()
            pltpu.make_async_copy(
                v_hbm.at[page, :, pl.ds(lane_lo, CD)],
                v_buf.at[slot, j], sem_v.at[slot, j]
            ).wait()

    m0 = jnp.full((R, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((R, 1), jnp.float32)
    acc0 = jnp.zeros((R, CD), jnp.float32)
    qbd = qbd_ref[0, 0] * (1.0 / (head_dim**0.5))  # [R, CD]

    def loop(blk, carry):
        m, l, acc = carry
        slot = lax.rem(blk, 2)

        @pl.when(blk + 1 < num_blocks)
        def _prefetch():
            start_block(lax.rem(blk + 1, 2), blk + 1)

        wait_block(slot, blk)
        start = blk * blk_tokens
        kv_idx = start + lax.broadcasted_iota(
            jnp.int32, (R, blk_tokens), 1
        )
        mask = belongs & (kv_idx <= pos_r) & (kv_idx < valid)
        mask &= kv_idx > pos_r - eff_w

        k = k_buf[slot].reshape(blk_tokens, CD)
        v = v_buf[slot].reshape(blk_tokens, CD)
        s = lax.dot_general(
            qbd.astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if attn_softcap:
            s = jnp.tanh(s * (1.0 / attn_softcap)) * attn_softcap
        s = jnp.where(mask, s, _NEG_INF)

        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        probs = jnp.where(s > _NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
        l_new = l * alpha + jnp.sum(probs, -1, keepdims=True)
        acc_new = acc * alpha + lax.dot_general(
            probs.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new)

    def run():
        start_block(lax.rem(first_block, 2), first_block)
        return lax.fori_loop(first_block, num_blocks, loop, (m0, l0, acc0))

    _, l, acc = lax.cond(
        num_blocks > first_block, run, lambda: (m0, l0, acc0)
    )
    vals = (acc / jnp.maximum(l, 1e-30)).astype(out_ref.dtype)
    # RMW: only this segment's rows land; the window's other segments own
    # (and have written / will write) the rest
    out_ref[0, 0] = jnp.where(belongs, vals, out_ref[0, 0])


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "q_block", "pages_per_block", "interpret",
                     "attn_softcap"),
)
def paged_attention_ragged(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    page_tables: jnp.ndarray,
    tok_row: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_valid_len: jnp.ndarray,
    *,
    page_size: int,
    q_block: int = 128,
    pages_per_block: int = 8,
    interpret: bool | None = None,
    sliding_window=0,
    attn_softcap: float = 0.0,
) -> jnp.ndarray:
    """Ragged mixed-batch paged GQA attention — ONE kernel for a packed
    batch of decode tokens (q_len 1) and prefill chunks (q_len up to the
    chunk budget), the Ragged Paged Attention recipe (PAPERS.md) with
    PackInfer-style packing: rows sit back-to-back on a flat token axis,
    TQ-wide windows of it become MXU tiles, and per-(window, row)
    segments run as grid steps whose KV loops cover only that row's
    pages. Subsumes the decode kernel (all rows q_len 1) and the
    chunked-prefill kernel (one row per window) — the engine's mixed
    step launches THIS kernel for both phases so they cannot drift.

    Contract: ``tok_row`` must be non-decreasing over the packed axis
    (each row's tokens contiguous; -1 padding anywhere is masked but the
    work-item bound assumes the packed form, so keep padding at the
    end). ``q_pos`` is each token's absolute position in its row, and
    positions within a row must ascend. K/V for the new tokens must
    already be written to the pool.

    Args:
      q: [S, H, D] packed query tokens.
      pool_k, pool_v: [num_slots, KV, D] one layer's flat page pool.
      page_tables: [Bm, P] page ids per row.
      tok_row: [S] owning row per packed token (-1 = padding).
      q_pos: [S] absolute position of each packed token.
      kv_valid_len: [Bm] valid tokens per row INCLUDING its new tokens.
      q_block: packed-query window width (VMEM residency unit).

    Returns: [S, H, D] attention outputs in q.dtype (padding and
    fully-masked rows are garbage; callers mask by tok_row).
    """
    S, H, D = q.shape
    num_slots, KV, _ = pool_k.shape
    G = H // KV
    num_pages = num_slots // page_size
    Bm, P = page_tables.shape
    PB = min(pages_per_block, P)
    TQ = min(q_block, S)
    while S % TQ:
        TQ //= 2
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # head packing into 128-lane chunks, exactly as the prefill kernel
    C = max(1, min(_LANES // D, KV))
    while KV % C:
        C -= 1
    KVc = KV // C
    CD = C * D
    R = TQ * C * G
    num_win = S // TQ

    tok_row = tok_row.astype(jnp.int32)
    q_pos = q_pos.astype(jnp.int32)

    # ---- work-item metadata (plain XLA, tiny arrays) ----
    # M[w, b]: window w holds tokens of row b. Segments are the set bits,
    # ordered (w, b) so same-window segments are consecutive grid steps;
    # with rows contiguous on the packed axis there are at most
    # num_win + Bm of them (one boundary row per window plus one segment
    # per window), the static work list size.
    onehot = tok_row[:, None] == jnp.arange(Bm, dtype=jnp.int32)[None, :]
    M = onehot.reshape(num_win, TQ, Bm).any(axis=1)  # [num_win, Bm]
    flat = M.reshape(-1)
    big = jnp.int32(num_win * Bm)
    keys = jnp.where(flat, jnp.arange(num_win * Bm, dtype=jnp.int32), big)
    W = num_win + Bm
    # pad the key pool to W before sorting: with num_win == 1 (or
    # Bm == 1) the set-bit pool is SMALLER than the work list, and a
    # bare [:W] slice would leave the scalar-prefetch arrays shorter
    # than the grid — out-of-bounds SMEM reads on real silicon (the
    # clamping gather hides it in interpret mode)
    keys = jnp.concatenate([keys, jnp.full((W,), big, jnp.int32)])
    sel = jnp.sort(keys)[:W]
    present = sel < big
    sel = jnp.where(present, sel, 0)
    work_row = jnp.where(present, sel % Bm, -1).astype(jnp.int32)
    # padding items park on the LAST window: the work list is ordered so
    # they form a suffix, and a belongs-empty RMW there is a no-op
    work_win = jnp.where(present, sel // Bm, num_win - 1).astype(jnp.int32)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), work_win[:-1]])
    work_first = ((work_win != prev) & present).astype(jnp.int32)

    # block-diagonal query expansion per window (same trick as prefill)
    eye = jnp.eye(C, dtype=q.dtype)
    qbd = jnp.einsum(
        "wtkugd,uj->wtkugjd",
        q.reshape(num_win, TQ, KVc, C, G, D), eye,
    )  # [num_win, TQ, KVc, C, G, C, D]
    qbd = qbd.transpose(0, 2, 1, 3, 4, 5, 6).reshape(num_win, KVc, R, CD)
    # per-q-row position / owning row (token-expanded to the R axis).
    # Shaped [num_win, R, 1] — a column per window — so each block's last
    # two dims EQUAL the array's (Mosaic's block-shape rule; a (1, R)
    # block of a [num_win, R] array is rejected) and the kernel reads the
    # [R, 1] column it broadcasts against with no lane->sublane reshape
    pos_r = jnp.broadcast_to(
        q_pos.reshape(num_win, TQ, 1), (num_win, TQ, C * G)
    ).reshape(num_win, R, 1)
    row_r = jnp.broadcast_to(
        tok_row.reshape(num_win, TQ, 1), (num_win, TQ, C * G)
    ).reshape(num_win, R, 1)

    k_pages = pool_k.reshape(num_pages, page_size, KV * D)
    v_pages = pool_v.reshape(num_pages, page_size, KV * D)
    tables = jnp.clip(page_tables.astype(jnp.int32), 0, num_pages - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(KVc, W),
        in_specs=[
            pl.BlockSpec((1, 1, R, CD),
                         lambda c, i, t, vl, wr, ww, wf, w: (ww[i], c, 0, 0)),
            pl.BlockSpec((1, R, 1),
                         lambda c, i, t, vl, wr, ww, wf, w: (ww[i], 0, 0)),
            pl.BlockSpec((1, R, 1),
                         lambda c, i, t, vl, wr, ww, wf, w: (ww[i], 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, R, CD),
            lambda c, i, t, vl, wr, ww, wf, w: (ww[i], c, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, PB, page_size, CD), pool_k.dtype),
            pltpu.VMEM((2, PB, page_size, CD), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, PB)),
            pltpu.SemaphoreType.DMA((2, PB)),
        ],
    )

    out_big = pl.pallas_call(
        functools.partial(
            _ragged_kernel,
            page_size=page_size,
            pages_per_block=PB,
            num_page_slots=P,
            head_dim=D,
            attn_softcap=attn_softcap,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_win, KVc, R, CD), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # segments of one window REVISIT the same out block (RMW);
            # both axes stay sequential
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * S * H * P * page_size * CD,
            bytes_accessed=2 * Bm * KV * P * page_size * D
            * pool_k.dtype.itemsize,
            transcendentals=S * H * P * page_size,
        ),
    )(
        tables, kv_valid_len.astype(jnp.int32), work_row, work_win,
        work_first, jnp.asarray(sliding_window, jnp.int32).reshape(1),
        qbd, pos_r, row_r, k_pages, v_pages,
    )
    out = jnp.einsum(
        "wktugjd,uj->wtkugd",
        out_big.reshape(num_win, KVc, TQ, C, G, C, D), eye,
    )
    return out.reshape(S, H, D)
