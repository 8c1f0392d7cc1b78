"""Ring attention: context-parallel blockwise attention over a `seq` mesh axis.

Long-context scaling the reference entirely lacked (context hard-capped at
8192 tokens, ``validator.rs:20``; SURVEY.md §5 "long-context: entirely
absent"). Here prefill of long prompts spans chips: the sequence is sharded
over the ``seq`` mesh axis, every device holds one Q/K/V chunk, and KV
chunks rotate around the ring via ``lax.ppermute`` while each device
accumulates blockwise online-softmax attention of its local queries —
flash-attention's math, with the outer loop running over ICI neighbors.
Compute on chunk i overlaps the DMA of chunk i+1 (XLA schedules the
ppermute concurrently with the local block matmuls).

Causality rides on absolute positions, which rotate with the KV chunks, so
the mask is exact for any sequence layout (contiguous chunks, padding
tails, ragged batches via kv_valid masks).

``ring_attention`` is the per-shard body (call inside shard_map);
``ring_attention_sharded`` is the mesh-level wrapper.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    axis_name: str = "seq",
    sliding_window=None,
    attn_softcap: float | None = None,
) -> jnp.ndarray:
    """Per-shard ring attention body (must run inside shard_map/pmap).

    Args:
      q: [B, Tl, H, D] local query chunk (Tl = T / ring size).
      k, v: [B, Tl, KV, D] local key/value chunks (GQA: H = G * KV).
      q_positions: [B, Tl] absolute positions of local queries; negative
        positions mark padding rows (they attend nothing and emit zeros).
      kv_positions: [B, Tl] absolute positions of local keys; negative
        positions mark padding keys (never attended).
      axis_name: the mesh axis the ring runs over.
      sliding_window: None = full causal (static fast path); otherwise a
        scalar — possibly TRACED (Gemma-2 per-layer windows ride the
        layer scan) — where <= 0 means full causal.
      attn_softcap: Gemma-2 score soft-capping, tanh(s/cap)*cap applied
        before masking (None = off; static).

    Returns [B, Tl, H, D] in q.dtype — attention over the FULL sequence.
    """
    B, Tl, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    ring = lax.axis_size(axis_name)
    scale = 1.0 / (D**0.5)

    qg = q.astype(jnp.float32).reshape(B, Tl, KV, G, D)

    def scores(k_blk, pos_kv):
        """Masked blockwise scores [B, KV, G, Tl, S] of the local queries
        against one KV chunk."""
        s = jnp.einsum(
            "btkgd,bskd->bkgts", qg, k_blk.astype(jnp.float32)
        ) * scale
        if attn_softcap is not None:
            s = jnp.tanh(s / attn_softcap) * attn_softcap
        causal = pos_kv[:, None, :] <= q_positions[:, :, None]  # [B, Tl, S]
        if sliding_window is not None:
            w = jnp.asarray(sliding_window, jnp.int32)
            causal &= (w <= 0) | (
                pos_kv[:, None, :] > q_positions[:, :, None] - w
            )
        valid = (pos_kv >= 0)[:, None, :] & (q_positions >= 0)[:, :, None]
        mask = (causal & valid)[:, None, None, :, :]
        return jnp.where(mask, s, _NEG_INF)

    def accumulate(stats, k_blk, v_blk, pos_kv):
        """Online-softmax update of (m, l, acc) with one KV chunk."""
        m, l, acc = stats
        s = scores(k_blk, pos_kv)
        m_cur = jnp.max(s, axis=-1)  # [B, KV, G, Tl]
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        # explicit zero for masked entries: when a query has seen nothing
        # yet (m == -inf), exp(s - m) would be exp(0) = 1, not 0
        probs = jnp.where(
            s > _NEG_INF * 0.5, jnp.exp(s - m_new[..., None]), 0.0
        )  # [B,KV,G,Tl,S]
        l_new = l * alpha + jnp.sum(probs, axis=-1)
        upd = jnp.einsum("bkgts,bskd->btkgd", probs, v_blk.astype(jnp.float32))
        acc_new = acc * alpha.transpose(0, 3, 1, 2)[..., None] + upd
        return m_new, l_new, acc_new

    def step(carry, _):
        stats, k_blk, v_blk, pos_kv = carry
        stats = accumulate(stats, k_blk, v_blk, pos_kv)
        # rotate KV (and its positions) to the next ring neighbor
        perm = [(i, (i + 1) % ring) for i in range(ring)]
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        p_nxt = lax.ppermute(pos_kv, axis_name, perm)
        return (stats, k_nxt, v_nxt, p_nxt), None

    stats0 = (
        jnp.full((B, KV, G, Tl), _NEG_INF, jnp.float32),
        jnp.zeros((B, KV, G, Tl), jnp.float32),
        jnp.zeros((B, Tl, KV, G, D), jnp.float32),
    )
    # when the surrounding manual region tracks varying-manual-axes (vma)
    # — e.g. the unified seq x stage shard_map of parallel/cp.py's
    # cp_pp_prefill — the scan carry must start with the same vma set the
    # accumulate step produces, or the carry types mismatch. Promote the
    # fresh zeros to the inputs' varying set (no-op under check_vma=False
    # wrappers, where the set is empty).
    try:
        vma = tuple(jax.typeof(q).vma | jax.typeof(k).vma)
    except (AttributeError, TypeError):
        vma = ()
    if vma:
        stats0 = tuple(lax.pcast(x, vma, to="varying") for x in stats0)
    # ring-1 rotate-and-accumulate steps, then a peeled final accumulate —
    # the last rotation's result would be discarded, so don't issue it
    (stats, k_last, v_last, pos_last), _ = lax.scan(
        step, (stats0, k, v, kv_positions), None, length=ring - 1
    )
    m, l, acc = accumulate(stats, k_last, v_last, pos_last)
    l = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return (acc / l).reshape(B, Tl, H, D).astype(q.dtype)


def ring_attention_sharded(
    mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    axis_name: str = "seq",
    sliding_window=None,
    attn_softcap: float | None = None,
) -> jnp.ndarray:
    """shard_map wrapper: sequence dim sharded over ``axis_name``, heads
    over ``tensor`` (ring attention composes with TP: each tensor shard
    rings its own heads). ``sliding_window`` may be a traced scalar (it
    rides the specs as a replicated operand, never a closure capture)."""
    row_specs = (
        P("data", axis_name, "tensor", None),
        P("data", axis_name, "tensor", None),
        P("data", axis_name, "tensor", None),
        P("data", axis_name),
        P("data", axis_name),
    )
    if sliding_window is None:
        fn = jax.shard_map(
            lambda *a: ring_attention(*a, axis_name=axis_name,
                                      attn_softcap=attn_softcap),
            mesh=mesh,
            in_specs=row_specs,
            out_specs=P("data", axis_name, "tensor", None),
            check_vma=False,
        )
        return fn(q, k, v, q_positions, kv_positions)
    fn = jax.shard_map(
        lambda q, k, v, qp, kp, w: ring_attention(
            q, k, v, qp, kp, axis_name=axis_name, sliding_window=w,
            attn_softcap=attn_softcap,
        ),
        mesh=mesh,
        in_specs=row_specs + (P(),),  # window: replicated scalar
        out_specs=P("data", axis_name, "tensor", None),
        check_vma=False,
    )
    return fn(q, k, v, q_positions, kv_positions,
              jnp.asarray(sliding_window, jnp.int32))
