"""Ulysses sequence parallelism: all-to-all head-scatter attention.

The second long-context strategy SURVEY.md §2.3 names next to ring
attention (the reference had neither — context hard-capped at 8192,
``validator.rs:20``). Where ring attention (ops/ring_attention.py) keeps
queries resident and rotates KV chunks around the ICI ring, Ulysses
re-shards: one all-to-all turns the sequence-sharded activations
[B, T/s, H, D] into head-sharded, sequence-complete [B, T, H/s, D]; each
device then runs ordinary full-sequence attention for its head group, and
a second all-to-all restores sequence sharding. Two collectives per layer
instead of s-1 permutes — cheaper when the head count comfortably divides
(attention is embarrassingly parallel over heads) and the all-to-all fits
ICI; ring wins when s exceeds the shardable head count or overlap with
compute matters more.

Constraint: the axis size must divide BOTH the query-head and KV-head
counts (GQA keeps its group structure after the scatter).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_inference_server_tpu.ops.attention import gqa_attention


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_valid_len: jnp.ndarray,
    axis_name: str = "seq",
    sliding_window=None,
    attn_softcap: float | None = None,
) -> jnp.ndarray:
    """Per-shard Ulysses attention body (must run inside shard_map).

    Args:
      q: [B, Tl, H, D] local query chunk (Tl = T / axis size), all heads.
      k, v: [B, Tl, KV, D] local key/value chunks.
      q_positions: [B, Tl] absolute positions of the local tokens
        (contiguous chunks: shard i holds positions [i*Tl, (i+1)*Tl)).
      kv_valid_len: [B] valid sequence length per row (replicated).
      axis_name: mesh axis to all-to-all over.

    Returns [B, Tl, H, D] in q.dtype — attention over the FULL sequence.
    """
    s = lax.axis_size(axis_name)
    H, KV = q.shape[2], k.shape[2]
    if H % s or KV % s:
        raise ValueError(
            f"Ulysses axis size {s} must divide query heads {H} and "
            f"KV heads {KV}; use ring attention for larger axes"
        )
    # scatter heads / gather sequence: [B, Tl, H, D] -> [B, T, H/s, D]
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    pos = lax.all_gather(q_positions, axis_name, axis=1, tiled=True)  # [B, T]
    # full-sequence causal attention for this device's head group; padding
    # keys sit at positions >= kv_valid_len (right-padded) and are masked
    out = gqa_attention(qh, kh, vh, pos, kv_valid_len, sliding_window,
                        attn_softcap)
    # gather heads / scatter sequence back: [B, T, H/s, D] -> [B, Tl, H, D]
    return lax.all_to_all(
        out, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ulysses_attention_sharded(
    mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_valid_len: jnp.ndarray,
    axis_name: str = "seq",
    sliding_window=None,
    attn_softcap: float | None = None,
) -> jnp.ndarray:
    """shard_map wrapper: sequence over ``axis_name``, heads over
    ``tensor`` (Ulysses composes with TP: the all-to-all re-shards each
    tensor shard's own heads). ``sliding_window`` may be a traced scalar
    (rides the specs as a replicated operand, never a closure capture)."""
    row_specs = (
        P("data", axis_name, "tensor", None),
        P("data", axis_name, "tensor", None),
        P("data", axis_name, "tensor", None),
        P("data", axis_name),
        P("data"),
    )
    if sliding_window is None:
        fn = jax.shard_map(
            lambda *a: ulysses_attention(*a, axis_name=axis_name,
                                         attn_softcap=attn_softcap),
            mesh=mesh,
            in_specs=row_specs,
            out_specs=P("data", axis_name, "tensor", None),
            check_vma=False,
        )
        return fn(q, k, v, q_positions, kv_valid_len)
    fn = jax.shard_map(
        lambda q, k, v, qp, kv, w: ulysses_attention(
            q, k, v, qp, kv, axis_name=axis_name, sliding_window=w,
            attn_softcap=attn_softcap,
        ),
        mesh=mesh,
        in_specs=row_specs + (P(),),  # window: replicated scalar
        out_specs=P("data", axis_name, "tensor", None),
        check_vma=False,
    )
    return fn(q, k, v, q_positions, kv_valid_len,
              jnp.asarray(sliding_window, jnp.int32))
