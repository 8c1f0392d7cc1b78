"""Context-parallel prefill: long prompts sharded over the `seq` mesh axis.

The reference capped context at 8192 tokens and had no sequence scaling
(``validator.rs:20``; SURVEY.md §5). Here a long prompt's prefill spans
chips: token ids/positions/activations are sharded over ``seq`` (GSPMD
keeps every elementwise/matmul op local to its chunk), and attention runs
as ring attention (ops/ring_attention.py) — KV chunks rotating over ICI
via collective-permute while each chip accumulates blockwise softmax for
its queries. Composes with tensor parallelism (heads sharded over
``tensor`` inside the ring) and data parallelism (batch over ``data``).

This is the prefill path for prompts too long for one chip's HBM or too
slow for one chip's MXU; decode afterwards proceeds on the paged cache
(the KV produced here lands in cache layout [B, S, KV, D] with slot ==
position, ready to be scattered into pool pages).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_inference_server_tpu.models import llama
from distributed_inference_server_tpu.models.configs import ModelConfig
from distributed_inference_server_tpu.ops.ring_attention import (
    ring_attention,
    ring_attention_sharded,
)


def cp_prefill(
    params: llama.Params,
    cfg: ModelConfig,
    mesh,
    input_ids: jnp.ndarray,
    valid_len: jnp.ndarray,
    sp_impl: str = "ring",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Context-parallel prefill of a ragged batch of prompts.

    Args:
      input_ids: [B, T] token ids, right-padded; T must divide by the
        ``seq`` axis size.
      valid_len: [B] prompt lengths.
      sp_impl: "ring" (KV chunks rotate over ICI, ops/ring_attention.py)
        or "ulysses" (all-to-all head scatter, ops/ulysses.py — axis size
        must divide the query- and KV-head counts).

    Returns (last_logits [B, V] f32, k, v) where k, v are
    [L, B, T, KV, D] caches with slot == position (padding slots hold
    zeros) — the dense-cache layout decode starts from.
    """
    B, T = input_ids.shape
    seq = mesh.shape.get("seq", 1)
    if T % seq:
        raise ValueError(f"prompt buffer {T} not divisible by seq axis {seq}")
    if sp_impl not in ("ring", "ulysses"):
        raise ValueError(f"sp_impl must be 'ring' or 'ulysses', got {sp_impl!r}")

    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    positions = jnp.where(pos < valid_len[:, None], pos, -1)
    # padding writes are dropped (slot T is out of range for the cache)
    write_pos = jnp.where(positions >= 0, positions, T)

    # per-layer window: w rides the layer scan as a traced scalar (the
    # Gemma-2 alternating local/global schedule works under CP), passed
    # into the attends through their specs; score soft-capping applies
    # inside the blockwise softmax. Non-sliding models keep the static
    # maskless branch (w arrives as None from scan_layer_blocks).
    softcap = cfg.attn_logit_softcap
    if sp_impl == "ulysses":
        from distributed_inference_server_tpu.ops.ulysses import (
            ulysses_attention_sharded,
        )

        def attend(q, k_layer, v_layer, w):
            return ulysses_attention_sharded(
                mesh, q, k_layer, v_layer, positions, valid_len,
                sliding_window=w, attn_softcap=softcap,
            )
    else:

        def attend(q, k_layer, v_layer, w):
            return ring_attention_sharded(
                mesh, q, k_layer, v_layer, positions, positions,
                sliding_window=w, attn_softcap=softcap,
            )

    cache = llama.KVCache.create(cfg, B, T, dtype=params["embed"].dtype)
    h, new_k, new_v = llama._run_layers(
        params, cfg, input_ids, positions, cache.k, cache.v,
        lambda pool, l, new: llama._write_kv(pool, l, new, write_pos),
        attend,
    )
    last = jnp.take_along_axis(
        h, (valid_len - 1)[:, None, None].astype(jnp.int32), axis=1
    )  # [B, 1, H]
    logits = llama._unembed(params, cfg, last)[:, 0]
    return logits, new_k, new_v


def cp_paged_prefill(
    params: llama.Params,
    cfg: ModelConfig,
    mesh,
    input_ids: jnp.ndarray,
    valid_len: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    write_slots: jnp.ndarray,
    sp_impl: str = "ring",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sequence-parallel prefill that lands in the paged pool — the
    dense-KV→pages hand-off the engine's long-prompt admission path uses
    (the reference had no long-context path at all; context hard-capped
    at 8192, ``validator.rs:20``).

    Runs ``cp_prefill`` (sequence sharded over the ``seq`` mesh axis;
    ``sp_impl`` picks ring attention or Ulysses all-to-all), then
    scatters the position-ordered dense K/V into the flat page pools at
    per-token ``write_slots`` ([B, T] flat slot per position, >=
    num_slots drops the write — padding). After this the prompt decodes
    from pages like any other sequence.

    Returns (last_logits [B, V] f32, new pool_k, new pool_v).
    """
    logits, k, v = cp_prefill(
        params, cfg, mesh, input_ids, valid_len, sp_impl=sp_impl
    )
    # k, v: [L, B, T, KV, D] slot==position; pool: [L, num_slots, KV, D]
    return logits, _scatter_pool(pool_k, k, write_slots), _scatter_pool(
        pool_v, v, write_slots
    )


def _scatter_pool(pool, kv, write_slots):
    """Scatter dense slot==position K/V [L, B, T, KV, D] into a flat page
    pool at per-token ``write_slots`` (>= num_slots drops — padding).
    ``QuantPool`` pools quantize at scatter time (per-vector absmax), so
    ring/Ulysses prefill composes with the int8 KV cache."""
    from distributed_inference_server_tpu.ops.quant import (
        QuantPool,
        quantize_kv,
    )

    if isinstance(pool, QuantPool):
        codes, scale = quantize_kv(kv)
        return QuantPool(
            pool.data.at[:, write_slots].set(codes, mode="drop"),
            pool.scale.at[:, write_slots].set(scale, mode="drop"),
        )
    return pool.at[:, write_slots].set(kv.astype(pool.dtype), mode="drop")


def cp_pp_prefill(
    params: llama.Params,
    cfg: ModelConfig,
    mesh,
    input_ids: jnp.ndarray,
    valid_len: jnp.ndarray,
    num_microbatches: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Ring-attention prefill on a ``seq`` x ``stage`` mesh — CP composed
    with pipeline parallelism in ONE program (VERDICT r4 #5).

    Why not ``cp_prefill`` under ``pp.pp_forward``: ring attention was a
    self-contained shard_map over {data, seq, tensor}, and nesting that
    inside the GPipe stage loop's partial-manual ``stage`` shard_map
    deadlocked XLA's collective scheduling (repro:
    tools/nested_shardmap_repro.py). The fix is structural — ONE
    partial-manual shard_map spanning BOTH axes, with the stage tick loop
    inside and the per-shard ``ring_attention`` body (not its sharded
    wrapper) as the attend. Every device then runs the identical tick
    program: the seq-axis ``ppermute``s of the KV ring and the stage-axis
    ``ppermute``s of the activation hand-off are issued in the same
    static order everywhere, which is exactly the property the nested
    form lost. ``data``/``tensor`` stay GSPMD-managed inside, so DP x TP
    x SP x PP all compose here.

    Layout: stage s holds layers [s*L/S, (s+1)*L/S); seq shard i holds
    token chunk i (Tl = T/seq) of every microbatch's activations and of
    the dense slot==position KV cache — each device's cache slice is
    [L/S, B, Tl, KV, D]: HBM for the prefill intermediate scales down by
    BOTH axes. Causality rides absolute positions (padding = -1), which
    rotate with the KV chunks, so the mask is exact for ragged batches.

    Args/returns match ``cp_prefill`` (plus ``num_microbatches``):
    (last_logits [B, V] f32, k, v [L, B, T, KV, D] slot==position).
    """
    from distributed_inference_server_tpu.ops.norms import rms_norm
    from distributed_inference_server_tpu.ops.rotary import rope_frequencies

    S = mesh.shape.get("stage", 1)
    R = mesh.shape.get("seq", 1)
    B, T = input_ids.shape
    M = num_microbatches
    if cfg.num_layers % S:
        raise ValueError(f"{S} stages do not divide num_layers={cfg.num_layers}")
    if B % M:
        raise ValueError(f"{M} microbatches do not divide batch={B}")
    if T % R:
        raise ValueError(f"prompt buffer {T} not divisible by seq axis {R}")
    B_mb = B // M
    Tl = T // R
    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
    softcap = cfg.attn_logit_softcap

    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    positions = jnp.where(pos < valid_len[:, None], pos, -1)

    def body(layers, embed, final_norm, unembed, ids, pos_l, valid):
        # locals: layers [L/S,...] (this stage), ids/pos_l [B, Tl] (this
        # seq chunk); cache slices are [L/S, B, Tl, KV, D]
        stage = lax.axis_index("stage")
        seq_i = lax.axis_index("seq")

        L_stage = layers["attn_norm"].shape[0]
        if cfg.sliding_window:
            win_stage = jnp.asarray(
                cfg.layer_windows(), jnp.int32
            ).reshape(-1, L_stage)[stage]
        else:
            win_stage = None

        # dense slot==position (local) writes; padding tokens drop (Tl)
        slot_of = jnp.broadcast_to(
            jnp.arange(Tl, dtype=jnp.int32)[None], (B, Tl)
        )
        wp_all = jnp.where(pos_l >= 0, slot_of, Tl)

        def run_stage(h_mb, pos_mb, ck_mb, cv_mb, wp_mb):
            write_fn = lambda pool, l, new: llama._write_kv(
                pool, l, new, wp_mb)

            def attend_fn(q, k_layer, v_layer, w):
                # per-shard ring body: KV chunks rotate over `seq` while
                # this device accumulates blockwise softmax for its
                # queries. Cache slot == local position, so the layer
                # cache IS the local KV chunk and pos_mb is both the
                # query- and key-position map (padding -1 never attends).
                return ring_attention(
                    q, k_layer, v_layer, pos_mb, pos_mb,
                    axis_name="seq", sliding_window=w,
                    attn_softcap=softcap,
                )

            h_mb, (nk, nv) = llama.scan_layer_blocks(
                cfg, h_mb, layers, ck_mb, cv_mb, win_stage, pos_mb,
                write_fn, attend_fn, inv_freq,
            )
            return h_mb, nk, nv

        def tick(t, carry):
            state, ck, cv, out = carry
            mb = t - stage
            tick_valid = (mb >= 0) & (mb < M)
            row = jnp.clip(mb, 0, M - 1) * B_mb
            ids_mb = lax.dynamic_slice_in_dim(ids, row, B_mb, 0)
            pos_mb = lax.dynamic_slice_in_dim(pos_l, row, B_mb, 0)
            wp_mb = lax.dynamic_slice_in_dim(wp_all, row, B_mb, 0)
            ck_mb = lax.dynamic_slice_in_dim(ck, row, B_mb, 1)
            cv_mb = lax.dynamic_slice_in_dim(cv, row, B_mb, 1)
            # bubble ticks must not mutate the cache
            wp_eff = jnp.where(tick_valid, wp_mb, Tl)

            h_emb = embed[ids_mb]
            if cfg.scale_embeddings:  # Gemma: sqrt(hidden) on input
                h_emb = h_emb * jnp.asarray(cfg.hidden_size**0.5, h_emb.dtype)
            h_in = jnp.where(stage == 0, h_emb, state)
            h_out, nk, nv = run_stage(h_in, pos_mb, ck_mb, cv_mb, wp_eff)
            ck = lax.dynamic_update_slice_in_dim(ck, nk, row, 1)
            cv = lax.dynamic_update_slice_in_dim(cv, nv, row, 1)

            out_upd = lax.dynamic_update_slice_in_dim(out, h_out, row, 0)
            out = jnp.where(tick_valid & (stage == S - 1), out_upd, out)

            state = lax.ppermute(
                h_out, "stage", [(i, i + 1) for i in range(S - 1)]
            )
            return state, ck, cv, out

        dt = embed.dtype
        state0 = lax.pcast(
            jnp.zeros((B_mb, Tl, cfg.hidden_size), dt), "stage", to="varying"
        )
        state0 = lax.pcast(state0, "seq", to="varying")
        out0 = lax.pcast(
            jnp.zeros((B, Tl, cfg.hidden_size), dt), "stage", to="varying"
        )
        out0 = lax.pcast(out0, "seq", to="varying")
        ck0 = lax.pcast(
            lax.pcast(
                jnp.zeros((L_stage, B, Tl, cfg.num_kv_heads, cfg.head_dim),
                          dt),
                "stage", to="varying",
            ),
            "seq", to="varying",
        )
        cv0 = ck0
        state, ck, cv, out = lax.fori_loop(
            0, M + S - 1, tick, (state0, ck0, cv0, out0)
        )

        out = lax.psum(out, "stage")  # only the last stage wrote
        # the last valid token lives on exactly one seq shard: pick the
        # local row (or zeros) and combine across the ring
        li = (valid - 1).astype(jnp.int32) - seq_i * Tl  # [B]
        here = (li >= 0) & (li < Tl)
        last = jnp.take_along_axis(
            out, jnp.clip(li, 0, Tl - 1)[:, None, None], axis=1
        )  # [B, 1, H]
        last = lax.psum(
            jnp.where(here[:, None, None], last, 0.0), "seq"
        )
        h = rms_norm(last, final_norm, cfg.rms_norm_eps)
        logits = jnp.einsum(
            "bth,hv->btv", h, unembed, preferred_element_type=jnp.float32
        )
        if cfg.final_logit_softcap is not None:
            cap = cfg.final_logit_softcap
            logits = jnp.tanh(logits / cap) * cap
        return logits[:, 0], ck, cv

    unembed = (
        params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        axis_names={"seq", "stage"},  # data/tensor stay GSPMD-managed
        in_specs=(
            P("stage"),  # layer stacks [L, ...] -> local [L/S, ...]
            P(),  # embed
            P(),  # final_norm
            P(),  # unembed
            P(None, "seq"),  # ids [B, T] -> [B, Tl]
            P(None, "seq"),  # positions
            P(),  # valid_len
        ),
        out_specs=(
            P(),  # last logits [B, V]
            P("stage", None, "seq"),  # k [L, B, T, KV, D]
            P("stage", None, "seq"),  # v
        ),
    )
    return fn(
        params["layers"], params["embed"], params["final_norm"], unembed,
        input_ids, positions, valid_len.astype(jnp.int32),
    )


def cp_paged_prefill_any(
    params: llama.Params,
    cfg: ModelConfig,
    mesh,
    input_ids: jnp.ndarray,
    valid_len: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    write_slots: jnp.ndarray,
    sp_impl: str = "ring",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``cp_paged_prefill`` that also handles ``stage`` meshes: on a
    seq x stage mesh the ring runs via ``cp_pp_prefill`` (one unified
    shard_map) and the dense K/V — sharded over BOTH the layer axis
    (stage) and positions (seq) — scatter into the stage-sharded page
    pools. The layer axis of pool and source align, so the scatter stays
    stage-local; GSPMD all-gathers each stage's seq chunks over ICI."""
    if mesh.shape.get("stage", 1) > 1:
        if sp_impl != "ring":
            raise ValueError(
                "sequence parallelism on a stage mesh supports sp_impl="
                "'ring' only (ulysses is seq-only)"
            )
        logits, k, v = cp_pp_prefill(params, cfg, mesh, input_ids, valid_len)
        return logits, _scatter_pool(pool_k, k, write_slots), _scatter_pool(
            pool_v, v, write_slots
        )
    return cp_paged_prefill(
        params, cfg, mesh, input_ids, valid_len, pool_k, pool_v,
        write_slots, sp_impl=sp_impl,
    )


def cp_shardings(mesh):
    """(ids, valid) input shardings for jitting ``cp_prefill``."""
    return (
        NamedSharding(mesh, P("data", "seq")),
        NamedSharding(mesh, P("data")),
    )
