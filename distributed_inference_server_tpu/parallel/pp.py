"""Pipeline parallelism: layer stages over the `stage` mesh axis.

For models too big for one chip/slice even under TP (north star: Llama-3
70B TP×PP on v5p-64, BASELINE.md), layers are split into contiguous
stages. TPU-idiomatic formulation: one SPMD program via ``shard_map`` over
``stage`` — every device runs the same tick loop on its own layer slice,
activations hop stage→stage through ``lax.ppermute`` over ICI/DCN, and
GPipe fill-drain microbatching keeps stages busy (M microbatches, M+S-1
ticks, bubble fraction (S-1)/(M+S-1)).

Key layout choices:
- Layer-stacked params keep their standard [L, ...] layout; shard_map's
  in_specs split the layer axis, so stage s holds layers [s*L/S, (s+1)*L/S)
  — no host-side re-packing.
- Each stage's dense KV cache lives on that stage (cache sharded over the
  layer axis too): cache HBM scales down 1/S per device.
- The shard_map is *partial-manual* (``axis_names={'stage'}``): the
  ``tensor`` axis stays GSPMD-managed inside the body, so TP composes with
  PP without manual collectives (weights keep their tp.py shardings).
- Embedding/final-norm/unembedding are replicated compute on every stage
  (cheap relative to the stacks; vocab-parallel unembed is a later
  optimization).

The reference has no PP (SURVEY.md §2.3 absence audit).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_inference_server_tpu.models import llama
from distributed_inference_server_tpu.models.configs import ModelConfig
from distributed_inference_server_tpu.ops.attention import gqa_attention
from distributed_inference_server_tpu.ops.norms import rms_norm
from distributed_inference_server_tpu.ops.rotary import rope_frequencies


def validate_pp(cfg: ModelConfig, stages: int, batch: int,
                num_microbatches: int) -> None:
    if cfg.num_layers % stages:
        raise ValueError(
            f"{stages} stages do not divide num_layers={cfg.num_layers}"
        )
    if batch % num_microbatches:
        raise ValueError(
            f"{num_microbatches} microbatches do not divide batch={batch}"
        )


def pp_forward(
    mesh,
    params: llama.Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    write_pos: jnp.ndarray,
    kv_valid_len: jnp.ndarray,
    num_microbatches: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pipeline-parallel forward over the dense KV cache.

    Same contract as ``llama.forward`` (prefill: T = prompt chunk; decode:
    T = 1), executed over the mesh's ``stage`` axis. Returns
    (logits [B, T, V] f32, new cache_k, new cache_v) with caches sharded
    over the layer axis by stage.
    """
    S = mesh.shape.get("stage", 1)
    B, T = input_ids.shape
    M = num_microbatches
    validate_pp(cfg, S, B, M)
    B_mb = B // M
    Smax = cache_k.shape[2]
    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)

    def body(layers, embed, final_norm, unembed, ids, pos, ck, cv, wp, kvv):
        # local views: layers/ck/cv hold this stage's L/S layers
        stage = lax.axis_index("stage")

        # this stage's slice of the per-layer sliding windows (0 = full
        # causal) — Gemma-2-style alternating layers keep their schedule
        # across stage boundaries. Non-sliding models skip the traced
        # window entirely (static None keeps gqa's maskless branch).
        L_stage = layers["attn_norm"].shape[0]
        if cfg.sliding_window:
            win_stage = jnp.asarray(cfg.layer_windows(), jnp.int32).reshape(
                -1, L_stage
            )[stage]
        else:
            win_stage = None

        def run_stage(h_mb, pos_mb, ck_mb, cv_mb, wp_mb, kvv_mb):
            write_fn = lambda pool, l, new: llama._write_kv(
                pool, l, new, wp_mb)
            attend_fn = lambda q, k, v, w: gqa_attention(
                q, k, v, pos_mb, kvv_mb, w, cfg.attn_logit_softcap)

            h_mb, (nk, nv) = llama.scan_layer_blocks(
                cfg, h_mb, layers, ck_mb, cv_mb, win_stage, pos_mb,
                write_fn, attend_fn, inv_freq,
            )
            return h_mb, nk, nv

        def tick(t, carry):
            state, ck, cv, out = carry
            mb = t - stage
            valid = (mb >= 0) & (mb < M)
            row = jnp.clip(mb, 0, M - 1) * B_mb
            ids_mb = lax.dynamic_slice_in_dim(ids, row, B_mb, 0)
            pos_mb = lax.dynamic_slice_in_dim(pos, row, B_mb, 0)
            wp_mb = lax.dynamic_slice_in_dim(wp, row, B_mb, 0)
            kvv_mb = lax.dynamic_slice_in_dim(kvv, row, B_mb, 0)
            ck_mb = lax.dynamic_slice_in_dim(ck, row, B_mb, 1)
            cv_mb = lax.dynamic_slice_in_dim(cv, row, B_mb, 1)
            # invalid ticks (pipeline bubble) must not mutate the cache
            wp_eff = jnp.where(valid, wp_mb, Smax)

            h_emb = embed[ids_mb]
            if cfg.scale_embeddings:  # Gemma: sqrt(hidden) on input
                h_emb = h_emb * jnp.asarray(
                    cfg.hidden_size**0.5, h_emb.dtype
                )
            h_in = jnp.where(stage == 0, h_emb, state)
            h_out, nk, nv = run_stage(h_in, pos_mb, ck_mb, cv_mb, wp_eff,
                                      kvv_mb)
            ck = lax.dynamic_update_slice_in_dim(ck, nk, row, 1)
            cv = lax.dynamic_update_slice_in_dim(cv, nv, row, 1)

            out_upd = lax.dynamic_update_slice_in_dim(out, h_out, row, 0)
            out = jnp.where(valid & (stage == S - 1), out_upd, out)

            # hand activations to the next stage (stage 0 always injects,
            # so the non-circular permute's zero-fill there is harmless)
            state = lax.ppermute(
                h_out, "stage", [(i, i + 1) for i in range(S - 1)]
            )
            return state, ck, cv, out

        # carries start stage-varying (vma tracking needs the promotion)
        state0 = lax.pcast(
            jnp.zeros((B_mb, T, cfg.hidden_size), embed.dtype),
            "stage", to="varying",
        )
        out0 = lax.pcast(
            jnp.zeros((B, T, cfg.hidden_size), embed.dtype),
            "stage", to="varying",
        )
        state, ck, cv, out = lax.fori_loop(
            0, M + S - 1, tick, (state0, ck, cv, out0)
        )

        out = lax.psum(out, "stage")  # only the last stage wrote; broadcast
        h = rms_norm(out, final_norm, cfg.rms_norm_eps)
        logits = jnp.einsum(
            "bth,hv->btv", h, unembed, preferred_element_type=jnp.float32
        )
        if cfg.final_logit_softcap is not None:  # Gemma soft-capping
            cap = cfg.final_logit_softcap
            logits = jnp.tanh(logits / cap) * cap
        return logits, ck, cv

    unembed = (
        params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        axis_names={"stage"},  # tensor/data stay GSPMD-managed inside
        in_specs=(
            P("stage"),  # layer stacks [L, ...] -> local [L/S, ...]
            P(),  # embed
            P(),  # final_norm
            P(),  # unembed
            P(),  # ids
            P(),  # positions
            P("stage"),  # cache_k [L, B, Smax, KV, D]
            P("stage"),  # cache_v
            P(),  # write_pos
            P(),  # kv_valid_len
        ),
        out_specs=(P(), P("stage"), P("stage")),
    )
    return fn(
        params["layers"], params["embed"],
        params["final_norm"], unembed,
        input_ids, positions, cache_k, cache_v, write_pos, kv_valid_len,
    )


def pp_paged_forward(
    mesh,
    params: llama.Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    write_slots: jnp.ndarray,
    gather_slots: jnp.ndarray,
    kv_valid_len: jnp.ndarray,
    num_microbatches: int = 1,
    page_size: int = 0,
    logits_idx: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pipeline-parallel forward over the PAGED KV pool — the serving
    engine's hot path under a ``stage`` mesh axis (the 70B TP x PP north
    star, BASELINE.md config 5).

    Same contract as ``llama.paged_forward`` (XLA gather attention path):
    pools are [L, num_slots, KV, D] and sharded over ``stage`` on the
    layer axis, so each stage holds its own layers' pages; write slots and
    gather rows are position-indexed and microbatch-sliced on the batch
    axis. The ``tensor`` axis (if present) stays GSPMD-managed inside the
    shard_map body, so TP composes without manual collectives. Unlike the
    dense ``pp_forward``, the pool is carried whole through the tick loop:
    microbatches write disjoint slots (their own rows' pages), and bubble
    ticks write to the drop sentinel.

    Int8 KV (VERDICT r4 #4): ``QuantPool`` pools thread through as
    pytrees — both members (codes [L, num_slots, KV, D] int8, scales
    [L, num_slots, KV] f32) shard over ``stage`` on the layer axis, new
    KV quantizes at write time inside each stage's scan, and the gather
    path dequantizes after the page-granular gather, exactly as the
    single-device ``llama.paged_forward`` XLA path does.
    """
    from distributed_inference_server_tpu.ops.quant import (
        QuantPool,
        dequantize_kv,
        pool_num_slots,
    )

    S = mesh.shape.get("stage", 1)
    B, T = input_ids.shape
    M = num_microbatches
    validate_pp(cfg, S, B, M)
    B_mb = B // M
    kv_quantized = isinstance(pool_k, QuantPool)
    num_slots = pool_num_slots(pool_k)
    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)

    slice_h = logits_idx is not None

    def body(layers, embed, final_norm, unembed, ids, pos, pk, pv, ws, gs,
             kvv, lidx):
        stage = lax.axis_index("stage")

        L_stage = layers["attn_norm"].shape[0]
        if cfg.sliding_window:
            win_stage = jnp.asarray(cfg.layer_windows(), jnp.int32).reshape(
                -1, L_stage
            )[stage]
        else:  # static None keeps the maskless gqa branch (no traced w)
            win_stage = None

        def run_stage(h_mb, pos_mb, pk, pv, ws_mb, gs_mb, kvv_mb):
            write_fn = llama.make_paged_write_fn(ws_mb, kv_quantized)

            def attend_fn(q, k_layer, v_layer, w):
                if kv_quantized:
                    kd, vd = llama.gather_kv_window(
                        k_layer.data, v_layer.data, gs_mb, page_size
                    )
                    ks, vs = llama.gather_kv_window(
                        k_layer.scale, v_layer.scale, gs_mb, page_size
                    )
                    k_seq = dequantize_kv(kd, ks, q.dtype)
                    v_seq = dequantize_kv(vd, vs, q.dtype)
                else:
                    k_seq, v_seq = llama.gather_kv_window(
                        k_layer, v_layer, gs_mb, page_size
                    )
                return gqa_attention(q, k_seq, v_seq, pos_mb, kvv_mb, w,
                                     cfg.attn_logit_softcap)

            h_mb, (nk, nv) = llama.scan_layer_blocks(
                cfg, h_mb, layers, pk, pv, win_stage, pos_mb,
                write_fn, attend_fn, inv_freq,
            )
            return h_mb, nk, nv

        def tick(t, carry):
            state, pk, pv, out = carry
            mb = t - stage
            valid = (mb >= 0) & (mb < M)
            row = jnp.clip(mb, 0, M - 1) * B_mb
            ids_mb = lax.dynamic_slice_in_dim(ids, row, B_mb, 0)
            pos_mb = lax.dynamic_slice_in_dim(pos, row, B_mb, 0)
            ws_mb = lax.dynamic_slice_in_dim(ws, row, B_mb, 0)
            gs_mb = lax.dynamic_slice_in_dim(gs, row, B_mb, 0)
            kvv_mb = lax.dynamic_slice_in_dim(kvv, row, B_mb, 0)
            # bubble ticks must not mutate the pool
            ws_eff = jnp.where(valid, ws_mb, num_slots)

            h_emb = embed[ids_mb]
            if cfg.scale_embeddings:  # Gemma: sqrt(hidden) on input
                h_emb = h_emb * jnp.asarray(
                    cfg.hidden_size**0.5, h_emb.dtype
                )
            h_in = jnp.where(stage == 0, h_emb, state)
            h_out, pk, pv = run_stage(h_in, pos_mb, pk, pv, ws_eff, gs_mb,
                                      kvv_mb)

            out_upd = lax.dynamic_update_slice_in_dim(out, h_out, row, 0)
            out = jnp.where(valid & (stage == S - 1), out_upd, out)

            state = lax.ppermute(
                h_out, "stage", [(i, i + 1) for i in range(S - 1)]
            )
            return state, pk, pv, out

        state0 = lax.pcast(
            jnp.zeros((B_mb, T, cfg.hidden_size), embed.dtype),
            "stage", to="varying",
        )
        out0 = lax.pcast(
            jnp.zeros((B, T, cfg.hidden_size), embed.dtype),
            "stage", to="varying",
        )
        state, pk, pv, out = lax.fori_loop(
            0, M + S - 1, tick, (state0, pk, pv, out0)
        )

        out = lax.psum(out, "stage")  # only the last stage wrote; broadcast
        if slice_h:
            # single-position unembed (prefill chunks): slice hidden
            # states BEFORE the vocab projection so the [B, T, V]
            # materialization never happens on any stage
            out = out[jnp.arange(out.shape[0]), lidx][:, None]
        h = rms_norm(out, final_norm, cfg.rms_norm_eps)
        logits = jnp.einsum(
            "bth,hv->btv", h, unembed, preferred_element_type=jnp.float32
        )
        if cfg.final_logit_softcap is not None:  # Gemma soft-capping
            cap = cfg.final_logit_softcap
            logits = jnp.tanh(logits / cap) * cap
        return logits, pk, pv

    unembed = (
        params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    )
    # QuantPool pools: codes AND scales stage-shard on the layer axis
    pool_spec = (
        QuantPool(P("stage"), P("stage")) if kv_quantized else P("stage")
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        axis_names={"stage"},  # tensor/data stay GSPMD-managed inside
        in_specs=(
            P("stage"),  # layer stacks [L, ...] -> local [L/S, ...]
            P(),  # embed
            P(),  # final_norm
            P(),  # unembed
            P(),  # ids
            P(),  # positions
            pool_spec,  # pool_k [L, num_slots, KV, D]
            pool_spec,  # pool_v
            P(),  # write_slots
            P(),  # gather_slots
            P(),  # kv_valid_len
            P(),  # logits_idx (or its zero placeholder)
        ),
        out_specs=(P(), pool_spec, pool_spec),
    )
    lidx = (
        logits_idx if slice_h
        else jnp.zeros((input_ids.shape[0],), jnp.int32)
    )
    return fn(
        params["layers"], params["embed"],
        params["final_norm"], unembed,
        input_ids, positions, pool_k, pool_v, write_slots, gather_slots,
        kv_valid_len, lidx,
    )


def pp_greedy_generate(
    mesh,
    params: llama.Params,
    cfg: ModelConfig,
    prompt_ids: jnp.ndarray,
    max_new_tokens: int,
    max_seq: int,
    num_microbatches: int = 1,
) -> jnp.ndarray:
    """Greedy generation through the pipeline: prefill then per-token
    decode steps, all over the stage axis. prompt_ids: [B, T0] (no
    padding). Returns [B, max_new_tokens]."""
    B, T0 = prompt_ids.shape
    cache = llama.KVCache.create(cfg, B, max_seq, dtype=params["embed"].dtype)
    positions = jnp.broadcast_to(jnp.arange(T0)[None], (B, T0))
    step = functools.partial(pp_forward, mesh, params, cfg,
                             num_microbatches=num_microbatches)
    with mesh:
        logits, ck, cv = step(
            prompt_ids, positions, cache.k, cache.v, positions,
            jnp.full((B,), T0, jnp.int32),
        )
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        outs = [tok]
        for i in range(1, max_new_tokens):
            pos = jnp.full((B, 1), T0 + i - 1, jnp.int32)
            logits, ck, cv = step(
                tok[:, None], pos, ck, cv, pos,
                jnp.full((B,), T0 + i, jnp.int32),
            )
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            outs.append(tok)
    return jnp.stack(outs, axis=1)
