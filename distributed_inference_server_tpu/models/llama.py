"""Llama-family transformer as pure JAX functions.

This realizes the model-execution layer the reference left as a stub
(``crates/inference/src/worker.rs:1``; llama.cpp was the planned backend,
``design.md:7``, ``tasks.md:196-200`` [spec]) — natively in JAX/XLA.

Design, TPU-first:

- Parameters are a pytree of **stacked** per-layer weights (leading axis =
  layer), and the forward pass runs layers with ``lax.scan`` — compile time
  is O(1) in depth and XLA sees one fused block body.
- Weights live in bf16 (MXU-native); RMSNorm statistics, softmax, and the
  final logits are f32.
- Linear weights are stored [in, out] so the hot path is plain ``x @ W``
  (row-major MXU tiling), the transpose of the HF [out, in] layout.
- Attention is pluggable: the block computes q/k/v and delegates cache
  write + attention to an ``AttentionBackend`` (dense here; paged in
  engine/kv_cache.py; Pallas kernels in ops/pallas/). All backends share the
  (q_positions, kv_valid_len) ragged-batch contract of ops/attention.py.
- MoE layers (Mixtral-style) route with top-k gating and compute every
  expert on every token at small scale; the expert-parallel path in
  parallel/ replaces this with all-to-all dispatch.
"""

from __future__ import annotations

import os
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from distributed_inference_server_tpu.models.configs import ModelConfig
from distributed_inference_server_tpu.ops.attention import gqa_attention
from distributed_inference_server_tpu.ops.norms import rms_norm
from distributed_inference_server_tpu.ops.rotary import apply_rope, rope_frequencies

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------


def init_params(
    rng: jax.Array, cfg: ModelConfig, dtype: jnp.dtype = jnp.bfloat16
) -> Params:
    """Random parameters with HF-compatible shapes (stacked per layer)."""
    keys = jax.random.split(rng, 16)
    H, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    std = 0.02

    def w(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    layers: Dict[str, jnp.ndarray] = {
        "attn_norm": jnp.ones((L, H), dtype),
        "wq": w(keys[0], (L, H, cfg.q_size)),
        "wk": w(keys[1], (L, H, cfg.kv_size)),
        "wv": w(keys[2], (L, H, cfg.kv_size)),
        "wo": w(keys[3], (L, cfg.q_size, H)),
        "mlp_norm": jnp.ones((L, H), dtype),
    }
    if cfg.sandwich_norms:  # Gemma-2 post-attention / post-MLP norms
        layers.update(
            post_attn_norm=jnp.ones((L, H), dtype),
            post_mlp_norm=jnp.ones((L, H), dtype),
        )
    if cfg.attention_bias:  # Qwen2-style q/k/v projection bias
        layers.update(
            bq=w(keys[10], (L, cfg.q_size)),
            bk=w(keys[11], (L, cfg.kv_size)),
            bv=w(keys[12], (L, cfg.kv_size)),
        )
    if cfg.is_moe:
        E = cfg.num_experts
        layers.update(
            router=w(keys[4], (L, H, E)),
            w_gate=w(keys[5], (L, E, H, I)),
            w_up=w(keys[6], (L, E, H, I)),
            w_down=w(keys[7], (L, E, I, H)),
        )
    else:
        layers.update(
            w_gate=w(keys[5], (L, H, I)),
            w_up=w(keys[6], (L, H, I)),
            w_down=w(keys[7], (L, I, H)),
        )

    params: Params = {
        "embed": w(keys[8], (cfg.vocab_size, H)),
        "layers": layers,
        "final_norm": jnp.ones((H,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(keys[9], (H, cfg.vocab_size))
    return params


# ---------------------------------------------------------------------------
# Dense contiguous KV cache (M1 backend; the paged cache lives in engine/)
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    """Contiguous per-layer KV cache: k, v are [L, B, S, KV_heads, head_dim]."""

    k: jnp.ndarray
    v: jnp.ndarray

    @classmethod
    def create(
        cls, cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16
    ) -> "KVCache":
        shape = (cfg.num_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def _write_kv(
    cache: jnp.ndarray, l, new: jnp.ndarray, write_pos: jnp.ndarray
) -> jnp.ndarray:
    """Scatter new K or V ([B, T, KV, D]) into layer ``l`` of the STACKED
    dense cache ([L, B, S, KV, D]) at per-row positions ([B, T]);
    out-of-range positions are dropped (used to discard padding tokens).
    One scatter on the stacked buffer — the form XLA aliases in place
    when the cache is a scan carry (see scan_layer_blocks)."""
    B = new.shape[0]
    rows = jnp.arange(B)[:, None]
    return cache.at[l, rows, write_pos].set(new, mode="drop")


# ---------------------------------------------------------------------------
# Transformer forward
# ---------------------------------------------------------------------------


def _mm(x: jnp.ndarray, w) -> jnp.ndarray:
    """x @ w with transparent weight-only quantization (ops/quant.py):
    quantized weights dequantize on the fly — XLA fuses the convert+scale
    into the matmul, so HBM traffic stays int8/int4. With
    DIS_TPU_PALLAS_FUSED=1 (single-device opt-in), aligned quantized
    matmuls take the Pallas group-dequant kernel instead: dequant happens
    in VMEM after the int tile's DMA, immune to XLA fusion misses."""
    from distributed_inference_server_tpu.ops.pallas.fused import (
        fused_mode,
        quant_matmul_pallas,
        quant_matmul_supported,
    )
    from distributed_inference_server_tpu.ops.quant import (
        Q4Tensor,
        dense_view,
        is_quantized,
    )

    mode = fused_mode()
    if mode is not None and is_quantized(w) and w.q.ndim == 2:
        packed = isinstance(w, Q4Tensor)
        K = w.q.shape[0] * (2 if packed else 1)
        N = w.s.shape[-1]
        group = K // w.s.shape[-2]
        M = 1
        for d in x.shape[:-1]:
            M *= d
        if x.shape[-1] == K and quant_matmul_supported(M, K, N, group,
                                                       packed):
            out = quant_matmul_pallas(
                x.reshape(M, K), w.q, w.s, group=group, packed=packed,
                interpret=mode == "interpret",
            )
            return out.reshape(*x.shape[:-1], N)
    return x @ dense_view(w, x.dtype)


def _dq(w, dtype):
    """Dense view of a possibly-quantized weight (einsum call sites)."""
    from distributed_inference_server_tpu.ops.quant import dense_view

    return dense_view(w, dtype)


def _act(x: jnp.ndarray, activation: str) -> jnp.ndarray:
    if activation == "gelu_tanh":  # Gemma GeGLU (HF gelu_pytorch_tanh)
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def _mlp(h: jnp.ndarray, layer: Dict[str, jnp.ndarray],
         activation: str = "silu") -> jnp.ndarray:
    """Gated MLP: down( act(gate(x)) * up(x) ) — SwiGLU or GeGLU."""
    gate = _act(_mm(h, layer["w_gate"]), activation)
    up = _mm(h, layer["w_up"])
    return _mm(gate * up, layer["w_down"])


def _moe_mlp(h: jnp.ndarray, layer: Dict[str, jnp.ndarray], cfg: ModelConfig):
    """Mixtral-style sparse MoE, dense-compute form: softmax(top-k) routing
    with every expert evaluated and combined by weight. Efficient enough at
    test scale; ops/moe.py provides the capacity-based sharded dispatch."""
    B, T, H = h.shape
    x = h.reshape(-1, H)  # [N, H]
    router_logits = (x @ layer["router"]).astype(jnp.float32)  # [N, E]
    weights, idx = lax.top_k(router_logits, cfg.num_experts_per_tok)
    weights = jax.nn.softmax(weights, axis=-1)  # [N, k]
    # combine weights per expert: [N, E]
    combine = jnp.zeros_like(router_logits)
    combine = combine.at[jnp.arange(x.shape[0])[:, None], idx].set(weights)
    # every expert on every token: [E, N, H] -> weighted sum
    gate = jax.nn.silu(
        jnp.einsum("nh,ehi->eni", x, _dq(layer["w_gate"], x.dtype))
    )
    up = jnp.einsum("nh,ehi->eni", x, _dq(layer["w_up"], x.dtype))
    expert_out = jnp.einsum(
        "eni,eih->enh", gate * up, _dq(layer["w_down"], x.dtype)
    )
    out = jnp.einsum("enh,ne->nh", expert_out, combine.astype(expert_out.dtype))
    return out.reshape(B, T, H)


def _moe(h: jnp.ndarray, layer: Dict[str, jnp.ndarray], cfg: ModelConfig,
         moe_impl: str, valid_tokens: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Route to the dense-compute MoE or the capacity-based dispatch
    (ops/moe.py; sharding constraints make GSPMD emit the all-to-all when
    the expert weights are mesh-sharded). ``valid_tokens`` keeps bucket
    padding / inactive decode slots from consuming expert capacity."""
    if moe_impl == "dense":
        return _moe_mlp(h, layer, cfg)
    from distributed_inference_server_tpu.ops.moe import (
        expert_capacity,
        moe_mlp_ep,
    )

    B, T, _ = h.shape
    cap = expert_capacity(
        B * T, cfg.num_experts, cfg.num_experts_per_tok,
        cfg.moe_capacity_factor,
    )
    return moe_mlp_ep(
        h, layer, cfg.num_experts, cfg.num_experts_per_tok,
        capacity=cap, shard_experts=(moe_impl == "ep"),
        valid_tokens=valid_tokens,
    )


def _run_layers(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    write_fn,
    attend_fn,
    moe_impl: str = "dense",
    valid_tokens: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared transformer trunk: embed, scan layer blocks, final norm.

    The cache backend is pluggable: ``write_fn(pool, l, new_kv) ->
    pool`` scatters the new tokens' K/V into layer ``l`` of the STACKED
    cache in one op (scan-carry in-place aliasing — scan_layer_blocks);
    ``attend_fn(q, k_layer, v_layer, window) -> out`` runs attention
    against this layer's cache view (``window`` = the layer's sliding
    window, 0 = full causal). Dense (contiguous) and paged backends both
    route through here, so the block body exists exactly once.

    Returns (normed hidden [B, T, H], new cache_k, new cache_v).
    """
    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
    h = params["embed"][input_ids]  # [B, T, H]
    if cfg.scale_embeddings:  # Gemma: embeddings scale by sqrt(hidden)
        h = h * jnp.asarray(cfg.hidden_size**0.5, h.dtype)
    windows = (
        jnp.asarray(cfg.layer_windows(), jnp.int32)
        if cfg.sliding_window else None
    )
    h, (new_k, new_v) = scan_layer_blocks(
        cfg, h, params["layers"], cache_k, cache_v, windows, positions,
        write_fn, attend_fn, inv_freq, moe_impl, valid_tokens,
    )
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h, new_k, new_v


def make_paged_write_fn(write_slots, kv_quantized: bool):
    """Stacked-pool write_fn for the paged cache: one scatter at
    ``[l, write_slots]`` (mode="drop" — out-of-range slots are padding),
    quantizing at write time for QuantPool pools. The ONE definition
    shared by ``paged_forward`` and ``parallel/pp.py:pp_paged_forward``
    so the quantized write path cannot drift between them."""
    from distributed_inference_server_tpu.ops.quant import (
        QuantPool,
        quantize_kv,
    )

    def write_fn(pool, l, new):
        if kv_quantized:
            codes, scale = quantize_kv(new)
            return QuantPool(
                pool.data.at[l, write_slots].set(codes, mode="drop"),
                pool.scale.at[l, write_slots].set(scale, mode="drop"),
            )
        return pool.at[l, write_slots].set(new, mode="drop")

    return write_fn


def pool_at(pool, l):
    """Read layer ``l``'s cache from a stacked pool (QuantPool-aware).

    A pure read: XLA fuses the dynamic-slice into the downstream gather
    (gather-of-slice folds the layer offset into the gather indices), so
    only the gathered rows cost HBM traffic."""
    from distributed_inference_server_tpu.ops.quant import QuantPool

    if isinstance(pool, QuantPool):
        return QuantPool(pool_at(pool.data, l), pool_at(pool.scale, l))
    return lax.dynamic_index_in_dim(pool, l, 0, keepdims=False)


def scan_layer_blocks(cfg, h, layers, cache_k, cache_v, windows, positions,
                      write_fn, attend_fn, inv_freq, moe_impl="dense",
                      valid_tokens=None):
    """``lax.scan`` over stacked layer blocks — the one place the scan
    body exists (``_run_layers`` and both pipeline-parallel stage runners
    in parallel/pp.py drive their layer stacks through here).

    The KV pools ride the scan as CARRY, not xs/ys (changed r5): the
    xs->ys form forced XLA to materialize the ENTIRE stacked pool as a
    fresh scan output every call — ~1.26 GB/decode-step of pure copy
    traffic at the 1B bench geometry, growing with batch (the prime
    suspect for the 10x roofline gap and the superlinear b128 step
    cost; CPU microbenchmark: 266 ms/call xs->ys vs 0.03 ms carried at
    a 135 MB pool). With the pools carried, ``write_fn(pool, l, new)``
    scatters DIRECTLY into the stacked buffer at layer ``l`` (XLA
    aliases scan carries in place, so only the written rows move), and
    reads extract layer ``l`` via ``pool_at`` (fuses into the gather).
    NOTE the write MUST be a single 2D scatter on the stacked pool —
    extract-scatter-writeback does NOT fuse (85 ms/call measured).

    ``windows`` rides the scan as per-layer data (Gemma-2's alternating
    local/global schedule shares ONE compiled block body — no per-layer
    recompile, no unrolled scan) or is None when no layer slides: then
    window=None is passed STATICALLY so full-causal models keep
    gqa_attention's maskless branch instead of paying a traced
    (w <= 0) | ... [B, T, S] term every layer."""
    L = layers["attn_norm"].shape[0]
    idx = jnp.arange(L, dtype=jnp.int32)

    def block(carry, xs):
        h, ck, cv = carry
        if windows is None:
            layer, l = xs
            window = None
        else:
            layer, l, window = xs
        h, ck, cv = layer_block(
            cfg, layer, h, positions, ck, cv, l, write_fn,
            attend_fn, inv_freq, moe_impl, valid_tokens, window=window,
        )
        return (h, ck, cv), None

    xs = (layers, idx) if windows is None else (layers, idx, windows)
    (h, ck, cv), _ = lax.scan(block, (h, cache_k, cache_v), xs)
    return h, (ck, cv)


def layer_block(
    cfg: ModelConfig,
    layer: Dict[str, jnp.ndarray],
    h: jnp.ndarray,
    positions: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    l: jnp.ndarray,
    write_fn,
    attend_fn,
    inv_freq: jnp.ndarray,
    moe_impl: str = "dense",
    valid_tokens: Optional[jnp.ndarray] = None,
    window=0,
):
    """One transformer block (attention + MLP/MoE) against the STACKED
    cache — the scan body of ``_run_layers``, exposed so the pipeline-
    parallel runners (parallel/pp.py) can drive per-stage layer stacks.

    ``pool_k``/``pool_v`` are the full (local) stacked pools and ``l``
    the traced layer index; ``write_fn(pool, l, new) -> pool`` must
    scatter in one op on the stacked buffer (see scan_layer_blocks on
    why), and attention reads this layer's cache via ``pool_at``.
    ``window`` is this layer's sliding window (0 = full causal; may be a
    traced scalar riding the layer scan) and is handed to ``attend_fn``
    as its fourth argument."""
    B, T, _ = h.shape
    x = rms_norm(h, layer["attn_norm"], cfg.rms_norm_eps)
    q, k, v = _mm(x, layer["wq"]), _mm(x, layer["wk"]), _mm(x, layer["wv"])
    if cfg.attention_bias:
        q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
    q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)
    if cfg.query_pre_attn_scalar is not None:
        # Gemma attention-scale override: backends scale by 1/sqrt(D), so
        # pre-scaling q by sqrt(D/scalar) nets 1/sqrt(query_pre_attn_scalar)
        q = q * jnp.asarray(
            (cfg.head_dim / cfg.query_pre_attn_scalar) ** 0.5, q.dtype
        )
    pool_k = write_fn(pool_k, l, k)
    pool_v = write_fn(pool_v, l, v)
    attn = attend_fn(q, pool_at(pool_k, l), pool_at(pool_v, l), window)
    attn_out = _mm(attn.reshape(B, T, cfg.q_size), layer["wo"])
    if cfg.sandwich_norms:
        attn_out = rms_norm(
            attn_out, layer["post_attn_norm"], cfg.rms_norm_eps
        )
    h = h + attn_out
    x = rms_norm(h, layer["mlp_norm"], cfg.rms_norm_eps)
    mlp_out = (
        _moe(x, layer, cfg, moe_impl, valid_tokens)
        if cfg.is_moe
        else _mlp(x, layer, cfg.activation)
    )
    if cfg.sandwich_norms:
        mlp_out = rms_norm(mlp_out, layer["post_mlp_norm"], cfg.rms_norm_eps)
    h = h + mlp_out
    return h, pool_k, pool_v


def _unembed(params: Params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    unembed = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    logits = jnp.einsum(
        "bth,hv->btv", h, unembed, preferred_element_type=jnp.float32
    )
    if cfg.final_logit_softcap is not None:  # Gemma logit soft-capping
        cap = cfg.final_logit_softcap
        logits = jnp.tanh(logits / cap) * cap
    return logits


def forward(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    cache: KVCache,
    write_pos: jnp.ndarray,
    kv_valid_len: jnp.ndarray,
    moe_impl: str = "dense",
) -> Tuple[jnp.ndarray, KVCache]:
    """Run the transformer over new tokens, updating the dense KV cache.

    Args:
      input_ids: [B, T] new token ids (prefill: the prompt; decode: T=1).
      positions: [B, T] absolute positions of those tokens.
      cache: dense KV cache to read/write.
      write_pos: [B, T] cache slot to write each new token's K/V into
        (>= max_seq to drop, e.g. padding).
      kv_valid_len: [B] valid cache length per row AFTER this write.

    Returns: (logits [B, T, vocab] f32, updated cache).
    """
    write_fn = lambda pool, l, new: _write_kv(pool, l, new, write_pos)
    attend_fn = lambda q, k, v, w: gqa_attention(
        q, k, v, positions, kv_valid_len, w, cfg.attn_logit_softcap)
    h, new_k, new_v = _run_layers(
        params, cfg, input_ids, positions, cache.k, cache.v, write_fn,
        attend_fn, moe_impl=moe_impl,
        valid_tokens=write_pos < cache.k.shape[2],
    )
    return _unembed(params, cfg, h), KVCache(k=new_k, v=new_v)


def pallas_tuning() -> Tuple[int, int, int]:
    """Kernel tuning knobs from env — the SINGLE parse site shared by the
    serving builder (``make_pallas_attend``) and the in-window probe
    (``tools/kernel_probe.py``), so a sweep tunes exactly the program
    serving launches and the two cannot drift.

    Returns (decode_pages_per_block, prefill_pages_per_block,
    prefill_q_block). ``DIS_TPU_PALLAS_PAGES_PER_BLOCK`` sets both
    phases; the per-phase ``..._DECODE_PAGES_PER_BLOCK`` /
    ``..._PREFILL_PAGES_PER_BLOCK`` override it (the best DMA depth can
    differ between one-query decode and tiled prefill). Unset = the
    kernels' shipped defaults (8 pages, 128 queries)."""
    env = os.environ
    shared = env.get("DIS_TPU_PALLAS_PAGES_PER_BLOCK", "8")
    dpb = int(env.get("DIS_TPU_PALLAS_DECODE_PAGES_PER_BLOCK", shared))
    ppb = int(env.get("DIS_TPU_PALLAS_PREFILL_PAGES_PER_BLOCK", shared))
    qb = int(env.get("DIS_TPU_PALLAS_QBLOCK", "128"))
    return dpb, ppb, qb


def make_pallas_attend(page_size: int, softcap: float, decode_step: bool,
                       interpret=None):
    """Build the per-shard Pallas attend callable — the EXACT kernel-arg
    wiring the serving path launches. The engine's AOT "auto" probe uses
    this same builder (optionally wrapped in ``shard_pallas_attend``) so
    the probed program and the served program cannot drift apart.

    Decode: ``fn(q3 [B,H,D], k_pool, v_pool, tables, kv_valid, window)``;
    prefill: ``fn(q4 [B,T,H,D], k_pool, v_pool, tables, kv_valid,
    q_start, window)`` (note the kernel itself takes q_start BEFORE
    kv_valid — this wrapper's arg order matches shard_pallas_attend's
    specs instead). ``interpret=None`` keeps the kernels' own off-TPU
    auto-interpret default; the AOT probe passes False to make Mosaic
    judge for real."""
    from distributed_inference_server_tpu.ops.pallas import (
        paged_attention_decode,
        paged_attention_prefill,
    )

    dpb, ppb, qb = pallas_tuning()
    if decode_step:
        def fn(q3, k_layer, v_layer, tables, valid, w):
            return paged_attention_decode(
                q3, k_layer, v_layer, tables, valid,
                page_size=page_size, pages_per_block=dpb,
                sliding_window=w,
                attn_softcap=softcap, interpret=interpret,
            )
    else:
        def fn(q4, k_layer, v_layer, tables, valid, qs, w):
            return paged_attention_prefill(
                q4, k_layer, v_layer, tables, qs, valid,
                page_size=page_size, q_block=qb, pages_per_block=ppb,
                sliding_window=w,
                attn_softcap=softcap, interpret=interpret,
            )
    return fn


def make_ragged_attend(page_size: int, softcap: float, interpret=None):
    """Build the ragged mixed-batch Pallas attend callable — the ONE
    builder both the engine's AOT probe and the mixed-step serving path
    go through (docs/PERF.md design rule: probe and serving cannot
    drift). Subsumes the decode and prefill kernels for the mixed step:
    decode rows are q_len-1 segments, prefill chunks multi-window rows,
    all served by ``paged_attention_ragged``.

    ``fn(q [S, H, D], k_pool, v_pool, tables [Bm, P], tok_row [S],
    q_pos [S], kv_valid_len [Bm], window)``."""
    from distributed_inference_server_tpu.ops.pallas import (
        paged_attention_ragged,
    )

    _, ppb, qb = pallas_tuning()

    def fn(q3, k_layer, v_layer, tables, tok_row, q_pos, valid, w):
        return paged_attention_ragged(
            q3, k_layer, v_layer, tables, tok_row, q_pos, valid,
            page_size=page_size, q_block=qb, pages_per_block=ppb,
            sliding_window=w, attn_softcap=softcap, interpret=interpret,
        )

    return fn


def shard_ragged_attend(fn, mesh):
    """shard_map-wrap the ragged attend over the ``tensor`` axis: query
    heads and the pools' KV-head axis split, every per-token/per-row
    operand replicated (the mixed step does not shard rows — the engine
    rejects mixed_step_tokens under a data axis). Shared by the probe
    and the serving path like ``shard_pallas_attend``."""
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(None, "tensor", None),  # q [S, H, D]
            P(None, "tensor", None),  # pool layer [slots, KV, D]
            P(None, "tensor", None),
            P(None, None),  # page tables [Bm, P]
            P(None),  # tok_row [S]
            P(None),  # q_pos [S]
            P(None),  # kv_valid_len [Bm]
            P(),  # sliding window (replicated scalar)
        ),
        out_specs=P(None, "tensor", None),
        check_vma=False,
    )


def shard_pallas_attend(fn, mesh, decode_step: bool,
                        kv_quantized: bool = False):
    """shard_map-wrap a per-shard Pallas attend callable over ``mesh``:
    ``tensor`` splits query heads and the pools' KV-head axis, ``data``
    splits rows; the kernel body stays fully local (no collectives).

    ``fn(q, k_pool, v_pool, page_tables, kv_valid_len, window)`` for
    decode (q = [B, H, D]) or ``fn(q, k_pool, v_pool, page_tables,
    kv_valid_len, q_start, window)`` for chunked prefill
    (q = [B, T, H, D]); every per-row operand rides the specs so data
    shards see their own rows (closure capture would replicate).
    ``kv_quantized`` pools (ops.quant.QuantPool) get per-leaf specs:
    codes shard like the dense pool, scales [slots, KV] shard on the
    same KV-head axis.

    Shared by ``paged_forward`` and the engine's AOT "auto" probe so the
    probe lowers the SAME shard_map program the serving path launches —
    a standalone kernel lowering could in principle pass Mosaic while the
    sharded lowering fails (or vice versa)."""
    from jax.sharding import PartitionSpec as P

    from distributed_inference_server_tpu.ops.quant import QuantPool

    q_spec = (
        P("data", "tensor", None) if decode_step
        else P("data", None, "tensor", None)
    )
    pool_spec = P(None, "tensor", None)  # pool layer [slots, KV, D]
    if kv_quantized:
        pool_spec = QuantPool(pool_spec, P(None, "tensor"))
    in_specs = [
        q_spec,  # q [B, H, D] / [B, T, H, D]
        pool_spec,
        pool_spec,
        P("data", None),  # page tables [B, P]
        P("data"),  # kv_valid_len [B]
    ]
    if not decode_step:
        in_specs.append(P("data"))  # q_start [B] row starts
    in_specs.append(P())  # this layer's sliding window (replicated scalar)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=q_spec,
        check_vma=False,
    )


def gather_kv_window(k_layer, v_layer, gather_slots, page_size: int):
    """Gather each row's KV window from the flat pool.

    PRECONDITION when ``page_size > 0`` and the shapes divide evenly:
    every ``gather_slots`` row must be a page-aligned run — exactly
    ``table[p] * page_size + offset`` for offset 0..page_size-1 per
    page, which is how the engine builds them (the Pallas kernels rely
    on the same contract, llama.py ``make_pallas_attend``). Under that
    precondition, indexing whole [page_size, KV, D] pages moves ~16 KB
    contiguous chunks per index instead of 1 KB slots — an order of
    magnitude fewer gather indices for XLA's TPU gather lowering at
    identical semantics (out-of-range sentinel pages clamp, and padding
    is masked by kv_valid_len either way). Shape divisibility CANNOT
    detect a misaligned layout; a caller with arbitrary (non-run)
    slot indices must pass ``page_size=0`` to get the slot-granular
    gather.

    Returns (k_seq, v_seq), each [B, S_max, KV, D].
    """
    B, S = gather_slots.shape
    if page_size > 0 and k_layer.shape[0] % page_size == 0 \
            and S % page_size == 0:
        if os.environ.get("DIS_TPU_DEBUG_GATHER") == "1" and not isinstance(
            gather_slots, jax.core.Tracer
        ):
            # Debug-mode guard (ADVICE r4): shape divisibility cannot
            # detect a caller whose slot rows are NOT page-aligned runs —
            # such a caller would get wrong KV values silently. Concrete
            # (non-traced) inputs — i.e. direct/test calls — verify the
            # precondition here; inside jit the slots are tracers and the
            # contract rests on the engine's table construction.
            import numpy as np

            slots = np.asarray(gather_slots).reshape(B, -1, page_size)
            base = slots[:, :, :1]
            is_run = (slots == base + np.arange(page_size)).all(axis=2)
            # a consecutive run starting mid-page (e.g. [4..11] at
            # page_size 8) is NOT table[p]*page_size+offset either — the
            # fast path would silently gather page 0 instead of 4..11
            is_run &= (slots[:, :, 0] % page_size) == 0
            # sentinel pages (any slot >= pool size) clamp page-granular;
            # their rows need not be runs
            sentinel = (slots >= k_layer.shape[0]).any(axis=2)
            bad = ~(is_run | sentinel)
            assert not bad.any(), (
                "gather_kv_window fast path requires page-aligned slot "
                f"runs; misaligned rows at (batch, page)={np.argwhere(bad)[:4].tolist()} "
                "— pass page_size=0 for arbitrary slot layouts"
            )
        pt = gather_slots[:, ::page_size] // page_size  # [B, P]
        kp = k_layer.reshape(-1, page_size, *k_layer.shape[1:])
        vp = v_layer.reshape(-1, page_size, *v_layer.shape[1:])
        return (kp[pt].reshape(B, S, *k_layer.shape[1:]),
                vp[pt].reshape(B, S, *v_layer.shape[1:]))
    return k_layer[gather_slots], v_layer[gather_slots]


def paged_forward(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    write_slots: jnp.ndarray,
    gather_slots: jnp.ndarray,
    kv_valid_len: jnp.ndarray,
    attention_impl: str = "xla",
    page_size: int = 0,
    moe_impl: str = "dense",
    mesh=None,
    logits_idx: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Forward pass over the paged KV pool (engine/kv_cache.py).

    Args:
      input_ids, positions: [B, T] new tokens and absolute positions.
      pool_k, pool_v: [L, num_slots, KV, D] flat page pools (num_slots =
        num_pages * page_size).
      write_slots: [B, T] flat pool slot per new token (>= num_slots drops
        the write — padding / inactive rows).
      gather_slots: [B, S_max] flat slots covering each row's block table
        (S_max = max_pages_per_seq * page_size).
      kv_valid_len: [B] tokens valid in each row's gathered window.
      attention_impl: "xla" (gather-then-dense-attend, the reference path)
        or "pallas" (ragged paged-attention kernels reading pages straight
        from the pool — the decode kernel for T == 1, the chunked-prefill
        kernel for T > 1; requires ``page_size``, and for T > 1 each
        row's positions must be a contiguous run starting at
        positions[:, 0] — the engine's prefill-chunk layout).
      page_size: tokens per page; required for the Pallas path.
      mesh: the device mesh when running tensor-parallel. GSPMD cannot
        partition an opaque kernel, so under TP the Pallas call is wrapped
        in shard_map over the ``tensor`` axis — each shard runs the kernel
        on its own KV heads' pages, fully local, no collectives.

    Returns (logits [B, T, V] f32, new pool_k, new pool_v) — with
    ``logits_idx`` given ([B] per-row position in T), only that position
    is unembedded and the logits are [B, 1, V]. Prefill chunks use this:
    unembedding every position materializes [B, T, 128k] f32 (~2 GB of
    HBM writes at the bench geometry) and pays the full-vocab projection
    for T-1 positions whose logits the caller immediately discards.
    """
    if not isinstance(attention_impl, str):
        # (decode_impl, prefill_impl) pair from the engine's per-kernel
        # "auto" probe — pick by this call's token count
        attention_impl = attention_impl[0 if input_ids.shape[1] == 1 else 1]
    from distributed_inference_server_tpu.ops.quant import (
        QuantPool,
        dequantize_kv,
        pool_num_slots,
        quantize_kv,
    )

    kv_quantized = isinstance(pool_k, QuantPool)
    use_pallas = attention_impl == "pallas"
    if use_pallas:
        if page_size <= 0:
            raise ValueError("attention_impl='pallas' requires page_size")
        decode_step = input_ids.shape[1] == 1
        if kv_quantized and not decode_step:
            raise ValueError(
                "the Pallas chunked-prefill kernel has no int8-pool "
                "variant; quantized prefill must take the XLA path "
                "(the engine's kv_quant resolution does this)"
            )
        # gather_slots rows are table[p]*page_size + offset by construction
        page_tables = gather_slots[:, ::page_size] // page_size
        if not decode_step:
            # q_start rides as an explicit row argument (NOT a closure
            # capture): shard_map replicates captured values, which would
            # hand every data shard the full global [B] starts misaligned
            # with its own rows
            q_start = positions[:, 0]

        _attend_pallas = make_pallas_attend(
            page_size, cfg.attn_logit_softcap or 0.0, decode_step
        )
        if mesh is not None and mesh.shape.get("tensor", 1) > 1:
            _attend_pallas = shard_pallas_attend(
                _attend_pallas, mesh, decode_step,
                kv_quantized=kv_quantized,
            )

    write_fn = make_paged_write_fn(write_slots, kv_quantized)

    def attend_fn(q, k_layer, v_layer, window):
        if use_pallas:
            if window is None:  # static full-causal: kernels take w <= 0
                window = jnp.int32(0)
            if decode_step:
                out = _attend_pallas(
                    q[:, 0], k_layer, v_layer, page_tables, kv_valid_len,
                    window,
                )
                return out[:, None]
            return _attend_pallas(
                q, k_layer, v_layer, page_tables, kv_valid_len, q_start,
                window,
            )
        if kv_quantized:
            kd, vd = gather_kv_window(
                k_layer.data, v_layer.data, gather_slots, page_size
            )
            ks, vs = gather_kv_window(
                k_layer.scale, v_layer.scale, gather_slots, page_size
            )
            k_seq = dequantize_kv(kd, ks, q.dtype)
            v_seq = dequantize_kv(vd, vs, q.dtype)
        else:
            k_seq, v_seq = gather_kv_window(
                k_layer, v_layer, gather_slots, page_size
            )  # [B, S_max, KV, D]
        return gqa_attention(q, k_seq, v_seq, positions, kv_valid_len,
                             window, cfg.attn_logit_softcap)

    h, new_k, new_v = _run_layers(
        params, cfg, input_ids, positions, pool_k, pool_v, write_fn,
        attend_fn, moe_impl=moe_impl,
        # real tokens have in-range write slots; padding is dropped
        valid_tokens=write_slots < pool_num_slots(pool_k),
    )
    if logits_idx is not None:
        h = h[jnp.arange(h.shape[0]), logits_idx][:, None]
    return _unembed(params, cfg, h), new_k, new_v


def ragged_paged_forward(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    write_slots: jnp.ndarray,
    tok_row: jnp.ndarray,
    gather_slots: jnp.ndarray,
    kv_valid_len: jnp.ndarray,
    attention_impl: str = "xla",
    page_size: int = 0,
    moe_impl: str = "dense",
    mesh=None,
    logits_idx: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Forward pass over a PACKED ragged mixed batch (the engine's mixed
    step, engine/engine.py ``_mixed_step``): one flat token axis carries
    decode rows (one token each) and prefill chunks back-to-back, each
    token attending its OWN row's pages — one dispatch serves both
    phases instead of a prefill-quantum program stalling the decode
    block.

    Args:
      input_ids, positions: [1, S] packed new tokens / absolute positions.
      pool_k, pool_v: [L, num_slots, KV, D] flat page pools (QuantPool
        for int8 KV — served on the XLA path).
      write_slots: [1, S] flat pool slot per packed token (>= num_slots
        drops — padding).
      tok_row: [S] owning batch row per token (-1 = padding).
      gather_slots: [Bm, S_max] flat slots covering each row's table.
      kv_valid_len: [Bm] valid tokens per row INCLUDING its new tokens.
      attention_impl: "xla" (ragged_gqa_attention over the gathered
        windows) or "pallas" (the ragged mixed-batch kernel via
        ``make_ragged_attend`` — the one builder the probe compiles).
      logits_idx: [N] packed positions to unembed (decode slots + the
        chunk-final tokens); required — a mixed step never wants all S.

    Returns (logits [N, V] f32, new pool_k, new pool_v).
    """
    from distributed_inference_server_tpu.ops.attention import (
        ragged_gqa_attention,
    )
    from distributed_inference_server_tpu.ops.quant import (
        QuantPool,
        dequantize_kv,
        pool_num_slots,
    )

    kv_quantized = isinstance(pool_k, QuantPool)
    use_pallas = attention_impl == "pallas"
    if use_pallas:
        if page_size <= 0:
            raise ValueError("attention_impl='pallas' requires page_size")
        if kv_quantized:
            raise ValueError(
                "the ragged mixed-batch kernel has no int8-pool variant; "
                "quantized pools serve the mixed step on the XLA path "
                "(the engine's resolution does this)"
            )
        page_tables = gather_slots[:, ::page_size] // page_size
        _attend = make_ragged_attend(
            page_size, cfg.attn_logit_softcap or 0.0
        )
        if mesh is not None and mesh.shape.get("tensor", 1) > 1:
            _attend = shard_ragged_attend(_attend, mesh)

    write_fn = make_paged_write_fn(write_slots, kv_quantized)
    flat_pos = positions[0]

    def attend_fn(q, k_layer, v_layer, window):
        if use_pallas:
            if window is None:
                window = jnp.int32(0)
            return _attend(
                q[0], k_layer, v_layer, page_tables, tok_row, flat_pos,
                kv_valid_len, window,
            )[None]
        if kv_quantized:
            kd, vd = gather_kv_window(
                k_layer.data, v_layer.data, gather_slots, page_size
            )
            ks, vs = gather_kv_window(
                k_layer.scale, v_layer.scale, gather_slots, page_size
            )
            k_seq = dequantize_kv(kd, ks, q.dtype)
            v_seq = dequantize_kv(vd, vs, q.dtype)
        else:
            k_seq, v_seq = gather_kv_window(
                k_layer, v_layer, gather_slots, page_size
            )  # [Bm, S_max, KV, D]
        return ragged_gqa_attention(
            q[0], k_seq, v_seq, tok_row, flat_pos, kv_valid_len,
            window, cfg.attn_logit_softcap,
        )[None]

    h, new_k, new_v = _run_layers(
        params, cfg, input_ids, positions, pool_k, pool_v, write_fn,
        attend_fn, moe_impl=moe_impl,
        valid_tokens=write_slots < pool_num_slots(pool_k),
    )
    # unembed only the sampled positions: [1, S, H] -> [N, V]
    h = h[0, logits_idx]
    return _unembed(params, cfg, h[None])[0], new_k, new_v


def hidden_states(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    kv_valid_len: jnp.ndarray,
) -> jnp.ndarray:
    """Final-layer hidden states (pre-unembedding) for the embeddings
    endpoint: a cache-less full forward. Returns [B, T, H] f32."""
    B, T = input_ids.shape
    cache = KVCache.create(cfg, B, T, dtype=params["embed"].dtype)
    write_fn = lambda pool, l, new: _write_kv(pool, l, new, positions)
    attend_fn = lambda q, k, v, w: gqa_attention(
        q, k, v, positions, kv_valid_len, w, cfg.attn_logit_softcap)
    h, _, _ = _run_layers(
        params, cfg, input_ids, positions, cache.k, cache.v, write_fn, attend_fn
    )
    return h.astype(jnp.float32)
