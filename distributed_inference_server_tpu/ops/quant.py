"""Weight quantization: symmetric per-group int8 and packed int4.

The reference planned quantized inference through llama.cpp's GGUF levels
(F32/F16/Q8_0/Q4_0/Q4_K_M — design.md:324-332 [spec]). The TPU-native
equivalents are weight-only int8 ("Q8_0"-class) and group-wise packed
int4 ("Q4_0"-class): weights live in HBM at 1/2 or 1/4 the bytes — decode
is HBM-bandwidth-bound, so weight bytes ≈ step time — and are dequantized
on the fly; XLA fuses the convert+scale into the matmul's operand read,
so nothing dense is materialized in HBM.

Representation: ``Q8Tensor``/``Q4Tensor`` NamedTuples (valid JAX pytrees,
so they ride through ``lax.scan`` layer stacking, ``jax.jit``, and
``shard_params`` unchanged). Scales are per (input-group, out-column),
group size along the input (contraction) axis. int4 packs two values per
byte along the input axis.

``quantize_params`` converts a Llama/Mixtral parameter tree's seven
linear families; embeddings/norms/unembedding stay full precision (they
are small and accuracy-critical).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple, Union

import jax.numpy as jnp

_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


class Q8Tensor(NamedTuple):
    """int8 weight [..., in, out] + f32 scales [..., in/G, out]."""

    q: jnp.ndarray
    s: jnp.ndarray


class Q4Tensor(NamedTuple):
    """packed uint8 weight [..., in/2, out] (two int4 along the input
    axis) + f32 scales [..., in/G, out]."""

    q: jnp.ndarray
    s: jnp.ndarray


QuantTensor = Union[Q8Tensor, Q4Tensor]


def _group_scales(w: jnp.ndarray, group_size: int, qmax: int) -> jnp.ndarray:
    *lead, d_in, d_out = w.shape
    g = w.reshape(*lead, d_in // group_size, group_size, d_out)
    absmax = jnp.max(jnp.abs(g.astype(jnp.float32)), axis=-2)
    return jnp.maximum(absmax, 1e-8) / qmax  # [..., G, out]


def quantize_int8(w: jnp.ndarray, group_size: int = 128) -> Q8Tensor:
    """Symmetric int8 over input-axis groups. w: [..., in, out]."""
    *lead, d_in, d_out = w.shape
    gs = min(group_size, d_in)
    if d_in % gs:
        raise ValueError(f"group_size {gs} does not divide in-dim {d_in}")
    s = _group_scales(w, gs, 127)
    g = w.astype(jnp.float32).reshape(*lead, d_in // gs, gs, d_out)
    q = jnp.clip(jnp.round(g / s[..., None, :]), -127, 127).astype(jnp.int8)
    return Q8Tensor(q=q.reshape(*lead, d_in, d_out), s=s)


def quantize_int4(w: jnp.ndarray, group_size: int = 64) -> Q4Tensor:
    """Symmetric int4 (range [-7, 7]) over input-axis groups, packed two
    values per byte along the input axis. w: [..., in, out], in even."""
    *lead, d_in, d_out = w.shape
    gs = min(group_size, d_in)
    if d_in % gs or d_in % 2:
        raise ValueError(
            f"int4 needs even in-dim divisible by group {gs}, got {d_in}"
        )
    s = _group_scales(w, gs, 7)
    g = w.astype(jnp.float32).reshape(*lead, d_in // gs, gs, d_out)
    q = jnp.clip(jnp.round(g / s[..., None, :]), -7, 7).astype(jnp.int8)
    q = q.reshape(*lead, d_in, d_out)
    # pack adjacent input rows: low nibble = even row, high nibble = odd
    even = q[..., 0::2, :].astype(jnp.uint8) & 0xF
    odd = q[..., 1::2, :].astype(jnp.uint8) & 0xF
    return Q4Tensor(q=(odd << 4) | even, s=s)


def dequantize(w: QuantTensor, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Dense [..., in, out] weight; under jit XLA fuses this into the
    consuming matmul (the HBM read stays int8/int4)."""
    if isinstance(w, Q4Tensor):
        packed = w.q
        low = (packed & 0xF).astype(jnp.int8)
        high = (packed >> 4).astype(jnp.int8)
        # sign-extend nibbles: values were clipped to [-7, 7]
        low = jnp.where(low > 7, low - 16, low)
        high = jnp.where(high > 7, high - 16, high)
        *lead, half, d_out = packed.shape
        q = jnp.stack([low, high], axis=-2)  # [..., half, 2, out]
        q = q.reshape(*lead, half * 2, d_out)
    elif isinstance(w, Q8Tensor):
        q = w.q
    else:
        return w.astype(dtype) if w.dtype != dtype else w
    *lead, d_in, d_out = q.shape
    groups = w.s.shape[-2]
    gs = d_in // groups
    deq = (
        q.astype(jnp.float32).reshape(*lead, groups, gs, d_out)
        * w.s[..., None, :]
    )
    return deq.reshape(*lead, d_in, d_out).astype(dtype)


def is_quantized(w: Any) -> bool:
    return isinstance(w, (Q8Tensor, Q4Tensor))


def init_random_quantized(
    rng, cfg, mode: str, dtype=jnp.bfloat16, group_size: int = 0
) -> Dict[str, Any]:
    """Random param tree with the linear families created DIRECTLY in
    quantized form — no dense intermediate. ``quantize_params`` over
    ``llama.init_params`` would materialize the full-precision tree
    first, which at 8B bf16 (~16 GB) exceeds one v5e chip's HBM; this
    builds int8/int4 leaves from random bits (an 8B int8 tree is ~8 GB),
    so single-chip 8B benchmarking is possible. Weight content is
    irrelevant to throughput; scales are 1/(qmax*sqrt(d_in)) so
    dequantized magnitudes match init_params' 0.02-ish normal init.
    """
    import jax
    from jax.tree_util import (
        DictKey,
        tree_flatten_with_path,
        tree_unflatten,
    )

    from distributed_inference_server_tpu.models import llama

    if mode == "none":
        return llama.init_params(rng, cfg, dtype=dtype)
    if mode not in ("int8", "int4"):
        raise ValueError(f"unknown quantization mode {mode!r}")
    qmax = 127 if mode == "int8" else 7
    gs_default = group_size or (128 if mode == "int8" else 64)

    shapes = jax.eval_shape(
        lambda k: llama.init_params(k, cfg, dtype=dtype), rng
    )
    leaves, treedef = tree_flatten_with_path(shapes)
    keys = jax.random.split(rng, len(leaves))

    def quant_leaf(shape, k):
        *lead, d_in, d_out = shape
        gs = min(gs_default, d_in)
        s = jnp.full(
            (*lead, d_in // gs, d_out),
            1.0 / (qmax * (d_in ** 0.5)), jnp.float32,
        )
        if mode == "int8":
            bits = jax.random.bits(k, tuple(shape), jnp.uint8)
            return Q8Tensor(
                q=jax.lax.bitcast_convert_type(bits, jnp.int8), s=s
            )
        packed = jax.random.bits(k, (*lead, d_in // 2, d_out), jnp.uint8)
        return Q4Tensor(q=packed, s=s)

    new_leaves = []
    for (path, sds), k in zip(leaves, keys):
        name = path[-1].key if isinstance(path[-1], DictKey) else ""
        if name in _QUANT_KEYS:
            new_leaves.append(quant_leaf(sds.shape, k))
        elif name.endswith("norm"):
            new_leaves.append(jnp.ones(sds.shape, sds.dtype))
        else:
            new_leaves.append(
                (jax.random.normal(k, sds.shape, jnp.float32) * 0.02)
                .astype(sds.dtype)
            )
    return tree_unflatten(treedef, new_leaves)


def dense_view(w: Any, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Dense array for a possibly-quantized weight (pass-through for plain
    arrays) — the single dispatch point for matmul/einsum call sites."""
    return dequantize(w, dtype) if is_quantized(w) else w


def quantize_params(
    params: Dict[str, Any], mode: str, group_size: int = 0
) -> Dict[str, Any]:
    """Quantize a Llama/Mixtral parameter tree's linear weights.

    mode: "int8" | "int4" | "none". Stacked layouts ([L, in, out] and MoE
    [L, E, in, out]) quantize directly — groups run along the input axis.
    """
    if mode == "none":
        return params
    if mode == "int8":
        fn = lambda w: quantize_int8(w, group_size or 128)
    elif mode == "int4":
        fn = lambda w: quantize_int4(w, group_size or 64)
    else:
        raise ValueError(f"unknown quantization mode {mode!r}")
    out = dict(params)
    out["layers"] = {
        k: (fn(v) if k in _QUANT_KEYS else v)
        for k, v in params["layers"].items()
    }
    return out


# ---------------------------------------------------------------------------
# KV-cache quantization (per-vector absmax int8)
# ---------------------------------------------------------------------------


class QuantPool(NamedTuple):
    """Int8-quantized KV pool: per-(slot, head) absmax scaling.

    Halves KV HBM traffic and doubles KV capacity vs bf16 — the decode
    bottleneck at long context, where per-step KV reads dwarf the fixed
    weight reads. Each cached K/V vector [D] stores int8 codes plus one
    f32 scale (absmax/127, ~6% overhead at D=64), reconstructed as
    ``codes * scale`` at attention time. A pytree, so ``lax.scan`` over
    stacked layers, buffer donation, and device_put thread it like a
    plain array; XLA-gather attention dequantizes after the page-granular
    gather. The Pallas DECODE kernel also accepts it (int8 page DMA with
    in-kernel scale folding, ops/pallas/paged_attention.py) in interpret
    mode only: Mosaic rejects it on the chip (the [page_size, KV] scale
    tiles are narrower than the 128-lane tiling; tools/kernel_probe.py,
    CHANGES.md PR 21), so serving keeps the XLA path for kv_quant. The
    prefill kernel has no int8 variant.

    data:  [..., num_slots, KV, D] int8 codes
    scale: [..., num_slots, KV] f32 per-vector scales
    """

    data: jnp.ndarray
    scale: jnp.ndarray


def pool_num_slots(pool) -> int:
    """Slot count of a per-layer (or stacked) pool, quantized or not —
    the slot axis is -3 in both layouts."""
    return (pool.data if isinstance(pool, QuantPool) else pool).shape[-3]


def quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-vector absmax int8 quantization of new K/V tokens.

    x: [..., KV, D] -> (codes int8 same shape, scale f32 [..., KV]).
    Zero vectors get scale 0 and reconstruct exactly to zero.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = amax / 127.0
    q = jnp.where(
        scale[..., None] > 0.0,
        jnp.round(x.astype(jnp.float32) / jnp.maximum(scale, 1e-30)[..., None]),
        0.0,
    ).astype(jnp.int8)
    return q, scale


def dequantize_kv(codes: jnp.ndarray, scale: jnp.ndarray,
                  dtype=jnp.bfloat16) -> jnp.ndarray:
    """Reconstruct K/V vectors: codes [..., KV, D] * scale [..., KV]."""
    return (codes.astype(jnp.float32)
            * scale[..., None]).astype(dtype)
