"""Pallas TPU kernels for the non-attention hot ops: RMSNorm, RoPE, and
group-dequant matmul (int8 / packed-int4).

These complete the native-kernel tier SURVEY.md §2.3 commits to ("Pallas
kernels: paged/ragged attention, RMSNorm, RoPE application, dequant-matmul
(int8/int4)" — the TPU equivalents of the llama.cpp C++ kernels the
reference planned to reach over FFI, design.md:7 [spec]). They are
**opt-in** (`DIS_TPU_PALLAS_FUSED=1`): XLA already fuses RMSNorm / RoPE /
dequant into neighbouring ops, so the honest default is the fused XLA
path; these kernels exist for (a) geometries where the measured number
says otherwise — `tools/kernel_probe.py` compares both on the real chip —
and (b) single-device quantized decode, where a fusion miss in XLA's
dequant (materializing the dense tile in HBM) costs 2-4x the weight
bytes. All three are single-device kernels: GSPMD cannot partition an
opaque `pallas_call`, so under a tensor mesh callers must keep the XLA
path (the paged-attention kernels solve this with an explicit shard_map
wrap; these ops are cheap enough that the wrap has no payoff).

Every kernel keeps Mosaic's tiling rules in mind the same way
paged_attention.py does: last dim a multiple of 128 where it matters,
no sub-128 lane slicing (the RoPE kernel takes the two head-dim halves
as separate refs instead of slicing 32-lane windows), leading-dim-only
reshapes inside kernel bodies.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def fused_mode() -> str | None:
    """Trace-time switch for the opt-in fused kernels.

    DIS_TPU_PALLAS_FUSED=1        -> "compiled"; needs a SINGLE-device TPU
                                      backend (GSPMD cannot partition an
                                      opaque pallas_call) and raises
                                      anywhere else — asking for the
                                      kernels and silently serving the
                                      XLA path would hide the device
    DIS_TPU_PALLAS_FUSED=interpret -> "interpret" on any backend (tests:
                                      exercises the exact dispatch path
                                      off-TPU)
    unset/0                        -> None (XLA fused path)
    """
    v = os.environ.get("DIS_TPU_PALLAS_FUSED", "0")
    if v == "interpret":
        return "interpret"
    if v == "1":
        if jax.default_backend() != "tpu" or jax.device_count() != 1:
            raise RuntimeError(
                "DIS_TPU_PALLAS_FUSED=1 needs a single-device TPU backend; "
                f"found {jax.device_count()} {jax.default_backend()} "
                "device(s). Unset it to serve on the XLA fused path."
            )
        return "compiled"
    return None


# ----------------------------------------------------------------------
# RMSNorm
# ----------------------------------------------------------------------


def _rms_norm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # [BM, H]
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * lax.rsqrt(ms + eps) * w_ref[...].astype(jnp.float32)
                  ).astype(o_ref.dtype)


def _row_block(m: int, cap: int = 256) -> int:
    """Largest divisor of ``m`` that is <= cap and a multiple of 8 (or
    ``m`` itself when m < 8 — Mosaic pads sublanes)."""
    if m <= 8:
        return m
    best = 8 if m % 8 == 0 else 0
    b = 8
    while b < cap:
        b += 8
        if m % b == 0:
            best = b
    return best or m


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def rms_norm_pallas(
    x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6,
    interpret: bool = False,
) -> jnp.ndarray:
    """RMSNorm over the last axis. x: [..., H]; weight: [H]."""
    orig_shape = x.shape
    H = orig_shape[-1]
    x2 = x.reshape(-1, H)
    M = x2.shape[0]
    BM = _row_block(M)
    out = pl.pallas_call(
        functools.partial(_rms_norm_kernel, eps=eps),
        grid=(M // BM,),
        in_specs=[
            pl.BlockSpec((BM, H), lambda m: (m, 0)),
            pl.BlockSpec((1, H), lambda m: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BM, H), lambda m: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((M, H), x.dtype),
        interpret=interpret,
    )(x2, weight.reshape(1, H))
    return out.reshape(orig_shape)


# ----------------------------------------------------------------------
# RoPE (half-split convention, matching ops/rotary.apply_rope)
# ----------------------------------------------------------------------


def _rope_kernel(pos_ref, x1_ref, x2_ref, inv_ref, o1_ref, o2_ref):
    # rows = flattened (seq, head); each row rotates by its position
    pos = pos_ref[...].astype(jnp.float32)  # [BM, 1]
    inv = inv_ref[...].astype(jnp.float32)  # [1, half]
    ang = pos * inv  # [BM, half]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1 = x1_ref[...].astype(jnp.float32)
    x2 = x2_ref[...].astype(jnp.float32)
    o1_ref[...] = (x1 * c - x2 * s).astype(o1_ref.dtype)
    o2_ref[...] = (x2 * c + x1 * s).astype(o2_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def apply_rope_pallas(
    x: jnp.ndarray, positions: jnp.ndarray, inv_freq: jnp.ndarray,
    interpret: bool = False,
) -> jnp.ndarray:
    """Half-split RoPE: x [..., seq, heads, D], positions [..., seq],
    inv_freq [D/2]. Sin/cos are computed in VMEM per row block — nothing
    position-dependent is materialized in HBM. The two head-dim halves
    travel as separate refs (Mosaic rejects sub-128 lane slicing for the
    D=64 models; two D/2-lane refs sidestep it the same way the
    attention kernels' block-diagonal trick does)."""
    *lead, T, nh, D = x.shape
    half = D // 2
    pos = jnp.broadcast_to(
        positions[..., None], (*lead, T, nh)
    ).reshape(-1, 1)
    x2d = x.reshape(-1, D)
    M = x2d.shape[0]
    BM = _row_block(M)
    o1, o2 = pl.pallas_call(
        _rope_kernel,
        grid=(M // BM,),
        in_specs=[
            pl.BlockSpec((BM, 1), lambda m: (m, 0)),
            pl.BlockSpec((BM, half), lambda m: (m, 0)),
            pl.BlockSpec((BM, half), lambda m: (m, 0)),
            pl.BlockSpec((1, half), lambda m: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BM, half), lambda m: (m, 0)),
            pl.BlockSpec((BM, half), lambda m: (m, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, half), x.dtype),
            jax.ShapeDtypeStruct((M, half), x.dtype),
        ],
        interpret=interpret,
    )(pos.astype(jnp.int32), x2d[:, :half], x2d[:, half:],
      inv_freq.reshape(1, half))
    return jnp.concatenate([o1, o2], axis=-1).reshape(*lead, T, nh, D)


# ----------------------------------------------------------------------
# Group-dequant matmul: x @ dequant(Wq)
# ----------------------------------------------------------------------


def _q8_matmul_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qt = q_ref[...]  # [BK, BN] int8
    st = s_ref[...].astype(jnp.float32)  # [BK//G, BN]
    groups, BN = st.shape
    BK = qt.shape[0]
    deq = (
        qt.astype(jnp.float32).reshape(groups, BK // groups, BN)
        * st[:, None, :]
    ).reshape(BK, BN)
    acc_ref[...] += lax.dot(
        x_ref[...].astype(jnp.bfloat16), deq.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _q4_matmul_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    packed = q_ref[...]  # [BK//2, BN] uint8: low nibble=even k, high=odd
    st = s_ref[...].astype(jnp.float32)  # [BK//G, BN]
    groups, BN = st.shape
    halfk = packed.shape[0]
    # nibble arithmetic in int32: Mosaic has no 8-bit vector shift
    # ("failed to legalize operation 'arith.shrui'" on vector<..xi8>)
    wide = packed.astype(jnp.int32)
    low = wide & 0xF
    high = wide >> 4
    low = jnp.where(low > 7, low - 16, low)
    high = jnp.where(high > 7, high - 16, high)
    # interleave to k order: row 2i = low_i, 2i+1 = high_i (quant.py pack)
    q = jnp.stack([low, high], axis=1).reshape(halfk * 2, BN)
    BK = halfk * 2
    deq = (
        q.astype(jnp.float32).reshape(groups, BK // groups, BN)
        * st[:, None, :]
    ).reshape(BK, BN)
    acc_ref[...] += lax.dot(
        x_ref[...].astype(jnp.bfloat16), deq.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _tile(n: int, cap: int, mult: int) -> int:
    """Largest divisor of n that is <= cap and a multiple of ``mult``;
    0 when none exists (caller falls back to XLA)."""
    best = 0
    b = mult
    while b <= min(n, cap):
        if n % b == 0:
            best = b
        b += mult
    return best


def quant_matmul_supported(M: int, K: int, N: int, group: int,
                           packed: bool) -> bool:
    """Static dispatch check: every dim must admit an aligned tiling."""
    if _row_block(M) % 8 and M > 8:
        return False
    kmult = max(group, 256 if packed else 128)
    return (_tile(K, 2048, kmult) > 0 and _tile(N, 512, 128) > 0
            and K % group == 0)


@functools.partial(
    jax.jit, static_argnames=("group", "packed", "interpret")
)
def quant_matmul_pallas(
    x: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray, group: int,
    packed: bool = False, interpret: bool = False,
) -> jnp.ndarray:
    """x [M, K] @ dequant(q, s) -> [M, N] in x.dtype.

    q: [K, N] int8, or [K/2, N] uint8 when ``packed`` (two int4 along K,
    quantize_int4's layout). s: [K/group, N] scales. Dequant happens in
    VMEM after the int tile's DMA — HBM traffic stays at the quantized
    byte count even if XLA would have failed to fuse (its failure mode
    materializes dense bf16 tiles, 2-4x the bytes of the int read)."""
    M, K = x.shape
    N = s.shape[-1]
    BM = _row_block(M)
    BK = _tile(K, 2048, max(group, 256 if packed else 128))
    BN = _tile(N, 512, 128)
    n_k = K // BK
    kern = _q4_matmul_kernel if packed else _q8_matmul_kernel
    qspec = (
        pl.BlockSpec((BK // 2, BN), lambda m, n, k: (k, n)) if packed
        else pl.BlockSpec((BK, BN), lambda m, n, k: (k, n))
    )
    return pl.pallas_call(
        functools.partial(kern, n_k=n_k),
        grid=(M // BM, N // BN, n_k),
        in_specs=[
            pl.BlockSpec((BM, BK), lambda m, n, k: (m, k)),
            qspec,
            pl.BlockSpec((BK // group, BN), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)],
        interpret=interpret,
    )(x, q, s)
