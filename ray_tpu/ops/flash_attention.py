"""Pallas flash attention for TPU.

Blockwise online-softmax attention (Flash Attention 2 schedule): the k/v
sequence axis is the innermost grid dimension, with the running max /
denominator / accumulator carried in VMEM scratch across grid steps (TPU
grids execute sequentially per core, so scratch persists). Softmax state is
f32 regardless of input dtype; the [Sq, Sk] score matrix never
materializes, so memory is O(Sq * D) instead of O(Sq * Sk).

The reference framework ships no attention kernels (it delegates to
torch/vLLM); this is the TPU-native equivalent of that delegated surface.
Interpret mode makes the same kernel testable on the CPU mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tuned on v5e (4x2048x8x128 bf16 causal: 128/128 -> 13 TFLOP/s useful,
# 512/1024 -> ~72 TFLOP/s): bigger k blocks amortize the per-step softmax
# state rescale; q=512 keeps q+k+v+acc well inside VMEM.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
NEG_INF = float("-inf")


def _auto_block(dim: int, preferred: int, align: int) -> int | None:
    """Largest divisor of `dim` that is a multiple of `align` (TPU sublane/
    lane tiling) and <= `preferred`. None when no aligned divisor exists
    (the dispatcher then takes the XLA path). Auto-deriving from the
    input shape keeps the tuned defaults for big sequences while accepting
    any lane-alignable Sq/Sk — e.g. Sq=Sk=640 picks 320/640, not a
    hard-coded 512/1024 that 640 doesn't divide."""
    if dim % align:
        return None
    best = None
    for cand in range(align, min(preferred, dim) + 1, align):
        if dim % cand == 0:
            best = cand
    return best


def _block_reasons(sq: int, sk: int, block_q: int | None,
                   block_k: int | None):
    """((block_q, block_k), None) for a [Sq, Sk] problem the kernel can
    tile, else (None, reason)."""
    bq = _auto_block(sq, block_q or DEFAULT_BLOCK_Q, 8)
    if bq is None:
        return None, (
            f"Sq={sq} has no divisor aligned to the TPU sublane tile (8)"
            + (f" at or under block_q={block_q}" if block_q else ""))
    bk = _auto_block(sk, block_k or DEFAULT_BLOCK_K, 128)
    if bk is None:
        # block_k spans the LANE axis of the [block_q, block_k] score
        # tile, so it needs 128-alignment (block_q only needs sublane 8).
        return None, (
            f"Sk={sk} has no divisor aligned to the TPU lane tile (128)"
            + (f" at or under block_k={block_k}" if block_k else ""))
    return (bq, bk), None


def derive_blocks(sq: int, sk: int, block_q: int | None = None,
                  block_k: int | None = None) -> tuple[int, int]:
    """Resolve the (block_q, block_k) pair for a [Sq, Sk] problem, CLAMPED
    to valid TPU tiles — block_q on the sublane grid (8), block_k on the
    lane grid (128). Explicit blocks are treated as preferences (upper
    bounds) and re-clamped the same way, so a caller-supplied 1024 against
    a short sequence can never squeeze past the divisibility check as a
    tile-violating remnant (the r05 bench regression: a raw min() clamp
    produced blocks like 8/8 and the opaque "violate TPU tiling" reason).
    Raises ValueError with the reason when no valid tile exists."""
    blocks, reason = _block_reasons(sq, sk, block_q, block_k)
    if blocks is None:
        raise ValueError(reason)
    return blocks


def unsupported_reason(q_shape, k_shape) -> str | None:
    """Why `flash_attention` cannot take q [B, Sq, Hq, D] with k/v
    [B, Sk, Hkv, D] at its default blocks, or None when it can. The
    dispatcher (ops/attention.py) asks this before it picks the kernel; it
    is the same derivation the kernel runs, so the two cannot drift."""
    _, sq, hq, _ = q_shape
    _, sk, hkv, _ = k_shape
    if hq % hkv:
        return f"Hq={hq} not a multiple of Hkv={hkv}"
    return _block_reasons(sq, sk, None, None)[1]


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  n_k_blocks: int, diag_offset: int):
    """diag_offset = Sk - Sq: query row i attends to keys <= i + offset
    (matches _xla_attention's tril(k=sk-sq) alignment)."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [block_q, D]
        k = k_ref[0].astype(jnp.float32)  # [block_k, D]
        v = v_ref[0].astype(jnp.float32)  # [block_k, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [block_q, block_k]
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows + diag_offset, s, NEG_INF)
        m_prev = m_ref[:, 0:1]  # [block_q, 1]
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # exp(-inf)=0 handles fully-masked cols
        alpha = jnp.exp(m_prev - m_new)  # [block_q, 1]
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # Skip blocks entirely above the (offset) diagonal.
        pl.when(k_start <= q_start + diag_offset + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        l = l_ref[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool = False):
    """q: [B, Sq, Hq, D]; k/v: [B, Sk, Hkv, D] (GQA when Hq > Hkv).
    Returns [B, Sq, Hq, D]. block_q/block_k are upper-bound preferences;
    the actual blocks are tile-aligned divisors of Sq/Sk derived by
    derive_blocks (defaults: the tuned 512/1024). Raises ValueError for
    shapes with no valid tiling (`unsupported_reason` says so beforehand)."""
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if hq % hkv != 0:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    block_q, block_k = derive_blocks(sq, sk, block_q, block_k)
    assert not (sq % block_q or sk % block_k or block_q % 8 or block_k % 128)
    rep = hq // hkv
    scale = d ** -0.5
    n_q = sq // block_q
    n_k = sk // block_k

    # [B, H, S, D] layout for clean blocking.
    qt = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k_blocks=n_k, diag_offset=sk - sq)

    def q_index(bi, hi, qi, ki):
        return (bi * hq + hi, qi, 0)

    def kv_index(bi, hi, qi, ki):
        return (bi * hkv + hi // rep, ki, 0)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        grid=(b, hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom
            pltpu.VMEM((block_q, d), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
