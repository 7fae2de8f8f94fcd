"""Pallas flash attention for TPU.

Blockwise online-softmax attention (Flash Attention 2 schedule): the k/v
sequence axis is the innermost grid dimension, with the running max /
denominator / accumulator carried in VMEM scratch across grid steps (TPU
grids execute sequentially per core, so scratch persists). Softmax state is
f32 regardless of input dtype; the [Sq, Sk] score matrix never
materializes, so memory is O(Sq * D) instead of O(Sq * Sk).

One kernel for full causal, windowed causal and non-causal attention. Q, K
and V go to the MXU in the dtype they come in (bf16 operands, float32
sums); the probabilities are cast to V's dtype for the weighted sum, as the
XLA form does (ops/attention.py `prefill_attention`). The query heads that
share a key/value head are one grid step against that head's K and V
block, which is fetched once for all of them. A query block visits only the
key blocks it can see: the grid's innermost extent is the most key blocks
any query block sees, counted from the block's own first visible one, and
the steps left over re-use the block already in VMEM (no fetch, no work).
Query blocks wholly at or past `q_len` are skipped the same way and come
back as zeros.

Nothing is transposed on the way in or out: the arrays are read as
[B, S, heads * D] and a head is a slice of whole lane tiles, which is why a
head's size must be a multiple of 128 on the chip (`unsupported_reason`).

Measured on one v5e chip (PERF.md section 6, PR 33; bf16, batch 1, 32
query heads on 4 key/value heads of 128, TFLOP/s of the VISIBLE pairs, 4
flops a pair a dim), blocks of 512 queries x 1024 keys: 4096 rows with a
window of 2048 1.66 ms (62 TFLOP/s) where `prefill_attention`'s tiles in
XLA take 6.2 ms; 4096 rows, full causal, 1.78 ms (77) against 10.6 ms;
2048 rows 0.62 ms either way (55) against 3.8; 6144 rows 2.77 ms windowed
(62) and 3.59 full (86) against 9.2 and 21.8. Other blocks, at 4096 rows:
256 x 2048 reads the same within 3%, 512 x 512 19-39% slower, 128 x 512
17-32%. The heads of a group are a loop inside the kernel, not eight
copies of its body: written out they ran 1-5% faster, and Mosaic took 10 s
a kernel to compile them where it takes 1.3, for 4 MB of code a layer where
this is 1.1.

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

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
#: Most query rows (positions x heads of a group) one grid step holds: the
#: preferred block_q is cut to this many rows over the group's heads (8
#: heads on one key/value head keep 512 positions; 12 MB of VMEM).
GROUP_ROWS = 4096
LANES = 128
NEG_INF = float("-inf")


def auto_block(dim: int, preferred: int, align: int) -> int | None:
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
    bq = auto_block(sq, block_q or DEFAULT_BLOCK_Q, 8)
    if bq is None:
        return None, (
            f"Sq={sq} has no divisor aligned to the TPU sublane tile (8)"
            + (f" at or under block_q={block_q}" if block_q else ""))
    bk = auto_block(sk, block_k or DEFAULT_BLOCK_K, 128)
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


def _shape_reason(q_shape, k_shape, causal: bool, window: int) -> str | None:
    """What the kernel cannot do whatever the chip's tiles are."""
    _, sq, hq, _ = q_shape
    _, sk, hkv, _ = k_shape
    if hq % hkv:
        return f"Hq={hq} not a multiple of Hkv={hkv}"
    if window and not causal:
        return "a window without causal attention"
    if causal and sq > sk:
        return f"causal attention with Sq={sq} > Sk={sk}"
    return None


def unsupported_reason(q_shape, k_shape, *, causal: bool = True,
                       window: int = 0) -> str | None:
    """Why `flash_attention` cannot take q [B, Sq, Hq, D] with k/v
    [B, Sk, Hkv, D] at its default blocks on the chip, or None when it
    can. The dispatcher (ops/attention.py) asks this before it picks the
    kernel; it is the same derivation the kernel runs, so the two cannot
    drift."""
    reason = _shape_reason(q_shape, k_shape, causal, window)
    if reason is not None:
        return reason
    d = q_shape[3]
    if d % LANES:
        # a head is a lane slice of the [S, heads * D] row
        return f"head size {d} is not a whole number of lane tiles ({LANES})"
    return _block_reasons(q_shape[1], k_shape[1], None, None)[1]


def _visible_blocks(qi, *, block_q: int, block_k: int, n_k: int, off: int,
                    causal: bool, window: int, lo=jnp.maximum,
                    hi=jnp.minimum):
    """(first, last) key block a query block can see. Query i sees key j
    iff j <= i + off and, in a window, i + off - j < window. For a traced
    `qi`; with `lo=max, hi=min`, for a Python int."""
    q_start = qi * block_q
    last = n_k - 1
    if causal:
        last = hi((q_start + block_q - 1 + off) // block_k, last)
    first = 0
    if window:  # the first key the block's first row sees
        first = lo(q_start + off - window + 1, 0) // block_k
    return first, last


def _flash_kernel(qlen_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, window: int, group: int,
                  d: int, block_q: int, block_k: int, n_k: int, n_steps: int,
                  off: int):
    """One (batch row, key/value head, query block, key step) of the grid.
    q_ref/o_ref: [block_q, group * d], head g of the group in lanes
    [g*d, (g+1)*d); k_ref/v_ref: [block_k, d]; m/l: [group, block_q, 128]
    (every lane the same), acc: [group, block_q, d]. `off` = Sk - Sq: query
    row i stands at key position i + off."""
    bi, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    q_start = qi * block_q
    first, last = _visible_blocks(qi, block_q=block_q, block_k=block_k,
                                  n_k=n_k, off=off, causal=causal,
                                  window=window)
    k_start = (first + kj) * block_k
    live = jnp.logical_and(first + kj <= last, q_start < qlen_ref[bi])

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked: bool):
        k, v = k_ref[...], v_ref[...]
        if masked:
            back = (q_start + off - k_start) + (
                jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                - jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
            visible = back >= 0
            if window:
                visible = jnp.logical_and(visible, back < window)

        def one_head(g, _):
            q = q_ref[:, pl.ds(pl.multiple_of(g * d, d), d)]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            m_prev = m_ref[g, :, 0:1]  # [block_q, 1]
            l_prev = l_ref[g, :, 0:1]
            if masked:
                s = jnp.where(visible, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # a row with no visible key so far: exp(-inf - 0) = 0, not NaN
            m_at = jnp.where(m_new == NEG_INF, 0.0, m_new) if masked else m_new
            p = jnp.exp(s - m_at)
            alpha = jnp.exp(m_prev - m_at)
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])

        jax.lax.fori_loop(0, group, one_head, None)

    if causal:
        # A block wholly inside the visible band needs no mask: its last
        # key is at or before the first row's position and, in a window,
        # its first key is within the last row's reach.
        inside = k_start + block_k - 1 <= q_start + off
        if window:
            inside = jnp.logical_and(
                inside, q_start + block_q - 1 + off - k_start < window)
        pl.when(jnp.logical_and(live, inside))(lambda: update(False))
        pl.when(jnp.logical_and(live, jnp.logical_not(inside)))(
            lambda: update(True))
    else:
        pl.when(live)(lambda: update(False))

    @pl.when(kj == n_steps - 1)
    def _finish():
        def one_head(g, _):
            l = l_ref[g, :, 0:1]
            l = jnp.where(l == 0.0, 1.0, l)  # rows that saw no key: zeros
            o_ref[:, pl.ds(pl.multiple_of(g * d, d), d)] = (
                acc_ref[g] / l).astype(o_ref.dtype)

        jax.lax.fori_loop(0, group, one_head, None)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_len=None, block_q: int | None = None,
                    block_k: int | None = None, interpret: bool = False):
    """q: [B, Sq, Hq, D]; k/v: [B, Sk, Hkv, D] (GQA when Hq > Hkv).
    Returns [B, Sq, Hq, D]. Query row i stands at key position
    i + (Sk - Sq) and, when `causal`, sees the keys at or before it; with
    `window` > 0 only the last `window` of them. `q_len` ([B] int32, or
    None for all): query blocks wholly at or past it are not computed and
    come back as zeros. block_q/block_k are upper-bound preferences; the
    actual blocks are tile-aligned divisors of Sq/Sk derived by
    derive_blocks. Raises ValueError for shapes with no valid tiling
    (`unsupported_reason` says so beforehand)."""
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    reason = _shape_reason(q.shape, k.shape, causal, window)
    if reason is not None:
        raise ValueError(reason)
    group = hq // hkv
    block_q, block_k = derive_blocks(
        sq, sk, block_q or max(8, min(DEFAULT_BLOCK_Q, GROUP_ROWS // group)),
        block_k)
    assert not (sq % block_q or sk % block_k or block_q % 8 or block_k % 128)
    n_q, n_k, off = sq // block_q, sk // block_k, sk - sq
    where = dict(block_q=block_q, block_k=block_k, n_k=n_k, off=off,
                 causal=causal, window=window)
    n_steps = max(last - first + 1 for first, last in (
        _visible_blocks(qi, **where, lo=max, hi=min) for qi in range(n_q)))
    if q_len is None:
        q_len = jnp.full((b,), sq, jnp.int32)

    def q_block(bi, qi, qlen):
        """The query block fetched at step (bi, ., qi): past the prompt, the
        last block that holds any of it (already in VMEM: no fetch)."""
        return jnp.minimum(qi, jnp.maximum(qlen[bi] - 1, 0) // block_q)

    def q_index(bi, hi, qi, kj, qlen):
        return (bi, q_block(bi, qi, qlen), hi)

    def o_index(bi, hi, qi, kj, qlen):
        return (bi, qi, hi)

    def kv_index(bi, hi, qi, kj, qlen):
        at = q_block(bi, qi, qlen)
        first, last = _visible_blocks(at, **where)
        # past the block's last visible key block, and in a skipped query
        # block, stay where the last fetch was
        return (bi, jnp.where(qi > at, last, jnp.minimum(first + kj, last)),
                hi)

    kernel = functools.partial(
        _flash_kernel, scale=d ** -0.5, causal=causal, window=window,
        group=group, d=d, block_q=block_q, block_k=block_k, n_k=n_k,
        n_steps=n_steps, off=off)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, sq, hq * d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, n_q, n_steps),
            in_specs=[
                pl.BlockSpec((None, block_q, group * d), q_index),
                pl.BlockSpec((None, block_k, d), kv_index),
                pl.BlockSpec((None, block_k, d), kv_index),
            ],
            out_specs=pl.BlockSpec((None, block_q, group * d), o_index),
            scratch_shapes=[
                pltpu.VMEM((group, block_q, LANES), jnp.float32),  # max
                pltpu.VMEM((group, block_q, LANES), jnp.float32),  # denom
                pltpu.VMEM((group, block_q, d), jnp.float32),  # accumulator
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(q_len.astype(jnp.int32), q.reshape(b, sq, hq * d),
      k.reshape(b, sk, hkv * d), v.reshape(b, sk, hkv * d))
    return out.reshape(b, sq, hq, d)


# ---------------------------------------------------------------------------
# Causal BY BLOCKS of L positions (a block-diffusion model's prefill,
# `models/transformer.py` `BlockAttention`): key j is visible to query i iff
# j < L * (i // L + 1), every earlier block and the query's own, whole. With
# query blocks of whole L only the masks of the tiles on the diagonal differ
# from the causal kernel's, and a query block visits the same key blocks. A
# kernel of its own, at the file's end: the causal kernel's program names its
# body's lines, so nothing above may move.

def _blocks_tiles(q_shape, k_shape, blocks: int, block_q=None, block_k=None):
    """((block_q, block_k), None) as `flash_attention` derives them, else
    (None, reason); query blocks must be whole blocks of `blocks`."""
    group = q_shape[2] // k_shape[2]
    tiles, reason = _block_reasons(
        q_shape[1], k_shape[1],
        block_q or max(8, min(DEFAULT_BLOCK_Q, GROUP_ROWS // group)), block_k)
    if tiles is not None and (tiles[0] % blocks or q_shape[1] % blocks):
        return None, (f"query blocks of {tiles[0]} rows are not whole "
                      f"blocks of {blocks} positions")
    return tiles, reason


def blocks_unsupported_reason(q_shape, k_shape, blocks: int) -> str | None:
    """`unsupported_reason` for `block_causal_attention`."""
    if q_shape[1] != k_shape[1]:
        return f"Sq={q_shape[1]} != Sk={k_shape[1]}: not a call's own rows"
    return (unsupported_reason(q_shape, k_shape)
            or _blocks_tiles(q_shape, k_shape, blocks)[1])


def _block_causal_kernel(qlen_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                         acc_ref, *, scale: float, blocks: int, group: int,
                         d: int, block_q: int, block_k: int, n_k: int,
                         n_steps: int):
    """`_flash_kernel` (its refs, its scratch, its grid) for visibility by
    blocks: no window, query row i at key position i."""
    bi, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    q_start, k_start = qi * block_q, kj * block_k
    _, last = _visible_blocks(qi, block_q=block_q, block_k=block_k, n_k=n_k,
                              off=0, causal=True, window=0)
    live = jnp.logical_and(kj <= last, q_start < qlen_ref[bi])

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked: bool):
        k, v = k_ref[...], v_ref[...]
        if masked:
            at = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            key = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            visible = key < at - at % blocks + blocks

        def one_head(g, _):
            q = q_ref[:, pl.ds(pl.multiple_of(g * d, d), d)]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            m_prev, l_prev = m_ref[g, :, 0:1], l_ref[g, :, 0:1]
            if masked:
                s = jnp.where(visible, s, NEG_INF)
            # (key 0 is visible to every row, so every max is finite)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])

        jax.lax.fori_loop(0, group, one_head, None)

    # A key block that ends at or before the end of the FIRST row's block of
    # positions is visible to every row of the query block.
    inside = k_start + block_k <= q_start + blocks
    pl.when(jnp.logical_and(live, inside))(lambda: update(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(inside)))(
        lambda: update(True))

    @pl.when(kj == n_steps - 1)
    def _finish():
        def one_head(g, _):
            l = l_ref[g, :, 0:1]
            l = jnp.where(l == 0.0, 1.0, l)  # a skipped query block: zeros
            o_ref[:, pl.ds(pl.multiple_of(g * d, d), d)] = (
                acc_ref[g] / l).astype(o_ref.dtype)

        jax.lax.fori_loop(0, group, one_head, None)


@functools.partial(
    jax.jit, static_argnames=("blocks", "block_q", "block_k", "interpret"))
def block_causal_attention(q, k, v, *, blocks: int, q_len=None,
                           block_q: int | None = None,
                           block_k: int | None = None,
                           interpret: bool = False):
    """q [B, S, Hq, D], k/v [B, S, Hkv, D] -> [B, S, Hq, D]: a call over its
    own rows, key j visible to query i iff j < blocks * (i // blocks + 1).
    `q_len`, `block_q`, `block_k` and `interpret` as `flash_attention` takes
    them. Raises ValueError where `blocks_unsupported_reason` has one."""
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    reason = _shape_reason(q.shape, k.shape, True, 0)
    tiles, tiling = _blocks_tiles(q.shape, k.shape, blocks, block_q, block_k)
    if reason or sq != sk or tiles is None:
        raise ValueError(reason or tiling or f"Sq={sq} != Sk={sk}")
    group = hq // hkv
    block_q, block_k = tiles
    n_q, n_k = sq // block_q, sk // block_k
    where = dict(block_q=block_q, block_k=block_k, n_k=n_k, off=0,
                 causal=True, window=0)
    n_steps = max(_visible_blocks(qi, **where, lo=max, hi=min)[1] + 1
                  for qi in range(n_q))
    if q_len is None:
        q_len = jnp.full((b,), sq, jnp.int32)

    def q_block(bi, qi, qlen):  # past the prompt: the last block of it
        return jnp.minimum(qi, jnp.maximum(qlen[bi] - 1, 0) // block_q)

    def kv_index(bi, hi, qi, kj, qlen):
        at = q_block(bi, qi, qlen)
        last = _visible_blocks(at, **where)[1]
        return (bi, jnp.where(qi > at, last, jnp.minimum(kj, last)), hi)

    kernel = functools.partial(
        _block_causal_kernel, scale=d ** -0.5, blocks=blocks, group=group,
        d=d, block_q=block_q, block_k=block_k, n_k=n_k, n_steps=n_steps)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, sq, hq * d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, n_q, n_steps),
            in_specs=[
                pl.BlockSpec((None, block_q, group * d),
                             lambda bi, hi, qi, kj, qlen: (
                                 bi, q_block(bi, qi, qlen), hi)),
                pl.BlockSpec((None, block_k, d), kv_index),
                pl.BlockSpec((None, block_k, d), kv_index),
            ],
            out_specs=pl.BlockSpec((None, block_q, group * d),
                                   lambda bi, hi, qi, kj, qlen: (bi, qi, hi)),
            scratch_shapes=[
                pltpu.VMEM((group, block_q, LANES), jnp.float32),  # max
                pltpu.VMEM((group, block_q, LANES), jnp.float32),  # denom
                pltpu.VMEM((group, block_q, d), jnp.float32),  # accumulator
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(q_len.astype(jnp.int32), q.reshape(b, sq, hq * d),
      k.reshape(b, sk, hkv * d), v.reshape(b, sk, hkv * d))
    return out.reshape(b, sq, hq, d)
