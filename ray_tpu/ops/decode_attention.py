"""Pallas decode attention: single-token queries against a KV cache.

The serving hot loop is q=[B, 1, H, D] attending over a fixed [B, S, KV, D]
cache with per-sequence valid lengths — shapes the prefill flash kernel
rejects (Sq=1 violates its q-block tiling), which previously forced the
O(Sq*Sk)-materializing XLA fallback every decode step (the r04 bench
warning). This kernel blocks only the cache axis: one grid program per
(batch, kv-head) pair streams the cache in VMEM-sized chunks, carrying
f32 online-softmax state in scratch, with the per-sequence length applied
as a column mask. GQA folds the q-head group for a kv head into the
sublane axis of a single [rep, D] tile.

Reference role: vLLM's paged-attention decode kernel (the engine seat
python/ray/llm delegates; no TPU equivalent exists in the reference).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
DEFAULT_BLOCK_K = 512


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, block_k: int, n_k_blocks: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    k_start = ki * block_k
    # lengths live whole-array in SMEM (scalars can't tile into VMEM blocks)
    length = len_ref[pl.program_id(0)]

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [rep, D]
        k = k_ref[0].astype(jnp.float32)  # [block_k, D]
        v = v_ref[0].astype(jnp.float32)  # [block_k, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [rep, block_k]
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        l = l_ref[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def unsupported_reason(q_shape, cache_shape,
                       block_k: int = DEFAULT_BLOCK_K) -> str | None:
    """Why `decode_attention_pallas` cannot take q [B, H, D] against a
    [B, S, KV, D] cache, or None when it can. The kernel raises exactly
    this; the dispatcher asks it first."""
    _, hq, _ = q_shape
    _, sk, hkv, _ = cache_shape
    if hq % hkv:
        return f"Hq={hq} not a multiple of Hkv={hkv}"
    block_k = min(block_k, sk)
    if sk % block_k or block_k % 128:
        return (f"cache length {sk} not divisible by lane-aligned block "
                f"{block_k}")
    return None


@functools.partial(jax.jit,
                   static_argnames=("block_k", "interpret"))
def decode_attention_pallas(q, k_cache, v_cache, lengths, *,
                            block_k: int = DEFAULT_BLOCK_K,
                            interpret: bool = False):
    """q: [B, H, D] (one new token per sequence); k/v_cache: [B, S, KV, D];
    lengths: [B] int32 — rows [0, lengths[b]) of sequence b's cache are
    valid (INCLUDING the just-written current token). Returns [B, H, D]."""
    b, hq, d = q.shape
    _, sk, hkv, _ = k_cache.shape
    reason = unsupported_reason(q.shape, k_cache.shape, block_k)
    if reason is not None:
        raise ValueError(reason)
    rep = hq // hkv
    block_k = min(block_k, sk)
    scale = d ** -0.5
    n_k = sk // block_k
    # Pad the per-kv-head q group up to the 8-row sublane tile: padded rows
    # are zeros (scores 0 -> uniform softmax -> finite garbage, sliced off).
    rep_pad = max(rep, 8)

    # [B*KV, rep_pad, D] q tiles; [B*KV, S, D] cache views.
    qt = q.reshape(b, hkv, rep, d).reshape(b * hkv, rep, d)
    if rep_pad != rep:
        qt = jnp.pad(qt, ((0, 0), (0, rep_pad - rep), (0, 0)))
    kt = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vt = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    lens = jnp.broadcast_to(
        lengths.astype(jnp.int32)[:, None], (b, hkv)).reshape(b * hkv)

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, n_k_blocks=n_k)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b * hkv, rep_pad, d), q.dtype),
        grid=(b * hkv, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths, whole array
            pl.BlockSpec((1, rep_pad, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, rep_pad, d), lambda bh, ki: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep_pad, 128), jnp.float32),  # running max
            pltpu.VMEM((rep_pad, 128), jnp.float32),  # running denom
            pltpu.VMEM((rep_pad, d), jnp.float32),    # accumulator
        ],
        interpret=interpret,
    )(lens, qt, kt, vt)
    if rep_pad == rep:
        return out.reshape(b, hq, d)
    return out[:, :rep].reshape(b, hq, d)


def _xla_decode_attention(q, k_cache, v_cache, lengths):
    """Reference path (any backend): masked dense attention over the cache."""
    b, hq, d = q.shape
    _, sk, hkv, _ = k_cache.shape
    if hkv < hq:
        repn = hq // hkv
        k_cache = jnp.repeat(k_cache, repn, axis=2)
        v_cache = jnp.repeat(v_cache, repn, axis=2)
    scores = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) / (d ** 0.5)
    mask = jnp.arange(sk)[None, None, :] < lengths[:, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", probs, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


#: A bounded decode step walks one of this many static prefixes of the
#: cache: quarters of `max_seq`, the whole cache the last of them.
KV_PREFIXES = 4


def kv_prefixes(max_seq: int) -> tuple[int, ...]:
    """The row counts a bounded step can stop at, ascending; the last is
    `max_seq`."""
    width = -(-max_seq // KV_PREFIXES)
    return tuple(range(width, max_seq, width)) + (max_seq,)


def kv_prefix_rows(kv_bound: int, max_seq: int) -> int:
    """The rows a step with this bound walks: the shortest prefix that
    holds `kv_bound` rows (host integers; `over_kv_prefix` picks the same
    one inside the program)."""
    return next(t for t in kv_prefixes(max_seq) if t >= min(kv_bound, max_seq))


def over_kv_prefix(attend, leaves, kv_bound):
    """`attend(*leaves)` over the shortest of `kv_prefixes` that holds
    `kv_bound` rows of the cache leaves `[B, max_seq, ...]`. `kv_bound` is a
    traced int32 scalar: no row at or beyond it is visible to a sequence
    whose output is used. The rows left out are rows whose softmax weight
    `attend`'s own mask makes exactly zero, so the result is the whole
    cache's. The prefix is static in each branch of one `lax.switch`, which
    takes the leaves as operands: a branch reads a slice of the cache where
    it lies, and the step has no loop in it."""
    ends = kv_prefixes(leaves[0].shape[1])
    index = jnp.clip((kv_bound - 1) // ends[0], 0, len(ends) - 1)
    return jax.lax.switch(
        index,
        [lambda *ls, t=t: attend(*(leaf[:, :t] for leaf in ls)) for t in ends],
        *leaves)


@jax.jit
def _xla_decode_walk(q, k_cache, v_cache, lengths, kv_bound):
    """`_xla_decode_attention` over the prefix `kv_bound` picks, taking the
    cache leaves whole: a row wider than q's head
    (`TransformerConfig.cache_row`) is cut with the prefix, inside the
    branch. Jitted, so that the layers of a model share one trace and one
    function of the lowered module. Two things differ from the whole walk's
    text, neither in what is computed. The scores' product is written out
    as what it is, one query row a head times the keys, multiplied and
    summed in f32: outside a `conditional` the TPU's compiler makes
    exactly that of the einsum (the `multiply_reduce_fusion` of a chunk's
    trace) and reads the cache where it lies, inside one it keeps the
    einsum a convolution and feeds it a transposed copy of the prefix
    (PERF.md section 6, PR 29). And what no branch needs its own copy of,
    the query in f32 and the mask, is made once outside them."""
    _, hq, d = q.shape
    q32 = q.astype(jnp.float32)
    visible = jnp.arange(k_cache.shape[1])[None, :] < lengths[:, None]

    def attend(k, v):
        k, v = k[..., :d], v[..., :d]
        if k.shape[2] < hq:
            k = jnp.repeat(k, hq // k.shape[2], axis=2)
            v = jnp.repeat(v, hq // v.shape[2], axis=2)
        scores = jnp.swapaxes(
            jnp.sum(q32[:, None] * k.astype(jnp.float32), axis=-1), 1, 2)
        scores = jnp.where(visible[:, None, :k.shape[1]],
                           scores / (d ** 0.5), NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bht,bthd->bhd", probs, v.astype(jnp.float32))
        return out.astype(q.dtype)

    return over_kv_prefix(attend, (k_cache, v_cache), kv_bound)


#: Cache bytes above which the Pallas kernel dispatches by default. At
#: serving-typical sizes (B=8, KV=16, D=64, S=1024: ~2x16MB bf16) the
#: fused XLA einsum was the faster of the two when last compared (1.44 vs
#: 2.83 ms per 8-layer decode step; taken over a shared remote link, not
#: measured on the chip since): per-layer pallas_call launch overhead
#: dominates when the per-head score row is only [1, S]. The kernel's
#: streaming VMEM schedule pays off once the per-call cache traffic is
#: large enough to amortize launches (long context / big batch).
#: RT_DECODE_KERNEL=pallas|xla overrides.
PALLAS_MIN_CACHE_BYTES = 256 * 1024 * 1024


def choose_impl(q_shape, cache_shape, cache_itemsize: int, *,
                backend: str, force: str = "") -> tuple[str, str]:
    """("pallas" | "xla", why) for one decode-attention call, from what can
    be observed before it runs: the forced choice (RT_DECODE_KERNEL), the
    backend, the cache size and whether the kernel can tile the shape."""
    if force == "xla":
        return "xla", "RT_DECODE_KERNEL=xla"
    if force == "pallas":
        return "pallas", "RT_DECODE_KERNEL=pallas"
    if force:
        raise ValueError(
            f"RT_DECODE_KERNEL={force!r}: expected 'pallas', 'xla' or ''")
    if backend != "tpu":
        return "xla", f"backend is {backend}"
    b, sk, hkv, d = cache_shape
    cache_bytes = 2 * b * sk * hkv * d * cache_itemsize
    if cache_bytes < PALLAS_MIN_CACHE_BYTES:
        return "xla", (f"k+v cache of {cache_bytes} bytes is under "
                       f"{PALLAS_MIN_CACHE_BYTES}")
    reason = unsupported_reason(q_shape, cache_shape)
    if reason is not None:
        return "xla", reason
    return "pallas", f"k+v cache of {cache_bytes} bytes"


def decode_attention(q, k_cache, v_cache, lengths, *, kv_bound=None,
                     interpret: bool = False):
    """One new token a sequence against its cache rows `[0, lengths[b])`.
    q: [B, H, D]; caches [B, S, KV, D]; lengths [B] -> [B, H, D].

    `choose_impl` picks the implementation up front and the choice is
    stated once at INFO; nothing is caught, so a forced kernel on a shape
    it rejects, or a kernel that fails to compile, raises. Every serving
    configuration measured so far takes the fused XLA path (the Pallas
    streaming kernel only above `PALLAS_MIN_CACHE_BYTES` of cache, or when
    forced). On the XLA path a `kv_bound` (`over_kv_prefix`: the longest
    live sequence's rows, from whoever knows which sequences are live)
    stops the walk at a static prefix of the cache instead of S, and the
    caches may then come with rows wider than D (zeros beyond it), which
    are cut with the prefix; `lengths` still masks each sequence inside
    it. Without one the whole cache is walked, by the program this always
    built. The Pallas kernel takes no bound: it skips the arithmetic of
    blocks beyond a sequence's length itself. `interpret=True` runs the
    kernel in the Pallas interpreter on any backend (tests)."""
    from ray_tpu._private.rtconfig import CONFIG
    from ray_tpu.ops.attention import _state_once

    d = q.shape[-1]
    if interpret:
        impl, why = "pallas", "interpret mode"
    else:
        impl, why = choose_impl(
            q.shape, k_cache.shape[:-1] + (d,), k_cache.dtype.itemsize,
            backend=jax.default_backend(),
            force=str(CONFIG.decode_kernel).lower())
    _state_once(f"decode attention: {impl} ({why}; q {tuple(q.shape)}, "
                f"cache {tuple(k_cache.shape)} {k_cache.dtype})")
    # One name for both paths in a device trace (operation metadata only).
    with jax.named_scope("decode_attention"):
        if impl == "pallas":
            if k_cache.shape[-1] > d:
                k_cache, v_cache = k_cache[..., :d], v_cache[..., :d]
            return decode_attention_pallas(
                q, k_cache, v_cache, lengths, interpret=interpret)
        if kv_bound is None:
            return _xla_decode_attention(q, k_cache, v_cache, lengths)
        return _xla_decode_walk(q, k_cache, v_cache, lengths, kv_bound)
