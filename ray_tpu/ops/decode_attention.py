"""Decode attention: one new token a sequence against its KV cache.

The serving hot loop is q [B, H, D] attending over a fixed [B, S, KV, D]
cache, each sequence up to its own length. One implementation, in plain
JAX, in two forms. `_xla_decode_attention` walks all S rows under a
per-sequence mask: the program of every caller that knows no bound
(`LLMEngine.generate`, the pipeline's stages, the tests' references).
`_xla_decode_walk` stops at a static prefix of the cache that holds the
longest LIVE sequence (`over_kv_prefix`, from the `kv_bound` the serving
scheduler hands down) and takes the cache in the engine's own layout, rows
as wide as the device's tiles. How the two are written decides what the
TPU's compiler makes of them (PERF.md section 6, PR 29): change either
only with a chip run beside it.

Reference role: vLLM's paged-attention decode kernel (the engine seat
python/ray/llm delegates; no TPU equivalent exists in the reference).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = float("-inf")


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
    `kv_bound` rows of the cache leaves `[B, rows, ...]`: `max_seq` rows
    of a full layer, or the ring of a window layer, whose last prefix, the
    whole ring, holds any bound beyond it. `kv_bound` is a traced int32
    scalar: no row at or beyond it is visible to a sequence whose output is
    used. The rows left out are rows whose softmax weight `attend`'s own
    mask makes exactly zero, so the result is the whole leaf's. The prefix
    is static in each branch of one `lax.switch`, which takes the leaves as
    operands: a branch reads a slice of the cache where it lies, and the
    step has no loop in it."""
    ends = kv_prefixes(leaves[0].shape[1])
    index = jnp.clip((kv_bound - 1) // ends[0], 0, len(ends) - 1)
    return jax.lax.switch(
        index,
        [lambda *ls, t=t: attend(*(leaf[:, :t] for leaf in ls)) for t in ends],
        *leaves)


def _grouped_attention(q, k, v, visible):
    """Query heads that share key/value heads: q [B, H, D]; k, v
    [B, T, KV, D] as they lie in the cache; visible [B, T]. The rows of all
    KV heads are ONE matrix `[T x KV, D]` a slot (merging two adjacent axes
    of a leaf moves nothing), every query head is multiplied against all of
    it, and the mask keeps, for each query head, the rows of its own
    key/value head: KV times the products a grouped form would make, on a
    unit that has them to spare in a decode step, against a copy of the
    prefix into a heads-major layout in every step, which is what the
    v5e's compiler makes of `bngd,btnd->bngt` inside a `conditional`
    (PERF.md section 6, PR 32). K and V are read once, where they lie; no
    `jnp.repeat`. bf16 operands, f32 sums."""
    b, hq, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    k, v = k.reshape(b, t * kv, d), v.reshape(b, t * kv, d)
    scores = jnp.einsum("bhd,bsd->bhs", q, k,
                        preferred_element_type=jnp.float32) / (d ** 0.5)
    # row s of the merged matrix is position s // KV of key/value head
    # s % KV; query head h reads key/value head h // (H / KV)
    own = (jnp.arange(t * kv)[None, :] % kv
           == jnp.arange(hq)[:, None] // (hq // kv))
    seen = jnp.repeat(visible, kv, axis=1)[:, None, :] & own[None]
    probs = jax.nn.softmax(jnp.where(seen, scores, NEG_INF), axis=-1)
    out = jnp.einsum("bhs,bsd->bhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


@jax.jit
def grouped_walk(q, k_cache, v_cache, lengths, kv_bound):
    """The bounded walk of heads that share key/value heads (fewer of those
    than query heads). Jitted, so that the layers of a kind share one trace
    and one function of the lowered module (a model with window layers has
    two: its full leaves and its rings). No loop."""
    d = q.shape[-1]
    visible = jnp.arange(k_cache.shape[1])[None, :] < lengths[:, None]
    return over_kv_prefix(
        lambda k, v: _grouped_attention(q, k[..., :d], v[..., :d],
                                        visible[:, :k.shape[1]]),
        (k_cache, v_cache), kv_bound)


@jax.jit
def _xla_decode_walk(q, k_cache, v_cache, lengths, kv_bound):
    """`_xla_decode_attention` over the prefix `kv_bound` picks, taking the
    cache leaves whole: a row wider than q's head
    (`TransformerConfig.cache_row`) is cut with the prefix, inside the
    branch. Jitted, so that the layers of a model that share a leaf's shape
    share one trace and one function of the lowered module (a model with
    window layers has two: its full leaves and its rings). Two things
    differ from the whole walk's text, neither in what is computed. The
    scores' product of a head with keys of its own is written out as what
    it is, one query row a head times the keys, multiplied and summed in
    f32: outside a `conditional` the TPU's compiler makes exactly that of
    the einsum (the `multiply_reduce_fusion` of a chunk's trace) and reads
    the cache where it lies, inside one it keeps the einsum a convolution
    and feeds it a transposed copy of the prefix (PERF.md section 6, PR
    29). And what no branch needs its own copy of, the query in f32 and the
    mask, is made once outside them. (Heads that share key/value heads
    take `grouped_walk`.)"""
    _, hq, d = q.shape
    q32 = q.astype(jnp.float32)
    visible = jnp.arange(k_cache.shape[1])[None, :] < lengths[:, None]

    def attend(k, v):
        k, v = k[..., :d], v[..., :d]
        scores = jnp.swapaxes(
            jnp.sum(q32[:, None] * k.astype(jnp.float32), axis=-1), 1, 2)
        scores = jnp.where(visible[:, None, :k.shape[1]],
                           scores / (d ** 0.5), NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bht,bthd->bhd", probs, v.astype(jnp.float32))
        return out.astype(q.dtype)

    return over_kv_prefix(attend, (k_cache, v_cache), kv_bound)


def decode_attention(q, k_cache, v_cache, lengths, *, kv_bound=None):
    """One new token a sequence against its cache rows `[0, lengths[b])`
    (all of a ring's rows once `lengths[b]` has passed its length).
    q: [B, H, D]; caches [B, S, KV, D]; lengths [B] -> [B, H, D].

    A `kv_bound` (`over_kv_prefix`: the longest live sequence's rows, from
    whoever knows which sequences are live) stops the walk at a static
    prefix of the cache instead of S, and the caches may then come with
    rows wider than D (zeros beyond it), which are cut with the prefix;
    `lengths` still masks each sequence inside it. Without one the whole
    cache is walked, by the program this always built."""
    # One name for both forms in a device trace (operation metadata only).
    with jax.named_scope("decode_attention"):
        if kv_bound is None:
            return _xla_decode_attention(q, k_cache, v_cache, lengths)
        if k_cache.shape[2] < q.shape[1]:
            return grouped_walk(q, k_cache, v_cache, lengths, kv_bound)
        return _xla_decode_walk(q, k_cache, v_cache, lengths, kv_bound)
