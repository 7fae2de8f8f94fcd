"""Decode attention: one new token a sequence against its KV cache.

The serving hot loop is q [B, H, D] attending over a fixed [B, S, KV, D]
cache, each sequence up to its own length. Which form serves where
(`decode_attention` picks from what it can observe and says so once):

- On a TPU, given a `kv_bound` (the serving scheduler's chunks), with cache
  rows of whole lane tiles and no mesh of several devices in context:
  `ragged_decode_attention`, one Pallas kernel for a head of its own and
  for grouped heads, full leaves and rings. Every slot stops at its OWN
  length rounded up to a row block, a free slot reads nothing, the leaf is
  read in the shape and layout it has, the scores never leave VMEM (PR 37).
- Given a `kv_bound` anywhere else (the CPU and the tests, a `tp` mesh, a
  row that is no whole lane tile): the bounded walk in plain JAX,
  `_xla_decode_walk` and `grouped_walk`, to a static prefix of the cache
  that holds the longest LIVE sequence (`over_kv_prefix`). How it is
  written decides what the TPU's compiler makes of it (PERF.md section 6,
  PR 29): change it only with a chip run beside it.
- Without a bound (`LLMEngine.generate`, the pipeline's stages, the tests'
  references): `_xla_decode_attention`, all S rows under a mask.
- Latent rows (`models/mla.py`, PR 41) and an "eva" layer's two leaves
  under one softmax (`models/eva.py`, PR 50: `two_leaf_decode_attention`)
  have a kernel, a rule and walks of their own, each in its own part of
  this file (a kernel's program names its body's lines: add at the end).

Reference role: vLLM's paged-attention decode kernel (the engine seat
python/ray/llm delegates; no TPU equivalent exists in the reference).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention as rule
from ray_tpu.ops.flash_attention import LANES, auto_block

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


#: Bytes of K (and as many of V) one block of a ragged kernel holds: the row
#: block is the largest divisor of the leaf's rows at or under this many
#: bytes of rows (Phi-3's rows of 32 heads of 128: 128 rows; Trinity's of 4:
#: 1024). The latent and the two-leaf kernels round a slot's walk up to it.
BLOCK_BYTES = 1 << 20

#: Bytes of K (and as many of V) one piece of a slot's LAST block in the
#: `mha` family's kernel: the block is fetched to the slot's own rows rounded
#: up to the largest divisor of the block at or under this many bytes of
#: rows (an eighth of a block: 16 rows of Phi-3's leaves, 32 of Ouro's, 128
#: of Trinity's).
GRANULE_BYTES = 128 << 10


def row_block(leaf_shape, dtype) -> int | None:
    """Rows of one block of the ragged kernel for a leaf `[slots, rows, KV,
    row]`: derived from the row's bytes, a divisor of `rows` in whole
    sublane tiles (or all of a short leaf). None: no such divisor."""
    _, rows, kv, row = leaf_shape
    most = max(8, BLOCK_BYTES // (kv * row * jnp.dtype(dtype).itemsize))
    return rows if rows <= most else auto_block(rows, most, 8)


def row_granule(leaf_shape, dtype) -> int | None:
    """Rows of one piece of a slot's last block (`GRANULE_BYTES`): a divisor
    of `row_block`'s block, which is what a slot's fetch is rounded up to.
    (Rows are a leaf's untiled axis: a piece of any rows is whole tiles.)"""
    block = row_block(leaf_shape, dtype)
    _, _, kv, row = leaf_shape
    most = max(1, GRANULE_BYTES // (kv * row * jnp.dtype(dtype).itemsize))
    return block and auto_block(block, most, 1)


def walk_refusal(q_shape, leaf_shape, dtype=jnp.bfloat16) -> str | None:
    """Why a bounded step of q [B, H, D] over a leaf [B, rows, KV, row]
    takes the XLA walk, or None when it takes the ragged kernel: the
    dispatcher's own rule (`ops/attention.py` `kernel_refusal` states the
    same for the prefill), for whoever wants to know the choice without
    making the call (llm/engine.py counts the decode steps either way)."""
    if not rule.on_tpu():
        return rule.NOT_ASKED
    (_, hq, d), (_, rows, kv, row) = q_shape, leaf_shape
    if (reason := rule.mesh_refusal()) is not None:
        return reason
    if hq % kv:
        return f"Hq={hq} not a multiple of Hkv={kv}"
    if row % LANES or d > row:
        return f"a cache row of {row} is not whole lane tiles ({LANES})"
    if row_block(leaf_shape, dtype) is None:
        return f"{rows} rows have no block in whole sublane tiles"
    return None


def _merged(ref):
    """The block `[1, T, KV, row]` a ref holds, as ONE matrix `[T x KV,
    row]`, row t * KV + h of it position t of key/value head h. The two
    images are the same bytes in VMEM, but a leaf of few heads of a packed
    dtype lies in tiles smaller than a register's (four bf16 heads: (4,
    128)), and merged as a value Mosaic shuffles every tile (Trinity's
    leaves: 520 GB/s against 617, and 1.8 s of compile against 0.3; my chip
    run, PR 37). Read as 32-bit words, which `KV / 2` rows of words a
    position are whatever the tile, and unpacked in registers, nothing
    moves."""
    _, t, kv, row = ref.shape
    pack = 4 // ref.dtype.itemsize
    if pack > 1 and kv % pack == 0:
        words = ref.bitcast(jnp.uint32).reshape(t * kv // pack, row)[...]
        return pltpu.bitcast(words, ref.dtype)
    return ref[0].reshape(t * kv, row)


def _merged_seen(ref, seen):
    """`_merged(ref)` with the block's rows at and past `seen` zeroed: what
    lies above a stop is an earlier occupant's, and a weight of exactly 0
    keeps nothing out of a product if the value is not finite. In words,
    as `_merged` reads them; the fetch hides it (my chip run, PR 50)."""
    _, t, kv, row = ref.shape
    pack = 4 // ref.dtype.itemsize
    if pack > 1 and kv % pack == 0:
        kv, image = kv // pack, jnp.uint32
    else:
        pack, image = 1, ref.dtype
    rows = ref.bitcast(image).reshape(t * kv, row)[...]
    at = jax.lax.broadcasted_iota(jnp.int32, (t * kv, 1), 0) // kv
    rows = jnp.where(at < seen, rows, jnp.zeros_like(rows))
    return pltpu.bitcast(rows, ref.dtype) if pack > 1 else rows


def _attend(q_ref, k_ref, v_ref, seen, m_ref, l_ref, acc_ref, *,
            scale: float, group: int, exact: bool):
    """One block of a slot's online softmax, the `mha` family's and the
    two-leaf kernel's: q_ref [H, row] against the first `seen` rows of the
    block k_ref/v_ref `[1, T, KV, row]` hold (what lies past them may be
    anything: the scores there are masked and V's rows zeroed); m/l [H,
    128] (every lane the same), acc [H, row]. The block's rows of all KV
    heads are ONE matrix `[T x KV, row]` (`_merged`), every query head is
    multiplied against all of it on the MXU, and the mask keeps, for each
    query head, the visible rows of its own key/value head (head h reads
    key/value head h // `group`). bf16 operands, float32 sums; `exact`:
    the float32 probabilities against V's own dtype, as two products."""
    (hq, _), (_, t, kv, _) = q_ref.shape, k_ref.shape
    k, v = _merged(k_ref), _merged_seen(v_ref, seen)
    s = jax.lax.dot_general(
        q_ref[...], k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    # column c of the merged matrix is row c // KV of the block, key/value
    # head c % KV
    col = jax.lax.broadcasted_iota(jnp.int32, (1, t * kv), 1)
    head = jnp.where(col // kv < seen, col % kv, -1)
    own = jax.lax.broadcasted_iota(jnp.int32, (hq, 1), 0)
    s = jnp.where(head == (own // group if group > 1 else own), s, NEG_INF)
    # (the block holds a visible row, so every head's max is finite)
    m_prev, l_prev = m_ref[:, 0:1], l_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    weigh = lambda w: jax.lax.dot_general(  # noqa: E731
        w, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    hi = p.astype(v.dtype)
    pv = weigh(hi)
    if exact and hi.dtype != p.dtype:
        pv = pv + weigh((p - hi.astype(jnp.float32)).astype(v.dtype))
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _ragged_kernel(stop_ref, slot_ref, at_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *, scale: float,
                   block: int, granule: int, group: int, exact: bool):
    """One entry of the grid: row block `at_ref[i]` of slot `slot_ref[i]`.
    q_ref/o_ref [H, row]; k_hbm/v_hbm the leaves `[slots, rows, KV, row]`
    where they lie; k_buf/v_buf [2, block, KV, row], the entry's block in
    buffer `i % 2` while the next entry's is on its way into the other
    (`sems` [2, 2]: buffer, K or V); m/l/acc `_attend`'s. An entry fetches
    the rows of its block its slot shows, a whole block as one copy and a
    slot's last as pieces of `granule` rows: what lies past them in the
    buffer is an earlier entry's, or nothing's, and `_attend` lets none of
    it through. Every copy an entry starts (the first its own, each the
    next one's) is waited for by the entry it is for, under the same
    condition: the last entry leaves none in flight."""
    i = pl.program_id(0)

    def fetch(entry, act):
        """Start, or wait for, the copies of `entry`'s rows into its
        buffer: none for a free slot's entry."""
        b, first = slot_ref[entry], at_ref[entry] * block
        seen = stop_ref[b] - first

        def copies(at, rows):
            """K's and V's rows `[at, at + rows)` of the block."""
            for c, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                act(pltpu.make_async_copy(
                    hbm.at[b, pl.ds(first + at, rows)],
                    buf.at[entry % 2, pl.ds(at, rows)], sems.at[entry % 2, c]))

        @pl.when(seen >= block)
        def _whole():
            copies(0, block)

        @pl.when(seen < block)
        def _last():
            jax.lax.fori_loop(0, (seen + granule - 1) // granule,
                              lambda n, _: copies(n * granule, granule), None)

    @pl.when(i == 0)
    def _first():
        fetch(i, lambda copy: copy.start())

    @pl.when(i + 1 < pl.num_programs(0))  # (the last entry starts none)
    def _ahead():
        fetch(i + 1, lambda copy: copy.start())

    j = at_ref[i]
    stop = stop_ref[slot_ref[i]]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block < stop)
    def _block():
        fetch(i, lambda copy: copy.wait())
        here = pl.ds(i % 2, 1)
        _attend(q_ref, k_buf.at[here], v_buf.at[here], stop - j * block,
                m_ref, l_ref, acc_ref, scale=scale, group=group, exact=exact)

    @pl.when((j + 1) * block >= stop)  # the slot's last entry
    def _finish():
        l = l_ref[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)  # a free slot: zeros
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def ragged_decode_attention(q, k_cache, v_cache, lengths, live=None, *,
                            interpret: bool = False):
    """q [B, H, D] against cache leaves [B, rows, KV, row] (row >= D, zeros
    beyond D), slot b up to `min(lengths[b], rows)` rows (a ring that has
    wrapped shows all its rows, in whatever order: a softmax does not
    care), and NO row of a slot `live` [B] bool marks free, whose output is
    zeros (its device-side length is stale and keeps growing). -> [B, H, D].

    The grid is ONE list of the row blocks that hold a visible row, slot
    after slot, its length a traced scalar (one program whatever it is):
    no entry passes without work but a free slot's one, which fetches
    nothing and writes the zeros. The leaves stay in HBM, read in the shape
    and layout they have, and the kernel fetches for itself, the next
    entry's rows on their way while this entry's are multiplied: a whole
    block in one copy, a slot's last block to the slot's own rows rounded
    up to `row_granule`'s piece. Online softmax in float32 VMEM scratch.
    bf16 operands, float32 sums; the probabilities go to the weighted sum
    in V's dtype where heads share key/value heads, and as float32 (two
    products) where each has its own, as the XLA walk of each family does."""
    (_, hq, d), (_, _, kv, row) = q.shape, k_cache.shape
    block = row_block(k_cache.shape, k_cache.dtype)
    if not block or hq % kv or row < d:
        raise ValueError(f"q {q.shape} against a leaf {k_cache.shape} in "
                         f"blocks of {block} rows (`walk_refusal` says so "
                         f"beforehand)")
    return _ragged(q, k_cache, v_cache, lengths, live, block=block,
                   granule=row_granule(k_cache.shape, k_cache.dtype),
                   interpret=interpret)


def work_list(stop, block: int, n_blocks: int):
    """The grid's entries for slots that show `stop` [B] rows of leaves of
    `n_blocks` blocks of `block` rows, as two tables a scalar prefetch
    carries: whose entry it is and which of its row blocks, slot after
    slot, one entry for each block that holds a visible row and one for a
    slot that shows none; and the entries counted up to each slot's last
    [B], whose last is the grid's length: entries past it are never looked
    at."""
    b = stop.shape[0]
    need = jnp.maximum(-(-stop // block), 1)
    ends = jnp.cumsum(need)
    entry = jnp.arange(b * n_blocks, dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(entry[:, None] >= ends[None, :], axis=1,
                               dtype=jnp.int32), b - 1)
    return slot, entry - (ends - need)[slot], ends


@functools.partial(jax.jit,
                   static_argnames=("block", "granule", "interpret"))
def _ragged(q, k_cache, v_cache, lengths, live, *, block: int, granule: int,
            interpret: bool):
    """`ragged_decode_attention` in row blocks of `block` and last pieces of
    `granule` (`row_block`'s, `row_granule`'s; static: the layers of a model
    that share a leaf's shape share one trace)."""
    b, hq, d = q.shape
    _, rows, kv, row = k_cache.shape
    stop = jnp.minimum(lengths.astype(jnp.int32), rows)
    if live is not None:
        stop = jnp.where(live, stop, 0)
    slot, at, ends = work_list(stop, block, rows // block)
    if row > d:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, row - d)))

    # (left to itself the compiler may stage a whole leaf that fits its
    # fast memory there ahead of the call, Trinity's rings at 16 slots:
    # every row of every slot, live or not)
    in_hbm = (lambda leaf: leaf) if interpret else functools.partial(
        pltpu.with_memory_space_constraint, memory_space=pltpu.HBM)
    kernel = functools.partial(
        _ragged_kernel, scale=d ** -0.5, block=block, granule=granule,
        group=hq // kv, exact=hq == kv)
    leaf = pl.BlockSpec(memory_space=pl.ANY)
    head = pl.BlockSpec((None, hq, row), lambda i, stop, slot, at: (
        slot[i], 0, 0))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hq, row), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(ends[-1],),
            in_specs=[head, leaf, leaf],
            out_specs=head,
            scratch_shapes=[
                pltpu.VMEM((2, block, kv, row), k_cache.dtype),
                pltpu.VMEM((2, block, kv, row), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hq, LANES), jnp.float32),  # max
                pltpu.VMEM((hq, LANES), jnp.float32),  # denominator
                pltpu.VMEM((hq, row), jnp.float32),  # accumulator
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(stop, slot, at, q, in_hbm(k_cache), in_hbm(v_cache))
    return out[..., :d]


def decode_attention(q, k_cache, v_cache, lengths, *, kv_bound=None,
                     live=None):
    """One new token a sequence against its cache rows `[0, lengths[b])`
    (all of a ring's rows once `lengths[b]` has passed its length).
    q: [B, H, D]; caches [B, S, KV, D]; lengths [B] -> [B, H, D].

    A `kv_bound` (the longest live sequence's rows, from whoever knows
    which sequences are live) bounds the walk, and the caches may then come
    with rows wider than D (zeros beyond it). On a TPU the ragged kernel
    takes such a step (`walk_refusal` is the rule; each choice is stated
    once at INFO): every slot stops at its own length and a slot that
    `live` [B] bool marks free reads nothing and returns zeros. Elsewhere
    the walk stops at a static prefix of the cache (`over_kv_prefix`) and
    `lengths` masks each sequence inside it. Without a bound the whole
    cache is walked, by the program this always built."""
    # One name for every form in a device trace (operation metadata only).
    with jax.named_scope("decode_attention"):
        if kv_bound is None:
            return _xla_decode_attention(q, k_cache, v_cache, lengths)
        reason = walk_refusal(q.shape, k_cache.shape, k_cache.dtype)
        if reason is None:
            rule.state_once("decode attention: ragged Pallas kernel")
            return ragged_decode_attention(q, k_cache, v_cache, lengths, live)
        if reason != rule.NOT_ASKED:
            rule.state_once(f"decode attention: XLA walk to a quarter "
                            f"prefix ({reason})")
        if k_cache.shape[2] < q.shape[1]:
            return grouped_walk(q, k_cache, v_cache, lengths, kv_bound)
        return _xla_decode_walk(q, k_cache, v_cache, lengths, kv_bound)


# ---------------------------------------------------------------------------
# Latent rows (models/mla.py): one "head" of `[c_kv | k_r | 0]` a position,
# key and value both. A path of its own beside the `mha` family's above,
# with which it shares the block's size (`row_block`) and `rule` and nothing
# whose text would have to bend: K and V as ONE operand against two, one
# head against grouped heads merged into one matrix, a row of 640 against
# 128 (PERF.md section 6, PR 41).


def latent_block(leaf_shape, dtype) -> int | None:
    """Rows of one block of the latent kernel for a leaf `[slots, rows,
    row]`: `row_block`'s, a latent row being its one-head case (Kimi's 4096
    rows of 640 bf16 values: 512 rows). None: no such divisor."""
    slots, rows, row = leaf_shape
    return row_block((slots, rows, 1, row), dtype)


def latent_refusal(leaf_shape, rank: int, dtype=jnp.bfloat16) -> str | None:
    """Why a bounded step over a latent leaf `[slots, rows, row]` whose
    first `rank` values are `c_kv` takes the XLA walk (`models/mla.py`
    `_latent_walk`), or None when it takes `ragged_latent_attention`: the
    rule `models/mla.py` asks, for whoever wants to know the choice without
    making the call (llm/engine.py counts the decode steps either way). A
    row as the model has it (576 values) is refused: the engine asks the
    compiler first and widens it (`_probe_cache_row`: 640)."""
    if not rule.on_tpu():
        return rule.NOT_ASKED
    if (reason := rule.mesh_refusal()) is not None:
        return reason
    _, rows, row = leaf_shape
    if row % LANES or rank % LANES:
        return (f"a latent row of {row} with {rank} of c_kv is not whole "
                f"lane tiles ({LANES})")
    if latent_block(leaf_shape, dtype) is None:
        return f"{rows} rows have no block in whole sublane tiles"
    return None


def _latent_kernel(stop_ref, slot_ref, at_ref, _held_ref, q_ref, c_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, scale: float, block: int,
                   rank: int):
    """One entry of the grid: row block `at_ref[i]` of slot `slot_ref[i]`.
    q_ref [H, row], the query in the latent space beside its rotary part,
    zeros beyond; c_ref [1, block, row], fetched ONCE and read as keys (the
    whole row) and as values (its first `rank` lanes); o_ref [H, rank];
    m/l [H, 128] (every lane the same), acc [H, rank]. The heads are the
    rows of both products."""
    i = pl.program_id(0)
    j = at_ref[i]
    stop = stop_ref[slot_ref[i]]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block < stop)
    def _block():
        s = jax.lax.dot_general(
            q_ref[...], c_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        s = jnp.where(col < stop - j * block, s, NEG_INF)
        # (the block holds a visible row, so every head's max is finite)
        m_prev, l_prev = m_ref[:, 0:1], l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        # the probabilities in the cache's dtype, as `_latent_attention`
        pv = jax.lax.dot_general(
            p.astype(c_ref.dtype), c_ref[0, :, :rank],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when((j + 1) * block >= stop)  # the slot's last entry
    def _finish():
        l = l_ref[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)  # a free slot: zeros
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def ragged_latent_attention(q, latents, lengths, live=None, *, rank: int,
                            scale: float, interpret: bool = False):
    """q [B, H, width] (`[q_lat | q_rope]`, `width` <= row) against a
    latent leaf [B, rows, row] (zeros beyond `width`), slot b up to
    `lengths[b]` rows and NO row of a slot `live` [B] bool marks free,
    whose output is zeros -> the weighted sums of the rows' first `rank`
    values, [B, H, rank]: `models/mla.py` `_latent_attention`'s result.

    The grid is ONE list of the row blocks that hold a visible row, slot
    after slot, its length a traced scalar (one program whatever it is):
    no entry passes without work but a free slot's one, which fetches
    nothing (it names the block the pipeline already holds) and writes the
    zeros. (In a grid of (slot, row block) as long as the longest slot's,
    `ragged_decode_attention`'s, three entries in five of these cells'
    lengths are past their slot's stop, at a third of a microsecond each:
    my chip run, PR 41.) Online softmax in float32 VMEM scratch; the leaf
    is read where it lies, each block once. bf16 operands, float32 sums;
    the scores never leave VMEM."""
    block = latent_block(latents.shape, latents.dtype)
    if not block or latents.shape[2] < q.shape[2] or rank > q.shape[2]:
        raise ValueError(f"q {q.shape} against a latent leaf "
                         f"{latents.shape} in blocks of {block} rows "
                         f"(`latent_refusal` says so beforehand)")
    return _ragged_latent(q, latents, lengths, live, rank=rank, scale=scale,
                          block=block, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("rank", "scale", "block", "interpret"))
def _ragged_latent(q, latents, lengths, live, *, rank: int, scale: float,
                   block: int, interpret: bool):
    """`ragged_latent_attention` in row blocks of `block` (`latent_block`'s,
    static: the latent layers of a model share one trace)."""
    b, hq, width = q.shape
    _, rows, row = latents.shape
    n_blocks = rows // block
    stop = jnp.minimum(lengths.astype(jnp.int32), rows)
    if live is not None:
        stop = jnp.where(live, stop, 0)
    # The grid's entries (`work_list`), and as a third table the block to
    # hold while an entry passes, `slot * n_blocks + block`: its own, or
    # for a free slot's entry the one before it (the first of all, if none).
    slot, at, ends = work_list(stop, block, n_blocks)
    held = jnp.maximum(jax.lax.cummax(jnp.where(
        stop[slot] > 0, slot * n_blocks + at, -1)), 0)
    if row > width:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, row - width)))

    per_slot = lambda i, stop, slot, *_: (slot[i], 0, 0)  # noqa: E731
    leaf = latents if interpret else pltpu.with_memory_space_constraint(
        latents, memory_space=pltpu.HBM)
    return pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, block=block,
                          rank=rank),
        out_shape=jax.ShapeDtypeStruct((b, hq, rank), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(ends[-1],),
            in_specs=[pl.BlockSpec((None, hq, row), per_slot),
                      pl.BlockSpec(
                          (1, block, row), lambda i, stop, slot, at, held: (
                              held[i] // n_blocks, held[i] % n_blocks, 0))],
            out_specs=pl.BlockSpec((None, hq, rank), per_slot),
            scratch_shapes=[
                pltpu.VMEM((hq, LANES), jnp.float32),  # max
                pltpu.VMEM((hq, LANES), jnp.float32),  # denominator
                pltpu.VMEM((hq, rank), jnp.float32),  # accumulator
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(stop, slot, at, held, q, leaf)


# ---------------------------------------------------------------------------
# ONE softmax over leaves of two kinds (models/eva.py): a window leaf whose
# rows `[0, stop_w)` are positions, and a chunks leaf whose rows `[0, stop_c)`
# each stand for several. On a TPU a bounded step takes ONE ragged kernel
# over both (`ragged_two_leaf_attention`, PERF.md section 6, PR 50): every
# slot stops at its own row in BOTH leaves and one online softmax runs over
# its window blocks and then its summary blocks. Elsewhere (the CPU and the
# tests, a `tp` mesh, the unbounded step) each leaf is walked on its own in
# XLA to what a softmax is made of (running maximum, sum, weighted sum), a
# bounded walk to the static prefix that holds its leaf's longest live stop,
# and the two are merged: what an online softmax does between two blocks,
# between two leaves. The `mha` family's programs above are left as they
# are, to the line.


@jax.jit
def partial_walk(q, k_cache, v_cache, stop, bound):
    """q [B, H, D] against rows `[0, stop[b])` of a leaf `[B, rows, H, D]`,
    walked to the shortest static prefix that holds `bound` rows (a traced
    scalar, `over_kv_prefix`): the scores' maximum m [B, H] (-inf where no
    row is visible), the sum l [B, H] of exp(score - m) and the weighted sum
    acc [B, H, D] of the values under the same weights, all float32 and not
    normalised, so that two leaves merge (`merge_partials`). Written as
    `_xla_decode_walk` is, for what the TPU's compiler makes of it."""
    d = q.shape[-1]
    q32 = q.astype(jnp.float32)
    visible = jnp.arange(k_cache.shape[1])[None, :] < stop[:, None]

    def attend(k, v):
        scores = jnp.swapaxes(
            jnp.sum(q32[:, None] * k.astype(jnp.float32), axis=-1), 1, 2)
        scores = jnp.where(visible[:, None, :k.shape[1]],
                           scores / (d ** 0.5), NEG_INF)
        m = jnp.max(scores, axis=-1)
        p = jnp.exp(scores - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
        acc = jnp.einsum("bht,bthd->bhd", p, v.astype(jnp.float32))
        return m, jnp.sum(p, axis=-1), acc

    return over_kv_prefix(attend, (k_cache, v_cache), bound)


def merge_partials(*parts):
    """The softmax over the rows of all `parts` (each `partial_walk`'s m, l,
    acc) together: [B, H, D] float32; zeros where no part saw a row."""
    m = functools.reduce(jnp.maximum, [p[0] for p in parts])
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    weights = [jnp.exp(p[0] - m) for p in parts]  # 0 for a part without rows
    total = sum(w * p[1] for w, p in zip(weights, parts))
    acc = sum(w[..., None] * p[2] for w, p in zip(weights, parts))
    return acc / jnp.where(total == 0.0, 1.0, total)[..., None]


def two_leaf_block(window_shape, chunks_shape, dtype) -> int | None:
    """Rows of one block of the two-leaf kernel, the same in both leaves
    `[slots, rows, H, row]` (four operands under BlockSpecs of one shape):
    `row_block`'s of as many rows as divide both leaves (EvaByte's 2048 and
    1024 rows of 32 heads of 128 bf16 values: 128 rows, and a window of
    2048 holds 128 summaries, so a summaries' stop is whole blocks). None:
    no such divisor."""
    import math  # (here: no line above the older kernels may move)

    slots, rows, kv, row = window_shape
    shared = math.gcd(rows, chunks_shape[1])
    block = row_block((slots, shared, kv, row), dtype)
    # (`row_block` takes a short leaf whole; here only if it IS both leaves)
    return block if block and (block % 8 == 0
                               or shared == rows == chunks_shape[1]) else None


def two_leaf_refusal(q_shape, window_shape, chunks_shape,
                     dtype=jnp.bfloat16) -> str | None:
    """Why a bounded step of q [B, H, D] over a window leaf and a chunks
    leaf `[B, rows, H, D]` takes the two XLA walks and the merge, or None
    when it takes `ragged_two_leaf_attention`: `two_leaf_decode_attention`'s
    own rule, for whoever wants to know the choice without making the call
    (llm/engine.py counts the decode steps either way)."""
    if not rule.on_tpu():
        return rule.NOT_ASKED
    if (reason := rule.mesh_refusal()) is not None:
        return reason
    (_, hq, d), (_, rows, kv, row) = q_shape, window_shape
    if (kv, row) != (hq, d) or chunks_shape[2:] != (kv, row):
        return (f"leaves {window_shape} and {chunks_shape} are not rows of "
                f"{hq} heads of {d}")
    if row % LANES:
        return f"a cache row of {row} is not whole lane tiles ({LANES})"
    if two_leaf_block(window_shape, chunks_shape, dtype) is None:
        return (f"{rows} and {chunks_shape[1]} rows share no block in whole "
                f"sublane tiles")
    return None


def _two_leaf_kernel(stop_w_ref, stop_c_ref, slot_ref, at_ref, _held_w_ref,
                     _held_c_ref, q_ref, kw_ref, vw_ref, kc_ref, vc_ref,
                     o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                     block: int):
    """One entry of the grid: entry `at_ref[i]` of slot `slot_ref[i]`, whose
    entries are its window blocks and then its summary blocks. q_ref/o_ref
    [H, D]; kw/vw and kc/vc [1, block, H, D] as the two leaves hold them,
    of which an entry reads one pair (the other names the block its
    pipeline already holds); m/l [H, 128] (every lane the same), acc [H,
    D]: ONE online softmax over both leaves' blocks."""
    i = pl.program_id(0)
    j = at_ref[i]
    stop_w, stop_c = stop_w_ref[slot_ref[i]], stop_c_ref[slot_ref[i]]
    ahead = (stop_w + block - 1) // block  # the slot's window entries

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def rows(k_ref, v_ref, seen):
        """Heads on key/value heads of their own: the float32 walk's
        arithmetic up to the order of its sums."""
        _attend(q_ref, k_ref, v_ref, seen, m_ref, l_ref, acc_ref,
                scale=scale, group=1, exact=True)

    @pl.when(j < ahead)
    def _window():
        rows(kw_ref, vw_ref, stop_w - j * block)

    @pl.when((j >= ahead) & ((j - ahead) * block < stop_c))
    def _chunks():
        rows(kc_ref, vc_ref, stop_c - (j - ahead) * block)

    @pl.when((j + 1 - ahead) * block >= stop_c)  # the slot's last entry
    def _finish():
        l = l_ref[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)  # a free slot: zeros
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def ragged_two_leaf_attention(q, window, chunks, stop_w, stop_c, *,
                              interpret: bool = False):
    """q [B, H, D] against `window` = (K, V) rows `[0, stop_w[b])` and
    `chunks` = (Kbar, Vbar) rows `[0, stop_c[b])`, leaves `[B, rows, H, D]`
    of heads of their own, under ONE softmax -> [B, H, D]; a slot with both
    stops 0 reads nothing and gets zeros.

    The grid is `ragged_latent_attention`'s: ONE list of the row blocks
    that hold a visible row, slot after slot, a slot's window blocks and
    then its summary blocks, its length a traced scalar (one program
    whatever it is). The four leaves are four operands read where they lie
    in blocks of one shape; the pair an entry does not read names the block
    its pipeline already holds and fetches nothing, as a free slot's one
    entry does for both. One online softmax in float32 VMEM scratch runs
    over all of a slot's entries, so nothing is left to merge. bf16
    operands on the MXU, float32 sums, the float32 probabilities against
    bf16 values as two products: `partial_walk`'s precision."""
    block = two_leaf_block(window[0].shape, chunks[0].shape, window[0].dtype)
    if not block or window[0].shape[2:] != q.shape[1:]:
        raise ValueError(f"q {q.shape} against leaves {window[0].shape} and "
                         f"{chunks[0].shape} in blocks of {block} rows "
                         f"(`two_leaf_refusal` says so beforehand)")
    return _ragged_two_leaf(q, *window, *chunks, stop_w, stop_c, block=block,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _ragged_two_leaf(q, kw, vw, kc, vc, stop_w, stop_c, *, block: int,
                     interpret: bool):
    """`ragged_two_leaf_attention` in row blocks of `block`
    (`two_leaf_block`'s, static: the layers of a model share one trace)."""
    b, hq, d = q.shape
    n_w, n_c = kw.shape[1] // block, kc.shape[1] // block
    stop_w = jnp.clip(stop_w.astype(jnp.int32), 0, kw.shape[1])
    stop_c = jnp.clip(stop_c.astype(jnp.int32), 0, kc.shape[1])
    # The grid's entries, as tables a scalar prefetch carries: whose entry
    # it is, which of its entries (its window blocks come first), and the
    # block each leaf's operands hold while it passes, `slot * blocks +
    # block`: its own, or the one before it (the first of all, if none).
    # Entries past the grid's length are never looked at.
    need_w, need_c = -(-stop_w // block), -(-stop_c // block)
    need = jnp.maximum(need_w + need_c, 1)
    ends = jnp.cumsum(need)
    entry = jnp.arange(b * (n_w + n_c), dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(entry[:, None] >= ends[None, :], axis=1,
                               dtype=jnp.int32), b - 1)
    at = entry - (ends - need)[slot]
    behind = at - need_w[slot]  # which summary block, of a summaries' entry
    held = lambda own: jnp.maximum(jax.lax.cummax(own), 0)  # noqa: E731
    held_w = held(jnp.where(behind < 0, slot * n_w + at, -1))
    held_c = held(jnp.where((behind >= 0) & (behind < need_c[slot]),
                            slot * n_c + behind, -1))

    # (134 MB of summaries a layer will not be staged in fast memory, a
    # window leaf might: `_ragged`'s reason)
    in_hbm = (lambda leaf: leaf) if interpret else functools.partial(
        pltpu.with_memory_space_constraint, memory_space=pltpu.HBM)
    tables = (stop_w, stop_c, slot, at, held_w, held_c)
    head = pl.BlockSpec((None, hq, d), lambda i, *refs: (refs[2][i], 0, 0))

    def leaf(held_at: int, n: int):
        """A leaf's operands: the block `tables[held_at]` names, of `n`."""
        return pl.BlockSpec((1, block, hq, d), lambda i, *refs: (
            refs[held_at][i] // n, refs[held_at][i] % n, 0, 0))

    leaf_w, leaf_c = leaf(4, n_w), leaf(5, n_c)
    return pl.pallas_call(
        functools.partial(_two_leaf_kernel, scale=d ** -0.5, block=block),
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(ends[-1],),
            in_specs=[head, leaf_w, leaf_w, leaf_c, leaf_c],
            out_specs=head,
            scratch_shapes=[
                pltpu.VMEM((hq, LANES), jnp.float32),  # max
                pltpu.VMEM((hq, LANES), jnp.float32),  # denominator
                pltpu.VMEM((hq, d), jnp.float32),  # accumulator
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(*tables, q, in_hbm(kw), in_hbm(vw), in_hbm(kc), in_hbm(vc))


def two_leaf_decode_attention(q, window, chunks, stop_w, stop_c, *,
                              bounded: bool):
    """One new token a sequence against `window` = (K, V) rows `[0,
    stop_w[b])` and `chunks` = (Kbar, Vbar) rows `[0, stop_c[b])`, under one
    softmax. A free slot comes with both stops 0 and gets zeros. `bounded`
    on a TPU: the ragged kernel, every slot to its own rows of both leaves
    (`two_leaf_refusal` is the rule; each choice is stated once at INFO).
    `bounded` elsewhere: each leaf is walked to the static prefix that
    holds its longest stop; else whole."""
    with jax.named_scope("decode_attention"):
        if bounded:
            reason = two_leaf_refusal(q.shape, window[0].shape,
                                      chunks[0].shape, window[0].dtype)
            if reason is None:
                rule.state_once("two-leaf decode attention: ragged Pallas "
                                "kernel")
                return ragged_two_leaf_attention(q, window, chunks, stop_w,
                                                 stop_c)
            if reason != rule.NOT_ASKED:
                rule.state_once(f"two-leaf decode attention: XLA walks to a "
                                f"quarter prefix ({reason})")
        parts = []
        for (k, v), stop in ((window, stop_w), (chunks, stop_c)):
            bound = jnp.max(stop) if bounded else jnp.int32(k.shape[1])
            parts.append(partial_walk(q, k, v, stop, bound))
        return merge_partials(*parts).astype(q.dtype)


# ---------------------------------------------------------------------------
# A block step (`models/transformer.py` `BlockAttention`): L queries a slot
# with ONE stop a slot. All L x H query rows of a slot read the same rows
# [0, stop) of its leaves, so to every form above they are L x (H / KV) query
# heads on each key/value head where a single-token step has H / KV: the
# ragged kernel's work list, copies and pieces, the walks' prefixes and
# masks are what they were; only the query matrix has more rows.

def block_decode_attention(q, k_cache, v_cache, stop, *, kv_bound=None,
                           live=None):
    """q [B, L, H, D]: the L positions of a slot's open block, each of whose
    H heads reads the slot's rows `[0, stop[b])` of the leaves [B, rows, KV,
    row] (the block's own rows among them: the caller wrote them first).
    -> [B, L, H, D]. `kv_bound` and `live` as `decode_attention` takes them,
    which serves it: the rows are laid out key/value head by key/value head
    (head h of position l is row kv * L * G + l * G + g of G = H / KV), so
    that a query row's key/value head is its index over L x G."""
    b, l, hq, d = q.shape
    kv = k_cache.shape[2]
    g = hq // kv
    rows = q.reshape(b, l, kv, g, d).transpose(0, 2, 1, 3, 4)
    out = decode_attention(rows.reshape(b, kv * l * g, d), k_cache, v_cache,
                           stop, kv_bound=kv_bound, live=live)
    return out.reshape(b, kv, l, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, l, hq, d)
