"""Pallas attention with a second key source, for TPU: the whole-sequence
call of an "eva" layer past its first window (`models/eva.py`: a query
attends exactly to its own window's rows up to itself and to ONE summary
row for every chunk of every window before its own, under one softmax).

`ops/flash_attention.py`'s schedule with two sources of keys: the grid is
(batch row, group of heads, query block, key step), the running max,
denominator and accumulator are float32 VMEM scratch carried across the
key steps, and no score tile leaves the chip. A query block in window w
visits, in this order,

- its own window's key blocks, from row `w W` up to its diagonal, with the
  mask in the diagonal block only (a query block lies in one window and a
  key block does too: both blocks divide W);
- the summary blocks `[0, w (W / C) / block)`, whole blocks all of them
  (W / C is a whole number of summary blocks: `two_source_refusal`), so
  they need no mask;

and nothing else: keys of later windows and summaries of the own and later
windows are never fetched. Each source has block index maps of its own; a
source that a step does not read names the block its pipeline already
holds, so nothing is fetched for it. The extent of the key steps is the
most any query block visits, and the steps left over re-use the blocks in
VMEM (no fetch, no work). Query blocks wholly at or past `q_len` are
skipped the same way and come back as zeros; inside the diagonal block the
values at and past `q_len` are zeroed before their product (a weight of
exactly 0 keeps nothing out of a product if the value is not finite).

Nothing is transposed: rows are read as [B, S, H * D] and a head is a
slice of whole lane tiles; a grid step holds `GROUP_ROWS / block_q` heads
(a loop inside the kernel, as the flash kernel's group is). Operands go to
the MXU in the dtype they come in (bf16), sums and softmax state are
float32, the probabilities are cast to V's dtype for the weighted sum:
what `eva_sequence` and the flash kernel both do.

A body and an entry point of its own, not an arm of `_flash_kernel`: a
kernel's program names its body's lines, and the flash kernel serves other
models' prefills (PERF.md section 6, PR 51 has this kernel's measurements).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention as rule
from ray_tpu.ops.flash_attention import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q,
                                         GROUP_ROWS, LANES, auto_block)

NEG_INF = float("-inf")


def two_source_blocks(heads: int, window: int, per: int,
                      block_q: int | None = None,
                      block_k: int | None = None):
    """(query block, own-window key block, summary block, heads a grid
    step) for windows of `window` rows that hold `per` summaries each, or
    None where the TPU's tiles do not divide them: the query and key blocks
    divide a window (a block never straddles two), the summary block
    divides a window's summaries (the visible ones are whole blocks).
    `block_q` and `block_k` are upper-bound preferences; the flash kernel's
    are this kernel's too (1024 queries x 4 heads a step read 16% faster
    alone, nothing end to end, and cost every warm start a longer load of
    each program: PERF.md section 6, PR 51)."""
    bq = auto_block(window, block_q or DEFAULT_BLOCK_Q, 8)
    bk = auto_block(window, block_k or DEFAULT_BLOCK_K, LANES)
    bs = auto_block(per, DEFAULT_BLOCK_K, LANES)
    if not (bq and bk and bs):
        return None
    group = max(g for g in range(1, heads + 1)
                if heads % g == 0 and g * bq <= max(GROUP_ROWS, bq))
    return bq, bk, bs, group


def two_source_refusal(q_shape, window: int, chunk: int) -> str | None:
    """Why the attention of an "eva" layer's whole-sequence call of q
    [B, S, H, D] past one window (S > `window`, one summary a `chunk`
    rows) takes the tile scan in XLA (`models/eva.py` `eva_sequence`)
    outside differentiation, or None when it takes `two_source_attention`:
    `models/eva.py` `eva_attention`'s own rule, for whoever wants to know
    the choice without making the call (llm/engine.py counts the prefill
    rows either way)."""
    if not rule.on_tpu():
        return rule.NOT_ASKED
    if (reason := rule.mesh_refusal()) is not None:
        return reason
    _, s, h, d = q_shape
    if d % LANES:
        return f"head size {d} is not a whole number of lane tiles ({LANES})"
    if s % window:
        return f"S={s} is not a whole number of windows of {window}"
    if window % chunk or (window // chunk) % LANES:
        return (f"a window of {window} rows holds {window / chunk:g} "
                f"summaries, not a whole number of {LANES}-row blocks")
    return None


def _visits(qi, *, block_q: int, block_k: int, block_s: int, window: int,
            per: int):
    """(first, own, behind) of a query block: it visits the key blocks
    `[first, first + own)` of its own window, the last of them its
    diagonal, and then the summary blocks `[0, behind)`. For a traced `qi`
    and for a Python int."""
    q_start = qi * block_q
    w = q_start // window
    first = w * (window // block_k)
    return (first, (q_start + block_q - 1) // block_k - first + 1,
            w * (per // block_s))


def _two_source_kernel(qlen_ref, q_ref, k_ref, v_ref, kbar_ref, vbar_ref,
                       o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                       group: int, d: int, n_steps: int, where: dict):
    """One (batch row, group of heads, query block, key step) of the grid.
    q_ref/o_ref [block_q, group * d], head g of the group in lanes
    [g*d, (g+1)*d); k_ref/v_ref [block_k, group * d] of the call's own
    rows, kbar_ref/vbar_ref [block_s, group * d] of its summaries, of which
    a step reads one pair; m/l [group, block_q, 128] (every lane the same),
    acc [group, block_q, d]: ONE online softmax over both sources."""
    bi, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    block_q, block_k = where["block_q"], where["block_k"]
    q_start = qi * block_q
    first, own, behind = _visits(qi, **where)
    k_start = (first + kj) * block_k
    q_len = qlen_ref[bi]
    live = q_start < q_len

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(keys_ref, values_ref, masked: bool):
        rows = keys_ref.shape[0]
        if masked:
            visible = (q_start - k_start) + (
                jax.lax.broadcasted_iota(jnp.int32, (block_q, rows), 0)
                - jax.lax.broadcasted_iota(jnp.int32, (block_q, rows), 1)
            ) >= 0
            # rows at and past q_len are read by nobody, whatever they hold
            given = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (rows, 1), 0) < q_len

        def one_head(g, _):
            lanes = pl.ds(pl.multiple_of(g * d, d), d)
            q, k, v = q_ref[:, lanes], keys_ref[:, lanes], values_ref[:, lanes]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(visible, s, NEG_INF)
                v = jnp.where(given, v, jnp.zeros_like(v))
            # (every row has met its window's first key: its max is finite)
            m_prev, l_prev = m_ref[g, :, 0:1], l_ref[g, :, 0:1]
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

    # An own-window block wholly at or before the block's first row needs
    # no mask; the diagonal block does.
    ours = jnp.logical_and(live, kj < own)
    inside = k_start + block_k - 1 <= q_start
    pl.when(jnp.logical_and(ours, inside))(
        lambda: update(k_ref, v_ref, False))
    pl.when(jnp.logical_and(ours, jnp.logical_not(inside)))(
        lambda: update(k_ref, v_ref, True))
    pl.when(jnp.logical_and(live, jnp.logical_and(
        kj >= own, kj - own < behind)))(
        lambda: update(kbar_ref, vbar_ref, False))

    @pl.when(kj == n_steps - 1)
    def _finish():
        def one_head(g, _):
            l = l_ref[g, :, 0:1]
            l = jnp.where(l == 0.0, 1.0, l)  # a skipped query block: zeros
            o_ref[:, pl.ds(pl.multiple_of(g * d, d), d)] = (
                acc_ref[g] / l).astype(o_ref.dtype)

        jax.lax.fori_loop(0, group, one_head, None)


@functools.partial(
    jax.jit,
    static_argnames=("window", "chunk", "block_q", "block_k", "interpret"))
def two_source_attention(q, k, v, kbar, vbar, *, window: int, chunk: int,
                         q_len=None, block_q: int | None = None,
                         block_k: int | None = None,
                         interpret: bool = False):
    """q, k, v [B, S, H, D], positions 0..S-1 in order, S a whole number
    of windows of `window` rows; kbar, vbar [B, S / chunk, H, D], one row a
    chunk of `chunk` positions -> [B, S, H, D]: query i in window w =
    i // window sees the keys `[w window, i]` and the summaries `[0,
    w window / chunk)` under one softmax (`eva_sequence`'s result).
    `q_len` ([B] int32, or None for all): query blocks wholly at or past it
    are not computed and come back as zeros, and no row at or past it
    reaches a sum. block_q/block_k are upper-bound preferences
    (`two_source_blocks`). ONE Pallas call whatever S is. Raises ValueError
    for shapes with no valid tiling (`two_source_refusal` says so
    beforehand)."""
    b, s, h, d = q.shape
    per = window // chunk
    blocks = two_source_blocks(h, window, per, block_q, block_k)
    if (blocks is None or s % window or window % chunk or d % LANES
            or kbar.shape != (b, s // chunk, h, d)):
        raise ValueError(
            f"q {q.shape} and summaries {kbar.shape} in windows of {window} "
            f"rows and chunks of {chunk} (`two_source_refusal` says so "
            f"beforehand)")
    block_q, block_k, block_s, group = blocks
    where = dict(block_q=block_q, block_k=block_k, block_s=block_s,
                 window=window, per=per)
    n_q = s // block_q
    n_steps = max(own + behind for _, own, behind in (
        _visits(qi, **where) for qi in range(n_q)))
    if q_len is None:
        q_len = jnp.full((b,), s, jnp.int32)

    def q_block(bi, qi, qlen):
        """The query block fetched at step (bi, ., qi): past the prompt, the
        last block that holds any of it (already in VMEM: no fetch)."""
        return jnp.minimum(qi, jnp.maximum(qlen[bi] - 1, 0) // block_q)

    def q_index(bi, hi, qi, kj, qlen):
        return (bi, q_block(bi, qi, qlen), hi)

    def o_index(bi, hi, qi, kj, qlen):
        return (bi, qi, hi)

    def own_index(bi, hi, qi, kj, qlen):
        at = q_block(bi, qi, qlen)
        first, own, _ = _visits(at, **where)
        # past the block's diagonal, and in a skipped query block, stay
        # where the last fetch was
        return (bi, first + jnp.where(qi > at, own - 1,
                                      jnp.minimum(kj, own - 1)), hi)

    def summary_index(bi, hi, qi, kj, qlen):
        at = q_block(bi, qi, qlen)
        _, own, behind = _visits(at, **where)
        last = jnp.maximum(behind - 1, 0)
        # while the own window's blocks pass, where the query block before
        # this one left it; past the last summary, and in a skipped query
        # block, where the last fetch was
        before = jnp.maximum(
            _visits(jnp.maximum(at - 1, 0), **where)[2] - 1, 0)
        return (bi, jnp.where(qi > at, last, jnp.where(
            kj < own, before, jnp.minimum(kj - own, last))), hi)

    rows = lambda block, index: pl.BlockSpec(  # noqa: E731
        (None, block, group * d), index)
    kernel = functools.partial(
        _two_source_kernel, scale=d ** -0.5, group=group, d=d,
        n_steps=n_steps, where=where)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, s, h * d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // group, n_q, n_steps),
            in_specs=[rows(block_q, q_index),
                      rows(block_k, own_index), rows(block_k, own_index),
                      rows(block_s, summary_index),
                      rows(block_s, summary_index)],
            out_specs=rows(block_q, o_index),
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
    )(q_len.astype(jnp.int32), q.reshape(b, s, h * d),
      k.reshape(b, s, h * d), v.reshape(b, s, h * d),
      kbar.reshape(b, s // chunk, h * d), vbar.reshape(b, s // chunk, h * d))
    return out.reshape(b, s, h, d)
