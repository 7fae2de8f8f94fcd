"""The expert layer's decode step: the held experts that got a row.

`models/moe.py`'s dense arm multiplies every row of a decode step by every
held expert, the unselected rows weighted 0, so a step reads every held
expert whatever was routed. With a few rows a step most held experts get
none (Kimi K2's share of a deployment: 8 rows to 12 held experts a layer,
5.7 of them touched; LongCat-Flash's 6.4 of 16), and their terms of the sum
are exactly zero. `occupied_experts` is that sum with those terms left out:
ONE Pallas kernel whose weight reads follow the experts that got a row. ALL
rows go through each touched expert (a row it did not select has gate 0
there), so nothing is gathered, no row is dropped and there is no capacity.

The kernel keeps the dense arm's rounding, which is a contract and not a
loss: a served share of a model routes on near-ties (margins of 1e-4 under
an error of 1e-2 a layer), so an arm that rounds elsewhere, even one closer
to float32, serves other tokens than the CPU's arm and a `tp` mesh's do
(PERF.md section 6, PR 47). The sequence is what the TPU's compiler makes
of the dense arm's five lines, settled on the chip bit by bit: only what
its program writes to memory or feeds to the matrix unit is rounded to the
operands' dtype, whatever the types of its optimised text say. That is `x
W_gate` (a fusion's result); `silu` of it (with the approximate reciprocal
its fusion takes) times the float32 `x W_up`, rounded once (the next
product's operand); each row's gate; and the result. An expert's `(...)
W_down` stays float32, is weighed by the ROUNDED gate and summed over the
experts in float32, and the sum is rounded once. bf16 operands, every sum
float32 and in the dense arm's order: a contraction one pass of the matrix
unit after another (`_passes`), the experts ascending. On the chip the two
arms' results are the same bits (`chip_smoke.py` holds them to that).

`occupied_refusal` is the rule, asked in one place, the dense branch of
`MoE.__call__`, and it decides from what the call can see: the chip, no
mesh of several devices, a decode step of one position a slot, blocks and
rows of whole tiles. Every expert layer's decode step on the chip passes it
(Kimi K2, Trinity-Mini, Kimi Linear, LongCat-Flash); every layer sows the
held experts it touched and those its arm read (`zero_counts`), which the
step rooflines count. The dense arm stays for training and `ep` sharding
(the only arm that differentiates), for the CPU, and for a `tp` mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention as rule
from ray_tpu.ops.flash_attention import LANES, auto_block

#: Most bytes of one block of an expert's matrix (whole contiguous rows of
#: it): the only place a block's size comes from. 2, 4 and 8 MiB stream
#: alike (0.617, 0.621, 0.625 ms for 6 of LongCat's experts: the first
#: block's fetch is the part nothing hides; PERF.md section 6, PR 43).
BLOCK_BYTES = 2 << 20


def expert_blocks(d: int, f: int, dtype) -> tuple[int, int] | None:
    """(rows of a block of `w_gate` / `w_up` `[d, f]`, rows of a block of
    `w_down` `[f, d]`): the most whole rows within `BLOCK_BYTES` that divide
    the matrix and are whole lane tiles (a block's rows are the lanes of the
    operand that meets it). None: no such divisor."""
    most = BLOCK_BYTES // jnp.dtype(dtype).itemsize  # values a block
    got = auto_block(d, most // f, LANES), auto_block(f, most // d, LANES)
    return got if all(got) else None


def occupied_refusal(x_shape, d_ff: int, *, serving: bool,
                     dtype=jnp.bfloat16) -> str | None:
    """Why the expert layer's call over x `[B, S, D]` with experts of
    `d_ff` takes the dense arm, or None when it takes `occupied_experts`."""
    if not rule.on_tpu():
        return rule.NOT_ASKED
    if (reason := rule.mesh_refusal()) is not None:
        return reason
    b, s, d = x_shape
    if not serving or s != 1:
        return "not a decode step of one position a slot"
    if expert_blocks(d, d_ff, dtype) is None:
        return (f"experts of {d} x {d_ff} have no blocks of whole lane "
                f"tiles ({LANES})")
    if b % (32 // jnp.dtype(dtype).itemsize):
        return f"{b} rows are no whole sublane tiles of {jnp.dtype(dtype)}"
    return None


def touched_first(rows_here):
    """`(order, n_touched)` of the rows each held expert got, `[held]`
    int: the held experts with at least one row first, each group in its
    own order, and how many those are. On the device, no sort."""
    held = rows_here.shape[0]
    k = jnp.arange(held, dtype=jnp.int32)
    got = rows_here > 0
    n = jnp.sum(got, dtype=jnp.int32)
    before = k[None, :] < k[:, None]  # [expert, those before it]
    place = jnp.where(
        got, jnp.sum(before & got[None, :], axis=1, dtype=jnp.int32),
        n + jnp.sum(before & ~got[None, :], axis=1, dtype=jnp.int32))
    order = jnp.sum(jnp.where(place[None, :] == k[:, None], k[None, :], 0),
                    axis=1, dtype=jnp.int32)
    return order, n


def _passes(k: int):
    """The contraction's `k` rows, one pass of the matrix unit (`LANES` of
    them) at a time: a product added pass by pass is the float32 sum in the
    order the compiler's own product makes it, to the bit; a block's product
    added whole is not (PERF.md section 6, PR 47)."""
    return [pl.ds(at, LANES) for at in range(0, k, LANES)]


def _occupied_kernel(order_ref, n_ref, x_ref, g_ref, wg_ref, wu_ref, wd_ref,
                     o_ref, gate_ref, up_ref, h_ref, out_ref, sum_ref, *,
                     ta: int, tb: int):
    """One entry of the grid: step `t` of place `i`'s expert. The first
    `ta` steps add a block of rows of `w_gate` and `w_up` (wg_ref, wu_ref
    [td, F]) against the same columns of the rows (x_ref [N, td]) to the
    float32 sums gate_ref, up_ref [N, F]; the last of them makes `silu` of
    the ROUNDED gate times up and keeps it in the operands' dtype as h_ref
    [tb, N, tf]; the next `tb` steps add h's columns against a block of rows
    of `w_down` (wd_ref [tf, D]) to this expert's output, out_ref [N, D]
    float32; the last of those weighs it by the rows' gates for this expert
    (its column of g_ref [N, held], picked by a mask and rounded) and adds
    it to sum_ref [N, D] float32, whose rounding is the result, o_ref [N,
    D]. Each rounding is one the dense arm's program makes, and it makes no
    other (the module's docstring)."""
    p = pl.program_id(0)
    i, t = p // (ta + tb), p % (ta + tb)
    dtype = o_ref.dtype

    def rounded(v):
        return v.astype(dtype).astype(jnp.float32)

    @pl.when(p == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])  # false only in the one place of an idle step
    def _expert():
        @pl.when(t == 0)
        def _start():
            gate_ref[...] = jnp.zeros_like(gate_ref)
            up_ref[...] = jnp.zeros_like(up_ref)

        @pl.when(t < ta)
        def _gate_up():
            for rows in _passes(x_ref.shape[1]):
                x = x_ref[:, rows]
                gate_ref[...] += jnp.dot(x, wg_ref[rows, :],
                                         preferred_element_type=jnp.float32)
                up_ref[...] += jnp.dot(x, wu_ref[rows, :],
                                       preferred_element_type=jnp.float32)

        @pl.when(t == ta - 1)
        def _hidden():
            gate = rounded(gate_ref[...])
            # (silu as the dense arm's fusion makes it: the EUP's reciprocal)
            h = (gate * pl.reciprocal(1.0 + jnp.exp(-gate), approx=True)
                 * up_ref[...]).astype(dtype)
            tf = h_ref.shape[2]
            for k in range(tb):
                h_ref[k] = h[:, k * tf:(k + 1) * tf]
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(t >= ta)
        def _down():
            for rows in _passes(wd_ref.shape[0]):
                out_ref[...] += jnp.dot(h_ref[t - ta, :, rows],
                                        wd_ref[rows, :],
                                        preferred_element_type=jnp.float32)

        @pl.when(t == ta + tb - 1)
        def _weigh():
            lane = jax.lax.broadcasted_iota(jnp.int32, g_ref.shape, 1)
            g = jnp.sum(jnp.where(lane == order_ref[i], g_ref[...], 0.0),
                        axis=1, keepdims=True)
            sum_ref[...] += out_ref[...] * rounded(g)

            @pl.when(i == n_ref[0] - 1)
            def _result():
                o_ref[...] = sum_ref[...].astype(dtype)


def occupied_experts(x, gates, rows_here, w_gate, w_up, w_down, *,
                     interpret: bool = False):
    """sum over the held experts e with `rows_here[e]` > 0 of
    `gates[:, e] * (silu(x W_gate[e]) * (x W_up[e])) W_down[e]`: the dense
    arm's sum without its zero terms, rounded where the dense arm rounds.
    x [N, D]; gates [N, held] float32, 0 where a row did not select the
    expert; rows_here [held] int, the rows each held expert got; w_gate,
    w_up [held, D, F], w_down [held, F, D], read where they lie. Returns
    `(y [N, D] in x's dtype, n_touched)`, the second the held experts whose
    weights the call read.

    The grid is the touched experts' places, one after another (its
    length a traced scalar: one program whatever it is), times the blocks of
    one expert's matrices, whole contiguous rows each: the weight operands'
    index maps take expert `order[place]` from a prefetched list, so an
    expert without a row is never named and nothing of it is fetched. While
    `w_gate` and `w_up` stream, `w_down`'s map holds the block the expert
    before ended on, and the other way round, so every step's fetch is the
    next step's operand and no more. A step in which NO held expert got a
    row is a grid of one place whose arithmetic is skipped: zeros."""
    n, d = x.shape
    held, _, f = w_gate.shape
    blocks = expert_blocks(d, f, x.dtype)
    if blocks is None or w_down.shape != (held, f, d):
        raise ValueError(f"rows {x.shape} against experts {w_gate.shape}, "
                         f"{w_down.shape} (`occupied_refusal` says so "
                         f"beforehand)")
    order, n_touched = touched_first(rows_here)
    y = _occupied(order, n_touched[None], x, gates.astype(jnp.float32),
                  w_gate, w_up, w_down, blocks=blocks, interpret=interpret)
    return y, n_touched


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _occupied(order, n_touched, x, gates, w_gate, w_up, w_down, *,
              blocks: tuple[int, int], interpret: bool):
    """`occupied_experts` in blocks of `blocks` rows (`expert_blocks`',
    static: a model's expert layers share one trace)."""
    n, d = x.shape
    held, _, f = w_gate.shape
    td, tf = blocks
    ta, tb = d // td, f // tf
    steps = ta + tb

    def gate_up_at(p, order, _n):
        return order[p // steps], jnp.minimum(p % steps, ta - 1), 0

    def down_at(p, order, _n):
        i, t = p // steps, p % steps
        here = t >= ta
        return (jnp.where(here, order[i], order[jnp.maximum(i - 1, 0)]),
                jnp.where(here, t - ta, jnp.where(i > 0, tb - 1, 0)), 0)

    return pl.pallas_call(
        functools.partial(_occupied_kernel, ta=ta, tb=tb),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(jnp.maximum(n_touched[0], 1) * steps,),
            in_specs=[
                pl.BlockSpec((n, td), lambda p, *_: (
                    0, jnp.minimum(p % steps, ta - 1))),
                pl.BlockSpec((n, held), lambda p, *_: (0, 0)),
                pl.BlockSpec((None, td, f), gate_up_at),
                pl.BlockSpec((None, td, f), gate_up_at),
                pl.BlockSpec((None, tf, d), down_at)],
            out_specs=pl.BlockSpec((n, d), lambda p, *_: (0, 0)),
            scratch_shapes=[
                pltpu.VMEM((n, f), jnp.float32),  # x W_gate so far
                pltpu.VMEM((n, f), jnp.float32),  # x W_up so far
                pltpu.VMEM((tb, n, tf), x.dtype),  # the hidden rows
                pltpu.VMEM((n, d), jnp.float32),  # this expert's output
                pltpu.VMEM((n, d), jnp.float32),  # the weighted sum so far
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        name="occupied_experts",
        interpret=interpret,
    )(order, n_touched, x, gates, w_gate, w_up, w_down)
