"""EVA attention as EvaByte ships it (Zheng et al., ICLR 2023; ISSUE 49
section 1): a query attends EXACTLY to the positions of its own window, an
aligned block of `eva_window` positions that starts over, and to ONE summary
key and value for every chunk of `eva_chunk` positions of every window
before its own, all under one softmax. For a head with its two learned
vectors `mu` and `phi` and a chunk c of rotated keys k_j and values v_j:

    kbar_c = sum_j softmax_j(k_j . mu)  k_j      float32, no 1/sqrt(d)
    vbar_c = sum_j softmax_j(k_j . phi) v_j
    query t in window w = t // W sees  { j : W w <= j <= t }  exactly and
    { c : c < (W / C) w }  as (kbar_c, vbar_c); scores q.k / sqrt(d) alike.

A chunk is seen from the end of its WINDOW on, never from the end of the
chunk. Within the first window the layer is plain causal attention.

The cache, a slot a layer: K and V of the current window, `[slots, W, H, D]`,
position p in row p mod W, rows `[0, p mod W]` visible to the step at p and
no other (what a previous window or an earlier request left above is never
seen); and `kbar`, `vbar`, `[slots, max_seq / C, H, D]`, chunk c in row c,
rows `[0, (W / C) (p // W))` visible: leaves of two kinds in one layer
(`TransformerConfig.cache_kind_of`). C divides W, so an unfinished chunk lies
whole in the window leaf, and the step that finishes chunk c (p mod C == C -
1) makes its summary from the window's last C rows and writes it, always
before the position from which it is seen.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.layers import rope
from ray_tpu.ops import dot_product_attention
from ray_tpu.ops.attention import SCORE_TILE_BYTES
from ray_tpu.ops.decode_attention import two_leaf_decode_attention


def summaries(k, v, mu, phi, chunk: int):
    """k, v [B, n x chunk, H, D] (keys rotated) -> kbar, vbar [B, n, H, D]
    float32: each chunk's keys pooled under softmax_j(k_j . mu) and its
    values under softmax_j(k_j . phi). Elementwise float32 throughout (no
    matrix unit: a product there rounds its operands)."""
    b, s, h, d = k.shape
    k = k.astype(jnp.float32).reshape(b, s // chunk, chunk, h, d)
    v = v.astype(jnp.float32).reshape(b, s // chunk, chunk, h, d)

    def pool(w, rows):
        a = jax.nn.softmax(jnp.sum(k * w.astype(jnp.float32), -1), axis=2)
        return jnp.sum(a[..., None] * rows, axis=2)

    with jax.named_scope("eva_summaries"):
        return pool(mu, k), pool(phi, v)


def eva_sequence(q, k, v, kbar, vbar, window: int, chunk: int):
    """Attention of a call over ITS OWN rows, positions 0..S-1 in order, S
    a multiple of `window`: q, k, v [B, S, H, D], kbar and vbar [B, S /
    chunk, H, D] -> [B, S, H, D]. Queries go in tiles of one shape under one
    loop (`prefill_attention`'s manner): a tile reads its own window's rows
    and every summary, and the mask keeps the window's rows up to the query
    and the summaries of the chunks of earlier windows."""
    b, s, h, d = q.shape
    dtype, per, n_sum = q.dtype, window // chunk, kbar.shape[1]
    tile = window
    while tile > 8 and b * h * tile * (window + n_sum) * 4 > SCORE_TILE_BYTES:
        tile //= 2

    def one_tile(_, start):
        w = start // window
        cut = lambda t, at, n: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            t, at, n, axis=1)
        qt, kw, vw = cut(q, start, tile), cut(k, w * window, window), cut(
            v, w * window, window)
        own = jnp.einsum("bshd,bthd->bhst", qt, kw,
                         preferred_element_type=jnp.float32)
        past = jnp.einsum("bshd,bchd->bhsc", qt, kbar,
                          preferred_element_type=jnp.float32)
        i = start + jnp.arange(tile)
        seen = jnp.concatenate(
            [w * window + jnp.arange(window)[None, :] <= i[:, None],
             jnp.broadcast_to(jnp.arange(n_sum)[None, :] < per * w,
                              (tile, n_sum))], axis=1)
        scores = jnp.concatenate([own, past], axis=-1) / (d ** 0.5)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                               axis=-1).astype(dtype)
        return None, (
            jnp.einsum("bhst,bthd->bshd", probs[..., :window], vw)
            + jnp.einsum("bhsc,bchd->bshd", probs[..., window:], vbar))

    _, outs = jax.lax.scan(one_tile, None, jnp.arange(0, s, tile))
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h, d).astype(dtype)


class EVA(nn.Module):
    cfg: "TransformerConfig"  # noqa: F821 - models/transformer.py

    @nn.compact
    def __call__(self, x, positions, decode: bool = False,
                 bounded: bool = False, prompt_len=None, live=None):
        cfg = self.cfg
        h, d = cfg.n_heads, cfg.head_dim
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            (h, d), axis=-1, use_bias=False, name=name, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype)
        q, k, v = dense("wq")(x), dense("wk")(x), dense("wv")(x)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        # normal, cut to [-1, 1] standard deviations, times d^-0.5: the
        # scale the pooling's scores have none of (ISSUE 49 section 1)
        pool = nn.initializers.truncated_normal(
            cfg.eva_pool_std * d ** -0.5, lower=-1.0, upper=1.0)
        mu = self.param("mu", pool, (h, d), cfg.param_dtype)
        phi = self.param("phi", pool, (h, d), cfg.param_dtype)
        with jax.named_scope("eva_attention"):
            if decode and x.shape[1] == 1:
                out = self._step(q, k, v, mu, phi, positions, bounded, live)
            else:
                out, rows = self._sequence(q, k, v, mu, phi, prompt_len)
                if decode:
                    self._hand_on(*rows, prompt_len, x.shape[1])
        return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False,
                               name="wo", dtype=cfg.dtype,
                               param_dtype=cfg.param_dtype)(out)

    def _leaves(self, b: int):
        """The layer's four cache leaves for `b` slots: the window's K and V
        and the summaries, in the model's dtype."""
        cfg = self.cfg
        leaf = lambda name, rows: self.variable(  # noqa: E731
            "cache", name, lambda: jnp.zeros(
                (b, rows, cfg.n_heads, cfg.head_dim), cfg.dtype))
        window = min(cfg.eva_window, cfg.max_seq)
        chunks = max(1, cfg.max_seq // cfg.eva_chunk)
        return (leaf("k", window), leaf("v", window), leaf("kbar", chunks),
                leaf("vbar", chunks))

    def _sequence(self, q, k, v, mu, phi, q_len=None):
        """A whole call from position 0 (a training batch, a prefill):
        its output, and (k, v, kbar, vbar) as the cache would hold them (a
        call longer than a window padded to whole windows). Rows at or past
        `q_len` ([B]) are read by nobody."""
        cfg = self.cfg
        window, chunk = cfg.eva_window, cfg.eva_chunk
        s = q.shape[1]
        k, v = k.astype(cfg.dtype), v.astype(cfg.dtype)
        if s > window and s % window:
            tail = ((0, 0), (0, -s % window), (0, 0), (0, 0))
            q, k, v = jnp.pad(q, tail), jnp.pad(k, tail), jnp.pad(v, tail)
        whole = k.shape[1] // chunk * chunk
        kbar, vbar = (t.astype(cfg.dtype) for t in summaries(
            k[:, :whole], v[:, :whole], mu, phi, chunk))
        if s <= window:  # the first window: plain causal attention
            out = dot_product_attention(q, k, v, causal=True, q_len=q_len)
        else:
            with jax.named_scope("eva_window"):
                out = eva_attention(q, k, v, kbar, vbar, window, q_len)[:, :s]
        return out.astype(cfg.dtype), (k, v, kbar, vbar)

    def _hand_on(self, k, v, kbar, vbar, prompt_len, s: int):
        """What a prefill of `s` rows whose prompts end at `prompt_len` ([B],
        traced; `s` when None) leaves in the cache, every leaf from its row 0:
        the window that holds position `prompt_len - 1`, its rows at their
        places, and the summaries of the chunks that lie wholly before
        `prompt_len`, zeros after them: a padded position enters no
        summary, and the rows it leaves in the window are above every row a
        step sees before writing it."""
        cfg = self.cfg
        b = k.shape[0]
        ck, cv, cbk, cbv = self._leaves(b)
        plen = (jnp.full((b,), s, jnp.int32) if prompt_len is None
                else prompt_len.astype(jnp.int32))
        window = ck.value.shape[1]
        if k.shape[1] > window:
            at = (plen - 1) // window * window
            cut = jax.vmap(lambda t, a: jax.lax.dynamic_slice_in_dim(
                t, a, window, axis=0))
            k, v = cut(k, at), cut(v, at)
        done = (jnp.arange(kbar.shape[1])[None, :]
                < (plen // cfg.eva_chunk)[:, None])[..., None, None]
        for leaf, rows in ((ck, k), (cv, v), (cbk, jnp.where(done, kbar, 0)),
                           (cbv, jnp.where(done, vbar, 0))):
            leaf.value = jax.lax.dynamic_update_slice(
                leaf.value, rows[:, :leaf.value.shape[1]].astype(cfg.dtype),
                (0, 0, 0, 0))

    def _step(self, q, k, v, mu, phi, positions, bounded, live):
        """One token a slot at its own position p ([B, 1]): its row goes to
        p mod W, one softmax runs over the visible rows of both leaves
        (`ops/decode_attention.py` `two_leaf_decode_attention`: on the chip
        a `bounded` step is one ragged kernel that stops every slot at its
        own row of each leaf, elsewhere two walks and a merge; a slot that
        `live` [B] bool marks free sees none), and where p ends a chunk the
        chunk's summary is made from the window's last C rows and written,
        for the live slots and no other."""
        cfg = self.cfg
        chunk = cfg.eva_chunk
        b = q.shape[0]
        ck, cv, cbk, cbv = self._leaves(b)
        window = ck.value.shape[1]
        pos = positions.astype(jnp.int32)[:, 0]
        slot = jnp.arange(b)
        at = pos % window
        ck.value = ck.value.at[slot, at].set(k[:, 0].astype(cfg.dtype))
        cv.value = cv.value.at[slot, at].set(v[:, 0].astype(cfg.dtype))
        here = jnp.ones((b,), bool) if live is None else live
        out = two_leaf_decode_attention(
            q[:, 0], (ck.value, cv.value), (cbk.value, cbv.value),
            jnp.where(here, at + 1, 0),
            jnp.where(here, pos // window * (window // chunk), 0),
            bounded=bounded)
        # the chunk this step finishes, if it does: its C rows end at `at`
        ends = here & (pos % chunk == chunk - 1)
        tail = (jnp.maximum(at - (chunk - 1), 0)[:, None]
                + jnp.arange(chunk)[None, :])
        kbar, vbar = summaries(ck.value[slot[:, None], tail],
                               cv.value[slot[:, None], tail], mu, phi, chunk)
        row = jnp.where(ends, pos // chunk, cbk.value.shape[1])  # or nowhere
        cbk.value = cbk.value.at[slot, row].set(
            kbar[:, 0].astype(cfg.dtype), mode="drop")
        cbv.value = cbv.value.at[slot, row].set(
            vbar[:, 0].astype(cfg.dtype), mode="drop")
        return out[:, None].astype(cfg.dtype)


# (A kernel's program names the line of this file that first traced a jitted
# helper its body reuses, `EVA._step`'s among them: what follows was added
# at the END so that no line above moved. ROADMAP D15.)
import functools  # noqa: E402

from ray_tpu.ops.attention import NOT_ASKED, state_once  # noqa: E402
from ray_tpu.ops.two_source_attention import (  # noqa: E402
    two_source_attention, two_source_refusal)


def eva_attention(q, k, v, kbar, vbar, window: int, q_len=None):
    """`eva_sequence`'s result (the chunk is S over the summaries' rows) by
    the form chosen up front from what can be observed, each choice stated
    once at INFO (`ops/two_source_attention.py` `two_source_refusal` is the
    rule): on a TPU, with no mesh of several devices in context, heads of
    whole lane tiles and a window that holds whole 128-row blocks of
    summaries, ONE Pallas call that keeps its scores on the chip
    (`two_source_attention`; rows at or past `q_len` ([B]) are read by
    nobody, so it skips them); under differentiation the tile scan, for the
    forward and the backward pass (the kernel has no VJP); everywhere else
    the tile scan."""
    chunk = q.shape[1] // kbar.shape[1]
    reason = two_source_refusal(q.shape, window, chunk)
    if reason is None:
        return _kernel_or_xla_grad(q, k, v, kbar, vbar, q_len, window, chunk)
    if reason != NOT_ASKED:
        state_once(f"eva attention past a window: XLA tile scan ({reason})")
    return eva_sequence(q, k, v, kbar, vbar, window, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernel_or_xla_grad(q, k, v, kbar, vbar, q_len, window, chunk):
    state_once("eva attention past a window: Pallas two-source kernel")
    return two_source_attention(q, k, v, kbar, vbar, window=window,
                                chunk=chunk, q_len=q_len)


def _kernel_or_xla_grad_fwd(q, k, v, kbar, vbar, q_len, window, chunk):
    state_once("eva attention past a window: XLA tile scan under "
               "differentiation (the two-source kernel has no VJP)")
    return jax.vjp(functools.partial(eva_sequence, window=window,
                                     chunk=chunk), q, k, v, kbar, vbar)


def _kernel_or_xla_grad_bwd(window, chunk, vjp, g):
    return (*vjp(g), None)


_kernel_or_xla_grad.defvjp(_kernel_or_xla_grad_fwd, _kernel_or_xla_grad_bwd)
