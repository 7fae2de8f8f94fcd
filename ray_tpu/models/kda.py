"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692): a gated
delta-rule linear attention. A layer keeps, per sequence, no rows per
position but ONE state `S` `[heads, dk, dv]` (float32) that every token
decays, channel by channel, and corrects by the delta rule, and the last
`kernel - 1` inputs of the short depthwise convolution that filters q, k and v.

    q~, k~, v~ = a W_q, a W_k, a W_v
    q^, k^, v^ = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~))     causal, depthwise
    q = q^ / sqrt(|q^|^2 + 1e-6) * dk^-1/2;   k = k^ / sqrt(|k^|^2 + 1e-6);   v = v^
    g = -exp(A_log) * softplus(f_b(f_a(a)) + dt_bias)               [heads, dk], float32
    beta = sigmoid(a W_beta)                                        [heads], float32
    S <- Diag(exp(g_t)) S;   S <- S + beta_t k_t (v_t - S^T k_t)^T;   o_t = S^T q_t
    y = (RMSNorm_head(o_t) * sigmoid(g_b(g_a(a_t)))) W_o

Two forms compute the same thing:

- the recurrence, one token at a time (a decode step): the four lines above
  on `[slots, heads, dk, dv]`, elementwise and by sums in float32, no loop.
  A step would read S twice (the sums over dk need the decayed state before
  the correction can be added to it) and write it once; so the state leaf
  lies ONE correction behind: beside it the layer keeps the last token's
  (alpha, k, u) (`pending`), and the step that reads S for this token's
  sums applies that correction in the same pass, which XLA makes one fusion
  on the v5e: one read and one write of S a step (`step`);
- the chunkwise form (`chunk_scan`: a prefill, training): one `lax.scan`
  over chunks of `kda_chunk` positions; inside a chunk every position's
  correction `u_t = beta_t (v_t - (decayed S)^T k_t)` is the solution of ONE
  unit lower triangular system, and the outputs and the chunk's last state
  are matrix products.

A position at or past `prompt_len` (a prefill padded to its bucket) takes
part with g = 0 and beta = 0: the state passes through it unchanged, so the
state handed on is the one after the prompt's last token, and the
convolution's tail handed on is the inputs at `prompt_len - 3 ..
prompt_len - 1`. A state has no rows above a position to hide behind: what a
slot's next occupant starts from is its own prefill's state, whole
(`llm/engine.py` `place`).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.layers import RMSNorm

#: Positions of a sub-block of a chunk. Inside one, the decays between two
#: positions are formed pair by pair (`[sub, sub, dk]` a head); between
#: sub-blocks they factor through the sub-block's first position, so that
#: both factors are <= 1 and the products are matrix products.
SUB_CHUNK = 16

_HIGHEST = jax.lax.Precision.HIGHEST


def _decay_init(key, shape, dtype):
    """A_log: the log of a uniform draw in [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    """dt_bias such that softplus(dt_bias) is log-uniform in [0.001, 0.1]."""
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1


class KDA(nn.Module):
    cfg: "TransformerConfig"  # noqa: F821 - models/transformer.py

    @nn.compact
    def __call__(self, x, decode: bool = False, prompt_len=None):
        cfg = self.cfg
        heads, dk, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
        b, s = x.shape[0], x.shape[1]
        chans = heads * dk
        f32 = jnp.float32
        dense = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, name=name,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        with jax.named_scope("kda_attention"):
            # What the filters see: q~ | k~ | v~, 3 x heads x dk channels.
            pre = jnp.concatenate(
                [dense((heads, dk), name)(x).reshape(b, s, chans)
                 for name in ("wq", "wk", "wv")], axis=-1)
            filt = jnp.concatenate(
                [self.param(name, nn.initializers.normal(0.5),
                            (taps, heads, dk), cfg.param_dtype
                            ).reshape(taps, chans)
                 for name in ("conv_q", "conv_k", "conv_v")],
                axis=-1).astype(f32)
            with jax.named_scope("kda_gate"):
                a_log = self.param("A_log", _decay_init, (heads,),
                                   cfg.param_dtype).astype(f32)
                dt_bias = self.param("dt_bias", _dt_bias_init, (heads, dk),
                                     cfg.param_dtype).astype(f32)
                f = dense((heads, dk), "f_b")(dense(dk, "f_a")(x))
                g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                    f.astype(f32) + dt_bias)  # [B, S, heads, dk]
                beta = jax.nn.sigmoid(dense(heads, "w_beta")(x).astype(f32))
            if decode:
                state = self.variable("cache", "state", lambda: jnp.zeros(
                    (b, heads, dk, dk), f32))
                tail = self.variable("cache", "conv", lambda: jnp.zeros(
                    (b, taps - 1, 3 * chans), cfg.dtype))
                # (three rows of heads x dk values a slot, as the tail is
                # three rows of inputs: the layout the v5e's compiler gives
                # such a leaf by default, slots in the tiles' sublanes, is
                # the one its decode loop computes in)
                pending = self.variable("cache", "pending", lambda: jnp.zeros(
                    (b, 3, chans), f32))
            with jax.named_scope("kda_conv"):
                if decode and s == 1:
                    seen = jnp.concatenate(
                        [tail.value, pre.astype(cfg.dtype)], axis=1)
                    tail.value = seen[:, 1:]
                    mixed = jnp.einsum("btc,tc->bc", seen.astype(f32),
                                       filt)[:, None]
                else:
                    padded = jnp.pad(pre.astype(cfg.dtype),
                                     ((0, 0), (taps - 1, 0), (0, 0)))
                    mixed = sum(filt[j] * padded[:, j:j + s].astype(f32)
                                for j in range(taps))
                    if decode:
                        plen = (jnp.full((b,), s, jnp.int32)
                                if prompt_len is None
                                else prompt_len.astype(jnp.int32))
                        # padded[plen : plen + taps - 1] are the inputs at
                        # plen - (taps - 1) .. plen - 1, zeros before 0
                        tail.value = jax.vmap(
                            lambda row, at: jax.lax.dynamic_slice_in_dim(
                                row, at, taps - 1, axis=0))(padded, plen)
                mixed = jax.nn.silu(mixed).reshape(b, s, 3, heads, dk)
                q, k, v = mixed[:, :, 0], mixed[:, :, 1], mixed[:, :, 2]
                unit = lambda t: t * jax.lax.rsqrt(  # noqa: E731
                    jnp.sum(t * t, -1, keepdims=True) + 1e-6)
                q, k = unit(q) * dk ** -0.5, unit(k)
            if decode and s == 1:
                with jax.named_scope("kda_recurrence"):
                    o, state.value, last = step(
                        q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                        state.value, pending.value.reshape(b, 3, heads, dk))
                    pending.value = last.reshape(b, 3, chans)
                    o = o[:, None]
            else:
                if prompt_len is not None:
                    live = (jnp.arange(s)[None, :]
                            < prompt_len.astype(jnp.int32)[:, None])
                    g = jnp.where(live[:, :, None, None], g, 0.0)
                    beta = jnp.where(live[:, :, None], beta, 0.0)
                with jax.named_scope("kda_chunk_scan"):
                    o, last = chunk_scan(
                        q, k, v, g, beta, jnp.zeros((b, heads, dk, dk), f32),
                        chunk=cfg.kda_chunk)
                if decode:  # nothing pending: (alpha, k, u) = (1, 0, 0)
                    state.value = last
                    pending.value = jnp.zeros((b, 3, chans), f32).at[
                        :, 0].set(1.0)
            with jax.named_scope("kda_out_gate"):
                gate = jax.nn.sigmoid(
                    dense((heads, dk), "g_b")(dense(dk, "g_a")(x)).astype(f32))
                o = RMSNorm(cfg.norm_eps, name="o_norm")(o) * gate
            return nn.DenseGeneral(
                cfg.d_model, axis=(-2, -1), use_bias=False, name="wo",
                dtype=cfg.dtype, param_dtype=cfg.param_dtype)(
                    o.astype(cfg.dtype))


def step(q, k, v, g, beta, state, pending):
    """One token of every sequence: q, k, g `[B, heads, dk]`, v `[B, heads,
    dv]`, beta `[B, heads]`, all float32; `state` `[B, heads, dk, dv]` is S
    as it was BEFORE the last token's correction, `pending` `[B, 3, heads,
    dk]` that token's (alpha, k, u), so that the state after it is `alpha S
    + k u^T` (dk = dv). Returns (o `[B, heads, dv]`, the state after the
    last token, this token's (alpha, k, u)).

    The state is read once and written once: the pass that applies the last
    correction also takes this token's two sums over dk of the DECAYED
    state, and the output of the corrected state is that of the decayed one
    plus the correction's own part, `S'^T q = (alpha S)^T q + (k . q) u`."""
    now = settled(state, pending)
    alpha = jnp.exp(g)
    decayed = now * alpha[..., None]
    s_k = jnp.sum(decayed * k[..., None], axis=-2)
    s_q = jnp.sum(decayed * q[..., None], axis=-2)
    u = beta[..., None] * (v - s_k)
    o = s_q + jnp.sum(k * q, -1, keepdims=True) * u
    return o, now, jnp.stack([alpha, k, u], axis=1)


def settled(state, pending):
    """The state with its pending correction applied: S after the last
    token."""
    alpha, k, u = pending[:, 0], pending[:, 1], pending[:, 2]
    return alpha[..., None] * state + k[..., None] * u[..., None, :]


def _inverse_unit_lower(strict):
    """(I + N)^-1 for N strictly lower triangular `[..., C, C]` (nilpotent:
    N^C = 0), as the product (I - N)(I + N^2)(I + N^4)...: log2(C) matrix
    products at full precision, no substitution position by position."""
    c = strict.shape[-1]
    inv = jnp.eye(c, dtype=strict.dtype) - strict
    power = strict
    for _ in range(max(0, (c - 1).bit_length() - 1)):
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inv = inv + jnp.matmul(inv, power, precision=_HIGHEST)
    return inv


def chunk_scan(q, k, v, g, beta, state, chunk: int = 64,
               sub: int = SUB_CHUNK):
    """The recurrence of `step` over S positions at once. q, k, g `[B, S,
    heads, dk]`, v `[B, S, heads, dv]`, beta `[B, S, heads]`, state `[B,
    heads, dk, dv]`: float32. Returns (o `[B, S, heads, dv]`, the state
    after position S - 1).

    Per chunk of C positions from the state S_0 at its start, G the running
    sum of g inside the chunk (the decay from i to t is exp(G_t - G_i) <= 1):

        A[t, i] = sum_d k[t, d] k[i, d] exp(G[t, d] - G[i, d])     i <  t
        P[t, i] = sum_d q[t, d] k[i, d] exp(G[t, d] - G[i, d])     i <= t
        U   = (I + Diag(beta) A)^-1 Diag(beta) (V - (K * exp(G)) S_0)
              (unit lower triangular: its inverse is a product of log2(C)
              matrices, `_inverse_unit_lower`)
        O   = (Q * exp(G)) S_0 + P U
        S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

    Differences of G are taken BEFORE the exponential, never exp(-G_i) alone
    (it overflows under a strong decay). Between sub-blocks of `sub`
    positions the decay factors through the later sub-block's first position
    R: exp(G_t - R) exp(R - G_i), both <= 1, so A and P there are matrix
    products; inside a sub-block they are formed pair by pair. S not a
    multiple of C is padded by positions that change nothing (g = 0,
    beta = 0)."""
    b, s, heads, dk = q.shape
    sub = sub if chunk % sub == 0 else chunk
    pad = -s % chunk
    n = (s + pad) // chunk

    def chunks(t):  # [B, S, heads, ...] -> [n, B, heads, C, ...]
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape((b, n, chunk) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 2, 3)

    blocks = chunk // sub
    early = (jnp.arange(chunk)[None, :]
             < (jnp.arange(blocks) * sub)[:, None])[..., None]  # [n_s, C, 1]
    at_or_before = (jnp.arange(sub)[:, None]
                    >= jnp.arange(sub)[None, :])  # i <= t inside a sub-block
    same_block = jnp.eye(blocks, dtype=jnp.float32)[:, None, :, None]

    def one(s0, xs):
        qc, kc, vc, gc, bc = xs  # [B, heads, C, ...]
        big = jnp.cumsum(gc, axis=2)  # G
        shape5 = (b, heads, blocks, sub, dk)
        g5, k5, q5 = big.reshape(shape5), kc.reshape(shape5), qc.reshape(shape5)
        # R: G just before each sub-block's first position
        ref = g5[:, :, :, 0] - gc.reshape(shape5)[:, :, :, 0]  # [B,h,n_s,dk]
        into = jnp.exp(g5 - ref[:, :, :, None])  # decay from R to t, <= 1
        back = ref[:, :, :, None] - big[:, :, None]  # R_s - G_i
        k_early = jnp.where(
            early, kc[:, :, None] * jnp.exp(jnp.where(early, back, 0.0)), 0.0)
        a_off = jnp.einsum("bhstd,bhsid->bhsti", k5 * into, k_early)
        p_off = jnp.einsum("bhstd,bhsid->bhsti", q5 * into, k_early)
        # inside a sub-block, pair by pair: [.., t, i, dk]
        gap = g5[:, :, :, :, None] - g5[:, :, :, None, :]
        keep = at_or_before[..., None]
        k_near = k5[:, :, :, None] * jnp.where(
            keep, jnp.exp(jnp.where(keep, gap, 0.0)), 0.0)
        a_in = jnp.sum(k5[:, :, :, :, None] * k_near, -1) * (
            jnp.arange(sub)[:, None] > jnp.arange(sub)[None, :])
        p_in = jnp.sum(q5[:, :, :, :, None] * k_near, -1)
        whole = lambda off, near: (  # noqa: E731
            off.reshape(b, heads, blocks, sub, blocks, sub)
            + near[:, :, :, :, None, :] * same_block
        ).reshape(b, heads, chunk, chunk)
        a, p = whole(a_off, a_in), whole(p_off, p_in)
        seen = jnp.einsum("bhcd,bhdv->bhcv", kc * jnp.exp(big), s0,
                          precision=_HIGHEST)
        u = jnp.einsum("bhci,bhiv->bhcv",
                       _inverse_unit_lower(bc[..., None] * a),
                       bc[..., None] * (vc - seen), precision=_HIGHEST)
        o = (jnp.einsum("bhcd,bhdv->bhcv", qc * jnp.exp(big), s0,
                        precision=_HIGHEST)
             + jnp.einsum("bhci,bhiv->bhcv", p, u))
        end = big[:, :, -1:]  # G_C
        s1 = (jnp.exp(end)[:, :, 0, :, None] * s0
              + jnp.einsum("bhcd,bhcv->bhdv", kc * jnp.exp(end - big), u,
                           precision=_HIGHEST))
        return s1, o

    last, o = jax.lax.scan(
        one, state, (chunks(q), chunks(k), chunks(v), chunks(g),
                     chunks(beta)))
    # [n, B, heads, C, dv] -> [B, S, heads, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1).reshape(
        b, n * chunk, heads, -1)
    return o[:, :s], last
