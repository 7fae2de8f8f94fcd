"""Multi-head latent attention (MLA; DeepSeek-V2/V3, Kimi K2).

Keys and values of all heads are linear maps of ONE latent per token: `c_kv`
(`kv_lora_rank` wide, after its own RMSNorm) expands through `wk_b` / `wv_b`
to each head's `k_nope` / `v`, and one rotary key `k_r` (`qk_rope_head_dim`
wide) is shared by all heads. So the cache holds `[c_kv | k_r]`, 576 values a
token a layer at the published sizes, where K and V per head would be 16,384.
A model without a position in its latent attention (`cfg.mla_rope` False: Kimi
Linear's `mla_use_nope`) leaves `k_r` and the query dims facing it unrotated;
`cfg.mla_q_scale`, `mla_kv_scale` (LongCat-Flash) scale q and the normed c_kv.

Two paths compute the same attention:

- expanded (training, and the serving prefill): `k_nope` and `v` are made
  for the rows of this call only, and queries attend over those in tiles, so
  that the float32 scores of a tile stay small.
- latent (a decode step): the query is carried into the latent space,
  `q' = q_nope wk_b` per head, scores are `q' . c_kv + q_r . k_r` against the
  cache as it lies, the weighted sum is taken of `c_kv` itself and only its
  512 values are carried out through `wv_b`. Nothing per head is ever read
  from or written to the cache.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (RMSNorm, rope_cos_sin_scale, rope_interleaved,
                                   rope_inv_freq, yarn_mscale)
from ray_tpu.ops import attention as rule
from ray_tpu.ops.decode_attention import (latent_refusal, over_kv_prefix,
                                          ragged_latent_attention)

#: Most bytes of float32 scores one tile of queries may hold in the expanded
#: path (heads x tile x keys x 4).
SCORE_TILE_BYTES = 256 << 20


def softmax_scale(cfg) -> float:
    """(qk_nope + qk_rope)^-0.5, times YaRN's mscale(factor, mscale_all_dim)
    squared under YaRN, times `mla_q_scale`: q meets nothing but the scores."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_yarn is not None:
        m = yarn_mscale(cfg.rope_yarn.factor, cfg.rope_yarn.mscale_all_dim)
        scale *= m * m
    return scale * cfg.mla_q_scale


class MLA(nn.Module):
    cfg: "TransformerConfig"  # noqa: F821 - models/transformer.py

    @nn.compact
    def __call__(self, x, positions, decode: bool = False, kv_bound=None,
                 live=None):
        cfg = self.cfg
        heads, rank = cfg.n_heads, cfg.kv_lora_rank
        nope, rot = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        vdim = cfg.v_head_dim
        dense = lambda feats, name, **kw: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, name=name,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, **kw)
        per_head = _up_init(cfg.mla_kv_scale, batch_axis=(0,))
        # The published `kv_b_proj` [rank, heads x (nope + v)] is held as two
        # head-major halves [heads, rank, dim]: the latent path uses each alone.
        q_up = _up_init(cfg.mla_q_scale)
        wk_b = self.param("wk_b", per_head, (heads, rank, nope),
                          cfg.param_dtype).astype(cfg.dtype)
        wv_b = self.param("wv_b", per_head, (heads, rank, vdim),
                          cfg.param_dtype).astype(cfg.dtype)
        with jax.named_scope("mla_attention"):
            if cfg.q_lora_rank:
                c_q = RMSNorm(cfg.norm_eps, name="q_norm")(
                    dense(cfg.q_lora_rank, "wq_a")(x))
                q = dense((heads, nope + rot), "wq_b", kernel_init=q_up)(c_q)
            else:
                q = dense((heads, nope + rot), "wq")(x)
            kv = dense(rank + rot, "wkv_a")(x)  # [B, S, rank + rot]
            c_kv = RMSNorm(cfg.norm_eps, cfg.mla_kv_scale, name="kv_norm")(
                kv[..., :rank])
            inv_freq = rope_inv_freq(rot, cfg.rope_theta, cfg.rope_yarn)
            cs = rope_cos_sin_scale(cfg.rope_yarn)
            q_nope = q[..., :nope]
            if cfg.mla_rope:
                q_rope = rope_interleaved(q[..., nope:], positions, inv_freq,
                                          cs)
                k_rope = rope_interleaved(kv[..., None, rank:], positions,
                                          inv_freq, cs)[:, :, 0]  # [B, S, rot]
            else:  # no position: the shared key dims go as they are
                q_rope, k_rope = q[..., nope:], kv[..., rank:]
            scale = softmax_scale(cfg)
            if decode:
                out = self._cached(q_nope, q_rope, c_kv, k_rope, wk_b, wv_b,
                                   positions, scale, kv_bound, live)
            else:
                out = _expanded(q_nope, q_rope, c_kv, k_rope, wk_b, wv_b,
                                positions, scale)
        return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False,
                               name="wo", dtype=cfg.dtype,
                               param_dtype=cfg.param_dtype)(out)

    def _cached(self, q_nope, q_rope, c_kv, k_rope, wk_b, wv_b, positions,
                scale, kv_bound=None, live=None):
        """Serving: one cache leaf `[slots, max_seq, row]` a layer, each
        sequence's rows written at its own absolute positions (as
        `Attention._cached_attention` writes K and V). A single-token step
        attends in the latent space over the sequence's rows up to its
        position; given `kv_bound`, on a TPU through the ragged kernel,
        which reads the rows of each slot that `live` ([B] bool; None:
        all) marks occupied up to its own position and nothing else
        (`ops/decode_attention.py` `ragged_latent_attention`;
        `latent_refusal` is the rule, and each choice is stated once at
        INFO), and elsewhere over the shortest static prefix of the rows
        that holds `kv_bound` of them (`over_kv_prefix`: a latent row is
        the one-KV-head case); a
        multi-token step is a prefill from position 0 and
        attends over its own rows, expanded, and only writes the latents.
        `row` is `kv_lora_rank + qk_rope_head_dim`, or wider when the
        engine found that the device lays such rows out in wider tiles
        (`cfg.cache_row`); the tail is zeros that are never read."""
        cfg = self.cfg
        b, s = c_kv.shape[0], c_kv.shape[1]
        rank = cfg.kv_lora_rank
        width = rank + cfg.qk_rope_head_dim
        row = max(cfg.cache_row, width)
        cache = self.variable("cache", "latent", lambda: jnp.zeros(
            (b, cfg.max_seq, row), cfg.dtype))
        pos = positions.astype(jnp.int32)
        new = jnp.concatenate([c_kv, k_rope], axis=-1).astype(cfg.dtype)
        if row > width:
            new = jnp.pad(new, ((0, 0), (0, 0), (0, row - width)))
        cache.value = cache.value.at[jnp.arange(b)[:, None], pos].set(new)
        if s > 1:
            with jax.named_scope("prefill_attention"):
                return _expanded(q_nope, q_rope, c_kv, k_rope, wk_b, wv_b,
                                 positions, scale)
        with jax.named_scope("decode_attention"):
            q_lat = jnp.einsum("bhn,hcn->bhc", q_nope[:, 0], wk_b)
            if kv_bound is None:
                o_lat = _latent_attention(q_lat, q_rope, cache.value, pos,
                                          rank, width, scale)
            elif (reason := latent_refusal(cache.value.shape, rank,
                                           cache.value.dtype)) is None:
                rule.state_once("latent decode attention: ragged Pallas "
                                "kernel")
                o_lat = ragged_latent_attention(
                    jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1),
                    cache.value, pos[:, 0] + 1, live, rank=rank, scale=scale)
            else:
                if reason != rule.NOT_ASKED:
                    rule.state_once(f"latent decode attention: XLA walk to "
                                    f"a quarter prefix ({reason})")
                o_lat = _latent_walk(q_lat, q_rope, cache.value, pos,
                                     kv_bound, rank, width, scale)
            out = jnp.einsum("bhc,hcv->bhv", o_lat, wv_b)
            return out[:, None].astype(cfg.dtype)


def _latent_attention(q_lat, q_rope, latents, pos, rank, width, scale):
    """One query a head (`q_lat` [slots, heads, rank] in the latent space,
    `q_rope` [slots, 1, heads, rot]) against the rows `[slots, rows, row]`
    it is given: f32 scores, the weighted sum of the `c_kv` part in the
    cache's dtype."""
    scores = jnp.einsum("bhc,btc->bht", q_lat, latents[..., :rank],
                        preferred_element_type=jnp.float32)
    q_rope = q_rope[:, 0]
    # The rotary key is read to the END of the row, against a query that is
    # zeros there as the row is: cut at `width` inside a row of whole lane
    # tiles, the v5e's compiler copies the WHOLE leaf into another layout
    # in every branch of a bounded walk (PERF.md section 6, PR 29).
    if latents.shape[-1] > width:
        q_rope = jnp.pad(
            q_rope, ((0, 0), (0, 0), (0, latents.shape[-1] - width)))
    scores = (scores + jnp.einsum("bhr,btr->bht", q_rope, latents[..., rank:],
                                  preferred_element_type=jnp.float32)) * scale
    visible = jnp.arange(latents.shape[1])[None, None, :] <= pos[:, :, None]
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
    return jnp.einsum("bht,btc->bhc", probs.astype(latents.dtype),
                      latents[..., :rank])


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _latent_walk(q_lat, q_rope, latents, pos, kv_bound, rank, width, scale):
    """`_latent_attention` over the prefix of the rows that `kv_bound`
    picks (`over_kv_prefix`: a latent row is its one-KV-head case).
    Jitted, so that the layers of a model share one trace and one function
    of the lowered module."""
    return over_kv_prefix(
        lambda rows: _latent_attention(q_lat, q_rope, rows, pos, rank, width,
                                       scale),
        (latents,), kv_bound)


def _expanded(q_nope, q_rope, c_kv, k_rope, wk_b, wv_b, positions, scale):
    """Causal attention over this call's own rows with keys and values
    expanded from the latents. Queries go in tiles; a tile's keys end where
    the tile ends, because `positions` ascend along a sequence (a prefill
    or a training batch), so later rows would be masked anyway."""
    dtype = q_nope.dtype
    b, s, heads, _ = q_nope.shape
    k_nope = jnp.einsum("btc,hcn->bthn", c_kv, wk_b)
    v = jnp.einsum("btc,hcv->bthv", c_kv, wv_b)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None], (b, s, heads,
                                                       k_rope.shape[-1]))],
        axis=-1).astype(dtype)
    tile = s
    while tile > 8 and b * heads * tile * s * 4 > SCORE_TILE_BYTES:
        tile //= 2
    pos = positions.astype(jnp.int32)
    outs = []
    for start in range(0, s, tile):
        end = min(start + tile, s)
        scores = jnp.einsum("bshd,bthd->bhst", q[:, start:end], k[:, :end],
                            preferred_element_type=jnp.float32) * scale
        visible = (pos[:, None, :end][:, :, None, :]
                   <= pos[:, None, start:end][:, :, :, None])
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhst,bthv->bshv", probs.astype(dtype),
                               v[:, :end]))
    return (outs[0] if len(outs) == 1
            else jnp.concatenate(outs, axis=1)).astype(dtype)


def _up_init(scale: float, **kw):
    """The initialiser of a low-rank path's up-projection (`wq_b`; `wk_b`,
    `wv_b`): variance 1 / fan-in, which is flax's own `lecun_normal`, over the
    square of the path's scale correction. `mla_scale_q_lora` and
    `mla_scale_kv_lora` multiply by (hidden / rank)^0.5 because, under one
    sigma for all matrices, what comes up from a rank r has r / hidden of the
    variance of what comes from the hidden size; a random tree has to be one
    the corrections are right for, or q and k_nope come out at 4 and 12 times
    unit variance and the scores at a standard deviation of 5.7 (PERF.md
    section 6, PR 42: served bf16 tokens then lay 1.87 below the float32
    reference's best logit). At scale 1 the values are `lecun_normal`'s."""
    return nn.initializers.variance_scaling(
        scale ** -2, "fan_in", "truncated_normal", **kw)
