"""What the decoder's blocks share: the norm, the SwiGLU feed-forward and
the rotary embeddings (plain, and with YaRN's blended frequencies).

`ray_tpu.models.transformer` holds the configuration and the model; the
latent attention (`mla.py`) and the expert layer (`moe.py`) import from here
so that they need not import the model that imports them."""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


class RMSNorm(nn.Module):
    eps: float = 1e-6
    #: A constant the normed values are multiplied by, in float32 before the
    #: cast (`models/mla.py`: a latent's `mla_kv_scale`); 1.0: nothing.
    gain: float = 1.0
    #: The weight is `1 + g` with g the parameter, zeros at the start (a
    #: model's `norm_add_unit_offset`), where it is else the parameter itself.
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        if self.unit_offset:
            scale = 1.0 + self.param("scale", nn.initializers.zeros,
                                     (x.shape[-1],), jnp.float32)
        else:
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        if self.gain != 1.0:
            scale = scale * self.gain
        return (norm * scale).astype(x.dtype)


class SwiGLU(nn.Module):
    """silu(x W_gate) * (x W_up) W_down. `d_ff` overrides the
    configuration's width: a leading dense layer and a shared expert are
    SwiGLUs of other widths than the rest of their model."""

    cfg: "TransformerConfig"  # noqa: F821 - models/transformer.py
    d_ff: int = 0

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        width = self.d_ff or cfg.d_ff
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, name=name, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        gate = nn.silu(dense(width, "w_gate")(x))
        up = dense(width, "w_up")(x)
        return dense(cfg.d_model, "w_down")(gate * up)


def rope(x, positions, theta: float):
    """Rotary position embeddings, rotate-half pairing (dim i with dim
    i + D/2). x: [B, S, H, D], positions: [B, S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@dataclass(frozen=True)
class YarnScaling:
    """A published `rope_scaling` of type "yarn" (Peng et al. 2023, as
    DeepSeek-V3's `DeepseekV3YarnRotaryEmbedding` computes it)."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def from_config(rope_scaling: Optional[dict]) -> "Optional[YarnScaling]":
        if not rope_scaling:
            return None
        kind = rope_scaling.get("type", rope_scaling.get("rope_type"))
        if kind != "yarn":
            raise ValueError(f"rope_scaling of type {kind!r} is not built")
        keys = ("factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "mscale", "mscale_all_dim")
        return YarnScaling(**{k: rope_scaling[k] for k in keys
                              if k in rope_scaling})


def yarn_mscale(factor: float, mscale: float) -> float:
    """`yarn_get_mscale`: 0.1 * mscale * ln(factor) + 1 above a factor of 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(dim: int, theta: float, yarn: Optional[YarnScaling] = None):
    """The `dim / 2` inverse frequencies of a rotary embedding, float32.
    With YaRN: the extrapolated frequencies theta^(-2i/dim) for the pairs
    that turn more than `beta_fast` times within the original context, the
    interpolated ones (divided by `factor`) for those that turn less than
    `beta_slow` times, and a linear ramp between the two correction dims."""
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if yarn is None:
        return extra

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    span = max(high - low, 0.001)  # the published guard against low == high
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / span,
                    0.0, 1.0)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def rope_cos_sin_scale(yarn: Optional[YarnScaling]) -> float:
    """What YaRN multiplies cos and sin by: mscale / mscale_all_dim."""
    if yarn is None:
        return 1.0
    return (yarn_mscale(yarn.factor, yarn.mscale)
            / yarn_mscale(yarn.factor, yarn.mscale_all_dim))


def rope_interleaved(x, positions, inv_freq, scale: float = 1.0):
    """Rotary embedding with interleaved pairing: dims (2i, 2i + 1) turn by
    positions * inv_freq[i], in place. x: [B, S, H, D]; positions: [B, S].
    (DeepSeek's code first permutes each pair's members to i and i + D/2
    and then rotates halves; a dot product of two vectors so treated is the
    same, and the cache keeps the dims where the projection put them.)"""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B,S,D/2]
    cos = (jnp.cos(angles) * scale)[:, :, None, :]
    sin = (jnp.sin(angles) * scale)[:, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
