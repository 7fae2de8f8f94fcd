"""Attention ops: reference XLA implementation with a Pallas fast path.

The reference framework has no attention kernels of its own (it delegates to
torch/vLLM); this module is the TPU-native equivalent of that delegated
surface. `dot_product_attention` dispatches to the Pallas flash kernel on TPU
when shapes allow and no gradient is taken (ray_tpu/ops/flash_attention.py),
else to a fused-softmax XLA implementation that GSPMD can shard.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import flash_attention, unsupported_reason

logger = logging.getLogger(__name__)
# Each distinct choice is stated once per process, not once per call.
_stated: set[str] = set()


def _state_once(msg: str) -> None:
    if msg not in _stated:
        _stated.add(msg)
        logger.info(msg)


def dot_product_attention(q, k, v, *, causal: bool = True, use_pallas: bool | None = None):
    """q: [B, Sq, Hq, D], k/v: [B, Sk, Hkv, D] (GQA when Hq > Hkv).

    Returns [B, Sq, Hq, D]. Softmax in f32 regardless of input dtype
    (bf16-safe), output in the input dtype.

    The implementation is chosen up front from what can be observed, and
    each choice is stated once at INFO:
      - not on a TPU (or use_pallas=False): the XLA path;
      - a shape the flash kernel cannot tile (`unsupported_reason`, the
        kernel's own block derivation): the XLA path, O(Sq*Sk) memory;
      - under differentiation: the XLA path for the forward and the
        backward pass, because the flash kernel has no VJP;
      - otherwise the Pallas flash kernel.
    Nothing is caught: an error from the kernel is the caller's error."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return _xla_attention(q, k, v, causal=causal)
    reason = unsupported_reason(q.shape, k.shape)
    if reason is not None:
        _state_once(f"attention: XLA path, O(Sq*Sk) memory ({reason})")
        return _xla_attention(q, k, v, causal=causal)
    return _flash_or_xla_grad(q, k, v, causal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_or_xla_grad(q, k, v, causal):
    _state_once("attention: Pallas flash kernel")
    return flash_attention(q, k, v, causal=causal)


def _flash_or_xla_grad_fwd(q, k, v, causal):
    _state_once("attention: XLA path under differentiation (the flash "
                "kernel has no VJP)")
    return jax.vjp(functools.partial(_xla_attention, causal=causal), q, k, v)


def _flash_or_xla_grad_bwd(causal, vjp, g):
    return vjp(g)


_flash_or_xla_grad.defvjp(_flash_or_xla_grad_fwd, _flash_or_xla_grad_bwd)


def _xla_attention(q, k, v, *, causal: bool):
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if hq != hkv:  # GQA: repeat kv heads
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask[None, None], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
