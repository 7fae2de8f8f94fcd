"""Attention ops: reference XLA implementations with a Pallas fast path.

The reference framework has no attention kernels of its own (it delegates to
torch/vLLM); this module is the TPU-native equivalent of that delegated
surface. `dot_product_attention` is the one door for attention over whole
sequences (a training batch, a serving prefill): it dispatches to the Pallas
flash kernel on a TPU when the shapes allow and no gradient is taken
(ray_tpu/ops/flash_attention.py), else to an XLA form that GSPMD can shard:
`prefill_attention` for causal attention of a call over its own rows (with
or without a window), `_xla_attention` for the rest. A decode step's
attention over the cache is ops/decode_attention.py.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import (block_causal_attention,
                                         blocks_unsupported_reason,
                                         flash_attention, unsupported_reason)
from ray_tpu.parallel.mesh import context_mesh_shape

logger = logging.getLogger(__name__)
# Each distinct choice is stated once per process, not once per call.
_stated: set[str] = set()

#: Most bytes of float32 scores one tile of queries may hold in
#: `prefill_attention` (heads x tile x keys x 4); models/mla.py's expanded
#: path has the same bound.
SCORE_TILE_BYTES = 256 << 20

#: `kernel_refusal` where no kernel was asked for: nothing to state.
NOT_ASKED = "not on a TPU"


def state_once(msg: str) -> None:
    if msg not in _stated:
        _stated.add(msg)
        logger.info(msg)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_refusal(q_shape, k_shape, *, causal: bool = True, window: int = 0,
                   use_pallas: bool | None = None,
                   blocks: int = 0) -> str | None:
    """Why `dot_product_attention` takes an XLA form for q [B, Sq, Hq, D]
    against k/v [B, Sk, Hkv, D] outside differentiation, or None when it
    takes the kernel: the dispatcher's own rule, for whoever wants to know
    the choice without making the call (llm/engine.py counts the prefill
    rows either way)."""
    if not (on_tpu() if use_pallas is None else use_pallas):
        return NOT_ASKED
    if blocks:  # causal by blocks: a kernel of its own
        return mesh_refusal() or blocks_unsupported_reason(
            q_shape, k_shape, blocks)
    return mesh_refusal() or unsupported_reason(
        q_shape, k_shape, causal=causal, window=window)


def mesh_refusal() -> str | None:
    """Mosaic refuses to lower a kernel into a program that GSPMD
    partitions, and nothing here wraps one in a shard_map."""
    devices = math.prod(context_mesh_shape().values())
    if devices > 1:
        return (f"a mesh of {devices} devices is in context: the kernel is "
                f"not partitioned")
    return None


def dot_product_attention(q, k, v, *, causal: bool = True, window: int = 0,
                          q_len=None, use_pallas: bool | None = None,
                          blocks: int = 0):
    """q: [B, Sq, Hq, D], k/v: [B, Sk, Hkv, D] (GQA when Hq > Hkv).

    Returns [B, Sq, Hq, D]. Softmax in f32 regardless of input dtype
    (bf16-safe), output in the input dtype. `window` > 0 (causal attention
    of a call over its own rows only): key j is visible to query i iff
    0 <= i - j < window. `q_len` ([B] int32): rows at or past it are read
    by nobody, so the kernel may skip them (they come back as zeros or as
    what they would be). `blocks` > 0 (a call over its own rows, no window):
    causal BY BLOCKS of so many positions, key j visible to query i iff
    j < blocks * (i // blocks + 1): every earlier block and the query's own,
    whole (a block-diffusion model's prefill; serving only, no gradient).

    The implementation is chosen up front from what can be observed, and
    each choice is stated once at INFO (`kernel_refusal` is the rule):
      - not on a TPU (or use_pallas=False): the XLA form;
      - a mesh of several devices in context (the kernel is not
        partitioned), or a shape the flash kernel cannot tile
        (`unsupported_reason`, the kernel's own block derivation): the XLA
        form, O(Sq*Sk) memory;
      - under differentiation: the XLA form for the forward and the
        backward pass, because the flash kernel has no VJP;
      - otherwise the Pallas flash kernel.
    Nothing is caught: an error from the kernel is the caller's error."""
    if window and not (causal and q.shape[1] == k.shape[1]):
        raise ValueError("a window is for causal attention of a call over "
                         "its own rows")
    if blocks and (window or not causal or q.shape[1] != k.shape[1]):
        raise ValueError("visibility by blocks is for causal attention of a "
                         "call over its own rows, without a window")
    reason = kernel_refusal(q.shape, k.shape, causal=causal, window=window,
                            use_pallas=use_pallas, blocks=blocks)
    if reason is None and blocks:
        state_once("attention: Pallas flash kernel, causal by blocks")
        return block_causal_attention(q, k, v, blocks=blocks, q_len=q_len)
    if reason is None:
        return _kernel_or_xla_grad(q, k, v, q_len, causal, window)
    if reason != NOT_ASKED:
        state_once(f"attention: XLA path, O(Sq*Sk) memory ({reason})")
    return _xla_form(q, k, v, causal, window, blocks)


def _xla_form(q, k, v, causal: bool, window: int, blocks: int = 0):
    if causal and q.shape[1] == k.shape[1]:
        return prefill_attention(q, k, v, window, blocks)
    return _xla_attention(q, k, v, causal=causal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _kernel_or_xla_grad(q, k, v, q_len, causal, window):
    state_once("attention: Pallas flash kernel")
    return flash_attention(q, k, v, causal=causal, window=window, q_len=q_len)


def _kernel_or_xla_grad_fwd(q, k, v, q_len, causal, window):
    state_once("attention: XLA path under differentiation (the flash "
               "kernel has no VJP)")
    return jax.vjp(functools.partial(_xla_form, causal=causal, window=window),
                   q, k, v)


def _kernel_or_xla_grad_bwd(causal, window, vjp, g):
    return (*vjp(g), None)


_kernel_or_xla_grad.defvjp(_kernel_or_xla_grad_fwd, _kernel_or_xla_grad_bwd)


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


def prefill_attention(q, k, v, window: int = 0, blocks: int = 0):
    """Causal attention of a call over ITS OWN rows, positions 0..S-1 in
    order (a prefill, or a training batch of a model with window layers):
    q [B, S, H, D], k and v [B, S, KV, D] -> [B, S, H, D]; with `blocks`
    causal by blocks of so many positions (a query sees its own block whole:
    a tile is whole blocks, so its keys still end where it ends). Queries go
    in tiles so that a tile's float32 scores stay small. The query heads that
    share a key/value head are one matrix product against it: K and V are
    never copied per query head.

    Several tiles are ONE loop over a body of one shape (a loop in a
    prefill is fine; a decode step has none): each tile reads the `band`
    rows before it and its own, of a K and V padded in front by `band`
    rows. In a window layer (key j visible to query i iff 0 <= i - j <
    window) the band is the window, so a long bucket costs its band, not
    its square. In a full layer the band is the whole call: half of what a
    tile reads is masked, twice the products a tile that stopped at its own
    end would make. Written out tile by tile at their own lengths, a prefill
    of 8192 rows was an executable of 2,700 fusions, 55 MB in the compile
    cache, whose serialisation held the interpreter's lock long enough for
    serve's 5 s health check to lose the replica (PERF.md section 6, PR 32)."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    dtype = q.dtype
    qg = q.reshape(b, s, kv, h // kv, d)
    band = min(window, s) if window else s
    fits = lambda t: (b * h * t * 4 * min(s, band + t)  # noqa: E731
                      <= SCORE_TILE_BYTES)
    # a call too long for one tile goes in tiles of a power of two that
    # divide it (a bucket of 6144 in tiles of 512 or 256, like its neighbours)
    tile = s if fits(s) else s & -s
    while tile > 8 and not fits(tile):
        tile //= 2
    if blocks and (window or tile % blocks or s % blocks):
        raise ValueError(f"blocks of {blocks} positions against tiles of "
                         f"{tile} of {s} rows (window {window})")

    def attend(qt, kt, vt, i, j):
        """One tile: queries at positions i [T] against keys at j [K]."""
        scores = jnp.einsum("bsngd,btnd->bngst", qt, kt,
                            preferred_element_type=jnp.float32) / (d ** 0.5)
        back = i[:, None] - j[None, :]
        if blocks:  # the query's block end stands in for its own position
            back = back + (blocks - 1 - i[:, None] % blocks)
        visible = (back >= 0) & (j[None, :] >= 0)
        if window:
            visible = visible & (back < window)
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bngst,btnd->bsngd", probs.astype(dtype), vt)

    if s == tile or s % tile:  # one tile, or a ragged call (no bucket)
        outs = [attend(qg[:, at:at + tile], k[:, :at + tile], v[:, :at + tile],
                       jnp.arange(at, min(at + tile, s)),
                       jnp.arange(min(at + tile, s)))
                for at in range(0, s, tile)]
        out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
        return out.reshape(b, s, h, d).astype(dtype)
    # keys [start - band, start + tile) of the padded K and V; the padding's
    # positions are negative and never visible
    front = ((0, 0), (band, 0), (0, 0), (0, 0))
    kp, vp = jnp.pad(k, front), jnp.pad(v, front)

    def one_tile(_, start):
        cut = lambda t, n: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            t, start, n, axis=1)
        return None, attend(cut(qg, tile), cut(kp, band + tile),
                            cut(vp, band + tile), start + jnp.arange(tile),
                            start - band + jnp.arange(band + tile))

    _, outs = jax.lax.scan(one_tile, None, jnp.arange(0, s, tile))
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h, d).astype(dtype)
