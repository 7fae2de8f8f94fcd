"""Pipeline parallelism: GPipe-style microbatched schedule over the "pp"
mesh axis.

TPU-native design: the block stack's parameters carry a leading [n_layers]
axis sharded over pp, so each device physically holds only its stage's
layers. Under shard_map, every pipeline tick applies the local stage to the
activation in flight and `ppermute`s it to the next stage; `lax.scan` rolls
the schedule into one compiled program and autodiff reverses the ring for
the backward pass (the transpose of ppermute is the reverse permute — the
backward pipeline comes for free). With M microbatches and S stages the
bubble is the standard (S-1)/(M+S-1).

The reference delegates PP to vLLM (llm/_internal/serve/.../vllm_models.py
passthrough); there is no reference code to mirror — this is designed
fresh for the XLA compilation model (SURVEY §7 step 11 peer).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.attention import _xla_attention


@dataclass(frozen=True)
class PipelineConfig:
    vocab_size: int = 512
    d_model: int = 128
    n_layers: int = 4  # total, split evenly across pp stages
    n_heads: int = 4
    d_ff: int = 256
    n_microbatches: int = 4


def init_params(cfg: PipelineConfig, seed: int = 0) -> dict:
    """Raw-pytree params; block weights stacked on a leading [n_layers]
    axis (the axis pp shards)."""
    rng = np.random.RandomState(seed)
    L, D, F, H = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_heads

    def w(*shape, scale=None):
        scale = scale or (1.0 / np.sqrt(shape[-2] if len(shape) > 1 else shape[0]))
        return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)

    return {
        "emb": w(cfg.vocab_size, D, scale=0.02),
        "blocks": {
            "wq": w(L, D, D), "wk": w(L, D, D), "wv": w(L, D, D),
            "wo": w(L, D, D),
            "w_gate": w(L, D, F), "w_up": w(L, D, F), "w_down": w(L, F, D),
            "norm1": jnp.ones((L, D), jnp.float32),
            "norm2": jnp.ones((L, D), jnp.float32),
        },
        "final_norm": jnp.ones((D,), jnp.float32),
    }


def _rms(x, scale):
    n = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    return n * scale


def _block(bp, x, n_heads: int):
    """One transformer block with single-layer params bp (no leading axis)."""
    b, s, d = x.shape
    hd = d // n_heads
    h = _rms(x, bp["norm1"])
    q = (h @ bp["wq"]).reshape(b, s, n_heads, hd)
    k = (h @ bp["wk"]).reshape(b, s, n_heads, hd)
    v = (h @ bp["wv"]).reshape(b, s, n_heads, hd)
    att = _xla_attention(q, k, v, causal=True).reshape(b, s, d)
    x = x + att @ bp["wo"]
    h = _rms(x, bp["norm2"])
    x = x + (jax.nn.silu(h @ bp["w_gate"]) * (h @ bp["w_up"])) @ bp["w_down"]
    return x


def _stage_apply(stage_blocks, x, n_heads: int):
    """Apply this device's layers_per_stage blocks (leading axis scanned)."""

    def body(carry, bp):
        return _block(bp, carry, n_heads), None

    out, _ = jax.lax.scan(body, x, stage_blocks)
    return out


def _pipeline_shard_fn(blocks, x_mb, cfg: PipelineConfig, n_stages: int):
    """Runs under shard_map over 'pp'. blocks: this stage's slice (leading
    axis = layers_per_stage). x_mb: [M, mb, S, D] microbatched embeddings
    (replicated). Returns [M, mb, S, D] block-stack outputs (valid on the
    LAST stage; zeros elsewhere — caller psums over pp)."""
    stage = jax.lax.axis_index("pp")
    M = cfg.n_microbatches
    T = M + n_stages - 1
    mb_shape = x_mb.shape[1:]

    perm_fwd = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        buf = carry  # activation arriving from the previous stage
        inject = x_mb[jnp.clip(t, 0, M - 1)]
        cur = jnp.where(stage == 0, inject, buf)
        y = _stage_apply(blocks, cur, cfg.n_heads)
        nxt = jax.lax.ppermute(y, "pp", perm_fwd)
        return nxt, y

    # The carry varies over pp (it depends on axis_index); the constant
    # initial value must be marked varying too or scan rejects the carry.
    zero = jax.lax.pcast(jnp.zeros(mb_shape, x_mb.dtype), "pp", to="varying")
    _, ys = jax.lax.scan(tick, zero, jnp.arange(T))
    # On the last stage, ys[t] for t in [S-1, S-1+M) are microbatches 0..M-1.
    outs = jax.lax.dynamic_slice_in_dim(ys, n_stages - 1, M, axis=0)
    outs = jnp.where(stage == n_stages - 1, outs, 0.0)
    # Broadcast the finished activations to every stage for the (replicated)
    # head: zeros elsewhere make this a plain psum.
    return jax.lax.psum(outs, "pp")


def pipeline_loss_fn(cfg: PipelineConfig, mesh: Mesh):
    """Returns loss(params, tokens) whose block stack runs as a GPipe
    pipeline over the mesh's pp axis (embedding/head replicated)."""
    n_stages = mesh.shape["pp"]
    assert cfg.n_layers % n_stages == 0

    pipe = jax.shard_map(
        functools.partial(_pipeline_shard_fn, cfg=cfg, n_stages=n_stages),
        mesh=mesh,
        in_specs=(P("pp"), P()),   # blocks stage-sharded; microbatches replicated
        out_specs=P(),
        check_vma=False,
    )

    def loss_fn(params, tokens):
        x = params["emb"][tokens[:, :-1]]  # [B, S, D]
        b, s, d = x.shape
        M = cfg.n_microbatches
        assert b % M == 0
        x_mb = x.reshape(M, b // M, s, d)
        y_mb = pipe(params["blocks"], x_mb)
        y = y_mb.reshape(b, s, d)
        y = _rms(y, params["final_norm"])
        logits = y @ params["emb"].T
        targets = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return nll.mean()

    return loss_fn


def reference_loss(cfg: PipelineConfig, params, tokens):
    """Single-device sequential apply of the same stacked params."""
    x = params["emb"][tokens[:, :-1]]
    x = _stage_apply(params["blocks"], x, cfg.n_heads)
    x = _rms(x, params["final_norm"])
    logits = x @ params["emb"].T
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0].mean()
