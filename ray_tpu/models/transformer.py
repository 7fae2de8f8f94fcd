"""Flagship model: llama-style decoder transformer, mesh-first.

TPU-native design notes:
- bfloat16 activations / f32 params & optimizer state (MXU-friendly).
- Megatron-style sharding via PartitionSpecs (param_specs): attention and
  MLP matmuls split over "tp", parameters additionally over "fsdp"
  (ZeRO-3 analogue), activations between blocks sequence-sharded over "sp";
  XLA/GSPMD inserts the all-gathers/reduce-scatters over ICI.
- Attention goes through ray_tpu.ops.dot_product_attention: the Pallas flash
  kernel on a TPU when no gradient is taken (the kernel has no VJP, so a
  training step takes the XLA path), the XLA reference elsewhere. Serving
  runs with decode=True and takes neither: `_cached_attention` below.
- The reference framework has no model zoo of its own — this fills the role
  its vLLM/torch delegation played (llm/_internal/serve/.../vllm_models.py
  TP/PP passthrough), natively.
"""

from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import dot_product_attention


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8  # < n_heads => GQA
    d_ff: int = 1376  # ~8/3 * d_model, SwiGLU
    max_seq: int = 2048
    rope_theta: float = 10000.0
    dtype: jnp.dtype = jnp.bfloat16  # activation/compute dtype
    param_dtype: jnp.dtype = jnp.float32
    #: >0 switches the MLP to a top-2 MoE with this many experts, sharded
    #: over the "ep" mesh axis.
    moe_experts: int = 0
    #: Width of a KV-cache row: head_dim when 0, else head_dim followed by
    #: zeros that are never read. The serving engine sets it to the width
    #: the device's compiler lays a row out in (llm/engine.py
    #: `_probe_cache_row`), so that the cache's default on-device layout
    #: is the one the decode loop computes in.
    cache_row: int = 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def _rope(x, positions, theta: float):
    """Rotary position embeddings. x: [B, S, H, D], positions: [B, S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(x.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, decode: bool = False):
        cfg = self.cfg
        hd = cfg.head_dim
        dense = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, name=name,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        q = dense((cfg.n_heads, hd), "wq")(x)
        k = dense((cfg.n_kv_heads, hd), "wk")(x)
        v = dense((cfg.n_kv_heads, hd), "wv")(x)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        if decode:
            out = self._cached_attention(q, k, v, positions)
        else:
            out = dot_product_attention(q, k, v, causal=True)
        return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False, name="wo",
                               dtype=cfg.dtype, param_dtype=cfg.param_dtype)(out)

    def _cached_attention(self, q, k, v, positions):
        """Autoregressive KV-cache attention with PER-SEQUENCE positions
        (reference role: vLLM's paged KV cache; here slot-per-sequence):
        new k/v rows scatter into fixed [B, max_seq, KV, D] buffers at each
        sequence's own absolute positions, so one compiled step can serve a
        continuous batch whose members are at different depths (the
        requirement of in-flight batching). Visibility for query i of
        sequence b is t <= positions[b, i]; rows above a sequence's current
        position are never visible, so stale pad/previous-request garbage
        in the slot can never leak into attention. Single-token steps
        (S==1, the serving hot loop) go through the decode-attention
        dispatcher (ops/decode_attention.py: fused XLA or the Pallas
        kernel, by cache size); multi-token steps (prefill) run the dense
        f32 einsum below over the whole cache."""
        cfg = self.cfg
        b, s = q.shape[0], q.shape[1]
        d = cfg.head_dim
        row = max(cfg.cache_row, d)
        ck = self.variable("cache", "k", lambda: jnp.zeros(
            (b, cfg.max_seq, cfg.n_kv_heads, row), cfg.dtype))
        cv = self.variable("cache", "v", lambda: jnp.zeros(
            (b, cfg.max_seq, cfg.n_kv_heads, row), cfg.dtype))
        pos = positions.astype(jnp.int32)
        bidx = jnp.arange(b)[:, None]
        k, v = k.astype(cfg.dtype), v.astype(cfg.dtype)
        if row > d:
            tail = ((0, 0),) * 3 + ((0, row - d),)
            k, v = jnp.pad(k, tail), jnp.pad(v, tail)
        ck.value = ck.value.at[bidx, pos].set(k)
        cv.value = cv.value.at[bidx, pos].set(v)
        keys, vals = ck.value, cv.value
        if row > d:
            keys, vals = keys[..., :d], vals[..., :d]
        if s == 1:
            from ray_tpu.ops.decode_attention import decode_attention

            out = decode_attention(q[:, 0], keys, vals, pos[:, 0] + 1)
            return out[:, None].astype(cfg.dtype)
        with jax.named_scope("prefill_attention"):
            if cfg.n_kv_heads < cfg.n_heads:  # GQA: broadcast kv heads
                rep = cfg.n_heads // cfg.n_kv_heads
                keys = jnp.repeat(keys, rep, axis=2)
                vals = jnp.repeat(vals, rep, axis=2)
            scores = jnp.einsum(
                "bshd,bthd->bhst", q.astype(jnp.float32),
                keys.astype(jnp.float32)) / (cfg.head_dim ** 0.5)
            # cache row t is visible to query i of sequence b iff
            # t <= pos[b, i]
            t_pos = jnp.arange(cfg.max_seq)[None, None, None, :]
            q_pos = pos[:, None, :, None]
            scores = jnp.where(t_pos <= q_pos, scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhst,bthd->bshd", probs,
                             vals.astype(jnp.float32))
            return out.astype(cfg.dtype)


class SwiGLU(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, name=name, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        gate = nn.silu(dense(cfg.d_ff, "w_gate")(x))
        up = dense(cfg.d_ff, "w_up")(x)
        return dense(cfg.d_model, "w_down")(gate * up)


class MoE(nn.Module):
    """Top-2 mixture-of-experts SwiGLU, expert-parallel over "ep".

    Expert weights carry a leading [E] axis sharded over the ep mesh axis;
    each device computes its expert shard over all tokens and the combine
    contraction reduces over ep (XLA inserts the collective). Dense
    dispatch (no capacity/dropping) keeps the math exactly equal to the
    single-device reference — the routing SEMANTICS and the ep sharding are
    what the dryrun proves; capacity-based all_to_all dispatch is the
    optimization seam."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        e, dm, ff = cfg.moe_experts, cfg.d_model, cfg.d_ff
        router = self.param("router", nn.initializers.normal(0.02),
                            (dm, e), jnp.float32)
        w_gate = self.param("w_gate", nn.initializers.lecun_normal(),
                            (e, dm, ff), cfg.param_dtype)
        w_up = self.param("w_up", nn.initializers.lecun_normal(),
                          (e, dm, ff), cfg.param_dtype)
        w_down = self.param("w_down", nn.initializers.lecun_normal(),
                            (e, ff, dm), cfg.param_dtype)
        logits = x.astype(jnp.float32) @ router  # [B, S, E]
        probs = jax.nn.softmax(logits, axis=-1)
        k = min(2, e)  # top-2 routing (top-1 when only one expert)
        kth = jax.lax.top_k(probs, k)[0][..., -1:]  # k-th highest prob
        gates = jnp.where(probs >= kth, probs, 0.0)
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)  # renorm top-k
        xc = x.astype(cfg.dtype)
        gate_h = nn.silu(jnp.einsum("bsd,edf->ebsf", xc, w_gate.astype(cfg.dtype)))
        up_h = jnp.einsum("bsd,edf->ebsf", xc, w_up.astype(cfg.dtype))
        expert_out = jnp.einsum("ebsf,efd->ebsd", gate_h * up_h,
                                w_down.astype(cfg.dtype))
        return jnp.einsum("ebsd,bse->bsd", expert_out,
                          gates.astype(cfg.dtype))


class Block(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, decode: bool = False):
        x = x + Attention(self.cfg, name="attn")(
            RMSNorm(name="attn_norm")(x), positions, decode=decode)
        mlp = (MoE(self.cfg, name="moe") if self.cfg.moe_experts
               else SwiGLU(self.cfg, name="mlp"))
        x = x + mlp(RMSNorm(name="mlp_norm")(x))
        return x


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions=None, decode: bool = False):
        """tokens: [B, S] int32 -> logits [B, S, vocab] (f32).

        decode=True uses per-layer KV caches (flax "cache" collection):
        pass `positions` (absolute) and apply with mutable=["cache"]."""
        cfg = self.cfg
        emb = self.param("tok_emb", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        x = emb[tokens].astype(cfg.dtype)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        for i in range(cfg.n_layers):
            if not decode:
                x = _seq_shard(x)
            x = Block(cfg, name=f"layer_{i}")(x, positions, decode=decode)
        x = RMSNorm(name="final_norm")(x)
        # Tied output head (vocab-sharded matmul over tp).
        with jax.named_scope("lm_head"):
            return jnp.einsum("bsd,vd->bsv", x,
                              emb.astype(cfg.dtype)).astype(jnp.float32)


def _seq_shard(x):
    """Sequence-parallel activation constraint between blocks: [B, S, D]
    sharded batch over (dp, fsdp) and sequence over sp. GSPMD gathers the
    sequence inside attention (Megatron-SP style); ring attention
    (ray_tpu/ops/ring_attention.py) removes that gather when enabled.

    Applied when the mesh in context has all three axes; with no mesh
    (single device) or a mesh without them (a tp-only serving mesh) there is
    nothing to constrain."""
    if not {"dp", "fsdp", "sp"} <= set(_context_mesh_axes()):
        return x
    return jax.lax.with_sharding_constraint(x, P(("dp", "fsdp"), "sp", None))


def _context_mesh_axes() -> tuple[str, ...]:
    """Axis names of the mesh in context, whichever way it was entered.

    JAX 0.9.0 keeps two contexts that do not see each other:
    `jax.set_mesh(mesh)` sets the abstract mesh, the older `with mesh:` sets
    only the thread-local physical mesh, which has no public reader.
    `with_sharding_constraint` honours a bare PartitionSpec under either, so
    both are read here: a caller under `with mesh:` must not lose sequence
    parallelism without an error."""
    axes = jax.sharding.get_abstract_mesh().axis_names
    if axes:
        return axes
    from jax._src.mesh import thread_resources

    return thread_resources.env.physical_mesh.axis_names


def param_specs(params) -> dict:
    """PartitionSpec tree matching init(params): Megatron TP + fsdp sharding.

    kernels are [in, out] (flax Dense); DenseGeneral qkv kernels are
    [d_model, heads, head_dim]; wo kernel is [heads, head_dim, d_model].
    """

    def rule(path: tuple[str, ...], leaf):
        last = path[-1]
        name = path[-2] if len(path) >= 2 else last
        moe = "moe" in path
        if last == "tok_emb":
            return P("tp", "fsdp")  # vocab over tp, d_model over fsdp
        if last == "router":
            return P("fsdp", None)
        if moe and last in ("w_gate", "w_up"):
            return P("ep", "fsdp", "tp")  # leading [E] axis over ep
        if moe and last == "w_down":
            return P("ep", "tp", "fsdp")
        if name in ("wq", "wk", "wv"):
            return P("fsdp", "tp", None)  # heads over tp
        if name == "wo":
            return P("tp", None, "fsdp")
        if name in ("w_gate", "w_up"):
            return P("fsdp", "tp")
        if name == "w_down":
            return P("tp", "fsdp")
        return P()  # norms etc: replicated

    from ray_tpu.parallel.mesh import spec_tree_like

    return spec_tree_like(params, rule)


def loss_fn(model: Transformer, params, tokens):
    """Next-token cross entropy, mean over all positions."""
    logits = model.apply(params, tokens[:, :-1])
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()
