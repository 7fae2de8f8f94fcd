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
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models.layers import RMSNorm, SwiGLU, YarnScaling, rope as _rope
from ray_tpu.models.mla import MLA
from ray_tpu.models.moe import MoE
from ray_tpu.ops import dot_product_attention

__all__ = ["Attention", "Block", "MLA", "MoE", "RMSNorm", "SwiGLU",
           "Transformer", "TransformerConfig", "YarnScaling", "loss_fn",
           "param_specs"]


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
    #: RMSNorm's epsilon, every norm of the model.
    norm_eps: float = 1e-6
    #: The logits are the final hidden state times the embedding (True) or
    #: times a matrix of their own, `lm_head` (False).
    tie_embeddings: bool = True
    #: "mha": K and V per head (`Attention`; GQA when n_kv_heads < n_heads).
    #: "mla": one latent per token (`models/mla.py`), sized by the five
    #: numbers below under their published names; `rope_yarn` blends the
    #: rotary frequencies of its `qk_rope_head_dim` dims.
    attention: str = "mha"
    q_lora_rank: int = 0  # 0: queries are projected straight from x
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_yarn: Optional[YarnScaling] = None
    #: >0 makes the feed-forward of every layer from `moe_first_layer` on an
    #: expert layer (`models/moe.py`) whose router scores this many experts:
    #: the count a model publishes. The defaults are a top-2 softmax mixture
    #: of experts `d_ff` wide, all held, their leading [E] axis sharded over
    #: the "ep" mesh axis.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_d_ff: int = 0  # an expert's width; 0: d_ff
    moe_scoring: str = "softmax"  # or "sigmoid"
    moe_norm_topk: bool = True  # weights sum to 1 over the selected
    moe_routed_scale: float = 1.0
    moe_score_bias: bool = False  # selection by score + a learnt bias
    moe_shared_experts: int = 0  # SwiGLUs beside the routed ones, never routed
    moe_first_layer: int = 0  # layers before it keep the dense SwiGLU
    #: The experts held here, `[first_expert, first_expert + experts_held)`
    #: of `moe_experts`; 0 holds them all. What the others would add to a
    #: token is left out (one device's share under expert parallelism).
    experts_held: int = 0
    first_expert: int = 0
    #: Rows of one expert in a tile of the expert layer's grouped path; the
    #: serving prefill takes that path above two tiles' worth of rows.
    moe_group_tile: int = 128
    #: Width of a row of a cache leaf: the leaf's own width when 0 (head_dim
    #: for K and V, kv_lora_rank + qk_rope_head_dim for a latent), else that
    #: followed by zeros that are never read. The serving engine sets it to
    #: the width the device's compiler lays such a row out in (llm/engine.py
    #: `_probe_cache_row`), so that the cache's default on-device layout
    #: is the one the decode loop computes in.
    cache_row: int = 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def is_moe_layer(self, i: int) -> bool:
        return self.moe_experts > 0 and i >= self.moe_first_layer

    @property
    def held_experts(self) -> int:
        """Routed experts an expert layer holds here; 0 without expert
        layers."""
        return (self.experts_held or self.moe_experts) if self.moe_experts else 0


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, decode: bool = False, kv_bound=None):
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
            out = self._cached_attention(q, k, v, positions, kv_bound)
        else:
            out = dot_product_attention(q, k, v, causal=True)
        return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False, name="wo",
                               dtype=cfg.dtype, param_dtype=cfg.param_dtype)(out)

    def _cached_attention(self, q, k, v, positions, kv_bound=None):
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
        dispatcher (ops/decode_attention.py: the fused XLA path in every
        configuration served so far). Given `kv_bound` (a traced int32
        scalar from the engine's scheduler: the longest LIVE sequence's
        length after this chunk, which only the host knows, because a
        retired slot's device-side position keeps growing), such a step
        reads the shortest static prefix of the cache that holds that many
        rows instead of all `max_seq`; without it, the whole cache, by the
        program it always was. Multi-token steps (prefill) run the dense
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
        if row > d and (s > 1 or kv_bound is None):
            # (a bounded step hands the leaves over whole: its branch cuts
            # rows and head together, where the cache lies)
            keys, vals = keys[..., :d], vals[..., :d]
        if s == 1:
            from ray_tpu.ops.decode_attention import decode_attention

            out = decode_attention(q[:, 0], keys, vals, pos[:, 0] + 1,
                                   kv_bound=kv_bound)
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


class Block(nn.Module):
    cfg: TransformerConfig
    #: this layer's feed-forward is the expert layer (cfg.is_moe_layer(i))
    moe: bool = False

    @nn.compact
    def __call__(self, x, positions, decode: bool = False, kv_bound=None):
        cfg = self.cfg
        attn = (MLA(cfg, name="attn") if cfg.attention == "mla"
                else Attention(cfg, name="attn"))
        x = x + attn(RMSNorm(cfg.norm_eps, name="attn_norm")(x), positions,
                     decode=decode, kv_bound=kv_bound)
        h = RMSNorm(cfg.norm_eps, name="mlp_norm")(x)
        if self.moe:
            return x + MoE(cfg, name="moe")(h, serving=decode)
        return x + SwiGLU(cfg, name="mlp")(h)


def output_head(module: nn.Module, cfg: TransformerConfig, x, emb):
    """Logits [B, S, vocab] (f32) of final hidden states, by the embedding
    when the head is tied to it, else by `lm_head` (a parameter of
    `module`, made here). Vocab-sharded matmul over tp either way."""
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            return jnp.einsum("bsd,vd->bsv", x,
                              emb.astype(cfg.dtype)).astype(jnp.float32)
        head = module.param("lm_head", nn.initializers.normal(0.02),
                            (cfg.d_model, cfg.vocab_size), cfg.param_dtype)
        return jnp.einsum("bsd,dv->bsv", x,
                          head.astype(cfg.dtype)).astype(jnp.float32)


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions=None, decode: bool = False,
                 kv_bound=None):
        """tokens: [B, S] int32 -> logits [B, S, vocab] (f32).

        decode=True uses per-layer caches (flax "cache" collection): pass
        `positions` (absolute) and apply with mutable=["cache"]. A
        single-token decode step may also be told `kv_bound`, how many
        cache rows its longest sequence of interest has
        (`Attention._cached_attention`)."""
        cfg = self.cfg
        emb = self.param("tok_emb", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        x = emb[tokens].astype(cfg.dtype)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        for i in range(cfg.n_layers):
            if not decode:
                x = _seq_shard(x)
            x = Block(cfg, moe=cfg.is_moe_layer(i), name=f"layer_{i}")(
                x, positions, decode=decode, kv_bound=kv_bound)
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
        return output_head(self, cfg, x, emb)


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
    The expert layer's own leaves (`router`, `w_gate`/`w_up`/`w_down` with a
    leading [E] axis) sit right under "moe"; its shared expert is a SwiGLU
    under "moe/shared" and takes the dense rules.
    """

    def rule(path: tuple[str, ...], leaf):
        last = path[-1]
        name = path[-2] if len(path) >= 2 else last
        moe = "moe" in path
        if last == "tok_emb":
            return P("tp", "fsdp")  # vocab over tp, d_model over fsdp
        if last == "lm_head":
            return P("fsdp", "tp")
        if last == "router":
            return P("fsdp", None)
        if last in ("wk_b", "wv_b"):
            return P("tp", None, None)  # MLA: [heads, rank, dim]
        if name == "wq_b":
            return P(None, "tp", None)  # [q_lora_rank, heads, dim]
        if name in ("wq_a", "wkv_a"):
            return P("fsdp", None)  # into a latent every head reads
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
