"""Flagship model: llama-style decoder transformer, mesh-first.

TPU-native design notes:
- bfloat16 activations / f32 params & optimizer state (MXU-friendly).
- Megatron-style sharding via PartitionSpecs (param_specs): attention and
  MLP matmuls split over "tp", parameters additionally over "fsdp"
  (ZeRO-3 analogue), activations between blocks sequence-sharded over "sp";
  XLA/GSPMD inserts the all-gathers/reduce-scatters over ICI.
- Attention over a call's own rows (a training batch, a serving prefill; full
  and window layers) goes through ray_tpu.ops.dot_product_attention: the
  Pallas flash kernel on a TPU when no gradient is taken (it has no VJP), else
  the XLA form. A decode step reads the cache through ops/decode_attention.py.
- A layer is mixer -> feed-forward, or (`moe_shortcut`, models/scmoe.py) two
  such halves with the expert layer on a shortcut across the second. Fills the
  role of the reference's vLLM/torch delegation (.../vllm_models.py), natively.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models.eva import EVA
from ray_tpu.models.kda import KDA
from ray_tpu.models.layers import RMSNorm, SwiGLU, YarnScaling, rope as _rope
from ray_tpu.models.mla import MLA
from ray_tpu.models.moe import MoE
from ray_tpu.models.scmoe import shortcut_layer
from ray_tpu.ops import dot_product_attention
from ray_tpu.ops.attention import prefill_attention
from ray_tpu.parallel.mesh import context_mesh_shape, spec_tree_like

__all__ = ["Attention", "Block", "EVA", "ExitGate", "KDA", "MLA", "MoE",
           "RMSNorm", "SwiGLU", "Transformer", "TransformerConfig",
           "YarnScaling", "loss_fn", "param_specs", "prefill_attention"]


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
    #: A published head size (`head_dim`); 0 is d_model // n_heads.
    head_size: int = 0
    #: Layers whose attention sees the last `sliding_window` positions only
    #: (key j visible to query i iff 0 <= i - j < sliding_window): the model's
    #: `layer_types`, True for a window layer; () is none. Such a layer's cache
    #: is a ring, position p in row p mod sliding_window (`_cached_attention`).
    sliding_window: int = 0
    window_layers: tuple = ()
    #: Rotary embedding on the window layers only: a full layer has none.
    rope_window_only: bool = False
    #: RMSNorm of each head's query and key over the head's dims.
    qk_norm: bool = False
    #: The attention's output times sigmoid(x W_g), a head dim each, before wo.
    attn_gate: bool = False
    #: Two further norms a layer: each sublayer's output is normed before it
    #: joins the residual stream.
    sandwich_norm: bool = False
    #: What the embedding's rows are multiplied by.
    emb_scale: float = 1.0
    #: The logits are the final hidden state times the embedding (True) or
    #: times a matrix of their own, `lm_head` (False).
    tie_embeddings: bool = True
    #: Each layer's mixer by name; () is "mha" in every layer. "mha": K and V
    #: per head (`Attention`; GQA when n_kv_heads < n_heads). "mla": one latent
    #: per token (`models/mla.py`), sized by the five numbers below under their
    #: published names; `rope_yarn` blends the rotary frequencies of its
    #: `qk_rope_head_dim` dims, `mla_rope` False leaves them unrotated (the
    #: model's `mla_use_nope`: no position). "kda": a gated delta-rule linear
    #: attention (`models/kda.py`) of `kda_heads` heads of `kda_head_dim`, a
    #: depthwise convolution of `kda_conv` positions on q, k and v, a prefill
    #: that scans chunks of `kda_chunk`, a state a slot (`cache_kind_of`).
    #: "eva": K and V per head, seen exactly inside the query's own aligned
    #: block of `eva_window` positions and, of every block before it, as one
    #: summary row for every `eva_chunk` positions, both under one softmax
    #: (`models/eva.py`): two kinds of cache leaf in ONE layer.
    mixers: tuple = ()
    q_lora_rank: int = 0  # 0: queries are projected straight from x
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_yarn: Optional[YarnScaling] = None
    mla_rope: bool = True
    #: What latent attention multiplies its queries (after `wq_b`) and its
    #: normed `c_kv` by (a model's `mla_scale_q_lora`, `mla_scale_kv_lora`).
    mla_q_scale: float = 1.0
    mla_kv_scale: float = 1.0
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_chunk: int = 64
    eva_window: int = 0
    eva_chunk: int = 0
    #: Standard deviation of the two pooling vectors a head (`mu`, `phi`) at
    #: their start, before the cut to [-1, 1] standard deviations.
    eva_pool_std: float = 1.0
    #: Every norm's weight is `1 + g` (a model's `norm_add_unit_offset`).
    norm_unit_offset: bool = False
    #: The residual stream is float32 whatever `dtype` (`fp32_skip_add`);
    #: every matrix product still takes its operands in `dtype`.
    residual_f32: bool = False
    #: `lm_head` holds this many heads of `vocab_size` columns each, the
    #: first the next token's; the logits are the first head's.
    pred_heads: int = 1
    #: >0 makes the feed-forward of every layer from `moe_first_layer` on an
    #: expert layer (`models/moe.py`) whose router scores this many experts,
    #: the count a model publishes. Defaults: a top-2 softmax mixture, experts
    #: `d_ff` wide, all held, their leading [E] axis sharded over "ep".
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_d_ff: int = 0  # an expert's width; 0: d_ff
    moe_scoring: str = "softmax"  # or "sigmoid"
    moe_norm_topk: bool = True  # weights sum to 1 over the selected
    moe_routed_scale: float = 1.0
    moe_score_bias: bool = False  # selection by score + a learnt bias
    moe_shared_experts: int = 0  # SwiGLUs beside the routed ones, never routed
    moe_first_layer: int = 0  # layers before it keep the dense SwiGLU
    #: Identity experts after the routed ones among the router's outputs: one
    #: selected adds its weight times the layer's input and computes nothing.
    moe_zero_experts: int = 0
    #: A layer is TWO mixer + dense feed-forward halves, and its expert layer
    #: rides a shortcut across the second (`models/scmoe.py` `shortcut_layer`).
    moe_shortcut: bool = False
    #: The experts held here, `[first_expert, first_expert + experts_held)` of
    #: `moe_experts`; 0: all. What the others would add to a token is left out.
    experts_held: int = 0
    first_expert: int = 0
    #: Rows of one expert in a tile of the expert layer's grouped path; the
    #: serving prefill takes that path above two tiles' worth of rows.
    moe_group_tile: int = 128
    #: Width of a cache leaf's row: its own when 0 (head_dim for K, V;
    #: kv_lora_rank + qk_rope_head_dim for a latent), else that and then unread
    #: zeros: the width the compiler lays a row out in (`_probe_cache_row`).
    cache_row: int = 0
    ut_steps: int = 1  #: passes of the WHOLE stack a token (`looped_stack`)
    block_length: int = 0  #: >0: generation by diffusion over blocks of so
    denoising: Optional["Denoising"] = None  #: many positions (file's end)
    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.n_heads

    def window_of(self, i: int) -> int:
        """Rows of layer i's window, cut to `max_seq`; 0 for a full layer.
        An "mha" layer's window slides and its leaf is a ring; an "eva"
        layer's is an aligned block that starts over."""
        if self.mixer_of(i) == "eva":
            return min(self.eva_window, self.max_seq)
        if self.sliding_window and i < len(self.window_layers) \
                and self.window_layers[i]:
            return min(self.sliding_window, self.max_seq)
        return 0

    def mixer_of(self, i: int) -> str:
        return self.mixers[i] if self.mixers else "mha"

    def cache_kind_of(self, i: int, leaf: str = "") -> str:
        """What `leaf` of layer i keeps for a sequence: `full`, rows to
        `max_seq`, one a position; `window`, the window's rows, position p
        in row p mod window; `chunks`, one row for every `eva_chunk`
        positions (an "eva" layer's `kbar` and `vbar`, beside its `window`
        leaves: the one mixer whose leaves differ in kind, and the kind of
        its K and V where no leaf is named); `state`, a fixed block that
        every token replaces."""
        if self.mixer_of(i) == "kda":
            return "state"
        if self.mixer_of(i) == "eva" and leaf in ("kbar", "vbar"):
            return "chunks"
        return "window" if self.window_of(i) else "full"

    def is_moe_layer(self, i: int) -> bool:
        return self.moe_experts > 0 and i >= self.moe_first_layer

    @property
    def held_experts(self) -> int:
        """Routed experts an expert layer holds here; 0 without expert
        layers."""
        return (self.experts_held or self.moe_experts) if self.moe_experts else 0


class Attention(nn.Module):
    cfg: TransformerConfig
    #: Rows of this layer's window (`cfg.window_of(i)`); 0: full attention.
    window: int = 0

    @nn.compact
    def __call__(self, x, positions, decode: bool = False, kv_bound=None,
                 prompt_len=None, live=None, ut_step: int = 0):
        cfg = self.cfg
        hd = cfg.head_dim
        dense = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, name=name,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        q = dense((cfg.n_heads, hd), "wq")(x)
        k = dense((cfg.n_kv_heads, hd), "wk")(x)
        v = dense((cfg.n_kv_heads, hd), "wv")(x)
        if cfg.qk_norm:
            with jax.named_scope("qk_norm"):
                q = RMSNorm(cfg.norm_eps, name="q_norm")(q)
                k = RMSNorm(cfg.norm_eps, name="k_norm")(k)
        if self.window or not cfg.rope_window_only:
            # (keys are rotated by their absolute position BEFORE they are
            # cached, so the order of a ring's rows means nothing)
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        with jax.named_scope("window_attention" if self.window
                             else "full_attention"):
            if decode:
                out = self._cached_attention(q, k, v, positions, kv_bound,
                                             prompt_len, live, ut_step)
            else:
                out = dot_product_attention(q, k, v, causal=True,
                    window=self.window, blocks=cfg.block_length)
        if cfg.attn_gate:
            with jax.named_scope("attn_gate"):
                out = out * jax.nn.sigmoid(dense((cfg.n_heads, hd), "wg")(x))
        return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False, name="wo",
                               dtype=cfg.dtype, param_dtype=cfg.param_dtype)(out)

    def _cached_attention(self, q, k, v, positions, kv_bound=None,
                          prompt_len=None, live=None, ut_step: int = 0):
        """Autoregressive KV-cache attention with PER-SEQUENCE positions
        (reference role: vLLM's paged KV cache; here slot-per-sequence):
        new k/v rows go into fixed per-slot buffers at each sequence's own
        absolute positions, so one compiled step can serve a continuous
        batch whose members are at different depths (the requirement of
        in-flight batching).

        Two kinds of leaf. A full layer keeps `max_seq` rows a slot,
        position p in row p. A window layer keeps a RING of `window` rows,
        position p in row p mod window: the rows of the last `window`
        positions are exactly the rows a query may see. Either way the rows
        visible to a single-token step at position p are `[0, min(p + 1,
        rows))`: rows above a sequence's current position are never
        visible, so stale pad/previous-request garbage in the slot can
        never leak into attention, and in a ring that has wrapped every row
        is live. A leaf is `[slots, rows, kv heads, row]`; `row` is the
        head's size or wider (`cfg.cache_row`).

        Single-token steps (S==1, the serving hot loop) go through
        ops/decode_attention.py. Given `kv_bound` (a traced int32 scalar
        from the engine's scheduler: the longest LIVE sequence's length
        after this chunk, which only the host knows, because a retired
        slot's device-side position keeps growing), such a step reads, on
        a TPU, each slot's own rows and none of a slot that `live` ([B]
        bool, the host's too) marks free, and elsewhere the shortest static
        prefix of the leaf that holds `kv_bound` rows, of `max_seq` or of
        the ring; without it, the whole leaf, by the program it always was.

        A multi-token step is a prefill from position 0: it attends over
        its own rows (`dot_product_attention`: the flash kernel on the chip,
        which skips the query blocks past `prompt_len`, else query tiles in
        XLA) and only writes the cache. Where the call is longer than a
        ring, the ring gets the rows of the last `window` positions before
        `prompt_len` ([B], traced; the call's length when None) at their
        ring places: a padded position past the prompt would otherwise land
        on the row of a live one."""
        cfg = self.cfg
        b, s = q.shape[0], q.shape[1]
        d = cfg.head_dim
        row = max(cfg.cache_row, d)
        rows = self.window or cfg.max_seq
        shape = (b, rows, cfg.n_kv_heads, row)
        ck = self.variable("cache", *pass_leaf("k", ut_step, shape, cfg.dtype))
        cv = self.variable("cache", *pass_leaf("v", ut_step, shape, cfg.dtype))
        pos = positions.astype(jnp.int32)
        bidx = jnp.arange(b)[:, None]
        k, v = k.astype(cfg.dtype), v.astype(cfg.dtype)
        kc, vc = k, v  # as the cache holds them: rows as wide as `row`
        if row > d:
            tail = ((0, 0),) * 3 + ((0, row - d),)
            kc, vc = jnp.pad(k, tail), jnp.pad(v, tail)
        if s > 1:
            with jax.named_scope("prefill_attention"):
                out = dot_product_attention(q, k, v, causal=True,
                                            window=self.window,
                                            q_len=prompt_len)
            if s > rows:  # a ring shorter than the call
                plen = (jnp.full((b,), s, jnp.int32) if prompt_len is None
                        else prompt_len.astype(jnp.int32))
                at = jnp.arange(rows)[None, :]
                # row r holds the last position before plen that is r mod rows
                src = jnp.clip(at + rows * ((plen[:, None] - 1 - at) // rows),
                               0, s - 1)
                kc, vc = kc[bidx, src], vc[bidx, src]
            ck.value = jax.lax.dynamic_update_slice(ck.value, kc, (0, 0, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(cv.value, vc, (0, 0, 0, 0))
            return out.astype(cfg.dtype)
        at = pos % rows if self.window else pos
        ck.value = ck.value.at[bidx, at].set(kc)
        cv.value = cv.value.at[bidx, at].set(vc)
        keys, vals = ck.value, cv.value
        if row > d and kv_bound is None:
            # (a bounded step hands the leaves over whole: its branch cuts
            # rows and head together, where the cache lies)
            keys, vals = keys[..., :d], vals[..., :d]
        from ray_tpu.ops.decode_attention import decode_attention

        out = decode_attention(q[:, 0], keys, vals, pos[:, 0] + 1,
                               kv_bound=kv_bound, live=live)
        return out[:, None].astype(cfg.dtype)


class Block(nn.Module):
    """One layer: mixer -> feed-forward, or `models/scmoe.py`'s two halves."""
    cfg: TransformerConfig
    moe: bool = False  # the feed-forward is the expert layer (is_moe_layer)
    window: int = 0  # rows of the attention's window (window_of); 0: full
    mixer: str = "mha"  # this layer's mixer (mixer_of)

    @nn.compact
    def __call__(self, x, positions, decode: bool = False, kv_bound=None,
                 prompt_len=None, live=None, ut_step: int = 0):
        cfg = self.cfg
        norm = lambda name: RMSNorm(  # noqa: E731
            cfg.norm_eps, unit_offset=cfg.norm_unit_offset, name=name)
        if cfg.moe_shortcut:
            return shortcut_layer(cfg, x, positions, decode, kv_bound, live)
        if self.mixer == "mla":
            a = MLA(cfg, name="attn")(norm("attn_norm")(x), positions, decode,
                                      kv_bound=kv_bound, live=live)
        elif self.mixer == "kda":
            # (a state is read and written whole: no notice of `kv_bound`)
            a = KDA(cfg, name="attn")(norm("attn_norm")(x), decode=decode,
                                      prompt_len=prompt_len)
        elif self.mixer == "eva":
            # (its two leaves' stops are the positions' own: `kv_bound` says
            # only whether the step's walk is bounded)
            a = EVA(cfg, name="attn")(
                norm("attn_norm")(x), positions, decode=decode,
                bounded=kv_bound is not None, prompt_len=prompt_len,
                live=live)
        else:
            a = attention_of(cfg)(cfg, window=self.window, name="attn")(
                norm("attn_norm")(x), positions, decode=decode, live=live,
                kv_bound=kv_bound, prompt_len=prompt_len, ut_step=ut_step)
        x = x + (norm("post_attn_norm")(a) if cfg.sandwich_norm else a)
        h = norm("mlp_norm")(x)
        f = (MoE(cfg, name="moe")(h, serving=decode) if self.moe
             else SwiGLU(cfg, name="mlp")(h))
        return x + (norm("post_mlp_norm")(f) if cfg.sandwich_norm else f)


def output_head(module: nn.Module, cfg: TransformerConfig, x, emb):
    """Logits [B, S, vocab] (f32) of final hidden states, by the embedding
    when the head is tied to it, else by `lm_head` (a parameter of
    `module`, made here). Vocab-sharded matmul over tp either way."""
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            return jnp.einsum("bsd,vd->bsv", x,
                              emb.astype(cfg.dtype)).astype(jnp.float32)
        head = module.param("lm_head", nn.initializers.normal(0.02),
                            (cfg.d_model, cfg.pred_heads * cfg.vocab_size),
                            cfg.param_dtype)
        if cfg.pred_heads > 1:  # held whole; the next token's head is read
            head = head[:, :cfg.vocab_size]
        if cfg.residual_f32:
            x = x.astype(cfg.dtype)
        return jnp.einsum("bsd,dv->bsv", x,
                          head.astype(cfg.dtype)).astype(jnp.float32)


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions=None, decode: bool = False,
                 kv_bound=None, prompt_len=None, live=None):
        """tokens: [B, S] int32 -> logits [B, S, vocab] (f32). decode=True
        uses per-layer caches (flax "cache" collection): pass `positions`
        (absolute) and apply with mutable=["cache"]. A single-token decode
        step may also be told `kv_bound`, how many cache rows its longest
        sequence of interest has, and `live` [B] bool, which rows of the batch
        have an occupant (a free row's cache need not be read), and a padded
        prefill `prompt_len` [B], where prompts end (`_cached_attention`)."""
        if self.cfg.ut_steps > 1:  # a path of its own, at this file's end
            return looped_stack(self, tokens, positions, decode, kv_bound,
                                prompt_len, live)
        cfg = self.cfg
        emb = self.param("tok_emb", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        x = emb[tokens].astype(jnp.float32 if cfg.residual_f32
                               else cfg.dtype)
        if cfg.emb_scale != 1.0:
            x = x * jnp.asarray(cfg.emb_scale, cfg.dtype)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        for i in range(cfg.n_layers):
            if not decode:
                x = _seq_shard(x)
            x = Block(cfg, moe=cfg.is_moe_layer(i), window=cfg.window_of(i),
                      mixer=cfg.mixer_of(i), name=f"layer_{i}")(
                x, positions, decode=decode, kv_bound=kv_bound,
                prompt_len=prompt_len, live=live)
        x = RMSNorm(cfg.norm_eps, unit_offset=cfg.norm_unit_offset,
                    name="final_norm")(x)
        return output_head(self, cfg, x, emb)


def _seq_shard(x):
    """Sequence-parallel activation constraint between blocks: [B, S, D]
    sharded batch over (dp, fsdp) and sequence over sp. GSPMD gathers the
    sequence inside attention (Megatron-SP style); ring attention
    (ray_tpu/ops/ring_attention.py) removes that gather when enabled.

    Applied when the mesh in context has all three axes; with no mesh
    (single device) or a mesh without them (a tp-only serving mesh) there is
    nothing to constrain."""
    if not {"dp", "fsdp", "sp"} <= set(context_mesh_shape()):
        return x
    return jax.lax.with_sharding_constraint(x, P(("dp", "fsdp"), "sp", None))


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
        if name in ("wq_a", "wkv_a", "f_a", "g_a"):
            return P("fsdp", None)  # into a latent every head reads
        if name in ("f_b", "g_b") or last in ("conv_q", "conv_k", "conv_v"):
            return P(None, "tp", None)  # KDA: [rank or taps, heads, dim]
        if name == "w_beta":
            return P("fsdp", "tp")  # KDA: one beta a head
        if last in ("A_log", "dt_bias", "mu", "phi"):
            # KDA, and EVA's pooling vectors [heads, dim]: per head
            return P("tp", *(None,) * (leaf.ndim - 1))
        if moe and last in ("w_gate", "w_up"):
            return P("ep", "fsdp", "tp")  # leading [E] axis over ep
        if moe and last == "w_down":
            return P("ep", "tp", "fsdp")
        if name in ("wq", "wk", "wv", "wg"):
            return P("fsdp", "tp", None)  # heads over tp (wg: the gate's)
        if name == "wo":
            return P("tp", None, "fsdp")
        if name in ("w_gate", "w_up"):
            return P("fsdp", "tp")
        if name == "w_down":
            return P("tp", "fsdp")
        return P()  # norms etc: replicated

    return spec_tree_like(params, rule)


def loss_fn(model: Transformer, params, tokens):
    """Next-token cross entropy, mean over all positions."""
    logits = model.apply(params, tokens[:, :-1])
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


# ---------------------------------------------------------------------------
# A looped stack (`ut_steps` > 1; a looped language model's `total_ut_steps`):
# the WHOLE stack of layers applied several times to every token with ONE set
# of weights. Everything of it stands here, at the file's end: a kernel's
# program names the line that first traced a helper its body reuses (the
# ring's `pos % rows` above: `tools/lowered.py` shows it), so no line above
# may move.

def pass_leaf(name: str, ut_step: int, shape, dtype) -> tuple:
    """(the name, the initialiser) of pass `ut_step`'s cache leaf `name` in an
    "mha" layer. A looped stack applies `Attention` once a pass with the SAME
    weights to ANOTHER hidden state, so each pass keeps a K and V pair of its
    own: `k`, `v` for pass 0 (the names every other model has: its cache tree
    and its programs are what they were) and `k_<t>`, `v_<t>` for pass t,
    each a leaf of its own that the step's walk is handed where it lies (one
    leaf with a leading pass axis would have to be sliced a pass, which the
    v5e's compiler answers with a copy). Pass t never sees another pass's
    rows. The leaf's kind is its layer's whatever its pass
    (`TransformerConfig.cache_kind_of`)."""
    return (f"{name}_{ut_step}" if ut_step else name,
            lambda: jnp.zeros(shape, dtype))


class ExitGate(nn.Module):
    """A looped stack's exit gate: `sigmoid(h . w + b)` of a pass's normed
    output, the probability of leaving the loop there, [B, S] float32. One
    linear unit with a bias, the same after every pass."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        w = self.param("kernel", nn.initializers.normal(0.02),
                       (cfg.d_model,), cfg.param_dtype)
        b = self.param("bias", nn.initializers.zeros, (), cfg.param_dtype)
        logit = jnp.einsum("bsd,d->bs", h.astype(cfg.dtype),
                           w.astype(cfg.dtype),
                           preferred_element_type=jnp.float32)
        return jax.nn.sigmoid(logit + b.astype(jnp.float32))


def looped_stack(module: Transformer, tokens, positions, decode, kv_bound,
                 prompt_len, live):
    """`Transformer.__call__` for `cfg.ut_steps` > 1: `layer_i`, `final_norm`
    and `exit_gate` are made ONCE (the tree of an 8-layer model plus the
    gate) and applied `ut_steps` times: `final_norm` closes each pass, its
    output is that pass's state AND the next pass's input, the gate reads it,
    and the head reads the last pass's alone. The exit distribution `[B, S,
    ut_steps]` (p_t = lam_t prod_{s<t} (1 - lam_s), the last pass taking what
    is left) is sown into the collection `loop` for whoever makes it mutable
    (the engine's chunk program, the tests); it decides nothing here: every
    token runs every pass (a threshold under 1 is refused where the
    configuration is read, `models/published.py`).

    The passes are WRITTEN OUT, in every program, not rolled into a loop
    inside the step: whoever reads a device trace by the most often started
    operation (`benchmark/trace_reduce.py` `loop_steps`) would count four
    steps for one (PERF.md section 7 (l))."""
    cfg = module.cfg
    if cfg.mixers or cfg.moe_shortcut:
        raise NotImplementedError(
            "a looped stack (`ut_steps` > 1) keeps a pair of cache leaves a "
            "pass in `Attention` only: no other mixer is applied twice")
    emb = module.param("tok_emb", nn.initializers.normal(0.02),
                       (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
    x = emb[tokens].astype(jnp.float32 if cfg.residual_f32 else cfg.dtype)
    if cfg.emb_scale != 1.0:
        x = x * jnp.asarray(cfg.emb_scale, cfg.dtype)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                     tokens.shape)
    blocks = [Block(cfg, moe=cfg.is_moe_layer(i), window=cfg.window_of(i),
                    name=f"layer_{i}") for i in range(cfg.n_layers)]
    final_norm = RMSNorm(cfg.norm_eps, unit_offset=cfg.norm_unit_offset,
                         name="final_norm")
    gate = ExitGate(cfg, name="exit_gate")
    stay, exits = 1.0, []  # the share of a token still in the loop, [B, S]
    for t in range(cfg.ut_steps):
        for block in blocks:
            if not decode:
                x = _seq_shard(x)
            x = block(x, positions, decode=decode, kv_bound=kv_bound,
                      prompt_len=prompt_len, live=live, ut_step=t)
        x = final_norm(x)
        with jax.named_scope("exit_distribution"):
            lam = gate(x)
            exits.append(stay if t == cfg.ut_steps - 1 else stay * lam)
            stay = stay * (1.0 - lam)
    module.sow("loop", "exit_p", jnp.stack(exits, -1),
               reduce_fn=lambda _old, new: new, init_fn=lambda: None)
    return output_head(module, cfg, x, emb)


# ---------------------------------------------------------------------------
# Generation by diffusion over blocks (`block_length` > 0; a block-diffusion
# language model's `block_length`): the decoder's one departure is WHO SEES
# WHOM. Position i belongs to block i // L, and key j is visible to query i
# iff j // L <= i // L: every earlier block whole and the query's own block
# whole, the positions after it included. The logits at position i are the
# distribution of the token AT position i. Everything of it stands here, at
# the file's end, as a path of its own beside the causal one (no line above
# may move, see `looped_stack`).

@dataclass(frozen=True)
class Denoising:
    """How a block leaves the mask (the engine's chunk program reads it; the
    model's arithmetic does not): a block of `block_length` positions starts
    as `mask_token` everywhere past the prompt, a denoising forward frees the
    schedule's count of positions by confidence (`counts`), or under
    `low_confidence_dynamic` every masked position whose confidence passes
    `threshold` where those are at least as many, and a block without a mask
    is committed by one more forward."""
    steps: int
    strategy: str  # "low_confidence_dynamic" or "low_confidence_static"
    threshold: float
    mask_token: int

    def counts(self, block_length: int) -> tuple:
        """Positions a denoising forward frees at least, by its number in the
        block: `block_length // steps` each, the remainder one each over the
        first forwards."""
        base, extra = divmod(block_length, self.steps)
        return tuple(base + (t < extra) for t in range(self.steps))

    def forwards(self, block_length: int, masked: int, done: int = 0) -> int:
        """The most forwards a block still takes that has `masked` positions
        under the mask after `done` denoising forwards: those until the
        schedule's counts cover them, and the commit."""
        counts = self.counts(block_length)
        n = 0
        while masked > 0:
            masked -= counts[min(done + n, self.steps - 1)]
            n += 1
        return n + 1


def attention_of(cfg: TransformerConfig):
    """The class of an "mha" layer's attention: `Attention`, or under
    `block_length` its form whose cached steps go by blocks."""
    return BlockAttention if cfg.block_length else Attention


class BlockAttention(Attention):
    """`Attention` where visibility goes by blocks of `cfg.block_length`."""

    def _cached_attention(self, q, k, v, positions, kv_bound=None,
                          prompt_len=None, live=None, ut_step: int = 0):
        """Two forms, full leaves only (`max_seq` rows a slot, position p in
        row p).

        A prefill (`prompt_len` given: the call is a slot's first positions,
        from 0) attends over its own rows, causal BY BLOCKS
        (`dot_product_attention(blocks=L)`), and writes them. Padding behind
        the prompt's whole blocks changes nothing before it.

        A BLOCK STEP (no `prompt_len`): the call's s positions a slot stand
        at the slot's own depth, `positions[b]` = c_b .. c_b + s - 1 with c_b
        the slot's committed length. Their keys and values are written to
        rows c_b .. c_b + s - 1 FIRST, then all s queries of a slot read rows
        [0, c_b + s): the committed rows and each other, one stop a slot
        (`ops/decode_attention.py` `block_decode_attention`). Rows at and
        past c_b are visible to no other call, so a block's forwards may
        rewrite them as often as they like: the last write before the
        scheduler moves c_b on is the one that stays."""
        from ray_tpu.ops.decode_attention import block_decode_attention

        cfg = self.cfg
        if self.window or ut_step:
            raise NotImplementedError(
                "visibility by blocks is built for full layers of a stack "
                "that runs once")
        b, s = q.shape[0], q.shape[1]
        d = cfg.head_dim
        row = max(cfg.cache_row, d)
        shape = (b, cfg.max_seq, cfg.n_kv_heads, row)
        ck = self.variable("cache", "k", lambda: jnp.zeros(shape, cfg.dtype))
        cv = self.variable("cache", "v", lambda: jnp.zeros(shape, cfg.dtype))
        kc, vc = k.astype(cfg.dtype), v.astype(cfg.dtype)
        if row > d:
            tail = ((0, 0),) * 3 + ((0, row - d),)
            kc, vc = jnp.pad(kc, tail), jnp.pad(vc, tail)
        if prompt_len is not None:
            with jax.named_scope("prefill_attention"):
                out = dot_product_attention(
                    q, k.astype(cfg.dtype), v.astype(cfg.dtype), causal=True,
                    q_len=prompt_len, blocks=cfg.block_length)
            ck.value = jax.lax.dynamic_update_slice(ck.value, kc, (0, 0, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(cv.value, vc, (0, 0, 0, 0))
            return out.astype(cfg.dtype)
        pos = positions.astype(jnp.int32)
        bidx = jnp.arange(b)[:, None]
        ck.value = ck.value.at[bidx, pos].set(kc)
        cv.value = cv.value.at[bidx, pos].set(vc)
        keys, vals = ck.value, cv.value
        if row > d and kv_bound is None:
            keys, vals = keys[..., :d], vals[..., :d]
        out = block_decode_attention(q, keys, vals, pos[:, -1] + 1,
                                     kv_bound=kv_bound, live=live)
        return out.astype(cfg.dtype)
