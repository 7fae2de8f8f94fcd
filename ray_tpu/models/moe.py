"""The expert layer: a router over all the experts a model publishes, and
the SwiGLU experts this device holds.

One layer serves the uses the repo has:

- the training dry-run's top-2 softmax mixture, every expert held and the
  leading `[E]` axis of the expert weights sharded over the `ep` mesh axis
  (`TransformerConfig(moe_experts=4)`);
- a served share of a large model (DeepSeek-V3 / Kimi K2 routing): sigmoid
  scores, selection by score plus a learnt correction bias, weights the
  scores themselves normalised over the selected and scaled, a shared expert
  beside the routed ones, and `experts_held` of the published experts on
  this device, `[first_expert, first_expert + experts_held)`. The router
  keeps its published width; what experts held elsewhere would add is left
  out, and that partial result goes on. No row is dropped for any routing.
  Four configurations are of this kind (Kimi K2, Trinity-Mini, Kimi Linear);
- the fifth, a router with identity experts (LongCat-Flash: `moe_zero_experts`
  outputs after the `moe_experts` routed ones, softmax over all of them,
  weights not renormalised): a selection at or above `moe_experts` adds its
  weight times the layer's input and computes nothing, so the number of real
  experts a token uses varies from token to token. Every device computes the
  identity part for its own tokens, like a shared expert; `experts_held` and
  `first_expert` index the routed experts only.

Three ways to the same sum, chosen by what the call can observe:

- dense: every held expert over every row, unselected rows weighted 0.
  Right for a decode step (a few rows a held expert, the weights are read
  once either way), and the only one that differentiates and shards over
  `ep` under GSPMD, so training takes it.
- grouped: rows are gathered per expert into tiles of `moe_group_tile` rows
  and a loop runs over the tiles that hold any, so the work follows the rows
  routed here, not held experts x rows. The serving prefill takes it.
- occupied (`ops/expert_decode.py`, a Pallas kernel): a decode step on the
  chip whose weight reads follow the held experts that got a row; all rows
  through each of those, the dense arm's sum without its zero terms.
  `occupied_refusal` is the rule and says whom it serves and why.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.layers import SwiGLU
from ray_tpu.ops import attention as rule
from ray_tpu.ops.expert_decode import occupied_experts, occupied_refusal


def route(cfg, x, router, bias):
    """Which experts each row selects and with what weight: `(idx, w)`,
    both `[..., k]`, over all the router's outputs (`cfg.moe_experts` routed
    experts, then `cfg.moe_zero_experts` identity ones). float32 all
    through: a near-tie decided in bf16 is another model."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif cfg.moe_scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown moe_scoring {cfg.moe_scoring!r}")
    # noaux_tc: the bias moves the selection only, never the weights.
    choose = scores if bias is None else scores + bias.astype(jnp.float32)
    k = min(cfg.moe_top_k, cfg.moe_experts + cfg.moe_zero_experts)
    _, idx = jax.lax.top_k(choose, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.moe_norm_topk and k > 1:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.moe_routed_scale


class MoE(nn.Module):
    cfg: "TransformerConfig"  # noqa: F821 - models/transformer.py

    @nn.compact
    def __call__(self, x, serving: bool = False):
        """x: [B, S, D]. `serving` says no gradient is wanted and nothing is
        sharded over `ep`, so many rows may take the grouped path."""
        cfg = self.cfg
        e_all, dm = cfg.moe_experts + cfg.moe_zero_experts, cfg.d_model
        held = cfg.held_experts
        ff = cfg.moe_d_ff or cfg.d_ff
        per_expert = nn.initializers.lecun_normal(batch_axis=(0,))
        router = self.param("router", nn.initializers.normal(0.02),
                            (dm, e_all), jnp.float32)
        # The correction bias, random here as every weight: a few percent of
        # the scores' spread, as the trained one that balances the load is. A
        # sigmoid's scores are O(1): 0.01; a softmax's average 1 / outputs, and
        # at 0.01 beside them the largest biases would be every token's choice.
        bias = (self.param("router_bias", nn.initializers.normal(
                    0.01 if cfg.moe_scoring == "sigmoid" else 1.0 / e_all),
                    (e_all,), jnp.float32)
                if cfg.moe_score_bias else None)
        w_gate = self.param("w_gate", per_expert, (held, dm, ff),
                            cfg.param_dtype).astype(cfg.dtype)
        w_up = self.param("w_up", per_expert, (held, dm, ff),
                          cfg.param_dtype).astype(cfg.dtype)
        w_down = self.param("w_down", per_expert, (held, ff, dm),
                            cfg.param_dtype).astype(cfg.dtype)
        with jax.named_scope("moe_router"):
            idx, w = route(cfg, x, router, bias)
            local = idx - cfg.first_expert  # [B, S, k]; held iff in [0, held)
            onehot = jax.nn.one_hot(local, held, dtype=jnp.float32)
            if not self.is_initializing():
                # Rows routed to each held expert, for the engine's counters
                # (collected only where "stats" is mutable).
                rows_here = onehot.sum((0, 1, 2)).astype(jnp.int32)
                self.sow("stats", "expert_rows", rows_here,
                         reduce_fn=lambda a, b: a + b,
                         init_fn=lambda: jnp.zeros((held,), jnp.int32))
        xc = x.astype(cfg.dtype)
        rows = x.shape[0] * x.shape[1]
        fetched = None  # every held expert, unless the arm says otherwise
        with jax.named_scope("moe_experts"):
            if serving and rows > 2 * cfg.moe_group_tile:
                y = _grouped(xc.reshape(rows, dm), local.reshape(rows, -1),
                             w.reshape(rows, -1), w_gate, w_up, w_down,
                             cfg.moe_group_tile)
                y = y.reshape(x.shape).astype(cfg.dtype)
            elif occupied_refusal(
                    x.shape, ff, dtype=cfg.dtype,
                    serving=serving and not self.is_initializing()) is None:
                rule.state_once("expert decode step: occupied-experts "
                                "Pallas kernel")
                gates = jnp.einsum("bsk,bske->bse", w, onehot)
                # (y comes in cfg.dtype: the kernel makes the dense arm's
                # roundings itself, where no compiler can take one away)
                y, fetched = occupied_experts(
                    xc.reshape(rows, dm), gates.reshape(rows, held),
                    rows_here, w_gate, w_up, w_down)
                y = y.reshape(x.shape)
            else:
                gates = jnp.einsum("bsk,bske->bse", w, onehot)
                gate_h = nn.silu(jnp.einsum("bsd,edf->ebsf", xc, w_gate))
                up_h = jnp.einsum("bsd,edf->ebsf", xc, w_up)
                expert_out = jnp.einsum("ebsf,efd->ebsd", gate_h * up_h,
                                        w_down)
                y = jnp.einsum("ebsd,bse->bsd", expert_out,
                               gates.astype(cfg.dtype))
        if not self.is_initializing():
            # `moe_touched` and the kernel's order are made from the ONE
            # `rows_here`, so what a step says it touched is what it read
            self.sow("stats", "picks",
                     zero_counts(cfg, idx, rows_here, fetched),
                     reduce_fn=lambda a, b: a + b,
                     init_fn=lambda: jnp.zeros((4,), jnp.int32))
        if cfg.moe_zero_experts:
            with jax.named_scope("moe_zero_experts"):
                # ONE weighted x: the sum of the identity selections' weights
                zero_w = jnp.sum(jnp.where(idx >= cfg.moe_experts, w, 0.0), -1)
                y = (y.astype(jnp.float32) + zero_w[..., None]
                     * x.astype(jnp.float32)).astype(cfg.dtype)
        if cfg.moe_shared_experts:
            with jax.named_scope("shared_expert"):
                y = y + SwiGLU(cfg, d_ff=ff * cfg.moe_shared_experts,
                               name="shared")(x)
        return y


def zero_counts(cfg, idx, rows_here, fetched=None):
    """[selections made, those that fell on an identity expert, held experts
    that got at least one row (`rows_here`: the rows of each), held experts
    whose weights the call's arm read (`fetched`; None: all of them, the
    dense arm's)] of one call, int32: the engine's counters `moe_picks`,
    `moe_zero_picks`, `moe_touched`, `moe_fetched`. One less the last over
    held experts x layers x steps is the share of them a step skipped."""
    return jnp.stack([
        jnp.asarray(idx.size, jnp.int32),
        jnp.sum(idx >= cfg.moe_experts, dtype=jnp.int32),
        jnp.sum(rows_here > 0, dtype=jnp.int32),
        jnp.asarray(rows_here.size if fetched is None else fetched,
                    jnp.int32)])


def _grouped(x, local, w, w_gate, w_up, w_down, tile: int):
    """sum_j w[n, j] * E_{local[n, j]}(x[n]) over the held selections (local
    in [0, held)), with the work in tiles of `tile` rows of one expert each.
    x [N, D]; local, w [N, k]. Returns float32 [N, D].

    Every (row, selection) pair that is held gets a place in a layout where
    expert g owns ceil(rows_g / tile) whole tiles, in expert order; a loop
    over the occupied tiles gathers a tile's rows, runs its expert and adds
    the weighted result to the rows' sums. Shapes are static (the layout has
    room for every pair plus one ragged tile an expert), the trip count is
    not: it is the number of tiles in use."""
    n, k = local.shape
    held = w_gate.shape[0]
    pairs = n * k
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(pairs)
    onehot = jax.nn.one_hot(key, held, dtype=jnp.int32)  # not held: zeros
    upto = jnp.cumsum(onehot, axis=0)  # [pairs, held]
    group = jnp.minimum(key, held - 1)
    rank = jnp.take_along_axis(upto, group[:, None], axis=1)[:, 0] - 1
    tiles = (upto[-1] + tile - 1) // tile  # tiles each expert owns
    tile_end = jnp.cumsum(tiles)
    max_tiles = pairs // tile + held
    place = jnp.where(key < held,
                      (tile_end - tiles)[group] * tile + rank,
                      max_tiles * tile)  # out of range: dropped
    pair_at = jnp.full((max_tiles * tile,), -1, jnp.int32).at[place].set(
        jnp.arange(pairs, dtype=jnp.int32), mode="drop")
    tile_expert = jnp.searchsorted(tile_end, jnp.arange(max_tiles),
                                   side="right")
    w_flat = w.reshape(pairs)

    def one_tile(i, out):
        at = jax.lax.dynamic_slice(pair_at, (i * tile,), (tile,))
        pair = jnp.maximum(at, 0)
        tok = pair // k
        g = tile_expert[i]
        xg = x[tok]  # [tile, D]
        h = nn.silu(xg @ w_gate[g]) * (xg @ w_up[g])
        y = (h @ w_down[g]).astype(jnp.float32)
        weight = jnp.where(at >= 0, w_flat[pair], 0.0)
        # (an empty place adds 0 to row 0; a row is in a tile at most once)
        return out.at[tok].add(y * weight[:, None])

    return jax.lax.fori_loop(0, tile_end[-1], one_tile,
                             jnp.zeros(x.shape, jnp.float32))
