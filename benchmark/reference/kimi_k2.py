"""Plain reference of the Kimi K2 decoder (the DeepSeek-V3 block), for
checking what the server served from ONE chip's share of the model.

Straightforward `jax.numpy` in float32 with `jax.default_matmul_precision(
"highest")`: no cache, no kernels, no batching, one sequence at a time, and
only the EXPANDED form of the attention (keys and values of every head made
from the latent; the served decode step never makes them). Written from the
equations of `modeling_deepseek.py` as ISSUE 28 states them, not from the
served modules; it shares with the program only the NAMES of the parameter
tree it reads.

    block   h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    MLA     c_q = RMSNorm(x W_qa); q = c_q W_qb -> heads of [q_nope | q_rope]
            [c_kv | k_r] = x W_kva; c_kv = RMSNorm(c_kv); k_r = RoPE(k_r)
            k_nope, v = c_kv W_kb, c_kv W_vb per head
            score = (q_nope.k_nope + RoPE(q_rope).k_r) * s, causal softmax
            s = (nope + rope)^-0.5 * m^2, m = 0.1 * mscale_all_dim * ln(factor) + 1
    RoPE    theta over the rope dims with YaRN's blended frequencies;
            interleaved pairs, de-interleaved before the rotation as
            DeepSeek's `apply_rotary_pos_emb` does
    FFN     layer < first_k_dense_replace: SwiGLU(intermediate_size)
            else sum_i w_i E_i(x) + E_shared(x): s = sigmoid(x W_r), the
            num_experts_per_tok largest s + b are selected, w_i = s_i / sum
            of the selected s, times routed_scaling_factor
    share   this chip holds experts [first_expert, first_expert + held): the
            sum runs over the selected experts that are held, the rest is
            left out, here as in the program

Departures of the served model from the published one, taken as served: the
weights are random from the seed; `kv_b_proj` is held as two head-major
halves (`wk_b`, `wv_b`); text only.

It reads the parameter tree the server itself builds (the program's
`Transformer.init` from the configuration's seed, held in bf16) and casts one
layer at a time up to float32: an expert layer is 2.7 GB in float32, the
whole model would be 16.7 GB.
"""

from __future__ import annotations

import math
import types

#: A served greedy token may lie this far below the reference's best logit
#: (logits of standard deviation 1.69 over 20,480 tokens). It lies between
#: two readings on the chip (PERF.md section 6, PR 28): 0.0973, the worst gap
#: of what the engine served at the published widths (bf16 through a bf16
#: latent cache, and of 274 routing decisions that involve a held expert 73
#: have a margin under 1e-3, so a few fall the other way than in float32:
#: one expert's weighted output at one position, six bf16 steps at these
#: logits), the same in every run because the check's prompts and the
#: weights are; and 0.40, the gap when the reference's experts are computed
#: in float8, the nearest precision below the configuration's (attention in
#: float8: 1.22; a wrong expert or a wrong latent row: PERF.md). 0.2 is their
#: geometric mean: twice the first, half the second.
LOGIT_TOLERANCE = 0.2
#: Longest sequence (prompt + answer) the reference is asked to run.
MAX_POSITIONS = 1024


def yarn_inv_freq(dim: int, theta: float, sc: dict):
    """`DeepseekV3YarnRotaryEmbedding`: extrapolated and interpolated
    inverse frequencies blended by a linear ramp between the correction
    dims of beta_fast and beta_slow."""
    import jax.numpy as jnp

    exps = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    freq_extra = 1.0 / theta ** exps
    freq_inter = 1.0 / (sc["factor"] * theta ** exps)
    orig = sc["original_max_position_embeddings"]

    def correction_dim(n_rot):
        return dim * math.log(orig / (n_rot * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def build(llm: dict, degrade: str | None = None):
    """Returns the reference's functions: `run(params, tokens) -> (logits
    [S, V] float32, margins)` for one sequence, where `params` is the served
    tree and `margins` is, per expert layer, each position's distance
    between the last selected and the first unselected expert's `s + b`,
    +inf where neither is held here; and its parts `attention(x, p)`,
    `experts(x, p)` and `layer(x, p)` on float32 trees, for the tests.

    `degrade` is only for setting the tolerance, by what must FAIL it:
    "attention" or "experts" rounds the operands of that part's matrix
    products to float8 (e4m3), what the served model would give were that
    part computed below bf16; "wrong_expert" gives every selected row the
    held expert after its own, "wrong_row" attends to the latent of the
    position before."""
    import jax
    import jax.numpy as jnp

    a = llm["arch"]
    rank, nope, rot = (a["kv_lora_rank"], a["qk_nope_head_dim"],
                       a["qk_rope_head_dim"])
    eps = a["rms_norm_eps"]
    sc = a["rope_scaling"]
    top_k = a["num_experts_per_tok"]
    n_all = a["n_routed_experts"]
    held = llm.get("experts_held") or n_all
    first = llm.get("first_expert", 0)
    inv_freq = yarn_inv_freq(rot, float(a["rope_theta"]), sc)
    cos_sin_scale = (mscale(sc["factor"], sc["mscale"])
                     / mscale(sc["factor"], sc["mscale_all_dim"]))
    m = mscale(sc["factor"], sc["mscale_all_dim"])
    softmax_scale = (nope + rot) ** -0.5 * m * m

    def low(x, part):
        if degrade is None or degrade != part:
            return x
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    wrong_expert = 1 if degrade == "wrong_expert" else 0

    def rmsnorm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * scale

    def rope(x, pos):  # x [S, H, rot]
        s, h, d = x.shape
        # DeepSeek: view(..., d/2, 2).transpose -> [evens | odds], then the
        # rotate-half form.
        x = x.reshape(s, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(s, h, d)
        ang = pos[:, None].astype(jnp.float32) * inv_freq  # [S, d/2]
        emb = jnp.concatenate([ang, ang], -1)
        cos = (jnp.cos(emb) * cos_sin_scale)[:, None]
        sin = (jnp.sin(emb) * cos_sin_scale)[:, None]
        rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
        return x * cos + rotated * sin

    def swiglu(x, p, part=None):
        w = {k: low(p[k]["kernel"], part) for k in ("w_gate", "w_up",
                                                    "w_down")}
        x = low(x, part)
        hidden = jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])
        return low(hidden, part) @ w["w_down"]

    def attention(x, p):
        s = x.shape[0]
        pos = jnp.arange(s)
        c_q = rmsnorm(x @ p["wq_a"]["kernel"], p["q_norm"]["scale"])
        q = jnp.einsum("sr,rhk->shk", c_q, p["wq_b"]["kernel"])
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos)
        kv = x @ p["wkv_a"]["kernel"]
        c_kv = rmsnorm(kv[:, :rank], p["kv_norm"]["scale"])
        if degrade == "wrong_row":
            c_kv = jnp.roll(c_kv, 1, axis=0)
        k_rope = rope(kv[:, None, rank:], pos)  # [S, 1, rot]: all heads
        # Departure: served `wk_b`, `wv_b` are the two halves of the
        # published `kv_b_proj`, stored [heads, rank, dim].
        k_nope = jnp.einsum("tc,hcn->thn", c_kv, p["wk_b"])
        v = jnp.einsum("tc,hcv->thv", c_kv, p["wv_b"])
        q_nope, q_rope, k_nope, k_rope, v = (
            low(t, "attention") for t in (q_nope, q_rope, k_nope, k_rope, v))
        scores = (jnp.einsum("qhn,thn->hqt", q_nope, k_nope)
                  + jnp.einsum("qhr,tr->hqt", q_rope, k_rope[:, 0])
                  ) * softmax_scale
        causal = pos[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        out = jnp.einsum("hqt,thv->qhv", low(probs, "attention"), v)
        return jnp.einsum("qhv,hvd->qd", out, p["wo"]["kernel"])

    def experts(x, p):
        s = jax.nn.sigmoid(jnp.einsum(
            "sd,de->se", x, p["router"], precision=jax.lax.Precision.HIGHEST))
        choose = s + p["router_bias"]
        ranked = jnp.argsort(-choose, axis=-1)
        selected = ranked[:, :top_k]  # [S, k]
        w = jnp.take_along_axis(s, selected, -1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * a["routed_scaling_factor"]
        out = jnp.zeros_like(x)
        # Each selected expert that is held adds w_i E_i(x); one absent adds
        # nothing. (Written as a loop over the held experts, each applied to
        # the rows that selected it: the same sum.)
        for e in range(held):
            w_e = jnp.sum(jnp.where(selected == first + e, w, 0.0), -1)
            y = swiglu(x, {k: {"kernel": p[k][(e + wrong_expert) % held]}
                           for k in ("w_gate", "w_up", "w_down")}, "experts")
            out = out + w_e[:, None] * y
        out = out + swiglu(x, p["shared"], "experts")
        # How close the selection came to falling the other way, where that
        # would have changed this chip's sum.
        last_in, first_out = ranked[:, top_k - 1], ranked[:, top_k]
        here = lambda e: (e >= first) & (e < first + held)  # noqa: E731
        gap = (jnp.take_along_axis(choose, last_in[:, None], -1)
               - jnp.take_along_axis(choose, first_out[:, None], -1))[:, 0]
        margin = jnp.where(here(last_in) | here(first_out), gap, jnp.inf)
        return out, margin

    def layer(x, p):  # x [S, D] float32; p one layer's tree, as served
        p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
        h = x + attention(rmsnorm(x, p["attn_norm"]["scale"]), p["attn"])
        normed = rmsnorm(h, p["mlp_norm"]["scale"])
        if "moe" in p:
            y, margin = experts(normed, p["moe"])
        else:
            y, margin = swiglu(normed, p["mlp"]), None
        return h + y, margin

    def head(x, final_scale, w):
        x = rmsnorm(x, final_scale.astype(jnp.float32))
        return x @ w.astype(jnp.float32)  # untied, as published

    layer_j, head_j = jax.jit(layer), jax.jit(head)

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = params["tok_emb"][jnp.asarray(tokens)].astype(jnp.float32)
            margins = []
            for i in range(llm["n_layers"]):
                x, margin = layer_j(x, params[f"layer_{i}"])
                if margin is not None:
                    margins.append(margin)
            return (head_j(x, params["final_norm"]["scale"],
                           params["lm_head"]), margins)

    return types.SimpleNamespace(run=run, attention=attention,
                                 experts=experts, layer=layer)


def served_params(llm: dict):
    """The tree the engine serves: the program's own `Transformer.init` from
    the configuration's seed, each leaf cast to the serving dtype inside the
    one program that makes it (as `ContinuousEngine` does)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.engine import model_config
    from ray_tpu.models.transformer import Transformer

    cfg = LLMConfig(**llm)
    net = Transformer(model_config(cfg))
    to = jnp.dtype(cfg.dtype)

    def make(key):
        params = net.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        return jax.tree.map(
            lambda x: x.astype(to) if x.dtype == jnp.float32 else x, params)

    return jax.jit(make)(jax.random.PRNGKey(cfg.seed))


def check(llm: dict, cases: list, degrade: str | None = None) -> dict:
    """For each served greedy (prompt, tokens): how far below the
    reference's best logit each served token's reference logit lies, and the
    smallest routing margin among the decisions that involve a held
    expert."""
    import time

    import jax
    import numpy as np

    t0 = time.monotonic()
    params = served_params(llm)
    run = build(llm, degrade).run
    rows = []
    # Every case is padded to one length, so that each program is built
    # once; attention is causal, so the padding changes no row before it.
    width = min(MAX_POSITIONS, max(len(p) + len(t) for p, t in cases))
    width = -(-width // 128) * 128
    for prompt, tokens in cases:
        seq = (list(prompt) + list(tokens))[:width]
        n = len(seq) - len(prompt)
        seq = seq + [0] * (width - len(seq))
        out, margins = run(params, np.asarray(seq, np.int32))
        out = np.asarray(out)
        at = np.arange(n) + len(prompt) - 1  # row that predicts token j
        rows_logits = out[at]
        gaps = rows_logits.max(-1) - rows_logits[np.arange(n), tokens[:n]]
        top2 = np.sort(rows_logits, -1)[:, -2:]
        used = len(prompt) + n  # the padding's routing decides nothing
        margin = np.stack([np.asarray(m)[:used] for m in margins])
        rows.append({"plen": len(prompt), "n": int(n),
                     "finite": bool(np.isfinite(out).all()),
                     "max_gap": float(gaps.max()),
                     "argmax_matches": int((gaps == 0).sum()),
                     "mean_top2_margin": float((top2[:, 1] - top2[:, 0])
                                                .mean()),
                     "logit_std": float(rows_logits.std()),
                     "min_route_margin": (float(margin.min())
                                          if np.isfinite(margin.min())
                                          else None),
                     "route_decisions_here": int(np.isfinite(margin).sum()),
                     "route_margins_under_1e-3": int((margin < 1e-3).sum())})
    dev = jax.devices()[0]
    return {"rows": rows, "tolerance": LOGIT_TOLERANCE,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "seconds": time.monotonic() - t0}
