"""Plain reference of the LongCat-Flash decoder (two latent attentions and
two dense feed-forwards a layer, ONE expert layer on a shortcut across the
second half, a softmax router whose last outputs are identity experts), for
checking what the server served from ONE chip's share of the model.

Straightforward `jax.numpy` in float32 with `jax.default_matmul_precision(
"highest")`: no cache, no kernels, no batching, one sequence at a time, and
only the EXPANDED form of the attention (keys and values of every head made
from the latent; the served decode step never makes them). Written from the
equations of `modeling_longcat_flash.py` as ISSUE 42 states them (section 1),
not from the served modules; it shares with the program only the NAMES of the
parameter tree it reads.

    layer   a0 = h  + MLA_0(norm(h; in_0));   u0 = norm(a0; post_0)
            m  = MoE(u0)                      # the shortcut branch: read here
            b0 = a0 + SwiGLU_0(u0)            # dense, ffn_hidden_size wide
            a1 = b0 + MLA_1(norm(b0; in_1));  u1 = norm(a1; post_1)
            h' = a1 + SwiGLU_1(u1) + m        # ... joined here
    MLA     c_q = norm(x W_qa); q = (c_q W_qb) * s_q, s_q = (hidden/q_rank)^0.5
            [c | k_r] = x W_kva; c_kv = norm(c) * s_kv, s_kv = (hidden/kv_rank)^0.5
            q = [q_nope | q_rope] per head; q_rope, k_r rotated (theta, plain
            frequencies, pairs (2i, 2i+1)); k_r is NOT scaled
            k_nope, v = c_kv W_kb, c_kv W_vb per head (both see the scaled c_kv)
            score = (q_nope.k_nope + q_rope.k_r) * (nope + rope)^-0.5, causal
    MoE     p = softmax over E + Z outputs of float32(u) float32(W_r)
            S = the moe_topk largest of p + bias; w_e = scaling * p_e, e in S,
            NOT renormalised
            m = sum_{e in S, e < E} w_e SwiGLU_e(u) + (sum_{e in S, e >= E} w_e) u
    share   this chip holds experts [first_expert, first_expert + held) of the
            E routed ones: the first sum runs over the selected experts that
            are held, the rest is left out, here as in the program. The
            identity part is whole: every chip computes it for its own tokens.

Departures of the served model from the published one, taken as served: the
weights are random from the seed; `kv_b_proj` is held as two head-major
halves (`wk_b`, `wv_b`); the router's matrix and bias are held in bf16.

Memory (the chip's 16 GB): the served tree is 10.35 GB in bf16, one layer in
float32 would be 4.97 GB. So each PART is a program of its own that casts what
it is given inside: an attention (0.36 GB in float32), a dense SwiGLU (0.91
GB), the expert layer (the held stack, 2.42 GB), one at a time.
"""

from __future__ import annotations

import types

#: A served greedy token may lie this far below the reference's best logit
#: (logits of standard deviation 1.57 over 16,384 tokens). It lies between
#: two readings on the chip at the published widths (PERF.md section 6,
#: PR 42): 0.0225, the worst gap of what the engine served (bf16 through a
#: bf16 latent cache; 47 of 48 tokens the reference's argmax; of 3,774
#: routing decisions that involve a held or an identity expert 74 have a
#: margin under 1e-5, so a few fall the other way than in float32), the same
#: in every run because the check's prompts and the weights are; and 0.1015,
#: the gap when the reference's latent rows `[c_kv | k_r]` are rounded to
#: float8, the nearest precision below the configuration's. 0.05 is their
#: geometric mean: twice the first, half the second. The held experts in
#: float8 read 0.0263, which no tolerance tells from the plain reading: one
#: selection in 48 reaches a held expert (12 of 768 outputs a token, 16
#: held), so this chip's share of the expert arithmetic is too small for
#: served tokens to show its precision. The identity experts' part left out
#: reads 4.93 (1 of 48 tokens the argmax): a third of all selections.
LOGIT_TOLERANCE = 0.05
#: Longest sequence (prompt + answer) the reference is asked to run.
MAX_POSITIONS = 1024

#: What `build`'s `degrade` may be. The first three set the chip's tolerance
#: (what must FAIL it); the others are the deliberate breaks of the layer's
#: wiring that the tests hold the program against.
DEGRADES = ("experts_float8", "latent_float8", "no_identity",
            "branch_from_u1", "joined_early", "no_s_q", "no_s_kv",
            "renormalised")


def build(llm: dict, degrade: str | None = None):
    """Returns the reference's functions: `run(params, tokens) -> (logits
    [S, V] float32, margins, picks)` for one sequence, where `params` is the
    served tree, `margins` is per layer each position's distance between the
    last selected and the first unselected output's `p + b` (+inf where
    neither is held here nor an identity expert) and `picks` per layer the
    selected outputs [S, k]; and its parts `attention(x, p)`, `experts(x,
    p)`, `swiglu(x, p)` and `layer(x, p)` for the tests (each casts the tree
    it is given to float32).

    `degrade` (one of `DEGRADES`): "experts_float8" rounds the operands of
    the held experts' matrix products to float8 (e4m3) and "latent_float8"
    the latent row `[c_kv | k_r]`, what the served model would give were that
    part held below bf16; "no_identity" leaves the identity experts' part
    out; "branch_from_u1" feeds the expert layer the SECOND half's
    feed-forward input, "joined_early" adds its output before the second
    attention, "no_s_q" / "no_s_kv" leave a scale out, "renormalised" makes
    the selected weights sum to the scaling factor."""
    import jax
    import jax.numpy as jnp

    if degrade is not None and degrade not in DEGRADES:
        raise ValueError(f"degrade {degrade!r} is none of {DEGRADES}")
    a = llm["arch"]
    hidden = llm["d_model"]
    q_rank, rank = a["q_lora_rank"], a["kv_lora_rank"]
    nope, rot = a["qk_nope_head_dim"], a["qk_rope_head_dim"]
    eps = a["rms_norm_eps"]
    top_k = a["moe_topk"]
    n_all, n_zero = a["n_routed_experts"], a["zero_expert_num"]
    held = llm.get("experts_held") or n_all
    first = llm.get("first_expert", 0)
    s_q = (hidden / q_rank) ** 0.5 if a.get("mla_scale_q_lora") else 1.0
    s_kv = (hidden / rank) ** 0.5 if a.get("mla_scale_kv_lora") else 1.0
    if degrade == "no_s_q":
        s_q = 1.0
    if degrade == "no_s_kv":
        s_kv = 1.0
    inv_freq = 1.0 / float(a["rope_theta"]) ** (
        jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)  # plain, no YaRN
    softmax_scale = (nope + rot) ** -0.5  # no mscale

    def f32(tree):
        return jax.tree.map(lambda t: t.astype(jnp.float32), tree)

    def low(x, part):
        if part is None or degrade != part:
            return x
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def rmsnorm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * scale

    def rope(x, pos):  # x [S, H, rot]; dims (2i, 2i+1) are a pair
        s, h, d = x.shape
        # view(..., d/2, 2).transpose -> [evens | odds], then rotate-half
        x = x.reshape(s, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(s, h, d)
        ang = pos[:, None].astype(jnp.float32) * inv_freq  # [S, d/2]
        emb = jnp.concatenate([ang, ang], -1)
        cos, sin = jnp.cos(emb)[:, None], jnp.sin(emb)[:, None]
        rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
        return x * cos + rotated * sin

    def swiglu_of(x, w_gate, w_up, w_down, part=None):
        x, w_gate, w_up, w_down = (low(t, part)
                                   for t in (x, w_gate, w_up, w_down))
        hidden_ = jax.nn.silu(x @ w_gate) * (x @ w_up)
        return low(hidden_, part) @ w_down

    def swiglu(x, p):
        p = f32(p)
        return swiglu_of(x, *(p[k]["kernel"] for k in ("w_gate", "w_up",
                                                       "w_down")))

    def attention(x, p):
        p = f32(p)
        pos = jnp.arange(x.shape[0])
        c_q = rmsnorm(x @ p["wq_a"]["kernel"], p["q_norm"]["scale"])
        q = jnp.einsum("sr,rhk->shk", c_q, p["wq_b"]["kernel"]) * s_q
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos)
        kv = x @ p["wkv_a"]["kernel"]
        c_kv = rmsnorm(kv[:, :rank], p["kv_norm"]["scale"]) * s_kv
        k_rope = rope(kv[:, None, rank:], pos)  # [S, 1, rot]: NOT scaled
        c_kv, k_rope = low(c_kv, "latent_float8"), low(k_rope,
                                                       "latent_float8")
        # Departure: served `wk_b`, `wv_b` are the two halves of the
        # published `kv_b_proj`, stored [heads, rank, dim].
        k_nope = jnp.einsum("tc,hcn->thn", c_kv, p["wk_b"])
        v = jnp.einsum("tc,hcv->thv", c_kv, p["wv_b"])
        scores = (jnp.einsum("qhn,thn->hqt", q_nope, k_nope)
                  + jnp.einsum("qhr,tr->hqt", q_rope, k_rope[:, 0])
                  ) * softmax_scale
        causal = pos[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        out = jnp.einsum("hqt,thv->qhv", probs, v)
        return jnp.einsum("qhv,hvd->qd", out, p["wo"]["kernel"])

    def experts(x, p):
        p = f32(p)
        # Departure: the router's matrix and bias are served in bf16.
        probs = jax.nn.softmax(jnp.einsum(
            "sd,de->se", x, p["router"],
            precision=jax.lax.Precision.HIGHEST), -1)  # over E + Z outputs
        choose = probs + p["router_bias"]  # the bias moves the selection only
        ranked = jnp.argsort(-choose, axis=-1)
        selected = ranked[:, :top_k]  # [S, k]
        w = jnp.take_along_axis(probs, selected, -1)
        if degrade == "renormalised":
            w = w / w.sum(-1, keepdims=True)
        w = w * a["routed_scaling_factor"]  # NOT renormalised
        out = jnp.zeros_like(x)
        # Each selected routed expert that is held adds w_e E_e(x); one held
        # elsewhere adds nothing. (A loop over the held experts, each applied
        # to the rows that selected it: the same sum.)
        for e in range(held):
            w_e = jnp.sum(jnp.where(selected == first + e, w, 0.0), -1)
            y = swiglu_of(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e],
                          "experts_float8")
            out = out + w_e[:, None] * y
        # The identity experts (outputs E .. E + Z - 1): w_e times x itself.
        if degrade != "no_identity":
            zero_w = jnp.sum(jnp.where(selected >= n_all, w, 0.0), -1)
            out = out + zero_w[:, None] * x
        # How close the selection came to falling the other way, where that
        # would have changed this chip's sum.
        last_in, first_out = ranked[:, top_k - 1], ranked[:, top_k]
        here = lambda e: ((e >= first) & (e < first + held)  # noqa: E731
                          ) | (e >= n_all)
        gap = (jnp.take_along_axis(choose, last_in[:, None], -1)
               - jnp.take_along_axis(choose, first_out[:, None], -1))[:, 0]
        margin = jnp.where(here(last_in) | here(first_out), gap, jnp.inf)
        return out, margin, selected

    # Each part a program of its own, casting inside what it is given: at
    # most the held experts' stack is ever in float32 (the module docstring).
    attention_j, swiglu_j, experts_j = (jax.jit(attention), jax.jit(swiglu),
                                        jax.jit(experts))
    norm_j = jax.jit(lambda x, p: rmsnorm(x, p["scale"].astype(jnp.float32)))

    def layer(x, p):  # x [S, D] float32; p one layer's tree, as served
        a0 = x + attention_j(norm_j(x, p["attn_norm_0"]), p["attn_0"])
        u0 = norm_j(a0, p["mlp_norm_0"])
        b0 = a0 + swiglu_j(u0, p["mlp_0"])
        if degrade != "branch_from_u1":
            m, margin, picks = experts_j(u0, p["moe"])  # read here ...
        if degrade == "joined_early":
            b0 = b0 + m
        a1 = b0 + attention_j(norm_j(b0, p["attn_norm_1"]), p["attn_1"])
        u1 = norm_j(a1, p["mlp_norm_1"])
        if degrade == "branch_from_u1":
            m, margin, picks = experts_j(u1, p["moe"])
        out = a1 + swiglu_j(u1, p["mlp_1"])
        if degrade != "joined_early":
            out = out + m  # ... joined here
        return out, margin, picks

    head_j = jax.jit(lambda x, scale, w: rmsnorm(
        x, scale.astype(jnp.float32)) @ w.astype(jnp.float32))  # untied

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = params["tok_emb"][jnp.asarray(tokens)].astype(jnp.float32)
            margins, picks = [], []
            for i in range(llm["n_layers"]):
                x, margin, picked = layer(x, params[f"layer_{i}"])
                margins.append(margin)
                picks.append(picked)
            return (head_j(x, params["final_norm"]["scale"],
                           params["lm_head"]), margins, picks)

    def in_highest(fn):
        def wrapped(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return wrapped

    return types.SimpleNamespace(
        run=run, attention=in_highest(attention_j),
        experts=in_highest(experts_j), swiglu=in_highest(swiglu_j),
        layer=in_highest(layer))


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
    reference's best logit each served token's reference logit lies, the
    smallest routing margin among the decisions that involve a held or an
    identity expert, and the share of selections that fell on an identity
    expert."""
    import time

    import jax
    import numpy as np

    t0 = time.monotonic()
    params = served_params(llm)
    run = build(llm, degrade).run
    n_all = llm["arch"]["n_routed_experts"]
    rows = []
    # Every case is padded to one length, so that each program is built
    # once; attention is causal, so the padding changes no row before it.
    width = min(MAX_POSITIONS, max(len(p) + len(t) for p, t in cases))
    width = -(-width // 128) * 128
    for prompt, tokens in cases:
        seq = (list(prompt) + list(tokens))[:width]
        n = len(seq) - len(prompt)
        seq = seq + [0] * (width - len(seq))
        out, margins, picks = run(params, np.asarray(seq, np.int32))
        out = np.asarray(out)
        at = np.arange(n) + len(prompt) - 1  # row that predicts token j
        rows_logits = out[at]
        gaps = rows_logits.max(-1) - rows_logits[np.arange(n), tokens[:n]]
        top2 = np.sort(rows_logits, -1)[:, -2:]
        used = len(prompt) + n  # the padding's routing decides nothing
        margin = np.stack([np.asarray(m)[:used] for m in margins])
        picked = np.stack([np.asarray(p)[:used] for p in picks])
        rows.append({"plen": len(prompt), "n": int(n),
                     "finite": bool(np.isfinite(out).all()),
                     "max_gap": float(gaps.max()),
                     "argmax_matches": int((gaps == 0).sum()),
                     "mean_top2_margin": float((top2[:, 1] - top2[:, 0])
                                                .mean()),
                     "logit_std": float(rows_logits.std()),
                     "zero_pick_share": float((picked >= n_all).mean()),
                     "min_route_margin": (float(margin.min())
                                          if np.isfinite(margin.min())
                                          else None),
                     "route_decisions_here": int(np.isfinite(margin).sum()),
                     "route_margins_under_1e-5": int((margin < 1e-5).sum())})
    dev = jax.devices()[0]
    return {"rows": rows, "tolerance": LOGIT_TOLERANCE,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "seconds": time.monotonic() - t0}
