"""Plain reference of the Trinity decoder (`model_type` `afmoe`), for checking
what the server served from ONE chip's share of the model.

Straightforward `jax.numpy` in float32 with `jax.default_matmul_precision(
"highest")`: no cache, no ring, no kernels, no batching, one sequence at a
time, the full `[S, S]` mask of every layer with the window written as
`0 <= i - j < W`, eight query heads (one key/value head's group) at a time so
that 5,000 positions fit beside the weights. Written from the equations of
ISSUE 32 (the config's keys and the published `modeling_afmoe.py`), not from
the served modules; it shares with the program only the NAMES of the
parameter tree it reads. d = hidden size, position p:

    x_0     = E[token] * sqrt(d)                                (mup_enabled)
    a       = RMSNorm_in(x)
    q, k, v = a W_q [H, 128], a W_k [KV, 128], a W_v [KV, 128];  g = a W_g [H, 128]
    q, k    = RMSNorm_q(q), RMSNorm_k(k)            per head, over its 128 dims
    sliding layer: q, k = RoPE(q, k; p, theta, rotate-half over all 128 dims)
    full layer:    no rotation
    head h reads key/value head h // (H / KV); score = q.k / sqrt(128); causal;
    sliding layer: key j is visible to query i iff 0 <= i - j < W
    o       = (softmax(score) v * sigmoid(g)) W_o
    x       = x + RMSNorm_post_attn(o)
    m       = RMSNorm_pre_mlp(x)
    l < num_dense_layers:  f = SwiGLU(m)
    else:   s = sigmoid(m W_r) in float32;  sel = top-k of (s + expert_bias)
            w_e = s_e / (sum_sel s + 1e-20) * route_scale
            f = SwiGLU_shared(m) + sum_{e in sel} w_e SwiGLU_e(m)
    x       = x + RMSNorm_post_mlp(f)
    logits  = RMSNorm_final(x_L) W_head                              (untied)
    share   this chip holds experts [first_expert, first_expert + held): the
            sum runs over the selected experts that are held, the rest is
            left out, here as in the program; the vocabulary is a slice

Departures of the served model from the published one, taken as served: the
weights are random from the seed; text only; any fused matrix is held apart.

It reads the parameter tree the server itself builds (the program's
`Transformer.init` from the configuration's seed, held in bf16) and casts one
layer at a time up to float32. What a run costs on the v5e is compiling, not
computing (a layer of 5,120 positions runs in 0.2 s, each kind of layer
compiles in 18-28 s: my chip run, PR 32), so every case is padded to ONE
width: three programs (window + dense, window + experts, full + experts)
whatever the cases. The head is applied to the rows that predict a served
token only.
"""

from __future__ import annotations

import types

#: A served greedy token may lie this far below the reference's best logit
#: (logits of standard deviation 0.90 over 25,024 tokens). It lies between
#: two readings on the chip (PERF.md section 6, PR 32): 0.3602, the worst gap
#: of what the engine served at the published widths (the prompt of 2040;
#: 0.0962 for the prompt of 5000), the same in every run because the check's
#: prompts and the weights are; and 0.4366, the gap when the reference's keys
#: and values are rounded to float8 as a float8 cache would hold them, the
#: nearest precision below the configuration's. 0.40 is their geometric mean.
#: The first reading is large beside Kimi's 0.0973 for a reason: 640 of 6,094
#: routing decisions that involve a held expert have a margin under 1e-3, a
#: few fall the other way in bf16, and where a chip holds 16 of 128 experts a
#: flipped decision adds or removes a token's WHOLE routed sum in that layer,
#: which the layer's own norm then brings to the size of every other
#: sublayer's output. A window of W - 1 rows (`degrade="window_minus_1"`)
#: reads 0.3602 and 0.0: one key of 2,048 moves no served token's rank, so
#: this comparison cannot see it; the CPU tests hold the window to the row
#: (`tests/test_swa_moe.py`, logits to 2e-4 at a window of 16).
LOGIT_TOLERANCE = 0.4
#: Longest sequence (prompt + answer) the reference is asked to run.
MAX_POSITIONS = 8192


def build(llm: dict, degrade: str | None = None):
    """Returns the reference's functions: `run(params, tokens) -> (logits
    [S, V] float32, margins)` for one sequence, where `params` is the served
    tree and `margins` is, per expert layer, each position's distance
    between the last selected and the first unselected expert's `s + b`,
    +inf where neither is held here; and its parts `attention(x, p, window)`,
    `experts(x, p)` and `layer(x, p, window)` on float32 trees, for the tests.

    `degrade` is only for setting the tolerance, by what must FAIL it:
    "kv_float8" rounds every key (after its norm and rotation) and value to
    float8 (e4m3), what a cache held below bf16 would give back;
    "window_minus_1" sees one row less in every window layer."""
    import jax
    import jax.numpy as jnp

    a = llm["arch"]
    d_model, heads = llm["d_model"], llm["n_heads"]
    kv_heads, hd = a["num_key_value_heads"], a["head_dim"]
    group = heads // kv_heads
    eps = a["rms_norm_eps"]
    theta = float(a["rope_theta"])
    window = int(a["sliding_window"]) - (degrade == "window_minus_1")
    sliding = [t == "sliding_attention" for t in a["layer_types"]]
    top_k = a["num_experts_per_tok"]
    n_all = a["num_experts"]
    held = llm.get("experts_held") or n_all
    first = llm.get("first_expert", 0)
    emb_scale = d_model ** 0.5 if a.get("mup_enabled") else 1.0

    def rmsnorm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * scale

    def rope(x, pos):  # x [S, H, hd]: rotate-half over all hd dims
        inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                   / hd)
        ang = pos[:, None].astype(jnp.float32) * inv_freq  # [S, hd/2]
        emb = jnp.concatenate([ang, ang], -1)[:, None]
        rotated = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
        return x * jnp.cos(emb) + rotated * jnp.sin(emb)

    def swiglu(x, p):
        hidden = (jax.nn.silu(x @ p["w_gate"]["kernel"])
                  * (x @ p["w_up"]["kernel"]))
        return hidden @ p["w_down"]["kernel"]

    def attention(x, p, win):  # win: rows of the window, 0 for a full layer
        s = x.shape[0]
        pos = jnp.arange(s)
        q = jnp.einsum("sd,dhk->shk", x, p["wq"]["kernel"])
        k = jnp.einsum("sd,dhk->shk", x, p["wk"]["kernel"])
        v = jnp.einsum("sd,dhk->shk", x, p["wv"]["kernel"])
        gate = jnp.einsum("sd,dhk->shk", x, p["wg"]["kernel"])
        q = rmsnorm(q, p["q_norm"]["scale"])
        k = rmsnorm(k, p["k_norm"]["scale"])
        if win:
            q, k = rope(q, pos), rope(k, pos)
        if degrade == "kv_float8":
            k = k.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            v = v.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        back = pos[:, None] - pos[None, :]  # i - j
        visible = (back >= 0) & (back < win) if win else back >= 0
        outs = []
        for n in range(kv_heads):  # one key/value head's queries at a time
            mine = slice(n * group, (n + 1) * group)
            scores = jnp.einsum("qhk,tk->hqt", q[:, mine], k[:, n]) / hd ** 0.5
            probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf),
                                   -1)
            outs.append(jnp.einsum("hqt,tk->qhk", probs, v[:, n]))
        out = jnp.concatenate(outs, 1) * jax.nn.sigmoid(gate)
        return jnp.einsum("qhk,hkd->qd", out, p["wo"]["kernel"])

    def experts(x, p):
        s = jax.nn.sigmoid(jnp.einsum(
            "sd,de->se", x, p["router"], precision=jax.lax.Precision.HIGHEST))
        choose = s + p["router_bias"]
        ranked = jnp.argsort(-choose, axis=-1)
        selected = ranked[:, :top_k]  # [S, k]
        w = jnp.take_along_axis(s, selected, -1)
        if a["route_norm"]:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        w = w * a["route_scale"]
        out = swiglu(x, p["shared"])
        # Each selected expert that is held adds w_e E_e(x); one absent adds
        # nothing. (A loop over the held experts, each weighted 0 on the
        # rows that did not select it: the same sum.)
        for e in range(held):
            w_e = jnp.sum(jnp.where(selected == first + e, w, 0.0), -1)
            out = out + w_e[:, None] * swiglu(
                x, {k: {"kernel": p[k][e]}
                    for k in ("w_gate", "w_up", "w_down")})
        # How close the selection came to falling the other way, where that
        # would have changed this chip's sum.
        last_in, first_out = ranked[:, top_k - 1], ranked[:, top_k]
        here = lambda e: (e >= first) & (e < first + held)  # noqa: E731
        gap = (jnp.take_along_axis(choose, last_in[:, None], -1)
               - jnp.take_along_axis(choose, first_out[:, None], -1))[:, 0]
        return out, jnp.where(here(last_in) | here(first_out), gap, jnp.inf)

    def layer(x, p, win):  # x [S, D] float32; p one layer's tree, as served
        p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
        o = attention(rmsnorm(x, p["attn_norm"]["scale"]), p["attn"], win)
        x = x + rmsnorm(o, p["post_attn_norm"]["scale"])
        m = rmsnorm(x, p["mlp_norm"]["scale"])
        if "moe" in p:
            f, margin = experts(m, p["moe"])
        else:
            f, margin = swiglu(m, p["mlp"]), None
        return x + rmsnorm(f, p["post_mlp_norm"]["scale"]), margin

    def head(x, final_scale, w):
        x = rmsnorm(x, final_scale.astype(jnp.float32))
        return x @ w.astype(jnp.float32)  # untied, as published

    # One program for each kind of layer: (window or full) x (dense or
    # expert feed-forward), whatever the layer's index.
    layer_j, head_j = jax.jit(layer, static_argnums=2), jax.jit(head)

    def run(params, tokens, rows=None):
        """Logits of `rows` (all positions when None) and the margins."""
        with jax.default_matmul_precision("highest"):
            x = (params["tok_emb"][jnp.asarray(tokens)].astype(jnp.float32)
                 * emb_scale)
            margins = []
            for i in range(llm["n_layers"]):
                x, margin = layer_j(x, params[f"layer_{i}"],
                                    window if sliding[i] else 0)
                if margin is not None:
                    margins.append(margin)
            if rows is not None:
                x = x[jnp.asarray(rows)]
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
    # Every case is padded to one width, a multiple of 128 positions, so that
    # each program is built once; attention is causal, so the padding
    # changes no row before it.
    width = min(MAX_POSITIONS, max(len(p) + len(t) for p, t in cases))
    width = -(-width // 128) * 128
    for prompt, tokens in cases:
        seq = (list(prompt) + list(tokens))[:width]
        n = len(seq) - len(prompt)
        at = np.arange(n) + len(prompt) - 1  # row that predicts token j
        rows_logits, margins = run(
            params, np.asarray(seq + [0] * (width - len(seq)), np.int32), at)
        rows_logits = np.asarray(rows_logits)
        gaps = rows_logits.max(-1) - rows_logits[np.arange(n), tokens[:n]]
        top2 = np.sort(rows_logits, -1)[:, -2:]
        used = len(prompt) + n  # the padding's routing decides nothing
        margin = np.stack([np.asarray(m)[:used] for m in margins])
        rows.append({"plen": len(prompt), "n": int(n),
                     "finite": bool(np.isfinite(rows_logits).all()),
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
