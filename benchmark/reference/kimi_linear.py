"""Plain reference of the Kimi Linear decoder (`model_type` `kimi_linear`), for
checking what the server served from ONE chip's share of the model.

Straightforward `jax.numpy` in float32 with `jax.default_matmul_precision(
"highest")`: no cache, no state hand-over, no chunks, no kernels, no batching,
one sequence at a time, one layer cast up at a time. The KDA recurrence runs
one position at a time under a scan from S = 0; the convolution is four
shifted sums; the latent attention is the expanded form under the full
`[S, S]` mask. Written from the equations of ISSUE 34 (the config's keys, the
paper arXiv:2510.26692 and the published `modeling_kimi.py` with the `fla`
operators it calls), not from the served modules; it shares with the program
only the NAMES of the parameter tree it reads. What no key of `config.json`
states is marked (+) here and listed under `assumed` in the configuration's
file. d = hidden size, eps = rms_norm_eps, layer l counted from 0 (the config
counts from 1):

    x     = E[token]                                           (no multiplier)
    a     = RMSNorm_in(x)
    KDA layer, H heads, dk = dv = head_dim, a state S in R^{H x dk x dv} a
    sequence, float32 (+), S = 0 before the first token:
      q~, k~, v~ = a W_q, a W_k, a W_v                         [H x dk each, no bias]
      q^, k^, v^ = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~))   (+)
                   conv(u)_{t,c} = sum_{j=0..K-1} w_{j,c} u_{t-(K-1)+j,c}: depthwise,
                   causal, zeros before the sequence, no bias (+), K = 4
      q = q^ / sqrt(|q^|^2 + 1e-6) * dk^-1/2 (+);  k = k^ / sqrt(|k^|^2 + 1e-6) (+);  v = v^
      g = -exp(A_log_h) * softplus(f_b(f_a(a)) + dt_bias) (+)  [H, dk], float32
      beta = sigmoid(a W_beta) (+)                             [H], float32
      S <- Diag(exp(g_t)) S
      S <- S + beta_t k_t (v_t - S^T k_t)^T
      o_t = S^T q_t
      o_t <- RMSNorm_head(o_t) * sigmoid(g_b(g_a(a_t))) (+)
      y = concat_h(o_t) W_o
    MLA layer (q_lora_rank null, mla_use_nope true):
      q_h = a W_q,h in R^{nope + rope};  [c | k_r] = a W_kva;  c <- RMSNorm_kv(c)
      k_h = [c W_kb,h | k_r]  (k_r the same for all heads, NOT rotated (+));
      v_h = c W_vb,h;  score = q_h . k_h / sqrt(nope + rope);  causal softmax
      y = concat_h(sum p v_h) W_o
    x     = x + y;   m = RMSNorm_post(x)
    l < first_k_dense_replace:  f = SwiGLU(m)
    else: s = sigmoid(m W_r) in float32;  sel = top-k of (s + e_score_correction_bias) (+)
          w_e = s_e / (sum_sel s + 1e-20) * routed_scaling_factor
          f = SwiGLU_shared(m) + sum_{e in sel} w_e SwiGLU_e(m)
    x     = x + f
    logits = RMSNorm_final(x_L) W_head                         (untied)
    share   this chip holds experts [first_expert, first_expert + held): the
            sum runs over the selected experts that are held, the rest is
            left out, here as in the program; the vocabulary is a slice

Departures of the served model from the published one, taken as served: the
weights are random from the seed; text only; any fused matrix is held apart.
"""

from __future__ import annotations

import types

#: A served greedy token may lie this far below the reference's best logit.
#: It lies between two readings on the chip (PERF.md section 6, PR 34):
#: 0.1473, the worst gap of what the engine served at the published widths
#: (the case of 2040 + 24; the same in every run because the check's prompts
#: and the weights are; bf16 activations, and of 5,638 routing decisions that
#: involve a held expert 806 have a margin under 1e-3), and 0.2309, the gap
#: when the reference holds S in bfloat16 (`degrade="state_bfloat16"`: S
#: rounded to bfloat16 after every position, the nearest precision below the
#: float32 the configuration states for it). 0.185 is their geometric mean:
#: a quarter above the first, a quarter below the second. The weights in
#: float8 (`degrade="weights_float8"`) read 1.37. Latent rows in float8
#: (`degrade="latent_float8"`) read 0.1439, UNDER the plain reading: four
#: latent layers of sixteen, and attention without a position over random
#: tokens is close to a mean of its rows, so this comparison cannot see it.
LOGIT_TOLERANCE = 0.185
#: Longest sequence (prompt + answer) the reference is asked to run.
MAX_POSITIONS = 4096


def build(llm: dict, degrade: str | None = None):
    """Returns the reference's functions: `run(params, tokens, rows) ->
    (logits float32, margins)` for one sequence, where `params` is the served
    tree and `margins` is, per expert layer, each position's distance
    between the last selected and the first unselected expert's `s + b`,
    +inf where neither is held here; and its parts `kda(x, p)`, `mla(x, p)`,
    `experts(x, p)` and `layer(x, p, mixer)` on float32 trees, for the tests.

    `degrade` is only for setting the tolerance, by what must FAIL it:
    "state_bfloat16" rounds S to bfloat16 after every position, what a state
    leaf held below float32 would give back; "latent_float8" rounds the
    latent row `[c | k_r]` to float8 (e4m3), what a latent cache held below
    bfloat16 would; "weights_float8" rounds every layer's weights to
    float8."""
    import jax
    import jax.numpy as jnp

    a = llm["arch"]
    lin = a["linear_attn_config"]
    heads_kda, dk, taps = (lin["num_heads"], lin["head_dim"],
                           lin["short_conv_kernel_size"])
    kda_layers = {i - 1 for i in lin["kda_layers"]}
    heads = llm["n_heads"]
    rank, nope, rot = (a["kv_lora_rank"], a["qk_nope_head_dim"],
                       a["qk_rope_head_dim"])
    eps = a["rms_norm_eps"]
    top_k = a["num_experts_per_token"]
    n_all = a["num_experts"]
    held = llm.get("experts_held") or n_all
    first = llm.get("first_expert", 0)
    if not a["mla_use_nope"] or a["q_lora_rank"]:
        raise ValueError("the reference is of the published model: "
                         "mla_use_nope true, q_lora_rank null")

    def rmsnorm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * scale

    def swiglu(x, p):
        hidden = (jax.nn.silu(x @ p["w_gate"]["kernel"])
                  * (x @ p["w_up"]["kernel"]))
        return hidden @ p["w_down"]["kernel"]

    def conv(u, w):  # u [S, H, dk], w [K, H, dk]: four shifted sums
        s = u.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1,) + u.shape[1:], u.dtype), u], 0)
        return sum(w[j] * padded[j:j + s] for j in range(taps))

    def kda(x, p):  # x [S, D] -> [S, D]
        unit = lambda t: t * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(t * t, -1, keepdims=True) + 1e-6)
        proj = lambda name: jnp.einsum(  # noqa: E731
            "sd,dhk->shk", x, p[name]["kernel"])
        q = unit(jax.nn.silu(conv(proj("wq"), p["conv_q"]))) * dk ** -0.5
        k = unit(jax.nn.silu(conv(proj("wk"), p["conv_k"])))
        v = jax.nn.silu(conv(proj("wv"), p["conv_v"]))
        f = jnp.einsum("sr,rhk->shk", x @ p["f_a"]["kernel"],
                       p["f_b"]["kernel"])
        g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(f + p["dt_bias"])
        beta = jax.nn.sigmoid(x @ p["w_beta"]["kernel"])  # [S, H]

        def one(state, at):  # state [H, dk, dv]
            q_t, k_t, v_t, g_t, b_t = at
            state = jnp.exp(g_t)[:, :, None] * state
            seen = jnp.einsum("hkv,hk->hv", state, k_t)
            state = state + b_t[:, None, None] * (
                k_t[:, :, None] * (v_t - seen)[:, None, :])
            o_t = jnp.einsum("hkv,hk->hv", state, q_t)
            if degrade == "state_bfloat16":
                # (not a pair of casts: the TPU's compiler keeps the excess
                # precision of float32 -> bfloat16 -> float32 and drops both)
                state = jax.lax.reduce_precision(state, 8, 7)
            return state, o_t

        _, o = jax.lax.scan(one, jnp.zeros((heads_kda, dk, dk), jnp.float32),
                            (q, k, v, g, beta))
        gate = jax.nn.sigmoid(jnp.einsum(
            "sr,rhk->shk", x @ p["g_a"]["kernel"], p["g_b"]["kernel"]))
        o = rmsnorm(o, p["o_norm"]["scale"]) * gate
        return jnp.einsum("shk,hkd->sd", o, p["wo"]["kernel"])

    def mla(x, p):  # x [S, D] -> [S, D]; no position anywhere
        s = x.shape[0]
        q = jnp.einsum("sd,dhk->shk", x, p["wq"]["kernel"])
        kv = x @ p["wkv_a"]["kernel"]
        c = rmsnorm(kv[:, :rank], p["kv_norm"]["scale"])
        k_r = kv[:, rank:]
        if degrade == "latent_float8":
            c = c.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            k_r = k_r.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        k_nope = jnp.einsum("tc,hcn->thn", c, p["wk_b"])
        v = jnp.einsum("tc,hcv->thv", c, p["wv_b"])
        scores = (jnp.einsum("shn,thn->hst", q[..., :nope], k_nope)
                  + jnp.einsum("shr,tr->hst", q[..., nope:], k_r)
                  ) / (nope + rot) ** 0.5
        visible = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), -1)
        out = jnp.einsum("hst,thv->shv", probs, v)
        return jnp.einsum("shv,hvd->sd", out, p["wo"]["kernel"])

    def experts(x, p):
        s = jax.nn.sigmoid(jnp.einsum(
            "sd,de->se", x, p["router"], precision=jax.lax.Precision.HIGHEST))
        choose = s + p["router_bias"]
        ranked = jnp.argsort(-choose, axis=-1)
        selected = ranked[:, :top_k]  # [S, k]
        w = jnp.take_along_axis(s, selected, -1)
        if a["moe_renormalize"]:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        w = w * a["routed_scaling_factor"]
        out = swiglu(x, p["shared"])
        # Each selected expert that is held adds w_e E_e(x); one absent adds
        # nothing. (A loop over the held experts, each weighted 0 on the
        # rows that did not select it: the same sum.)
        for e in range(held):
            w_e = jnp.sum(jnp.where(selected == first + e, w, 0.0), -1)
            out = out + w_e[:, None] * swiglu(
                x, {k: {"kernel": p[k][e]}
                    for k in ("w_gate", "w_up", "w_down")})
        last_in, first_out = ranked[:, top_k - 1], ranked[:, top_k]
        here = lambda e: (e >= first) & (e < first + held)  # noqa: E731
        gap = (jnp.take_along_axis(choose, last_in[:, None], -1)
               - jnp.take_along_axis(choose, first_out[:, None], -1))[:, 0]
        return out, jnp.where(here(last_in) | here(first_out), gap, jnp.inf)

    def layer(x, p, mixer):  # x [S, D] float32; p one layer's tree, as served
        p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
        if degrade == "weights_float8":
            p = jax.tree.map(lambda t: t.astype(jnp.float8_e4m3fn)
                             .astype(jnp.float32), p)
        mix = kda if mixer == "kda" else mla
        x = x + mix(rmsnorm(x, p["attn_norm"]["scale"]), p["attn"])
        m = rmsnorm(x, p["mlp_norm"]["scale"])
        if "moe" in p:
            f, margin = experts(m, p["moe"])
        else:
            f, margin = swiglu(m, p["mlp"]), None
        return x + f, margin

    def head(x, final_scale, w):
        x = rmsnorm(x, final_scale.astype(jnp.float32))
        return x @ w.astype(jnp.float32)  # untied, as published

    # One program for each kind of layer: (KDA or MLA) x (dense or expert
    # feed-forward), whatever the layer's index.
    layer_j, head_j = jax.jit(layer, static_argnums=2), jax.jit(head)

    def run(params, tokens, rows=None):
        """Logits of `rows` (all positions when None) and the margins."""
        with jax.default_matmul_precision("highest"):
            x = params["tok_emb"][jnp.asarray(tokens)].astype(jnp.float32)
            margins = []
            for i in range(llm["n_layers"]):
                x, margin = layer_j(x, params[f"layer_{i}"],
                                    "kda" if i in kda_layers else "mla")
                if margin is not None:
                    margins.append(margin)
            if rows is not None:
                x = x[jnp.asarray(rows)]
            return (head_j(x, params["final_norm"]["scale"],
                           params["lm_head"]), margins)

    return types.SimpleNamespace(run=run, kda=kda, mla=mla, experts=experts,
                                 layer=layer)


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
    # each program is built once; every layer is causal, so the padding
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
