"""Plain reference of the EvaByte decoder (`model_type` `evabyte`), for
checking what the server served from the first pipeline stage of the model.

Straightforward `jax.numpy` in float32 with `jax.default_matmul_precision(
"highest")`: no cache, no kernels, no batching, one sequence at a time, the
whole sequence at once, window by window, one layer cast to float32 at a
time. Written from the equations of ISSUE 49 section 1 (the config's keys,
the EVA paper and the published modelling code), not from the served modules;
it shares with the program only the NAMES of the parameter tree it reads.
d = hidden size, H heads of D = 128, W = window_size, C = chunk_size:

    x_0      = E[byte]                                 the stream in float32
    h        = rmsnorm(x) * (1 + g)                    eps rms_norm_eps
    q, k, v  = h W_q, h W_k, h W_v                     [H, D] each, no bias
    q, k     = RoPE(q, k; p, theta, rotate-half over all D dims)
    chunk c = positions C c .. C c + C - 1, head h with its mu_h, phi_h:
      kbar_c = sum_j softmax_j(k_j . mu_h)  k_j        no 1/sqrt(D)
      vbar_c = sum_j softmax_j(k_j . phi_h) v_j
    query t, w = t // W:
      S_t = { j : W w <= j <= t },   C_t = { c : c < (W / C) w }
      p   = softmax over S_t and C_t together of q_t . k_j / sqrt(D) and
            q_t . kbar_c / sqrt(D)
      o_t = sum_{S_t} p_j v_j + sum_{C_t} p_c vbar_c
    x        = x + o W_o
    x        = x + (silu(h' W_g) * (h' W_u)) W_d,      h' = rmsnorm(x) (1 + g')
    logits   = (rmsnorm(x_L) (1 + g_f)) W_head[:, :V]  head 0 of num_pred_heads

Departures of the served model from the published one, taken as served: the
weights are random from the seed; the first 8 of 32 layers with the final
norm and the head; heads 1-7 of `lm_head` are held and not read.

It reads the parameter tree the server itself builds (the program's
`Transformer.init` from the configuration's seed, held in bf16) and casts one
layer at a time up to float32. Every case is padded to ONE width, so the
layer is compiled once; attention is causal and a chunk is seen only from a
later window, so the padding changes no row before it. The head is applied
to the rows that predict a served token only.
"""

from __future__ import annotations

import types

#: A served greedy token may lie this far below the reference's best logit
#: (logits of standard deviation 1.29-1.31 over 320 bytes). It lies between
#: two readings on the chip (PERF.md section 6, PR 49): 0.0, the worst gap
#: of what the engine served in bf16 at the published widths (all 48 served
#: tokens are the reference's best, in every run, because the check's prompts
#: and the weights are the same in every run; `degrade="summaries_bf16_merge"`
#: reads 0.0 too: rounding the summaries' scores to bf16 moves no served
#: token's rank, so this comparison cannot see it and the CPU tests hold the
#: merge to 1e-4), and 0.0329, the gap when the reference's keys, values and
#: summaries are rounded to float8 as a cache held below bf16 would give them
#: back (three of the 24 tokens after the prompt of 2040 are then no longer
#: the best; the prompt of 5000 reads 0.0). A plain-mean pooling
#: (`degrade="mean_pool"`) reads 1.757 and 1.139: the random `mu` and `phi`
#: are large enough to matter (the configuration's `assumed`).
LOGIT_TOLERANCE = 0.015
#: Longest sequence (prompt + answer) the reference is asked to run.
MAX_POSITIONS = 8192


def build(llm: dict, degrade: str | None = None):
    """Returns the reference's functions: `run(params, tokens, rows) ->
    logits [rows, V] float32` for one sequence, where `params` is the served
    tree; and its parts `attention(x, p)` and `layer(x, p)` on float32
    trees, for the tests.

    `degrade` is only for setting the tolerance, by what must FAIL it:
    "kv_float8" rounds every key (after its rotation), value and summary to
    float8 (e4m3), what a cache held below bf16 would give back;
    "mean_pool" pools each chunk by a plain mean, as if mu and phi were 0;
    "summaries_bf16_merge" rounds the summaries' scores to bf16 before the
    softmax they share with the window's."""
    import jax
    import jax.numpy as jnp

    a = llm["arch"]
    heads = llm["n_heads"]
    hd = a.get("head_dim") or llm["d_model"] // heads
    eps = a["rms_norm_eps"]
    theta = float(a["rope_theta"])
    window, chunk = int(a["window_size"]), int(a["chunk_size"])
    per = window // chunk
    vocab = llm["vocab_size"]

    def rmsnorm(x, g):  # the weight is 1 + g (norm_add_unit_offset)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * (1.0 + g)

    def rope(x, pos):  # x [S, H, hd]: rotate-half over all hd dims
        inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                   / hd)
        ang = pos[:, None].astype(jnp.float32) * inv_freq  # [S, hd/2]
        emb = jnp.concatenate([ang, ang], -1)[:, None]
        rotated = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
        return x * jnp.cos(emb) + rotated * jnp.sin(emb)

    def f8(t):
        return t.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def attention(x, p):  # x [S, d] float32, already normed
        s = x.shape[0]
        pos = jnp.arange(s)
        q = jnp.einsum("sd,dhk->shk", x, p["wq"]["kernel"])
        k = jnp.einsum("sd,dhk->shk", x, p["wk"]["kernel"])
        v = jnp.einsum("sd,dhk->shk", x, p["wv"]["kernel"])
        q, k = rope(q, pos), rope(k, pos)
        if degrade == "kv_float8":
            k, v = f8(k), f8(v)
        n = s // chunk  # the whole chunks
        kc = k[:n * chunk].reshape(n, chunk, heads, hd)
        vc = v[:n * chunk].reshape(n, chunk, heads, hd)
        flat = degrade == "mean_pool"
        wk = jax.nn.softmax(jnp.einsum("nchk,hk->nch", kc, p["mu"])
                            * (0.0 if flat else 1.0), axis=1)
        wv = jax.nn.softmax(jnp.einsum("nchk,hk->nch", kc, p["phi"])
                            * (0.0 if flat else 1.0), axis=1)
        kbar = jnp.einsum("nch,nchk->nhk", wk, kc)
        vbar = jnp.einsum("nch,nchk->nhk", wv, vc)
        if degrade == "kv_float8":
            kbar, vbar = f8(kbar), f8(vbar)
        outs = []
        for w in range(-(-s // window)):  # window by window
            lo, hi = w * window, min(s, (w + 1) * window)
            seen = per * w  # chunks of the windows before this one
            own = jnp.einsum("qhk,thk->hqt", q[lo:hi], k[lo:hi]) / hd ** 0.5
            own = jnp.where(pos[lo:hi, None] >= pos[None, lo:hi], own,
                            -jnp.inf)
            past = jnp.einsum("qhk,chk->hqc", q[lo:hi],
                              kbar[:seen]) / hd ** 0.5
            if degrade == "summaries_bf16_merge":
                past = past.astype(jnp.bfloat16).astype(jnp.float32)
            probs = jax.nn.softmax(jnp.concatenate([own, past], -1), -1)
            outs.append(
                jnp.einsum("hqt,thk->qhk", probs[..., :hi - lo], v[lo:hi])
                + jnp.einsum("hqc,chk->qhk", probs[..., hi - lo:],
                             vbar[:seen]))
        out = jnp.concatenate(outs, 0)
        return jnp.einsum("qhk,hkd->qd", out, p["wo"]["kernel"])

    def swiglu(x, p):
        hidden = (jax.nn.silu(x @ p["w_gate"]["kernel"])
                  * (x @ p["w_up"]["kernel"]))
        return hidden @ p["w_down"]["kernel"]

    def layer(x, p):  # x [S, d] float32; p one layer's tree, as served
        p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
        x = x + attention(rmsnorm(x, p["attn_norm"]["scale"]), p["attn"])
        return x + swiglu(rmsnorm(x, p["mlp_norm"]["scale"]), p["mlp"])

    def head(x, g, w):
        x = rmsnorm(x, g.astype(jnp.float32))
        return x @ w[:, :vocab].astype(jnp.float32)  # head 0: the next byte

    layer_j, head_j = jax.jit(layer), jax.jit(head)

    def run(params, tokens, rows=None):
        """Logits of `rows` (all positions when None)."""
        with jax.default_matmul_precision("highest"):
            x = params["tok_emb"][jnp.asarray(tokens)].astype(jnp.float32)
            for i in range(llm["n_layers"]):
                x = layer_j(x, params[f"layer_{i}"])
            if rows is not None:
                x = x[jnp.asarray(rows)]
            return head_j(x, params["final_norm"]["scale"],
                          params["lm_head"])

    return types.SimpleNamespace(run=run, attention=attention, layer=layer)


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
    reference's best logit each served token's reference logit lies."""
    import time

    import jax
    import numpy as np

    t0 = time.monotonic()
    params = served_params(llm)
    run = build(llm, degrade).run
    rows = []
    # Every case is padded to one width, a multiple of 128 positions, so that
    # the layer is built once.
    width = min(MAX_POSITIONS, max(len(p) + len(t) for p, t in cases))
    width = -(-width // 128) * 128
    for prompt, tokens in cases:
        seq = (list(prompt) + list(tokens))[:width]
        n = len(seq) - len(prompt)
        at = np.arange(n) + len(prompt) - 1  # row that predicts token j
        logits = np.asarray(run(
            params, np.asarray(seq + [0] * (width - len(seq)), np.int32), at))
        gaps = logits.max(-1) - logits[np.arange(n), tokens[:n]]
        top2 = np.sort(logits, -1)[:, -2:]
        rows.append({"plen": len(prompt), "n": int(n),
                     "finite": bool(np.isfinite(logits).all()),
                     "max_gap": float(gaps.max()),
                     "argmax_matches": int((gaps == 0).sum()),
                     "mean_top2_margin": float((top2[:, 1] - top2[:, 0])
                                                .mean()),
                     "logit_std": float(logits.std())})
    dev = jax.devices()[0]
    return {"rows": rows, "tolerance": LOGIT_TOLERANCE,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "seconds": time.monotonic() - t0}
