"""Plain reference of the Phi-3 decoder, for checking what the server served.

Straightforward `jax.numpy` in float32 with `jax.default_matmul_precision(
"highest")` (on a TPU a float32 matmul otherwise runs in bf16 passes): no
cache, no kernels, no batching, one sequence at a time. It follows the
published model (microsoft/Phi-3-mini-4k-instruct, `modeling_phi3.py`):
pre-norm decoder blocks of RMSNorm -> multi-head attention with rotary
embeddings (rotate-half form, theta 10000) -> residual -> RMSNorm -> SwiGLU
-> residual, a final RMSNorm and a linear head. Each departure of the SERVED
block from the published one is noted at the line it concerns; the reference
takes the served side of each, because it checks the server, and the
configuration file lists them under `departures`.

It reads the parameter tree the server itself builds (`Transformer.init` of
the program, from the configuration's seed, held in bf16 as the engine holds
it) and casts one layer at a time up to float32, so that the whole model
never exists in float32 on the device.
"""

from __future__ import annotations

#: A served greedy token may differ from the reference's best only where the
#: two logits are this close. The server computes in bf16 through a bf16
#: cache and other programs than this reference; with random weights the best
#: two of 32064 logits are often one bf16 step apart (0.0156 at a logit of 2
#: to 4). A token read from a wrong cache row or position is off by about 3,
#: and the attention or the MLP computed in less than bf16 would be too.
LOGIT_TOLERANCE = 0.1
#: Longest sequence (prompt + answer) the reference is asked to run.
MAX_POSITIONS = 512


def build(llm: dict):
    """Returns `logits(params, tokens) -> [S, V] float32` for one sequence,
    where `params` is the served tree (`tok_emb`, `layer_i`, `final_norm`)."""
    import jax
    import jax.numpy as jnp

    n_heads = llm["n_heads"]
    head = llm["d_model"] // n_heads
    theta = 10000.0  # published rope_theta; the served block uses the same
    # Departure: the served RMSNorm adds 1e-6 under the root, the published
    # config says rms_norm_eps 1e-5.
    eps = 1e-6

    def rmsnorm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * scale

    def rope(x, pos):  # x [S, H, D]; rotate-half, as published
        freqs = 1.0 / theta ** (jnp.arange(0, head, 2, dtype=jnp.float32)
                                / head)
        ang = pos[:, None].astype(jnp.float32) * freqs  # [S, D/2]
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :head // 2], x[..., head // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def layer(x, p):  # x [S, D] float32; p one layer's tree, bf16
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        s = x.shape[0]
        pos = jnp.arange(s)
        h = rmsnorm(x, p["attn_norm"]["scale"])
        # Departure: published Phi-3 multiplies by one fused `qkv_proj`
        # [D, 3D]; the served block holds wq, wk, wv apart. Same width, same
        # arithmetic. No bias, as published.
        q = jnp.einsum("sd,dhk->shk", h, p["attn"]["wq"]["kernel"])
        k = jnp.einsum("sd,dhk->shk", h, p["attn"]["wk"]["kernel"])
        v = jnp.einsum("sd,dhk->shk", h, p["attn"]["wv"]["kernel"])
        q, k = rope(q, pos), rope(k, pos)
        scores = jnp.einsum("qhk,thk->hqt", q, k) / jnp.sqrt(float(head))
        causal = pos[:, None] >= pos[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        att = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(scores, -1), v)
        x = x + jnp.einsum("qhk,hkd->qd", att, p["attn"]["wo"]["kernel"])
        h = rmsnorm(x, p["mlp_norm"]["scale"])
        # Departure: published `gate_up_proj` is one fused [D, 2F] matrix;
        # served as w_gate and w_up. silu(gate) * up, as published.
        gate = jax.nn.silu(h @ p["mlp"]["w_gate"]["kernel"])
        up = h @ p["mlp"]["w_up"]["kernel"]
        return x + (gate * up) @ p["mlp"]["w_down"]["kernel"]

    def head_logits(x, final_scale, emb):
        x = rmsnorm(x, final_scale.astype(jnp.float32))
        # Departure: the published model has an untied `lm_head`; the served
        # one multiplies by the embedding. Same shape, same arithmetic.
        return x @ emb.astype(jnp.float32).T

    layer_j, head_j = jax.jit(layer), jax.jit(head_logits)

    def logits(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = params["tok_emb"][jnp.asarray(tokens)].astype(jnp.float32)
            for i in range(llm["n_layers"]):
                x = layer_j(x, params[f"layer_{i}"])
            return head_j(x, params["final_norm"]["scale"],
                          params["tok_emb"])

    return logits


def served_params(llm: dict):
    """The tree the engine serves: the program's own `Transformer.init` from
    the configuration's seed, cast to bf16 as `ContinuousEngine` casts it,
    made in one jitted call so that the float32 tree is never held whole."""
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


def check(llm: dict, cases: list) -> dict:
    """For each served greedy (prompt, tokens): how far below the
    reference's best logit each served token's reference logit lies."""
    import time

    import jax
    import numpy as np

    t0 = time.monotonic()
    params = served_params(llm)
    logits = build(llm)
    rows = []
    # Every case is padded to one length, so that each program is built
    # once; attention is causal, so the padding changes no row before it.
    width = min(MAX_POSITIONS, max(len(p) + len(t) for p, t in cases))
    width = -(-width // 128) * 128
    for prompt, tokens in cases:
        seq = (list(prompt) + list(tokens))[:width]
        n = len(seq) - len(prompt)
        seq = seq + [0] * (width - len(seq))
        out = np.asarray(logits(params, np.asarray(seq, np.int32)))
        at = np.arange(n) + len(prompt) - 1  # row that predicts token j
        rows_logits = out[at]
        gaps = rows_logits.max(-1) - rows_logits[np.arange(n), tokens[:n]]
        top2 = np.sort(rows_logits, -1)[:, -2:]
        rows.append({"plen": len(prompt), "n": int(n),
                     "finite": bool(np.isfinite(out).all()),
                     "max_gap": float(gaps.max()),
                     "argmax_matches": int((gaps == 0).sum()),
                     "mean_top2_margin": float((top2[:, 1] - top2[:, 0])
                                                .mean()),
                     "logit_std": float(rows_logits.std())})
    dev = jax.devices()[0]
    return {"rows": rows, "tolerance": LOGIT_TOLERANCE,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "seconds": time.monotonic() - t0}
