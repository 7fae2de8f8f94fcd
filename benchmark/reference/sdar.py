"""Plain reference of the SDAR decoder (`model_type` `sdar_moe`, a
block-diffusion language model) and of its generation, for checking what the
server served.

Straightforward `jax.numpy` in float32 with `jax.default_matmul_precision(
"highest")`: no cache, no kernels, no batching, one sequence at a time, the
full `[S, S]` mask written as `j // L <= i // L`, one key/value head's group
of query heads at a time. Written from the equations of ISSUE 58 (the
config's keys and the release's `modeling_sdar_moe.py` and `generate.py` as
that issue writes them down), not from the served modules; it shares with the
program only the NAMES of the parameter tree it reads. Position p, stream x:

    a        = RMSNorm_in(x)                              weight g itself
    q, k, v  = a W_q [H, hd], a W_k [KV, hd], a W_v [KV, hd]
    q, k     = RMSNorm_q(q), RMSNorm_k(k)     per head, ONE weight of hd dims
    q, k     = RoPE(q, k; p, theta, rotate-half over all hd dims)
    head h reads key/value head h // (H / KV); score = q.k / sqrt(hd)
    VISIBILITY: key j is visible to query i iff j // L <= i // L
    x        = x + concat(heads) W_o
    m        = RMSNorm_post(x)
    s        = softmax(m W_r) over all experts, float32; sel = top-k of s
    w_e      = s_e / sum_{sel} s
    x        = x + sum_{e in sel} w_e W_down,e(silu(m W_gate,e) * (m W_up,e))
    logits   = RMSNorm_final(x_L) W_head       of the token AT the position

Generation (`generate`; L positions a block, T denoising steps, mask token M,
threshold tau): the prompt's whole blocks are context; the open block is the
prompt's remainder and then M; a forward over everything so far gives the
open block's logits; every position that holds M draws a token (greedy: the
argmax) with the confidence of its probability under the plain softmax; the
forward's count of positions (L // T, the remainder over the first steps) of
highest confidence leave the mask, or under `low_confidence_dynamic` every
one above tau where those are at least as many; a block without M is
committed and the next is all M. The answer is cut at its length.

Departures of the served model from the published one, taken as served: the
weights are random from the seed; any fused matrix is held apart; the
reference runs no commit forward (its logits are read by nobody, and with no
cache there is nothing to commit).

It reads the parameter tree the server itself builds (`Transformer.init`
from the configuration's seed, held in bf16) and casts one layer at a time up
to float32. Every case and every forward is padded to ONE width (visibility
by blocks: padding behind changes nothing before), so one program a layer
serves them all.
"""

from __future__ import annotations

import types

#: A served greedy token's reference logit may lie this far below the
#: reference's best at its position, and the log confidence of a position the
#: replay frees this far below the highest among the masked (`replay`): both
#: are distances between two float32 numbers that bf16 arithmetic of the
#: served program moves by the same rounding (logits of standard deviation
#: 0.90 over 151,936 tokens). It lies between two readings on the chip
#: (PERF.md section 6, PR 58; my chip runs, the replay run at this
#: tolerance): 0.1911, the worst gap of what the engine served at the
#: published widths (the prompt of 101; 0.1116 for the prompt of 510), the
#: same in every run because the check's prompts and the weights are; and
#: 0.4095 / 0.4793, the two prompts' gaps when the reference's keys and
#: values are rounded to float8 (`degrade="kv_float8"`: what a cache held
#: below bf16 would give back, the nearest precision below the
#: configuration's), which fails on both. 0.30 is the geometric mean of
#: 0.1911 and 0.4793. The experts' three matrices rounded to float8 read
#: 0.1054 and 0.2421: one flipped routing decision swaps one of a token's
#: eight experts where Trinity's share of 16 of 128 adds or removes a
#: token's whole routed sum, so the served gap is half Trinity's 0.36 and
#: this degrade stays under the tolerance; it is not the limit's measure.
#: The tolerance also decides which positions the replay holds admissible:
#: at 0.16 the position that reads 0.1911 was not, the replay had to free
#: another first and read 0.3838 there.
LOGIT_TOLERANCE = 0.3
#: Longest sequence (prompt + answer) the reference is asked to run.
MAX_POSITIONS = 2048


def build(llm: dict, degrade: str | None = None):
    """The reference's functions: `forward(params, tokens, rows) -> logits
    [len(rows), V] float32` of one sequence under visibility by blocks (every
    position a given token, masks among them), and its parts `attention(x,
    p)`, `experts(x, p)`, `layer(x, p)` on float32 trees, for the tests.

    `degrade` is only for setting the tolerance, by what must FAIL it:
    "kv_float8" rounds every key (after its norm and rotation) and value to
    float8 (e4m3), what a cache held below bf16 would give back;
    "experts_float8" rounds the experts' three matrices."""
    import jax
    import jax.numpy as jnp

    a = llm["arch"]
    heads, kv_heads, hd = llm["n_heads"], a["num_key_value_heads"], a["head_dim"]
    group = heads // kv_heads
    eps, theta = a["rms_norm_eps"], float(a["rope_theta"])
    size = int(a["block_length"])
    top_k = a["num_experts_per_tok"]

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

    def float8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def attention(x, p):
        s = x.shape[0]
        pos = jnp.arange(s)
        q = jnp.einsum("sd,dhk->shk", x, p["wq"]["kernel"])
        k = jnp.einsum("sd,dhk->shk", x, p["wk"]["kernel"])
        v = jnp.einsum("sd,dhk->shk", x, p["wv"]["kernel"])
        # one weight of hd dims for all heads
        q = rope(rmsnorm(q, p["q_norm"]["scale"]), pos)
        k = rope(rmsnorm(k, p["k_norm"]["scale"]), pos)
        if degrade == "kv_float8":
            k, v = float8(k), float8(v)
        # every earlier block whole, and the query's own block whole
        visible = pos[None, :] // size <= pos[:, None] // size
        outs = []
        for n in range(kv_heads):  # one key/value head's queries at a time
            mine = slice(n * group, (n + 1) * group)
            scores = jnp.einsum("qhk,tk->hqt", q[:, mine], k[:, n]) / hd ** 0.5
            probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf),
                                   -1)
            outs.append(jnp.einsum("hqt,tk->qhk", probs, v[:, n]))
        return jnp.einsum("qhk,hkd->qd", jnp.concatenate(outs, 1),
                          p["wo"]["kernel"])

    def experts(x, p):
        s = jax.nn.softmax(jnp.einsum(
            "sd,de->se", x, p["router"],
            precision=jax.lax.Precision.HIGHEST), -1)
        ranked = jnp.argsort(-s, axis=-1)  # no bias, no groups
        selected = ranked[:, :top_k]
        w = jnp.take_along_axis(s, selected, -1)
        w = w / w.sum(-1, keepdims=True)  # norm_topk_prob; no further scale

        def one(out, expert):
            # (a loop over all experts, each weighted 0 on the rows that did
            # not select it: the same sum)
            e, gate, up, down = expert
            if degrade == "experts_float8":
                gate, up, down = float8(gate), float8(up), float8(down)
            w_e = jnp.sum(jnp.where(selected == e, w, 0.0), -1)
            return out + w_e[:, None] * (
                (jax.nn.silu(x @ gate) * (x @ up)) @ down), None

        n = p["w_gate"].shape[0]
        out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
            jnp.arange(n), p["w_gate"], p["w_up"], p["w_down"]))
        # how close the selection came to falling the other way
        margin = (jnp.take_along_axis(s, ranked[:, top_k - 1:top_k], -1)
                  - jnp.take_along_axis(s, ranked[:, top_k:top_k + 1], -1))
        return out, margin[:, 0]

    def layer(x, p):  # x [S, D] float32; p one layer's tree, as served
        p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
        x = x + attention(rmsnorm(x, p["attn_norm"]["scale"]), p["attn"])
        f, margin = experts(rmsnorm(x, p["mlp_norm"]["scale"]), p["moe"])
        return x + f, margin

    def head(x, final_scale, w):
        x = rmsnorm(x, final_scale.astype(jnp.float32))
        return x @ w.astype(jnp.float32)  # untied; no shift of the logits

    layer_j, head_j = jax.jit(layer), jax.jit(head)

    def forward(params, tokens, rows=None):
        """Logits of `rows` (all positions when None) of one sequence."""
        with jax.default_matmul_precision("highest"):
            x = params["tok_emb"][jnp.asarray(tokens)].astype(jnp.float32)
            for i in range(llm["n_layers"]):
                x, _ = layer_j(x, params[f"layer_{i}"])
            if rows is not None:
                x = x[jnp.asarray(rows)]
            return head_j(x, params["final_norm"]["scale"], params["lm_head"])

    return types.SimpleNamespace(forward=forward, attention=attention,
                                 experts=experts, layer=layer)


def schedule(llm: dict) -> list[int]:
    """Positions a denoising forward frees at least, by its number in the
    block: L // T each, the remainder of L / T one each over the first."""
    a = llm["arch"]
    size, steps = int(a["block_length"]), int(a["denoising_steps"])
    return [size // steps + (t < size % steps) for t in range(steps)]


def _width(llm: dict, longest: int) -> int:
    """The one width every forward is padded to: whole blocks, and whole
    tiles of 128 positions where the sequences are that long."""
    size = int(llm["arch"]["block_length"])
    unit = 128 if longest > 128 else size
    unit = unit * size if unit % size else unit
    return -(-min(MAX_POSITIONS, longest + size) // unit) * unit


def _block_logits(forward, params, committed: list, block: list, width: int,
                  mask: int | None = None):
    """The open block's logits [L, V]: a forward over the committed tokens
    and the block's content, padded to `width`; -inf at the `mask` token,
    which no position may draw (it would stay masked, and under greedy
    sampling a block's last one for ever: the release draws over the whole
    vocabulary and a trained checkpoint does not predict its mask; on random
    weights a draw in 151,936 would)."""
    import numpy as np

    seq = list(committed) + list(block)
    tokens = np.asarray(seq + [0] * (width - len(seq)), np.int32)
    logits = np.array(forward(params, tokens,
                              np.arange(len(committed), len(seq))))
    if mask is not None:  # the mask is never drawn (`assumed`)
        logits[:, mask] = -np.inf
    return logits


def _log_softmax(row):
    import numpy as np

    row = row.astype(np.float64)
    top = row.max()
    return row - top - np.log(np.exp(row - top).sum())


def generate(llm: dict, params, prompt, n: int, width: int | None = None,
             forward=None) -> dict:
    """The reference's OWN greedy generation of an answer of n tokens:
    `{"tokens", "forwards" (denoising forwards run), "freed" (positions
    freed, forward by forward), "logits" (each denoising forward's [L, V])}`.
    Equal confidences go to the earlier position."""
    import numpy as np

    a = llm["arch"]
    size, mask = int(a["block_length"]), int(a["mask_token_id"])
    tau = float(a["confidence_threshold"])
    dynamic = a["remasking_strategy"] == "low_confidence_dynamic"
    counts = schedule(llm)
    forward = forward or build(llm).forward
    prompt = [int(t) for t in prompt]
    width = width or _width(llm, len(prompt) + n)
    whole = len(prompt) // size * size
    committed, block = prompt[:whole], prompt[whole:]
    block = block + [mask] * (size - len(block))
    freed, logits_seen = [], []
    while len(committed) < len(prompt) + n:
        step = 0
        while mask in block:
            logits = _block_logits(forward, params, committed, block, width,
                                   mask)
            logits_seen.append(logits)
            masked = [i for i in range(size) if block[i] == mask]
            x0 = {i: int(logits[i].argmax()) for i in masked}
            conf = {i: float(np.exp(_log_softmax(logits[i])[x0[i]]))
                    for i in masked}
            want = counts[min(step, len(counts) - 1)]
            by_conf = sorted(masked, key=lambda i: (-conf[i], i))
            sure = [i for i in masked if conf[i] > tau]
            chosen = sure if dynamic and len(sure) >= want else by_conf[:want]
            for i in chosen:
                block[i] = x0[i]
            freed.append(sorted(chosen))
            step += 1
        committed, block = committed + block, [mask] * size
    return {"tokens": committed[len(prompt):len(prompt) + n],
            "forwards": len(freed), "freed": freed, "logits": logits_seen}


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


def replay(llm: dict, forward, params, prompt, tokens, width: int,
           tolerance: float) -> dict:
    """One served greedy answer replayed decision by decision (the served
    tokens do not say in which order a block's positions left the mask).
    With the served tokens of earlier blocks committed, at each denoising
    forward and for each position still masked: `token_gap`, the position's
    best logit less the logit of the token that was served there, and its
    confidence, the served token's probability. The positions whose
    `token_gap` is within `tolerance` are ADMISSIBLE (the served program,
    which frees a position with its own best token, can have freed them at
    this forward); the replay frees the admissible ones of highest
    confidence, the schedule's count of them (under the threshold rule every
    admissible one above tau where those are as many), and records
    `order_gap`: how far the log confidences of the positions it freed lie
    under the highest among all the masked (0 where its own choice was the
    served one), and for a position above tau that is not admissible, how
    far above. `max_gap` is the largest of the freed positions' `token_gap`s
    and all `order_gap`s; a forward with no admissible position puts its
    smallest `token_gap` there, which fails, and frees that position. A
    position of the last block past the answer's end was served to nobody:
    the replay gives it its own best token, always admissible."""
    import numpy as np

    a = llm["arch"]
    size, mask = int(a["block_length"]), int(a["mask_token_id"])
    tau = float(a["confidence_threshold"])
    dynamic = a["remasking_strategy"] == "low_confidence_dynamic"
    counts = schedule(llm)
    prompt, tokens = [int(t) for t in prompt], [int(t) for t in tokens]
    served = prompt + tokens
    whole = len(prompt) // size * size
    committed, block = prompt[:whole], prompt[whole:]
    block = block + [mask] * (size - len(block))
    token_gaps, order_gaps, forwards, own = [], [], 0, 0
    finite, stds = True, []
    while len(committed) < len(served):
        step = 0
        while mask in block:
            logits = _block_logits(forward, params, committed, block, width,
                                   mask)
            kept = np.delete(logits, mask, axis=1)
            finite = finite and bool(np.isfinite(kept).all())
            stds.append(float(kept.std()))
            forwards += 1
            masked = [i for i in range(size) if block[i] == mask]
            target, gap, logc = {}, {}, {}
            for i in masked:
                at = len(committed) + i
                best = int(logits[i].argmax())
                target[i] = served[at] if at < len(served) else best
                gap[i] = float(logits[i].max() - logits[i][target[i]])
                logc[i] = float(_log_softmax(logits[i])[target[i]])
            admissible = [i for i in masked if gap[i] <= tolerance]
            want = counts[min(step, len(counts) - 1)]
            if not admissible:  # nothing the served program can have freed
                admissible = [min(masked, key=lambda i: gap[i])]
            by_conf = sorted(admissible, key=lambda i: (-logc[i], i))
            sure = [i for i in by_conf if logc[i] > np.log(tau)]
            chosen = sure if dynamic and len(sure) >= want else by_conf[:want]
            ranked = sorted((logc[i] for i in masked), reverse=True)
            order_gaps += [top - logc[i] for top, i in zip(ranked, chosen)]
            if dynamic:  # above tau, and yet not what was served
                order_gaps += [logc[i] - float(np.log(tau)) for i in masked
                               if i not in admissible and logc[i] > np.log(tau)]
            for i in chosen:
                token_gaps.append(gap[i])
                own += gap[i] == 0.0
                block[i] = target[i]
            step += 1
        committed, block = committed + block, [mask] * size
    return {"plen": len(prompt), "n": len(tokens), "finite": finite,
            "max_gap": float(max(token_gaps + order_gaps)),
            "max_token_gap": float(max(token_gaps)),
            "max_order_gap": float(max(order_gaps)),
            "argmax_matches": int(own), "freed": len(token_gaps),
            "forwards": forwards, "logit_std": float(np.mean(stds))}


def check(llm: dict, cases: list, degrade: str | None = None) -> dict:
    """For each served greedy (prompt, tokens): `replay`'s row."""
    import time

    import jax

    t0 = time.monotonic()
    params = served_params(llm)
    forward = build(llm, degrade).forward
    width = _width(llm, max(len(p) + len(t) for p, t in cases))
    rows = [replay(llm, forward, params, prompt, tokens, width,
                   LOGIT_TOLERANCE) for prompt, tokens in cases]
    dev = jax.devices()[0]
    return {"rows": rows, "tolerance": LOGIT_TOLERANCE,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "seconds": time.monotonic() - t0}
