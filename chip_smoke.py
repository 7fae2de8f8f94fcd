"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

    python chip_smoke.py

Drives HTTP -> proxy -> router -> replica -> ContinuousEngine once, through
the entry points a user calls, at the full width of the widest model this
repository has run (1024d x 8L, bf16, random weights from a seed), with one
one-chip replica per chip of the host. Then, in an actor that holds a chip,
it checks what was served against a cache-free forward pass of the same
model, compiles the Pallas flash-attention kernel with Mosaic and compares
it with its XLA reference.

This parent process never imports JAX: a chip belongs to one process, and a
worker sees one only by holding the `TPU` resource. It exits non-zero, and
prints no `"ok": true` line, when the host has no TPU or any phase fails; it
never serves from the CPU. On success the last line of its output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it inside the replicas. Set-up facts and
correctness only: it measures no speed.
"""

from __future__ import annotations

import concurrent.futures
import json
import os
import random
import socket
import sys
import time
import urllib.request

T_START = time.monotonic()
#: The whole run, compilation included, has to end well inside 1200 s.
DEADLINE = T_START + 1080.0

#: Heads of 128: a head the flash kernel takes (a whole lane tile), so the
#: served prefill goes through it on the chip (ops/attention.py).
MODEL = dict(vocab_size=32000, d_model=1024, n_layers=8, n_heads=8,
             max_seq=1024, dtype="bfloat16")
MAX_BATCH = 8
DECODE_CHUNK = 16
#: Kimi K2's latent attention at its published latent and head widths (a
#: row of 512 + 64 values a token, which the engine widens to 640), 16
#: heads, two layers, the second with experts: the model whose bounded
#: decode steps read the cache through the latent kernel
#: (ops/decode_attention.py `ragged_latent_attention`).
LATENT_MODEL = dict(
    vocab_size=32000, d_model=1024, n_layers=2, n_heads=16, max_seq=2048,
    dtype="bfloat16", experts_held=4,
    arch={"model_type": "kimi_k2", "intermediate_size": 2048,
          "q_lora_rank": 512, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128,
          "moe_intermediate_size": 512, "n_routed_experts": 16,
          "n_shared_experts": 1, "num_experts_per_tok": 4,
          "first_k_dense_replace": 1, "norm_topk_prob": True,
          "routed_scaling_factor": 2.827, "scoring_func": "sigmoid",
          "rms_norm_eps": 1e-5, "rope_theta": 50000,
          "rope_scaling": {"type": "yarn", "factor": 64, "beta_fast": 32,
                           "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                           "original_max_position_embeddings": 4096},
          "tie_word_embeddings": False})
#: Programs of the serving path, as their compile-cache entries are named.
SERVING_PROGRAMS = ("jit_prefill", "jit_chunk", "jit_place", "jit_sample1")
#: A served greedy token may differ from the argmax of the cache-free
#: reference only where the two logits are this close: the engine reads a
#: bf16 cache through other programs than the reference runs, and with
#: random weights the best two of 32000 logits are often one bf16 step
#: apart. On the v5e the worst gap seen is 0.0143 (one bf16 step at a logit
#: of 2 to 4 is 0.0156); a token from a wrong cache row or position is off
#: by about 3.
REFERENCE_LOGIT_TOL = 0.1


def say(msg: str) -> None:
    print(f"[{time.monotonic() - T_START:7.1f}s] {msg}", flush=True)


def left(budget: float) -> float:
    """A phase's time limit: its own budget, cut to what the run has left."""
    return max(5.0, min(budget, DEADLINE - time.monotonic()))


class PhaseFailed(Exception):
    pass


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# ------------------------------------------------------------------ requests
def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _prompts() -> tuple[list[int], list[int]]:
    """A 100-token and a 200-token prompt: prefill buckets 128 and 256."""
    rng = random.Random(0)
    vocab = MODEL["vocab_size"]
    return ([rng.randrange(vocab) for _ in range(100)],
            [rng.randrange(vocab) for _ in range(200)])


def _request(prompt, max_tokens, temperature, stream, seed=0) -> dict:
    return {"prompt": prompt, "max_tokens": max_tokens,
            "temperature": temperature, "top_k": 50 if temperature else 0,
            "seed": seed, "stream": stream}


def warmup_requests() -> list[dict]:
    """Sent one at a time. A request alone in the batch decodes in a fixed
    sequence of chunks — 31 tokens as 16+8+4+2, 33 as 16+16+1 — so these
    four compile both prefill buckets and every decode-chunk program there
    is (sizes 1..16, greedy and sampled), whatever the timing."""
    short, long_ = _prompts()
    return [_request(short, 31, 0.0, False), _request(long_, 33, 0.0, True),
            _request(long_, 31, 0.8, False), _request(short, 33, 0.8, True)]


def wave_requests(n_replicas: int) -> list[dict]:
    """Sent all at once, ten per replica for MAX_BATCH slots each, so that
    some wait for a slot and admission, splice and retire all run: both
    buckets, greedy and sampled, streamed and not, lengths that differ so
    that requests retire at different steps."""
    short, long_ = _prompts()
    shapes = [  # (prompt, max_tokens, temperature, stream)
        (short, 33, 0.0, False), (short, 33, 0.0, True),
        (long_, 17, 0.0, False), (long_, 17, 0.0, True),
        (short, 24, 0.8, False), (long_, 40, 0.8, True),
        (short, 9, 0.0, True), (long_, 33, 1.0, False),
        (short, 48, 0.0, False), (long_, 5, 0.7, True),
    ]
    return [_request(*shape, seed=7 + i + 100 * r)
            for r in range(n_replicas) for i, shape in enumerate(shapes)]


def complete(base: str, body: dict, timeout: float) -> dict:
    """POST one completion; returns {"tokens", "finish"} for the streamed
    (SSE) and the plain form alike."""
    req = urllib.request.Request(
        f"{base}/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        if not body["stream"]:
            doc = json.loads(resp.read())
            return {"tokens": doc["token_ids"],
                    "finish": doc["choices"][0]["finish_reason"]}
        tokens, finish = [], None
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            if line[6:] == "[DONE]":
                break
            doc = json.loads(line[6:])
            tokens += doc.get("token_ids", [])
            finish = doc["choices"][0]["finish_reason"] or finish
        return {"tokens": tokens, "finish": finish}


def check_outputs(reqs: list[dict], outs: list[dict]) -> None:
    vocab = MODEL["vocab_size"]
    for r, o in zip(reqs, outs):
        what = (f"{'sse' if r['stream'] else 'plain'} t={r['temperature']} "
                f"plen={len(r['prompt'])}")
        require(len(o["tokens"]) == r["max_tokens"],
                f"{what}: {len(o['tokens'])} tokens, "
                f"expected {r['max_tokens']}")
        require(o["finish"] == "length",
                f"{what}: finish_reason {o['finish']!r}, expected 'length'")
        require(all(isinstance(t, int) and 0 <= t < vocab
                    for t in o["tokens"]),
                f"{what}: token ids outside [0, {vocab})")


def replica_stats(base: str, n_replicas: int, timeout: float) -> list[dict]:
    """/v1/stats answers from one replica per call; ask until every replica
    (one pid each) has answered."""
    seen: dict[int, dict] = {}
    t_end = time.monotonic() + timeout
    while len(seen) < n_replicas and time.monotonic() < t_end:
        with urllib.request.urlopen(f"{base}/v1/stats", timeout=30) as r:
            st = json.loads(r.read())
        seen[st["pid"]] = st
    require(len(seen) == n_replicas,
            f"only {len(seen)} of {n_replicas} replicas answered /v1/stats")
    return list(seen.values())


# ------------------------------------------------- checks that need the chip
class ChipProbe:
    """Runs in a worker that holds one chip, after the replicas are gone."""

    def reference(self, model: dict, cases: list) -> list[dict]:
        """For each served greedy (prompt, tokens): how far below the best
        logit of a cache-free forward pass of the same model (seeded weights,
        no KV cache, the dispatcher's attention — the flash kernel here)
        each served token's logit lies. One row per case."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.llm import LLMConfig
        from ray_tpu.models.published import model_config
        from ray_tpu.models.transformer import Transformer

        cfg = LLMConfig(**model)
        net = Transformer(model_config(cfg))
        params = net.init(jax.random.PRNGKey(cfg.seed),
                          jnp.zeros((1, 8), jnp.int32))["params"]
        params = jax.tree.map(  # as the engine holds them
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, params)
        width = max(len(p) + len(t) for p, t in cases)
        width = -(-width // 128) * 128  # a length the flash kernel tiles
        forward = jax.jit(lambda p, t: net.apply({"params": p}, t)[0])
        rows = []
        for prompt, tokens in cases:
            toks = np.zeros((1, width), np.int32)
            toks[0, :len(prompt) + len(tokens)] = prompt + tokens
            at = np.arange(len(tokens)) + len(prompt) - 1  # predicts token j
            logits = np.asarray(forward(params, toks))[at]
            gaps = logits.max(axis=-1) - logits[np.arange(len(at)), tokens]
            rows.append({"plen": len(prompt), "n": len(tokens),
                         "finite": bool(np.isfinite(logits).all()),
                         "max_gap": float(gaps.max()),
                         "argmax_matches": int((gaps == 0).sum())})
        return rows

    def kernels(self, model: dict) -> dict:
        """Compiles the two Pallas kernels with Mosaic (interpret=False)
        and compares each with its XLA form. The flash-attention kernel
        (the prefill's attention and a forward pass without a gradient)
        against `prefill_attention`: at this model's prefill buckets, at
        the bench shape, and at Trinity-Mini's (32 heads on 4, a window of
        2048 and none, a prompt that ends inside its bucket). The ragged
        decode kernel (a bounded decode step's attention) against the
        whole-cache walk `_xla_decode_attention`: at Phi-3's leaf (heads of
        96 in rows of 128) and at Trinity-Mini's full leaf and ring, with
        ragged lengths, a wrapped ring and free rows. The latent kernel
        against `models/mla.py` `_latent_attention` over the whole leaf, at
        Kimi K2's and Kimi Linear's cells' shapes (32 slots of 64 heads, 64
        of 32, on 4096 rows of 640), ragged lengths and free rows. The
        two-leaf kernel (an "eva" layer's bounded step) against the two
        walks merged, at EvaByte's cell's leaves (16 slots of 32 heads on a
        window of 2048 rows and 1024 summaries). The two-source kernel (an
        "eva" layer's prefill past its first window) against the tile scan
        `eva_sequence`, at the bucket of the cell's check prompt (6144
        rows of 32 heads of 128, a prompt of 5000). The
        occupied-experts kernel (`ops/expert_decode.py`, every expert
        layer's decode step on the chip) against the dense arm of the same
        layer, at the four expert cells' shapes (LongCat-Flash's 32 rows
        of 6144 on 16 held experts of 2048 with identity experts, Kimi K2's
        32 of 7168 on 12 of 2048, Trinity-Mini's 16 of 2048 on 16 of 1024,
        Kimi Linear's 64 of 2304 on 16 of 1024), under dense routing, every
        held expert several rows, and sparse: the arithmetic the cells' own
        `correct` sees little of (a few selections of their checked prompts
        reach a held expert), held to the dense arm's bits. An exception
        from a kernel is reported in its row, and the smoke fails on it."""
        import time
        import traceback

        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models.eva import eva_sequence, summaries
        from ray_tpu.models.mla import _latent_attention
        from ray_tpu.ops.attention import kernel_refusal, prefill_attention
        from ray_tpu.ops.decode_attention import (_xla_decode_attention,
                                                  latent_refusal,
                                                  merge_partials,
                                                  partial_walk,
                                                  ragged_decode_attention,
                                                  ragged_latent_attention,
                                                  ragged_two_leaf_attention,
                                                  two_leaf_refusal,
                                                  walk_refusal)
        from ray_tpu.ops.flash_attention import flash_attention
        from ray_tpu.ops.two_source_attention import (two_source_attention,
                                                      two_source_refusal)

        dev = jax.devices()[0]
        heads = model["n_heads"]
        hd = model["d_model"] // heads
        rows = []

        def compare(name, kernel, ref, upto):
            row = {"kernel": name}
            try:
                t0 = time.monotonic()
                got = np.asarray(jax.block_until_ready(kernel()), np.float32)
                row["compile_and_run_s"] = round(time.monotonic() - t0, 2)
                want = np.asarray(ref(), np.float32)
                err = np.abs(got - want)[:, :upto]
                # bf16 keeps 8 bits: an output near 4 is rounded by ~0.016.
                tol = 2e-2 + 2e-2 * np.abs(want)[:, :upto]
                row.update(max_abs_err=float(err.max()),
                           ok=bool(np.isfinite(got).all()
                                   and (err <= tol).all()))
            except Exception:  # noqa: BLE001 - reported row by row
                row.update(ok=False, error=traceback.format_exc(limit=6))
            rows.append(row)

        # Prefill buckets of this model, the bench shape, Trinity-Mini's.
        for b, s, h, kv, d, window, plen in [
                (1, 128, heads, heads, hd, 0, 100),
                (1, 256, heads, heads, hd, 0, 200),
                (1, 1024, heads, heads, hd, 0, 1024),
                (4, 2048, 8, 8, 128, 0, 2048),
                (1, 4096, 32, 4, 128, 2048, 3282),
                (1, 4096, 32, 4, 128, 0, 3282)]:
            ks = jax.random.split(jax.random.PRNGKey(s), 3)
            q, k, v = (jax.random.normal(key, (b, s, n, d), jnp.bfloat16)
                       for key, n in zip(ks, (h, kv, kv)))
            q_len = jnp.full((b,), plen, jnp.int32)
            compare(f"flash_attention b{b} s{s} h{h} on {kv} d{d} "
                    f"window {window} prompt {plen}",
                    lambda: flash_attention(q, k, v, causal=True,
                                            window=window, q_len=q_len),
                    lambda: prefill_attention(q, k, v, window), plen)

        # Decode: Phi-3's leaf, Trinity-Mini's full leaf and its ring,
        # Ouro's leaf.
        for b, rows_, h, kv, d in [(8, 2048, 32, 32, 96),
                                   (16, 8192, 32, 4, 128),
                                   (16, 2048, 32, 4, 128),
                                   (16, 2048, 16, 16, 128)]:
            ks = jax.random.split(jax.random.PRNGKey(rows_ + kv), 4)
            q = jax.random.normal(ks[0], (b, h, d), jnp.bfloat16)
            wide = ((0, 0),) * 3 + ((0, 128 - d),)
            k, v = (jnp.pad(jax.random.normal(key, (b, rows_, kv, d),
                                              jnp.bfloat16), wide)
                    for key in ks[1:3])
            lens = jax.random.randint(ks[3], (b,), 1, 3 * rows_ // 2)
            live = jnp.arange(b) % 4 != 1
            compare(f"ragged_decode_attention b{b} rows{rows_} h{h} on {kv} "
                    f"d{d}",
                    lambda: jnp.where(
                        live[:, None], ragged_decode_attention(
                            q, k, v, lens, live).reshape(b, -1), 0),
                    lambda: jnp.where(
                        live[:, None], _xla_decode_attention(
                            q, k[..., :d], v[..., :d],
                            jnp.minimum(lens, rows_)).reshape(b, -1), 0),
                    h * d)

        # Latent rows: Kimi K2's cell and Kimi Linear's.
        rank, width, row, scale = 512, 576, 640, 0.1147
        for b, h in [(32, 64), (64, 32)]:
            ks = jax.random.split(jax.random.PRNGKey(b + h), 3)
            q = jax.random.normal(ks[0], (b, h, width), jnp.bfloat16)
            leaf = jnp.pad(
                jax.random.normal(ks[1], (b, 4096, width), jnp.bfloat16),
                ((0, 0), (0, 0), (0, row - width)))
            lens = jax.random.randint(ks[2], (b,), 1, 4097)
            live = jnp.arange(b) % 4 != 1
            compare(f"ragged_latent_attention b{b} rows4096 h{h} on a row "
                    f"of {row}",
                    lambda: ragged_latent_attention(
                        q, leaf, lens, live, rank=rank,
                        scale=scale).reshape(b, -1),
                    lambda: jnp.where(
                        live[:, None], _latent_attention(
                            q[..., :rank], q[:, None, :, rank:], leaf,
                            lens[:, None] - 1, rank, width,
                            scale).reshape(b, -1), 0),
                    h * rank)

        # An "eva" layer's two leaves under one softmax, at EvaByte's cell:
        # slots at scattered positions, free rows between them.
        b, h, d, window, per = 16, 32, 128, 2048, 128
        ks = jax.random.split(jax.random.PRNGKey(50), 6)
        q = jax.random.normal(ks[0], (b, h, d), jnp.bfloat16)
        leaves = [jax.random.normal(key, (b, rows_, h, d), jnp.bfloat16)
                  for key, rows_ in zip(ks[1:5], (window, window, 8 * per,
                                                  8 * per))]
        at = jax.random.randint(ks[5], (b,), 0, 8 * window)
        live = jnp.arange(b) % 4 != 1
        stop_w = jnp.where(live, at % window + 1, 0)
        stop_c = jnp.where(live, at // window * per, 0)
        compare(f"ragged_two_leaf_attention b{b} rows {window} + {8 * per} "
                f"h{h} d{d}",
                lambda: ragged_two_leaf_attention(
                    q, leaves[:2], leaves[2:], stop_w, stop_c).reshape(b, -1),
                lambda: merge_partials(
                    partial_walk(q, *leaves[:2], stop_w, jnp.max(stop_w)),
                    partial_walk(q, *leaves[2:], stop_c, jnp.max(stop_c))
                ).reshape(b, -1), h * d)
        two_leaf = two_leaf_refusal(q.shape, leaves[0].shape, leaves[2].shape)
        del leaves

        # An "eva" layer's prefill past its first window: three windows of
        # EvaByte's, the prompt ending inside the third.
        s, chunk, plen = 6144, 16, 5000
        ks = jax.random.split(jax.random.PRNGKey(51), 5)
        q, k, v = (jax.random.normal(key, (1, s, h, d), jnp.bfloat16)
                   for key in ks[:3])
        kbar, vbar = (t.astype(jnp.bfloat16) for t in jax.jit(
            lambda k, v, mu, phi: summaries(k, v, mu, phi, chunk))(
                k, v, *(jax.random.normal(key, (h, d)) * d ** -0.5
                        for key in ks[3:])))
        compare(f"two_source_attention b1 s{s} h{h} d{d} window {window} "
                f"chunk {chunk} prompt {plen}",
                lambda: two_source_attention(
                    q, k, v, kbar, vbar, window=window, chunk=chunk,
                    q_len=jnp.full((1,), plen, jnp.int32)),
                lambda: jax.jit(lambda *rows: eva_sequence(
                    *rows, window, chunk))(q, k, v, kbar, vbar), plen)
        two_source = two_source_refusal(q.shape, window, chunk)
        del q, k, v, kbar, vbar

        # The expert decode step at the four expert cells' layers: a router
        # narrow enough that every held expert gets rows (dense routing),
        # and one sixteen times as wide, of which this share holds a
        # sixteenth (sparse: some held experts get none, and are not read). `serving`
        # picks the arm: a decode step takes the kernel on the chip, the
        # same rows outside serving the dense arm. The kernel makes the
        # dense arm's roundings and float32 sums in its order, so the two
        # results are held to the same BITS (a share of 1e-3 is allowed for
        # what the compiler makes of the shared expert beside either arm).
        from ray_tpu.models.moe import MoE
        from ray_tpu.models.transformer import TransformerConfig
        from ray_tpu.ops.expert_decode import occupied_refusal

        sigmoid = dict(moe_scoring="sigmoid", moe_norm_topk=True,
                       moe_routed_scale=2.5, moe_shared_experts=1)
        expert_cells = {  # slots, hidden, held experts, expert width, router
            "b32 d6144 x 2048": (32, 6144, 16, 2048, dict(
                moe_zero_experts=8, moe_norm_topk=False,
                moe_routed_scale=6.0)),
            "b32 d7168 x 2048": (32, 7168, 12, 2048, sigmoid),
            "b16 d2048 x 1024": (16, 2048, 16, 1024, sigmoid),
            "b64 d2304 x 1024": (64, 2304, 16, 1024, sigmoid)}
        refusals = {}
        for cell, (b, d, held, ff, router) in expert_cells.items():
            refusals[cell] = occupied_refusal((b, 1, d), ff, serving=True)
            for routing, published in (("dense", held), ("sparse", 16 * held)):
                layer = MoE(TransformerConfig(
                    vocab_size=8, d_model=d, n_layers=1, n_heads=1, d_ff=ff,
                    max_seq=8, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                    moe_experts=published, experts_held=held, moe_top_k=6,
                    moe_d_ff=ff, moe_score_bias=True, **router))
                x = jax.random.normal(jax.random.PRNGKey(43), (b, 1, d),
                                      jnp.bfloat16)
                weights = jax.jit(layer.init)(jax.random.PRNGKey(44), x)
                arms = {serving: jax.jit(
                    lambda w, x, serving=serving: layer.apply(
                        w, x, serving=serving, mutable=["stats"]))
                    for serving in (True, False)}
                out = {serving: arm(weights, x)
                       for serving, arm in arms.items()}
                picks = {serving: np.asarray(o[1]["stats"]["picks"])
                         for serving, o in out.items()}
                compare(f"occupied_experts {cell.split(' x ')[0]} on {held} "
                        f"held experts of {ff}, {routing} routing, touched "
                        f"{picks[True][2]}, read {picks[True][3]} (the "
                        f"dense arm {picks[False][3]})",
                        lambda: out[True][0].reshape(b, -1),
                        lambda: out[False][0].reshape(b, -1), d)
                other_bits = float(np.mean(
                    np.asarray(out[True][0], np.float32)
                    != np.asarray(out[False][0], np.float32)))
                rows[-1]["other_bits_share"] = other_bits
                rows[-1]["ok"] = bool(
                    rows[-1]["ok"] and other_bits <= 1e-3
                    and picks[True][3] == picks[True][2]
                    and picks[False][3] == held
                    and (picks[True][2] == held if routing == "dense"
                         else 0 < picks[True][2] < held))
                del weights, out

        return {"platform": dev.platform, "device_kind": dev.device_kind,
                "rows": rows,
                "expert_kernel_refusal": refusals,
                "latent_kernel_refusal": latent_refusal(
                    (32, 4096, row), rank),
                "two_leaf_kernel_refusal": two_leaf,
                "two_source_kernel_refusal": two_source,
                "prefill_kernel_refusal": kernel_refusal(
                    (1, 128, heads, hd), (1, 128, heads, hd)),
                "decode_kernel_refusal": walk_refusal(
                    (8, heads, hd), (8, model["max_seq"], heads, hd))}


# -------------------------------------------------------------------- phases
def preflight() -> int:
    """Chips of this host, found without JAX. Exits when there is none."""
    platforms = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if platforms and "tpu" not in platforms.split(","):
        sys.exit(f"chip_smoke: no TPU: JAX_PLATFORMS={platforms} holds this "
                 f"run to another platform; nothing was served")
    from ray_tpu._private.accelerators import num_tpu_chips

    chips = num_tpu_chips()
    if chips < 1:
        sys.exit("chip_smoke: no TPU found: this host has no /dev/accel* or "
                 "/dev/vfio/* device files; nothing was served")
    return chips


def serve_phase(serve, LLMConfig, build_openai_app, chips: int) -> dict:
    """Deploy, warm up, answer a wave of requests. Returns the replicas'
    last /v1/stats and the served greedy (prompt, tokens) pairs."""
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    wave = wave_requests(chips)
    app = build_openai_app(
        LLMConfig(**MODEL), num_replicas=chips, max_batch=MAX_BATCH,
        decode_chunk=DECODE_CHUNK, ray_actor_options={"num_tpus": 1},
        max_ongoing_requests=len(wave))
    t0 = time.monotonic()
    serve.run(app, port=port, timeout_s=left(300))
    t_ready = time.monotonic() - t0
    stats = replica_stats(base, chips, left(60))
    for st in stats:
        say(f"replica pid={st['pid']} platform={st['platform']} "
            f"kind={st['device_kind']!r} device_ids={st['device_ids']} "
            f"holds open {st['chip_files_open']} "
            f"(booked TPU_VISIBLE_CHIPS={st['tpu_visible_chips']}) "
            f"runtime_init={st['runtime_init_s']}s "
            f"engine_init={st['engine_init_s']}s")
        require(st["platform"] == "tpu",
                f"replica {st['pid']} runs on {st['platform']!r}, not a TPU")
        require(bool(st["device_kind"]), "replica reports no device_kind")
        # Every narrowed process calls its one device id 0, so which chip a
        # replica runs on is read from the device files its process holds
        # open (/proc/self/fd), not from what the node agent told it.
        require(len(st["device_ids"]) == 1 and len(st["chip_files_open"]) == 1,
                f"one-chip replica {st['pid']} sees devices "
                f"{st['device_ids']} and holds open {st['chip_files_open']}")
    held = [tuple(st["chip_files_open"]) for st in stats]
    require(len(set(held)) == chips,
            f"replicas do not hold distinct chips open: {held}")
    booked = [st["tpu_visible_chips"] for st in stats]
    require(len(set(booked)) == chips and None not in booked,
            f"the node agent did not book distinct chips: {booked}")
    say(f"set-up: deployment ready in {t_ready:.1f}s (process start, TPU "
        f"runtime init, parameters)")

    warm = warmup_requests()
    t0 = time.monotonic()
    warm_outs = [complete(base, r, left(300)) for r in warm]
    again = complete(base, warm[0], left(120))
    t_warm = time.monotonic() - t0
    check_outputs(warm, warm_outs)
    require(again["tokens"] == warm_outs[0]["tokens"],
            "two greedy runs of one prompt, alone in the batch, disagree")
    mid = replica_stats(base, chips, left(60))
    say(f"set-up: {len(warm) + 1} warm-up requests, one at a time, in "
        f"{t_warm:.1f}s ({sum(s['compile_count'] for s in mid)} programs "
        f"built, {sum(s['compile_s'] for s in mid):.1f}s in the compiler "
        f"or its cache); two greedy runs of one prompt agree")

    t0 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(len(wave)) as pool:
        futs = [pool.submit(complete, base, r, left(300)) for r in wave]
        outs = [f.result(timeout=left(310)) for f in futs]
    t_wave = time.monotonic() - t0
    check_outputs(wave, outs)
    end = replica_stats(base, chips, left(60))
    for st in end:
        require(st["served"] >= 1, f"replica {st['pid']} served no request")
        require(st["active"] == 0,
                f"replica {st['pid']} still holds {st['active']} slots")
        say(f"replica pid={st['pid']} kv cache {st['cache_layout']}; "
            f"{st['cache_boundary_copies']} whole-leaf copies in its chunk "
            f"program; peak device memory "
            f"{st['memory_peak_bytes'] / 2**30:.2f} GiB; decode steps "
            f"walked {st['kv_walk_share']:.3f} of max_seq "
            f"(live rows {st['kv_live_share']:.3f}); {st['splices']} "
            f"hand-overs of a batch row, {st['splices_in_flight']} behind "
            f"a chunk in flight, {st['pipeline_dry']} passes began with "
            f"nothing in flight, {st['cover_chunks']} cover chunks; "
            f"{st['prefill_rows']} rows of prefill "
            f"buckets, {st['prefill_rows_kernel']} of them through the "
            f"flash kernel; {st['decode_steps']} decode steps, "
            f"{st['decode_steps_kernel']} of them through the ragged "
            f"kernel; {st['sampler_steps']} sampled, "
            f"{st['sampler_steps_select']} of them by selection")
        # Every sampled request here asks for a top_k of 50: no step of the
        # wave sorts the vocabulary.
        require(st["sampler_steps_select"] == st["sampler_steps"] > 0,
                f"replica {st['pid']}: {st['sampler_steps_select']} of "
                f"{st['sampler_steps']} sampled decode steps selected")
        # Heads of 128 and buckets of at least one lane tile: on the chip
        # the dispatcher's rule gives every prefill of MODEL the kernel.
        require(st["prefill_rows_kernel"] == st["prefill_rows"] > 0,
                f"replica {st['pid']}: {st['prefill_rows_kernel']} of "
                f"{st['prefill_rows']} prefill rows went through the flash "
                f"kernel")
        # Rows of whole lane tiles: on the chip the dispatcher's rule gives
        # every bounded decode step of MODEL the ragged kernel.
        require(st["decode_steps_kernel"] == st["decode_steps"] > 0,
                f"replica {st['pid']}: {st['decode_steps_kernel']} of "
                f"{st['decode_steps']} decode steps went through the ragged "
                f"kernel")
        # The cache must cross a program's boundary in the layout the
        # decode loop computes in: a copy of a whole leaf there is a
        # conversion paid by every chunk, whatever its length.
        require(st["cache_boundary_copies"] == 0,
                f"replica {st['pid']}: the chunk program copies whole cache "
                f"leaves {st['cache_boundary_copies']} times "
                f"(kv cache {st['cache_layout']})")
    built = (sum(s["compile_count"] for s in end)
             - sum(s["compile_count"] for s in mid))
    say(f"requests: {len(wave)} at once answered in {t_wave:.1f}s; token "
        f"counts and finish reasons hold; {built} programs built inside "
        f"it; served per replica: {sorted(s['served'] for s in end)}")
    greedy = {(tuple(r["prompt"]), tuple(o["tokens"]))
              for r, o in zip(warm + wave, warm_outs + outs)
              if r["temperature"] == 0.0}
    return {"stats": end,
            "greedy": [(list(p), list(t)) for p, t in sorted(greedy)]}


def latent_phase(serve, LLMConfig, build_openai_app) -> None:
    """One replica of LATENT_MODEL: greedy requests of mixed lengths, more
    than it has slots, so that slots of different lengths decode together
    and stand free in between; the line `/v1/stats` gives of it."""
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    rng = random.Random(1)
    vocab = LATENT_MODEL["vocab_size"]
    reqs = [_request([rng.randrange(vocab) for _ in range(n)], m, 0.0,
                     i % 2 == 0)
            for i, (n, m) in enumerate([(100, 33), (900, 17), (200, 48),
                                        (1500, 24), (40, 40), (600, 9),
                                        (300, 31), (1100, 20), (70, 16),
                                        (450, 28)])]
    serve.run(build_openai_app(
        LLMConfig(**LATENT_MODEL), num_replicas=1, max_batch=MAX_BATCH,
        decode_chunk=DECODE_CHUNK, ray_actor_options={"num_tpus": 1},
        max_ongoing_requests=len(reqs)), port=port, timeout_s=left(300))
    with concurrent.futures.ThreadPoolExecutor(len(reqs)) as pool:
        futs = [pool.submit(complete, base, r, left(300)) for r in reqs]
        check_outputs(reqs, [f.result(timeout=left(310)) for f in futs])
    (st,) = replica_stats(base, 1, left(60))
    block = 512 / LATENT_MODEL["max_seq"]  # (a MiB of rows of 640)
    say(f"latent replica pid={st['pid']} {st['cache_kind']} cache "
        f"{st['cache_layout']}; {st['cache_boundary_copies']} whole-leaf "
        f"copies in its chunk program; {st['decode_steps']} decode steps, "
        f"{st['decode_steps_kernel']} of them through the latent kernel; "
        f"they walked {st['kv_walk_share']:.3f} of max_seq (live rows "
        f"{st['kv_live_share']:.3f})")
    require(st["cache_kind"] == "latent" and ", 640]" in st["cache_layout"],
            f"not a latent cache in rows of 640: {st['cache_layout']}")
    require(st["decode_steps_kernel"] == st["decode_steps"] > 0,
            f"latent replica: {st['decode_steps_kernel']} of "
            f"{st['decode_steps']} decode steps went through the latent "
            f"kernel")
    require(st["kv_live_share"] <= st["kv_walk_share"]
            < st["kv_live_share"] + block,
            f"latent replica: walked {st['kv_walk_share']:.3f} of max_seq, "
            f"over a block beyond the live rows' {st['kv_live_share']:.3f}")
    require(st["cache_boundary_copies"] == 0,
            f"latent replica: the chunk program copies whole cache leaves "
            f"{st['cache_boundary_copies']} times")


def chip_phase(ray_tpu, greedy: list) -> None:
    probe = ray_tpu.remote(num_cpus=0, num_tpus=1)(ChipProbe).remote()
    try:
        ref = (ray_tpu.get(probe.reference.remote(MODEL, greedy),
                           timeout=left(300)) if greedy else [])
        # (21 rows, each a kernel and its XLA form compiled: 5 min at PR 50)
        rep = ray_tpu.get(probe.kernels.remote(MODEL),
                          timeout=left(480))
    finally:
        ray_tpu.kill(probe)

    say(f"chip actor on {rep['platform']} {rep['device_kind']!r}")
    for row in ref:
        say(f"  served greedy plen={row['plen']} n={row['n']}: "
            f"{row['argmax_matches']}/{row['n']} tokens are the reference "
            f"argmax, worst logit gap {row['max_gap']:.4f}")
    for row in rep["rows"]:
        if row["ok"]:
            say(f"  ok   {row['kernel']}: max|err|={row['max_abs_err']:.4f} "
                f"({row['compile_and_run_s']}s)")
        else:
            say(f"  FAIL {row['kernel']}: {row.get('error') or row}")
    say("attention by phase: a decode step reads the cache through "
        "ops/decode_attention.py, which for this model's leaves chooses "
        + ("the ragged Pallas kernel"
           if rep["decode_kernel_refusal"] is None
           else f"the XLA walk ({rep['decode_kernel_refusal']})")
        + (", and a latent model's through the latent kernel"
           if rep["latent_kernel_refusal"] is None
           else f", and a latent model's through its own XLA walk "
                f"({rep['latent_kernel_refusal']})")
        + (", and an \"eva\" layer's two leaves through the two-leaf kernel"
           if rep["two_leaf_kernel_refusal"] is None
           else f", and an \"eva\" layer's two leaves through two XLA walks "
                f"({rep['two_leaf_kernel_refusal']})")
        + (", and an expert layer's decode step reads the held experts that "
           "got a row"
           if not any(rep["expert_kernel_refusal"].values())
           else f", and an expert layer's through every held expert "
                f"({rep['expert_kernel_refusal']})")
        + "; a prefill goes through dot_product_attention, which for this "
        "model's buckets chooses "
        + ("the Pallas flash kernel"
           if rep["prefill_kernel_refusal"] is None
           else f"the XLA form ({rep['prefill_kernel_refusal']})")
        + (", and an \"eva\" layer's past its first window through the "
           "two-source kernel"
           if rep["two_source_kernel_refusal"] is None
           else f", and an \"eva\" layer's past its first window through "
                f"the tile scan ({rep['two_source_kernel_refusal']})")
        + "; each replica's line above counts the steps and rows either "
        "form served")
    require(rep["platform"] == "tpu", "chip actor did not run on a TPU")
    require(bool(ref), "no served greedy continuation to check")
    off = [r for r in ref
           if not r["finite"] or r["max_gap"] > REFERENCE_LOGIT_TOL]
    require(not off, f"{len(off)} served continuations disagree with the "
                     f"cache-free reference: {off}")
    bad = [r["kernel"] for r in rep["rows"] if not r["ok"]]
    require(not bad, f"{len(bad)} kernel checks failed: {bad}")


def serving_entries(cache_dir: str) -> set[str]:
    try:
        return {n for n in os.listdir(cache_dir)
                if n.startswith(SERVING_PROGRAMS)}
    except FileNotFoundError:
        return set()


def main() -> int:
    chips = preflight()

    import ray_tpu
    from ray_tpu import _native, serve
    from ray_tpu._private import compile_cache
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.openai import build_openai_app

    # Where the workers will keep what they compile (this process compiles
    # nothing, so its own environment is left alone).
    cache_dir = compile_cache.apply(dict(os.environ))
    entries_before = compile_cache.entries(cache_dir)
    serving_before = serving_entries(cache_dir)
    say(f"host chips: {chips}; compile cache: {cache_dir} "
        f"({entries_before} entries, {len(serving_before)} of the serving "
        f"programs)")

    failed: list[str] = []

    def phase(name: str, fn):
        say(f"--- {name}")
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 - every phase is reported
            failed.append(name)
            say(f"FAILED {name}: {type(e).__name__}: {e}")
            return None

    native = _native.get_lib() is not None
    say(f"_native.get_lib() loaded: {native}")
    if not native:
        failed.append("native")

    device = None
    t0 = time.monotonic()
    ray_tpu.init()
    try:
        have = ray_tpu.cluster_resources().get("TPU", 0)
        say(f"ray_tpu.init(): {time.monotonic() - t0:.1f}s, "
            f"cluster TPU={have:g}")
        if have != chips:
            failed.append("init")
            say(f"FAILED init: cluster advertises TPU={have}, "
                f"host has {chips}")
        served = phase("serve", lambda: serve_phase(
            serve, LLMConfig, build_openai_app, chips))
        phase("serve shutdown", serve.shutdown)
        phase("serve a latent model", lambda: latent_phase(
            serve, LLMConfig, build_openai_app))
        phase("latent shutdown", serve.shutdown)
        dead = [n["NodeID"][:8] for n in ray_tpu.nodes() if not n["Alive"]]
        if dead:
            failed.append("nodes")
            say(f"FAILED nodes: declared dead during the run: {dead}")
        if served:
            stats = served["stats"]
            device = {"platform": stats[0]["platform"],
                      "kind": stats[0]["device_kind"],
                      "count": sum(len(s["device_ids"]) for s in stats)}
        if not dead:
            phase("reference and kernels", lambda: chip_phase(
                ray_tpu, served["greedy"] if served else []))
    finally:
        ray_tpu.shutdown()

    entries_after = compile_cache.entries(cache_dir)
    new_serving = sorted(serving_entries(cache_dir) - serving_before)
    say(f"compile cache: {entries_before} -> {entries_after} entries; "
        f"{len(new_serving)} new for the serving programs")
    say(f"total: {time.monotonic() - T_START:.1f}s")
    if "jax" in sys.modules:
        failed.append("parent imported jax")
    if failed or device is None:
        print(json.dumps({"ok": False, "failed": failed}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
