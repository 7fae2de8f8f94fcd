"""ray_tpu.llm — LLM batch inference + serving on the cluster runtime.

Parity target: reference python/ray/llm (_internal/batch/processor — Data
map_batches pipelines with a stateful model actor; _internal/serve/
deployments/llm/llm_server.py — a Serve deployment wrapping an engine).
The reference delegates the engine to vLLM; here the engine is the native
flagship Transformer with KV-cached greedy decoding: one prefill pass
fills per-layer caches, then every generated token is a fixed-shape
compiled step under lax.scan (see LLMEngine). A step yields one token a
sequence for every model but one kind: a model that generates by diffusion
over blocks (`model_type: sdar_moe`) is stepped a FORWARD of a whole block a
sequence, several forwards finish a block and a forward yields no token or
several (`ContinuousEngine` alone serves it: llm/engine.py).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np


@dataclass
class LLMConfig:
    """reference llm_config.py (model_loading_config + engine args)."""

    vocab_size: int = 512
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 8
    max_seq: int = 256
    max_new_tokens: int = 16
    seed: int = 0
    #: "bfloat16" halves cache/activation bytes and roughly doubles decode
    #: throughput on TPU; float32 keeps CPU-test numerics exact.
    dtype: str = "float32"
    #: optional pytree of trained params; random init otherwise
    params: Any = None
    #: The architecture, in the keys of the model's published `config.json`
    #: (`model_type` first, and the experts as PUBLISHED): `models/published.py`
    #: `model_config` has an arm a `model_type`, reads the rest from here and
    #: refuses a key it does not know how to build. None is the Llama-style
    #: block the six sizes above describe alone (MHA, SwiGLU of 8/3 d, a tied
    #: head). The sizes above stay what is RUN (a vocabulary slice, a cut in
    #: depth).
    arch: Optional[dict] = None
    #: This device's share of the routed experts under expert parallelism:
    #: `[first_expert, first_expert + experts_held)` of `n_routed_experts`;
    #: 0 holds them all.
    experts_held: int = 0
    first_expert: int = 0


class LLMEngine:
    """Greedy-decoding engine over the flagship Transformer (the seat the
    reference gives vLLM). KV-cache decode: prefill fills per-layer caches
    in one pass, then every generated token is ONE fixed-shape compiled
    step attending over the cache — O(S) per token instead of the naive
    O(S^2) re-forward of the growing context."""

    def __init__(self, cfg: LLMConfig):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.published import model_config
        from ray_tpu.models.transformer import Transformer

        self.cfg = cfg
        self.model = Transformer(model_config(cfg))
        if self.model.cfg.block_length:
            raise NotImplementedError(
                "LLMEngine decodes one token a step: a model that generates "
                "by diffusion over blocks is served by ContinuousEngine")
        if cfg.params is not None:
            self.params = cfg.params
        else:
            dummy = jnp.zeros((1, 8), jnp.int32)
            self.params = self.model.init(
                jax.random.PRNGKey(cfg.seed), dummy)

        def _prefill(params, toks):
            """Full-prompt pass that also fills the KV caches."""
            b, s = toks.shape
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
            logits, vars_out = self.model.apply(
                params, toks, positions=positions, decode=True,
                mutable=["cache"])
            return jnp.argmax(logits[:, -1, :], axis=-1), vars_out["cache"]

        def _decode(params, cache, first_tok, start_pos, n_steps):
            """n_steps single-token cached steps under ONE lax.scan."""
            def step(carry, _):
                cache, tok, pos = carry
                logits, vars_out = self.model.apply(
                    {**params, "cache": cache}, tok[:, None],
                    positions=pos[:, None], decode=True, mutable=["cache"])
                nxt = jnp.argmax(logits[:, -1, :], axis=-1)
                return (vars_out["cache"], nxt, pos + 1), tok

            # length=n_steps-1: the scan COLLECTS the carried-in token each
            # step, so [first, g2..g_{n-1}] plus the final carry `last`
            # covers all n tokens without a wasted trailing forward pass.
            (cache, last, _), toks = jax.lax.scan(
                step, (cache, first_tok, start_pos), None,
                length=n_steps - 1)
            return jnp.moveaxis(toks, 0, 1), last  # [B, n_steps-1], [B]

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode, static_argnums=4)

    def generate(self, prompts: np.ndarray,
                 max_new_tokens: Optional[int] = None) -> np.ndarray:
        """prompts: [B, S] int32 -> [B, S + new] (greedy, KV-cached)."""
        import jax.numpy as jnp

        toks = jnp.asarray(prompts, jnp.int32)
        b, s = toks.shape
        n = max_new_tokens or self.cfg.max_new_tokens
        if s + n > self.cfg.max_seq:
            # The KV cache is a fixed [B, max_seq] buffer; requests past it
            # must fail loudly, not silently return fewer tokens.
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({n}) exceeds the engine's "
                f"max_seq ({self.cfg.max_seq})")
        first, cache = self._prefill({"params": self.params["params"]}, toks)
        start_pos = jnp.full((b,), s, jnp.int32)
        if n == 1:
            return np.asarray(jnp.concatenate([toks, first[:, None]], axis=1))
        gen, last = self._decode({"params": self.params["params"]}, cache,
                                 first, start_pos, n)
        # gen = [first, g2..g_{n-1}] (the scan collects carried-in tokens);
        # `last` completes the n generated tokens.
        out = jnp.concatenate([toks, gen, last[:, None]], axis=1)
        return np.asarray(out)


class LLMPredictor:
    """map_batches callable class (reference batch processor's stateful
    UDF): the engine loads once per actor."""

    def __init__(self, cfg: LLMConfig):
        self.engine = LLMEngine(cfg)

    def __call__(self, batch: dict) -> dict:
        out = self.engine.generate(np.asarray(batch["tokens"]))
        return {"tokens": batch["tokens"], "generated": out}


def batch_inference(ds, cfg: LLMConfig, *, concurrency: int = 1):
    """Run generation over a Dataset of {'tokens': [S] int} rows
    (reference llm batch processor: Data pipeline + engine actors)."""
    return ds.map_batches(LLMPredictor, concurrency=concurrency,
                          fn_constructor_args=(cfg,))


def __getattr__(name):
    # Lazy: the continuous engine / OpenAI surface pull in jax + serve.
    if name in ("ContinuousEngine", "SamplingParams", "GenStream"):
        from ray_tpu.llm import engine as _e

        return getattr(_e, name)
    if name in ("PipelinedEngine", "PipelineStage"):
        from ray_tpu.llm import pipeline as _p

        return getattr(_p, name)
    if name in ("build_openai_app", "OpenAIServer", "ByteTokenizer"):
        from ray_tpu.llm import openai as _o

        return getattr(_o, name)
    raise AttributeError(name)


def build_llm_deployment(cfg: LLMConfig, *, name: str = "llm",
                         num_replicas: int = 1,
                         ray_actor_options: Optional[dict] = None):
    """A Serve application serving generate() over HTTP/handle (reference
    llm_server.py build_llm_deployment)."""
    from ray_tpu import serve

    @serve.deployment(name=name, num_replicas=num_replicas,
                      ray_actor_options=ray_actor_options)
    class LLMServer:
        def __init__(self, llm_cfg: LLMConfig):
            self.engine = LLMEngine(llm_cfg)

        def __call__(self, request):
            body = request.json()
            prompts = np.asarray(body["tokens"], np.int32)
            if prompts.ndim == 1:
                prompts = prompts[None]
            out = self.engine.generate(
                prompts, body.get("max_new_tokens"))
            return {"generated": out.tolist()}

    return LLMServer.bind(cfg)
