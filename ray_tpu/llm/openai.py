"""OpenAI-compatible serving surface for the continuous-batching engine.

Parity target: the reference's OpenAI router + application builder
(python/ray/llm/_internal/serve/deployments/routers/router.py — /v1/models,
/v1/completions, /v1/chat/completions with SSE streaming — and
builders/application_builders.py build_openai_app). The engine behind the
routes is the native TPU ContinuousEngine (llm/engine.py) instead of vLLM;
prompts are strings (byte-level tokenizer) or raw token lists.
"""

from __future__ import annotations

import codecs
import json
import os
import time
from typing import Any, Optional

from ray_tpu.llm import LLMConfig
from ray_tpu.llm.engine import ContinuousEngine, GenStream, SamplingParams


class ByteTokenizer:
    """Byte-level tokenizer: token = byte value; BOS=256, EOS=257. Needs
    vocab_size >= 258. Stands in for the reference's HF tokenizer load
    (model_loading_config) — swap in a trained tokenizer the same way."""

    BOS = 256
    EOS = 257
    vocab_size = 258

    def encode(self, text: str, *, bos: bool = True) -> list[int]:
        toks = list(text.encode("utf-8"))
        return ([self.BOS] if bos else []) + toks

    def decode(self, tokens) -> str:
        data = bytes(t for t in tokens if 0 <= t < 256)
        return data.decode("utf-8", "replace")

    def stream_decoder(self):
        """`decode(tokens, final=False)` for ONE stream, fed a batch at a
        time: a character whose bytes straddle two batches comes out whole
        with the second of them, so the text a stream adds up to is
        `decode` of all its tokens wherever its batches were cut (they are
        cut by timing: what the engine had ready at each wakeup)."""
        utf8 = codecs.getincrementaldecoder("utf-8")("replace")
        return lambda tokens, final=False: utf8.decode(
            bytes(t for t in tokens if 0 <= t < 256), final)


def _sampling_from_body(body: dict, default_max: int) -> SamplingParams:
    return SamplingParams(
        temperature=float(body.get("temperature", 1.0)),
        top_k=int(body.get("top_k", 0)),
        top_p=float(body.get("top_p", 1.0)),
        max_tokens=int(body.get("max_tokens", default_max)),
        stop_token=body.get("stop_token"),
        seed=int(body.get("seed", 0)),
    )


def _device_report() -> dict:
    """This process's devices as JAX reports them, the chip device files it
    really holds open, the chips the node agent booked to it
    (TPU_VISIBLE_CHIPS; None when it was granted none), what it has
    compiled since the server was built, and the most device memory it has
    held at once (None where the backend keeps no such count)."""
    import jax

    from ray_tpu._private import accelerators, telemetry

    devs = jax.local_devices()
    compiled = telemetry.compile_stats()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_ids": [d.id for d in devs],
            "chip_files_open": accelerators.open_chip_files(),
            "tpu_visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "compile_count": compiled["count"],
            "compile_s": round(compiled["seconds"], 3),
            "memory_peak_bytes": (devs[0].memory_stats() or {}).get(
                "peak_bytes_in_use")}


class OpenAIServer:
    """Deployment callable serving /v1/models, /v1/completions and
    /v1/chat/completions (reference LLMRouter + LLMServer collapsed into
    one deployment; the engine IS local, no second hop needed)."""

    def __init__(self, cfg: LLMConfig, model_id: str = "ray-tpu-llm",
                 max_batch: int = 8, decode_chunk: int = 8,
                 default_max_tokens: int = 64,
                 pipeline_stages: Optional[int] = None):
        self.cfg = cfg
        self.model_id = model_id
        self.default_max_tokens = default_max_tokens
        self.tok = ByteTokenizer()
        self._served = 0
        # Set-up, told by the process's account (telemetry.py; /v1/stats
        # reads it): starting the device runtime (the first call that needs
        # a device) and building the engine (parameters made and placed;
        # the serving programs are built from the last start's list, or on
        # the first request of each shape: `llm/programs.py`).
        import jax

        from ray_tpu._private import telemetry

        telemetry.ensure_compile_listener()
        with telemetry.setup_stage("runtime.init"):
            jax.local_devices()
        # pipeline_stages > 1 swaps in the pipeline-parallel engine
        # (README "Pipeline-parallel serving"); None defers to RT_PP_STAGES
        # so a deployment can be re-pointed without a code change. The two
        # engines share the submit()/GenStream surface, so every route —
        # and the serve admission layer above — is engine-agnostic.
        from ray_tpu._private.rtconfig import CONFIG

        stages = (int(CONFIG.pp_stages) if pipeline_stages is None
                  else int(pipeline_stages))
        with telemetry.setup_stage("engine.init"):
            if stages > 1:
                from ray_tpu.llm.pipeline import PipelinedEngine

                self.engine = PipelinedEngine(
                    cfg, n_stages=stages, max_batch=max_batch)
            else:
                self.engine = ContinuousEngine(
                    cfg, max_batch=max_batch, decode_chunk=decode_chunk)

    # ------------------------------------------------------------ helpers
    def _encode_prompt(self, body: dict) -> list[int]:
        if "messages" in body:  # chat form
            text = "".join(
                f"<{m.get('role', 'user')}>{m.get('content', '')}"
                for m in body["messages"])
            return self.tok.encode(text)
        prompt = body.get("prompt", "")
        if isinstance(prompt, list):
            return [int(t) for t in prompt]  # raw token ids
        return self.tok.encode(str(prompt))

    def _completion_body(self, req_id: str, text: str, tokens: list[int],
                         finish: Optional[str], chat: bool,
                         stream_delta: bool = False) -> dict:
        if chat:
            key = "delta" if stream_delta else "message"
            choice = {"index": 0, key: {"role": "assistant", "content": text},
                      "finish_reason": finish}
            obj = ("chat.completion.chunk" if stream_delta
                   else "chat.completion")
        else:
            choice = {"index": 0, "text": text, "finish_reason": finish}
            obj = "text_completion"
        return {"id": req_id, "object": obj, "created": int(time.time()),
                "model": self.model_id, "choices": [choice],
                "token_ids": tokens}

    # ------------------------------------------------------------- routes
    def __call__(self, request):
        path = request.path
        if path.endswith("/v1/models") or path.endswith("/models"):
            return {"object": "list",
                    "data": [{"id": self.model_id, "object": "model",
                              "owned_by": "ray_tpu"}]}
        if path.endswith("/v1/stats") or path.endswith("/stats"):
            # Introspection for chaos tests / ops: which process hosts the
            # engine and how many slots are live (a leaked slot shows here).
            # `served` counts the requests this replica has taken, and the
            # device fields are what JAX reports inside this process — the
            # proof of which chip a replica really runs on.
            from ray_tpu._private import telemetry

            setup = telemetry.ACCOUNT.summary()
            out = {"pid": os.getpid(), "active": self.engine.num_active,
                   "running": self.engine._running, "served": self._served,
                   "runtime_init_s": setup["stages"]["runtime.init"],
                   "engine_init_s": setup["stages"]["engine.init"],
                   "setup": setup, **_device_report()}
            stages = getattr(self.engine, "n_stages", 0)
            if stages:
                out["pipeline_stages"] = stages
                out["stages"] = self.engine.stage_devices()
            else:
                out.update(self.engine.cache_stats())
                # how the serving programs came to be (`llm/programs.py`)
                setup.update(self.engine.program_stats())
            return out
        self._served += 1
        body = request.json() or {}
        chat = "chat" in path or "messages" in body
        prompt = self._encode_prompt(body)
        sampling = _sampling_from_body(body, self.default_max_tokens)
        req_id = f"cmpl-{int(time.time() * 1e6):x}"
        stream = self.engine.submit(prompt, sampling)
        if body.get("stream"):
            return self._stream_chunks(req_id, stream, chat)
        toks = stream.tokens()
        return self._completion_body(
            req_id, self.tok.decode(toks), toks, stream.finish_reason, chat)

    def _stream_chunks(self, req_id: str, stream: GenStream, chat: bool):
        """Generator of OpenAI SSE chunk dicts — one per token BATCH
        (GenStream.next_batch drains every token available per wakeup, so
        a chunk of decode output is one dict, one downstream flush — not
        one wakeup and one SSE event per token). A model that generates by
        blocks gives a block's tokens when the block commits, so an event
        holds the tokens of the blocks a chunk committed; `usage` and
        `finish_reason` are what they are for every model."""
        def gen():
            decode = self.tok.stream_decoder()
            try:
                while True:
                    try:
                        toks = stream.next_batch()
                    except StopIteration:
                        break
                    yield self._completion_body(
                        req_id, decode(toks), toks, None, chat,
                        stream_delta=True)
                # (the text of bytes that never became a whole character)
                yield self._completion_body(
                    req_id, decode([], True), [],
                    stream.finish_reason or "length", chat,
                    stream_delta=True)
            finally:
                # Consumer gone (client disconnect propagates as
                # GeneratorExit through the serve streaming path): free the
                # engine slot instead of decoding to max_tokens for nobody.
                stream.close()
        return gen()

    def check_health(self):
        if not self.engine._running:
            raise RuntimeError("llm engine stopped")

    def __del__(self):
        try:
            self.engine.shutdown()
        except Exception:
            pass


def build_openai_app(cfg: LLMConfig, *, name: str = "llm",
                     model_id: str = "ray-tpu-llm", num_replicas: int = 1,
                     max_batch: int = 8, decode_chunk: int = 8,
                     default_max_tokens: int = 64,
                     ray_actor_options: Optional[dict] = None,
                     max_ongoing_requests: int = 16,
                     max_queued_requests: int = -1,
                     queue_deadline_s: Optional[float] = None,
                     pipeline_stages: Optional[int] = None):
    """Serve application exposing the OpenAI surface (reference
    build_openai_app, application_builders.py). The admission budgets
    (README "Overload & admission control") pass straight through to the
    deployment: cap ongoing requests near max_batch so excess load sheds
    fast 429s at the proxy instead of stacking onto the engine's queue."""
    from ray_tpu import serve
    from ray_tpu._private.rtconfig import CONFIG

    stages = (int(CONFIG.pp_stages) if pipeline_stages is None
              else int(pipeline_stages))
    opts = ray_actor_options or {}
    if stages > 1 and (opts.get("num_tpus")
                       or (opts.get("resources") or {}).get("TPU")):
        # A chip belongs to one process: the replica of a pipelined
        # deployment only schedules, and each of its stages asks for a chip
        # of its own (llm/pipeline.py `_stage_options`).
        raise ValueError(
            f"pipeline_stages={stages}: deploy the replica without "
            f"num_tpus; each pipeline stage asks for its own TPU chip")
    dep = serve.deployment(
        OpenAIServer, name=name, num_replicas=num_replicas,
        ray_actor_options=ray_actor_options,
        max_ongoing_requests=max_ongoing_requests,
        max_queued_requests=max_queued_requests,
        queue_deadline_s=queue_deadline_s)
    return dep.bind(cfg, model_id=model_id, max_batch=max_batch,
                    decode_chunk=decode_chunk,
                    default_max_tokens=default_max_tokens,
                    pipeline_stages=pipeline_stages)
