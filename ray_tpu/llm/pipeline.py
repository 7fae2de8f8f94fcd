"""Pipeline-parallel LLM decode on the compiled DAG plane.

The ContinuousEngine (engine.py) is one process: the whole model, the
whole KV cache, one device. This module cuts the SAME model at layer
boundaries into N pipeline stages — each a long-lived actor bound into
one compiled DAG (`stage0.step -> stage1.step -> ...`) — and runs decode
iterations as DAG invocations:

- **Stage slicing**: `stage_layer_split` / `stage_param_slice` /
  `make_stage_net` keep per-layer module names GLOBAL (`layer_{i}`), so a
  stage's params are a strict subtree of the full checkpoint and the
  pipelined model is bit-compatible with the single-process one.
- **Microbatched occupancy**: the batch splits into `n_mb` microbatches;
  each decode invocation steps ONE microbatch through all stages, and
  the driver keeps every microbatch's invocation in flight at once, so
  stage k works on microbatch j while stage k+1 works on microbatch j-1
  — classic GPipe-style bubble filling, bounded by RT_DAG_MAX_INFLIGHT.
- **Zero-RPC activation edges**: stage outputs are (tag, mb, activation,
  ...) tuples; the DAG edge publisher pins the activation arrays
  (RT_DAG_EDGE_MIN_BYTES, far below the general device-object threshold)
  and ships ~200B placeholders through the shm channels, eagerly
  exported so a same-host consumer's resolve is a store hit — the steady
  state moves tokens, not activations, and pays no per-token RPC.
- **On-device sampling**: the LAST stage holds the tied head and the
  per-slot sampling mirrors (temperature/top-k/top-p/PRNG keys), so only
  sampled token ids cross back to the driver.
- **Failure contract** (mirrors the DAG plane's): a stage killed
  mid-generation fails every open GenStream with the attributed
  DagStageError (stage name, invocation, node), then the engine tears
  the graph down, rebuilds fresh stages, and resumes from the request
  queue — consumers see a typed error or tokens, never a hang.

Drop-in: PipelinedEngine exposes the ContinuousEngine surface
(`submit() -> GenStream`, `generate`, `shutdown`, `num_active`), so the
serve/OpenAI layer (PR 13 streaming, PR 17 admission control) runs
unchanged on top of it.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import logging
import os
import queue
import threading
import time
import uuid
from typing import Optional

import numpy as np

from ray_tpu._private.rtconfig import CONFIG
from ray_tpu.llm.engine import (GenStream, SamplingParams, _count_tokens,
                                _Slot)
from ray_tpu.llm.sampler import _make_sampler
from ray_tpu.models.published import model_config

logger = logging.getLogger(__name__)


def stage_layer_split(n_layers: int, n_stages: int) -> list[tuple[int, ...]]:
    """Contiguous, balanced layer ranges, one per pipeline stage (the
    remainder layers go to the EARLIEST stages: the last stage already
    carries final_norm + the tied head + the sampler)."""
    if not (1 <= n_stages <= n_layers):
        raise ValueError(
            f"n_stages ({n_stages}) must be in [1, n_layers ({n_layers})]")
    base, rem = divmod(n_layers, n_stages)
    out, start = [], 0
    for s in range(n_stages):
        n = base + (1 if s < rem else 0)
        out.append(tuple(range(start, start + n)))
        start += n
    return out


def stage_param_slice(params: dict, layers: tuple, first: bool,
                      last: bool) -> dict:
    """This stage's shard of a full Transformer param tree. Layer keys keep
    their GLOBAL names (`layer_{i}`) so a shard is a strict subtree of the
    full checkpoint; the embedding rides along on the first stage (embed)
    and the last (tied output head), an untied `lm_head` on the last."""
    out = {}
    tied = "lm_head" not in params
    if first or (last and tied):
        out["tok_emb"] = params["tok_emb"]
    for i in layers:
        out[f"layer_{i}"] = params[f"layer_{i}"]
    if last:
        out["final_norm"] = params["final_norm"]
        if not tied:
            out["lm_head"] = params["lm_head"]
    return out


def make_stage_net(mcfg, layers: tuple, first: bool, last: bool):
    """Flax module computing one pipeline stage's slice of the Transformer:
    embed (first stage) -> layers[a:b] -> final_norm + the output head
    (last stage). Per-layer module names match the full model's, so
    stage_param_slice output applies directly and a 1-stage net is
    numerically the full Transformer."""
    import flax.linen as nn

    from ray_tpu.models.transformer import Block, RMSNorm, output_head

    if mcfg.ut_steps > 1:
        raise NotImplementedError(
            f"a stage applies its layers once a token: a looped stack "
            f"(`ut_steps` {mcfg.ut_steps}) gets the LAST stage's output "
            f"back as the first stage's input {mcfg.ut_steps - 1} times a "
            f"token, each pass with cache leaves of its own, and a net "
            f"that ran one pass would be another model; it is served by "
            f"ContinuousEngine only")

    if mcfg.block_length:
        raise NotImplementedError(
            f"a stage steps one position a slot and hands on one token a "
            f"step (llm/pipeline.py `_run_scheduler`): a model that generates "
            f"by diffusion over blocks of {mcfg.block_length} positions "
            f"(several forwards a block, the last one's cache rows kept) is "
            f"served by ContinuousEngine only")

    if any(mcfg.mixer_of(i) == "eva" for i in layers):
        raise NotImplementedError(
            "pipeline stages keep one kind of cache leaf, max_seq rows a "
            "slot (llm/pipeline.py `place`, `_init_cache`): a model whose "
            "layers keep a window's rows beside one row a chunk of "
            "positions (`eva`) is served by ContinuousEngine only")

    if any(mcfg.window_of(i) for i in layers):
        raise NotImplementedError(
            "pipeline stages keep one kind of cache leaf, max_seq rows a "
            "slot (llm/pipeline.py `place`, `_init_cache`, and no "
            "`prompt_len` for a ring's hand-over): a model with window "
            "layers is served by ContinuousEngine only")

    if any(mcfg.cache_kind_of(i) == "state" for i in layers):
        raise NotImplementedError(
            "pipeline stages keep rows per position only (llm/pipeline.py "
            "`place`, `_init_cache`, and no `prompt_len` for where a padded "
            "prefill's state ends): a model with state layers (recurrent "
            "state a slot) is served by ContinuousEngine only")

    class _StageNet(nn.Module):
        @nn.compact
        def __call__(self, x, positions, decode: bool = True):
            emb = None
            if first or (last and mcfg.tie_embeddings):
                emb = self.param(
                    "tok_emb", nn.initializers.normal(0.02),
                    (mcfg.vocab_size, mcfg.d_model), mcfg.param_dtype)
            if first:
                x = emb[x].astype(mcfg.dtype)
            for i in layers:
                x = Block(mcfg, moe=mcfg.is_moe_layer(i),
                          mixer=mcfg.mixer_of(i),
                          name=f"layer_{i}")(x, positions, decode=decode)
            if last:
                x = RMSNorm(mcfg.norm_eps, name="final_norm")(x)
                x = output_head(self, mcfg, x, emb)
            return x

    return _StageNet()


# ---------------------------------------------------------------- occupancy
#: Cumulative per-stage busy time in THIS process (stage actors record into
#: it from step()). telemetry's WorkerSampler and the metrics drain hook
#: read windowed busy fractions via occupancy_snapshot — the pipeline
#: bubble is (1 - occupancy) of the busiest window.
_occ_lock = threading.Lock()
_occ: dict[str, list] = {}  # stage name -> [busy_seconds, steps]
_occ_marks: dict[str, dict] = {}  # consumer -> stage -> (t, busy_seconds)


def _occ_record(stage: str, busy_s: float) -> None:
    with _occ_lock:
        ent = _occ.setdefault(stage, [0.0, 0])
        ent[0] += busy_s
        ent[1] += 1


def occupancy_snapshot(consumer: str = "telemetry") -> dict:
    """Per-stage busy fraction of wall time since this consumer's previous
    call (first call anchors the window and reports 0.0). Empty dict when
    no pipeline stage lives in this process."""
    now = time.monotonic()
    out: dict[str, float] = {}
    with _occ_lock:
        marks = _occ_marks.setdefault(consumer, {})
        for stage, ent in _occ.items():
            busy = ent[0]
            prev = marks.get(stage)
            marks[stage] = (now, busy)
            if prev is None or now <= prev[0]:
                out[stage] = 0.0
            else:
                out[stage] = min(1.0, max(0.0,
                                          (busy - prev[1]) / (now - prev[0])))
    return out


# ------------------------------------------------------------- stage actor
class PipelineStage:
    """One pipeline stage: a contiguous layer range of the serving model
    plus its OWN per-microbatch KV caches, bound into the compiled DAG via
    `step`. The first stage embeds token ids; the last holds final_norm,
    the tied head, and the sampling state, returning token ids only.

    Messages (the DAG invocation payloads):
      ("d", mb, toks|x, lens, greedy)  one decode step for microbatch `mb`
      ("p", row, toks|x, plen, samp)   prefill one request into batch row
    Mid-pipeline, toks becomes the activation x — a jax.Array the edge
    publisher replaces with a device-object placeholder.
    """

    def __init__(self, cfg, stage_idx: int, n_stages: int, layers: tuple,
                 first: bool, last: bool, shard: dict, mb_size: int,
                 n_mb: int):
        import jax
        import jax.numpy as jnp

        self._jax, self._jnp = jax, jnp
        self.cfg = cfg
        self.name = f"pp{stage_idx}"
        self.first, self.last = bool(first), bool(last)
        self.layers = tuple(layers)
        self.mb_size, self.n_mb = int(mb_size), int(n_mb)
        mcfg = model_config(cfg)
        self.mcfg = mcfg
        self.net = make_stage_net(mcfg, self.layers, self.first, self.last)
        params = jax.tree.map(jnp.asarray, shard)
        if mcfg.dtype == jnp.bfloat16:
            params = jax.tree.map(
                lambda x: x.astype(jnp.bfloat16)
                if x.dtype == jnp.float32 else x, params)
        self.params = params
        self._sampler = _make_sampler(cfg.vocab_size) if self.last else None
        self._build_compiled()
        self._caches = [self._init_cache() for _ in range(self.n_mb)]
        if self.last:
            # Per-microbatch sampling mirrors, set at prefill: decode
            # sampling reads them on device, so the driver never ships
            # sampling state in the steady state.
            self._temps = [jnp.zeros(self.mb_size, jnp.float32)
                           for _ in range(self.n_mb)]
            self._topks = [jnp.zeros(self.mb_size, jnp.int32)
                           for _ in range(self.n_mb)]
            self._topps = [jnp.ones(self.mb_size, jnp.float32)
                           for _ in range(self.n_mb)]
            self._keys = [jax.vmap(jax.random.PRNGKey)(
                jnp.arange(self.mb_size, dtype=jnp.uint32))
                for _ in range(self.n_mb)]

    # ---------------------------------------------------------- compiled
    def _build_compiled(self):
        jax, jnp = self._jax, self._jnp
        net = self.net

        def dstep(params, cache, x, positions):
            y, vars_out = net.apply(
                {"params": params, "cache": cache}, x, positions=positions,
                decode=True, mutable=["cache"])
            return y, vars_out["cache"]

        self._dstep = jax.jit(dstep, donate_argnums=(1,))

        def prefill(params, x):
            positions = jnp.arange(x.shape[1])[None]
            y, vars_out = net.apply(
                {"params": params}, x, positions=positions, decode=True,
                mutable=["cache"])
            return y, vars_out["cache"]

        self._prefill = jax.jit(prefill)

        def place(cache, slice_cache, row):
            return jax.tree.map(
                lambda big, small: jax.lax.dynamic_update_slice(
                    big, small.astype(big.dtype),
                    (row,) + (0,) * (small.ndim - 1)),
                cache, slice_cache)

        self._place = jax.jit(place, donate_argnums=(0,))
        if not self.last:
            return
        sampler = self._sampler

        def psample(y, plen, key, temp, top_k, top_p):
            logits = jax.lax.dynamic_index_in_dim(
                y[0].astype(jnp.float32), plen - 1, 0, keepdims=False)
            return sampler(logits[None], key[None], temp[None],
                           top_k[None], top_p[None])[0]

        self._psample = jax.jit(psample)

        def dsample(y, keys, temp, top_k, top_p):
            split = jax.vmap(jax.random.split)(keys)  # [mb, 2, 2]
            toks = sampler(y[:, -1].astype(jnp.float32), split[:, 1],
                           temp, top_k, top_p)
            return toks, split[:, 0]

        self._dsample = jax.jit(dsample)

        def dgreedy(y):
            return jnp.argmax(y[:, -1], axis=-1).astype(jnp.int32)

        self._dgreedy = jax.jit(dgreedy)

    def _init_cache(self):
        """Zero KV cache for ONE microbatch of this stage's layers (traced
        via eval_shape, exactly like ContinuousEngine._init_cache)."""
        jax, jnp = self._jax, self._jnp
        b = self.mb_size
        if self.first:
            x = jnp.zeros((b, 1), jnp.int32)
        else:
            x = jnp.zeros((b, 1, self.mcfg.d_model), self.mcfg.dtype)
        pos = jnp.zeros((b, 1), jnp.int32)
        shapes = jax.eval_shape(
            lambda p, t, pp: self.net.apply(
                {"params": p}, t, positions=pp, decode=True,
                mutable=["cache"])[1]["cache"],
            self.params, x, pos)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    # -------------------------------------------------------------- step
    def step(self, msg):
        t0 = time.monotonic()
        try:
            kind = msg[0]
            if kind == "d":
                return self._step_decode(msg)
            if kind == "p":
                return self._step_prefill(msg)
            raise ValueError(f"unknown pipeline message kind {kind!r}")
        finally:
            _occ_record(self.name, time.monotonic() - t0)

    def _step_decode(self, msg):
        jnp = self._jnp
        _k, mb, x, lens, greedy = msg
        mb = int(mb)
        if self.first:
            x = jnp.asarray(np.asarray(x, np.int32).reshape(self.mb_size, 1))
        positions = jnp.asarray(
            np.asarray(lens, np.int32).reshape(self.mb_size, 1))
        y, self._caches[mb] = self._dstep(
            self.params, self._caches[mb], x, positions)
        if not self.last:
            return ("d", mb, y, lens, greedy)
        if greedy:
            toks = self._dgreedy(y)
        else:
            toks, self._keys[mb] = self._dsample(
                y, self._keys[mb], self._temps[mb], self._topks[mb],
                self._topps[mb])
        # The ONE device->host sync per invocation: token ids, not logits,
        # cross back to the driver.
        return ("d", mb, np.asarray(toks))

    def _step_prefill(self, msg):
        jax, jnp = self._jax, self._jnp
        _k, row, x, plen, samp = msg
        mb, r = divmod(int(row), self.mb_size)
        if self.first:
            x = jnp.asarray(np.asarray(x, np.int32))  # [1, Lb]
        y, cslice = self._prefill(self.params, x)
        self._caches[mb] = self._place(self._caches[mb], cslice,
                                       jnp.int32(r))
        if not self.last:
            return ("p", row, y, plen, samp)
        key = jax.random.fold_in(
            jax.random.PRNGKey(int(samp["seed"])), int(samp["rid"]))
        first = self._psample(
            y, jnp.int32(plen), key, jnp.float32(samp["temperature"]),
            jnp.int32(samp["top_k"]), jnp.float32(samp["top_p"]))
        self._keys[mb] = self._keys[mb].at[r].set(jax.random.fold_in(key, 1))
        self._temps[mb] = self._temps[mb].at[r].set(
            float(samp["temperature"]))
        self._topks[mb] = self._topks[mb].at[r].set(int(samp["top_k"]))
        self._topps[mb] = self._topps[mb].at[r].set(float(samp["top_p"]))
        return ("p", row, int(first))

    # --------------------------------------------------------------- RPC
    def pid(self) -> int:
        return os.getpid()

    def device(self) -> dict:
        """Where this stage runs, said from inside its own process: the
        replica that schedules a pipeline holds no chip, so /v1/stats
        reports its stages' devices beside its own."""
        from ray_tpu._private import accelerators

        devs = self._jax.local_devices()
        return {"stage": self.name, "pid": os.getpid(),
                "platform": devs[0].platform,
                "device_kind": devs[0].device_kind,
                "chip_files_open": accelerators.open_chip_files(),
                "tpu_visible_chips": os.environ.get("TPU_VISIBLE_CHIPS")}

    def server_addr(self) -> tuple:
        from ray_tpu._private.worker import global_worker

        return tuple(global_worker().server_addr)

    def join_group(self, world_size: int, rank: int, addrs: dict,
                   group_name: str) -> bool:
        """Join the driver-pushed stage group: no KV rendezvous, no
        polling — the address map was negotiated at engine build time,
        exactly like the DAG's channels."""
        from ray_tpu.util import collective

        collective.init_prenegotiated_group(
            world_size, rank,
            {int(k): tuple(v) for k, v in addrs.items()},
            group_name=group_name, connect=True)
        return True

    def edge_stats(self) -> dict:
        """This stage's device-edge resolve counters + busy time (the
        bench's zero-RPC proof reads these)."""
        from ray_tpu._private import device_store

        with _occ_lock:
            ent = _occ.get(self.name, [0.0, 0])
            busy, steps = float(ent[0]), int(ent[1])
        return {"stage": self.name,
                "resolve": device_store.resolve_stats(),
                "busy_s": busy, "steps": steps}

    def reset_stats(self) -> bool:
        from ray_tpu._private import device_store

        device_store.reset_resolve_stats()
        with _occ_lock:
            _occ.pop(self.name, None)
        return True


# ------------------------------------------------------------------ engine
class PipelinedEngine:
    """Pipeline-parallel ContinuousEngine drop-in: same submit()/GenStream
    surface, decode executed as compiled-DAG invocations across N stage
    actors (module docstring has the full design)."""

    def __init__(self, cfg, *, n_stages: int = 2, max_batch: int = 8,
                 microbatch: int = 0, decode_chunk: int = 0, mesh=None,
                 stall_timeout_s: float = 120.0):
        # decode_chunk/mesh are accepted for ContinuousEngine signature
        # compatibility; chunking is replaced by microbatch pipelining and
        # TP meshes live inside stages.
        del decode_chunk, mesh
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.transformer import Transformer

        self.cfg = cfg
        self.n_stages = int(n_stages)
        if self.n_stages < 1:
            raise ValueError(f"n_stages ({n_stages}) must be >= 1")
        mb = int(microbatch) or int(CONFIG.pp_microbatch)
        if mb <= 0:
            # Auto: 2 microbatches per stage keeps every stage busy while
            # its neighbours work (the GPipe occupancy rule of thumb).
            mb = max(1, int(max_batch) // (2 * self.n_stages))
        self.mb_size = mb
        self.n_mb = max(2, -(-int(max_batch) // mb))
        self.max_batch = self.mb_size * self.n_mb
        self._stall_s = float(stall_timeout_s)

        mcfg = model_config(cfg)
        model = Transformer(mcfg)
        if cfg.params is not None:
            params = (cfg.params["params"] if "params" in cfg.params
                      else cfg.params)
        else:
            dummy = jnp.zeros((1, 8), jnp.int32)
            params = model.init(jax.random.PRNGKey(cfg.seed), dummy)["params"]
        self._splits = stage_layer_split(cfg.n_layers, self.n_stages)
        # Shards ship as numpy (cheap pickles); stage actors re-device-put.
        self._shards = [
            jax.tree.map(np.asarray, stage_param_slice(
                params, layers, s == 0, s == self.n_stages - 1))
            for s, layers in enumerate(self._splits)]
        self._stage_cfg = (dataclasses.replace(cfg, params=None)
                          if cfg.params is not None else cfg)
        del params

        # Host scheduler state (mirrors ContinuousEngine's).
        self._lock = threading.Condition()
        self._pending: "queue.Queue" = queue.Queue()
        self._slots: list[Optional[_Slot]] = [None] * self.max_batch
        self._streams: set = set()
        self._req_counter = itertools.count()
        self._n_active = 0
        self._running = True
        self._rebuilds = 0
        self._mb_toks = np.zeros((self.n_mb, self.mb_size), np.int32)
        self._mb_lens = np.zeros((self.n_mb, self.mb_size), np.int32)
        self._mb_active: list[set] = [set() for _ in range(self.n_mb)]
        self._mb_inflight = [False] * self.n_mb
        self._prefilling: dict[int, tuple] = {}  # slot -> (stream, s, plen)
        self._fifo: collections.deque = collections.deque()
        self._dag = None
        self._actors: list = []
        self._group_name: Optional[str] = None
        self._build_graph()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="rt-llm-pp")
        self._thread.start()

    # -------------------------------------------------------------- graph
    def _build_graph(self):
        import ray_tpu
        from ray_tpu import dag as _dag

        stage_opts = self._stage_options()
        stage_cls = ray_tpu.remote(**stage_opts)(PipelineStage)
        actors = []
        for s, layers in enumerate(self._splits):
            actors.append(stage_cls.remote(
                self._stage_cfg, s, self.n_stages, tuple(layers),
                s == 0, s == self.n_stages - 1, self._shards[s],
                self.mb_size, self.n_mb))
        # Pre-negotiated stage collective group: the driver gathers every
        # stage's listen address and pushes the full rank->addr map at
        # build time (compile-time wiring, like the DAG's channels) —
        # device_store's peer-conn tier then reuses the established conns.
        try:
            addrs = {s: tuple(ray_tpu.get(a.server_addr.remote(),
                                          timeout=60))
                     for s, a in enumerate(actors)}
            gname = f"pp-{uuid.uuid4().hex[:8]}"
            ray_tpu.get([a.join_group.remote(len(actors), s, addrs, gname)
                         for s, a in enumerate(actors)], timeout=60)
            self._group_name = gname
        except Exception:
            logger.exception(
                "pipeline stage-group pre-negotiation failed (stages fall "
                "back to on-demand peer conns)")
        with _dag.InputNode() as inp:
            node = actors[0].step.bind(inp)
            for a in actors[1:]:
                node = a.step.bind(node)
        self._dag = _dag.compile(node)
        self._actors = actors

    def _stage_options(self) -> dict:
        """Actor options of one stage. A chip belongs to one process, and a
        worker sees one only by holding the TPU resource (node_agent.py
        `_spawn_worker`): on a cluster with chips each stage asks for its
        own, and the process that builds the pipeline must hold none — it
        only schedules. Too few free chips is an error, never a stage on
        the CPU unannounced. A cluster without any runs them on the CPU."""
        import jax

        import ray_tpu

        total = int(ray_tpu.cluster_resources().get("TPU", 0))
        if not total:
            logger.info("no TPU in the cluster: %d pipeline stages run on "
                        "the CPU", self.n_stages)
            return {"num_cpus": 0}
        if jax.default_backend() == "tpu":
            # __init__ made the parameters with JAX, so this process has a
            # chip open: one its stages can then never open.
            raise RuntimeError(
                f"pipeline of {self.n_stages} stages: the process that "
                f"builds it holds a TPU chip itself. Deploy the pipelined "
                f"replica without num_tpus (each stage asks for its own "
                f"chip), and hold a driver that builds one to the CPU "
                f"(JAX_PLATFORMS=cpu)")
        # After a rebuild the chips of the killed stages come back only when
        # their processes have exited: wait for those, not on a first build.
        deadline = time.monotonic() + (
            CONFIG.worker_register_timeout_s if self._rebuilds else 0.0)
        while True:
            free = int(ray_tpu.available_resources().get("TPU", 0))
            if free >= self.n_stages:
                return {"num_cpus": 0, "num_tpus": 1}
            if time.monotonic() >= deadline:
                raise RuntimeError(
                    f"pipeline of {self.n_stages} stages needs one TPU chip "
                    f"per stage; {free} of the cluster's {total} are free")
            time.sleep(0.2)

    def _teardown_graph(self):
        import ray_tpu

        dag, self._dag = self._dag, None
        if dag is not None:
            try:
                dag.teardown()
            except Exception:
                logger.exception("pipeline DAG teardown failed")
        actors, self._actors = self._actors, []
        for a in actors:
            try:
                ray_tpu.kill(a)
            except Exception:
                pass

    # -------------------------------------------------------------- public
    def submit(self, prompt_tokens,
               sampling: Optional[SamplingParams] = None) -> GenStream:
        """Queue one request; returns its token stream immediately."""
        sampling = sampling or SamplingParams()
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) + sampling.max_tokens > self.cfg.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_tokens "
                f"({sampling.max_tokens}) exceeds max_seq "
                f"({self.cfg.max_seq})")
        stream = GenStream(next(self._req_counter), len(prompt))
        # Atomic vs shutdown's flag flip (see ContinuousEngine.submit).
        with self._lock:
            if not self._running:
                raise RuntimeError("engine is shut down")
            self._streams.add(stream)
            self._pending.put((prompt, sampling, stream))
            self._lock.notify_all()
        return stream

    def generate(self, prompts,
                 sampling: Optional[SamplingParams] = None
                 ) -> list[list[int]]:
        streams = [self.submit(p, sampling) for p in prompts]
        return [s.tokens() for s in streams]

    def shutdown(self):
        with self._lock:
            self._running = False
            self._lock.notify_all()
        self._pending.put(None)
        self._thread.join(timeout=20)
        self._teardown_graph()
        self._drain_all_streams()

    @property
    def num_active(self) -> int:
        return self._n_active

    def stage_devices(self) -> list[dict]:
        """Each stage's `PipelineStage.device()`, in stage order."""
        import ray_tpu

        return ray_tpu.get([a.device.remote() for a in list(self._actors)],
                           timeout=30)

    def pipeline_stats(self) -> dict:
        """Aggregated per-stage counters: device-edge pins, resolve tiers
        (the zero-RPC proof: resolve_rpcs stays 0 in steady state), and
        per-stage busy time."""
        import ray_tpu

        per = []
        for a in list(self._actors):
            try:
                per.append(ray_tpu.get(a.edge_stats.remote(), timeout=30))
            except Exception:
                pass
        agg = {"edge_pins": 0, "store_hits": 0, "tier0": 0,
               "resolve_rpcs": 0, "stages": per}
        for p in per:
            r = p.get("resolve", {})
            agg["edge_pins"] += int(r.get("edge_pins", 0))
            agg["store_hits"] += int(r.get("store_hit", 0))
            agg["tier0"] += int(r.get("tier0", 0))
            agg["resolve_rpcs"] += (int(r.get("export_rpc", 0))
                                    + int(r.get("fetch", 0)))
        return agg

    def reset_pipeline_stats(self) -> None:
        import ray_tpu

        for a in list(self._actors):
            try:
                ray_tpu.get(a.reset_stats.remote(), timeout=30)
            except Exception:
                pass

    # ----------------------------------------------------------- scheduler
    def _bucket(self, plen: int) -> int:
        b = 8
        while b < plen:
            b *= 2
        return min(b, self.cfg.max_seq)

    def _cap(self) -> int:
        # Outstanding invocations stay under the DAG's own inflight bound,
        # so execute() never blocks the scheduler on the semaphore.
        return max(2, min(int(CONFIG.dag_max_inflight), self.n_mb + 2))

    def _loop(self):
        """Scheduler wrapper: an unexpected scheduler death surfaces an
        attributed error on every open stream — never a hang."""
        error: Optional[Exception] = None
        try:
            self._run_scheduler()
        except Exception as e:  # noqa: BLE001 - terminal: loop is dead
            logger.exception("pipelined llm engine scheduler died")
            error = RuntimeError(
                f"pipelined llm engine scheduler died: {e!r}")
        finally:
            with self._lock:
                self._running = False
            self._drain_all_streams(error)
            self._teardown_graph()

    def _run_scheduler(self):
        while self._running:
            self._admit()
            self._issue_decodes()
            if not self._fifo:
                with self._lock:
                    if self._running and self._pending.empty():
                        self._lock.wait(timeout=0.05)
                continue
            # Fulfill strictly in issue order: the DAG is itself FIFO, so
            # the head ref is always the next to complete.
            kind, ref, meta = self._fifo[0]
            try:
                out = self._get_head(ref)
            except Exception as e:
                self._on_graph_failure(e)
                continue
            if out is None:  # shutdown raced the wait
                continue
            self._fifo.popleft()
            self._rebuilds = 0  # a completed invocation resets the budget
            if kind == "p":
                self._on_prefill_done(out, meta)
            else:
                self._on_decode_done(out, meta)

    def _get_head(self, ref):
        """Head-of-line result wait in shutdown-checked slices; a stall
        past the deadline is a graph failure (never-a-hang)."""
        from ray_tpu.exceptions import GetTimeoutError

        deadline = time.monotonic() + self._stall_s
        while True:
            if not self._running:
                return None
            try:
                return ref.get(timeout=0.25)
            except GetTimeoutError:
                if time.monotonic() > deadline:
                    raise

    def _admit(self):
        cap = self._cap()
        while len(self._fifo) < cap:
            free = next(
                (i for i in range(self.max_batch)
                 if self._slots[i] is None and i not in self._prefilling),
                None)
            if free is None:
                break
            try:
                item = self._pending.get_nowait()
            except queue.Empty:
                break
            if item is None:
                continue
            prompt, sampling, stream = item
            if stream.closed:
                stream.finish_reason = "cancelled"
                self._finish_stream(stream)
                continue
            plen = len(prompt)
            lb = self._bucket(plen)
            toks = np.zeros((1, lb), np.int32)
            toks[0, :plen] = prompt
            samp = {"temperature": float(sampling.temperature),
                    "top_k": int(sampling.top_k),
                    "top_p": float(sampling.top_p),
                    "seed": int(sampling.seed),
                    "rid": int(stream.request_id)}
            try:
                ref = self._dag.execute(("p", free, toks, plen, samp),
                                        timeout=30.0)
            except Exception as e:
                # The graph died before this request started: requeue it
                # (it resumes after the rebuild) and run the failure path.
                self._pending.put((prompt, sampling, stream))
                self._on_graph_failure(e)
                return
            self._prefilling[free] = (stream, sampling, plen)
            self._fifo.append(("p", ref, free))

    def _issue_decodes(self):
        cap = self._cap()
        pre_mbs = {s // self.mb_size for s in self._prefilling}
        for mb in range(self.n_mb):
            if len(self._fifo) >= cap:
                break
            # A microbatch with a prefill in flight must not decode: the
            # decode would land at the stages AFTER the prefill and step
            # the fresh row's cache with a stale position.
            if (self._mb_inflight[mb] or not self._mb_active[mb]
                    or mb in pre_mbs):
                continue
            greedy = all(
                self._slots[mb * self.mb_size + r].sampling.temperature
                <= 0.0 for r in self._mb_active[mb])
            msg = ("d", mb, self._mb_toks[mb].copy(),
                   self._mb_lens[mb].copy(), bool(greedy))
            try:
                ref = self._dag.execute(msg, timeout=30.0)
            except Exception as e:
                self._on_graph_failure(e)
                return
            self._mb_inflight[mb] = True
            self._fifo.append(("d", ref, mb))

    def _on_prefill_done(self, out, slot: int):
        stream, sampling, plen = self._prefilling.pop(slot)
        first = int(out[2])
        if stream.closed:
            stream.finish_reason = "cancelled"
            self._finish_stream(stream)
            return
        st = _Slot(slot, stream, sampling)
        self._slots[slot] = st
        self._n_active += 1
        mb, r = divmod(slot, self.mb_size)
        self._mb_toks[mb][r] = first
        self._mb_lens[mb][r] = plen
        self._deliver(slot, [first])
        if self._slots[slot] is not None:
            self._mb_active[mb].add(r)

    def _on_decode_done(self, out, mb: int):
        self._mb_inflight[mb] = False
        toks = np.asarray(out[2]).reshape(-1)
        for r in sorted(self._mb_active[mb]):
            slot = mb * self.mb_size + r
            tok = int(toks[r])
            self._mb_toks[mb][r] = tok
            self._mb_lens[mb][r] += 1
            self._deliver(slot, [tok])

    def _on_graph_failure(self, e: Exception):
        """The failure contract: fail every open stream with the
        ATTRIBUTED error, tear down, rebuild fresh stages, resume from the
        request queue. Consecutive failures beyond RT_PP_REBUILD_MAX kill
        the engine (the wrapper drains with the terminal error)."""
        logger.warning("pipeline graph failure (%s: %s); rebuilding",
                       type(e).__name__, e)
        self._fifo.clear()
        for slot in list(self._prefilling):
            stream, _s, _p = self._prefilling.pop(slot)
            self._finish_stream(stream, e)
        for i, st in enumerate(self._slots):
            if st is not None:
                self._slots[i] = None
                self._n_active -= 1
                self._finish_stream(st.stream, e)
        for mb in range(self.n_mb):
            self._mb_active[mb].clear()
            self._mb_inflight[mb] = False
        self._mb_toks[:] = 0
        self._mb_lens[:] = 0
        self._teardown_graph()
        self._rebuilds += 1
        limit = max(1, int(CONFIG.pp_rebuild_max))
        if self._rebuilds > limit:
            raise RuntimeError(
                f"pipeline graph failed {self._rebuilds} consecutive times "
                f"(RT_PP_REBUILD_MAX={limit}); last: {e!r}") from e
        self._build_graph()

    # ------------------------------------------------------------ delivery
    def _deliver(self, slot: int, toks: list):
        st = self._slots[slot]
        if st is None:
            return
        if st.stream.closed:
            st.stream.finish_reason = "cancelled"
            self._retire(slot)
            return
        out = toks[:max(0, st.remaining)]
        finish = None
        stop = st.sampling.stop_token
        if stop is not None and stop in out:
            out = out[:out.index(stop) + 1]
            finish = "stop"
        st.emitted += len(out)
        st.remaining -= len(out)
        if finish is None and st.remaining <= 0:
            finish = "length"
        if out:
            st.stream._q.put(out)
            _count_tokens(len(out))
        if finish is not None:
            st.stream.finish_reason = finish
            self._retire(slot)

    def _retire(self, slot: int):
        st = self._slots[slot]
        self._finish_stream(st.stream)
        self._slots[slot] = None
        self._n_active -= 1
        mb, r = divmod(slot, self.mb_size)
        self._mb_active[mb].discard(r)
        # The retired row's cache is garbage until the next prefill places
        # over it; in-flight decodes step it harmlessly (driver discards).

    def _finish_stream(self, stream: GenStream,
                       error: Optional[Exception] = None):
        if error is not None:
            stream._q.put(error)
        stream._q.put(GenStream._DONE)
        with self._lock:
            self._streams.discard(stream)

    def _drain_all_streams(self, error: Optional[Exception] = None):
        while True:
            try:
                item = self._pending.get_nowait()
            except queue.Empty:
                break
            if item is None:
                continue
            _p, _s, stream = item
            self._finish_stream(stream, error)
        with self._lock:
            streams = list(self._streams)
            self._streams.clear()
        for stream in streams:
            if error is not None:
                stream._q.put(error)
            stream._q.put(GenStream._DONE)
