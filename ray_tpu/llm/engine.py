"""Continuous-batching LLM engine: the production serving core.

Parity target: the engine seat the reference fills with vLLM
(python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py —
continuous batching, sampling params, streaming token output, TP-sharded
engine workers via vllm_models.py:123-137). TPU-native design:

- **Slot KV cache**: fixed [max_batch, max_seq] per-layer cache buffers;
  each in-flight request owns one slot. Requests join (bucketed-length
  prefill compiled once per bucket, then a compiled scatter places the
  slot) and leave independently — no lockstep. Fixed shapes mean every
  decode step is the same compiled XLA program; a TPU cannot afford
  vLLM's dynamic block tables, slots are the idiomatic equivalent.
- **Kinds of leaf**: a leaf keeps rows to `max_seq`, its window's rows
  (position p in row p mod window: a ring, or under "eva" a block that
  starts over), one row for every CHUNK of positions (`chunks`: an "eva"
  layer's summaries, beside its window leaves: a layer may keep leaves of
  more than one kind), or a STATE, a fixed block a slot that every token
  replaces (a gated delta-rule layer's float32 `S` and its convolution's
  tail: models/kda.py). The cache is the model's flax collection; the
  engine asks each LEAF's kind (`_leaf_kind`) and goes by it wherever the
  kinds differ: what a prefill hands on, what a parked request holds, how
  a leaf is sharded, what the stats count. Under a looped stack
  (`ut_steps` > 1: the layers run several times a token with one set of
  weights) a layer keeps a K and V pair FOR EACH PASS, all of one kind,
  and a step walks them one after the other: a leaf's rows are read once a
  step, a layer's leaves `ut_steps` times.
- **Cache layout**: the cache crosses every program boundary in the
  on-device layout the decode loop computes in. The engine asks the
  compiler for it once, and where rows as wide as their tiles make it the
  default layout (head_dim 96 in 128 lanes on the v5e) it widens the rows
  (`_probe_cache_row`); no chunk program then converts the cache on its
  way in or out.
- **Chunked decode**: between admission points the engine runs
  `decode_chunk` single-token steps under ONE lax.scan dispatch,
  amortizing host->device latency while bounding join latency to a few
  tokens. Single-token attention (ops/decode_attention.py) goes by two
  things only the scheduler knows and hands each chunk: `kv_bound`, the
  rows its longest LIVE slot will have, and `live`, which rows of the
  batch have an occupant (`_run_scheduler`). On a TPU a ragged kernel
  reads each live slot's own rows (K and V, or latent rows) and nothing of
  a free one; elsewhere the step reads a static prefix of the slot cache
  chosen inside the program from `kv_bound`.
- **Steps that carry a block** (`_make_block_chunk`): a model that
  generates by diffusion over blocks of L positions is stepped a FORWARD of
  [B, L] a scan step; several forwards finish a block, only the last one's
  cache rows stay, and a forward gives a slot no token or several. The
  same scheduler, manager and builder: a chunk counts forwards, and the
  host counts forwards and tokens apart (`_Slot.steps_left`).
- **One table of programs** (`llm/programs.py`): every serving program
  (`chunk` by `(n, greedy)`, `prefill` and `place` by bucket, `sample1`,
  the layout probe) is built by one route, `jitted.lower(<abstract
  arguments>).compile()` (`_lower`), and kept in one table; the four call
  sites (`_Kind`) take their program from it and call it, so no `jax.jit`
  dispatch lies on a serving call. One thread traces and lowers, a small
  pool compiles. What a start asked for, in the order it asked, is left as
  a list under the compile cache's directory
  (`<directory>/programs/<hash of all that decides the texts>.json`), and
  a start that finds its list builds AHEAD, in the last start's order,
  while the constructor and the first requests go on; without a cache
  directory (the CPU) there is no list and everything is built when first
  asked for. `/v1/stats` `setup` says which: `programs_ahead`,
  `programs_waited`, `programs_on_demand`, `list_unused`.
- **In-graph sampling** (`llm/sampler.py`): per slot, inside the compiled
  step; a step sorts the vocabulary only where a live row has a nucleus.
- **TP over a mesh**: pass `mesh` (axis "tp") and params/caches shard via
  the model's Megatron PartitionSpecs; XLA inserts the ICI collectives.
- **Zero-sync hot loop** (README "Serving hot loop"): decode chunks stay
  pipelined on device with their inputs chained through device-resident
  mirrors; each chunk's token block starts its device→host copy at
  dispatch (`copy_to_host_async`) and is read back one chunk per
  iteration while every younger chunk keeps executing — the XLA stream
  never drains on a readback. Prefill dispatches on its own lane thread
  and splices into the batch at chunk boundaries, so admissions never
  stall steady-state decode. A batch row changes hands through ONE device
  program, and the scheduler's thread issues no eager program at a
  hand-over or a drain (`_run_scheduler`). Tokens are DELIVERED in
  per-chunk batches (one consumer wakeup per chunk, not per token).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import logging
import queue
import re
import threading
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ray_tpu._private import compile_cache, telemetry, tracing as _tracing
from ray_tpu.llm.programs import ProgramTable
from ray_tpu.llm.sampler import _make_sampler, _sampler_path, token_prob
from ray_tpu.models.published import model_config

logger = logging.getLogger(__name__)

#: Cumulative tokens delivered to GenStream consumers across every engine
#: in this process — the `llm.tokens_per_s` telemetry series' source
#: (telemetry.WorkerSampler reads the per-tick rate via
#: tokens_per_s_snapshot; sys.modules-gated, so jax-free workers never
#: import this module for it).
_tok_lock = threading.Lock()
_tok_count = 0
_tok_rate_state: list = [None, 0]  # [last snapshot monotonic, last count]


def _count_tokens(n: int) -> None:
    global _tok_count
    with _tok_lock:
        _tok_count += n


def tokens_per_s_snapshot() -> float:
    """Decode-throughput rate since the previous snapshot (telemetry tick
    cadence). First call anchors the window and reports 0."""
    with _tok_lock:
        c = _tok_count
    now = time.monotonic()
    t0, c0 = _tok_rate_state
    _tok_rate_state[0], _tok_rate_state[1] = now, c
    if t0 is None or now <= t0:
        return 0.0
    return (c - c0) / (now - t0)


@dataclass
class SamplingParams:
    """reference vllm SamplingParams subset (the fields the serve layer
    forwards; vllm_engine.py maps OpenAI body fields onto these)."""

    temperature: float = 1.0
    top_k: int = 0  # 0 = disabled
    top_p: float = 1.0
    max_tokens: int = 16
    stop_token: Optional[int] = None
    seed: int = 0


class GenStream:
    """Host-side token stream of one request: iterate to receive token ids
    as the engine emits them; ends with StopIteration (or raises the
    engine's error).

    Delivery is BATCHED: the engine enqueues one token-id list per decode
    chunk, so a blocked reader wakes once per chunk. `next_batch()`
    exposes the batches directly — it drains every token currently
    available in one call (the serve SSE path coalesces such a batch into
    a single flush); `__next__`/`next()` keep the one-token-at-a-time
    surface on top of the same queue."""

    _DONE = object()
    #: Set only while `trace` is: the id its `engine.prefill` span will
    #: carry, so that a program built for it can be recorded as that span's
    #: child before it ends, and the builds whose call's result its first
    #: token is (the set-up account, README "Tracing & timeline").
    prefill_span: Optional[str] = None
    _built: Optional[list] = None

    def __init__(self, request_id: int, prompt_len: int):
        self.request_id = request_id
        self.prompt_len = prompt_len
        self._q: "queue.Queue" = queue.Queue()
        self._buf: collections.deque = collections.deque()
        self._exc: Optional[Exception] = None  # deferred: tokens first
        self.finish_reason: Optional[str] = None
        self.closed = False
        # Trace context captured at submit (README "Tracing & timeline"):
        # the engine scheduler thread parents its per-iteration spans —
        # prefill, chunk dispatch, host-sync readback — to the submitting
        # request's trace, making each per-chunk host round trip visible.
        self.trace: Optional[tuple] = None
        # The stage of its life inside the engine the request is in, as
        # (start, attributes): set only while `trace` is (see _stage_begin).
        self._stage: Optional[tuple] = None

    def close(self):
        """Consumer abandoned the request (client disconnect): the engine
        retires the slot at its next emit instead of decoding the full
        max_tokens for nobody (reference: vLLM abort_request)."""
        self.closed = True

    def __iter__(self):
        return self

    def _pop(self, timeout: Optional[float] = None):
        """One token; blocks on the batch queue. Raises StopIteration at
        end of stream, queue.Empty on timeout, or the engine's error."""
        while True:
            if self._buf:
                return self._buf.popleft()
            if self._exc is not None:
                exc, self._exc = self._exc, None
                raise exc
            item = self._q.get(timeout=timeout)
            if item is GenStream._DONE:
                self._q.put(GenStream._DONE)  # idempotent re-next
                raise StopIteration
            if isinstance(item, Exception):
                raise item
            if isinstance(item, list):
                self._buf.extend(item)
            else:
                return item

    def __next__(self):
        return self._pop()

    def next(self, timeout: Optional[float] = None):
        try:
            return self._pop(timeout=timeout)
        except queue.Empty:
            from ray_tpu.exceptions import GetTimeoutError

            # Match ObjectRefGenerator.next: a timeout is a typed runtime
            # error carrying the request identity, not a bare queue.Empty.
            raise GetTimeoutError(
                f"request {self.request_id} yielded no token within "
                f"{timeout}s") from None

    def next_batch(self, timeout: Optional[float] = None) -> list[int]:
        """Every token currently available, blocking only for the first:
        one reader wakeup drains the whole burst (the engine enqueues one
        batch per decode chunk). Raises StopIteration at end of stream and
        GetTimeoutError when nothing arrives in time."""
        out = [self.next(timeout=timeout)]
        while True:
            if self._buf:
                out.append(self._buf.popleft())
                continue
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                return out
            if item is GenStream._DONE:
                self._q.put(GenStream._DONE)  # next call raises Stop
                return out
            if isinstance(item, Exception):
                self._exc = item  # tokens in hand first; raise next call
                return out
            if isinstance(item, list):
                self._buf.extend(item)
            else:
                out.append(item)

    def tokens(self) -> list[int]:
        """Drain the stream to completion."""
        return list(self)


class _Slot:
    """One request's occupancy of batch row `slot`, from its hand-over
    (`_splice`) to the end of its stream. Every chunk dispatched for it
    records IT, not the row's index, so the row can pass to the next
    request while chunks that stepped this one are still in flight."""

    __slots__ = ("slot", "stream", "sampling", "remaining", "emitted",
                 "in_flight", "done", "covered", "state", "forwards")

    def __init__(self, slot: int, stream: GenStream,
                 sampling: SamplingParams, state=None, forwards: int = 0):
        self.slot = slot
        self.stream = stream
        self.sampling = sampling
        self.remaining = sampling.max_tokens  # tokens its stream is owed
        self.emitted = 0
        self.in_flight = 0  # decode steps dispatched for it and not read
        self.done = False   # its stream has ended (`_retire`)
        self.covered = False  # a cover chunk went past its unread last step
        # Generation by blocks (`ContinuousEngine._make_block_chunk`): the
        # row's state as last read (at first as seated), and the most
        # forwards the request still takes from there. A step of such a
        # model is a forward and yields no token or several, so forwards
        # and tokens are counted apart.
        self.state = state
        self.forwards = forwards

    def steps_left(self) -> int:
        """Steps still to dispatch for it: one a token it is owed, or, by
        blocks, the most forwards it can still take (with a confidence
        threshold in play only that bound is known)."""
        return (self.remaining if self.state is None
                else self.forwards) - self.in_flight


# ------------------------------------------------------ engine tracing
# A traced request's life inside the engine is four spans that follow one
# another without a hole (README "Tracing & timeline"): engine.queue,
# engine.prefill, engine.ready_wait, engine.first_token. The stream carries
# the open stage's start; a stage's span is recorded when it ends. Called
# only for streams whose `trace` is set.
def _stage_begin(stream: GenStream, now: float, **attrs) -> None:
    stream._stage = (now, attrs)


def _stage_end(stream: GenStream, name: str, now: float, **attrs) -> None:
    stage, stream._stage = stream._stage, None
    if stage is not None:
        _tracing.record_span_in(stream.trace, name, "engine", stage[0], now,
                                {**stage[1], **attrs})


class _Phases:
    """One pass of the scheduler loop on two clocks, made only while
    tracing is on. Each phase (admit, dispatch, sync, deliver, idle_wait)
    is a `jax.profiler.TraceAnnotation` named `engine.<phase>`: outside a
    profiler session that is a check of one flag, inside one the event
    lands on this thread's line of the trace's host plane, on the device
    planes' clock. The same boundaries, on the wall clock, add up to the
    attributes of the pass's one `engine.iteration` span."""

    __slots__ = ("_profiler", "_ann", "_name", "_t", "t0", "ms", "idle_ms")

    def __init__(self, profiler):
        self._profiler = profiler  # jax.profiler
        self._ann = self._name = None
        self._t = self.t0 = 0.0
        self.ms: dict = {}
        self.idle_ms = 0.0  # waited since the last recorded pass

    def begin(self, name: str, **kw) -> float:
        """Close the open phase and open `name` at one instant; returns it."""
        now = self.end()
        self._ann = self._profiler.TraceAnnotation("engine." + name, **kw)
        self._ann.__enter__()
        self._name, self._t = name, now
        return now

    def end(self, record=None) -> float:
        """Close the open phase. `record(now)` runs before its annotation
        ends, so that on the profiler's clock it is part of the phase."""
        now = time.time()
        ann, self._ann = self._ann, None
        if ann is not None:
            ms = (now - self._t) * 1e3
            if self._name == "idle_wait":
                self.idle_ms += ms
            else:
                self.ms[self._name] = self.ms.get(self._name, 0.0) + ms
        if record is not None:
            record(now)
        if ann is not None:
            ann.__exit__(None, None, None)
        return now

    def start_pass(self) -> None:
        self.ms = {}
        self.t0 = self.begin("admit")

    def end_pass(self, ctx: Optional[tuple], **counts) -> None:
        """One `engine.iteration` span under `ctx` for the pass that ends."""
        def record(now):
            attrs = {k + "_ms": round(self.ms.get(k, 0.0), 3)
                     for k in ("admit", "dispatch", "sync", "deliver")}
            attrs["idle_ms"] = round(self.idle_ms, 3)
            attrs.update(counts)
            self.idle_ms = 0.0
            _tracing.record_span_in(ctx, "engine.iteration", "engine",
                                    self.t0, now, attrs)

        self.end(record)


#: The kinds of cache leaf (`TransformerConfig.cache_kind_of`), in the order
#: the stats list them.
_KINDS = ("full", "window", "chunks", "state")


def _layer_of(path) -> int:
    """The layer of the cache leaf at `path` (`layer_<i>/.../<leaf>`)."""
    return int(path[0].key.rpartition("_")[2])


def _leaf_kind(mcfg, path) -> str:
    """The kind of the cache leaf at `path` of the cache collection:
    `cache_kind_of` of its layer and its name."""
    return mcfg.cache_kind_of(_layer_of(path), path[-1].key)


def _leaves_by_kind(mcfg, cache) -> dict:
    """kind -> [(layer, leaf)] of a cache tree, in `_KINDS`' order."""
    import jax

    found: dict = {kind: [] for kind in _KINDS}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        found[_leaf_kind(mcfg, path)].append((_layer_of(path), leaf))
    return {kind: leaves for kind, leaves in found.items() if leaves}


#: What an "eva" model's decode steps count on the device beside their
#: tokens: `<name>` on `engine.host_sync`, `<name>_total` in `/v1/stats`,
#: `LLM_<NAME>` (`rt_llm_<name>_total`) in `util/metrics.py`.
_EVA_COUNTERS = ("eva_summaries", "eva_restarts")


#: What a model that generates by blocks counts on the device beside its
#: tokens, slot by slot: the forwards live slots ran, those of them that
#: committed a block, the answer tokens given out and the positions that
#: denoising forwards freed: `<name>` on `engine.host_sync`, `<name>_total`
#: in `/v1/stats`. Tokens over forwards is the model's acceptance rate.
_BD_COUNTERS = ("bd_forwards", "bd_commits", "bd_tokens", "bd_freed")


#: `models/moe.py` `zero_counts`' four, by the names they go by from here
#: on: `<name>` on `engine.host_sync`, `<name>_total` in `/v1/stats`,
#: `LLM_<NAME>` (`rt_llm_<name>_total`) in `util/metrics.py`. One less
#: `moe_fetched` over held experts x expert layers x steps is the share of
#: the held experts a step did not read.
_PICK_COUNTERS = ("moe_picks", "moe_zero_picks", "moe_touched", "moe_fetched")


def _moe_counters(mcfg) -> int:
    """Counters a decode step of this model carries to the host behind its
    tokens: the rows of each held expert and `models/moe.py` `zero_counts`'
    four; 0 without expert layers."""
    held = mcfg.held_experts
    return held + len(_PICK_COUNTERS) * bool(held)


def _passes(mcfg) -> int:
    """The passes of a looped stack (`ut_steps` > 1: the layers run several
    times a token); 0 for a stack that runs once."""
    return mcfg.ut_steps if mcfg.ut_steps > 1 else 0


def _counted(stats, so_far):
    """`so_far` plus what one step's expert layers sowed into `stats`: the
    rows of each held expert, then the four of `zero_counts`. `so_far` as
    it is where no layer of the program is an expert layer (the probe's
    one layer of a model whose first is dense)."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(stats)[0]
    if not flat:
        return so_far
    rows, picks = ([leaf for path, leaf in flat if path[-1].key == key]
                   for key in ("expert_rows", "picks"))
    return so_far + jnp.concatenate([sum(rows), sum(picks)])


def _rows_columns(rows, batch: int):
    """Per-expert row counts [held] as whole columns of a [batch, n] token
    block: ceil(held / batch) columns, filled column by column, zeros
    after. `_rows_from_columns` is the inverse, on the host."""
    import jax.numpy as jnp

    cols = -(-rows.shape[0] // batch)
    padded = jnp.pad(rows, (0, cols * batch - rows.shape[0]))
    return padded.reshape(cols, batch).T


def _rows_from_columns(columns: np.ndarray, held: int) -> np.ndarray:
    return columns.T.reshape(-1)[:held]


def _count_metric(name: str, n, tags: Optional[dict] = None) -> None:
    """`ray_tpu.util.metrics.<name>` up by n; never the caller's problem."""
    if n > 0:
        try:
            from ray_tpu.util import metrics as _metrics

            getattr(_metrics, name).inc(n, tags)
        except Exception:
            pass


def _agreed(values: set):
    """The one value of a set whose members all agree, else None."""
    return values.pop() if len(values) == 1 else None


def _start_host_copy(arr) -> None:
    try:
        arr.copy_to_host_async()
    except Exception:
        pass  # backend without async copy: the read pays it


#: Decode chunks kept in flight (`ContinuousEngine._run_scheduler`): the
#: oldest is read back while the younger ones execute, so the device never
#: waits for a read. No caller ever asked for another depth.
PIPELINE_DEPTH = 4

#: Prefill buckets are powers of two, each its own compiled program. Above
#: this many rows a bucket comes at three quarters of one as well: the rows a
#: prompt is padded by are device time that every request decoding beside
#: it waits for (tenths of a second at these lengths: PERF.md section 6,
#: PR 32); below it a step in between is one more program to build for
#: milliseconds.
HALF_STEP_ABOVE = 4096


class _Kind:
    """One kind of serving program (`chunk`, `prefill`, `place`, `sample1`).
    Called with the arguments of the jitted function its programs are
    lowered from, it takes the program of THIS call's key (the kind and the
    few integers `key_of` reads off the arguments) from the engine's table
    and calls that: no `jax.jit` dispatch, and so no trace, lies on a
    serving call. `lower` is the jitted function's own."""

    __slots__ = ("name", "jitted", "_get", "_key_of")

    def __init__(self, name: str, jitted, get, key_of):
        self.name, self.jitted = name, jitted
        self._get, self._key_of = get, key_of

    def __call__(self, *args):
        ints, args = self._key_of(args)
        return self._get((self.name, *ints))(*args)

    def lower(self, *args):
        return self.jitted.lower(*args)


class ContinuousEngine:
    """In-flight-batching engine over the flagship Transformer."""

    #: Positions a block of a model that generates by diffusion over blocks
    #: (`_build_compiled` reads it from the model); 0: a token a step.
    _blocks = 0

    def __init__(self, cfg, *, max_batch: int = 8, decode_chunk: int = 8,
                 mesh=None):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.transformer import Transformer

        self.cfg = cfg
        self.max_batch = max_batch
        self.decode_chunk = decode_chunk
        self.mesh = mesh
        mcfg = model_config(cfg)
        self.model = Transformer(mcfg)
        def serving_dtype(params):
            # Inference needs no f32 master weights: held in bf16, every
            # decode step reads half the bytes (flax would otherwise cast
            # f32->bf16 per call, paying f32 HBM reads each step).
            if mcfg.dtype != jnp.bfloat16:
                return params
            return jax.tree.map(
                lambda x: x.astype(jnp.bfloat16)
                if x.dtype == jnp.float32 else x, params)

        with telemetry.setup_stage("engine.params"):
            if cfg.params is not None:
                params = serving_dtype(
                    cfg.params["params"] if "params" in cfg.params
                    else cfg.params)
            else:
                # One program makes each leaf and casts it: the float32 tree
                # is never held whole beside its bf16 copy (6 bytes a
                # parameter), only a leaf at a time. The values are those of
                # an eager init followed by the cast.
                self._make_params = jax.jit(lambda key: serving_dtype(
                    self.model.init(
                        key, jnp.zeros((1, 8), jnp.int32))["params"]))
                params = self._make_params(jax.random.PRNGKey(cfg.seed))
            if mesh is not None:
                params = self._shard_params(params, mesh)
        self.params = params
        self._sampler = _make_sampler(cfg.vocab_size)
        self._jax = jax
        self._jnp = jnp
        with telemetry.setup_stage("engine.programs"):
            self._build_compiled()

        # Host scheduler state.
        self._lock = threading.Condition()
        self._pending: "queue.Queue" = queue.Queue()
        self._slots: list[Optional[_Slot]] = [None] * max_batch
        self._lengths = np.zeros(max_batch, np.int32)  # next write position
        # Sampling params live ON DEVICE (set by the hand-over program):
        # steady-state chunk dispatch must transfer nothing host->device.
        self._temps_dev = jnp.zeros(max_batch, jnp.float32)
        self._topks_dev = jnp.zeros(max_batch, jnp.int32)
        self._topps_dev = jnp.ones(max_batch, jnp.float32)
        self._keys = jax.vmap(jax.random.PRNGKey)(
            jnp.arange(max_batch, dtype=jnp.uint32))
        self._cache = None  # created lazily at first admit
        self._req_counter = itertools.count()
        self._n_active = 0
        # Pipelining state: FIFO of dispatched-but-unread chunks, each with
        # the occupants it stepped; first tokens dispatched by the prefill
        # lane and not read; and device-resident next-token/length mirrors
        # so steady-state chunk dispatch needs NO host->device transfer.
        # [(tokens_device, occupants, n, cover, seq)]
        self._q_chunks: list = []
        self._pending_firsts: list = []  # [(occupant, first_token_device)]
        # The device's account (RT_TRACING=1 only; README "Tracing &
        # timeline"): the chunks whose dispatch has begun and ended, the
        # buckets of the prefills the lane has enqueued since the last
        # chunk, whether the lane is inside a prefill program's call just
        # now, and `splices` as it stood at that chunk. With tracing off
        # none of it is touched.
        self._chunks_begun = 0
        self._chunks_done = 0
        self._prefills_since: collections.deque = collections.deque()
        self._prefill_in_call = False
        self._splices_at_chunk = 0
        self._built_at: dict = {}  # chunk ordinal -> programs built for it
        # (by blocks the "next token" is a row's whole state: its open block,
        # the answer tokens out, those wanted, the denoising forwards done)
        self._toks_dev = jnp.zeros(
            (max_batch, self._blocks + 3) if self._blocks else max_batch,
            jnp.int32)
        self._lens_dev = jnp.zeros(max_batch, jnp.int32)
        # Every GenStream not yet _DONE, independent of slot state: the
        # scheduler-death safety net terminates these with an attributed
        # error even when the slot table itself is the casualty.
        self._streams: set = set()
        self._running = True
        # Prefill lane (README "Serving hot loop"): admissions dispatch on
        # their own thread and splice at chunk boundaries via _ready, so a
        # prefill compile/dispatch never blocks the decode loop.
        self._ready: collections.deque = collections.deque()
        self._prefill_inflight = 0
        self._parked_bytes = 0  # of the cache slices in `_ready` or on their way
        self._threads = [
            threading.Thread(target=self._prefill_loop, daemon=True,
                             name="rt-llm-prefill"),
            threading.Thread(target=self._loop, daemon=True,
                             name="rt-llm-engine")]
        for t in self._threads:
            t.start()

    # ------------------------------------------------------------ sharding
    def _shard_params(self, params, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu.models.transformer import param_specs

        specs = param_specs({"params": params})["params"]

        def _filter(spec):
            # Drop mesh axes the caller's mesh doesn't have (e.g. a
            # tp-only serving mesh has no fsdp/ep axis).
            parts = []
            for p in spec:
                if p is None:
                    parts.append(None)
                elif isinstance(p, tuple):
                    kept = tuple(a for a in p if a in mesh.axis_names)
                    parts.append(kept if kept else None)
                else:
                    parts.append(p if p in mesh.axis_names else None)
            return P(*parts)

        return jax.tree.map(
            lambda leaf, spec: jax.device_put(
                leaf, NamedSharding(mesh, _filter(spec))),
            params, specs)

    # ------------------------------------------------------------ compiled
    def _build_compiled(self):
        import jax
        import jax.numpy as jnp
        import jaxlib

        from ray_tpu.models.transformer import Transformer

        compile_cache.program_identity()  # whoever made this process
        sampler = self._sampler
        # Every program from here on is built by ONE route and kept in ONE
        # table (`llm/programs.py`): `_lower(key)` on the table's lowering
        # thread, the compile on its pool. The list of what the last start
        # like this one asked for is named by all that decides the texts.
        device = jax.devices()[0]
        self._programs = ProgramTable(
            self._lower, compile_cache.lists_dir(), repr((
                self.model.cfg, self.max_batch, self.decode_chunk,
                self.cfg.max_seq,
                self.mesh and (dict(self.mesh.shape),
                               self.mesh.devices.flat[0].device_kind),
                jax.__version__, jaxlib.__version__, device.platform,
                device.device_kind, device.client.platform_version)))
        # Expert layers: the columns behind a chunk's tokens, and since start
        # the rows routed to the held experts and `_PICK_COUNTERS`' four.
        self._moe_held = self.model.cfg.held_experts
        self._moe_cols = -(-_moe_counters(self.model.cfg) // self.max_batch)
        self.moe_rows_total = 0
        # "eva" layers: the columns behind those, `_EVA_COUNTERS`' two.
        self._eva = "eva" in self.model.cfg.mixers
        self._eva_cols = -(-len(_EVA_COUNTERS) // self.max_batch) * self._eva
        # Generation by blocks: positions a block (0: a token a step), the
        # columns of `_BD_COUNTERS`' four and, last, of a row's state.
        self._blocks = self.model.cfg.block_length
        self._bd_cols = -(-len(_BD_COUNTERS) // self.max_batch) * bool(
            self._blocks)
        for name in _PICK_COUNTERS + _EVA_COUNTERS + _BD_COUNTERS:
            setattr(self, f"{name}_total", 0)
        # A looped stack: the passes a token runs (0: the stack runs once,
        # no loop), the columns behind those (a chunk's exit mass by pass,
        # float32 carried as its bits), and since start the passes run and
        # the exit mass by pass.
        self._passes = _passes(self.model.cfg)
        self._loop_cols = -(-self._passes // self.max_batch)
        self.loop_passes_total = 0
        self.exit_mass_total = [0.0] * self._passes
        # The decode steps dispatched since start, those whose attention
        # over K and V, latent rows or an "eva" layer's two leaves is a
        # ragged kernel (`_decode_blocks`),
        # the cache rows they walked a slot, and the rows a live slot had
        # on average (`cache_stats`: kv_walk_share, kv_live_share).
        self.decode_steps = 0
        self.decode_steps_kernel = 0
        self._kv_walked = dict.fromkeys(_KINDS[:3], 0)
        self._kv_live = dict.fromkeys(_KINDS[:3], 0.0)
        # Hand-overs of a batch row since start, those dispatched behind at
        # least one decode chunk still in flight, and the scheduler's
        # passes that began with occupants seated and no chunk in flight
        # (the pipeline drained to a retirement), and the cover chunks: one
        # step for the occupants who go on, dispatched past a known last
        # step that a parked request waits behind (`cache_stats`).
        self.splices = 0
        self.splices_in_flight = 0
        self.pipeline_dry = 0
        self.cover_chunks = 0
        # Rows of the prefill buckets dispatched since start, and those of
        # buckets whose program's attention is a Pallas kernel
        # (`_prefill_form`).
        self.prefill_rows = 0
        self.prefill_rows_kernel = 0
        # Decode steps dispatched in the sampled program since start, and
        # those in which no occupant's nucleus made the sampler sort
        # (`_sampler_path`).
        self.sampler_steps = 0
        self.sampler_steps_select = 0
        self._prefill_form_of: dict = {}

        def make_chunk(model):
            if model.cfg.block_length:  # a step is a forward of a block
                return self._make_block_chunk(model)
            held = _moe_counters(model.cfg)  # counters a step carries on
            eva = (model.cfg.eva_window, model.cfg.eva_chunk) \
                if "eva" in model.cfg.mixers else None
            passes = _passes(model.cfg)
            mutable = ["cache"] + ["stats"] * bool(held) + ["loop"] * bool(
                passes)

            def chunk(params, cache, toks, lengths, keys, temp, top_k, top_p,
                      n: int, greedy: bool, kv_bound=None, live=None):
                """n in-flight decode steps under one scan. toks/lengths
                [B]; returns (cache, keys, tokens [B, n], lengths [B]).
                `kv_bound` (int32 scalar, traced: one program whatever its
                value) is the most cache rows any LIVE slot has after
                these n steps; attention stops at the shortest static
                prefix that holds them (ops/decode_attention.py
                `over_kv_prefix`), the same in every step of the chunk,
                or, where the ragged kernel serves (`_decode_blocks`), at
                each slot's own length. Without it every step walks all
                max_seq rows. `live` [B] bool marks the rows with an
                occupant: a row whose occupant has left keeps its sampling
                mirrors and its growing length, and the sampler takes no
                order on a stale row's account, nor does the kernel read a
                row of its cache (without it every row counts).
                greedy=True compiles an argmax-only variant: the
                sampler is pure waste when no active slot samples. A model
                with expert layers appends to the token block the columns
                of `_rows_columns`: the rows its held experts were routed
                in these n steps (and `_moe_counters`' others) ride to the
                host in the read that brings the tokens; a model with
                "eva" layers, behind those, `_EVA_COUNTERS`' two: the live
                slots' steps that ended a chunk (each eva layer wrote a
                summary) and those that began a window after the first; a
                looped stack, behind those, the sum over the live slots'
                steps of each pass's exit probability (`exit_p`: float32,
                carried as its bits)."""
                def step(carry, _):
                    cache, tok, lens, keys, *rows = carry
                    # (the mesh in context, as the prefill's: what
                    # `_decode_blocks` asks the rule, the trace asks too)
                    with self._mesh_scope():
                        logits, vars_out = model.apply(
                            {"params": params, "cache": cache}, tok[:, None],
                            positions=lens[:, None], decode=True,
                            kv_bound=kv_bound, live=live, mutable=mutable)
                    if held:
                        rows[0] = _counted(vars_out.get("stats", {}),
                                           rows[0])
                    if passes:
                        left = vars_out["loop"]["exit_p"][:, 0]  # [B, passes]
                        if live is not None:
                            left = jnp.where(live[:, None], left, 0.0)
                        rows[-1] = rows[-1] + left.sum(0)
                    if greedy:
                        nxt = jnp.argmax(
                            logits[:, -1], axis=-1).astype(jnp.int32)
                    else:
                        split = jax.vmap(jax.random.split)(keys)  # [B, 2, 2]
                        keys = split[:, 0]
                        nxt = sampler(logits[:, -1].astype(jnp.float32),
                                      split[:, 1], temp, top_k, top_p, live)
                    return (vars_out["cache"], nxt, lens + 1, keys, *rows), nxt

                rows0 = [jnp.zeros((held,), jnp.int32)] if held else []
                if passes:
                    rows0.append(jnp.zeros((passes,), jnp.float32))
                (cache, _tok, lens, keys, *rows), out = jax.lax.scan(
                    step, (cache, toks, lengths, keys, *rows0), None,
                    length=n)
                block = jnp.moveaxis(out, 0, 1)
                if passes:
                    rows[-1] = jax.lax.bitcast_convert_type(rows[-1],
                                                            jnp.int32)
                if eva:
                    # the positions the live slots stepped, [B, n]
                    window, piece = eva
                    at = lengths[:, None] + jnp.arange(n)[None, :]
                    if live is not None:
                        at = jnp.where(live[:, None], at, 0)  # counts nowhere
                    rows.append(jnp.stack([
                        jnp.sum(at % piece == piece - 1),
                        jnp.sum((at % window == 0) & (at > 0))]
                    ).astype(jnp.int32))
                if rows:
                    block = jnp.concatenate(
                        [block, *(_rows_columns(r, block.shape[0])
                                  for r in rows)], axis=1)
                return cache, keys, block, lens

            return chunk

        # Before any program is traced through the model: the width of a
        # cache row is the compiler's to choose (see "cache layout" below).
        row = self._probe_cache_row(make_chunk)
        if row:
            self.model = Transformer(
                dataclasses.replace(self.model.cfg, cache_row=row))
        model = self.model
        self._cache_spec = self._cache_shapes(model, self.params)
        mcfg = model.cfg
        # Four kinds of leaf in one manager, a kind a LEAF (`cache_kind_of`;
        # a layer keeps one leaf or several: K and V, a state's parts, the two
        # latents under `moe_shortcut`, and under "eva" leaves of two kinds):
        # a full leaf's `max_seq` rows a slot, a window leaf's ring or block,
        # and a chunks leaf's one row for several positions, all `[slots,
        # rows, ...]`; and a state, a fixed block a slot that every token
        # replaces. Whatever goes by a leaf's kind asks `_leaf_kind`, never
        # its rank or its layer alone.
        self._window = max((mcfg.window_of(i) for i in range(mcfg.n_layers)),
                           default=0)
        self._cache_kinds = {}
        for kind, found in _leaves_by_kind(mcfg, self._cache_spec).items():
            leaves = [leaf for _i, leaf in found]
            nbytes = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)
            self._cache_kinds[kind] = {
                "layers": len({i for i, _leaf in found}),
                "leaves": len(leaves),
                # (a looped stack: a layer's K and V pair, once a pass)
                **({"passes": self._passes} if self._passes else {}),
                # (visibility by blocks of so many rows: a block's forwards
                # rewrite its rows until the commit's write stays)
                **({"block_length": self._blocks} if self._blocks else {}),
                **({"bytes_per_slot": nbytes // self.max_batch}
                   if kind == "state" else {"rows": leaves[0].shape[1]}),
                "bytes": nbytes}
        # What one decode step reads and writes of state, all slots.
        self._state_rw_bytes = 2 * self._cache_kinds.get(
            "state", {"bytes": 0})["bytes"]
        # Where the ragged kernel serves the decode step's attention, its
        # row block by kind of leaf ({} where the XLA walk does), and what
        # a chunk's span calls that: `kernel`, `xla`, or `mixed` where the
        # dispatcher takes one kind of leaf and refuses the other.
        # `_kernel_pieces`: the rows a live slot's fetch is rounded up to,
        # by kind: the block, or under the `mha` family's kernel the piece
        # of its last block.
        (self._kernel_blocks, self._kernel_pieces,
         self._decode_form) = self._decode_blocks()
        # A request parked in `_ready` holds its prefill's cache slices on
        # the device. The lane runs ahead of the scheduler only while what
        # is parked stays under a quarter of the cache's own bytes.
        self._park_budget = sum(
            k["bytes"] for k in self._cache_kinds.values()) // 4
        self._slice_bytes_of: dict = {}

        def prefill(params, toks, plen):
            """toks [1, Lb] -> (last-position logits [V], each cache leaf's
            first min(Lb, its rows) rows, a state leaf whole); by blocks the
            slices alone. A full leaf's
            rows beyond the bucket were not written; a ring shorter than
            the bucket comes whole, holding the last positions before `plen`
            at their ring places (`Attention._cached_attention`); a state is
            the one after position `plen - 1`, not after the bucket's last
            row (`models/kda.py`). A request parked in _ready holds what
            this returns, not max_seq rows a leaf."""
            lb = toks.shape[1]
            positions = jnp.arange(lb)[None]
            with self._mesh_scope():
                logits, vars_out = model.apply(
                    {"params": params}, toks, positions=positions,
                    decode=True, prompt_len=jnp.reshape(plen, (1,)),
                    mutable=["cache"])
            def slices():
                return self._by_kind(
                    lambda kind, c: c if kind == "state"
                    else c[:, :self._slice_rows(kind, lb, c.shape[1])],
                    vars_out["cache"])

            if self._blocks:
                # No logit of such a prefill is read: `plen` is the prompt's
                # whole blocks, its rows are committed, and the prompt's
                # remainder opens the first block (`_prefill_dispatch`).
                return slices()
            # (the logits first: a program's text is its operations' order)
            last = jax.lax.dynamic_index_in_dim(
                logits[0].astype(jnp.float32), plen - 1, 0, keepdims=False)
            return last, slices()

        def place(cache, slice_cache, mirrors, first, key, ints, floats):
            """The hand-over of batch row `slot` to a prefilled request, as
            ONE program whatever its values (one per prefill bucket: the
            slice's shape). `ints` is [slot, prompt length, top_k], `floats`
            [temperature, top_p], `mirrors` the per-row (next token,
            length, key, temperature, top_k, top_p) the chunk programs
            chain through. Every slice lands at the row's origin. The [1,
            Lb, ...] slice of a rows leaf goes into the row's first rows (of
            a ring: all of them, once the bucket is as long); its later rows
            keep what an earlier request left there: a row is written by the
            step that first makes it visible (Attention._cached_attention).
            A state leaf's slice is the slot's whole block: nothing of the
            last occupant's state, or of what chunks in flight made of it
            since, is left. By blocks `first` is the row's whole first
            state ([L + 3]: its open block and the counts behind it) and
            the length the rows the prefill committed."""
            slot = ints[0]
            cache = jax.tree.map(
                lambda big, small: jax.lax.dynamic_update_slice(
                    big, small.astype(big.dtype),
                    (slot,) + (0,) * (small.ndim - 1)),
                cache, slice_cache)
            values = (first, ints[1], key, floats[0], ints[2], floats[1])
            return cache, tuple(
                m.at[slot].set(v) for m, v in zip(mirrors, values))

        def sample1(logits, key, temp, top_k, top_p):
            return sampler(logits[None], key[None], temp[None], top_k[None],
                           top_p[None])[0]

        # Under a mesh every program says where its results lie (the cache
        # as `_cache_shapes` shards it, a slice as its leaf, the rest whole
        # on every device): a loaded program takes its arguments where it
        # was compiled to find them, so what one program hands the next has
        # to be settled, not the compiler's choice. Without a mesh nothing
        # is said, and the texts are what they were.
        where = dict.fromkeys(("prefill", "place", "sample1", "chunk"), {})
        if self.mesh is not None:
            rep = self._replicated()
            cache, slices = (
                jax.tree.map(lambda leaf: leaf.sharding, tree)
                for tree in (self._cache_spec, self._slice_shapes(8)))
            where = {kind: {"out_shardings": trees} for kind, trees in (
                ("prefill", slices if self._blocks else (rep, slices)),
                ("place", (cache, (rep,) * 6)), ("sample1", rep),
                ("chunk", (cache, rep, rep, rep)))}
        get = self._programs.get
        self._prefill = _Kind(
            "prefill", jax.jit(prefill, **where["prefill"]), get,
            lambda a: ((int(a[1].shape[1]),), a))
        self._place = _Kind(
            "place", jax.jit(place, donate_argnums=(0,), **where["place"]),
            # (the bucket of the prompt whose length the hand-over sets)
            get, lambda a: ((self._bucket(int(a[5][1])),), a))
        self._sample1 = _Kind(
            "sample1", jax.jit(sample1, **where["sample1"]), get,
            lambda a: ((), a))
        self._chunk = _Kind(
            "chunk", jax.jit(make_chunk(model), static_argnums=(8, 9),
                             donate_argnums=(1,), **where["chunk"]),
            # (the statics are the key; the program takes the rest)
            get, lambda a: ((int(a[8]), bool(a[9])), a[:8] + a[10:]))
        # (what `_lower` lowers from, whatever stands in for a call site)
        self._kinds = {kind.name: kind for kind in (
            self._prefill, self._place, self._sample1, self._chunk)}
        # From here the table has its shapes: what the last start like this
        # one asked for is built AHEAD of the calls, in that start's order.
        self._programs.build_ahead()
        self._count_boundary_copies()

    def _replicated(self):
        """Under a mesh, whole on every device; None without one."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return None if self.mesh is None else NamedSharding(self.mesh, P())

    def _arg(self, dtype, *dims, **kw):
        """A small argument of a serving program, as a shape: whole on
        every device under a mesh."""
        return self._jax.ShapeDtypeStruct(
            dims, dtype, sharding=self._replicated(), **kw)

    def _slice_shapes(self, bucket: int):
        """What a prefill of this bucket hands on, as shapes: one slot's
        first rows of every rows leaf (`_slice_rows`), a state leaf's whole
        block of one slot; each sharded as its leaf."""
        import jax

        return self._by_kind(
            lambda kind, leaf: jax.ShapeDtypeStruct(
                (1, *leaf.shape[1:]) if kind == "state" else
                (1, self._slice_rows(kind, bucket, leaf.shape[1]),
                 *leaf.shape[2:]), leaf.dtype, sharding=leaf.sharding),
            self._cache_spec)

    def _lower(self, key: tuple):
        """The lowering of the program of `key` from ABSTRACT arguments:
        exactly what the program's call site passes (the dtypes, the weak
        type of the prompt's length, a Python integer there, `kv_bound`'s
        `int32[]`, `live`'s `bool[B]`, by blocks `[B, L + 3]`, under a mesh
        the shardings). Called on the table's lowering thread only."""
        import jax.numpy as jnp

        kind, *ints = key
        if kind == "probe":  # (`_probe_cache_row`'s, while it asks)
            jitted, args = self._probe
            return jitted.lower(*args)
        if kind not in self._kinds:
            raise KeyError(f"no serving program is called {key!r}")
        lower = self._kinds[kind].jitted.lower
        arg, b = self._arg, self.max_batch
        first = (self._blocks + 3,) if self._blocks else ()
        i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
        if kind == "chunk":
            n, greedy = ints
            return lower(*self._chunk_shapes(
                self.params, self._cache_spec, greedy, n))
        if kind == "prefill":
            return lower(
                self.params, arg(i32, 1, *ints), arg(i32, weak_type=True))
        if kind == "place":
            mirrors = (arg(i32, b, *first), arg(i32, b), arg(u32, b, 2),
                       arg(f32, b), arg(i32, b), arg(f32, b))
            return lower(
                self._cache_spec, self._slice_shapes(*ints), mirrors,
                arg(i32, *first), arg(u32, 2), arg(i32, 3), arg(f32, 2))
        assert kind == "sample1" and not ints, key
        return lower(arg(f32, self.cfg.vocab_size), arg(u32, 2), arg(f32),
                     arg(i32), arg(f32))

    def _make_block_chunk(self, model):
        """`make_chunk`'s program for a model that generates by diffusion
        over blocks of L positions (`TransformerConfig.block_length`,
        `Denoising`): a chunk of n FORWARDS under one scan, the same
        arguments and results as every chunk program, with a row's `toks`
        its whole state [B, L + 3]: the open block's content (a token or
        the mask a position), the answer tokens given out so far (negative
        while the open block still holds the prompt's last tokens), those
        wanted, and the denoising forwards the open block has had.
        `lengths` are the committed rows.

        A scan step is ONE forward of [B, L] at each slot's own depth (the
        block's keys and values go to rows `lengths` on, every forward
        rewrites them: `BlockAttention`), then, by each slot's own state:

        - a block that holds a mask was DENOISED: every masked position
          draws a token by the slot's sampling (`llm/sampler.py`, the
          token's probability beside it), and the positions freed are the
          schedule's count of highest confidence, or under
          `low_confidence_dynamic` every one above the threshold where
          those are at least as many;
        - a block without a mask was COMMITTED: the forward's write of its
          rows stays, `lengths` moves on by L, its tokens that lie past the
          prompt and before the answer's end are given out, and the next
          block is all mask.

        Slots in different phases share a forward. A slot whose answer is
        out is frozen (and reads no cache row), so the host may dispatch
        past an end it only knows a bound of. The block that rides to the
        host is [B, n * L] tokens, forward by forward, -1 where none was
        given out; behind them the expert layers' columns, `_BD_COUNTERS`'
        four, and the rows' states after the chunk. No loop inside a step."""
        import jax
        import jax.numpy as jnp

        mcfg, sampler = model.cfg, self._sampler
        size, how = mcfg.block_length, mcfg.denoising
        held = _moe_counters(mcfg)
        mutable = ["cache"] + ["stats"] * bool(held)

        def chunk(params, cache, state, lengths, keys, temp, top_k, top_p,
                  n: int, greedy: bool, kv_bound=None, live=None):
            b = state.shape[0]
            at = jnp.arange(size, dtype=jnp.int32)
            schedule = jnp.asarray(how.counts(size), jnp.int32)

            def step(carry, _):
                cache, state, lens, keys, tally, *rows = carry
                blk, given, want, had = (state[:, :size], state[:, size],
                                         state[:, size + 1],
                                         state[:, size + 2])
                on = given < want
                if live is not None:
                    on = on & live
                with self._mesh_scope():
                    logits, vars_out = model.apply(
                        {"params": params, "cache": cache}, blk,
                        positions=lens[:, None] + at[None], decode=True,
                        kv_bound=kv_bound, live=on, mutable=mutable)
                if held:
                    rows[0] = _counted(vars_out.get("stats", {}), rows[0])
                # (the mask is never drawn: a position that drew it would
                # stay masked, and a greedy slot's last one for ever)
                flat = logits.reshape(b * size, -1).astype(
                    jnp.float32).at[:, how.mask_token].set(-jnp.inf)
                if greedy:
                    x0 = jnp.argmax(flat, axis=-1).astype(jnp.int32)
                    conf = token_prob(flat, x0)
                else:
                    split = jax.vmap(
                        lambda key: jax.random.split(key, size + 1))(keys)
                    keys = split[:, 0]
                    x0, conf = sampler(
                        flat, split[:, 1:].reshape(b * size, 2),
                        *(jnp.repeat(a, size)
                          for a in (temp, top_k, top_p, on)), with_prob=True)
                x0, conf = x0.reshape(b, size), conf.reshape(b, size)
                masked = blk == how.mask_token
                conf = jnp.where(masked, conf, -jnp.inf)
                # positions ahead of position i by confidence, the earlier
                # first among equals: [B, i, j]
                ahead = (conf[:, None, :] > conf[:, :, None]) | (
                    (conf[:, None, :] == conf[:, :, None])
                    & (at[None, None, :] < at[None, :, None]))
                count = schedule[jnp.minimum(had, how.steps - 1)]
                free = masked & (ahead.sum(-1) < count[:, None])
                if how.strategy == "low_confidence_dynamic":
                    sure = masked & (conf > how.threshold)
                    free = jnp.where((sure.sum(-1) >= count)[:, None], sure,
                                     free)
                commit = on & ~masked.any(-1)
                free = free & on[:, None]
                place = given[:, None] + at[None]  # of the answer, from 0
                out = jnp.where(commit[:, None] & (place >= 0)
                                & (place < want[:, None]), blk, -1)
                moved = commit.astype(jnp.int32) * size
                state = jnp.concatenate([
                    jnp.where(commit[:, None], how.mask_token,
                              jnp.where(free, x0, blk)),
                    (given + moved)[:, None], want[:, None],
                    jnp.where(commit, 0, had + (on & ~commit))[:, None]],
                    axis=1)
                tally = tally + jnp.stack([
                    on.sum(), commit.sum(), (out >= 0).sum(),
                    free.sum()]).astype(jnp.int32)
                return (vars_out["cache"], state, lens + moved, keys, tally,
                        *rows), out

            rows0 = [jnp.zeros((held,), jnp.int32)] if held else []
            (cache, state, lens, keys, tally, *rows), out = jax.lax.scan(
                step, (cache, state, lengths, keys,
                       jnp.zeros((len(_BD_COUNTERS),), jnp.int32), *rows0),
                None, length=n)
            block = jnp.concatenate(
                [jnp.moveaxis(out, 0, 1).reshape(b, n * size),
                 *(_rows_columns(r, b) for r in (*rows, tally)), state],
                axis=1)
            return cache, keys, block, lens

        return chunk

    def _forwards_left(self, state) -> int:
        """The most forwards the request whose row shows `state` (`[L + 3]`,
        as `_make_block_chunk` lays it out) still takes: what its open block
        can take from where it is, and a whole block's for each block its
        answer still needs behind that one. Exact where no confidence
        passes the threshold."""
        mcfg = self.model.cfg
        size, how = mcfg.block_length, mcfg.denoising
        given, want, had = (int(v) for v in state[size:])
        if given >= want:
            return 0
        later = -(-max(0, want - given - size) // size)
        return (how.forwards(size, int((state[:size] == how.mask_token).sum()),
                             had) + later * how.forwards(size, size))

    # ------------------------------------------------------- cache layout
    # The KV cache crosses every program boundary in the on-device layout
    # the decode loop computes in (README "Serving hot loop"). A program's
    # parameters and results are in their shapes' DEFAULT layouts; where
    # that is not the loop's, every chunk program converts every cache leaf
    # on its way in and back on its way out, whatever its length. So the
    # engine asks the compiler which layout it wants, and gives the cache
    # rows the width at which that layout is the default one.
    def _cache_shapes(self, model, params):
        """`model`'s per-layer cache structure at [max_batch, ...], each
        leaf with the sharding `_init_cache` places it in."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        b = self.max_batch
        shapes = jax.eval_shape(
            lambda p, t, pos: model.apply(
                {"params": p}, t, positions=pos, decode=True,
                mutable=["cache"])[1]["cache"],
            params, jnp.zeros((b, 1), jnp.int32),
            jnp.zeros((b, 1), jnp.int32))
        if self.mesh is None:
            return shapes

        def sharded(kind, leaf):
            # K and V [slots, rows, heads, dim], and an "eva" layer's
            # summaries alike: the head axis over tp, as the attention's
            # heads are. A latent leaf [slots, rows, row] belongs to every
            # head: each tp shard keeps all of it.
            if kind == "state":
                raise NotImplementedError(
                    "a state leaf under a `tp` mesh: its heads would be "
                    "sharded as the layer's are, and no cell or test serves "
                    "that; a model with state layers takes no mesh")
            spec = P(None, None, "tp", None) if leaf.ndim == 4 else P()
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype,
                sharding=NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: sharded(_leaf_kind(model.cfg, path), leaf),
            shapes)

    def _by_kind(self, fn, cache, *rest):
        """`fn(kind, leaf, ...)` over a cache tree, the kind the leaf's own
        (`_leaf_kind`); one map over the whole tree, in its own order."""
        import jax

        return jax.tree_util.tree_map_with_path(
            lambda path, *leaves: fn(_leaf_kind(self.model.cfg, path),
                                     *leaves),
            cache, *rest)

    def _slice_rows(self, kind: str, bucket: int, rows: int) -> int:
        """Rows of a rows leaf of `rows` rows that a prefill of `bucket`
        positions hands on: one a position up to the leaf's own, one a whole
        chunk of a `chunks` leaf (and never none: a slice has a row)."""
        if kind == "chunks":
            bucket = max(1, bucket // self.model.cfg.eva_chunk)
        return min(bucket, rows)

    def _chunk_shapes(self, params, cache, greedy: bool,
                      n: Optional[int] = None) -> tuple:
        """Arguments to lower a chunk program of n steps from (of
        `decode_chunk` steps, the longest, where no n is given)."""
        import jax.numpy as jnp

        b, arg = self.max_batch, self._arg
        return (params, cache,
                arg(jnp.int32, b, *((self._blocks + 3,) * bool(self._blocks))),
                arg(jnp.int32, b), arg(jnp.uint32, b, 2),
                arg(jnp.float32, b), arg(jnp.int32, b), arg(jnp.float32, b),
                n or self.decode_chunk, greedy, arg(jnp.int32),
                arg(jnp.bool_, b))

    def _probe_cache_row(self, make_chunk) -> int:
        """The row width the compiler wants for the cache, 0 for the one it
        has. A chunk program is lowered once with the layout of its donated
        cache input and of its cache output left to the compiler
        (`Layout.AUTO`). If the answer is row-major in tiles that a row
        does not fill (head_dim 96 in 128 lanes on the v5e), a row as wide
        as its tiles has that layout by default; any other answer, the
        default layout among them (head_dim 128; the CPU), leaves the
        cache as it is. The program asked about is the greedy one of one
        layer alone, the model's first that keeps rows (a state leaf has no
        row to widen: its layout is put to the compiler by
        `_count_boundary_copies`): every layer uses its rows alike and the
        sampler never sees them, so the question is the same at a fraction
        of the lowering and without the sampler."""
        import jax
        from jax.experimental.layout import Format, Layout

        from ray_tpu.models.transformer import Transformer

        mcfg = self.model.cfg
        at = next((i for i in range(mcfg.n_layers)
                   if mcfg.cache_kind_of(i) != "state"), None)
        if at is None:
            return 0
        one = mcfg if at == 0 else dataclasses.replace(
            mcfg, mixers=mcfg.mixers[at:], window_layers=mcfg.window_layers[at:],
            moe_first_layer=max(0, mcfg.moe_first_layer - at))
        one = Transformer(dataclasses.replace(one, n_layers=1))
        params = {name: leaf for name, leaf in self.params.items()
                  if not name.startswith("layer_")}
        params["layer_0"] = self.params[f"layer_{at}"]
        cache = self._cache_shapes(one, params)
        auto = jax.tree.map(
            lambda leaf: Format(Layout.AUTO, leaf.sharding), cache)
        probe = jax.jit(make_chunk(one), static_argnums=(8, 9),
                        donate_argnums=(1,),
                        in_shardings=(None, auto) + (None,) * 8,
                        out_shardings=(auto, None, None, None))
        self._probe = (probe, self._chunk_shapes(params, cache, True))
        wanted = self._programs.get(("probe",)).input_formats[0][1]
        del self._probe
        rows = set()
        for leaf, fmt in zip(jax.tree.leaves(cache), jax.tree.leaves(wanted)):
            lay, width = fmt.layout, leaf.shape[-1]
            lanes = lay.tiling[0][-1] if lay.tiling else 1
            row_major = lay.major_to_minor == tuple(range(leaf.ndim))
            rows.add(-(-width // lanes) * lanes
                     if row_major and width % lanes else 0)
        return rows.pop() if len(rows) == 1 else 0

    def _count_boundary_copies(self):
        """Read from the compiled text of the longest sampled chunk program
        what it does with the cache at its boundary. The table's program:
        the one this variant's first call takes too."""
        import jax

        compiled = self._programs.get(("chunk", self.decode_chunk, False))
        text = compiled.as_text()
        leaves = jax.tree.leaves(self._cache_spec)
        formats = jax.tree.leaves(compiled.input_formats[0][1])
        local = {f.sharding.shard_shape(leaf.shape)
                 for leaf, f in zip(leaves, formats)}
        self.cache_boundary_copies = sum(
            len(re.findall(r"= \w+\[%s\]\S* copy\("
                           % ",".join(map(str, dims)), text))
            for dims in local)
        self.cache_layout = "; ".join(sorted(
            {f"{leaf.dtype}{list(leaf.shape)} {f.layout}"
             for leaf, f in zip(leaves, formats)}))
        logger.info("kv cache %s: %d whole-leaf copies in the %d-step chunk "
                    "program", self.cache_layout, self.cache_boundary_copies,
                    self.decode_chunk)

    def program_stats(self) -> dict:
        """For /v1/stats `setup`: how the serving programs came to be. With
        the last start's list found, `programs_ahead` were built from it
        before anyone asked and `programs_waited` were asked for while its
        build had them in hand; `programs_on_demand` were on no list (all of
        them where there was none) and `list_unused` were built from the
        list and never asked for. A warm start that the list covers reads
        `programs_on_demand` 0."""
        return self._programs.stats()

    def cache_stats(self) -> dict:
        """For /v1/stats: the cache's leaves and their on-device layout, how
        many copies of a whole leaf the longest sampled chunk program makes
        (a conversion at its boundary; 0 wanted), and, over the decode
        steps dispatched since start, the share of `max_seq` rows a step's
        attention walked (`kv_walk_share`: the prefix its chunk's
        `kv_bound` chose) beside the share its live slots had written on
        average (`kv_live_share`: what a walk that stopped at each slot's
        own length would read); `cache_kinds`, the same two shares and the
        layers, LEAVES (a layer keeps K and V, a state's parts, or, under
        `moe_shortcut`, two latents), rows a slot and bytes of each kind of
        rows leaf, `full` (`max_seq` rows; the latent leaves are of this
        kind), `window` (a ring, or under "eva" a block that starts over)
        and `chunks` (an "eva" layer's summaries, `max_seq` / chunk rows,
        beside `eva_summaries_total` and `eva_restarts_total`, the live
        slots' steps that wrote a summary a layer and that began a window
        after the first), and of the `state` leaves their layers,
        leaves, `bytes_per_slot` and bytes (`state_bytes` at the top); for
        a model with expert layers what `_moe_stats` counts (the identity
        experts' selections among it); `kv_heads`, the key/value
        heads a row holds; and how batch rows changed hands: the
        hand-overs (`splices`), those whose program was dispatched behind
        at least one decode chunk in flight (`splices_in_flight`), and
        the scheduler's passes that began with occupants seated and no
        chunk in flight (`pipeline_dry`), and the one-step chunks
        dispatched past an occupant's known last step for the occupants who
        go on, a parked request waiting for the row (`cover_chunks`: at
        saturation about one a hand-over, `splices_in_flight` near
        `splices` and `pipeline_dry` near none; below capacity none); and
        the rows of the prefill
        buckets dispatched (`prefill_rows`) beside those whose program's
        attention is a Pallas kernel (`prefill_rows_kernel`); and the
        decode steps dispatched in the sampled program (`sampler_steps`)
        beside those in which the sampler selected and sorted nothing
        (`sampler_steps_select`); and all decode steps dispatched
        (`decode_steps`) beside those whose attention is the ragged kernel
        (`decode_steps_kernel`), for which the walked share is what the
        kernel fetches: the live slots' own rows rounded up to its row
        block, or under the `mha` family's to the piece of a slot's last
        block (`_kernel_pieces`). Under a
        looped stack `cache_kinds.full` says `passes` (its `leaves` are 2 x
        passes x layers; the two shares stay ONE leaf's rows a step, which
        a reader multiplies by `ut_steps`), and at the top come `ut_steps`,
        `loop_passes_total` (the live slots' decode steps x the passes each
        ran) and `exit_mass_total` (by pass, the sum over those steps of
        the probability of leaving the loop there)."""
        mcfg = self.model.cfg
        steps = max(1, self.decode_steps)
        kinds = {kind: k if kind == "state" else {
                     **k, "walk_share": self._kv_walked[kind]
                     / (steps * k["rows"]),
                     "live_share": self._kv_live[kind] / (steps * k["rows"])}
                 for kind, k in self._cache_kinds.items()}
        # (the two shares at the top are the full leaves', as they were
        # before a leaf could be a ring; `cache_kinds` has both kinds')
        top = kinds.get("full") or kinds.get("window") or {}
        latent = "mla" in mcfg.mixers
        out = {"cache_layout": self.cache_layout,
               "cache_boundary_copies": self.cache_boundary_copies,
               "cache_kind": "latent" if latent else "kv",
               "cache_bytes": sum(k["bytes"] for k in kinds.values()),
               "cache_kinds": kinds,
               "kv_heads": 1 if latent else mcfg.n_kv_heads,
               "kv_walk_share": top.get("walk_share", 0.0),
               "kv_live_share": top.get("live_share", 0.0),
               "splices": self.splices,
               "splices_in_flight": self.splices_in_flight,
               "pipeline_dry": self.pipeline_dry,
               "cover_chunks": self.cover_chunks,
               "prefill_rows": self.prefill_rows,
               "prefill_rows_kernel": self.prefill_rows_kernel,
               "sampler_steps": self.sampler_steps,
               "sampler_steps_select": self.sampler_steps_select,
               "decode_steps": self.decode_steps,
               "decode_steps_kernel": self.decode_steps_kernel}
        if "state" in kinds:
            out["state_bytes"] = kinds["state"]["bytes"]
        if self._eva:
            out.update({f"{name}_total": getattr(self, f"{name}_total")
                        for name in _EVA_COUNTERS})
        if self._blocks:
            # (`decode_steps` are forwards; a forward yields no token or
            # several: `bd_tokens_total` / `bd_forwards_total` a live slot)
            out.update({f"{name}_total": getattr(self, f"{name}_total")
                        for name in _BD_COUNTERS})
        if self._passes:
            out.update(ut_steps=self._passes,
                       loop_passes_total=self.loop_passes_total,
                       exit_mass_total=[round(m, 3)
                                        for m in self.exit_mass_total])
        if self._moe_held:
            out.update(self._moe_stats())
        return out

    def _count_moe(self, block: np.ndarray, at: int, n: int) -> dict:
        """The expert layers' counts of the n-step chunk just read (they
        ride behind its tokens, columns `at` on of its block): added to the
        totals, and returned as the attributes `engine.host_sync` carries."""
        counts = _rows_from_columns(block[:, at:at + self._moe_cols],
                                    _moe_counters(self.model.cfg))
        rows = counts[:self._moe_held]
        total = int(rows.sum())
        self.moe_rows_total += total
        _count_metric("LLM_MOE_ROWS", total)
        attrs = {"moe_rows": total, "moe_rows_busiest": int(rows.max()),
                 "moe_steps": n}
        # every expert layer's four (`_PICK_COUNTERS`): the selections made,
        # those on an identity expert, the held experts that got a row, and
        # those whose weights the steps' arm read
        attrs.update(self._count_picks(*counts[len(rows):]))
        return attrs

    def _count_named(self, names: tuple, block: np.ndarray, at: int) -> dict:
        """The counters `names` (`_EVA_COUNTERS`, `_BD_COUNTERS`) of the
        chunk just read (columns `at` on of its block): added to the totals
        and, where `util/metrics.py` has them, the process's metrics, and
        returned as the attributes `engine.host_sync` carries."""
        cols = -(-len(names) // self.max_batch)
        got = dict(zip(names, map(int, _rows_from_columns(
            block[:, at:at + cols], len(names)))))
        for name, n in got.items():
            setattr(self, f"{name}_total", getattr(self, f"{name}_total") + n)
            _count_metric(f"LLM_{name.upper()}", n)
        return got

    def _count_loop(self, block: np.ndarray, at: int, slot_steps: int
                    ) -> dict:
        """A looped stack's counts of the chunk just read: its exit mass by
        pass (columns `at` on of its block, float32 as its bits) and the
        passes its `slot_steps` live slots' steps ran (every pass, while
        nothing leaves the loop early): added to the totals, and returned
        as the attributes `engine.host_sync` carries."""
        mass = _rows_from_columns(
            block[:, at:at + self._loop_cols], self._passes
        ).astype(np.int32).view(np.float32).tolist()
        passes = slot_steps * self._passes
        self.loop_passes_total += passes
        _count_metric("LLM_LOOP_PASSES", passes)
        for t, m in enumerate(mass):
            self.exit_mass_total[t] += m
            _count_metric("LLM_EXIT_MASS", m, {"pass": str(t + 1)})
        return {"exit_mass": [round(m, 4) for m in mass],
                "loop_passes": passes}

    def _init_cache(self):
        """Zero cache for the full batch."""
        import jax
        import jax.numpy as jnp

        def zeros(leaf):
            z = jnp.zeros(leaf.shape, leaf.dtype)
            return (z if leaf.sharding is None
                    else jax.device_put(z, leaf.sharding))

        with telemetry.setup_stage("engine.cache_alloc"):
            return jax.tree.map(zeros, self._cache_spec)

    # -------------------------------------------------------------- public
    def submit(self, prompt_tokens, sampling: Optional[SamplingParams] = None
               ) -> GenStream:
        """Queue one request; returns its token stream immediately."""
        sampling = sampling or SamplingParams()
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) + sampling.max_tokens > self.cfg.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_tokens ({sampling.max_tokens}) "
                f"exceeds max_seq ({self.cfg.max_seq})")
        stream = GenStream(next(self._req_counter), len(prompt))
        if _tracing.enabled():
            stream.trace = _tracing.current()
            stream.prefill_span = _tracing._new_id(8)
        # The _running check and the enqueue must be ONE atomic step
        # against shutdown()'s flag flip: a submit that slips between the
        # check and the put could otherwise queue a stream after the
        # scheduler's final drain — stranding it without _DONE forever.
        with self._lock:
            if not self._running:
                raise RuntimeError("engine is shut down")
            self._streams.add(stream)
            if stream.trace is not None:
                _stage_begin(stream, time.time(),
                             pending=self._pending.qsize())
            self._pending.put((prompt, sampling, stream))
            self._lock.notify_all()
        return stream

    def generate(self, prompts, sampling: Optional[SamplingParams] = None
                 ) -> list[list[int]]:
        """Batch convenience: submit all, drain all."""
        streams = [self.submit(p, sampling) for p in prompts]
        return [s.tokens() for s in streams]

    def shutdown(self):
        with self._lock:
            self._running = False
            self._lock.notify_all()
        self._pending.put(None)  # wake the prefill lane past its get()
        self._programs.close()  # and whoever waits for a program
        for t in self._threads:
            t.join(timeout=10)
        # Belt and braces after the join: the scheduler thread drains
        # _pending on exit, but if the join timed out (thread wedged in a
        # device call) any queued streams would hang their consumers —
        # terminate them here. Safe against the loop's own drain (done
        # markers are idempotent) because no new submit can enqueue after
        # the flag flipped under the lock.
        self._drain_all_streams()

    def _drain_all_streams(self, error: Optional[Exception] = None):
        """Terminate every stream that has not seen _DONE: queued, ready,
        slotted, or otherwise tracked. Idempotent (done markers re-queue
        harmlessly); the error, when given, lands before the marker."""
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

    def _finish_stream(self, stream: GenStream,
                       error: Optional[Exception] = None):
        if error is not None:
            stream._q.put(error)
        stream._q.put(GenStream._DONE)
        with self._lock:
            self._streams.discard(stream)

    @property
    def num_active(self) -> int:
        return self._n_active

    # ----------------------------------------------------------- scheduler
    def _bucket(self, plen: int) -> int:
        """Rows a prompt's prefill is padded to: the next power of two and,
        above HALF_STEP_ABOVE, three quarters of it where the prompt fits
        (..., 2048, 4096, 6144, 8192, 12288, ...). By blocks a prefill
        runs the prompt's whole blocks; the rest opens the first block."""
        plen = self._committed(plen)
        b = 8
        while b < plen:
            b *= 2
        if b > HALF_STEP_ABOVE and plen <= b // 4 * 3:
            b = b // 4 * 3
        return min(b, self.cfg.max_seq)

    def _committed(self, plen: int) -> int:
        """Rows a prompt's prefill commits: the prompt, or by blocks its
        whole blocks (the rest opens the first block)."""
        return plen - plen % self._blocks if self._blocks else plen

    def _prefill_form(self, bucket: int) -> str:
        """`kernel` where every layer's attention of this bucket's prefill
        program is a Pallas kernel that keeps its scores on the chip, else
        `xla`: the dispatchers' own rules put to the shapes the program
        was traced with (`ops/attention.py` `kernel_refusal`, the flash
        kernel; for an "eva" model that one up to a window and, past it,
        `ops/two_source_attention.py` `two_source_refusal` at the bucket
        padded to whole windows, as `models/eva.py` pads it). Nothing is
        read back from the device. Latent attention has a prefill of its
        own (`models/mla.py`)."""
        from ray_tpu.ops.attention import kernel_refusal
        from ray_tpu.ops.two_source_attention import two_source_refusal

        if bucket not in self._prefill_form_of:
            mcfg = self.model.cfg
            q = (1, bucket, mcfg.n_heads, mcfg.head_dim)
            with self._mesh_scope():
                if set(mcfg.mixers) == {"eva"}:
                    window = mcfg.eva_window
                    kernel = (kernel_refusal(q, q) if bucket <= window
                              else two_source_refusal(
                                  (1, -(-bucket // window) * window, *q[2:]),
                                  window, mcfg.eva_chunk)) is None
                else:
                    kernel = not mcfg.mixers and all(
                        kernel_refusal(
                            q, (1, bucket, mcfg.n_kv_heads, mcfg.head_dim),
                            window=window, **(
                                {"blocks": self._blocks} if self._blocks
                                else {})) is None
                        for window in {mcfg.window_of(i)
                                       for i in range(mcfg.n_layers)})
            self._prefill_form_of[bucket] = "kernel" if kernel else "xla"
        return self._prefill_form_of[bucket]

    def _decode_blocks(self) -> tuple[dict, dict, str]:
        """The ragged kernels' row block by kind of rows leaf (`full`,
        `window`, `chunks`) whose layers' bounded decode step takes a kernel
        (a kind the dispatcher refuses is left out, and {} is the XLA walk
        throughout), the rows a slot's fetch is rounded up to by the same
        kinds, and the name of that: `kernel`, `xla`, `mixed`. The
        dispatchers' own rules (`ops/decode_attention.py`: `walk_refusal`
        for the K and V leaves of `mha` layers, `latent_refusal` for the
        latent leaf of `mla` layers, each deciding leaf by leaf, and
        `two_leaf_refusal` for an "eva" layer's window and chunks leaves,
        ONE answer and one block for both) put to the leaves the chunk
        program is traced with, under the mesh it is traced under. Nothing
        is read back from the device. (No model has rows leaves of two
        families.)"""
        import jax

        from ray_tpu.ops.decode_attention import (latent_block,
                                                  latent_refusal, row_block,
                                                  row_granule,
                                                  two_leaf_block,
                                                  two_leaf_refusal,
                                                  walk_refusal)

        mcfg = self.model.cfg
        q = (self.max_batch, mcfg.n_heads, mcfg.head_dim)

        def block_of(mixer, shape, dtype):
            """A leaf's row block and the rows a slot's fetch is rounded up
            to, None where its rule refuses it."""
            if mixer == "mla":
                refused = latent_refusal(shape, mcfg.kv_lora_rank, dtype)
                return None if refused else (latent_block(shape, dtype),) * 2
            if mixer == "eva":  # (the window leaf's shape, the chunks')
                refused = two_leaf_refusal(q, *shape, dtype)
                return None if refused else (
                    two_leaf_block(*shape, dtype),) * 2
            refused = walk_refusal(q, shape, dtype)
            return None if refused else (row_block(shape, dtype),
                                         row_granule(shape, dtype))

        leaves: dict = {}  # kind -> EVERY leaf of its layers, as the rules ask
        for i in range(mcfg.n_layers):
            mixer, layer = mcfg.mixer_of(i), self._cache_spec[f"layer_{i}"]
            if mixer in ("mha", "mla"):
                leaves.setdefault(mcfg.cache_kind_of(i), set()).update(
                    (mixer, leaf.shape, leaf.dtype)
                    for leaf in jax.tree.leaves(layer))
            elif mixer == "eva":
                # both kinds of leaf get the layer's one question
                kinds = _leaves_by_kind(mcfg, {f"layer_{i}": layer})
                asked = (mixer, tuple(kinds[kind][0][1].shape
                                      for kind in ("window", "chunks")),
                         kinds["window"][0][1].dtype)
                for kind in ("window", "chunks"):
                    leaves.setdefault(kind, set()).add(asked)
        with self._mesh_scope():  # a kind's block: the one ALL its leaves get
            blocks = {kind: _agreed({block_of(*leaf) for leaf in asked})
                      for kind, asked in leaves.items()}
        blocks = {kind: block for kind, block in blocks.items() if block}
        return ({kind: block for kind, (block, _) in blocks.items()},
                {kind: piece for kind, (_, piece) in blocks.items()},
                ("xla" if not blocks else "kernel"
                 if len(blocks) == len(leaves) else "mixed"))

    def _mesh_scope(self):
        """The engine's mesh as the mesh in context, for what is traced
        inside: the attention's dispatcher keeps its unpartitioned kernel
        out of a program that GSPMD shards over `tp`."""
        return contextlib.nullcontext() if self.mesh is None else self.mesh

    def _slice_bytes(self, bucket: int) -> int:
        """Bytes of the cache slices a prefill of this bucket hands on."""
        import jax

        if bucket not in self._slice_bytes_of:
            self._slice_bytes_of[bucket] = sum(
                leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(self._slice_shapes(bucket)))
        return self._slice_bytes_of[bucket]

    def _prefill_dispatch(self, prompt, sampling, stream):
        """Dispatch bucketed prefill + first-token sample WITHOUT reading
        anything back: returns (first_token_dev, cache_slice, next_key) —
        pure device handles, safe to produce off the scheduler thread (no
        shared scheduler state is touched)."""
        import jax.numpy as jnp

        plen = len(prompt)
        lb = self._bucket(plen)
        # By blocks the prefill runs the prompt's whole blocks (none at all
        # of a prompt shorter than one: its bucket's rows are then padding,
        # written and never seen), and the rest opens the first block.
        held_back = plen - self._committed(plen)
        toks = np.zeros((1, lb), np.int32)
        toks[0, :plen - held_back] = prompt[:plen - held_back]
        ann = None
        if stream.trace is not None:
            ann = self._jax.profiler.TraceAnnotation(
                "engine.prefill_dispatch")
            ann.__enter__()
        account = _tracing.enabled()
        t_adm = time.time()
        try:
            toks_dev = jnp.asarray(toks)
            # The account: the chunks whose dispatch had ended when the
            # prefill program's call began, and the prefill's bucket for
            # the next chunk the scheduler dispatches to count ahead of
            # itself. A chunk whose dispatch ends while the lane is inside
            # the call, or finds the bucket already there, says so
            # (`prefills_beside`): the two may lie either way round.
            if account:
                done0 = self._chunks_done
                self._prefill_in_call = True
                # The set-up account: a program built in one of this
                # request's calls is a child of its `engine.prefill`.
                ctx = stream.trace and (stream.trace[0], stream.prefill_span)
                telemetry.ACCOUNT.begin_call(ctx)
            try:
                got = self._prefill(self.params, toks_dev,
                                    plen - held_back)
                last_logits, cache_slice = (None, got) if self._blocks else got
                if account:
                    self._prefills_since.append(lb)
                    built = telemetry.ACCOUNT.end_call(
                        bucket=lb, kernel=self._prefill_form(lb) == "kernel")
                    telemetry.ACCOUNT.begin_call(ctx)  # key and first token
            finally:
                if account:
                    self._prefill_in_call = False
            key = self._jax.random.fold_in(
                self._jax.random.PRNGKey(sampling.seed), stream.request_id)
            if self._blocks:
                # the row's first state: the prompt's last tokens and the
                # mask behind them, so many tokens short of an answer's
                # first, `max_tokens` wanted, no denoising forward yet
                mask = self.model.cfg.denoising.mask_token
                first = np.array(
                    [*prompt[plen - held_back:],
                     *[mask] * (self._blocks - held_back),
                     -held_back, sampling.max_tokens, 0], np.int32)
            else:
                first = self._sample1(
                    last_logits, key,
                    jnp.float32(sampling.temperature),
                    jnp.int32(sampling.top_k), jnp.float32(sampling.top_p))
                # The scheduler reads it at the drain after the hand-over:
                # the copy is under way by then, and nothing is built there.
                _start_host_copy(first)
        finally:
            if ann is not None:
                ann.__exit__(None, None, None)
        # The HOST's dispatch of the prefill programs (asynchronous: the
        # device may run them later). The device's prefill time is
        # `jit_prefill` in a device trace. The benchmark's `admit_wait_ms`
        # reads this span's start.
        form = self._prefill_form(lb)
        self.prefill_rows += lb
        if form == "kernel":
            self.prefill_rows_kernel += lb
        attrs = {"prompt_len": plen, "bucket": lb, "attention": form}
        t_end = time.time()
        if account:
            # the last chunk surely enqueued before the prefill
            attrs["after_seq"] = done0 - 1
            # what was built for it waits for its first token's read
            stream._built = (built or []) + (
                telemetry.ACCOUNT.end_call(bucket=lb) or [])
        if self._state_rw_bytes:
            # a state layer's prefill is a scan over chunks of the bucket
            mcfg = self.model.cfg
            attrs.update(scan_chunks=-(-lb // mcfg.kda_chunk), mixers=",".join(
                f"{m}:{mcfg.mixers.count(m)}" for m in sorted(set(mcfg.mixers))))
        if self._passes:
            attrs["ut_steps"] = self._passes  # slices handed on a layer
        if self._blocks:
            # the rows the prefill committed, and the prompt's tokens that
            # open the first block
            attrs.update(committed=plen - held_back, open=held_back)
        if self._eva:
            # what the prefill handed on: the windows it ran, and the
            # summaries of the whole chunks before the prompt's end
            attrs.update(windows=-(-lb // self._window),
                         summaries=plen // self.model.cfg.eva_chunk)
        _tracing.record_span_in(stream.trace, "engine.prefill", "engine",
                                t_adm, t_end, attrs, stream.prefill_span)
        return first, cache_slice, self._jax.random.fold_in(key, 1)

    def _prefill_loop(self):
        """The prefill lane: drains submits, dispatches their prefills,
        and parks the device-resident results in _ready for the scheduler
        to splice at the next chunk boundary. Prefill COMPILES (new
        buckets) and dispatches happen here — the decode loop never
        stalls for an admission."""
        while True:
            try:
                item = self._pending.get(timeout=0.25)
            except queue.Empty:
                if not self._running:
                    return
                continue
            if item is None:  # shutdown wakeup
                if not self._running:
                    return
                continue
            prompt, sampling, stream = item
            if stream.trace is not None:
                _stage_end(stream, "engine.queue", time.time())
            if not self._running:
                # Shutdown raced the pop: terminate the stream instead of
                # compiling/dispatching a prefill nobody will consume (a
                # cold bucket compile here would stall shutdown's join).
                self._finish_stream(stream)
                continue
            if stream.closed:
                stream.finish_reason = "cancelled"
                self._finish_stream(stream)
                continue
            # inflight guards the scheduler's idle-wait: a popped submit
            # whose prefill is still dispatching must keep the loop from
            # concluding "nothing pending" (it would only cost the 0.1s
            # wait timeout, but the first token is latency-critical).
            nbytes = self._slice_bytes(self._bucket(len(prompt)))
            with self._lock:
                # Parked slices live on the device: run ahead of the
                # scheduler only within `_park_budget` (one request may
                # always be parked, whatever its size).
                while (self._running and self._parked_bytes
                       and self._parked_bytes + nbytes > self._park_budget):
                    self._lock.wait(timeout=0.25)
                self._parked_bytes += nbytes
                self._prefill_inflight += 1
            try:
                entry = (len(prompt), sampling, stream,
                         *self._prefill_dispatch(prompt, sampling, stream))
            except Exception as e:  # bad request or device failure
                with self._lock:
                    self._prefill_inflight -= 1
                    self._parked_bytes -= nbytes
                self._finish_stream(stream, e)
                continue
            with self._lock:
                if stream.trace is not None:
                    _stage_begin(stream, time.time(), ready=len(self._ready))
                self._ready.append(entry)
                self._prefill_inflight -= 1
                self._lock.notify_all()

    def _splice(self, slot: int, plen: int, sampling, stream, first,
                cache_slice, key):
        """Hand free batch row `slot` to one prefilled request (scheduler
        thread only): ONE device program scatters the cache slice and sets
        the row's device mirrors (`place`), then the occupant is booked.
        The program queues behind whatever chunks are in flight, and
        every chunk dispatched from here on records the new occupant."""
        if stream.trace is not None:
            now = time.time()
            _stage_end(stream, "engine.ready_wait", now,
                       active=self._n_active)
            _stage_begin(stream, now, slot=slot,
                         chunks_in_flight=len(self._q_chunks))
            # the hand-over program, and the cache at the first hand-over
            telemetry.ACCOUNT.begin_call(
                (stream.trace[0], stream.prefill_span))
        if self._cache is None:
            self._cache = self._init_cache()
        mirrors = (self._toks_dev, self._lens_dev, self._keys,
                   self._temps_dev, self._topks_dev, self._topps_dev)
        self._cache, mirrors = self._place(
            self._cache, cache_slice, mirrors, first, key,
            # (by blocks the row's length is what the prefill committed)
            np.array([slot, self._committed(plen), sampling.top_k], np.int32),
            np.array([sampling.temperature, sampling.top_p], np.float32))
        (self._toks_dev, self._lens_dev, self._keys, self._temps_dev,
         self._topks_dev, self._topps_dev) = mirrors
        if self._blocks:
            # (its first tokens come from its first commit, in a chunk's
            # block: there is no first token to read)
            st = _Slot(slot, stream, sampling, first,
                       self._forwards_left(first))
        else:
            st = _Slot(slot, stream, sampling)
            self._pending_firsts.append((st, first))
        self._slots[slot] = st
        self._n_active += 1
        self._lengths[slot] = plen
        self.splices += 1
        if self._q_chunks:
            self.splices_in_flight += 1

    def _free_slot(self) -> Optional[int]:
        return next((i for i, s in enumerate(self._slots) if s is None),
                    None)

    def _deliver(self, st: _Slot, toks: list):
        """Hand one chunk's tokens to the occupant's stream as ONE queue
        put (a blocked reader wakes once per chunk, not once per token),
        applying stop-token / length truncation host-side."""
        if st.done:
            return  # stopped or cancelled earlier; the tail is garbage
        if st.stream.closed:
            st.stream.finish_reason = "cancelled"
            self._retire(st)
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
            first = st.emitted == len(out) and st.stream.trace is not None
            t_put = time.time() if first else 0.0
            st.stream._q.put(out)
            if first:
                _stage_end(st.stream, "engine.first_token", t_put)
            _count_tokens(len(out))
        if finish is not None:
            st.stream.finish_reason = finish
            self._retire(st)

    def _retire(self, st: _Slot):
        """End the occupant's stream; its row is free for the next request
        at once. Chunks in flight may still step the row (the occupant
        stopped early or its consumer went away): they run before the next
        hand-over's program on the device, and what they decode belongs to
        the occupant they recorded, which takes no more."""
        self._finish_stream(st.stream)
        st.done = True
        self._slots[st.slot] = None
        self._n_active -= 1
        self._lengths[st.slot] = 0
        # (the row's device mirrors keep stale values until the next
        # hand-over; it decodes garbage that nobody is handed)

    def _fail(self, occupants, error: Exception):
        """A dispatch or a read failed: the error, then the end, on every
        stream among `occupants` that is still open."""
        for st in occupants:
            if not st.done:
                st.stream._q.put(error)
                self._retire(st)

    def _loop(self):
        """Scheduler wrapper: an unexpected scheduler death must surface
        an attributed error on EVERY open stream (queued, ready, or
        decoding) — a consumer blocked in next() can never hang on a dead
        engine. Normal exit drains the same way without the error."""
        error: Optional[Exception] = None
        try:
            self._run_scheduler()
        except Exception as e:  # noqa: BLE001 - terminal: loop is dead
            logger.exception("llm engine scheduler loop died")
            error = RuntimeError(f"llm engine scheduler died: {e!r}")
        finally:
            with self._lock:
                self._running = False
            self._drain_all_streams(error)

    def _run_scheduler(self):
        """Scheduler with depth-D software pipelining. Host syncs are the
        scarce resource (a blocking read stalls dispatch until the device
        catches up): up to `PIPELINE_DEPTH` decode chunks stay in flight with
        their inputs chained ENTIRELY on device (next-token/length mirrors
        ride chunk outputs, so steady-state dispatch transfers nothing).
        Each chunk's token block starts its device→host copy AT DISPATCH
        (copy_to_host_async) and is read back one chunk per iteration —
        double-buffered extraction: reading chunk N overlaps the execution
        of chunks N+1..N+D-1, so the XLA stream never drains.

        How a batch row changes hands: through ONE device program
        (`_splice`), where its occupant's last token is READ. A chunk is cut
        to the fewest steps any seated occupant still needs, and nobody is
        stepped past a known end that nobody waits behind: with no request
        parked the pipeline drains to that chunk, and an arrival's prefill
        queues behind nothing. With a request parked in `_ready` (every row
        is taken: it waits for this one) ONE cover chunk goes past the end:
        one step, the shortest program there is, for the occupants who go
        on, so that the device steps them while the host reads the block,
        hands out its tokens, retires the occupant and splices the
        newcomer. The cover records only those occupants; the finished row
        steps in it as a free row does (`live` off: what it decodes is
        handed to nobody), and its `kv_bound` is theirs alone. No second
        chunk goes past an end that has not been read (`_Slot.covered`), so
        the host's reads pace the chain and the newcomer's `place` queues
        behind one step at most. The newcomer's first token is read with
        the first chunk that steps it, not with the cover (`_drain`): the
        two reach the client in one drain, as they do after a hand-over
        into a drained pipeline. (Handing the row on at DISPATCH time,
        with the pipeline kept at its depth, was measured and left out:
        the newcomer's first step then queues behind up to three chunks,
        PERF.md section 6, PR 31; the cover is PR 57's.) A chunk records
        its occupants (`_Slot`), not their rows, so a row given up early (a
        stop token, a consumer gone) is the next request's at once,
        whatever chunks still step it: device program order alone protects
        the cache (place/chunk chain through the cache handle and the
        mirrors).

        One pass: `_fill_pipeline` with hand-overs and decode chunks,
        `_drain` the oldest chunk."""
        phases = _Phases(self._jax.profiler)
        while self._running:
            # Tracing on: the pass's phases on the host's and the
            # profiler's clock (_Phases). Off: this one read, nothing else.
            ph = phases if _tracing.enabled() else None
            if ph is not None:
                ph.start_pass()
            if self._n_active and not self._q_chunks:
                self.pipeline_dry += 1
            spliced, dispatched, iter_ctx = self._fill_pipeline(ph)
            if not (self._q_chunks or self._pending_firsts):
                # Nothing in flight, so nobody is seated: wait for work.
                if ph is not None:
                    ph.begin("idle_wait")
                with self._lock:
                    if (self._running and self._pending.empty()
                            and not self._ready
                            and self._prefill_inflight == 0):
                        self._lock.wait(timeout=0.1)
                if ph is not None:
                    ph.end()  # no span: the next pass carries its idle_ms
                continue
            sync_ctx = self._drain(ph)
            if ph is not None:
                # the traced request the pass's span is bound to
                ph.end_pass(iter_ctx or sync_ctx, spliced=spliced,
                            chunks=dispatched,
                            in_flight=len(self._q_chunks),
                            active=self._n_active)

    def _admit(self) -> int:
        """Phase `admit`: hand the free rows to the requests the prefill
        lane has parked in `_ready`. Nothing here reads from the device:
        first tokens are NOT read at admission, they join the next drain's
        readback (an admission-wave readback would cost its own blocking
        host sync). Returns the requests spliced."""
        spliced = 0
        while self._ready:
            free = self._free_slot()
            if free is None:
                break
            with self._lock:
                if not self._ready:
                    break
                entry = self._ready.popleft()
                self._parked_bytes -= self._slice_bytes(
                    self._bucket(entry[0]))
                self._lock.notify_all()  # the lane may run ahead again
            plen, sampling, stream, first, cache_slice, key = entry
            if stream.closed:
                stream.finish_reason = "cancelled"
                self._finish_stream(stream)
                continue
            try:
                self._splice(free, plen, sampling, stream, first,
                             cache_slice, key)
                spliced += 1
            except Exception as e:
                self._finish_stream(stream, e)
        return spliced

    def _account_ahead(self) -> dict:
        """The admission programs enqueued since the last chunk's dispatch
        (scheduler thread, tracing on): the prefills the lane has counted
        and the `place`s of this thread's own hand-overs."""
        buckets: dict = {}
        while self._prefills_since:
            lb = self._prefills_since.popleft()
            buckets[lb] = buckets.get(lb, 0) + 1
        places, self._splices_at_chunk = (
            self.splices - self._splices_at_chunk, self.splices)
        return {"prefill_buckets_ahead": ",".join(
            f"{lb}:{k}" for lb, k in sorted(buckets.items())),
            "places_ahead": places}

    def _rows_walked(self, kind: str, seen, kv_bound) -> tuple:
        """Rows of a leaf of this kind a slot's attention reads in a step of
        a chunk whose live slots show `seen` [slots, steps] rows of it: (as
        the chunk's span says it, as read). The XLA walk reads the static
        prefix `kv_bound` picks, every slot alike (the chunk's one bound,
        or under "eva" each step's own, [steps]: a step's mean then). The
        ragged kernel fetches
        each live slot's own rows rounded up to `_kernel_pieces`' rows (its
        row block, or the piece of its last block): `/v1/stats` sums their
        mean as it is, and the span carries the mean of the slots' BLOCKS
        as a whole multiple of the block (ISSUE 37 asked for that form: a
        reader that groups chunks by the rows walked,
        `benchmark/device_account.py`'s classes, needs few distinct values
        until it buckets them itself, ROADMAP M0 (j))."""
        from ray_tpu.ops.decode_attention import kv_prefix_rows

        block = self._kernel_blocks.get(kind)
        if block is None:
            rows = float(np.mean([
                kv_prefix_rows(int(bound), self._cache_kinds[kind]["rows"])
                for bound in np.atleast_1d(kv_bound)]))
            return (int(rows) if rows.is_integer() else round(rows, 2)), rows
        piece = self._kernel_pieces[kind]
        return (int(round(float(np.ceil(seen / block).mean()))) * block,
                float(np.ceil(seen / piece).mean()) * piece)

    def _block_rows(self, st: _Slot, n: int) -> np.ndarray:
        """The most rows `st`'s attention reads in each of the next n
        forwards, [n]: the rows committed when its state was last read, its
        open block's, and a block's more for every two forwards since (a
        block takes a denoising forward and a commit at least), never past
        its answer's last block."""
        size = self._blocks
        given, want, had = (int(v) for v in st.state[size:])
        ahead = had + st.in_flight + np.arange(n)  # forwards before each
        rows = st.stream.prompt_len + given + size * (1 + ahead // 2)
        return np.minimum(rows, st.stream.prompt_len + given
                          + -(-(want - given) // size) * size)

    def _fill_pipeline(self, ph) -> tuple:
        """Phases `admit` and `dispatch`, as often as they alternate: hand
        every free row to a waiting request, then dispatch a chunk for the
        occupants seated, until PIPELINE_DEPTH chunks are in flight
        (dispatches are asynchronous and nearly free; only the readback
        costs a round trip). A request that is parked while the loop runs
        joins before its next chunk. Past an occupant's known last step
        nothing is dispatched until that step is read, but for ONE cover
        chunk: one step for the occupants who go on, and only while a
        parked request waits for the row (`_run_scheduler`). Returns the
        requests spliced, the chunks dispatched and the first traced
        request a chunk's span was bound to, if any. Entered in phase
        `admit`."""
        max_seq = self.cfg.max_seq
        iter_ctx = None
        spliced = dispatched = 0
        while True:
            spliced += self._admit()
            seated = [s for s in self._slots if s is not None]
            if not seated or len(self._q_chunks) >= PIPELINE_DEPTH:
                break
            # Who still needs a step; the others' known last step is in
            # flight, and each one's row changes hands where that is read.
            ended = [s for s in seated if s.steps_left() < 1]
            active = [s for s in seated if s not in ended]
            cover = bool(ended)
            if not active or cover and (
                    not self._ready or any(s.covered for s in ended)):
                # Nobody is stepped past an end that nobody waits behind
                # (every row that was free has been given out: whoever is
                # still parked waits for this one), and no second chunk
                # goes past an end that has not been read: the host's
                # reads pace the chain, and a newcomer's `place` queues
                # behind one step at most.
                break
            if self._blocks:
                # (rows as last read; nobody's answer passes `max_seq`:
                # `submit` refuses it, and a slot holds whole blocks)
                live = [s.stream.prompt_len + int(s.state[self._blocks])
                        for s in active]
                budget = min(s.steps_left() for s in active)
            else:
                live = [int(self._lengths[s.slot]) for s in active]
                budget = int(min(min(s.steps_left() for s in active),
                                 max_seq - max(live)))
            if budget < 1:
                break  # a row at max_seq: `submit` refuses what gets there
            # Power-of-2 chunk sizes only: each distinct scan length
            # is its own compiled program, and an arbitrary shrinking
            # budget would recompile on nearly every call. The cover is
            # the shortest of them: it has the host's pass to cover, and
            # the newcomer's first step waits behind it.
            n = 1 if cover else max(1, min(
                self.decode_chunk, 1 << (budget.bit_length() - 1)))
            path = _sampler_path(s.sampling for s in active)
            live_rows = np.zeros(self.max_batch, bool)
            live_rows[[s.slot for s in active]] = True
            # Per-iteration tracing (README "Tracing & timeline"): bind
            # the decode loop's spans to the oldest active TRACED
            # request — in the one-request case every dispatch and
            # host sync lands in its timeline.
            tctx = next((s.stream.trace for s in active
                         if s.stream.trace is not None), None)
            # The rows the longest LIVE occupant has after these n steps:
            # where the chunk's attention may stop. Only the host can
            # say it, from integers it holds: a free row's device-side
            # length is stale and keeps growing, and what such a row
            # decodes is handed to nobody.
            if self._blocks:
                # Forwards, not rows: a slot's rows after them are known
                # only as a bound (`_block_rows`).
                seen = np.stack([self._block_rows(s, n) for s in active])
                kv_bound = int(seen.max())
            else:
                # step j sees length + j + 1 rows
                seen = np.add.outer(live, np.arange(1, n + 1))
                kv_bound = max(live) + n
            assert kv_bound <= max_seq and all(
                length + n <= kv_bound for length in live
                if not self._blocks), (live, n)
            seq = None
            if ph is not None:
                # wall_ns ties the spans' wall clock to the trace's own.
                ph.begin("dispatch", wall_ns=time.time_ns())
                # The account: this chunk's ordinal, and what the device
                # was handed since the chunk before it.
                seq = self._chunks_begun
                self._chunks_begun = seq + 1
                ahead = self._account_ahead()
                telemetry.ACCOUNT.begin_call(tctx)
            try:
                t_disp = time.time()
                self._cache, self._keys, toks_out, lens_out = \
                    self._chunk(
                        self.params, self._cache,
                        self._toks_dev, self._lens_dev,
                        self._keys, self._temps_dev,
                        self._topks_dev, self._topps_dev, n,
                        path == "greedy", np.int32(kv_bound), live_rows)
                # Start the device→host copy of this chunk's tokens
                # NOW: by the time the drain reads it (D iterations
                # later), the transfer has overlapped the younger
                # chunks' execution instead of serializing after it.
                _start_host_copy(toks_out)
                # Rows a slot's attention walks in each step of the chunk,
                # and rows a live slot has to show (`seen`, a ring at most
                # its own length): by kind of leaf, a step's mean.
                rows = {}
                if "full" in self._cache_kinds:
                    rows["full"] = (
                        *self._rows_walked("full", seen, kv_bound),
                        float(seen.mean()))
                if self._eva:
                    # a block that starts over, and the summaries of the
                    # windows behind it; each step's XLA walk is bounded by
                    # the longest of its own live stops (`models/eva.py`),
                    # the kernel's by each slot's own
                    per = self._window // self.model.cfg.eva_chunk
                    for kind, shown in (
                            ("window", (seen - 1) % self._window + 1),
                            ("chunks", (seen - 1) // self._window * per)):
                        rows[kind] = (
                            *self._rows_walked(kind, shown,
                                               shown.max(axis=0)),
                            float(shown.mean()))
                elif self._window:
                    ring = np.minimum(seen, self._window)
                    rows["window"] = (
                        *self._rows_walked("window", ring, kv_bound),
                        float(ring.mean()))
                form = self._decode_form
                attrs = {"tokens": n, "active": len(active),
                         "sampler": path, "attention": form,
                         "kv_bound": kv_bound,
                         "kv_rows": next(iter(rows.values()))[0]}
                if self._state_rw_bytes:
                    attrs["state_rw_bytes"] = self._state_rw_bytes
                if self._passes:
                    # each of a layer's leaves is walked once a step: what
                    # the rows below say of ONE leaf holds `ut_steps` times
                    attrs["ut_steps"] = self._passes
                if self._blocks:
                    # `tokens` are FORWARDS of so many positions a slot
                    attrs["block_length"] = self._blocks
                if cover:
                    # the occupants who go on, stepped while the host
                    # reads the others' last step (said only where true)
                    attrs["cover"] = True
                if seq is not None:
                    # in_flight 0: the device had no chunk of ours queued
                    attrs.update(ahead, seq=seq,
                                 in_flight=len(self._q_chunks))
                    if self._prefills_since or self._prefill_in_call:
                        # a prefill program's call ran beside this
                        # dispatch: on the device it may lie before this
                        # chunk, and the next chunk counts it ahead of
                        # itself. Said, not guessed.
                        attrs["prefills_beside"] = True
                    # a chunk program built in the call waits for the read
                    # of this chunk's block
                    built = telemetry.ACCOUNT.end_call(
                        tokens=n, sampler=path, kernel=form == "kernel")
                    if built:
                        self._built_at[seq] = built
                for kind, (walked, read, visible) in rows.items():
                    attrs["kv_rows_" + kind] = walked
                    attrs["kv_live_" + kind] = round(visible, 2)
                    self._kv_walked[kind] += n * read
                    self._kv_live[kind] += n * visible
                _tracing.record_span_in(
                    tctx, "engine.dispatch_chunk", "engine", t_disp,
                    time.time(), attrs)
                self.decode_steps += n
                self.decode_steps_kernel += n * (form == "kernel")
                self.sampler_steps += n * (path != "greedy")
                self.sampler_steps_select += n * (path == "select")
                # Chain on device; mirror lengths on host (every row
                # steps n times — deterministic, no read needed).
                if self._blocks:  # the rows' states ride last in the block
                    self._toks_dev = toks_out[:, -(self._blocks + 3):]
                else:
                    self._toks_dev = toks_out[:, n - 1]
                    self._lengths = self._lengths + n
                self._lens_dev = lens_out
                self._q_chunks.append((toks_out, active, n, cover, seq))
                dispatched += 1
                iter_ctx = iter_ctx or tctx
                for s in active:
                    s.in_flight += n
                if cover:
                    self.cover_chunks += 1
                    for s in ended:
                        s.covered = True
            except Exception as e:
                logger.exception("llm engine decode chunk failed")
                self._fail(active, e)
                self._chunks_done = self._chunks_begun
                break
            if ph is not None:
                self._chunks_done = self._chunks_begun
                ph.begin("admit")
        return spliced, dispatched, iter_ctx

    def _drain(self, ph):
        """Phases `sync` and `deliver`: read the OLDEST in-flight chunk
        (plus the first tokens of the hand-overs since the last drain, whose
        host copies the prefill lane started; behind a cover chunk, those
        of the occupants it stepped), leaving the younger chunks
        executing — the double buffer — and hand the tokens to the
        occupants the chunk recorded. One host_sync per chunk: a request's
        span count is bounded by its CHUNK count, never its token count.
        The chunk's block is read first and the first tokens after it, so
        that a traced sync can stamp the block's arrival alone.
        Returns the traced request the sync's span is bound to, if any."""
        toks_dev, occupants, n, cover, seq = (
            self._q_chunks.pop(0) if self._q_chunks
            else (None, [], 0, False, None))
        firsts, self._pending_firsts = self._pending_firsts, []
        if cover:
            # A hand-over behind a cover: the cover did not step the
            # newcomer, whose first token is read with the first chunk that
            # did, as it is after a hand-over into a drained pipeline.
            # (Read here it would start the client's clock between two
            # tokens a step or more early; ROADMAP S2 (d).)
            self._pending_firsts = [
                (st, f) for st, f in firsts
                if not (st.done or st in occupants)]
            firsts = [(st, f) for st, f in firsts
                      if st.done or st in occupants]
        owed = occupants + [st for st, _f in firsts]
        # The host-sync readback: THE per-iteration host-link round
        # trip the decode loop pays (once one per TOKEN; now one per
        # chunk, overlapped). Span it against the oldest traced
        # in-flight request + the decode-step histogram.
        sync_ctx = None
        if _tracing.enabled():
            sync_ctx = next(
                (st.stream.trace for st in owed
                 if not st.done and st.stream.trace is not None), None)
            # The set-up account: a hand-over's call ends here at the
            # latest, and nobody waits for a chunk past its occupants' ends.
            telemetry.ACCOUNT.close_call()
            if sync_ctx is None and seq in self._built_at:
                telemetry.ACCOUNT.builds_ready(self._built_at.pop(seq), None)
        t_sync = ph.begin("sync") if ph is not None else time.time()
        # The account (a traced sync only): the instant each of the two
        # reads returned, and whether it had to wait. A read that waited
        # returns when the device finished what it read, so its stamp is
        # the device's clock on the host's; one that did not says only
        # that the host came late.
        acct = None if sync_ctx is None else {}
        try:
            block, first_toks = None, []
            if toks_dev is not None:
                if acct is not None:
                    acct["block_waited"] = not toks_dev.is_ready()
                block = np.asarray(toks_dev)
                if acct is not None:
                    acct["block_ready"] = time.time()
            if firsts:
                if acct is not None:
                    acct["firsts_waited"] = not all(
                        f.is_ready() for _st, f in firsts)
                first_toks = [int(f) for _st, f in firsts]
                if acct is not None:
                    acct["firsts_ready"] = time.time()
        except Exception as e:
            self._fail(owed, e)
            return sync_ctx
        # sync_ms of the pass is engine.host_sync's own interval.
        t_end = ph.begin("deliver") if ph is not None else None
        width = n * max(1, self._blocks)  # the block's columns of tokens
        moe = (self._count_moe(block, width, n)
               if self._moe_cols and block is not None else {})
        if self._eva_cols and block is not None:
            moe.update(self._count_named(_EVA_COUNTERS, block,
                                         width + self._moe_cols))
        if self._bd_cols and block is not None:
            moe.update(self._count_named(_BD_COUNTERS, block,
                                         width + self._moe_cols))
        if self._loop_cols and block is not None:
            moe.update(self._count_loop(
                block, width + self._moe_cols + self._eva_cols,
                n * len(occupants)))
        if sync_ctx is not None:
            t_end = t_end or time.time()
            _tracing.record_span_in(
                sync_ctx, "engine.host_sync", "engine", t_sync, t_end,
                {**moe, **acct,
                 **({} if seq is None else {"seq": seq, "tokens": n})})
            if seq is not None:
                # ties a first token to the chunk it was read beside
                for st, _f in firsts:
                    if st.stream._stage is not None:
                        st.stream._stage[1]["sync_seq"] = seq
            # The set-up account: the programs built in the calls whose
            # results these reads brought to the host.
            # (by blocks no first token is read: what a request's prefill
            # and hand-over built is ready with the first block that
            # stepped it)
            fresh = ([st for st in occupants if st.stream._built]
                     if self._blocks else [])
            for built, ready in [
                    (self._built_at.pop(seq, None), "block_ready")] + [
                    (st.stream._built, "firsts_ready") for st, _f in firsts
                    ] + [(st.stream._built, "block_ready") for st in fresh]:
                if built:
                    telemetry.ACCOUNT.builds_ready(built, acct.get(ready))
            for st in fresh:
                st.stream._built = None
            try:
                from ray_tpu.util import metrics as _metrics

                _metrics.LLM_HOST_SYNC_SECONDS.observe(t_end - t_sync)
            except Exception:
                pass
        for (st, _f), tok in zip(firsts, first_toks):
            self._deliver(st, [tok])
        for st in occupants:
            st.in_flight -= n
            given = block[st.slot, :width]
            if self._blocks:
                # the tokens its commits gave out, forward by forward, and
                # where the row stands after the chunk
                given = given[given >= 0]
                st.state = block[st.slot, -(self._blocks + 3):]
                st.forwards = self._forwards_left(st.state)
            self._deliver(st, given.tolist())
        return sync_ctx

    # ------------------------------------------------ expert layers' counts
    def _moe_stats(self) -> dict:
        """What /v1/stats says of the expert layers: the share held, the rows
        routed to it since start, the selections made since start beside
        those that fell on an identity expert, the held experts that got a
        row and those whose weights the steps read and, for a router with
        identity experts, how many it has and the router's width."""
        mcfg = self.model.cfg
        out = dict(experts_held=self._moe_held,
                   experts_published=mcfg.moe_experts,
                   first_expert=mcfg.first_expert,
                   moe_rows_total=self.moe_rows_total,
                   **{f"{name}_total": getattr(self, f"{name}_total")
                      for name in _PICK_COUNTERS})
        if mcfg.moe_zero_experts:
            out.update(
                zero_experts=mcfg.moe_zero_experts,
                router_outputs=mcfg.moe_experts + mcfg.moe_zero_experts)
        return out

    def _count_picks(self, *counts) -> dict:
        """A chunk's four counters of its expert layers (`models/moe.py`
        `zero_counts`, summed over expert layers and steps;
        `_PICK_COUNTERS` has their names): into the totals and the
        process's metrics, and as `engine.host_sync`'s attributes."""
        got = dict(zip(_PICK_COUNTERS, map(int, counts), strict=True))
        for name, n in got.items():
            setattr(self, f"{name}_total", getattr(self, f"{name}_total") + n)
            _count_metric(f"LLM_{name.upper()}", n)
        return got
