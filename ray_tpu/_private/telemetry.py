"""Cluster telemetry: continuous node/worker resource sampling + on-demand
in-process profiling.

Parity target: the reference's reporter plane (dashboard/modules/reporter/
reporter_agent.py streams per-node CPU/mem/GPU samples into the metrics
head; its profiling endpoints serve on-demand py-spy captures of live
workers). Here the plane rides existing seams instead of new daemons:

- sampling: armed by RT_TELEMETRY_INTERVAL_S (unset => NO sampler thread
  anywhere and heartbeat frames stay byte-identical — the PR 9/11
  zero-cost-when-off pattern). The node agent samples node CPU/mem/disk and
  per-worker RSS/CPU% from /proc on its own loop; each worker samples
  device-side series (jax `memory_stats()` HBM bytes, live compile
  count/seconds via a `jax.monitoring` listener, device-object-plane bytes
  from device_store) on a daemon thread and pushes them to its agent.
- transport: samples piggyback on the existing agent->controller heartbeats
  (`telemetry` key, batched) — no new connection or cadence, same as the
  PR 11 span drain.
- profiling: `sample_profile()` is the worker-side CPU sampling profiler
  behind `ray-tpu profile --mode cpu` — sys._current_frames() walked at
  RT_PROFILE_HZ for the capture window, rendered as collapsed stacks plus
  Chrome-trace flame events (the generalization of the per-pid SIGUSR1
  one-shot stack dump into a timed sampler).

Everything here is stdlib + /proc reads; jax and device_store are observed
through sys.modules gates so a process that never imported them never pays
(or triggers) the import.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import re
import sys
import threading
import time
from typing import Callable, Optional

from ray_tpu._private import tracing
from ray_tpu._private.rtconfig import CONFIG


def interval_s() -> float:
    """Sampling cadence; <= 0 means the telemetry plane is OFF."""
    try:
        return float(CONFIG.telemetry_interval_s)
    except (TypeError, ValueError):
        return 0.0


_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096
_CLK_TCK = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100


class CpuTracker:
    """Whole-node CPU utilization percent from /proc/stat deltas between
    successive percent() calls (first call returns 0.0 — no window yet)."""

    def __init__(self):
        self._last: Optional[tuple] = None  # (busy_jiffies, total_jiffies)

    @staticmethod
    def _read() -> Optional[tuple]:
        try:
            with open("/proc/stat") as f:
                line = f.readline()
        except OSError:
            return None
        parts = line.split()
        if not parts or parts[0] != "cpu":
            return None
        vals = [int(v) for v in parts[1:]]
        idle = vals[3] + (vals[4] if len(vals) > 4 else 0)  # idle + iowait
        total = sum(vals)
        return (total - idle, total)

    def percent(self) -> float:
        cur = self._read()
        if cur is None:
            return 0.0
        last, self._last = self._last, cur
        if last is None or cur[1] <= last[1]:
            return 0.0
        busy = cur[0] - last[0]
        total = cur[1] - last[1]
        return round(100.0 * max(0, busy) / max(1, total), 2)


class PidCpuTracker:
    """Per-pid CPU percent from /proc/<pid>/stat utime+stime deltas.
    Tracks many pids; entries for pids not seen in a sweep are pruned."""

    def __init__(self):
        self._last: dict[int, tuple] = {}  # pid -> (jiffies, monotonic)

    @staticmethod
    def _read_jiffies(pid: int) -> Optional[int]:
        try:
            with open(f"/proc/{pid}/stat") as f:
                data = f.read()
        except OSError:
            return None
        # comm may contain spaces/parens: fields start after the last ')'.
        try:
            rest = data[data.rindex(")") + 2:].split()
            return int(rest[11]) + int(rest[12])  # utime + stime
        except (ValueError, IndexError):
            return None

    def percent(self, pid: int) -> float:
        jif = self._read_jiffies(pid)
        now = time.monotonic()
        if jif is None:
            self._last.pop(pid, None)
            return 0.0
        last = self._last.get(pid)
        self._last[pid] = (jif, now)
        if last is None or now <= last[1]:
            return 0.0
        dt = now - last[1]
        return round(100.0 * max(0, jif - last[0]) / _CLK_TCK / dt, 2)

    def prune(self, live_pids) -> None:
        live = set(live_pids)
        for pid in [p for p in self._last if p not in live]:
            self._last.pop(pid, None)


def pid_rss_bytes(pid: int) -> Optional[int]:
    try:
        with open(f"/proc/{pid}/statm") as f:
            fields = f.read().split()
        return int(fields[1]) * _PAGE_SIZE
    except (OSError, ValueError, IndexError):
        return None


def mem_percent() -> float:
    """Node memory utilization percent (MemTotal vs MemAvailable)."""
    total = avail = None
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    total = int(line.split()[1])
                elif line.startswith("MemAvailable:"):
                    avail = int(line.split()[1])
                if total is not None and avail is not None:
                    break
    except OSError:
        return 0.0
    if not total or avail is None:
        return 0.0
    return round(100.0 * (1.0 - avail / total), 2)


def disk_percent(path: str) -> float:
    try:
        st = os.statvfs(path)
    except OSError:
        return 0.0
    total = st.f_blocks * st.f_frsize
    free = st.f_bavail * st.f_frsize
    if total <= 0:
        return 0.0
    return round(100.0 * (1.0 - free / total), 2)


# ------------------------------------------------------- the set-up account
# ONE account per process of what its set-up was spent on (README "Tracing
# & timeline"): the STAGES the program goes through before it can serve
# (`setup_stage`: `replica.start`, `runtime.init`, `engine.init`, ...) and
# one record per program BUILD, made from JAX's own monitoring events: the
# time-span form of its three compile events, which carry the program's
# name, and the four events of its compilation cache. `compile_stats()`,
# the sampler's `compile_count` / `compile_s` and `/v1/stats` are views of
# it. Registration is idempotent and NEVER imports jax itself (sys.modules
# gate — pool workers that stay jax-free must not pay the jax import for a
# gauge). The listeners are called at builds only; a stage is two stamps.
# With RT_TRACING=1 a stage and a build are also spans and the account is
# written to `<RT_SESSION_DIR>/setup/<pid>.json`; unset, nothing is.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}

#: Build records and stages kept (a serving process builds a few dozen
#: programs; the sums by name stay exact past the bound).
_MAX_RECORDS = 4096

_SUMMED = ("trace_s", "lower_s", "compile_s", "retrieval_s")


def program_name(fun_name: str) -> str:
    """`jit(chunk)`, as JAX 0.9 names a program to its listeners, in the
    form its module carries on the device and in a trace: `jit_chunk`."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return "jit_" + re.sub(r"\W", "_", fun_name[4:-1])
    return fun_name


class SetupAccount:
    """A process's stages and builds. Events of one build arrive on the
    thread that builds, in the order trace, lowering, backend compile (each
    when it ENDS; the cache's events inside the compile), so everything
    still open is kept per thread. A build whose compile ends on another
    thread than its lowering did (`llm/programs.py`: one thread lowers, a
    pool compiles) is carried across by whoever moves it (`hand_over`,
    `take_over`) and is still ONE record with its three parts."""

    def __init__(self):
        self._lock = threading.Lock()
        self._here = threading.local()
        self.process_start = time.time()
        self.stages: collections.deque = collections.deque(
            maxlen=_MAX_RECORDS)
        self.builds: collections.deque = collections.deque(
            maxlen=_MAX_RECORDS)
        self.by_name: dict[str, dict] = {}
        self.compile_count = 0
        self.compile_s = 0.0
        self._engine_up = False  # `engine.init` has ended: the file exists
        self._file_lock = threading.Lock()  # one writer of the file at a time

    def _thread(self):
        h = self._here
        if not hasattr(h, "traces"):
            h.traces, h.lower, h.cache = {}, None, {}
            h.stages, h.ctx, h.call = [], None, None
            h.held, h.last = False, None
        return h

    # ------------------------------------------------------------- events
    def on_time_span(self, event: str, start: float, end: float,
                     fun_name: str = "", **_kw) -> None:
        if event == _TRACE_EVENT:
            # The last trace of each name since the thread's last lowering:
            # a trace ends after the traces of what it calls, so the jit's
            # own is the last of its name. One that is never lowered (its
            # jaxpr met a compiled program) goes with the next lowering.
            self._thread().traces[fun_name] = (fun_name, start, end)
        elif event == _LOWER_EVENT:
            h = self._thread()
            trace = h.traces.get(fun_name[4:-1])  # "jit(<name>)"
            h.traces = {}  # and what the lowering itself traced
            if trace is not None and trace[2] > start:
                trace = None
            h.lower = (fun_name, start, end, trace)
        elif event == _COMPILE_EVENT:
            self._build_ended(fun_name, start, end)

    def on_event(self, event: str, **_kw) -> None:
        what = _CACHE_EVENTS.get(event)
        if what:
            self._thread().cache["cache"] = what

    def on_duration(self, event: str, duration: float, **_kw) -> None:
        key = _CACHE_SECONDS.get(event)
        if key:
            self._thread().cache[key] = float(duration)

    def _build_ended(self, fun_name: str, start: float, end: float) -> None:
        h = self._thread()
        lower, h.lower = h.lower, None
        cache, h.cache = h.cache, {}
        if lower is not None and (lower[0] != fun_name or lower[2] > start):
            lower = None  # another program's, lowered and never compiled
        trace = lower[3] if lower else None
        rec = {"fun_name": program_name(fun_name),
               "a": (trace or lower or (None, start))[1], "b": end,
               "trace_s": trace[2] - trace[1] if trace else 0.0,
               "lower_s": lower[2] - lower[1] if lower else 0.0,
               "compile_s": end - start,
               # `off`: the cache held nothing of it and was given nothing
               # (no directory, or a compile under its owner's floor)
               "cache": cache.get("cache", "off"),
               "retrieval_s": cache.get("retrieval_s", 0.0),
               "saved_s": cache.get("saved_s", 0.0),
               "stage": h.stages[-1]["n"] if h.stages else None}
        with self._lock:
            self.compile_count += 1
            self.compile_s += rec["compile_s"]
            self.builds.append(rec)
            tot = self.by_name.get(rec["fun_name"])
            if tot is None:
                tot = self.by_name[rec["fun_name"]] = {
                    "builds": 0, "hits": 0, "misses": 0,
                    **dict.fromkeys(_SUMMED, 0.0)}
            tot["builds"] += 1
            tot["hits"] += rec["cache"] == "hit"
            tot["misses"] += rec["cache"] == "miss"
            for k in _SUMMED:
                tot[k] += rec[k]
        if h.held:
            # a build handed over to this thread: whoever first calls the
            # program is its cause (`first_call`), and has not come yet
            h.held, h.last = False, rec
            if tracing.enabled():
                self.write()
            return
        if not tracing.enabled():
            return
        ctx = h.stages[-1].get("ctx") if h.stages else h.ctx
        if h.call is not None:
            rec["ctx"] = ctx  # its span waits for `builds_ready`
            h.call[1].append(rec)
        else:
            self._build_span(rec, ctx)
        self.write()

    # ------------------------------------------------------------- stages
    @contextlib.contextmanager
    def stage(self, name: str, **attrs):
        """A stage of the process's set-up: its start and end are kept
        whether tracing is on or off, and with tracing on it is a span of
        kind `setup`, a child of the stage it lies in (else of the thread's
        build context, else a root)."""
        h = self._thread()
        rec = {"n": name, "a": time.time(), "b": None,
               "p": h.stages[-1]["n"] if h.stages else None}
        if attrs:
            rec["at"] = attrs
        ctx = None
        if tracing.enabled():
            up = h.stages[-1].get("ctx") if h.stages else h.ctx
            ctx = rec["ctx"] = (up[0] if up else tracing._new_id(16),
                                tracing._new_id(8))  # what children hang on
        h.stages.append(rec)
        try:
            yield
        finally:
            h.stages.pop()
            rec["b"] = time.time()
            rec.pop("ctx", None)
            with self._lock:
                self.stages.append(rec)
            if ctx is not None:
                tracing.record_span(ctx[0], ctx[1], up[1] if up else None,
                                    name, "setup", rec["a"], rec["b"],
                                    attrs or None)
                self._engine_up = self._engine_up or name == "engine.init"
                self.write()

    def stage_seconds(self) -> dict:
        """name -> seconds of the stages that have ended (the last of a
        name, should one be run again)."""
        with self._lock:
            return {s["n"]: s["b"] - s["a"] for s in self.stages}

    # ---------------------------------------- a build that changes threads
    def hand_over(self):
        """The lowering this thread has just ended, taken from it: another
        thread compiles it (`take_over`)."""
        h = self._thread()
        lower, h.lower = h.lower, None
        return lower

    def take_over(self, lower) -> None:
        """This thread's next compile is of `lower`, handed over by the
        thread that lowered it. The record it ends (`built`) is nobody's
        yet: no span, no call, until `first_call`."""
        h = self._thread()
        h.lower, h.cache, h.held, h.last = lower, {}, True, None

    def built(self) -> Optional[dict]:
        """The record of the build `take_over` began on this thread, None
        where no listener is installed (nothing is recorded then)."""
        h = self._thread()
        rec, h.held, h.last = h.last, False, None
        return rec

    def ahead(self, rec: dict) -> None:
        """`rec`'s build ended before anyone had asked for its program."""
        with self._lock:
            rec["ahead"] = True

    def first_call(self, rec: dict) -> None:
        """This thread is the first to call the program of `rec`, a build
        that ended on another thread: the record joins the thread's open
        call as if built inside it (`call_a`, `call_s`, `ready_s`; with
        a build ahead, `call_a` lies after `b`), or, outside any call, is
        a child of the stage the thread is in."""
        h = self._thread()
        with self._lock:
            rec["stage"] = h.stages[-1]["n"] if h.stages else None
        if not tracing.enabled():
            return
        ctx = h.stages[-1].get("ctx") if h.stages else h.ctx
        if h.call is not None:
            rec["ctx"] = ctx  # its span waits for `builds_ready`
            h.call[1].append(rec)
        else:
            self._build_span(rec, ctx)
            self.write()

    # -------------------------------------------- the engine's part (traced)
    # Called only under the engine's `_tracing.enabled()` branches: what
    # JAX cannot know of a build is the call it happened in and when that
    # call's result was first on the host.
    def begin_call(self, ctx: Optional[tuple]) -> None:
        """A jitted call begins on this thread: a build inside it is a
        child of `ctx`, a wire (trace_id, span_id), and so is every later
        build of the thread until the next call."""
        self.close_call()
        h = self._thread()
        h.ctx, h.call = ctx, (time.time(), [])

    def close_call(self) -> None:
        """End the thread's open call, if any, whose result nobody reads
        (the hand-over program's: its builds get no `ready_s`)."""
        built = self.end_call()
        if built:
            self.builds_ready(built, None)

    def end_call(self, **attrs) -> Optional[list]:
        """The call has returned. The builds inside it, each with `call_a`
        (the call's start on the wall clock: builds of one call share it),
        `call_s` and `attrs`; None where it built nothing. They go to
        `builds_ready` once the call's result is on the host."""
        h = self._thread()
        call, h.call = h.call, None
        if not call or not call[1]:
            return None
        now = time.time()
        with self._lock:  # `write` reads the records on other threads
            for rec in call[1]:
                rec.update(attrs, call_a=call[0], call_s=now - call[0])
        return call[1]

    def builds_ready(self, built: list, ready: Optional[float]) -> None:
        """The result of the call that built `built` was on the host at
        wall time `ready` (None: it is never read): their spans are
        recorded and the file rewritten."""
        for rec in built:
            with self._lock:
                if ready is not None:
                    rec["ready_s"] = ready - rec["call_a"]
                ctx = rec.pop("ctx", None)
            self._build_span(rec, ctx)
        self.write()

    @staticmethod
    def _build_span(rec: dict, ctx: Optional[tuple]) -> None:
        tracing.record_span_in(
            ctx, "program.build", "engine", rec["a"], rec["b"],
            {k: v for k, v in rec.items() if k not in ("a", "b", "ctx")})

    # ------------------------------------------------------------- views
    def summary(self) -> dict:
        """For /v1/stats `setup`: the stages' seconds, and per program name
        the builds, the cache's hits and misses and the summed parts."""
        with self._lock:
            programs = {name: {k: round(v, 4) if isinstance(v, float) else v
                               for k, v in tot.items()}
                        for name, tot in self.by_name.items()}
        return {"stages": {k: round(v, 3)
                           for k, v in self.stage_seconds().items()},
                "builds": sum(p["builds"] for p in programs.values()),
                "programs": programs}

    def one_line(self) -> str:
        """builds, hits, misses and the four sums, for a builder's eye."""
        with self._lock:
            tots = list(self.by_name.values())
        return (f"{sum(t['builds'] for t in tots)} builds "
                f"({sum(t['hits'] for t in tots)} cache hits, "
                f"{sum(t['misses'] for t in tots)} misses): " + ", ".join(
                    f"{k} {sum(t[k] for t in tots):.2f}" for k in _SUMMED))

    def write(self) -> None:
        """The account as a file, for readers outside the process (tracing
        on only; not before `engine.init` has ended: a process that serves
        no engine writes none)."""
        if not self._engine_up:
            return
        d = os.path.join(CONFIG.session_dir, "setup")
        with self._file_lock:  # the lane and the scheduler both build
            with self._lock:
                doc = {"pid": os.getpid(),
                       "process_start": self.process_start,
                       "written": time.time(),
                       "compile_count": self.compile_count,
                       "compile_s": self.compile_s,
                       "stages": list(self.stages),
                       "builds": [{k: v for k, v in b.items() if k != "ctx"}
                                  for b in self.builds]}
            try:
                os.makedirs(d, exist_ok=True)
                tmp = os.path.join(d, f".{doc['pid']}.tmp")
                with open(tmp, "w") as f:
                    json.dump(doc, f)
                os.replace(tmp, os.path.join(d, f"{doc['pid']}.json"))
            except OSError:
                pass  # a full or read-only disk must not fail a build


ACCOUNT = SetupAccount()
setup_stage = ACCOUNT.stage
_listeners_installed = False
_install_lock = threading.Lock()


def ensure_compile_listener() -> bool:
    """Register the account's listeners iff jax is ALREADY imported.
    Returns True once installed. Builds that happened before are not
    counted (the listeners cannot observe the past). ONCE, whoever asks:
    the sampler's thread asks on every tick and a server's constructor
    asks too, and a listener registered twice counts every build twice
    (PERF.md section 6, PR 56). A jax still being imported by another
    thread has no `monitoring` yet: not installed, and nobody waits for it."""
    global _listeners_installed
    if _listeners_installed:
        return True
    monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
    if monitoring is None:
        return False
    with _install_lock:
        if _listeners_installed:
            return True
        try:
            monitoring.register_event_time_span_listener(
                ACCOUNT.on_time_span)
            monitoring.register_event_listener(ACCOUNT.on_event)
            monitoring.register_event_duration_secs_listener(
                ACCOUNT.on_duration)
        except Exception:
            return False
        _listeners_installed = True
    return True


def compile_stats() -> dict:
    """Count and summed seconds of the backend-compile events so far."""
    with ACCOUNT._lock:
        return {"count": ACCOUNT.compile_count,
                "seconds": ACCOUNT.compile_s}


# ------------------------------------------------------- worker-side sampler
class WorkerSampler:
    """Daemon thread inside a worker process sampling device-side series and
    pushing them to the node agent (worker_telemetry). Started by
    worker_proc ONLY when RT_TELEMETRY_INTERVAL_S is set — with the plane
    off this class is never instantiated (no thread, pinned by test)."""

    THREAD_NAME = "rt-telemetry"

    def __init__(self, push: Callable[[dict], None], interval: float):
        self._push = push
        self._interval = max(0.05, interval)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name=self.THREAD_NAME)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def _loop(self) -> None:
        while not self._stop.wait(self._interval):
            try:
                series = self.sample()
            except Exception:
                continue  # a bad sample tick must never kill the thread
            if series:
                try:
                    self._push(series)
                except Exception:
                    pass  # agent away; next tick retries

    @staticmethod
    def sample() -> dict:
        """One device-side sample. Every source is sys.modules-gated: a
        worker that never touched jax or the device plane reports nothing
        for those series (and never triggers their import)."""
        out: dict = {}
        if ensure_compile_listener():
            st = compile_stats()
            out["compile_count"] = st["count"]
            out["compile_s"] = round(st["seconds"], 4)
        jax = sys.modules.get("jax")
        # Gate on the backend being ALREADY initialized, not merely jax
        # being imported: local_devices() on a cold backend would trigger
        # full runtime init from the sampler thread — on TPU hosts that
        # acquires the chips (exclusive!) for a worker that may never
        # compute on them, and blocks the tick for seconds.
        xb = sys.modules.get("jax._src.xla_bridge")
        if jax is not None and xb is not None \
                and xb.backends_are_initialized():
            used = peak = 0
            have = False
            try:
                for d in jax.local_devices():
                    ms = d.memory_stats()
                    if not ms:
                        continue  # CPU backends report no memory stats
                    have = True
                    used += int(ms.get("bytes_in_use") or 0)
                    peak += int(ms.get("peak_bytes_in_use")
                                or ms.get("bytes_in_use") or 0)
            except Exception:
                have = False
            if have:
                out["hbm_used"] = used
                out["hbm_peak"] = peak
        ds = sys.modules.get("ray_tpu._private.device_store")
        if ds is not None:
            try:
                st = ds.table_stats()
                out["device_bytes"] = int(st.get("bytes") or 0)
            except Exception:
                pass
        eng = sys.modules.get("ray_tpu.llm.engine")
        if eng is not None:
            # Live decode throughput (README "Serving hot loop"): tokens
            # delivered to stream consumers since the previous tick. Only
            # workers that actually host a continuous engine ever import
            # the module, so everyone else skips the series entirely.
            try:
                out["llm.tokens_per_s"] = round(
                    eng.tokens_per_s_snapshot(), 2)
            except Exception:
                pass
        xch = sys.modules.get("ray_tpu.data._internal.exchange")
        if xch is not None:
            # Exchange pressure (README "Data plane"): blocks in flight,
            # bytes spilled through the storage plane, and submit-loop
            # backpressure stalls. The module only loads in processes that
            # drive or execute an exchange.
            try:
                st = xch.exchange_stats()
                out["data.blocks_inflight"] = st["blocks_inflight"]
                out["data.spilled_bytes"] = st["spilled_bytes"]
                out["data.bp_stalls"] = st["bp_stalls"]
            except Exception:
                pass
        pp = sys.modules.get("ray_tpu.llm.pipeline")
        if pp is not None:
            # Pipeline-stage occupancy (README "Pipeline-parallel
            # serving"): busy fraction of this process's stage(s) since
            # the previous tick — the bubble is its complement. Only
            # processes hosting a PipelineStage import the module.
            try:
                occ = pp.occupancy_snapshot("telemetry")
                if occ:
                    out["llm.pp_occupancy"] = round(max(occ.values()), 3)
            except Exception:
                pass
        return out


# --------------------------------------------------- CPU sampling profiler
#: Raw stack snapshots kept per capture (~KBs each across a worker's
#: threads): bounds capture RSS at tens of MB worst case.
_MAX_PROFILE_SAMPLES = 20_000


def clamp_profile_seconds(seconds) -> float:
    """One capture-window clamp shared by every hop of the profile path
    (controller -> agent -> worker): 0.05s floor, 300s cap, 5s default.
    The hops' RPC timeout margins (+110s controller, +100s agent) are tuned
    against these constants — change them here, nowhere else. The margins
    are what a `jax` capture needs after its window: on the v5e a serving
    replica takes 25-30 s to stop a one-second trace, zip it (27 MB) and
    hand it on, and one capture in ten went over the 30 s it used to be
    given (PERF.md section 6, PR 27). A worker that dies fails the call at
    once through its closed connection, whatever the margin."""
    try:
        seconds = float(seconds)
    except (TypeError, ValueError):
        seconds = 5.0  # unset/garbage -> default; explicit 0 clamps to floor
    return min(300.0, max(0.05, seconds))


def sample_profile(seconds: float, hz: Optional[int] = None,
                   exclude_thread: Optional[int] = None) -> dict:
    """In-process CPU sampling profile over ALL of this process's threads:
    sys._current_frames() walked at `hz` for `seconds`, folded into
    collapsed stacks (root;...;leaf -> sample count, the flamegraph input)
    and reconstructed into Chrome-trace flame events (one lane per thread;
    consecutive samples sharing a frame prefix merge into one "X" event).
    `exclude_thread` drops the sampler's own lane. Runs on a caller-owned
    thread — the capture loop sleeps between samples."""
    if hz is None:
        try:
            hz = int(CONFIG.profile_hz)
        except (TypeError, ValueError):
            hz = 100
    hz = max(1, min(1000, int(hz)))
    seconds = max(0.05, float(seconds))
    period = 1.0 / hz
    me = threading.get_ident()
    names = {t.ident: t.name for t in threading.enumerate()}
    samples: list[tuple[float, dict]] = []  # (t_rel, tid -> stack tuple)
    t0 = time.monotonic()
    deadline = t0 + seconds
    while True:
        now = time.monotonic()
        if now >= deadline or len(samples) >= _MAX_PROFILE_SAMPLES:
            # The raw-snapshot buffer is bounded: profiling must never
            # OOM the live worker it is observing (an extreme
            # seconds x hz request ends early with what it has; the
            # returned `seconds` reflects the actual window).
            break
        frames = sys._current_frames()
        snap: dict[int, tuple] = {}
        for tid, frame in frames.items():
            if tid == me or tid == exclude_thread:
                continue
            stack = []
            f = frame
            depth = 0
            while f is not None and depth < 128:
                code = f.f_code
                stack.append(f"{code.co_name} "
                             f"({os.path.basename(code.co_filename)}:"
                             f"{f.f_lineno})")
                f = f.f_back
                depth += 1
            snap[tid] = tuple(reversed(stack))  # root -> leaf
        samples.append((now - t0, snap))
        time.sleep(max(0.0, period - (time.monotonic() - now)))
    duration = time.monotonic() - t0

    collapsed: dict[str, int] = {}
    for _, snap in samples:
        for stack in snap.values():
            key = ";".join(stack)
            collapsed[key] = collapsed.get(key, 0) + 1
    events = _flame_events(samples, names, period)
    return {
        "mode": "cpu",
        "pid": os.getpid(),
        "hz": hz,
        "seconds": round(duration, 3),
        "samples": len(samples),
        "threads": sorted({tid for _, s in samples for tid in s}),
        "collapsed": collapsed,
        "traceEvents": events,
    }


def _flame_events(samples: list, names: dict, period: float) -> list[dict]:
    """Merge per-thread sample stacks into Chrome-trace complete events: at
    each depth, a run of consecutive samples sharing the same frame (and
    the same ancestry) becomes one "X" event. Timestamps are relative
    microseconds; lanes (tid) are OS thread ids with name metadata."""
    by_tid: dict[int, list[tuple[float, tuple]]] = {}
    for t, snap in samples:
        for tid, stack in snap.items():
            by_tid.setdefault(tid, []).append((t, stack))
    events: list[dict] = []
    lane = 0
    for tid, rows in by_tid.items():
        lane += 1
        events.append({"ph": "M", "name": "thread_name", "pid": 1,
                       "tid": lane,
                       "args": {"name": f"{names.get(tid) or tid}"}})
        open_ev: list[dict] = []  # stack of open events, one per depth
        prev: tuple = ()
        for i, (t, stack) in enumerate(rows):
            # Close events where the frame (or an ancestor) changed.
            common = 0
            while (common < len(prev) and common < len(stack)
                   and prev[common] == stack[common]):
                common += 1
            end_us = t * 1e6
            while len(open_ev) > common:
                ev = open_ev.pop()
                ev["dur"] = max(1.0, end_us - ev["ts"])
            for d in range(common, len(stack)):
                ev = {"ph": "X", "name": stack[d], "cat": "sample",
                      "pid": 1, "tid": lane, "ts": t * 1e6, "dur": 1.0}
                events.append(ev)
                open_ev.append(ev)
            prev = stack
        tail = (rows[-1][0] + period) * 1e6 if rows else 0.0
        while open_ev:
            ev = open_ev.pop()
            ev["dur"] = max(1.0, tail - ev["ts"])
    return events


def jax_profile(seconds: float) -> dict:
    """Capture a jax.profiler trace window (XLA/TPU device timeline) and
    return it as a zip archive blob. Requires jax in the worker; the
    caller surfaces failures as attributed errors."""
    import io
    import shutil
    import tempfile
    import zipfile

    import jax

    seconds = max(0.05, float(seconds))
    d = tempfile.mkdtemp(prefix="rt-jaxprof-")
    # The Python tracer stays off: with it a second of trace stalled the
    # traced process's threads for half a minute (PERF.md). The host tracer
    # stays on: the engine's `TraceAnnotation`s are its events.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(d, profiler_options=options)
        time.sleep(seconds)
        jax.profiler.stop_trace()
        buf = io.BytesIO()
        nfiles = 0
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
            for root, _, files in os.walk(d):
                for name in files:
                    p = os.path.join(root, name)
                    z.write(p, os.path.relpath(p, d))
                    nfiles += 1
        return {"mode": "jax", "pid": os.getpid(),
                "seconds": round(seconds, 3), "files": nfiles,
                "archive": buf.getvalue()}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def default_profile_dir(session_id: str) -> str:
    d = CONFIG.profile_dir
    if d:
        return d
    return os.path.join(CONFIG.session_dir, session_id, "profiles")
