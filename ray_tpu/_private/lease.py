"""Owner-side worker leases: the direct task submission path.

Parity target: the reference NormalTaskSubmitter + lease pools
(core_worker/transport/normal_task_submitter.h:79 — RequestWorkerLease at
normal_task_submitter.cc:296, direct worker-to-worker PushNormalTask at
:186, lease reuse keyed by SchedulingKey). The owner leases workers from the
controller once per scheduling class, then streams task specs DIRECTLY to
the leased workers over coalescing connections; results come back on the
same connection. The controller is out of the per-task hot path entirely —
it only accounts lease resources and brokers worker acquisition.

Failure model (owner-based, like the reference TaskManager): a dead leased
worker fails its in-flight specs back into the class queue (attempt++ up to
max_retries), a `lease_invalid` push from the controller does the same, and
`need_resources` returns idle leases so other demand can place. Specs that
fail over to the controller path need the dead lease's resources to run
there, so their class asks for no new lease until they have resolved
(`_hold_for_failover`).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from collections import deque
from typing import Optional

from ray_tpu._private import rpc
from ray_tpu._private import tracing as _tracing
from ray_tpu._private.rtconfig import CONFIG
from ray_tpu._private.serialization import dumps_oob
from ray_tpu._private.task_spec import STREAMING, TaskSpec

logger = logging.getLogger(__name__)

# In-flight pipeline depth per leased worker. Tasks beyond the depth wait in
# the class queue; the worker executes its pipeline serially in order.
# 16 (up from 8): at direct-dispatch rates the pump/flush round trip per
# burst is the dominant bubble — measured 9.6k -> 14.1k tasks/s on a
# single saturated lease; still shallow enough that a slow task's
# head-of-line collateral stays bounded. Lease-count ceiling and
# idle-return window live in rtconfig (RT_LEASE_BATCH / RT_LEASE_IDLE_S).
DEPTH = 16
REQUEST_RETRY_S = 0.1
# After the controller answers a scale-up request short, the class stops
# asking for more than it got for this long (a fully-subscribed cluster
# must not be begged at submit rate — the parked requests would fire
# need_resources and steal momentarily-idle leases from their owners).
CAP_PROBE_S = 0.25
# Per-lease assignment depth while the lease set can still GROW: deep
# pipelining must not let the first granted lease swallow a whole small
# batch before its siblings exist (12 slow tasks would all serialize on
# one worker while a second node sits idle). Once the class holds the
# cluster's proven capacity, the full DEPTH applies.
RAMP_DEPTH = 4
# How long severed specs wait at the owner before they are submitted on the
# controller path. A severed worker that is alive reports the spec it is
# still running to its node agent (`ltask_running`), whose dedup parks the
# failover re-dispatch of that id; nothing orders that report before the
# re-dispatch, which takes two hops more. While the owner leased the
# severed worker's resources again at once, the re-dispatch waited for them
# long enough; now that it does not (_hold_for_failover) this is the margin
# (one sever test in 40 ran a spec twice under load without it, none with).
FAILOVER_GRACE_S = 0.25

_metrics_mod = None


def _record_dispatch(path: str, n: int = 1):
    """Count a task submission route ('direct' vs 'controller') — lazy
    import keeps the module graph acyclic (util.metrics reaches back into
    worker for its flusher)."""
    global _metrics_mod
    if _metrics_mod is None:
        from ray_tpu.util import metrics as _m

        _metrics_mod = _m
    _metrics_mod.record_task_dispatch(path, n)


def _class_key(spec: TaskSpec) -> tuple:
    s = spec.strategy
    return (tuple(sorted(spec.resources.items())), s.kind, s.node_id, s.soft,
            s.pg_id, s.pg_bundle_index)


class _Lease:
    __slots__ = ("lease_id", "worker_id", "node_id", "addr", "conn", "inflight",
                 "buf", "flushing", "dead", "idle_since", "cls", "kill_target",
                 "fail_cause", "incarnation")

    def __init__(self, cls, lease_id: str, worker_id: str, node_id: str,
                 addr: tuple, incarnation: int | None = None):
        self.cls = cls
        self.lease_id = lease_id
        self.worker_id = worker_id
        self.node_id = node_id
        self.addr = addr
        # Node incarnation the grant was minted against: echoed in
        # reasserts so a restarted controller can fence leases from a
        # node's previous life.
        self.incarnation = incarnation
        self.conn: Optional[rpc.Connection] = None
        self.inflight: dict[str, TaskSpec] = {}
        self.buf: list[TaskSpec] = []
        self.flushing = False
        self.dead = False
        self.idle_since = time.monotonic()
        self.fail_cause: Optional[str] = None  # e.g. "oom" from the monitor
        # task_id being force-cancelled via worker kill; while set, the lease
        # takes no new work and _lease_failed requeues innocent bystanders
        # without burning an attempt.
        self.kill_target: Optional[str] = None


class _Class:
    __slots__ = ("key", "resources", "strategy", "queue", "leases", "requesting",
                 "depth", "cap", "cap_ts", "proven_cap", "failover")

    def __init__(self, key: tuple, spec: TaskSpec):
        self.key = key
        self.resources = dict(spec.resources)
        self.strategy = spec.strategy
        self.queue: deque[TaskSpec] = deque()
        self.leases: dict[str, _Lease] = {}
        self.requesting = False
        # Grant back-off: a short grant sets cap = what the cluster proved
        # it can give; requests stay under it until the probe window
        # passes (see CAP_PROBE_S).
        self.cap: int | None = None
        self.cap_ts = 0.0
        # Persistent capacity watermark driving the RAMP_DEPTH->DEPTH
        # switch. Unlike `cap` it survives the periodic probes (a probe
        # answered short re-proves it; only a grant that actually GROWS
        # the set clears it), so steady-state pipelining never dips.
        self.proven_cap: int | None = None
        # Specs of this class failed over to the controller path and not
        # yet resolved; while any are, the class requests no new lease
        # (_hold_for_failover).
        self.failover = 0
        # SPREAD must place per task across nodes (reference spread policy),
        # so no pipelining: each task forces its own lease while the queue
        # is non-empty.
        self.depth = 1 if spec.strategy.kind == "SPREAD" else DEPTH


class LeaseManager:
    """One per Worker process (drivers and executing workers alike)."""

    def __init__(self, worker):
        self.w = worker  # ray_tpu._private.worker.Worker
        self.classes: dict[tuple, _Class] = {}
        self._by_conn: dict = {}  # conn -> _Lease
        self._by_id: dict[str, _Lease] = {}
        self._lock = threading.Lock()
        self._pump_scheduled = False
        self._cancelled: dict[str, bool] = {}  # task_id -> force
        self._idle_task = None
        # worker_id -> (conn, expires): connections of returned leases kept
        # warm — the controller pools returned workers for lease_idle_s, so
        # a regrant usually names a worker we already verified, skipping
        # the TCP connect + whoami round trips of the handoff hot path.
        self._conn_cache: dict[str, tuple] = {}
        self._shutdown = False

    # ------------------------------------------------------------- submit
    def submit(self, spec: TaskSpec):
        """Called from any thread. Refs/resolutions already registered by
        Worker.submit_task."""
        _record_dispatch("direct")
        key = _class_key(spec)
        with self._lock:
            cls = self.classes.get(key)
            if cls is None:
                cls = self.classes[key] = _Class(key, spec)
            cls.queue.append(spec)
            need = not self._pump_scheduled
            self._pump_scheduled = True
        if need:
            self.w.io.spawn(self._a_pump_all())

    # All methods below run on the worker's IO loop.
    async def _a_pump_all(self):
        with self._lock:
            self._pump_scheduled = False
        for cls in list(self.classes.values()):
            self._pump(cls)
        if self._idle_task is None and not self._shutdown:
            self._idle_task = asyncio.ensure_future(self._a_idle_loop())

    def _pump(self, cls: _Class):
        # Assign queued specs to the least-loaded live leases (skip leases
        # whose worker is being force-kill-cancelled: it is already doomed).
        # Specs are handed out in per-lease batches (ONE lock acquisition +
        # ONE flush kick per round): a burst of N submissions costs
        # O(leases) lock/min() rounds, not O(N). Each round takes at most
        # ceil(queue/live) specs so a burst smaller than depth*leases still
        # SPREADS across the live leases instead of convoying on one.
        live = [l for l in cls.leases.values()
                if not l.dead and l.kill_target is None]
        if cls.depth == 1:  # SPREAD: per-task placement, no pipelining
            eff_depth = 1
        elif cls.proven_cap is not None and len(live) >= cls.proven_cap:
            eff_depth = cls.depth
        else:
            # Lease set may still grow: stay shallow so a small batch
            # leaves queue for the leases about to be granted.
            eff_depth = RAMP_DEPTH
        while cls.queue and live:
            lease = min(live, key=lambda l: len(l.inflight))
            room = eff_depth - len(lease.inflight)
            if room <= 0:
                break
            batch = []
            with self._lock:
                qlen = len(cls.queue)
                take = min(room, -(-qlen // len(live)))
                for _ in range(min(take, qlen)):
                    batch.append(cls.queue.popleft())
            if not batch:
                break
            assigned = False
            for spec in batch:
                if self._consume_cancel_queued(spec):
                    continue
                lease.inflight[spec.task_id] = spec
                lease.buf.append(spec)
                assigned = True
            if assigned and not lease.flushing:
                lease.flushing = True
                asyncio.ensure_future(self._a_flush(lease))
        if cls.queue and not cls.requesting and not cls.failover:
            outstanding = len(cls.queue) + sum(len(l.inflight) for l in live)
            want = min(max(1, CONFIG.lease_batch), outstanding)
            if cls.cap is not None:
                if time.monotonic() - cls.cap_ts >= CAP_PROBE_S:
                    cls.cap = None  # probe again: capacity may have freed
                else:
                    want = min(want, cls.cap)
            need = want - len(cls.leases)
            # Slow-start (ask at most double the current holding): under
            # multi-client contention the first requester must not vacuum
            # the whole pool and leave its peers starving — redistribution
            # afterwards costs rounds of need_resources churn. A lone
            # client still reaches lease_batch in a handful of cheap
            # doubling grants.
            need = min(need, max(1, len(cls.leases)))
            if need > 0:
                cls.requesting = True
                asyncio.ensure_future(self._a_request(cls, need))

    def _consume_cancel_queued(self, spec: TaskSpec) -> bool:
        force = self._cancelled.pop(spec.task_id, None)
        if force is None:
            return False
        self._fail_spec(spec, {"type": "TaskCancelledError",
                               "message": f"task {spec.name} cancelled"})
        return True

    async def _a_request(self, cls: _Class, count: int):
        have = sum(1 for l in cls.leases.values() if not l.dead)
        try:
            rep = await self.w.controller.call(
                "lease_workers", resources=cls.resources, strategy=cls.strategy,
                count=count, have=have, owner_id=self.w.worker_id)
        except Exception:
            rep = {"leases": []}
        finally:
            cls.requesting = False
        if len(rep["leases"]) < count:
            # The cluster gave less than asked: remember the proven level
            # and stop begging until the probe window passes.
            cls.cap = max(1, len(cls.leases) + len(rep["leases"]))
            cls.cap_ts = time.monotonic()
            cls.proven_cap = cls.cap
        else:
            cls.cap = None
            if rep["leases"]:
                # The set actually grew to (or past) what was asked:
                # capacity is unknown again — ramp shallow until the next
                # short answer re-proves the ceiling.
                cls.proven_cap = None
        if cls.failover and rep["leases"]:
            # Asked before a lease was severed, granted after: the
            # resources are the failed-over specs' (_hold_for_failover).
            await self._a_return([g["lease_id"] for g in rep["leases"]])
            return
        for g in rep["leases"]:
            lease = _Lease(cls, g["lease_id"], g["worker_id"], g["node_id"],
                           tuple(g["address"]), g.get("incarnation"))
            cls.leases[lease.lease_id] = lease
            self._by_id[lease.lease_id] = lease
            asyncio.ensure_future(self._a_connect(lease))
        if not rep["leases"] and cls.queue and not any(
                not l.dead for l in cls.leases.values()):
            # Nothing placeable right now: poll until resources free up
            # (node death recovery, infeasible-demand waiting).
            await asyncio.sleep(REQUEST_RETRY_S)
            if not self._shutdown:
                self._pump(cls)

    async def _a_connect(self, lease: _Lease):
        cached = self._conn_cache.pop(lease.worker_id, None)
        if cached is not None and not cached[0].closed:
            # Warm-pool regrant of a worker we already talked to: the
            # connection's identity was verified when first established and
            # a connection to a dead worker closes, so reuse it as-is — no
            # TCP connect, no whoami round trip.
            conn = cached[0]
        else:
            try:
                conn = await rpc.connect(
                    *lease.addr, on_push=self._on_worker_push,
                    on_close=self._on_worker_conn_close, timeout=10,
                    label="lease")
                rep = await conn.call("whoami", _timeout=10)
                if rep.get("worker_id") != lease.worker_id:
                    await conn.close()
                    raise ConnectionError("stale lease address (port reused)")
            except Exception as e:
                logger.warning("lease %s connect failed: %s",
                               lease.lease_id[:8], e)
                self._lease_failed(lease)
                return
        lease.conn = conn
        self._by_conn[conn] = lease
        if lease.dead:  # invalidated while connecting
            self._park_conn(lease)
            return
        self._pump(lease.cls)
        if lease.buf and not lease.flushing:
            lease.flushing = True
            asyncio.ensure_future(self._a_flush(lease))

    def _park_conn(self, lease: _Lease):
        """Detach and cache a (healthy) lease connection for reuse by a
        later grant of the same worker; close it when the cache is full."""
        conn = lease.conn
        lease.conn = None
        if conn is None:
            return
        self._by_conn.pop(conn, None)
        if conn.closed:
            return
        if len(self._conn_cache) >= 32:
            asyncio.ensure_future(conn.close())
            return
        self._conn_cache[lease.worker_id] = (
            conn, time.monotonic() + CONFIG.lease_idle_s + 2.0)

    async def _a_flush(self, lease: _Lease):
        while True:
            if lease.conn is None:
                lease.flushing = False
                return  # _a_connect flushes once connected
            batch = lease.buf
            lease.buf = []
            if not batch:
                lease.flushing = False
                return
            try:
                # Compact wire form (see TaskSpec.task_call_tuple): the
                # frame-constant owner + class resources ride once; per-spec
                # fields go as tuples instead of full 24-field spec pickles.
                await lease.conn.push(
                    "exec_tasks",
                    common=(self.w.worker_id, self.w.server_addr,
                            lease.cls.resources),
                    calls=[s.task_call_tuple() for s in batch])
                for s in batch:
                    if s.trace is not None:
                        _tracing.record_instant(
                            s.trace, "dispatch", "dispatch",
                            {"task": s.task_id,
                             "worker": lease.worker_id[:12]})
            except Exception:
                lease.flushing = False
                self._lease_failed(lease)
                return

    # ----------------------------------------------------------- results
    async def _on_worker_push(self, conn, method, a):
        if method == "gen_items":
            # Needs no lease binding: trailing stream items may arrive on a
            # connection that was parked in the cache after its lease
            # retired (the old path closed the conn and lost them anyway).
            self.w._on_gen_items(conn, a["items"])
            return
        lease = self._by_conn.get(conn)
        if lease is None:
            return
        if method == "tasks_done":
            for item in a["done"]:
                self._task_done(lease, item)
            lease.idle_since = time.monotonic()
            self._pump(lease.cls)

    def _task_done(self, lease: _Lease, item: tuple):
        # item: (task_id, attempt, results, error, retryable, exec_failure)
        tid, _attempt, results, error, retryable, _ef = item  # rtcheck: wire=tasks_done.item
        spec = lease.inflight.pop(tid, None)
        if spec is None:
            self._cancelled.pop(tid, None)
            return
        self._cancelled.pop(tid, None)
        if (error is not None and retryable
                and spec.attempt < spec.max_retries):
            spec.attempt += 1
            with self._lock:
                lease.cls.queue.appendleft(spec)
            return
        if spec.trace is not None:
            _tracing.record_instant(spec.trace, "result", "result",
                                    {"task": tid, "ok": error is None})
        for oid, inline, size, holder in results or ():
            res = self.w._resolutions.get(oid)
            if res is not None:
                res.resolve(inline, [tuple(holder)] if holder else [], error)
        if lease.cls.strategy.kind == "SPREAD" and not lease.inflight:
            # SPREAD is a PER-TASK placement decision (reference spread
            # policy): return the lease after its task so the controller
            # places the next one fresh — reusing it would funnel a burst
            # through whichever node connected first.
            self._retire_lease(lease)

    def _retire_lease(self, lease: _Lease):
        if lease.dead:
            return
        lease.dead = True
        lease.cls.leases.pop(lease.lease_id, None)
        self._by_id.pop(lease.lease_id, None)
        self._park_conn(lease)
        asyncio.ensure_future(self._a_return([lease.lease_id]))

    def _fail_spec(self, spec: TaskSpec, blob: dict):
        h, bufs = dumps_oob(blob)
        err = [h, *bufs]
        for oid in spec.return_object_ids():
            res = self.w._resolutions.get(oid)
            if res is not None:
                res.resolve(None, [], err)

    # ----------------------------------------------------------- failure
    def _on_worker_conn_close(self, conn):
        lease = self._by_conn.pop(conn, None)
        for wid, (c, _exp) in list(self._conn_cache.items()):
            if c is conn:
                self._conn_cache.pop(wid, None)
        if not self._shutdown:
            self.w._gen_conn_lost(conn)
        if lease is not None and not self._shutdown:
            self._lease_failed(lease)

    def _lease_failed(self, lease: _Lease):
        """Worker/connection died; drop the lease and re-route its specs.

        Transport sever (no known cause — the worker may well be alive and
        still executing its pipeline): SENT specs fail over to the classic
        CONTROLLER path without burning an attempt. At-most-once holds
        because the worker skips the unstarted specs of a dead holder
        connection and reports the one that WAS executing to its node
        agent, whose task-id dedup parks/absorbs the failover re-dispatch.
        (A worker that really died mid-task leaves no record, so the
        failover re-executes it — the same at-least-once window every
        retry has.)

        Known worker death (lease_invalid / OOM / force-kill) keeps the
        original owner-side retry semantics.

        The lease id is ALWAYS returned to the controller: for a
        severed-but-alive worker that's what frees (and warm-pools) the
        slot — the old keep-the-lease behavior leaked it until the owner
        process exited; for a dead worker the return races the agent's
        worker_died report and loses harmlessly."""
        if lease.dead:
            return
        lease.dead = True
        lease.cls.leases.pop(lease.lease_id, None)
        self._by_id.pop(lease.lease_id, None)
        if lease.conn is not None:
            self._by_conn.pop(lease.conn, None)
        requeue = []
        failover = []
        # Specs still in lease.buf provably never reached the worker; of the
        # rest, worker exec order == arrival order and _task_done pops
        # completions, so the OLDEST remaining SENT spec is the one that may
        # have been executing when the worker died; everything younger never
        # started.
        unsent = {s.task_id for s in lease.buf}
        executing_candidate = next(
            (tid for tid in lease.inflight if tid not in unsent), None)
        sever = (lease.fail_cause is None and lease.kill_target is None
                 and CONFIG.direct_dispatch)
        for spec in lease.inflight.values():
            force = self._cancelled.pop(spec.task_id, None)
            if force is not None:
                self._fail_spec(spec, {
                    "type": "WorkerCrashedError" if force else "TaskCancelledError",
                    "message": f"task {spec.name} cancelled"})
            elif spec.task_id in unsent:
                # Never sent: requeue without burning an attempt, whatever
                # killed the worker.
                requeue.append(spec)
            elif sever and spec.num_returns != STREAMING:
                # Sent to a worker we can no longer talk to: controller
                # failover (streaming specs stay on the lease path — the
                # controller transport has no item stream).
                failover.append(spec)
            elif (lease.kill_target is not None
                  and spec.task_id != executing_candidate):
                # The worker was killed to force-cancel ONE task; this spec is
                # an unstarted bystander pipelined behind it (a reference
                # leased worker runs one task at a time, so it has no such
                # collateral). Requeue WITHOUT burning a retry attempt. The
                # executing candidate deliberately falls through to normal
                # retry semantics: re-running a possibly-started task for
                # free could duplicate side effects of a max_retries=0 task.
                requeue.append(spec)
            elif spec.attempt < spec.max_retries:
                spec.attempt += 1
                requeue.append(spec)
            elif lease.fail_cause == "oom":
                self._fail_spec(spec, {
                    "type": "OutOfMemoryError",
                    "message": f"leased worker {lease.worker_id[:8]} was "
                               f"killed by the node memory monitor"})
            elif lease.fail_cause == "stall":
                self._fail_spec(spec, {
                    "type": "WorkerCrashedError",
                    "message": f"leased worker {lease.worker_id[:8]} was "
                               f"killed by the stall watchdog (no progress "
                               f"past RT_STALL_KILL_S; see "
                               f"util.state.list_stalls())"})
            else:
                self._fail_spec(spec, {
                    "type": "WorkerCrashedError",
                    "message": f"leased worker {lease.worker_id[:8]} died"})
        lease.inflight.clear()
        if requeue:
            with self._lock:
                for spec in reversed(requeue):
                    lease.cls.queue.appendleft(spec)
        asyncio.ensure_future(self._a_return([lease.lease_id]))
        if failover:
            logger.warning(
                "lease %s severed: failing %d in-flight spec(s) over to the "
                "controller path", lease.lease_id[:8], len(failover))
            # Owner-side event: when the direct connection drops BEFORE the
            # controller hears of the worker's death, the owner is the only
            # process that knows a failover happened (the controller may
            # see only a routine lease return).
            from ray_tpu._private import events as _events

            _events.emit_event(
                "lease_failover",
                f"lease {lease.lease_id[:8]} severed: {len(failover)} "
                f"in-flight spec(s) fail over to the controller path",
                entity=(lease.lease_id, lease.worker_id),
                attrs={"path": "owner_sever", "specs": len(failover)})
            self._hold_for_failover(lease.cls, failover)
            asyncio.ensure_future(self._a_submit_failover(failover))
        if lease.cls.queue:
            self._pump(lease.cls)

    async def _a_submit_failover(self, specs: list):
        await asyncio.sleep(FAILOVER_GRACE_S)
        if not self._shutdown:
            self.w.submit_specs_via_controller(specs)

    def _hold_for_failover(self, cls: _Class, specs: list):
        """Keep `cls` from leasing until `specs` have resolved on the
        controller path. They can only run there on resources no lease
        holds, and what the severed lease gave back is all a full cluster
        has: an owner that leased it again would fill the new worker with
        younger specs that wait, on the worker, for the failed-over specs'
        results, while those wait at the controller for the worker's
        resources - forever (a 2-CPU cluster, one of two leased workers
        SIGKILLed mid-shuffle: one run in three). Live leases keep
        draining the queue meanwhile."""

        def resolved():
            cls.failover -= 1
            if not cls.failover and not self._shutdown:
                self._pump(cls)

        for spec in specs:
            oids = spec.return_object_ids()  # none: nothing waits for it
            res = self.w._resolutions.get(oids[0]) if oids else None
            # Watchers run on the resolving thread; the count is the IO
            # loop's. An unresolved resolution outlives its ref (_free).
            if res is not None and res.add_watcher(
                    lambda: self.w.io.loop.call_soon_threadsafe(resolved)):
                cls.failover += 1

    def task_status(self, task_id: str) -> dict | None:
        """Best-effort status of a task this owner submitted on the direct
        path (GetTimeoutError enrichment). Read-only scan from the caller's
        thread; deliberately racy — diagnostics must not take loop-side
        locks or block on the IO thread."""
        try:
            with self._lock:
                for cls in self.classes.values():
                    for spec in cls.queue:
                        if spec.task_id == task_id:
                            return {"found": True, "state": "queued",
                                    "via": "direct", "name": spec.name,
                                    "attempt": spec.attempt,
                                    "node_id": None, "worker_id": None,
                                    "beacon_age_s": None}
            for lease in list(self._by_id.values()):
                spec = lease.inflight.get(task_id)
                if spec is None:
                    continue
                sent = all(s.task_id != task_id for s in list(lease.buf))
                return {"found": True,
                        "state": "running" if sent else "queued",
                        "via": "direct", "name": spec.name,
                        "attempt": spec.attempt, "node_id": lease.node_id,
                        "worker_id": lease.worker_id, "beacon_age_s": None}
        except Exception:
            pass
        return None

    def on_lease_invalid(self, lease_id: str, cause: str | None = None):
        lease = self._by_id.get(lease_id)
        if lease is not None:
            # A controller invalidation IS a known worker death (the agent
            # reported it): keep retry semantics, don't treat as a sever.
            lease.fail_cause = cause or "worker died"
            self._lease_failed(lease)

    # -------------------------------------------------------- cancellation
    def cancel(self, task_id: str, force: bool) -> bool:
        """True if the task is managed here (queued or in flight).

        Called from the user's thread, but every structure it touches beyond
        the lock-guarded class queues (lease.inflight, lease.buf) is owned by
        loop-side code (_pump/_task_done/_a_flush), so the scan+mutation runs
        as one atomic step ON the IO loop."""

        async def _go() -> bool:
            with self._lock:
                for cls in self.classes.values():
                    for spec in cls.queue:
                        if spec.task_id == task_id:
                            cls.queue.remove(spec)
                            self._fail_spec(spec, {
                                "type": "TaskCancelledError",
                                "message": f"task {spec.name} cancelled"})
                            return True
            for lease in list(self._by_id.values()):
                spec = lease.inflight.get(task_id)
                if spec is None:
                    continue
                self._cancelled[task_id] = force
                spec.max_retries = 0  # never retry a cancelled task
                if spec in lease.buf:
                    # Never sent to the worker: unbuffer and fail immediately
                    # (reference cancels pre-dispatch tasks synchronously).
                    # Applies to force too — killing the worker for a spec it
                    # never received would only hurt innocent neighbors.
                    lease.buf.remove(spec)
                    lease.inflight.pop(task_id, None)
                    self._cancelled.pop(task_id, None)
                    self._fail_spec(spec, {"type": "TaskCancelledError",
                                           "message": f"task {spec.name} cancelled"})
                elif force:
                    # Kill the worker, but do NOT requeue pipelined neighbors
                    # yet: they are requeued (attempt intact) by _lease_failed
                    # once the death is actually observed, so a neighbor can
                    # never run twice concurrently. Setting kill_target takes
                    # the lease out of _pump rotation immediately.
                    lease.kill_target = task_id
                    asyncio.ensure_future(
                        self._a_kill_for_cancel(lease, task_id))
                else:
                    # Already on the worker (queued or executing there).
                    # Don't guess the outcome: push the cancel and let the
                    # worker's tasks_done report decide — a value if the task
                    # wins the race (reference: ray.cancel losing the race
                    # delivers the value), a TaskCancelledError if the
                    # interrupt/skip wins.
                    if lease.conn is not None:
                        asyncio.ensure_future(
                            lease.conn.push("cancel", task_id=task_id))
                return True
            return False

        return self.w.io.run(_go())

    async def _a_kill_for_cancel(self, lease: _Lease, task_id: str):
        """Deliver a force-cancel kill, then make sure the doomed state
        resolves: a lease must never stay out of _pump rotation forever.

        - kill delivered → wait (bounded) for the death to arrive as a conn
          close; if it never does (kill push lost downstream), declare the
          lease failed ourselves so the class unblocks.
        - kill undeliverable (lease already torn down, controller blip) →
          un-doom: force cancel is best-effort in the reference too — the
          task then simply runs to completion and tasks_done decides the
          ref's outcome."""
        delivered = False
        for attempt in range(2):
            try:
                rep = await self.w.controller.call(
                    "kill_leased_worker", worker_id=lease.worker_id)
            except Exception:
                await asyncio.sleep(0.2)
                continue
            delivered = bool(rep.get("killed"))
            break
        # Grace period even when undeliverable: a concurrent kill (second
        # force-cancel on the same lease) may already be felling the worker.
        deadline = time.monotonic() + (10.0 if delivered else 1.0)
        while not lease.dead and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        if lease.dead:
            return
        if delivered:
            self._lease_failed(lease)
        elif lease.kill_target == task_id:
            lease.kill_target = None
            self._pump(lease.cls)

    # ------------------------------------------------------ lease returns
    async def _a_idle_loop(self):
        while not self._shutdown:
            await asyncio.sleep(min(0.25, max(0.05, CONFIG.lease_idle_s / 2)))
            now = time.monotonic()
            to_return = []
            for cls in self.classes.values():
                if cls.queue:
                    continue
                for lease in list(cls.leases.values()):
                    if (not lease.dead and not lease.inflight and not lease.buf
                            and now - lease.idle_since > CONFIG.lease_idle_s):
                        lease.dead = True
                        cls.leases.pop(lease.lease_id, None)
                        self._by_id.pop(lease.lease_id, None)
                        to_return.append(lease)
            if to_return:
                for lease in to_return:
                    self._park_conn(lease)
                await self._a_return([l.lease_id for l in to_return])
            # Cache sweep: drop dead or expired parked connections.
            for wid, (c, exp) in list(self._conn_cache.items()):
                if c.closed or exp < now:
                    self._conn_cache.pop(wid, None)
                    if not c.closed:
                        asyncio.ensure_future(c.close())

    def reassert(self):
        """After a controller restart: re-declare every live lease so the
        new controller can rebuild its lease table + resource accounting
        (reference: raylets report held leases when the GCS restarts).
        Runs on the IO loop (called from the reconnect coroutine)."""
        entries = []
        for lease in self._by_id.values():
            if lease.dead:
                continue
            entries.append({
                "lease_id": lease.lease_id,
                "worker_id": lease.worker_id,
                "node_id": lease.node_id,
                "address": lease.addr,
                "incarnation": lease.incarnation,
                "resources": lease.cls.resources,
                "strategy": lease.cls.strategy,
            })
        if entries:
            asyncio.ensure_future(self.w.controller.push(
                "reassert_leases", leases=entries,
                owner_id=self.w.worker_id))

    def on_need_resources(self):
        """Controller has demand it can't place: return idle leases now."""
        self.w.io.spawn(self._a_return_idle())

    async def _a_return_idle(self):
        to_return = []
        for cls in self.classes.values():
            if cls.queue:
                continue
            for lease in list(cls.leases.values()):
                if not lease.dead and not lease.inflight and not lease.buf:
                    lease.dead = True
                    cls.leases.pop(lease.lease_id, None)
                    self._by_id.pop(lease.lease_id, None)
                    self._park_conn(lease)
                    to_return.append(lease.lease_id)
        if to_return:
            await self._a_return(to_return)

    async def _a_return(self, lease_ids: list[str]):
        try:
            await self.w.controller.call("return_leases", lease_ids=lease_ids)
        except Exception:
            pass

    def shutdown(self):
        self._shutdown = True
        ids = list(self._by_id)
        if ids:
            try:
                self.w.io.run(self._a_return(ids), timeout=2)
            except Exception:
                pass
        cached, self._conn_cache = list(self._conn_cache.values()), {}
        for c, _exp in cached:
            if not c.closed:
                try:
                    self.w.io.spawn(c.close())
                except Exception:
                    pass
