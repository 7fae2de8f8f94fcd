"""Deterministic chaos tests for owner-side direct dispatch.

The direct path keeps the controller off the per-task critical path, so its
failure story is owner-based: severing the owner->worker lease connection
mid-batch must fail the in-flight specs over to the classic controller path
with NO duplicate execution (worker-side skip of unstarted specs + the node
agent's task-id dedup of the one that was executing) and no hung refs; and
a lease reasserted against a node's PREVIOUS incarnation is dead on arrival
(fencing), never a resource charge against the fresh life.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

import pytest

import ray_tpu
from ray_tpu._private import rpc
from ray_tpu._private.ids import NodeID
from ray_tpu._private.resources import ResourceSet


def _spawn_agent(controller_addr: str, session: str, num_cpus=2):
    env = dict(os.environ)
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    driver_paths = [p for p in sys.path if p and os.path.exists(p)]
    env["PYTHONPATH"] = os.pathsep.join([pkg_root] + driver_paths)
    node_id = NodeID.from_random().hex()
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node_agent",
         "--controller", controller_addr,
         "--node-id", node_id,
         "--session", session,
         "--resources",
         json.dumps(ResourceSet({"CPU": float(num_cpus)}).raw())],
        env=env)
    return node_id, proc


def _wait(pred, timeout: float, what: str):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise TimeoutError(f"timed out waiting for {what}")


def _snapshot():
    return ray_tpu._private.worker.global_worker().state_snapshot()


@pytest.fixture
def chaos_cleanup():
    procs = []
    yield procs
    try:
        ray_tpu.shutdown()
    except Exception:
        pass
    for proc in procs:
        try:
            proc.kill()
        except Exception:
            pass
    inj = rpc.fault_injector()
    if inj is not None:
        inj.clear()
    rpc.disable_fault_injection()


def test_sever_mid_batch_fails_over_to_controller_no_duplicates(chaos_cleanup):
    """Sever every owner->worker lease connection while a batch is in
    flight: all refs still resolve (failover via the controller path), each
    task executed EXACTLY once (the worker skips unstarted specs of the
    dead holder; the agent's dedup absorbs the re-dispatch of the one that
    was executing), and the dispatch-path counters show the reroute."""
    ray_tpu.init(num_cpus=0, _system_config={"fault_injection": True})
    head = ray_tpu._head
    addr = f"{head.controller_addr[0]}:{head.controller_addr[1]}"
    nid, proc = _spawn_agent(addr, head.session_id, num_cpus=2)
    chaos_cleanup.append(proc)
    _wait(lambda: (_snapshot()["nodes"].get(nid) or {}).get("alive"),
          60, "node to register")

    marker_dir = tempfile.mkdtemp(prefix="rt_chaos_dd_")
    log = os.path.join(marker_dir, "executions.log")

    @ray_tpu.remote(num_cpus=1, max_retries=0)
    def tracked(i, path):
        import os as _os
        import time as _t

        # O_APPEND single write: concurrent executions can't interleave.
        fd = _os.open(path, _os.O_WRONLY | _os.O_CREAT | _os.O_APPEND, 0o644)
        _os.write(fd, f"{i}\n".encode())
        _os.close(fd)
        _t.sleep(0.15)
        return i

    # Warm the leases/workers so the sever hits established pipelines.
    ray_tpu.get([tracked.remote(-1 - j, log) for j in range(2)], timeout=60)

    n = 12
    refs = [tracked.remote(i, log) for i in range(n)]

    def _started():
        try:
            with open(log) as f:
                return sum(1 for l in f if not l.startswith("-")) >= 2
        except OSError:
            return False

    _wait(_started, 30, "batch to start executing")
    inj = rpc.fault_injector()
    severed = inj.sever("lease")
    assert severed >= 1, "no lease connections to sever"

    # Every ref resolves despite the sever (no hung refs), max_retries=0
    # notwithstanding: a transport sever is a re-route, not a retry.
    values = ray_tpu.get(refs, timeout=120)
    assert values == list(range(n))

    # Exactly-once: each index appears exactly once in the execution log.
    with open(log) as f:
        runs = [int(l) for l in f if l.strip()]
    counts = {}
    for i in runs:
        if i >= 0:
            counts[i] = counts.get(i, 0) + 1
    assert counts == {i: 1 for i in range(n)}, counts

    # The failover went through the controller path (owner-side counter).
    from ray_tpu.util.metrics import task_dispatch_counts

    counts = task_dispatch_counts()
    assert counts["controller"] > 0, counts
    assert counts["direct"] >= n, counts

    # And the cluster still works on fresh leases afterwards.
    assert ray_tpu.get([tracked.remote(100 + j, log) for j in range(4)],
                       timeout=60) == [100, 101, 102, 103]


def test_severed_specs_get_the_dead_leases_resources(chaos_cleanup):
    """On a full cluster (one CPU, one lease) the owner loses its leased
    worker with a producer executing, consumers pipelined behind it and
    more consumers still queued at the owner. The sent specs fail over to
    the controller path, which can run them only on the CPU the dead lease
    gave back: the owner must not lease it again for the queued consumers,
    which would wait on the new worker for the producer while the producer
    waits at the controller for the worker's CPU."""
    import signal

    ray_tpu.init(num_cpus=1, _system_config={"fault_injection": True})

    @ray_tpu.remote(num_cpus=1, max_retries=2)
    def produce(gate):
        import os as _os
        import time as _t

        while not _os.path.exists(gate):  # the first attempt is killed
            _t.sleep(0.02)
        return 7

    @ray_tpu.remote(num_cpus=1, max_retries=2)
    def consume(x, i):
        return x * 100 + i

    assert ray_tpu.get(consume.remote(0, 0), timeout=60) == 0  # warm lease
    gate = os.path.join(tempfile.mkdtemp(prefix="rt_chaos_dd_"), "go")
    src = produce.remote(gate)
    # More consumers than a lease takes at its deepest (lease.DEPTH): the
    # rest wait in the owner's class queue.
    outs = [consume.remote(src, i) for i in range(24)]

    def _executing_pid():
        slots = [s for s in ray_tpu._head.agent.workers.values()
                 if s.state == "leased" and s.proc.poll() is None]
        lm = ray_tpu._private.worker.global_worker().lease_mgr
        sent = sum(len(l.inflight) - len(l.buf)
                   for l in list(lm._by_id.values()))
        return slots[0].proc.pid if slots and sent >= 2 else None

    _wait(_executing_pid, 30, "producer and a consumer on the leased worker")
    # A SIGKILLed worker is mostly known by its closed connection first (a
    # sever, as here) and now and then by the controller's lease_invalid (a
    # retry on a new lease): the connection goes first, so that it is the
    # sever every time.
    pid = _executing_pid()
    assert rpc.fault_injector().sever("lease") == 1
    lm = ray_tpu._private.worker.global_worker().lease_mgr
    (cls,) = lm.classes.values()
    _wait(lambda: cls.failover, 30, "the sent specs to fail over")
    asked = []
    request = lm._a_request
    lm._a_request = lambda c, n: asked.append(n) or request(c, n)
    os.kill(pid, signal.SIGKILL)
    # The producer goes to the controller (lease.FAILOVER_GRACE_S after
    # the sever) and holds the CPU there, its gate shut. Until it resolves
    # the owner asks for no lease, though consumers are queued and it
    # holds none (a request from before the sever may still be answered:
    # that lease goes straight back).
    deadline = time.monotonic() + 1.0
    while time.monotonic() < deadline:
        assert cls.failover and cls.queue
        assert not asked and not cls.leases
        time.sleep(0.005)
    open(gate, "w").close()

    assert ray_tpu.get(outs, timeout=60) == [700 + i for i in range(24)]
    from ray_tpu.util.metrics import task_dispatch_counts

    assert task_dispatch_counts()["controller"] > 0
    # The hold ends with the failed-over specs: the owner leases again.
    assert ray_tpu.get([consume.remote(1, i) for i in range(6)],
                       timeout=60) == [100 + i for i in range(6)]
    assert cls.failover == 0 and task_dispatch_counts()["direct"] >= 32


def test_lease_fencing_across_incarnation_bump(chaos_cleanup):
    """A lease reasserted against a node's previous incarnation is dead on
    arrival: rejected (counted + lease_invalid pushed to the owner), with
    ZERO resource consumption on the node's fresh life; the same reassert
    with the current incarnation is accepted and charged."""
    ray_tpu.init(num_cpus=1, _system_config={"fault_injection": True})
    ctrl = ray_tpu._head.controller
    addr = ray_tpu._head.controller_addr
    io = rpc.EventLoopThread(name="fence-io")
    nid = "fence" + NodeID.from_random().hex()[:8]
    try:
        async def _register():
            conn = await rpc.connect(*addr)
            rep = await conn.call(
                "register", kind="node", node_id=nid,
                address=("127.0.0.1", 1),
                resources=ResourceSet({"CPU": 2.0}).raw(), labels={})
            return conn, rep["incarnation"]

        _old_conn, old_inc = io.run(_register(), timeout=30)
        _new_conn, new_inc = io.run(_register(), timeout=30)
        assert new_inc == old_inc + 1

        invalidated = []

        async def _owner():
            conn = await rpc.connect(
                *addr,
                on_push=lambda c, m, a: invalidated.append((m, a)) or _noop())
            await conn.call("register", kind="client",
                            worker_id="fenceowner" + "0" * 23,
                            mode="driver", address=("127.0.0.1", 2))
            return conn

        def _noop():
            async def _n():
                return None
            return _n()

        owner_conn = io.run(_owner(), timeout=30)
        node = ctrl.nodes[nid]
        avail_before = dict(node.available.raw())
        rejected_before = ctrl.stale_incarnation_rejections

        stale = {
            "lease_id": "stalelease0000ff",
            "worker_id": "w" * 32,
            "node_id": nid,
            "address": ("127.0.0.1", 3),
            "incarnation": old_inc,
            "resources": ResourceSet({"CPU": 1.0}).raw(),
            "strategy": None,
        }
        io.run(owner_conn.push("reassert_leases", leases=[stale],
                               owner_id="fenceowner" + "0" * 23))
        _wait(lambda: ctrl.stale_incarnation_rejections > rejected_before,
              10, "stale lease reassert to be rejected")
        assert "stalelease0000ff" not in ctrl.leases
        assert node.available.raw() == avail_before, \
            "fenced lease charged resources against the fresh incarnation"
        _wait(lambda: any(m == "lease_invalid" for m, _a in invalidated),
              10, "owner to be told the fenced lease is invalid")

        # Current-incarnation reassert: accepted and charged.
        from ray_tpu._private.task_spec import SchedulingStrategy

        fresh = dict(stale, lease_id="freshlease0000ff",
                     incarnation=new_inc, strategy=SchedulingStrategy())
        io.run(owner_conn.push("reassert_leases", leases=[fresh],
                               owner_id="fenceowner" + "0" * 23))
        _wait(lambda: "freshlease0000ff" in ctrl.leases, 10,
              "current-incarnation lease reassert to be applied")
        assert node.available.raw() != avail_before
    finally:
        io.stop()
