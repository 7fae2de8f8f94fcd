"""Observability: task events -> chrome-trace timeline, state list APIs,
worker log streaming to the driver.

reference tests: python/ray/tests/test_state_api.py, test_timeline.py,
test_output.py (log_to_driver).
"""

import json
import time

import pytest

import ray_tpu
from ray_tpu.util import state


def test_timeline_chrome_trace(ray_start_2cpu, tmp_path):
    @ray_tpu.remote
    def work(i):
        time.sleep(0.05)
        return i

    ray_tpu.get([work.remote(i) for i in range(6)], timeout=120)
    time.sleep(0.3)  # let the event batches drain to the controller

    out = str(tmp_path / "trace.json")
    trace = ray_tpu.timeline(filename=out)
    xs = [e for e in trace if e.get("ph") == "X"]
    assert len(xs) >= 6
    ev = next(e for e in xs if e["name"] == "work")
    assert ev["dur"] >= 0.04 * 1e6  # the sleep is visible
    assert ev["args"]["ok"] is True
    metas = [e for e in trace if e.get("ph") == "M"]
    assert any(m["name"] == "process_name" for m in metas)
    assert any(m["name"] == "thread_name" for m in metas)
    # the file is valid chrome-trace JSON
    loaded = json.load(open(out))
    assert loaded == trace


def test_state_list_apis(ray_start_2cpu):
    @ray_tpu.remote
    def fin():
        return 1

    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    a = A.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
    ray_tpu.get([fin.remote() for _ in range(3)], timeout=60)
    big = ray_tpu.put(b"x" * (1 << 20))  # non-inline: stays in directory
    time.sleep(0.3)

    tasks = state.list_tasks()
    assert any(t["name"] == "fin" and t["state"] == "FINISHED" for t in tasks)
    assert any(t["name"] == "ping" for t in tasks)

    actors = state.list_actors()
    assert any(x["class"] == "A" and x["state"] == "ALIVE" for x in actors)

    objs = state.list_objects()
    assert any(o["object_id"] == big.hex() for o in objs)

    nodes = state.list_nodes()
    assert len(nodes) == 1 and nodes[0]["alive"]

    summary = state.summarize_tasks()
    assert summary.get("fin:FINISHED", 0) >= 3


def test_worker_logs_stream_to_driver(ray_start_2cpu, capfd):
    @ray_tpu.remote
    def shout():
        print("HELLO-FROM-WORKER-xyzzy")
        return 1

    assert ray_tpu.get(shout.remote(), timeout=60) == 1
    deadline = time.monotonic() + 10
    seen = False
    while time.monotonic() < deadline and not seen:
        time.sleep(0.2)
        err = capfd.readouterr().err
        seen = "HELLO-FROM-WORKER-xyzzy" in err
    assert seen, "worker stdout never reached the driver"


def test_live_worker_stack_dump(ray_start_2cpu):
    """Live thread stacks of a running worker via SIGUSR1 + faulthandler
    (the py-spy/reporter-agent role): the dump must show the worker's
    executing frame."""
    import time as _t

    @ray_tpu.remote
    class Busy:
        def spin(self, seconds):
            deadline = _t.time() + seconds
            while _t.time() < deadline:
                _t.sleep(0.01)
            return "done"

    a = Busy.remote()
    ref = a.spin.remote(8.0)
    w = ray_tpu._private.worker.global_worker()
    # resolve the actor's worker id via the controller
    info = w.io.run(w.controller.call(
        "get_actor_info", actor_id=a._actor_id, wait=True))
    # the call executes for 8 s; on a loaded host its worker may take more
    # than a second to start it, so ask until the frame shows
    give_up = _t.time() + 6.0
    while True:
        rep = w.io.run(w.controller.call(
            "worker_stacks", worker_id=info["worker_id"], node_id=None),
            timeout=15)
        assert rep["found"], rep
        if "spin" in rep["stacks"] or _t.time() > give_up:
            break
        _t.sleep(0.25)
    assert "spin" in rep["stacks"], rep["stacks"][:500]
    assert ray_tpu.get(ref, timeout=60) == "done"
