"""Multi-node scheduling + fault tolerance.

Parity: reference tests test_multi_node*.py, test_actor_failures.py,
test_reconstruction*.py — run against the one-machine Cluster fixture
(reference cluster_utils.Cluster:135)."""

import time

import pytest

import ray_tpu
from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy


def _node_of_task():
    import os

    return os.environ.get("RT_NODE_ID")


def test_two_nodes_spread(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)
    ray_tpu.init(address=cluster.address)
    assert ray_tpu.cluster_resources()["CPU"] == 3.0

    @ray_tpu.remote(scheduling_strategy="SPREAD")
    def where():
        import os

        return os.environ.get("RT_NODE_ID")

    nodes = set(ray_tpu.get([where.remote() for _ in range(6)], timeout=120))
    assert len(nodes) == 2


def test_node_affinity(ray_start_cluster):
    cluster = ray_start_cluster
    n2 = cluster.add_node(num_cpus=1)
    ray_tpu.init(address=cluster.address)

    @ray_tpu.remote
    def where():
        import os

        return os.environ.get("RT_NODE_ID")

    strat = NodeAffinitySchedulingStrategy(node_id=n2.node_id)
    got = ray_tpu.get(where.options(scheduling_strategy=strat).remote(), timeout=60)
    assert got == n2.node_id


def test_task_retry_on_node_death(ray_start_cluster):
    cluster = ray_start_cluster
    n2 = cluster.add_node(num_cpus=1)
    ray_tpu.init(address=cluster.address)

    @ray_tpu.remote(max_retries=3)
    def slow_then_value():
        import time

        time.sleep(3)
        return "survived"

    strat = NodeAffinitySchedulingStrategy(node_id=n2.node_id, soft=True)
    ref = slow_then_value.options(scheduling_strategy=strat).remote()
    time.sleep(0.8)  # let it start on n2
    cluster.remove_node(n2)  # kill the node mid-task
    assert ray_tpu.get(ref, timeout=120) == "survived"


def test_actor_restart_on_node_death(ray_start_cluster):
    cluster = ray_start_cluster
    n2 = cluster.add_node(num_cpus=2)
    ray_tpu.init(address=cluster.address)

    @ray_tpu.remote(max_restarts=-1, max_task_retries=-1)
    class Pinger:
        def node(self):
            import os

            return os.environ.get("RT_NODE_ID")

    strat = NodeAffinitySchedulingStrategy(node_id=n2.node_id, soft=True)
    p = Pinger.options(scheduling_strategy=strat, max_restarts=2, max_task_retries=2).remote()
    assert ray_tpu.get(p.node.remote(), timeout=60) == n2.node_id
    cluster.remove_node(n2)
    # Actor restarts on the remaining (head) node.
    got = ray_tpu.get(p.node.remote(), timeout=120)
    assert got is not None and got != n2.node_id


def test_placement_group_pack_and_task(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)
    ray_tpu.init(address=cluster.address)
    from ray_tpu.util.placement_group import placement_group, remove_placement_group

    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
    assert pg.wait(timeout_seconds=30)

    @ray_tpu.remote(num_cpus=1, placement_group=pg)
    def inside():
        import os

        return os.environ.get("RT_NODE_ID")

    n = ray_tpu.get(inside.remote(), timeout=60)
    assert n is not None
    remove_placement_group(pg)


def test_placement_group_strict_spread(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=1)
    cluster.add_node(num_cpus=1)
    ray_tpu.init(address=cluster.address)
    from ray_tpu.util.placement_group import placement_group

    pg = placement_group([{"CPU": 1}] * 3, strategy="STRICT_SPREAD")
    assert pg.wait(timeout_seconds=30)

    @ray_tpu.remote(num_cpus=1, placement_group=pg)
    def where():
        import os

        return os.environ.get("RT_NODE_ID")

    nodes = ray_tpu.get([where.options(placement_group_bundle_index=i).remote() for i in range(3)], timeout=120)
    assert len(set(nodes)) == 3


def test_infeasible_pg_pending(ray_start_cluster):
    ray_tpu.init(address=ray_start_cluster.address)
    from ray_tpu.util.placement_group import placement_group

    pg = placement_group([{"CPU": 99}], strategy="PACK")
    assert not pg.wait(timeout_seconds=0.5)


def test_controller_persistence_restart(shutdown_only, tmp_path):
    """KV contents and named actors survive a full controller restart: the
    new controller restores its snapshot and re-creates the actor from its
    persisted spec once the node joins (reference GCS+Redis restart,
    redis_store_client.h — our agents share fate with the controller, so
    re-creation rather than adoption is the contract)."""
    persist = str(tmp_path / "ctrl")

    ray_tpu.init(num_cpus=2, _system_config={"controller_persist_dir": persist})

    @ray_tpu.remote
    class Registry:
        def __init__(self):
            self.greeting = "hello-from-v1"

        def greet(self):
            return self.greeting

    reg = Registry.options(name="registry", lifetime="detached").remote()
    assert ray_tpu.get(reg.greet.remote(), timeout=60) == "hello-from-v1"
    from ray_tpu._private.worker import global_worker

    global_worker().kv("put", ns="app", key="cfg", value=b"v42")
    ray_tpu.shutdown()  # stop() flushes dirty state before exiting

    # Fresh cluster, same persist dir: restore.
    ray_tpu.init(num_cpus=2, _system_config={"controller_persist_dir": persist})
    w = global_worker()
    assert w.kv("get", ns="app", key="cfg")["value"] == b"v42"
    reg2 = ray_tpu.get_actor("registry")
    assert ray_tpu.get(reg2.greet.remote(), timeout=120) == "hello-from-v1"


def test_controller_stall_does_not_kill_healthy_nodes(shutdown_only):
    """A controller that was not running cannot judge who else was not:
    when its own event loop loses far more than the heartbeat timeout
    (blocked, or the machine paused — a four-chip host stands still for 9
    to 10 s while four workers start the TPU runtime at once), the head
    node, whose agent shares that loop, must not be declared dead with
    every actor on it."""
    ray_tpu.init(num_cpus=1, _system_config={
        "heartbeat_interval_s": 0.2, "num_heartbeats_timeout": 2})

    @ray_tpu.remote(num_cpus=0)
    class Pinger:
        def ping(self):
            return "pong"

    a = Pinger.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=30) == "pong"
    # Block the loop for 1.5 s, nearly four times the 0.4 s timeout.
    ray_tpu._head.io.loop.call_soon_threadsafe(time.sleep, 1.5)
    time.sleep(2.5)
    assert all(n["Alive"] for n in ray_tpu.nodes())
    assert ray_tpu.get(a.ping.remote(), timeout=30) == "pong"
