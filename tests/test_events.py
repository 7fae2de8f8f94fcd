"""Cluster event plane (README "Cluster events"): lifecycle events with
monotonic seqs, a per-entity index, storage-backed JSONL segments, the
normalized worker-exit cause enum, error-message enrichment, and the
job-logs truncation contract that rides along in the same PR."""

import json
import os
import signal
import time

import pytest

import ray_tpu
from ray_tpu._private import events as events_mod
from ray_tpu.util import state


def _wait_for(pred, timeout=20.0, interval=0.2, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = pred()
        if out:
            return out
        time.sleep(interval)
    raise TimeoutError(f"timed out waiting for {what}")


def test_lifecycle_events_seq_ordered_and_entity_indexed(ray_start_2cpu):
    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    a = A.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"

    rows = _wait_for(
        lambda: [e for e in state.list_events()
                 if e["kind"] in ("actor_create", "actor_ready")] or None,
        what="actor lifecycle events")
    kinds = [e["kind"] for e in rows]
    assert "actor_create" in kinds and "actor_ready" in kinds
    # seqs are strictly increasing in list order (arrival-order minting).
    all_rows = state.list_events()
    seqs = [e["seq"] for e in all_rows]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # Every emitted kind is declared (the registry the rtcheck event-kinds
    # pass enforces statically holds at runtime too).
    for e in all_rows:
        assert e["kind"] in events_mod.KINDS, e
        assert e["sev"] in events_mod.SEVERITIES, e
    # Entity filter: the actor's id prefix-matches only its own chain.
    mine = state.list_events(entity=a._actor_id[:12])
    assert mine and all(
        any(str(x).startswith(a._actor_id[:12]) for x in e["entity"])
        for e in mine)
    assert [e["kind"] for e in mine][:2] == ["actor_create", "actor_ready"]
    # Kind + severity filters.
    assert all(e["kind"] == "actor_ready"
               for e in state.list_events(kind="actor_ready"))
    assert all(e["sev"] == "debug"
               for e in state.list_events(severity="debug"))
    # Worker spawns arrive via the heartbeat piggyback path.
    _wait_for(lambda: state.list_events(kind="worker_start") or None,
              what="worker_start via heartbeat")
    # since= is an exclusive seq cursor (the --follow contract).
    last = all_rows[-1]["seq"]
    assert all(e["seq"] > last for e in state.list_events(since=last))


def test_worker_exit_cause_normalized_and_error_enriched(ray_start_2cpu):
    @ray_tpu.remote(max_restarts=0)
    class Frail:
        def pid(self):
            return os.getpid()

    f = Frail.remote()
    pid = ray_tpu.get(f.pid.remote(), timeout=60)
    os.kill(pid, signal.SIGKILL)
    ev = _wait_for(
        lambda: next((e for e in state.list_events(kind="worker_exit")
                      if (e.get("attrs") or {}).get("pid") == pid), None),
        what="worker_exit event")
    # The normalized cause enum — not a raw signal int, not "killed".
    assert (ev["attrs"]["cause"] == events_mod.CAUSE_CRASH
            and ev["attrs"]["cause"] in events_mod.EXIT_CAUSES)
    # Error enrichment: the ActorDiedError a caller sees names the event
    # seq range that explains the death.
    def _dead_error():
        try:
            ray_tpu.get(f.pid.remote(), timeout=10)
            return None
        except ray_tpu.exceptions.ActorDiedError as e:
            return str(e)

    msg = _wait_for(_dead_error, what="ActorDiedError")
    assert "[events " in msg and "ray-tpu events --entity" in msg, msg
    death = _wait_for(
        lambda: state.list_events(entity=f._actor_id, kind="actor_death")
        or None, what="actor_death event")
    assert death[-1]["sev"] == "error"

    # Explicit kills are a DIFFERENT cause: ray_tpu.kill routes through
    # the agent's kill_worker path, which has no worker_died report — the
    # event must still appear, with cause "killed" (not crash).
    @ray_tpu.remote
    class Victim:
        def pid(self):
            return os.getpid()

    v = Victim.remote()
    vpid = ray_tpu.get(v.pid.remote(), timeout=60)
    ray_tpu.kill(v)
    kev = _wait_for(
        lambda: next((e for e in state.list_events(kind="worker_exit")
                      if (e.get("attrs") or {}).get("pid") == vpid), None),
        what="killed worker_exit event")
    assert kev["attrs"]["cause"] == events_mod.CAUSE_KILLED, kev
    # Exactly one exit event per worker (the slot-level dedup).
    exits = [e for e in state.list_events(kind="worker_exit")
             if (e.get("attrs") or {}).get("pid") == vpid]
    assert len(exits) == 1, exits


def test_events_plane_off_is_inert(shutdown_only, monkeypatch):
    monkeypatch.setenv("RT_EVENTS_BUFFER", "0")
    events_mod.refresh()
    try:
        ray_tpu.init(num_cpus=1)

        @ray_tpu.remote
        class A:
            def ping(self):
                return 1

        a = A.remote()
        assert ray_tpu.get(a.ping.remote(), timeout=60) == 1
        time.sleep(1.2)
        rows = state.list_events()
        assert rows == [] and not rows.truncated
        # Agent side: no pending deque at all — heartbeat frames carry no
        # `events` key (byte-identical to a plane-free build).
        assert ray_tpu._head.agent._pending_events is None
        assert ray_tpu._head.controller.events.maxlen is None \
            and len(ray_tpu._head.controller.events) == 0
        # Driver-side emission is a no-op, not a buffered leak.
        events_mod.emit_event("job_start", "should vanish")
        assert events_mod.drain() == []
    finally:
        monkeypatch.delenv("RT_EVENTS_BUFFER", raising=False)
        events_mod.refresh()


def test_event_persistence_segments_and_rotation(tmp_path, shutdown_only,
                                                 monkeypatch):
    ev_dir = str(tmp_path / "ev")
    monkeypatch.setenv("RT_EVENTS_DIR", ev_dir)
    monkeypatch.setenv("RT_EVENTS_SEGMENT_EVENTS", "16")
    monkeypatch.setenv("RT_EVENTS_KEEP_SEGMENTS", "3")
    ray_tpu.init(num_cpus=1)
    head = ray_tpu._head
    ctrl = head.controller

    async def _pump(n):
        ctrl._ingest_events([
            events_mod.build_event("job_start", f"synthetic {i}",
                                   entity=(f"job{i % 7}",))
            for i in range(n)])

    head.io.run(_pump(100))

    def _segments():
        try:
            return sorted(n for n in os.listdir(ev_dir)
                          if n.startswith("seg-") and n.endswith(".jsonl"))
        except OSError:
            return []

    # (the last full segment of the 100 pumped events ends at seq 95 or
    # later: an earlier look can find the first segments before rotation
    # has dropped them, the head's own `node_register` among their rows)
    segs = _wait_for(
        lambda: s if len(s := _segments()) and len(s) <= 3
        and int(s[-1][len("seg-"):-len(".jsonl")]) >= 95 else None,
        what="rotated segments")
    # keep-last-K rotation: 100 events / 16 per segment > 3 kept.
    assert 1 <= len(segs) <= 3
    # Segments are parseable JSONL with strictly increasing seqs, and the
    # file name carries the segment's LAST seq (the restore-scan contract).
    last_seen = -1
    for name in segs:
        with open(os.path.join(ev_dir, name)) as fh:
            rows = [json.loads(ln) for ln in fh if ln.strip()]
        assert rows and all(r["kind"] == "job_start" for r in rows)
        seqs = [r["seq"] for r in rows]
        assert seqs == sorted(seqs) and seqs[0] > last_seen
        last_seen = seqs[-1]
        assert int(name[len("seg-"):-len(".jsonl")]) == seqs[-1]
    # The in-progress tail rewrites as current.jsonl.
    _wait_for(lambda: os.path.exists(os.path.join(ev_dir, "current.jsonl")),
              what="current.jsonl tail")
    # Driver-side emit_event rides the metrics flush into the same ring.
    events_mod.emit_event("job_stop", "driver emitted",
                          entity=("driver-ev",))
    rows = _wait_for(lambda: state.list_events(entity="driver-ev") or None,
                     what="driver event via metrics flush")
    assert rows[-1]["kind"] == "job_stop"


def test_snapshot_restore_seq_never_collides(tmp_path, monkeypatch):
    """Satellite: a restored head must not re-mint seqs that collide with
    persisted segments — via the snapshot watermark AND the segment scan
    (which covers seqs minted after the last snapshot)."""
    from ray_tpu._private.controller import Controller

    ev_dir = str(tmp_path / "ev")
    monkeypatch.setenv("RT_EVENTS_DIR", ev_dir)
    c1 = Controller("sess-events")
    c1._ingest_events([events_mod.build_event("job_start", f"e{i}",
                                              entity=(f"j{i}",))
                       for i in range(10)])
    assert c1._event_seq == 10
    snap = c1._build_snapshot()
    assert snap["events_seq"] == 10
    # Persist everything the sweep would have (5 full + tail of 5 under a
    # synthetic segment size), using the same sync helper the sweep uses.
    buf = list(c1._evseg_buf)
    c1._persist_event_segments_sync(ev_dir, [buf[:5]], buf[5:], 4, 0)
    # Restore path 1: segment scan alone (snapshot lost/stale at 0).
    c2 = Controller("sess-events")
    assert c2._event_seq == 0
    c2._restore_event_seq()
    assert c2._event_seq == 10, (
        f"restored head would re-mint seq {c2._event_seq} colliding with "
        f"persisted history")
    # History survives the restart QUERYABLY: the ring and entity index
    # reload from the persisted segments + current tail.
    assert [e["seq"] for e in c2.events] == list(range(10))
    assert c2._event_index  # entity index rebuilt
    # current.jsonl's tail events refill the persistence buffer (they live
    # in no full segment yet — the next tail rewrite must keep them).
    assert [e["seq"] for e in c2._evseg_buf] == list(range(5, 10))
    c2._ingest_events([events_mod.build_event("job_start", "fresh")])
    assert c2.events[-1]["seq"] == 10
    # Restore path 2: the snapshot watermark beats an even staler scan.
    c3 = Controller("sess-events")
    c3._event_seq = int(snap["events_seq"])
    c3._restore_event_seq()
    assert c3._event_seq >= 10
    # Crash window: killed between the seg-N write and the current.jsonl
    # rewrite, the tail exists in BOTH files. Restore dedupes by seq and
    # only segment-uncovered tail events refill the persistence buffer —
    # the duplicate never becomes permanent in durable history.
    ev_dir2 = str(tmp_path / "ev2")
    monkeypatch.setenv("RT_EVENTS_DIR", ev_dir2)
    c1._persist_event_segments_sync(ev_dir2, [buf[:8]], buf[5:], 4, 0)
    c4 = Controller("sess-events")
    c4._restore_event_seq()
    assert [e["seq"] for e in c4.events] == list(range(10))  # deduped
    assert [e["seq"] for e in c4._evseg_buf] == [8, 9]  # covered tail out
    assert c4._event_seq == 10


def test_job_logs_capped_with_truncated_marker(ray_start_2cpu, monkeypatch):
    """Satellite: one job_logs RPC returns at most JOB_LOG_CHUNK_BYTES and
    marks clipped replies truncated; the client loops to EOF."""
    from ray_tpu._private.node_agent import NodeAgent
    from ray_tpu.job_submission import JobSubmissionClient

    monkeypatch.setattr(NodeAgent, "JOB_LOG_CHUNK_BYTES", 512)
    w = ray_tpu._private.worker.global_worker()
    client = JobSubmissionClient(
        f"{w.controller_addr[0]}:{w.controller_addr[1]}")
    try:
        sid = client.submit_job(
            entrypoint="python -c \"print('x' * 5000)\"")
        assert client.wait_until_finished(sid, timeout=120) == "SUCCEEDED"
        # Direct agent contract: capped reply, truncated marker set.
        rep = ray_tpu._head.agent._job_logs(sid, 0)
        assert rep["found"] and len(rep["data"]) == 512 and rep["truncated"]
        # EOF reply: not truncated.
        end = ray_tpu._head.agent._job_logs(sid, 1 << 30)
        assert end["found"] and end["data"] == b"" and not end["truncated"]
        # The client loops on the marker and reassembles the whole log.
        logs = client.get_job_logs(sid)
        assert "x" * 5000 in logs
    finally:
        client.close()


def test_dashboard_api_events(ray_start_2cpu):
    import urllib.request

    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    a = A.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == 1
    _wait_for(lambda: state.list_events(kind="actor_ready") or None,
              what="actor_ready event")
    from ray_tpu.dashboard import start_dashboard

    d = start_dashboard(port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{d.port}/api/events", timeout=10) as r:
            rep = json.loads(r.read())
        kinds = {e["kind"] for e in rep["events"]}
        assert {"actor_create", "actor_ready"} <= kinds, kinds
        assert isinstance(rep["next_seq"], int)
        ent = a._actor_id[:12]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{d.port}/api/events?entity={ent}"
                f"&kind=actor_ready", timeout=10) as r:
            rep = json.loads(r.read())
        assert rep["events"] and all(
            e["kind"] == "actor_ready" for e in rep["events"])
    finally:
        d.stop()


def test_cli_events_command(ray_start_2cpu, capsys):
    from ray_tpu.scripts import cli

    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    a = A.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == 1
    _wait_for(lambda: state.list_events(kind="actor_ready") or None,
              what="actor_ready event")
    w = ray_tpu._private.worker.global_worker()
    addr = f"{w.controller_addr[0]}:{w.controller_addr[1]}"
    assert cli.main(["events", "--address", addr]) == 0
    out = capsys.readouterr().out
    assert "actor_ready" in out and "SEQ" in out
    assert cli.main(["events", "--address", addr, "--entity",
                     a._actor_id[:12]]) == 0
    out = capsys.readouterr().out
    assert "actor_create" in out and "node_register" not in out
