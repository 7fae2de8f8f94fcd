"""The six readers of a replica's set-up account (`setup_*`,
`benchmark/setup_spans.py`) on account files and a `run` written by hand:
what each computes, which files count, and that every one returns None,
without raising, where the program wrote no file (the parent of PR 56)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, setup_spans as su  # noqa: E402

SIX = ("setup_trace_lower_s", "setup_compile_s", "setup_first_run_s",
       "setup_runtime_init_s", "setup_engine_init_s", "setup_outside_s")
PID = 4242
#: The benchmark starts at wall 1000 and its window at 1100.
RUN = {"window_wall": (1100.0, 1151.0), "e2e": {"setup_s": 100.0},
       "split": {"cluster_s": 1.0, "deploy_s": 30.0, "warmup_s": 50.0},
       "traffic": {"preload_s": 8},
       "spans": [{"n": "engine.prefill", "k": "engine", "pid": PID,
                  "t": "x", "a": 1101.0, "b": 1101.1}]}


def build(name, a, trace, lower, compile_, **more):
    return {"fun_name": name, "a": a, "b": a + trace + lower + compile_,
            "trace_s": trace, "lower_s": lower, "compile_s": compile_,
            "cache": "hit", "retrieval_s": 0.8 * compile_, "saved_s": 0.0,
            "stage": None, **more}


def account(pid=PID, process_start=1004.0):
    """replica.start at 1005: the runtime 1005-1013, the engine 1013-1028
    with two builds of 3 s and 4 s inside it; then a prefill built in a call
    that begins at 1030 (6 s of parts, its first token read at 1042), a
    chunk program built beside it on the scheduler's thread (1037-1041, read
    at 1043) and an eager slice after it; one more build inside the window."""
    return {"pid": pid, "process_start": process_start, "written": 1200.0,
            "compile_count": 6, "compile_s": 12.0,
            "stages": [
                {"n": "runtime.init", "a": 1005.0, "b": 1013.0,
                 "p": "replica.start"},
                {"n": "engine.params", "a": 1013.0, "b": 1020.0,
                 "p": "engine.init"},
                {"n": "engine.programs", "a": 1020.0, "b": 1027.0,
                 "p": "engine.init"},
                {"n": "engine.init", "a": 1013.0, "b": 1028.0,
                 "p": "replica.start"},
                {"n": "replica.start", "a": 1005.0, "b": 1028.5, "p": None,
                 "at": {"deployment": "llm"}}],
            "builds": [
                build("jit__lambda_", 1014.0, 0.5, 0.5, 2.0,
                      stage="engine.params"),
                build("jit_chunk", 1021.0, 1.0, 1.0, 2.0,
                      stage="engine.programs"),
                build("jit_prefill", 1030.0, 2.0, 1.0, 3.0, call_a=1030.0,
                      call_s=6.5, ready_s=12.0, kernel=True, bucket=256),
                build("jit_chunk", 1037.0, 1.0, 1.0, 2.0, call_a=1037.0,
                      call_s=4.2, ready_s=6.0, kernel=False, tokens=16),
                build("jit_dynamic_slice", 1041.5, 0.0, 0.1, 0.1),
                build("jit_chunk", 1120.0, 1.0, 1.0, 2.0, call_a=1120.0,
                      call_s=4.2, ready_s=5.0, tokens=2)]}


@pytest.fixture
def session(tmp_path, monkeypatch):
    monkeypatch.setenv("RT_SESSION_DIR", str(tmp_path))
    os.makedirs(tmp_path / "setup")

    def write(doc, name=None):
        with open(tmp_path / "setup" / (name or f"{doc['pid']}.json"),
                  "w") as f:
            json.dump(doc, f)
    return write


def read_all(run=RUN):
    return {name: manifest.layer_reader(name)(run) for name in SIX}


def test_the_six_over_what_ended_before_the_window(session, capsys):
    session(account())
    got = read_all()
    # five builds ended before the window; the sixth lies inside it
    assert got["setup_trace_lower_s"] == pytest.approx(
        1.0 + 2.0 + 3.0 + 2.0 + 0.1)
    assert got["setup_compile_s"] == pytest.approx(2 + 2 + 3 + 2 + 0.1)
    # the calls wait 1030-1042 and 1037-1043: thirteen seconds, of which
    # the builds 1030-1036, 1037-1041 and 1041.5-1041.7 cover 10.2
    assert got["setup_first_run_s"] == pytest.approx(13.0 - 10.2)
    assert got["setup_runtime_init_s"] == pytest.approx(8.0)
    assert got["setup_engine_init_s"] == pytest.approx(15.0 - 3.0 - 4.0)
    # the account covers 1005 to 1043 of a set-up of 100 s
    assert got["setup_outside_s"] == pytest.approx(100.0 - 38.0)
    said = capsys.readouterr().out
    assert "5 builds" in said and "5 cache hits, 0 misses" in said
    assert "1 first calls with a Mosaic kernel" in said
    assert "5.00s before replica.start, 57.00s after the last build" in said
    assert "cluster_s 1.00, deploy_s 30.00, warmup_s 50.00, preload 8" in said
    # the check: the six, and the covered time in none of them (1028-1030:
    # the constructor's end and the request's way to its prefill), make
    # setup_s
    assert sum(got.values()) + 2.0 == pytest.approx(100.0)
    assert "the covered time in none of them 2.00s outside a build and " \
           "0.00s between the builds' parts, together 100.00s against " \
           "setup_s 100.00s" in said


def test_files_of_other_processes_do_not_count(session):
    # a stale file of another run's pid, and a file of this pid whose
    # process started before the benchmark did (the session directory
    # outlives a run and pids come round)
    session(account(pid=777))
    session(account(process_start=990.0))
    assert read_all() == dict.fromkeys(SIX)
    session(account(pid=777))
    session(account())  # this run's replica, beside the stale one
    assert read_all()["setup_runtime_init_s"] == pytest.approx(8.0)


def test_the_slowest_replica_is_reported(session):
    slow = account(pid=4243)
    slow["stages"][0]["b"] = 1016.0  # its runtime took 11 s
    session(account())
    session(slow)
    run = dict(RUN, spans=RUN["spans"] + [
        dict(RUN["spans"][0], pid=4243)])
    got = read_all(run)
    assert got["setup_runtime_init_s"] == pytest.approx(11.0)
    assert got["setup_compile_s"] == pytest.approx(9.1)


def test_none_where_the_program_writes_no_account(tmp_path, monkeypatch,
                                                  session):
    """The parent's shape: no directory, no variable, an unreadable file."""
    session({"pid": PID}, name="broken.json")
    with open(tmp_path / "setup" / "torn.json", "w") as f:
        f.write('{"pid": 42')
    assert read_all() == dict.fromkeys(SIX)
    monkeypatch.setenv("RT_SESSION_DIR", str(tmp_path / "nowhere"))
    assert read_all() == dict.fromkeys(SIX)
    monkeypatch.delenv("RT_SESSION_DIR")
    assert read_all() == dict.fromkeys(SIX)
    assert read_all({"spans": [], "e2e": {"setup_s": 1.0}}) == dict.fromkeys(
        SIX)


def test_an_account_without_a_read_call_still_gives_every_metric(session):
    doc = account()
    doc["builds"] = [{k: v for k, v in b.items()
                      if k not in ("call_a", "call_s", "ready_s")}
                     for b in doc["builds"]]
    session(doc)
    got = read_all()
    assert got["setup_first_run_s"] == 0.0
    assert all(v is not None for v in got.values())


@pytest.mark.parametrize("spans, holes, left", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 4), (3, 8)], [(0, 8)], []),
    ([(0, 4), (6, 8)], [(3, 7)], [(0, 3), (7, 8)]),
    ([(1, 2)], [], [(1, 2)]),
])
def test_intervals(spans, holes, left):
    assert su.less(spans, holes) == left
    assert su.seconds(left) == pytest.approx(sum(b - a for a, b in left))
