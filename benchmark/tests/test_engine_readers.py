"""The readers of the engine's own spans and of the host's phases: on a
recorded span list (`data/spans_engine_stages.json`: four requests of a
traced `tiny.open` run on the CPU, every stage of each), on a recorded slice
of a v5e trace as plain data (`data/trace_v5e_host_slice.json`: the host
plane's `engine.*` annotations and the intervals in which the device was
busy), on hand-made planes, and on the runs that must not make a
reader raise: an empty one, one without a profile, one with spans of four
replicas and a profile of one. Run by hand: `pytest benchmark/tests -q`."""

import json
import os
import statistics
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import engine_spans as es, host_phases as hp  # noqa: E402
from benchmark import host_trace, manifest  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("engine_queue_ms", "ready_wait_ms", "first_token_read_ms",
       "sched_host_ms", "idle_host_share")


def reader(name):
    fn = manifest.layer_reader(name)
    assert fn is not None, name
    return fn


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.fixture()
def run():
    doc = load("spans_engine_stages.json")
    return {"spans": doc["spans"], "window_wall": tuple(doc["window_wall"]),
            "records": [types.SimpleNamespace(**r) for r in doc["records"]],
            "profile": None, "device": {"kind": "cpu"},
            "config": {"app_kwargs": {"max_batch": 4}}}


def durations_ms(run, name):
    return [(s["b"] - s["a"]) * 1e3 for s in run["spans"] if s["n"] == name]


# ------------------------------------------------------------ span readers
@pytest.mark.parametrize("metric, span", [
    ("engine_queue_ms", "engine.queue"),
    ("ready_wait_ms", "engine.ready_wait"),
    ("first_token_read_ms", "engine.first_token")])
def test_a_stage_metric_is_the_median_of_its_span(run, metric, span):
    durs = durations_ms(run, span)
    assert len(durs) == 4
    assert reader(metric)(run) == pytest.approx(statistics.median(durs))


def test_a_stage_outside_the_window_is_not_counted(run):
    roots = sorted((s for s in run["spans"] if s["n"].startswith("http POST")),
                   key=lambda s: s["a"])
    run["window_wall"] = (roots[2]["a"] - 1e-4, run["window_wall"][1])
    late = {r["t"] for r in roots[2:]}
    durs = [(s["b"] - s["a"]) * 1e3 for s in run["spans"]
            if s["n"] == "engine.queue" and s["t"] in late]
    assert len(durs) == 2
    assert reader("engine_queue_ms")(run) == pytest.approx(
        statistics.median(durs))


def test_the_split_of_the_time_to_first_token_is_printed(run, capsys):
    value = reader("first_token_read_ms")(run)
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if "time to first token" in ln)
    parts = {"admit_wait": reader("admit_wait_ms")(run),
             "prefill_dispatch": statistics.median(
                 durations_ms(run, "engine.prefill")),
             "ready_wait": reader("ready_wait_ms")(run), "first_token": value}
    for name, v in parts.items():
        assert f"{name} {v:.1f}" in line
    ttft = reader("ttft_p50_ms")(run)
    assert f"sum {sum(parts.values()):.1f} of ttft_p50_ms {ttft:.1f}" in line
    # on this recorded run the stages cover the time to first token but for
    # the way back to the client
    assert 0 <= ttft - sum(parts.values()) < 0.5 * ttft
    assert "chunks in flight at the splice: median" in out


def test_sched_host_is_the_mean_of_the_hosts_own_phases(run, capsys):
    its = [s["at"] for s in run["spans"] if s["n"] == "engine.iteration"]
    assert len(its) >= 5
    want = statistics.mean(a["admit_ms"] + a["dispatch_ms"] + a["deliver_ms"]
                           for a in its)
    assert reader("sched_host_ms")(run) == pytest.approx(want)
    out = capsys.readouterr().out
    assert f"{len(its)} passes of 1 replica(s)" in out
    assert "of the window's wall time sync" in out


def test_an_iteration_without_its_phases_is_left_out(run):
    for s in run["spans"]:
        if s["n"] == "engine.iteration":
            del s["at"]["deliver_ms"]
            break
    assert len(es.iterations(run)) == sum(
        s["n"] == "engine.iteration" for s in run["spans"]) - 1
    assert reader("sched_host_ms")(run) > 0


# ---------------------------------------------- runs that must not raise
def empty_run():
    return {"spans": [], "window_wall": (0.0, 1.0), "records": [],
            "profile": None, "device": {"kind": "TPU v5 lite"},
            "config": {"app_kwargs": {"max_batch": 8}}}


def four_replicas(run):
    """The recorded requests as four replicas would have recorded them, and
    the profile of one of them as `trace_reduce` gives it (no device)."""
    spans = []
    for i in range(4):
        for s in run["spans"]:
            spans.append(dict(s, t=f"{i}{s['t']}", pid=1000 + i,
                              at=dict(s.get("at") or {})))
    lo, hi = run["window_wall"]
    prof = {"profile_start_ns": int(lo * 1e9), "profile_stop_ns": int(hi * 1e9),
            "window_s": 0.0, "devices": [], "replica_pid": 1000,
            "replicas_traced": 1, "bytes": 1}
    return dict(run, spans=spans, profile=prof)


def parent_program(run):
    """What a program without this PR's spans leaves: the three old names."""
    old = ("engine.prefill", "engine.dispatch_chunk", "engine.host_sync")
    return dict(run, spans=[s for s in run["spans"]
                            if s["k"] == "request" or s["n"] in old])


def odd(run):
    """Spans that lack what a reader looks for."""
    spans = [dict(s) for s in run["spans"]]
    for s in spans:
        if s["n"].startswith("engine."):
            s.pop("at", None)
    return dict(run, spans=spans + [{"n": "engine.queue", "k": "engine"}])


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("case", ["empty", "no-profile", "four-replicas",
                                  "parent-program", "odd-spans"])
def test_no_new_reader_raises(run, name, case, monkeypatch, tmp_path):
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))  # no trace there
    made = {"empty": empty_run, "no-profile": lambda: run,
            "four-replicas": lambda: four_replicas(run),
            "parent-program": lambda: parent_program(run),
            "odd-spans": lambda: odd(run)}[case]()
    value = reader(name)(made)
    assert value is None or isinstance(value, float)
    if case in ("empty", "parent-program") or name == "idle_host_share":
        assert value is None
    elif case in ("no-profile", "four-replicas"):
        assert value >= 0


def test_four_replicas_count_as_four_in_the_shares(run, capsys):
    one = reader("sched_host_ms")(run)
    assert reader("sched_host_ms")(four_replicas(run)) == pytest.approx(one)
    assert "passes of 4 replica(s)" in capsys.readouterr().out


def test_a_reader_that_would_raise_returns_nothing_and_says_so(capsys):
    @es.never_raises
    def read(run):
        return run["no such key"]

    assert read({}) is None
    assert "KeyError" in capsys.readouterr().out


# ------------------------------------------------- the host's phases: data
def hand_made():
    """One device, busy 0-100, 150-300, 400-1000 (ns); the scheduler in
    admit 90-160, dispatch 160-170, sync 170-420, deliver 420-430; the
    prefill lane dispatching 280-350."""
    host = [["engine.admit", 90, 70], ["engine.dispatch", 160, 10, 5_000_160],
            ["engine.sync", 170, 250], ["engine.deliver", 420, 10],
            ["PjitFunction(chunk)", 161, 5]]
    lane = [["engine.prefill_dispatch", 280, 70]]
    ops = [["%a", 0, 100], ["%b", 150, 150], ["%c", 400, 600],
           ["%d", 900, 100]]
    return [{"name": "/host:CPU", "lines": [
                {"name": "python", "events": host},
                {"name": "python", "events": lane}]},
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules",
                 "events": [["jit_chunk(123)", 0, 1000]]},
                {"name": "XLA Ops", "events": ops}]}]


def test_idle_seconds_fall_into_the_phase_that_covers_them():
    got = hp.reduce_host(hand_made())
    assert got["host"]["admit"] == {"events": 1, "seconds": 70e-9}
    assert got["wall_offset_ns"] == 5_000_000
    dev, = got["devices"]
    assert dev["idle_s"] == pytest.approx(150e-9)  # 100-150 and 300-400
    by = {k: round(v * 1e9) for k, v in dev["idle_by_phase_s"].items()}
    assert by == {"admit": 50, "dispatch": 0, "sync": 100, "deliver": 0,
                  "idle_wait": 0, "none": 0}
    assert dev["idle_in_prefill_dispatch_s"] == pytest.approx(50e-9)
    # the host's own work: admit's 50 and the lane's 300-350
    assert dev["idle_in_host_work_s"] == pytest.approx(100e-9)
    assert [g[2] for g in dev["gaps"]] == ["sync", "admit"]
    assert dev["gaps"][0][:2] == [300, pytest.approx(100e-9)]


def test_a_recorded_slice_of_a_v5e_trace_is_attributed():
    """0.3 s of `phi3.chat-saturated` on the chip: the device idles while
    the scheduler splices (engine.admit), and the longest gap begins while
    the host still waits for the chunk the device has just finished."""
    got = hp.reduce_host(load("trace_v5e_host_slice.json")["planes"])
    assert set(got["host"]) == {"admit", "deliver", "dispatch",
                                "prefill_dispatch", "sync"}
    dev, = got["devices"]
    by = dev["idle_by_phase_s"]
    assert dev["idle_s"] == pytest.approx(0.019386856)
    assert sum(by.values()) == pytest.approx(dev["idle_s"])
    assert by["none"] < 0.01 * dev["idle_s"]
    assert by["admit"] == pytest.approx(0.014588878)
    assert max(by, key=by.get) == "admit"
    assert dev["idle_in_host_work_s"] == pytest.approx(0.016346598)
    assert dev["gaps"][0][1:3] == [pytest.approx(0.00557809), "sync"]
    assert [g[2] for g in dev["gaps"][1:6]] == ["admit"] * 5
    # the trace's clock starts at the wall time the dispatch events carry
    assert got["wall_offset_ns"] == pytest.approx(1.790520329524e18, rel=1e-9)


def test_a_trace_without_the_engines_events_attributes_nothing():
    planes = hand_made()
    planes[0]["lines"] = [{"name": "python",
                           "events": [["PjitFunction(chunk)", 161, 5]]}]
    got = hp.reduce_host(planes)
    assert got["host"] == {} and got["wall_offset_ns"] is None
    dev, = got["devices"]
    assert "idle_by_phase_s" not in dev and dev["idle_s"] > 0
    assert hp.reduce_host([]) == {"host": {}, "devices": [],
                                  "wall_offset_ns": None}


# ------------------------------------------ the trace readers end to end
@pytest.fixture()
def traced(run, monkeypatch):
    """A run whose trace is the hand-made one: the child is replaced by the
    reduction itself, and counted."""
    calls = []

    def reduce_file(path):
        calls.append(path)
        return hp.reduce_host(hand_made())

    monkeypatch.setattr(host_trace, "trace_file", lambda: "some.xplane.pb")
    monkeypatch.setattr(host_trace, "reduce_file", reduce_file)
    lo, hi = run["window_wall"]
    run["profile"] = {"profile_start_ns": int(lo * 1e9),
                      "profile_stop_ns": int(hi * 1e9), "devices": [],
                      "window_s": 0.0, "replica_pid": None}
    return run, calls


def test_idle_host_share_runs_the_child_once(traced, capsys):
    run, calls = traced
    assert reader("idle_host_share")(run) == pytest.approx(100 * 100 / 150)
    assert reader("idle_host_share")(run) == pytest.approx(100 * 100 / 150)
    assert len(calls) == 1  # the child runs at most once in a run
    out = capsys.readouterr().out
    assert "attributed to a phase 100.0%" in out
    assert "gap of 0.000 ms at +0.000s: sync" in out


def test_without_a_trace_file_the_child_is_not_run(run, monkeypatch,
                                                   tmp_path):
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    monkeypatch.setattr(host_trace, "reduce_file",
                        lambda path: pytest.fail("ran the child"))
    run["profile"] = {"devices": []}
    assert host_trace.trace_file() is None
    assert host_trace.host_phases(run) is None
    assert host_trace.device(run) is None


def test_a_child_that_fails_gives_nothing(tmp_path, capsys):
    bad = tmp_path / "not-a-trace.xplane.pb"
    bad.write_bytes(b"\x00not a trace")
    got = host_trace.reduce_file(str(bad))
    # an unreadable file is an empty trace or a failed child: never a raise
    assert got is None or got["devices"] == []


# ---------------------------------------------------------- the manifests
def test_the_tiny_traced_manifest_lists_the_real_manifests_metrics():
    real = manifest._read(os.path.join(ROOT, "BENCHMARK.json"))
    tiny = manifest._read(os.path.join(
        ROOT, "benchmark/tests/BENCHMARK.tiny-traced.json"))
    assert ([m["name"] for m in tiny["per_layer"]]
            == [m["name"] for m in real["per_layer"]])
    for m in real["per_layer"]:
        assert manifest.layer_reader(m["name"]) is not None
    by_name = {m["name"]: m for m in real["per_layer"]}
    for name in NEW:
        assert name in by_name
    assert by_name["engine_queue_ms"]["workloads"] == ["phi3.chat-steady"]
    assert "workloads" not in by_name["sched_host_ms"]
