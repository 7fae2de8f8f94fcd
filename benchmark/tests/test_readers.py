"""The per-layer readers on a recorded span list (one streamed request on
the CPU, as the controller's trace index returned it) and on hand-made
profiles. Run by hand: `pytest benchmark/tests -q`."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture()
def run():
    with open(os.path.join(DATA, "spans_one_request.json")) as f:
        spans = json.load(f)
    root = next(s for s in spans if s["n"].startswith("http POST"))
    config = manifest._read(os.path.join(
        ROOT, "benchmark/configs/phi3-mini-16l.json"))
    rec = types.SimpleNamespace(due=10.0, sent=10.002, ok=True, plen=200,
                                n_tokens=40)
    return {"spans": spans, "window_wall": (root["a"] - 1, root["b"] + 1),
            "records": [rec], "config": config, "profile": None,
            "device": {"kind": "TPU v5 lite"}}


def reader(name):
    fn = manifest.layer_reader(name)
    assert fn is not None, name
    return fn


def test_every_per_layer_metric_of_the_manifest_has_a_reader():
    man = manifest._read(os.path.join(ROOT, "BENCHMARK.json"))
    for m in man["per_layer"]:
        assert manifest.layer_reader(m["name"]) is not None, m["name"]
    assert manifest.layer_reader("no_such_metric") is None


def test_admit_wait_is_prefill_start_less_root_start(run):
    spans = run["spans"]
    root = next(s for s in spans if s["n"].startswith("http POST"))
    pre = next(s for s in spans if s["n"] == "engine.prefill")
    want = (pre["a"] - root["a"]) * 1000.0
    assert 0 < want < 1000
    assert reader("admit_wait_ms")(run) == pytest.approx(want)


def test_host_sync_is_the_median_span_duration(run):
    durs = sorted((s["b"] - s["a"]) * 1000.0 for s in run["spans"]
                  if s["n"] == "engine.host_sync")
    assert len(durs) == 3
    assert reader("host_sync_ms")(run) == pytest.approx(durs[1])


def test_batch_occupancy_is_active_over_max_batch(run):
    # three chunks, one request active in each, 8 slots
    assert reader("batch_occupancy")(run) == pytest.approx(1 / 8)


def test_loadgen_late_needs_a_due_time(run):
    assert reader("loadgen_late_ms")(run) == pytest.approx(2.0)
    run["records"][0].due = None  # a closed loop
    assert reader("loadgen_late_ms")(run) is None


def test_time_to_first_token_readers_take_it_from_the_due_time(run):
    rec = run["records"][0]
    rec.t_first = rec.due + 0.25
    assert reader("ttft_p50_ms")(run) == pytest.approx(250.0)
    assert reader("ttft_p95_ms")(run) == pytest.approx(250.0)
    rec.due = None  # a closed loop has no due time
    assert reader("ttft_p50_ms")(run) is None


def test_readers_return_nothing_outside_the_window_or_without_a_trace(run):
    run["window_wall"] = (0.0, 1.0)
    for name in ("admit_wait_ms", "host_sync_ms", "batch_occupancy"):
        assert reader(name)(run) is None
    for name in ("prefill_dev_share", "decode_step_ms",
                 "decode_step_roofline"):
        assert reader(name)(run) is None


def test_device_readers_on_a_hand_made_profile(run):
    chunks = [s for s in run["spans"] if s["n"] == "engine.dispatch_chunk"]
    steps = sum(c["at"]["tokens"] for c in chunks)  # 8 + 8 + 4
    lo = min(c["a"] for c in chunks) - 0.01
    hi = max(c["a"] for c in chunks) + 0.01
    run["profile"] = {
        "profile_start_ns": int(lo * 1e9), "profile_stop_ns": int(hi * 1e9),
        "window_s": hi - lo, "replica_pid": chunks[0]["pid"],
        "devices": [{"busy_s": 1.0, "loop_steps": {"jit_chunk": 30},
                     "programs": {
            "jit_chunk": 0.6, "jit_prefill": 0.15, "jit_place": 0.04,
            "jit_sample1": 0.01, "jit_other": 0.2}}]}
    assert steps == 20  # the spans' own count is only printed beside it
    assert reader("prefill_dev_share")(run) == pytest.approx(20.0)
    step_ms = reader("decode_step_ms")(run)
    assert step_ms == pytest.approx(1000 * 0.6 / 30)
    # one slot active at a context of 200 + 40 / 2 = 220 rows
    from benchmark import peaks, shapes
    llm = run["config"]["llm_config"]
    least = shapes.decode_step_min_seconds(llm, 8, 220.0,
                                           peaks.peaks("TPU v5 lite"))
    assert reader("decode_step_roofline")(run) == pytest.approx(
        100 * least["seconds"] / (step_ms / 1000))
    # the slots active come from the traced replica's own chunks
    run["profile"]["replica_pid"] = -1
    assert reader("decode_step_roofline")(run) is None
