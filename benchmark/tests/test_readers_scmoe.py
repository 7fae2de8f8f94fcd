"""The three readers of `longcat-flash.assistant-saturated` on a recorded
span list (one streamed request on the CPU by a program from before the
identity experts were counted: what the parent commit gives them) and on
hand-made spans at the published configuration. Run by hand:
`pytest benchmark/tests -q`."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, peaks, shapes_scmoe as sh  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("scmoe_step_roofline", "scmoe_expert_rows_per_step",
       "zero_expert_share")
CONFIG = manifest._read(os.path.join(
    ROOT, "benchmark/configs/longcat-flash-ep32-4l.json"))


def test_the_manifest_lists_the_three_for_the_new_cell_alone():
    man = manifest._read(os.path.join(ROOT, "BENCHMARK.json"))
    mine = {m["name"]: m for m in man["per_layer"] if m["name"] in NEW}
    assert set(mine) == set(NEW)
    for m in mine.values():
        assert m["workloads"] == ["longcat-flash.assistant-saturated"]
        assert m["moves"] == "tpot_p95_ms"
        assert manifest.layer_reader(m["name"]) is not None
    cell = manifest.load_cell(os.path.join(ROOT, "BENCHMARK.json"),
                              "longcat-flash.assistant-saturated")
    assert {m["name"] for m in cell["end_to_end"]} == {"tpot_p95_ms",
                                                       "setup_s"}
    assert set(NEW) <= {m["name"] for m in cell["per_layer"]}
    assert cell["traffic"]["driver"].endswith("serve_http_preflight.py")


def test_a_parents_spans_give_none():
    with open(os.path.join(DATA, "spans_one_request.json")) as f:
        spans = json.load(f)
    root = next(s for s in spans if s["n"].startswith("http POST"))
    run = {"spans": spans, "window_wall": (root["a"] - 1, root["b"] + 1),
           "records": [], "config": CONFIG, "profile": None,
           "device": {"kind": "TPU v5 lite"}}
    for name in NEW:
        assert manifest.layer_reader(name)(run) is None


def chunk(seq, touched):
    steps, layers, batch, picks = 16, 4, 32, 12
    return [{"n": "engine.dispatch_chunk", "k": "engine", "a": 10.0 + seq,
             "b": 10.1 + seq, "pid": 1,
             "at": {"tokens": steps, "active": 30, "kv_live_full": 700.0,
                    "kv_rows_full": 1024, "seq": seq}},
            {"n": "engine.host_sync", "k": "engine", "a": 10.5 + seq,
             "b": 10.6 + seq,
             "at": {"seq": seq, "tokens": steps, "moe_steps": steps,
                    "moe_rows": steps * layers * 8, "moe_rows_busiest": 40,
                    "moe_picks": steps * layers * batch * picks,
                    "moe_zero_picks": steps * layers * batch * 4,
                    "moe_touched": steps * touched}}]


def test_the_readers_arithmetic_at_the_published_configuration(capsys):
    spans = chunk(0, 25) + chunk(1, 27)
    profile = {"devices": [{"programs": {"jit_chunk": 32 * 0.016},
                            "loop_steps": {"jit_chunk": 32}}],
               "profile_start_ns": 9.5e9, "profile_stop_ns": 10.5e9,
               "replica_pid": 1}  # the profiler saw chunk 0's dispatch only
    run = {"spans": spans, "window_wall": (0.0, 20.0), "records": [],
           "profile": profile, "device": {"kind": "TPU v5 lite"},
           "config": CONFIG}
    assert manifest.layer_reader("scmoe_expert_rows_per_step")(run) == 8.0
    assert manifest.layer_reader("zero_expert_share")(run) == pytest.approx(
        1 / 3)
    got = manifest.layer_reader("scmoe_step_roofline")(run)
    least = sh.decode_step_min_seconds(
        CONFIG["llm_config"], 32, 30 * 700.0, peaks.peaks("TPU v5 lite"),
        expert_rows=32.0, touched=25.0)  # chunk 0's, not the window's 26
    assert got == pytest.approx(100 * least["seconds"] / 0.016)
    assert 50 < got < 65
    said = capsys.readouterr().out
    assert "25.00 of 64 held experts touched" in said
    assert "routed_experts 1.887" in said and "bound by bandwidth" in said
    assert "8.00 real experts a token of 12" in said
