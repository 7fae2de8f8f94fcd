"""The three readers of `sdar.solve-saturated` on a recorded span list (one
streamed request on the CPU by a program from before a forward was counted:
what the parent commit gives them) and on hand-made spans at the published
configuration. Run by hand: `pytest benchmark/tests -q`."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, peaks, shapes_blockdiff as sh  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("blockdiff_step_roofline", "blockdiff_tokens_per_forward",
       "blockdiff_expert_rows_per_step")
CONFIG = manifest._read(os.path.join(
    ROOT, "benchmark/configs/sdar-30b-a3b-pp8-6l.json"))


def test_the_manifest_lists_the_three_for_the_new_cell_alone():
    man = manifest._read(os.path.join(ROOT, "BENCHMARK.json"))
    mine = {m["name"]: m for m in man["per_layer"] if m["name"] in NEW}
    assert set(mine) == set(NEW)
    for m in mine.values():
        assert m["workloads"] == ["sdar.solve-saturated"]
        assert m["moves"] == "tpot_p95_ms"
        assert manifest.layer_reader(m["name"]) is not None
    cell = manifest.load_cell(os.path.join(ROOT, "BENCHMARK.json"),
                              "sdar.solve-saturated")
    assert {m["name"] for m in cell["end_to_end"]} == {"tpot_p95_ms",
                                                       "setup_s"}
    assert set(NEW) <= {m["name"] for m in cell["per_layer"]}
    assert cell["traffic"]["driver"].endswith("serve_http_preflight.py")
    assert cell["config"]["app_kwargs"]["max_batch"] == 32
    assert man["workloads"][-1]["name"] == "sdar.solve-saturated"


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
    forwards, layers, rows = 16, 6, 32 * 4 * 8
    return [{"n": "engine.dispatch_chunk", "k": "engine", "a": 10.0 + seq,
             "b": 10.1 + seq, "pid": 1,
             "at": {"tokens": forwards, "active": 30, "kv_live_full": 500.0,
                    "kv_rows_full": 1024, "block_length": 4, "seq": seq}},
            {"n": "engine.host_sync", "k": "engine", "a": 10.5 + seq,
             "b": 10.6 + seq,
             "at": {"seq": seq, "tokens": forwards, "moe_steps": forwards,
                    "moe_rows": forwards * layers * rows,
                    "moe_rows_busiest": 900,
                    "moe_touched": forwards * touched,
                    "bd_forwards": forwards * 30, "bd_commits": 96,
                    "bd_tokens": 384, "bd_freed": 384}}]


def test_the_readers_arithmetic_at_the_published_configuration(capsys):
    spans = chunk(0, 750) + chunk(1, 760)
    profile = {"devices": [{"programs": {"jit_chunk": 32 * 0.0125},
                            "loop_steps": {"jit_chunk": 32},
                            "ops": [["custom-call bf16[32,128,128]", 0.02],
                                    ["fusion bf16[128,151936]", 0.1]]}],
               "profile_start_ns": 9.5e9, "profile_stop_ns": 10.5e9,
               "replica_pid": 1}  # the profiler saw chunk 0's dispatch only
    run = {"spans": spans, "window_wall": (0.0, 20.0), "records": [],
           "profile": profile, "device": {"kind": "TPU v5 lite"},
           "config": CONFIG}
    assert manifest.layer_reader("blockdiff_expert_rows_per_step")(run) == 8.0
    assert manifest.layer_reader("blockdiff_tokens_per_forward")(
        run) == pytest.approx(0.8)
    got = manifest.layer_reader("blockdiff_step_roofline")(run)
    least = sh.forward_min_seconds(
        CONFIG["llm_config"], 32, 30 * 500.0, peaks.peaks("TPU v5 lite"),
        expert_rows=6 * 1024.0, touched=750.0)  # chunk 0's, not the window's
    assert got == pytest.approx(100 * least["seconds"] / 0.0125)
    assert 70 < got < 80
    said = capsys.readouterr().out
    assert "750.00 of 768 held experts touched" in said
    assert "routed_experts 7.078" in said and "bound by bandwidth" in said
    assert "the ragged kernel's calls take 5.0% of a forward" in said
    assert "4.00 forwards a block" not in said and "5.00 forwards a block" in said


def test_a_count_that_contradicts_the_others_gives_no_share():
    """More experts touched than rows routed: no count to go by."""
    spans = chunk(0, 750)
    spans[1]["at"]["moe_rows"] = 100
    profile = {"devices": [{"programs": {"jit_chunk": 0.2},
                            "loop_steps": {"jit_chunk": 16}, "ops": []}],
               "profile_start_ns": 9.5e9, "profile_stop_ns": 10.5e9,
               "replica_pid": 1}
    run = {"spans": spans, "window_wall": (0.0, 20.0), "records": [],
           "profile": profile, "device": {"kind": "TPU v5 lite"},
           "config": CONFIG}
    assert manifest.layer_reader("blockdiff_step_roofline")(run) is None
