"""The three readers of EVA attention's spans (`eva_step_roofline`,
`eva_walk_over_visible`, `eva_summary_row_share`) on spans written by hand:
what they compute, and that they return nothing, without raising, for the
parent commit's spans and for a configuration without EVA attention."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, shapes_eva  # noqa: E402

LLM = json.load(open(os.path.join(
    ROOT, "benchmark/configs/evabyte-pp4-8l.json")))["llm_config"]
NEW = ("eva_step_roofline", "eva_walk_over_visible", "eva_summary_row_share")


def chunk(at_s, **at):
    return {"n": "engine.dispatch_chunk", "k": "engine", "a": at_s,
            "b": at_s + 0.01, "pid": 7,
            "at": {"tokens": 16, "active": 16, "kv_bound": 5000, **at}}


def run_of(spans, llm=LLM, profile=None):
    return {"spans": spans, "window_wall": (0.0, 10.0), "records": [],
            "profile": profile, "device": {"kind": "TPU v5 lite"},
            "config": {"llm_config": llm, "app_kwargs": {"max_batch": 16}}}


BOTH = dict(kv_rows_window=2048, kv_rows_chunks=512, kv_live_window=1024.0,
            kv_live_chunks=256.0)


def test_the_walk_and_the_share_are_read_from_the_spans_alone(capsys):
    run_ = run_of([chunk(1.0, **BOTH),
                   chunk(2.0, **dict(BOTH, kv_rows_chunks=256, active=8))])
    walked = 256 * (2048 + 512) + 128 * (2048 + 256)
    visible = (256 + 128) * (1024.0 + 256.0)
    assert manifest.layer_reader("eva_walk_over_visible")(run_) == (
        walked / visible)
    assert manifest.layer_reader("eva_summary_row_share")(run_) == (
        pytest.approx(100.0 * 256 / 1280))
    said = capsys.readouterr().out
    assert "window leaf 2.000" in said and "1024 window rows" in said
    # no device trace: the roofline has no step time to divide by
    assert manifest.layer_reader("eva_step_roofline")(run_) is None


def test_the_roofline_divides_the_least_step_by_the_traced_one(capsys):
    profile = {"devices": [{"programs": {"jit_chunk": 0.32},
                            "loop_steps": {"jit_chunk": 32}}],
               "profile_start_ns": 0.5e9, "profile_stop_ns": 3e9,
               "replica_pid": 7, "window_s": 1.0}
    run_ = run_of([chunk(1.0, **BOTH), chunk(2.0, **BOTH)], profile=profile)
    least = shapes_eva.decode_step_min_seconds(
        LLM, 16, 16 * 1024.0, 16 * 256.0,
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    got = manifest.layer_reader("eva_step_roofline")(run_)
    assert got == pytest.approx(100.0 * least["seconds"] / 0.010)
    assert 70 < got < 76  # 7.3 ms of 10
    said = capsys.readouterr().out
    assert "bandwidth" in said and "summary_rows" in said


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_attributes_leaves_the_metric_out(name):
    """What the parent commit gives (it cannot build the configuration, but
    the traced runs of the OTHER cells lay these readers over it too), and
    what the five older configurations give: chunk spans without the
    summaries' rows, an `arch` without `attention_class`, none at all."""
    read = manifest.layer_reader(name)
    older = chunk(1.0, kv_rows_full=4096, kv_rows_window=2048,
                  kv_live_full=3000.0, kv_live_window=2048.0)
    assert read(run_of([older])) is None
    trinity = {"n_layers": 16, "arch": {"model_type": "afmoe",
                                        "layer_types": [], "sliding_window": 2048}}
    assert read(run_of([chunk(1.0, **BOTH)], llm=trinity)) is None
    assert read(run_of([chunk(1.0, **BOTH)], llm={"n_layers": 2})) is None
    assert read(run_of([])) is None
    assert read(run_of(None)) is None
