"""The two readers of a looped stack's spans (`ouro_step_roofline`,
`ouro_cache_share`) on spans written by hand: what they compute, and that
they return nothing, without raising, for the parent commit's spans and for
a configuration that runs its layers once."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, shapes_loop  # noqa: E402

LLM = json.load(open(os.path.join(
    ROOT, "benchmark/configs/ouro-2.6b-8l.json")))["llm_config"]
NEW = ("ouro_step_roofline", "ouro_cache_share")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def chunk(at_s, **at):
    return {"n": "engine.dispatch_chunk", "k": "engine", "a": at_s,
            "b": at_s + 0.01, "pid": 7,
            "at": {"tokens": 16, "active": 16, "kv_bound": 900, **at}}


def run_of(spans, llm=LLM, profile=None):
    return {"spans": spans, "window_wall": (0.0, 10.0), "records": [],
            "profile": profile, "device": {"kind": "TPU v5 lite"},
            "config": {"llm_config": llm, "app_kwargs": {"max_batch": 16}}}


LOOP = dict(ut_steps=4, kv_rows_full=768, kv_live_full=620.0)


def test_the_share_is_read_from_the_spans_alone(capsys):
    """Two chunks: 16 steps of 16 slots at 620 rows, 8 steps of 8 slots at
    310: a step's mean over the 24 steps."""
    run_ = run_of([chunk(1.0, **LOOP),
                   chunk(2.0, **dict(LOOP, tokens=8, active=8,
                                     kv_live_full=310.0))])
    visible = (16 * 16 * 620.0 + 8 * 8 * 310.0) / 24
    weights = sum(shapes_loop.decode_step_weight_bytes(LLM).values())
    rows = visible * 8_192 * 8 * 4
    assert manifest.layer_reader("ouro_cache_share")(run_) == pytest.approx(
        100.0 * rows / (weights + rows))
    said = capsys.readouterr().out
    assert "4 passes" in said and "3.490 GB of weights" in said
    # 16 slots at 620 rows: the issue's 43%
    assert manifest.layer_reader("ouro_cache_share")(
        run_of([chunk(1.0, **LOOP)])) == pytest.approx(42.7, abs=0.1)
    # no device trace: the roofline has no step time to divide by
    assert manifest.layer_reader("ouro_step_roofline")(run_) is None


def test_the_roofline_divides_the_least_step_by_the_traced_one(capsys):
    profile = {"devices": [{"programs": {"jit_chunk": 0.288},
                            "loop_steps": {"jit_chunk": 32}}],
               "profile_start_ns": 0.5e9, "profile_stop_ns": 3e9,
               "replica_pid": 7, "window_s": 1.0}
    run_ = run_of([chunk(1.0, **LOOP), chunk(2.0, **LOOP)], profile=profile)
    least = shapes_loop.decode_step_min_seconds(LLM, 16, 16 * 620.0, PEAK)
    got = manifest.layer_reader("ouro_step_roofline")(run_)
    assert got == pytest.approx(100.0 * least["seconds"] / 0.009)
    assert 82 < got < 83  # 7.44 ms of 9
    said = capsys.readouterr().out
    assert "bandwidth" in said and "layers_later_passes" in said
    assert "rows read over rows visible 1.239" in said
    # a loop INSIDE the step would read four steps for one: four times the
    # share, far over 100%. The program writes its passes out
    # (`tests/test_v5e_compile.py` holds it to one `while`).
    profile["devices"][0]["loop_steps"]["jit_chunk"] = 4 * 32
    assert manifest.layer_reader("ouro_step_roofline")(run_) > 300


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_attributes_leaves_the_metric_out(name):
    """What the parent commit gives (it cannot build the configuration, but
    the traced runs of the OTHER cells lay these readers over it too), and
    what the six older configurations give: chunk spans without `ut_steps`,
    an `arch` without `total_ut_steps`, none at all."""
    read = manifest.layer_reader(name)
    profile = {"devices": [{"programs": {"jit_chunk": 0.288},
                            "loop_steps": {"jit_chunk": 32}}],
               "profile_start_ns": 0.5e9, "profile_stop_ns": 3e9,
               "replica_pid": 7, "window_s": 1.0}
    older = chunk(1.0, kv_rows_full=768, kv_live_full=620.0)
    assert read(run_of([older], profile=profile)) is None
    phi3 = {"n_layers": 16, "d_model": 3072, "n_heads": 32}
    assert read(run_of([chunk(1.0, **LOOP)], llm=phi3,
                       profile=profile)) is None
    trinity = {"n_layers": 16, "arch": {"model_type": "afmoe",
                                        "layer_types": []}}
    assert read(run_of([chunk(1.0, **LOOP)], llm=trinity,
                       profile=profile)) is None
    assert read(run_of([], profile=profile)) is None
    assert read(run_of(None, profile=profile)) is None
