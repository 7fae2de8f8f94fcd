"""The benchmark's own arithmetic on hand-made samples: percentiles, time
per output token, missed requests, the traffic generator's steadiness, the
span readers on a recorded span list, the trace reduction on a small recorded
trace. Run by hand: `pytest benchmark/tests -q` (they are not part of
tests/)."""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, peaks, shapes, stats, trace_reduce  # noqa: E402
from benchmark import traffic as traffic_mod  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# ------------------------------------------------------------------ stats
@pytest.mark.parametrize("q, want", [(0, 1.0), (50, 3.0), (100, 5.0),
                                     (25, 2.0), (95, 4.8)])
def test_percentile_interpolates_between_ranks(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error_not_zero():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_tpot_is_the_pace_of_the_whole_answer():
    # 17 tokens, the first at 1.0 s and the last at 1.8 s: 16 gaps of 50 ms,
    # however the tokens were grouped into SSE events.
    assert stats.tpot_ms(1.0, 1.8, 17) == pytest.approx(50.0)
    assert stats.tpot_ms(1.0, 1.0, 1) is None


def test_a_missed_request_pushes_the_tail_up():
    sample = stats.with_missed([10.0] * 19, 1)
    assert stats.percentile(sample, 50) == 10.0
    assert stats.percentile(sample, 99) == math.inf


def test_summary_names_the_highest_percentile_with_ten_beyond():
    assert stats.summary(range(100))["highest_supported"] == "p90"
    assert stats.summary(range(200))["highest_supported"] == "p95"
    assert stats.summary(range(1000))["highest_supported"] == "p99"
    assert "highest_supported" not in stats.summary(range(50))


# ---------------------------------------------------------------- traffic
def _mix(**kw):
    with open(os.path.join(ROOT, "benchmark/traffic/chat-saturated.json")) as f:
        mix = json.load(f)
    mix.update(kw)
    return mix


def test_lengths_follow_the_stated_distribution():
    sizes = traffic_mod.size_pool(_mix())
    prompts = sorted(p for p, _ in sizes)
    answers = sorted(a for _, a in sizes)
    assert len(sizes) == 64
    assert 32 <= prompts[0] and prompts[-1] <= 1024
    assert 16 <= answers[0] and answers[-1] <= 192
    assert 170 <= prompts[32] <= 215  # the median is 192
    assert 58 <= answers[32] <= 70    # the median is 64


def test_every_seed_offers_the_same_sizes_in_another_order():
    mix = _mix()

    def first_pass(seed):
        it = traffic_mod.requests(mix, 32064, seed)
        return [(len(r["body"]["prompt"]), r["body"]["max_tokens"])
                for r in (next(it) for _ in range(64))]

    a, b = first_pass(1), first_pass(2**31 + 5)
    assert a != b and sorted(a) == sorted(b)
    assert first_pass(1) == a  # the same seed, the same requests


def test_open_loop_gaps_have_the_stated_mean_for_every_seed():
    mix = _mix(loop="open", rate_req_s=4.0, arrivals="poisson")
    for seed in (0, 7, 2**31 + 1):
        it = traffic_mod.requests(mix, 100, seed)
        due = [next(it)["due_s"] for _ in range(128)]
        assert due == sorted(due)
        assert due[63] == pytest.approx(64 / 4.0)   # one pass of the pool
        assert due[127] == pytest.approx(128 / 4.0)


def test_bursts_arrive_together_at_the_stated_mean_rate():
    mix = _mix(loop="open", rate_req_s=3.0, arrivals="bursts",
               burst_size=12, burst_window_s=1.0, pool=48)
    it = traffic_mod.requests(mix, 100, 3)
    due = [next(it)["due_s"] for _ in range(48)]
    assert due[11] - due[0] == pytest.approx(11 / 12)
    assert due[12] - due[0] == pytest.approx(12 / 3.0)
    assert due[47] == pytest.approx(due[0] + 36 / 3.0 + 11 / 12)


def test_request_bodies_carry_the_sampling_fields_and_a_seed_of_their_own():
    it = traffic_mod.requests(_mix(), 32064, 2**31 + 17)
    a, b = next(it)["body"], next(it)["body"]
    assert a["stream"] is True and a["temperature"] == 0.7
    assert a["top_k"] == 50 and a["top_p"] == 1.0
    assert a["seed"] != b["seed"] and 0 <= a["seed"] < 2**31
    assert all(0 <= t < 32064 for t in a["prompt"])


def test_warmup_is_the_same_in_every_run():
    a = traffic_mod.warmup_bodies(_mix(), 32064)
    assert a == traffic_mod.warmup_bodies(_mix(), 32064)
    assert [w["check"] for w in a].count(True) == 2
    assert all(w["body"]["temperature"] == 0.0 for w in a if w["check"])


# ----------------------------------------------------------------- shapes
def test_phi3_mini_16_layers_is_1_91_billion_parameters():
    llm = manifest._read(os.path.join(
        ROOT, "benchmark/configs/phi3-mini-16l.json"))["llm_config"]
    assert shapes.d_ff(3072) == 8192
    assert shapes.param_count(llm) == pytest.approx(1.91e9, rel=0.005)
    assert shapes.decode_step_weight_bytes(llm) == pytest.approx(3.82e9,
                                                                 rel=0.005)
    # 8 sequences of 250 valid rows: 2000 rows x 2 x 16 layers x 3072 x 2 B
    assert shapes.decode_step_cache_bytes(llm, 2000) == 2000 * 2 * 16 * 3072 * 2
    least = shapes.decode_step_min_seconds(llm, 8, 2000,
                                           peaks.peaks("TPU v5 lite"))
    assert least["bound"] == "bandwidth"
    assert least["seconds"] == pytest.approx(4.21e9 / 819e9, rel=0.01)


def test_an_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks.peaks("TPU v5e")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9")


# ---------------------------------------------------------- trace reduction
def test_busy_union_merges_overlaps_and_nesting():
    # [0,10) with [2,5) nested, [8,12) overlapping, [20,25) apart: 12 + 5
    iv = [(0, 10), (2, 5), (8, 12), (20, 25)]
    assert trace_reduce.union_seconds(iv) == pytest.approx(17e-9)
    assert trace_reduce.gaps(iv, 0, 30) == [(12, 8), (25, 5)]


def test_self_time_takes_nested_operations_out_of_their_parent():
    # a `while` of 100 ns that holds two bodies of 30 ns; then a lone op.
    evs = [["while", 0, 100], ["fusion", 10, 30], ["fusion", 50, 30],
           ["copy", 200, 40]]
    got = trace_reduce.self_times(evs)
    assert got["while"] == pytest.approx(40e-9)
    assert got["fusion"] == pytest.approx(60e-9)
    assert got["copy"] == pytest.approx(40e-9)


def test_program_names_lose_their_fingerprint():
    assert trace_reduce.program_name("jit_chunk(123456789)") == "jit_chunk"
    assert trace_reduce.program_name("jit_prefill") == "jit_prefill"


def test_reduction_of_a_hand_made_device_plane():
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_chunk(1)", 0, 400], ["jit_prefill(2)", 500, 100],
            ["jit_chunk(1)", 700, 300]]},
        {"name": "XLA Ops", "events": [
            ["while.1", 0, 400], ["fusion.3", 0, 150], ["fusion.3", 200, 150],
            ["dot.1", 500, 100], ["while.1", 700, 300]]}]},
        {"name": "/host:CPU", "lines": []}]
    red = trace_reduce.reduce_planes(planes, 1_000_000_000, 1_000_001_000)
    (dev,) = red["devices"]
    assert dev["busy_s"] == pytest.approx(800e-9)
    assert dev["programs"]["jit_chunk"] == pytest.approx(700e-9)
    assert dev["programs"]["jit_prefill"] == pytest.approx(100e-9)
    assert dev["program_runs"] == {"jit_chunk": 2, "jit_prefill": 1}
    # fusion.3 ran twice inside the first chunk: two steps; one in the second
    assert dev["loop_steps"] == {"jit_chunk": 3, "jit_prefill": 1}
    assert dev["gaps"][0] == [400, pytest.approx(100e-9)]
    assert red["window_s"] == pytest.approx(1e-6)
    # idle share over the device's own extent: 1 - 800 / 1000
    extent = (dev["last_ns"] - dev["first_ns"]) / 1e9
    assert 1 - dev["busy_s"] / extent == pytest.approx(0.2)


def test_reduction_of_a_slice_recorded_on_the_v5e():
    """75 ms of a trace of phi3.chat-saturated: one whole one-step chunk
    (16.8 ms), a first-token sample, a cache placement, and the start of a
    16-step chunk."""
    with open(os.path.join(DATA, "trace_v5e_slice.json")) as f:
        rec = json.load(f)
    red = trace_reduce.reduce_planes(rec["planes"], rec["profile_start_ns"],
                                     rec["profile_stop_ns"])
    (dev,) = red["devices"]
    assert red["window_s"] == pytest.approx(0.075, abs=1e-4)
    assert 0.6 * red["window_s"] < dev["busy_s"] <= red["window_s"]
    assert dev["program_runs"]["jit_chunk"] == 2
    assert dev["programs"]["jit_chunk"] == pytest.approx(
        0.016799094 + (0.075 - (0.107303192 - 0.061061376)), abs=1e-4)
    # one step in the first chunk, and the steps whose every operation had
    # begun in the 28.8 ms of the second, at about 13 ms a step
    assert dev["loop_steps"]["jit_chunk"] == 1 + 2
    assert dev["loop_steps"]["jit_place"] == 1
    # the longest gap is the 11 ms between the sample and the placement
    assert dev["gaps"][0][1] == pytest.approx(0.011010981)
    # the cache copies around the loop, summed over their names
    assert dev["ops"][0][0].startswith("copy bf16[8,2048,32,96] (x")
