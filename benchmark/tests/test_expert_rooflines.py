"""What the four expert rooflines count (PR 45): the routed experts' bytes in
a step's least time are those of the held experts the step TOUCHED, where the
program reports the count (`moe_touched` on `engine.host_sync`), and every
held expert where it reports none. No JAX; every property is one parametrised
test, so each case counts. The shapes are held to the benchmark's own
configuration files, the span function and the readers to hand-made runs.
The pinned numbers are the parent's (commit df6fa68): its shapes at the same
rows, its readers on the same runs. Run by hand: `pytest benchmark/tests -q`.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import (manifest, moe_spans, peaks,  # noqa: E402
                       shapes_kda_moe, shapes_mla_moe, shapes_scmoe,
                       shapes_swa_moe)

PEAK = peaks.peaks("TPU v5 lite")


def config(name):
    return manifest._read(os.path.join(ROOT, "benchmark/configs",
                                       name + ".json"))


# family: (shapes module, configuration, the cache rows a step is given, the
# expert rows a step, and the parent's bytes of that step, every held expert
# read: Kimi K2's 8,296,056,576 at 35,200 latent rows is ISSUE 45's)
FAMILIES = {
    "mla": (shapes_mla_moe, "kimi-k2-ep32-6l", (35200,), 40.5,
            8_296_056_576),
    "swa": (shapes_swa_moe, "trinity-mini-ep8-16l", (60000, 30000), 226.8,
            5_357_059_584),
    "kda": (shapes_kda_moe, "kimi-linear-ep16-16l", (70000,), 200.0,
            8_687_644_416),
    "scmoe": (shapes_scmoe, "longcat-flash-ep32-4l", (21000,), 32.0,
              10_337_708_032),
}


def least(family, touched=None):
    sh, name, rows, expert_rows, _ = FAMILIES[family]
    cfg = config(name)
    return sh.decode_step_min_seconds(
        cfg["llm_config"], cfg["app_kwargs"]["max_batch"], *rows, PEAK,
        expert_rows=expert_rows, touched=touched)


def routed(parts):
    """The one part `touched` may move (`shapes_kda_moe` names it apart)."""
    key = "routed_experts" if "routed_experts" in parts else "experts"
    return key, parts[key]


@pytest.mark.parametrize("family", FAMILIES)
def test_without_a_count_the_bytes_are_the_parents_to_the_byte(family):
    got = least(family)
    assert got["bytes"] == FAMILIES[family][4]
    assert isinstance(got["bytes"], int)
    assert got["touched"] == got["held"]


@pytest.mark.parametrize("family", FAMILIES)
def test_every_held_expert_touched_is_the_same_as_no_count(family):
    none = least(family)
    full = least(family, touched=none["held"])
    assert full["bytes"] == none["bytes"]
    assert full["seconds"] == none["seconds"]
    assert full["parts"] == none["parts"] and full["held"] == none["held"]


@pytest.mark.parametrize("family", FAMILIES)
def test_fewer_touched_lower_the_routed_experts_and_nothing_else(family):
    sh, name = FAMILIES[family][:2]
    llm = config(name)["llm_config"]
    none = least(family)
    half = none["held"] / 2 + 0.25  # a mean over steps is no whole number
    got = least(family, touched=half)
    key, before = routed(none["parts"])
    one_expert = sh.expert_params(llm) * 2  # bf16
    assert before == none["held"] * one_expert
    assert before - got["parts"][key] == (none["held"] - half) * one_expert
    assert {k: v for k, v in got["parts"].items() if k != key} == {
        k: v for k, v in none["parts"].items() if k != key}
    assert got["flops"] == none["flops"]  # `moe_rows` counts the rows
    assert got["held"] == none["held"] and got["touched"] == half
    assert sh.decode_step_weight_bytes(llm, half)["routed_experts"] == (
        half * one_expert)


# ---------------------------------------------------------- hand-made runs
STEPS = 16


def chunk(seq, *, touched=None, rows=STEPS * 40, steps=STEPS, extra=None,
          sync=None):
    """One decode chunk: its dispatch at 10 + seq s, its read half a second
    later. `touched` and `rows` are the chunk's sums."""
    at = {"seq": seq, "tokens": steps, "moe_steps": steps, "moe_rows": rows,
          "moe_rows_busiest": 3 * steps, **(sync or {})}
    if touched is not None:
        at["moe_touched"] = touched
    return [{"n": "engine.dispatch_chunk", "k": "engine", "a": 10.0 + seq,
             "b": 10.1 + seq, "pid": 1,
             "at": {"tokens": steps, "active": 30, "seq": seq,
                    **(extra or {})}},
            {"n": "engine.host_sync", "k": "engine", "a": 10.5 + seq,
             "b": 10.6 + seq, "at": at}]


class Rec:
    ok, plen, n_tokens = True, 900, 256


def run_of(name, spans, step_ms, traced=(9.5, 10.5)):
    """A run whose profiler saw the dispatches that began in `traced` (wall
    seconds): chunk 0's alone unless told otherwise."""
    return {"spans": spans, "window_wall": (0.0, 40.0), "records": [Rec()],
            "config": config(name), "device": {"kind": "TPU v5 lite"},
            "profile": {"devices": [{
                "programs": {"jit_chunk": 32 * step_ms / 1e3},
                "loop_steps": {"jit_chunk": 32}}],
                "profile_start_ns": traced[0] * 1e9,
                "profile_stop_ns": traced[1] * 1e9, "replica_pid": 1}}


SPAN_CASES = {
    # name: (chunks, traced window, held, max_batch, expected)
    "no_moe_touched": (chunk(0) + chunk(1), (9.5, 10.5), 60, 32, None),
    "the_traced_chunk_by_seq": (
        chunk(0, touched=STEPS * 25) + chunk(1, touched=STEPS * 31),
        (9.5, 10.5), 60, 32, {"touched": 25.0, "steps": STEPS}),
    "the_other_traced_chunk": (
        chunk(0, touched=STEPS * 25) + chunk(1, touched=STEPS * 31),
        (10.5, 11.5), 60, 32, {"touched": 31.0, "steps": STEPS}),
    "the_whole_window_where_none_matches": (
        chunk(0, touched=STEPS * 25) + chunk(1, touched=STEPS * 31),
        (30.0, 31.0), 60, 32, {"touched": 28.0, "steps": 2 * STEPS}),
    "chunks_without_the_count_are_left_out": (
        chunk(0) + chunk(1, touched=STEPS * 31), (9.5, 10.5), 60, 32,
        {"touched": 31.0, "steps": STEPS}),
    "all_held_touched_is_no_contradiction": (
        chunk(0, touched=STEPS * 60, rows=STEPS * 60), (9.5, 10.5), 60, 32,
        {"touched": 60.0, "steps": STEPS}),
    "more_than_held_x_layers_x_steps": (
        chunk(0, touched=STEPS * 60 + 1, rows=STEPS * 90), (9.5, 10.5), 60,
        32, None),
    "more_than_the_rows": (
        chunk(0, touched=STEPS * 40 + 1), (9.5, 10.5), 60, 32, None),
    "fewer_than_the_rows_over_the_slots": (
        chunk(0, touched=STEPS * 2, rows=STEPS * 2 * 32 + 1), (9.5, 10.5),
        60, 32, None),
    "one_bad_chunk_of_two_traced": (
        chunk(0, touched=STEPS * 25) + chunk(1, touched=-1), (9.5, 11.5),
        60, 32, None),
}


@pytest.mark.parametrize("case", SPAN_CASES)
def test_touched_per_step_on_hand_made_host_syncs(case):
    spans, traced, held, batch, want = SPAN_CASES[case]
    run = run_of("kimi-k2-ep32-6l", spans, 11.39, traced)
    assert moe_spans.touched_per_step(run, held, batch) == want


# The three older readers: (reader, configuration, what its dispatch spans
# carry beside `active` and `tokens`, rows a chunk, the traced step in ms,
# the PARENT's reader's number on the run without `moe_touched`, its held
# experts x expert layers, a count a step a program could report).
OLDER = {
    "mla": ("mla_moe_step_roofline", "kimi-k2-ep32-6l", {}, STEPS * 40,
            11.39, 88.61017318063848, 60, 30),
    "swa": ("swa_moe_step_roofline", "trinity-mini-ep8-16l",
            {"active": 16, "kv_live_full": 3600.0, "kv_live_window": 2000.0,
             "kv_rows_full": 8192, "kv_rows_window": 2048}, STEPS * 226,
            7.94, 82.83356529280961, 224, 140),
    "kda": ("kda_moe_step_roofline", "kimi-linear-ep16-16l",
            {"active": 60, "kv_live_full": 1100.0, "kv_rows_full": 4096,
             "state_rw_bytes": 3257401344}, STEPS * 480, 13.7,
            77.26364193470764, 240, 207),
}


def older_run(family, touched=None, rows=None):
    name, cfg, extra, chunk_rows, step_ms = OLDER[family][:5]
    rows = chunk_rows if rows is None else rows
    spans = (chunk(0, touched=touched, rows=rows, extra=extra)
             + chunk(1, touched=touched, rows=rows, extra=extra))
    return manifest.layer_reader(name), run_of(cfg, spans, step_ms)


@pytest.mark.parametrize("family", OLDER)
def test_an_older_roofline_without_the_count_is_the_parents(family, capsys):
    read, run = older_run(family)
    got = read(run)
    assert got == OLDER[family][5]  # to the last digit, not approximately
    said = capsys.readouterr().out
    held = OLDER[family][6]
    assert f"all {held} held experts counted as read a step" in said
    assert "counted on all held it would be" in said
    assert f", {got:.4f}%" in said


@pytest.mark.parametrize("family", OLDER)
def test_an_older_roofline_with_the_count_reads_the_experts_touched(
        family, capsys):
    held, per_step = OLDER[family][6:]
    read, run = older_run(family, touched=STEPS * per_step)
    got = read(run)
    said = capsys.readouterr().out
    assert f"{per_step:.2f} of {held} held experts touched a step" in said
    # the all-held share is printed beside it, and it is the parent's
    assert f"{OLDER[family][5]:.4f}%" in said
    assert 0 < got < OLDER[family][5]
    # counted on every held expert, the same run reads the parent's number
    read, run = older_run(family, touched=STEPS * held,
                          rows=STEPS * max(held, OLDER[family][3] // STEPS))
    assert read(run) == OLDER[family][5]  # bound by bytes, whatever the rows


def test_kimi_k2_at_6_of_12_a_layer_reads_a_least_step_of_6_9_ms(capsys):
    """ISSUE 45's figure: 35,200 latent rows (32 slots at a context of
    1100), 6 of 12 held experts touched in each of 5 expert layers."""
    class At1100:
        ok, plen, n_tokens = True, 1000, 200
    spans = (chunk(0, touched=STEPS * 30, extra={"active": 32})
             + chunk(1, touched=STEPS * 30, extra={"active": 32}))
    run = run_of("kimi-k2-ep32-6l", spans, 11.39)
    run["records"] = [At1100()]
    got = manifest.layer_reader("mla_moe_step_roofline")(run)
    said = capsys.readouterr().out
    assert "least step 6.903 ms (5.654 GB" in said
    assert "30.00 of 60 held experts touched a step" in said
    assert "counted on all held it would be 10.129 ms" in said
    assert "routed_experts 2.642" in said
    assert got == pytest.approx(100 * 6.903 / 11.39, rel=1e-3)


# (touched, rows) of a chunk, from held experts x expert layers and slots
CONTRADICTIONS = {
    "more_than_the_held_experts": lambda held, batch: (
        STEPS * held + 1, 2 * STEPS * held),
    "more_than_moe_rows": lambda held, batch: (STEPS * 10 + 1, STEPS * 10),
    "too_few_for_moe_rows": lambda held, batch: (
        STEPS, STEPS * batch + 1),
}


@pytest.mark.parametrize("case", CONTRADICTIONS)
@pytest.mark.parametrize("family", OLDER)
def test_a_count_that_contradicts_the_others_gives_nothing(family, case,
                                                           capsys):
    batch = config(OLDER[family][1])["app_kwargs"]["max_batch"]
    touched, rows = CONTRADICTIONS[case](OLDER[family][6], batch)
    read, run = older_run(family, touched=touched, rows=rows)
    assert read(run) is None
    assert "contradict one another" in capsys.readouterr().out
    # one fewer touched, or one row fewer, and the same run is believed
    fix = {"too_few_for_moe_rows": (touched, rows - 1)}.get(
        case, (touched - 1, rows))
    read, run = older_run(family, *fix)
    assert read(run) is not None


# `scmoe_step_roofline` through the shared function: the run of
# `test_readers_scmoe.py` (`chunk(seq, touched)`), the number the parent's
# reader returns on it.
def scmoe_chunk(seq, touched):
    layers, batch, picks = 4, 32, 12
    return chunk(seq, touched=STEPS * touched,
                 rows=STEPS * max(layers * 8, touched),
                 extra={"kv_live_full": 700.0, "kv_rows_full": 1024},
                 sync={"moe_picks": STEPS * layers * batch * picks,
                       "moe_zero_picks": STEPS * layers * batch * 4})


SCMOE = {
    "the_traced_chunk_of_two": ((25, 27), (9.5, 10.5), 56.42022759462758,
                                "25.00 of 64"),
    "both_chunks_traced": ((25, 27), (9.5, 11.5), 56.996368253968264,
                           "26.00 of 64"),
    "every_held_expert_touched": ((64, 64), (9.5, 11.5), 78.88971330891331,
                                  "64.00 of 64"),
}


@pytest.mark.parametrize("case", SCMOE)
def test_scmoe_step_roofline_keeps_the_parents_number(case, capsys):
    touched, traced, parents, words = SCMOE[case]
    spans = scmoe_chunk(0, touched[0]) + scmoe_chunk(1, touched[1])
    run = run_of("longcat-flash-ep32-4l", spans, 16.0, traced)
    run["records"] = []
    assert manifest.layer_reader("scmoe_step_roofline")(run) == parents
    said = capsys.readouterr().out
    assert words + " held experts touched a step" in said
    assert "counted on all held it would be 12.622 ms, 78.8897%" in said


def test_scmoe_step_roofline_without_its_counters_still_gives_nothing():
    spans = chunk(0, extra={"kv_live_full": 700.0}) + chunk(
        1, extra={"kv_live_full": 700.0})
    run = run_of("longcat-flash-ep32-4l", spans, 16.0)
    assert manifest.layer_reader("scmoe_step_roofline")(run) is None
