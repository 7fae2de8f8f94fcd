"""The arithmetic of `benchmark/device_account.py` and its four readers on
hand-made spans: what the engine writes under RT_TRACING=1 (`seq`,
`in_flight`, what was enqueued ahead on `engine.dispatch_chunk`; `seq`, `tokens`,
`block_ready`, `block_waited` on `engine.host_sync`), with instants chosen so
that every figure can be said beforehand. Nothing here is a device number."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import device_account as da, manifest  # noqa: E402

NEW = ("decode_step_window_ms", "admit_dev_share_window", "admit_dev_ms",
       "handover_gap_share_window")


def chunk(seq, ready, *, pid=1, tokens=8, waited=True, in_flight=1,
          buckets="", places=0, rows=1024, dispatched=None, firsts=None,
          beside=False):
    """The two spans of one chunk: its dispatch and the read of its block."""
    a = ready - 0.2 if dispatched is None else dispatched
    at = {"seq": seq, "tokens": tokens, "block_ready": ready,
          "block_waited": waited}
    if firsts is not None:
        at.update(firsts_ready=firsts, firsts_waited=True)
    return [
        {"n": "engine.dispatch_chunk", "k": "engine", "pid": pid, "t": "x",
         "a": a, "b": a + 0.001,
         "at": {"tokens": tokens, "active": 4, "sampler": "select",
                "kv_bound": rows, "kv_rows": rows, "kv_rows_full": rows,
                "kv_live_full": rows / 2, "seq": seq, "in_flight": in_flight,
                "prefill_buckets_ahead": buckets, "places_ahead": places,
                **({"prefills_beside": True} if beside else {})}},
        {"n": "engine.host_sync", "k": "engine", "pid": pid, "t": "x",
         "a": ready - 0.03, "b": (firsts or ready) + 0.0005, "at": at}]


def run_of(*chunks, wall=(0.0, 10.0)):
    return {"spans": [s for c in chunks for s in c], "window_wall": wall,
            "profile": None, "records": [], "device": {"kind": "cpu"},
            "config": {"llm_config": {"n_layers": 2},
                       "app_kwargs": {"max_batch": 4}}}


def steady(n, step=0.010, start=1.0, first_seq=0, **kw):
    """n chunks of 8 steps back to back, `step` seconds a step."""
    return [chunk(first_seq + i, start + 8 * step * i, **kw)
            for i in range(n)]


def read(name, run):
    return manifest.layer_reader(name)(run)


def test_a_run_of_chunks_without_admissions_gives_the_step_and_no_share():
    run = run_of(*steady(7))
    assert len(da.intervals(run, 0, 10)) == 6
    assert read("decode_step_window_ms", run) == pytest.approx(10.0)
    assert read("admit_dev_ms", run) is None  # nobody was admitted
    assert read("handover_gap_share_window", run) == 0.0
    # 0.48 s of a window of 10 s are paired: the account does not speak
    # for the window, and the share says so and not "0"
    assert da.coverage(run, 0, 10) == pytest.approx(0.048)
    assert read("admit_dev_share_window", run) is None
    assert read("admit_dev_share_window",
                dict(run_of(*steady(7)), window_wall=(1.0, 1.5))) == 0.0


def test_the_step_is_reckoned_within_a_class_and_weighted_by_steps():
    """Two classes (rows walked 1024 and 2048) at 10 and 20 ms a step, 40
    and 16 paired steps: (10 x 40 + 20 x 16) / 56. The chunk with an
    admission ahead weighs in with its steps and lends no sample."""
    short = steady(5)                                        # ready 1.00-1.32
    adm = [chunk(5, 1.32 + 0.08 + 0.05, buckets="1024:1")]   # ready 1.45
    long = [chunk(6 + i, 1.45 + 0.16 * (i + 1), rows=2048) for i in range(2)]
    run = run_of(*short, *adm, *long)
    steps = da.Steps(da.intervals(run, 0, 10))
    assert steps.used == 6 and len(steps.by_class) == 2
    assert read("decode_step_window_ms", run) == pytest.approx(
        (10.0 * 40 + 20.0 * 16) / 56)


def test_admission_programs_are_what_their_buckets_cost_where_seen_alone():
    """A prefill of 1024 rows alone between two chunks back to back costs
    the device 10 ms, one of 4096 rows 40 ms. The interval with one of each
    ahead of a dry dispatch holds 70 ms beyond its steps: 50 ms of
    admission programs, and the device stood idle for the other 20 at the
    hand-over. 5 paired intervals of 8 steps, 0.4 s, and 120 ms more."""
    cs = [chunk(0, 1.0), chunk(1, 1.09, buckets="1024:1"),
          chunk(2, 1.21, buckets="4096:1"),
          chunk(3, 1.36, buckets="1024:1,4096:1", places=1, in_flight=0,
                dispatched=1.21 + 0.015),
          chunk(4, 1.44), chunk(5, 1.52)]
    run = run_of(*cs, wall=(1.0, 1.53))
    ivs = da.intervals(run, 1.0, 1.53)
    steps = da.Steps(ivs)
    assert steps.used == 2
    assert read("decode_step_window_ms", run) == pytest.approx(10.0)
    adm = da.Admissions(ivs, steps)
    assert {b: [round(v, 6) for v in vs] for b, vs in adm.seen.items()} == {
        1024: [0.01], 4096: [0.04]}
    both = ivs[2]
    assert (both.prefills, both.prefill_rows, both.places) == (2, 5120, 1)
    assert da.excess(both, steps) == pytest.approx(0.07)
    assert adm.programs(both) == (pytest.approx(0.05), True)
    # the one hand-over whose prefills have a price: the device stood idle
    # for 20 ms, more than all of its dry gap of 15
    assert adm.idle_of_gap == 1.0 and not adm.at_hand_overs
    assert read("admit_dev_share_window", run) == pytest.approx(
        100 * (0.01 + 0.04 + 0.05) / 0.52)
    # 100 ms of programs for 2 x (1024 + 4096) bucket rows
    assert read("admit_dev_ms", run) == pytest.approx(100.0 / 10240 * 1024)
    assert read("handover_gap_share_window", run) == pytest.approx(
        100 * 0.015 / 0.53)


def test_a_bucket_never_seen_alone_is_priced_from_its_hand_overs():
    """A prefill of 1024 rows alone costs 10 ms. At a hand-over with a dry
    gap of 20 ms it leaves an excess of 18: the device idled for 8 of the
    20, 0.4 of the gap. A bucket of 4096 rows is only ever seen at such
    hand-overs, with an excess of 48 and a gap of 20: 40 ms. A bucket of
    512 rows is only ever seen beside another: that interval goes by its
    excess less 0.4 of its gap. And a prefill counted into an interval that
    holds less than its bucket costs (its place was one off) is given no
    more than the interval has."""
    def hand_over(seq, ready, excess_ms, buckets):
        return chunk(seq, ready + 0.08 + excess_ms / 1e3, buckets=buckets,
                     places=1, in_flight=0, dispatched=ready + 0.020)
    cs = [chunk(0, 1.0), chunk(1, 1.09, buckets="1024:1"),
          chunk(2, 1.18, buckets="1024:1"), hand_over(3, 1.18, 18, "1024:1"),
          chunk(4, 1.358), hand_over(5, 1.358, 48, "4096:1"),
          chunk(6, 1.566), hand_over(7, 1.566, 48, "4096:1"),
          chunk(8, 1.774), hand_over(9, 1.774, 60, "512:1,4096:1"),
          chunk(10, 1.994), chunk(11, 1.994 + 0.084, buckets="1024:1"),
          chunk(12, 2.158)]
    run = run_of(*cs, wall=(1.0, 2.2))
    ivs = da.intervals(run, 1.0, 2.2)
    steps = da.Steps(ivs)
    adm = da.Admissions(ivs, steps)
    assert sorted(round(v, 6) for v in adm.seen[1024]) == [0.004, 0.01, 0.01]
    assert set(adm.seen) == {1024}
    assert adm.idle_of_gap == pytest.approx(0.4)
    assert adm.at_hand_overs == {4096: pytest.approx(0.04)}
    assert adm.programs(ivs[4]) == (pytest.approx(0.04), True)
    assert adm.programs(ivs[8]) == (pytest.approx(0.06 - 0.4 * 0.02), False)
    assert adm.programs(ivs[10]) == (pytest.approx(0.004), True)
    assert read("admit_dev_share_window", run) == pytest.approx(
        100 * (0.01 + 0.01 + 0.01 + 0.04 + 0.04 + 0.052 + 0.004) / 1.158)


def iteration(a, idle_ms, pid=1):
    return {"n": "engine.iteration", "k": "engine", "pid": pid, "t": "x",
            "a": a, "b": a + 0.004,
            "at": {"admit_ms": 1.0, "dispatch_ms": 1.0, "sync_ms": 1.0,
                   "deliver_ms": 1.0, "idle_ms": idle_ms, "spliced": 1,
                   "chunks": 1, "in_flight": 1, "active": 1}}


def test_a_dry_dispatch_is_a_hand_overs_hole_and_a_wait_for_work_is_not():
    """The chunk after a hand-over is enqueued 5 ms after the one before it
    was done (`in_flight` 0), behind a `place` that takes 2 ms: 5 ms of the
    window were dry, the hand-over cost the device 7 ms, and the interval
    lends the step no sample. A second dry dispatch comes after the
    scheduler waited 300 ms for work (`idle_ms` of the pass that began in
    the gap): dry as well, and no admission's cost."""
    hand_over = chunk(4, 1.24 + 0.005 + 0.002 + 0.08, in_flight=0, places=1,
                      dispatched=1.24 + 0.005)
    rest = steady(3, start=1.327 + 0.08, first_seq=5)      # ready to 1.567
    arrival = chunk(8, 1.567 + 0.31 + 0.012 + 0.08, in_flight=0, places=1,
                    buckets="256:1", dispatched=1.567 + 0.31)
    run = run_of(*steady(4), hand_over, *rest, arrival,
                 *steady(2, start=1.969 + 0.08, first_seq=9), wall=(1.0, 2.2))
    run["spans"].append(iteration(1.567 + 0.301, 300.0))
    ivs = da.intervals(run, 1.0, 2.2)
    steps = da.Steps(ivs)
    dry = [iv for iv in ivs if iv.chunks[0].in_flight == 0]
    assert [(round(iv.dry, 6), round(iv.wait, 6)) for iv in dry] == [
        (0.005, 0.0), (0.31, 0.3)]
    assert not any(iv.clean for iv in dry)
    assert [da.excess(iv, steps) for iv in dry] == [
        pytest.approx(0.007), pytest.approx(0.022)]
    assert read("decode_step_window_ms", run) == pytest.approx(10.0)
    assert read("handover_gap_share_window", run) == pytest.approx(
        100 * 0.315 / 1.2)
    # the hand-over held no prefill; the arrival's bucket was never seen
    # alone: its 22 ms less the 10 ms of its gap that were no wait
    assert read("admit_dev_share_window", run) == pytest.approx(
        100 * 0.012 / sum(iv.seconds for iv in ivs))
    assert read("admit_dev_ms", run) == pytest.approx(12.0 / 256 * 1024)


def test_a_sum_over_a_short_stretch_takes_each_interval_by_its_part_there():
    run = run_of(*steady(7))                       # ready 1.00, 1.08, ...
    got = da.overlapping(run, 1.10, 1.30)
    assert [(iv.chunks[0].seq, round(w, 6)) for iv, w in got] == [
        (2, 0.75), (3, 1.0), (4, 0.75)]
    assert sum(w * iv.seconds for iv, w in got) == pytest.approx(0.2)


@pytest.mark.parametrize("spoil, want", [
    (lambda cs: cs[3][1]["at"].update(block_waited=False),
     [(1,), (2,), (3, 4), (5,), (6,), (7,)]),
    (lambda cs: cs[3][1]["at"].update(tokens=4),
     [(1,), (2,), (3, 4), (5,), (6,), (7,)]),
    (lambda cs: cs[3][1]["at"].pop("seq"),
     [(1,), (2,), (3, 4), (5,), (6,), (7,)]),
    (lambda cs: cs.pop(3), [(1,), (2,), (5,), (6,), (7,)]),
], ids=["a-late-host", "a-read-of-other-tokens", "a-read-without-an-ordinal",
        "a-hole-in-seq"])
def test_an_interval_runs_from_one_stamp_of_the_device_to_the_next(spoil,
                                                                   want):
    """A read that found its block ready stamps the host's lateness, not
    the device's completion: the interval runs on to the next read that
    had to wait and holds both chunks. Likewise past a read that cannot be
    tied to its chunk. Across a chunk nobody saw dispatched nothing is
    paired: what the device ran there is not known."""
    cs = steady(8)
    spoil(cs)
    run = run_of(*cs)
    ivs = da.intervals(run, 0, 10)
    assert [tuple(c.seq for c in iv.chunks) for iv in ivs] == want
    assert [iv.clean for iv in ivs] == [len(w) == 1 for w in want]
    steps = da.Steps(ivs)
    assert all(da.excess(iv, steps) == pytest.approx(0.0, abs=1e-9)
               for iv in ivs)
    assert read("decode_step_window_ms", run) == pytest.approx(10.0)


def test_two_replicas_are_counted_apart():
    """Ordinals are a replica's own: pid 1 steps at 10 ms, pid 2 at 20 ms,
    and a chunk of one is never paired with a chunk of the other."""
    run = run_of(*steady(5), *steady(5, step=0.020, pid=2, start=1.003),
                 wall=(1.0, 2.0))
    ivs = da.intervals(run, 1.0, 2.0)
    assert len(ivs) == 8 and da.replicas(run) == 2
    assert {(iv.pid, round(iv.seconds, 3)) for iv in ivs} == {
        (1, 0.08), (2, 0.16)}
    # one class, eight samples: the median lies between the replicas'
    assert read("decode_step_window_ms", run) == pytest.approx(15.0)
    assert da.coverage(run, 1.0, 2.0) == pytest.approx(
        (4 * 0.08 + 4 * 0.16) / 2.0)
    assert len(da.intervals(run, 1.0, 2.0, pid=2)) == 4


def request(t, *, bucket, after_seq, sync_seq, pid=1):
    """The two spans that tie a request's prefill to the read of its first
    token."""
    at = {"bucket": bucket, "prompt_len": bucket - 3, "attention": "xla",
          "after_seq": after_seq}
    return [{"n": "engine.prefill", "k": "engine", "pid": pid, "t": t,
             "a": 1.0, "b": 1.001, "at": at},
            {"n": "engine.first_token", "k": "engine", "pid": pid, "t": t,
             "a": 1.002, "b": 1.05, "at": {"slot": 0, "chunks_in_flight": 1,
                                           "sync_seq": sync_seq}}]


def test_a_first_token_that_was_waited_for_is_a_stamp_of_the_device_too():
    """Below capacity the read of a first token waits for a prefill that
    is queued behind the chunks in flight, and the reads after it find
    their blocks ready. The first token read beside chunk 2 belongs to a
    prefill of 512 rows enqueued right after chunk 4 (`after_seq`): its
    arrival says the device was done with chunks 3 and 4 and then with the
    prefill, 30 ms; the next interval holds the `place` and chunk 5."""
    cs = steady(3)                                         # ready to 1.16
    cs[2] = chunk(2, 1.16, firsts=1.16 + 2 * 0.08 + 0.03)  # 1.35
    late = [chunk(3, 1.351, waited=False), chunk(4, 1.352, waited=False)]
    after = [chunk(5, 1.35 + 0.002 + 0.08, buckets="512:1", places=1),
             *steady(2, start=1.432 + 0.08, first_seq=6)]
    run = run_of(*cs, *late, *after, wall=(1.0, 1.6))
    run["spans"] += request("r", bucket=512, after_seq=4, sync_seq=2)
    ivs = da.intervals(run, 1.0, 1.6)
    assert [(tuple(c.seq for c in iv.chunks), iv.prefills, iv.places)
            for iv in ivs] == [((1,), 0, 0), ((2,), 0, 0), ((3, 4), 1, 0),
                               ((5,), 0, 1), ((6,), 0, 0), ((7,), 0, 0)]
    steps = da.Steps(ivs)
    assert [round(da.excess(iv, steps), 6) for iv in ivs] == [
        0, 0, 0.03, 0.002, 0, 0]
    assert da.coverage(run, 1.0, 1.592) == pytest.approx(1.0)
    # the prefill alone between two stamps: what its bucket costs
    assert da.Admissions(ivs, steps).seen == {512: [pytest.approx(0.03)]}
    assert read("admit_dev_ms", run) == pytest.approx(30.0 / 512 * 1024)
    assert read("admit_dev_share_window", run) == pytest.approx(
        100 * 0.03 / 0.592)


@pytest.mark.parametrize("spoil", [
    lambda run: [s["at"].update(prefills_beside=True)
                 for s in run["spans"] if s["n"] == "engine.dispatch_chunk"
                 and s["at"]["seq"] == 4],
    lambda run: run["spans"][-2]["at"].update(after_seq=1),
    lambda run: run["spans"].__delitem__(-2),
    lambda run: [s["at"].update(prefill_buckets_ahead="512:2")
                 for s in run["spans"] if s["n"] == "engine.dispatch_chunk"
                 and s["at"]["seq"] == 5],
    lambda run: [s["at"].update(firsts_waited=False)
                 for s in run["spans"] if s["n"] == "engine.host_sync"
                 and s["at"]["seq"] == 2],
], ids=["a-prefills-call-ran-beside-the-chunk-before", "it-was-enqueued-before-the-block",
        "its-prefill-is-not-known", "another-prefill-lies-beside-it",
        "the-read-did-not-wait"])
def test_a_first_token_whose_place_is_not_sure_is_no_stamp(spoil):
    """Nothing is guessed: the interval then runs from chunk 2's block to
    chunk 5's and holds chunks 3 to 5 and all that was counted ahead of
    them."""
    cs = steady(3)
    cs[2] = chunk(2, 1.16, firsts=1.35)
    run = run_of(*cs, chunk(3, 1.351, waited=False),
                 chunk(4, 1.352, waited=False),
                 chunk(5, 1.432, buckets="512:1", places=1),
                 *steady(2, start=1.512, first_seq=6), wall=(1.0, 1.6))
    run["spans"] += request("r", bucket=512, after_seq=4, sync_seq=2)
    spoil(run)
    assert [tuple(c.seq for c in iv.chunks)
            for iv in da.intervals(run, 1.0, 1.6)] == [
        (1,), (2,), (3, 4, 5), (6,), (7,)]


def test_a_prefill_enqueued_beside_a_chunks_dispatch_parts_no_intervals():
    """`prefills_beside` on chunk 3: a prefill the NEXT chunk counts ahead
    of itself, which on the device may lie before chunk 3. Nothing is
    guessed: chunk 3's block is no boundary, one interval holds chunks 3
    and 4 and the prefill, whichever side of chunk 3 it ran on, and lends
    the step no sample."""
    cs = steady(3) + [chunk(3, 1.16 + 0.08 + 0.012, beside=True),
                      chunk(4, 1.252 + 0.08, buckets="512:1")]
    run = run_of(*cs, *steady(3, start=1.332 + 0.08, first_seq=5),
                 wall=(1.0, 1.58))
    ivs = da.intervals(run, 1.0, 1.58)
    assert [(tuple(c.seq for c in iv.chunks), iv.admissions, iv.clean)
            for iv in ivs] == [((1,), 0, True), ((2,), 0, True),
                               ((3, 4), 1, False), ((5,), 0, True),
                               ((6,), 0, True), ((7,), 0, True)]
    assert read("decode_step_window_ms", run) == pytest.approx(10.0)
    assert read("admit_dev_share_window", run) == pytest.approx(
        100 * 0.012 / sum(iv.seconds for iv in ivs))
    assert read("admit_dev_ms", run) == pytest.approx(12.0 / 512 * 1024)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_attributes_leaves_the_metric_out(name):
    """What the parent commit gives the new readers: the same spans without
    an ordinal, a stamp or a count."""
    cs = steady(6)
    for dispatch, sync in cs:
        for k in ("seq", "in_flight", "prefill_buckets_ahead",
                  "places_ahead"):
            dispatch["at"].pop(k)
        sync["at"] = {"chunks": 1, "cols": 8}
    assert read(name, run_of(*cs)) is None
    assert read(name, run_of()) is None
