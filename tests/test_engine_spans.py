"""The engine's own tracing (README "Tracing & timeline", the engine's
table): a traced request's four stages inside the engine as spans without a
hole, the scheduler loop's passes as `engine.iteration` spans whose phases
add up, the same phases as `TraceAnnotation`s on the profiler's clock, the
names the decode step's parts carry in a device trace, and nothing of all
that with RT_TRACING unset.

The engine is driven in this process at a tiny size on the CPU; spans are
caught where they are recorded (no cluster, no flusher). No pytest-timeout
is installed: every wait below has a deadline of its own."""

import glob
import os
import re
import threading
import time

import numpy as np
import pytest

from ray_tpu._private import tracing
from ray_tpu.llm import LLMConfig
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams

CFG = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, max_seq=64)
STAGES = ["engine.queue", "engine.prefill", "engine.ready_wait",
          "engine.first_token"]
WAIT_S = 120.0


@pytest.fixture
def spans(monkeypatch):
    """Tracing on in this process; every span recorded lands in the list."""
    caught, lock = [], threading.Lock()

    def record_span(trace_id, span_id, parent, name, kind, start, end,
                    attrs=None):
        with lock:
            caught.append({"t": trace_id, "s": span_id, "p": parent,
                           "n": name, "k": kind, "a": start, "b": end,
                           "at": attrs or {},
                           "tid": threading.get_ident()})

    monkeypatch.setattr(tracing, "_ON", True)
    monkeypatch.setattr(tracing, "record_span", record_span)
    yield caught
    tracing._ctx.set(None)


@pytest.fixture
def engine():
    eng = ContinuousEngine(LLMConfig(**CFG), max_batch=2, decode_chunk=4)
    yield eng
    eng.shutdown()
    assert not any(t.is_alive() for t in eng._threads)


def serve(eng, n, max_tokens=12, traced=True):
    """n requests at once on 2 slots, each under a trace context of its
    own. Returns [(context, stream, time its first token reached us)]."""
    out = []
    for i in range(n):
        ctx = (f"{i:032x}", f"{i:016x}") if traced else None
        tracing._ctx.set(ctx)
        stream = eng.submit([1 + i, 2, 3, 4 + i],
                            SamplingParams(max_tokens=max_tokens,
                                           temperature=0.7, top_k=8, seed=i))
        out.append([ctx, stream, None])
    tracing._ctx.set(None)
    for row in out:
        tokens = [row[1].next(timeout=WAIT_S)]
        row[2] = time.time()
        deadline = time.monotonic() + WAIT_S
        while time.monotonic() < deadline:
            try:
                tokens.append(row[1].next(timeout=WAIT_S))
            except StopIteration:
                break
        assert len(tokens) == max_tokens
    return out


def of_request(spans, ctx, names=STAGES):
    return [s for s in spans if s["t"] == ctx[0] and s["n"] in names]


def test_a_traced_request_has_each_stage_once_in_order(engine, spans):
    for ctx, stream, t_first in serve(engine, 5):
        mine = sorted(of_request(spans, ctx), key=lambda s: s["a"])
        assert [s["n"] for s in mine] == STAGES
        for s in mine:
            assert s["k"] == "engine" and s["p"] == ctx[1]
            assert s["b"] >= s["a"]
        for before, after in zip(mine, mine[1:]):
            assert before["b"] <= after["a"] + 1e-6, (before, after)
        assert mine[-1]["b"] <= t_first
        assert stream._stage is None  # every stage was closed
        by_name = {s["n"]: s for s in mine}
        # (on the CPU the prefill's attention is the XLA form)
        prefill = dict(by_name["engine.prefill"]["at"])
        assert prefill.pop("after_seq") >= -1
        assert prefill == {"prompt_len": 4, "bucket": 8, "attention": "xla"}
        assert set(by_name["engine.queue"]["at"]) == {"pending"}
        assert set(by_name["engine.first_token"]["at"]) - {"sync_seq"} == {
            "slot", "chunks_in_flight"}
        assert set(by_name["engine.ready_wait"]["at"]) == {
            "ready", "active"}


def test_the_stages_leave_no_hole_from_submit_to_the_first_token(engine,
                                                                 spans):
    """queue + prefill + ready_wait + first_token cover the request's time
    inside the engine but for the instants between two stamps."""
    t_submit = time.time()
    (ctx, _stream, t_first), = serve(engine, 1)
    mine = of_request(spans, ctx)
    covered = sum(s["b"] - s["a"] for s in mine)
    start, end = min(s["a"] for s in mine), max(s["b"] for s in mine)
    assert t_submit <= start and end <= t_first
    assert covered >= 0.95 * (end - start) or (end - start) - covered < 0.005


def test_an_iteration_spans_phases_add_up(engine, spans):
    served = serve(engine, 4, max_tokens=20)
    # The last token reaches us from inside the pass that delivers it, and
    # a pass records its span as it ends: let the scheduler get there.
    deadline = time.monotonic() + WAIT_S
    while time.monotonic() < deadline and max(
            s["b"] for s in spans if s["n"] == "engine.host_sync") > max(
            s["b"] for s in spans if s["n"] == "engine.iteration"):
        time.sleep(0.01)
    its = [s for s in spans if s["n"] == "engine.iteration"]
    assert len(its) >= 5
    ctxs = {ctx[0]: ctx[1] for ctx, _s, _t in served}
    for it in its:
        at = it["at"]
        assert set(at) == {"admit_ms", "dispatch_ms", "sync_ms",
                           "deliver_ms", "idle_ms", "spliced", "chunks",
                           "in_flight", "active"}
        assert it["k"] == "engine" and ctxs[it["t"]] == it["p"]
        parts = (at["admit_ms"] + at["dispatch_ms"] + at["sync_ms"]
                 + at["deliver_ms"])
        assert parts <= (it["b"] - it["a"]) * 1e3 + 0.01, it
        assert min(at.values()) >= 0
    # every splice and every chunk is counted in exactly one pass
    assert sum(it["at"]["spliced"] for it in its) == 4
    chunks = [s for s in spans if s["n"] == "engine.dispatch_chunk"]
    assert sum(it["at"]["chunks"] for it in its) == len(chunks)
    # the sync_ms of a pass is the interval of its engine.host_sync
    syncs = [s for s in spans if s["n"] == "engine.host_sync"]
    assert syncs
    for sync in syncs:
        inside = [it for it in its
                  if it["a"] <= sync["a"] and sync["b"] <= it["b"]]
        assert len(inside) == 1, sync
        assert inside[0]["at"]["sync_ms"] == pytest.approx(
            (sync["b"] - sync["a"]) * 1e3, abs=0.002)


def test_the_stats_count_how_rows_changed_hands(engine, spans):
    """`splices`, `splices_in_flight`, `pipeline_dry`, `cover_chunks` of
    `cache_stats` (what `/v1/stats` reports) against what the spans saw: the
    traced stage `engine.first_token` carries the chunks in flight at its
    hand-over, a pass that began dry dispatched into an empty pipeline, and
    a cover chunk's `engine.dispatch_chunk` says `cover`."""
    keys = ("splices", "splices_in_flight", "pipeline_dry", "cover_chunks")
    assert [engine.cache_stats()[k] for k in keys] == [0, 0, 0, 0]
    serve(engine, 5)
    # a request that joins a neighbour in mid-flight: behind its chunks
    tracing._ctx.set(("c" * 32, "d" * 16))
    long = engine.submit([1], SamplingParams(max_tokens=40, temperature=0.0))
    long.next(timeout=WAIT_S)
    joiner = engine.submit([2, 3], SamplingParams(max_tokens=6,
                                                  temperature=0.0))
    tracing._ctx.set(None)
    assert len(joiner.tokens()) == 6 and len(long.tokens()) == 39
    st = engine.cache_stats()
    firsts = [s for s in spans if s["n"] == "engine.first_token"]
    assert st["splices"] == len(firsts) == 7
    assert st["splices_in_flight"] == sum(
        1 for s in firsts if s["at"]["chunks_in_flight"] > 0) >= 1
    # A row changes hands where its occupant's last token is read: the
    # pipeline has drained to there, and where a neighbour is seated the
    # next pass begins with nothing in flight, or with the neighbour's one
    # cover step if a request was parked for the row.
    assert 0 <= st["pipeline_dry"] <= st["splices"]
    chunks = [s["at"] for s in spans if s["n"] == "engine.dispatch_chunk"]
    assert st["cover_chunks"] == sum(1 for at in chunks if "cover" in at)


def saturated(eng, requests, park: int = 4) -> list:
    """Two rows at saturation, as the spans record it: the first `park`
    of `requests` [(prompt, max_tokens)] are parked before a row is given
    out (a quarter of this cache holds 32 rows of slices), the rest are
    submitted once the first token is out, so that their prefills lie
    ahead of later chunks. Every request is traced. Returns the streams,
    drained."""
    streams = []

    def submit(i, prompt, max_tokens):
        tracing._ctx.set((f"{i:032x}", f"{i:016x}"))
        streams.append(eng.submit(prompt, SamplingParams(
            max_tokens=max_tokens, temperature=0.0)))
        tracing._ctx.set(None)

    def until(cond, what):
        deadline = time.monotonic() + WAIT_S
        while not cond():
            assert time.monotonic() < deadline, what
            time.sleep(0.005)

    free, eng._free_slot = eng._free_slot, lambda: None
    try:
        for i, request in enumerate(requests[:park]):
            submit(i, *request)
        until(lambda: len(eng._ready) == len(requests[:park]),
              "the prefills")
    finally:
        eng._free_slot = free
    first = streams[0].next(timeout=WAIT_S)
    for i, request in enumerate(requests[park:], park):
        submit(i, *request)
    got = [s.tokens() for s in streams]
    assert [len(g) + (s is streams[0]) for g, s in zip(got, streams)] == [
        n for _p, n in requests], first
    settle(eng)
    return streams


def test_a_cover_chunks_span_says_so_and_carries_its_own_occupants_rows(
        engine, spans):
    """A long prompt's last step is in flight, a request is parked, and the
    neighbour with a short prompt goes on: the cover's
    `engine.dispatch_chunk` carries `cover` (no other chunk has the key),
    `tokens` 1, `active` 1, and `kv_bound`, `kv_rows_full`, `kv_live_full`
    of the short occupant ALONE, though the finished row steps in the
    program beside it."""
    from ray_tpu.ops.decode_attention import kv_prefix_rows

    long_prompt = list(range(1, 13))
    saturated(engine, [(long_prompt, 22), ([5, 6, 7], 40), ([8, 9], 8)],
              park=3)
    chunks, _reads, _others, _prefills = account(spans)
    covers = [c["at"] for c in chunks if "cover" in c["at"]]
    assert covers and engine.cache_stats()["cover_chunks"] == len(covers)
    at = covers[0]
    before = chunks[at["seq"] - 1]["at"]
    assert at["active"] == 1
    # the chunk before stepped both, the 21st time: its bound is the long
    # prompt's rows; the cover's is the short one's, stepped once more
    assert before["active"] == 2 and before["kv_bound"] == 12 + 21
    assert at["kv_bound"] == 3 + 21 + 1 == at["kv_live_full"]
    assert at["kv_rows"] == at["kv_rows_full"] == kv_prefix_rows(
        at["kv_bound"], CFG["max_seq"]) < before["kv_rows"]
    assert all(c["at"]["cover"] is True and c["at"]["tokens"] == 1
               for c in chunks if "cover" in c["at"])


def test_the_device_account_pairs_its_intervals_across_cover_chunks(
        engine, spans):
    """`benchmark/device_account.py` over a saturated sequence as the engine
    records it (ordinals, steps, covers, what was enqueued ahead), on a
    clock of the test's own: 10 ms a step, 4 ms a prefill, 2 ms a `place`,
    every block read with a wait but a cover's, which the host finds ready
    (it ran while the host read the chunk before it, and gives no stamp).
    Every chunk after the first lies in one paired interval, a cover's runs
    on to the next stamp, the hand-overs behind a cover leave no dry gap,
    and the four readers of the whole window all speak."""
    from benchmark import device_account as da, manifest

    saturated(engine, [([1 + i, 2, 3, 4 + i], n) for i, n in enumerate(
        [21, 30, 27, 25, 22, 26])])
    chunks, reads, _others, _prefills = account(spans)
    assert len(reads) == len(chunks)
    covers = {c["at"]["seq"] for c in chunks if "cover" in c["at"]}
    assert len(covers) >= 2
    laid, now = [], 1.0
    for c, r in zip(chunks, sorted(reads, key=lambda r: r["at"]["seq"])):
        at = c["at"]
        ahead = sum(da.parse_buckets(at["prefill_buckets_ahead"]).values())
        if not at["in_flight"]:
            now += 0.004  # the host's pass, the device standing still
        start, now = now, (now + 0.004 * ahead + 0.002 * at["places_ahead"]
                           + 0.010 * at["tokens"])
        laid.append({**c, "pid": 1, "a": start - 0.02 * at["in_flight"],
                     "b": start - 0.02 * at["in_flight"] + 0.0005})
        laid.append({**r, "pid": 1, "a": now - 0.003, "b": now + 0.0005,
                     "at": {"seq": at["seq"], "tokens": at["tokens"],
                            "block_ready": now,
                            "block_waited": at["seq"] not in covers}})
    window = (laid[1]["at"]["block_ready"], now + 0.001)
    run = {"spans": laid, "window_wall": window, "profile": None,
           "records": [], "device": {"kind": "cpu"},
           "config": {"llm_config": {"n_layers": 2},
                      "app_kwargs": {"max_batch": 2}}}
    ivs = da.paired(run)
    assert sorted(c.seq for iv in ivs for c in iv.chunks) == list(
        range(1, len(chunks)))
    for iv in ivs:
        assert iv.chunks[-1].seq not in covers
        assert all(c.seq in covers for c in iv.chunks[:-1]) or any(
            c.beside for c in iv.chunks[:-1])
    assert da.coverage(run, *window) > 0.95
    # a hand-over behind a cover: a `place` ahead, a chunk in flight, no gap
    behind = [c for iv in ivs for c in iv.chunks
              if c.places and c.seq - 1 in covers]
    assert behind and all(c.in_flight >= 1 for c in behind)
    got = {name: manifest.layer_reader(name)(run) for name in (
        "decode_step_window_ms", "admit_dev_share_window", "admit_dev_ms",
        "handover_gap_share_window")}
    assert None not in got.values(), got
    assert got["decode_step_window_ms"] == pytest.approx(10.0, abs=0.5)
    dry = [c for c in chunks[1:] if not c["at"]["in_flight"]]
    assert got["handover_gap_share_window"] == pytest.approx(
        100 * 0.004 * len(dry) / (window[1] - window[0]), rel=0.05)


def test_the_stats_and_the_chunk_spans_name_the_samplers_path(engine, spans):
    """`sampler` on `engine.dispatch_chunk` is the sampler's path for the
    chunk's occupants (`greedy`, `select`, `sort`: `llm/sampler.py`
    `_sampler_path`), and `sampler_steps`, `sampler_steps_select` of
    `cache_stats` (what `/v1/stats` reports) count the steps of the sampled
    program and those in which it sorted nothing."""
    keys = ("sampler_steps", "sampler_steps_select")
    assert [engine.cache_stats()[k] for k in keys] == [0, 0]

    def chunks_of(**sampling):
        del spans[:]
        tracing._ctx.set(("a" * 32, "b" * 16))
        stream = engine.submit([1, 2, 3], SamplingParams(
            max_tokens=9, **sampling))
        tracing._ctx.set(None)
        assert len(stream.tokens()) == 9
        return [(s["at"]["sampler"], s["at"]["tokens"]) for s in spans
                if s["n"] == "engine.dispatch_chunk"]

    greedy = chunks_of(temperature=0.0, top_p=0.5)
    assert {path for path, _n in greedy} == {"greedy"}
    assert [engine.cache_stats()[k] for k in keys] == [0, 0]
    # the benchmark's traffic, a top_k of any size, and no order asked for
    select = (chunks_of(temperature=0.7, top_k=50)
              + chunks_of(temperature=0.7, top_k=100)
              + chunks_of(temperature=1.0))
    assert {path for path, _n in select} == {"select"}
    steps = sum(n for _path, n in select)
    assert steps >= 3 * 8
    assert [engine.cache_stats()[k] for k in keys] == [steps, steps]
    # a nucleus, bare or under a top_k: one sort a step
    sort = (chunks_of(temperature=0.7, top_p=0.9)
            + chunks_of(temperature=0.7, top_k=50, top_p=0.9))
    assert {path for path, _n in sort} == {"sort"}
    assert [engine.cache_stats()[k] for k in keys] == [
        steps + sum(n for _path, n in sort), steps]


#: `tests/test_eva.py`'s toy: a window of 32 positions that starts over
#: beside a summary for every chunk of 4, 128 positions a slot.
EVA = dict(CFG, max_seq=128, dtype="float32", arch={
    "model_type": "evabyte", "attention_class": "eva", "chunk_size": 4,
    "window_size": 32, "num_chunks": None, "num_key_value_heads": 4,
    "intermediate_size": 96, "hidden_act": "silu", "attention_bias": False,
    "rope_theta": 100000, "rope_scaling": None, "rms_norm_eps": 1e-5,
    "norm_add_unit_offset": True, "fp32_skip_add": True,
    "fp32_logits": True, "num_pred_heads": 8, "tie_word_embeddings": False,
    "pool_init_std": 4.0})


@pytest.mark.parametrize("family", ["mha", "eva"])
def test_the_stats_count_prefill_rows_and_which_attention_served_them(
        family, spans, monkeypatch):
    """`prefill_rows` and `prefill_rows_kernel` of `cache_stats` (what
    `/v1/stats` reports) beside the attribute `attention` of the span
    `engine.prefill`: the rows of the buckets dispatched, and those whose
    program's attention is a Pallas kernel by the dispatcher's own rule
    for the bucket's shape. On the CPU that is none of them; where the rule
    says the kernel (asked of it here, nothing is run), a bucket dispatched
    from then on counts under it. An "eva" model's buckets up to a window
    are put to the flash kernel's rule, those past it to the two-source
    kernel's, at whole windows."""
    from ray_tpu.ops import attention, two_source_attention

    engine = ContinuousEngine(LLMConfig(**(CFG if family == "mha" else EVA)),
                              max_batch=2, decode_chunk=4)
    try:
        keys = ("prefill_rows", "prefill_rows_kernel")
        assert [engine.cache_stats()[k] for k in keys] == [0, 0]
        serve(engine, 3)
        prefills = [s for s in spans if s["n"] == "engine.prefill"]
        assert [s["at"]["attention"] for s in prefills] == ["xla"] * 3
        assert [engine.cache_stats()[k] for k in keys] == [
            sum(s["at"]["bucket"] for s in prefills), 0] == [24, 0]
        # the engine asks the rule once a bucket: a bucket it has not seen
        asked = []
        with monkeypatch.context() as patch:
            patch.setattr(  # None: the kernel
                attention, "kernel_refusal",
                lambda q, k, **kw: asked.append((q, k, kw)))
            patch.setattr(
                two_source_attention, "two_source_refusal",
                lambda q, window, chunk: asked.append((q, window, chunk)))
            assert engine._prefill_form(8) == "xla"  # remembered
            assert engine._prefill_form(16) == "kernel"
            if family == "eva":
                assert engine._prefill_form(64) == "kernel"
        assert asked == [((1, 16, 4, 16), (1, 16, 4, 16), {"window": 0})] \
            if family == "mha" else asked == [
                ((1, 16, 4, 16), (1, 16, 4, 16), {}),
                ((1, 64, 4, 16), 32, 4)]
        tracing._ctx.set(("e" * 32, "f" * 16))
        rows = 16 if family == "mha" else 64
        engine.submit(list(range(1, rows - 4)),
                      SamplingParams(max_tokens=2)).tokens()
        tracing._ctx.set(None)
        last = [s for s in spans if s["n"] == "engine.prefill"][-1]["at"]
        assert (last["bucket"], last["attention"]) == (rows, "kernel")
        assert [engine.cache_stats()[k] for k in keys] == [24 + rows, rows]
    finally:
        engine.shutdown()
    assert not any(t.is_alive() for t in engine._threads)


@pytest.mark.parametrize("form,name", [("xla", "mha"), ("kernel", "mha"),
                                       ("xla", "swa"), ("kernel", "swa"),
                                       ("mixed", "swa"),
                                       ("xla", "mla"), ("kernel", "mla")])
def test_the_chunk_spans_and_the_stats_say_which_attention_served(
        form, name, spans, monkeypatch):
    """`attention` on `engine.dispatch_chunk` (`kernel` or `xla`, as
    `engine.prefill` carries it) and `decode_steps`, `decode_steps_kernel`
    of `cache_stats` (what `/v1/stats` reports): the decode steps
    dispatched, and those whose attention is the ragged kernel by the
    dispatcher's own rule for the model's leaves. With the kernel (forced
    here, in interpret mode, in blocks of 8 rows) `kv_rows_full` and
    `kv_rows_window` are what it reads, the live slots' own rows rounded
    up to the block, as a whole multiple of the block
    (`benchmark/device_account.py` keys a step's class on them); the XLA
    walk's are its quarter prefixes, as they were. The rule decides leaf
    by leaf: a ring of 20 rows has no block in whole sublane tiles and
    keeps the XLA walk beside full leaves that take the kernel, which the
    span calls `mixed` and the kernel's counter leaves out. A model with
    latent layers (`mla`) says the same of its latent leaf, which the
    kernel of its own reads (`ops/decode_attention.py`
    `ragged_latent_attention`, PR 41): before, its steps were `xla`
    wherever they ran."""
    import dataclasses

    from ray_tpu.ops.decode_attention import kv_prefix_rows
    from tests.test_ragged_decode import (MHA, MLA, SWA, latent_on_the_chip,
                                          on_the_chip)  # heads of 128

    block, ring = 8, 20 if form == "mixed" else 16
    # (the `mha` kernel fetches a slot's last block in `on_the_chip`'s
    # pieces, a quarter of a block; the latent kernel whole blocks)
    piece = block if name == "mla" else block // 4
    if form != "xla" and name == "mla":
        latent_on_the_chip(monkeypatch, block)
    elif form != "xla":
        on_the_chip(monkeypatch, block * 2 * 128 * 4)
    cfg = {"mha": MHA, "mla": MLA, "swa": dataclasses.replace(
        SWA, arch={**SWA.arch, "sliding_window": ring})}[name]
    eng = ContinuousEngine(cfg, max_batch=2, decode_chunk=4)
    try:
        assert eng.cache_stats()["decode_steps"] == 0
        serve(eng, 3, max_tokens=30)  # (asserts every answer's length)
        st = eng.cache_stats()
    finally:
        eng.shutdown()
    chunks = [s["at"] for s in spans if s["n"] == "engine.dispatch_chunk"]
    assert chunks and {at["attention"] for at in chunks} == {form}
    steps = sum(at["tokens"] for at in chunks)
    assert st["decode_steps"] == steps
    assert st["decode_steps_kernel"] == (steps if form == "kernel" else 0)
    kinds = {"full": 64, **({"window": ring} if name == "swa" else {})}
    for at in chunks:
        assert at["kv_rows"] == at["kv_rows_full"]
        for kind, leaf_rows in kinds.items():
            walked, live = at["kv_rows_" + kind], at["kv_live_" + kind]
            if form == "xla" or (form, kind) == ("mixed", "window"):
                assert walked == kv_prefix_rows(at["kv_bound"], leaf_rows)
            else:
                assert walked % block == 0 and 0 < walked <= leaf_rows
                # a slot's rows rounded up to a block, their mean to a
                # whole block
                assert live - block / 2 <= walked <= live + 1.5 * block
    if form != "xla":
        # the slots' own lengths, not the longest one's quarter; the
        # stats' share is the mean of what is FETCHED as it is: under a
        # piece over the live
        assert len({at["kv_rows_full"] for at in chunks}) > 2
        assert 0 < st["kv_live_share"] <= st["kv_walk_share"] < (
            st["kv_live_share"] + piece / 64)


@pytest.mark.parametrize("name", ["mha", "swa"])
def test_the_walk_share_is_the_rows_the_kernel_fetches(name, spans,
                                                       monkeypatch):
    """One request alone on its slot under the `mha` family's kernel
    (forced, in interpret mode, blocks of 8 rows fetched in pieces of 2):
    step by step its rows rounded up to the PIECE are what `/v1/stats`
    `walk_share` sums, for full leaves and rings, while the span's
    `kv_rows_<kind>` stays the chunk's mean in whole BLOCKS."""
    from tests.test_ragged_decode import MHA, SWA, on_the_chip

    block, piece = 8, 2
    on_the_chip(monkeypatch, block * 2 * 128 * 4)
    eng = ContinuousEngine({"mha": MHA, "swa": SWA}[name], max_batch=2,
                           decode_chunk=4)
    try:
        serve(eng, 1, max_tokens=30)
        st = eng.cache_stats()
    finally:
        eng.shutdown()
    assert (eng._kernel_blocks["full"], eng._kernel_pieces["full"]) == (
        block, piece)
    chunks = [s["at"] for s in spans if s["n"] == "engine.dispatch_chunk"]
    assert st["decode_steps"] == sum(at["tokens"] for at in chunks) > 0
    for kind, k in st["cache_kinds"].items():
        fetched = blocks = 0
        for at in chunks:
            # one live slot: step j of the chunk sees bound - n + j + 1 rows
            seen = np.minimum(np.arange(
                at["kv_bound"] - at["tokens"] + 1, at["kv_bound"] + 1),
                k["rows"])
            fetched += np.ceil(seen / piece).sum() * piece
            assert at["kv_rows_" + kind] == round(
                np.ceil(seen / block).mean()) * block
            blocks += np.ceil(seen / block).sum() * block
        assert k["walk_share"] == pytest.approx(
            fetched / (st["decode_steps"] * k["rows"]))
        assert k["live_share"] < k["walk_share"] < blocks / (
            st["decode_steps"] * k["rows"])


def test_idle_time_is_carried_by_the_next_recorded_pass(engine, spans):
    serve(engine, 1)
    time.sleep(0.35)  # the scheduler waits with nothing to do
    serve(engine, 1)
    its = [s for s in spans if s["n"] == "engine.iteration"]
    assert max(it["at"]["idle_ms"] for it in its) >= 200
    # a pass that only waited records no span of its own
    assert all(it["at"]["chunks"] or it["at"]["spliced"]
               or it["at"]["sync_ms"] > 0 for it in its)


class Counting:
    """Stands in for jax.profiler.TraceAnnotation and counts its uses."""

    entered = 0

    def __init__(self, name, **kw):
        self.name = name

    def __enter__(self):
        Counting.entered += 1
        return self

    def __exit__(self, *exc):
        return False


def watch_blocks(eng, monkeypatch):
    """Every chunk's token block goes through a stand-in that counts the
    `is_ready` calls made on it; returns the list they are counted in."""
    import numpy as np

    asked = []

    class Watched:
        def __init__(self, arr):
            self._arr = arr

        def is_ready(self):
            asked.append(1)
            return self._arr.is_ready()

        def __array__(self, *a, **kw):
            return np.asarray(self._arr)

        def __getitem__(self, i):
            return self._arr[i]

        def __getattr__(self, name):
            return getattr(self._arr, name)

    chunk = eng._chunk

    def watched(*a, **kw):
        cache, keys, toks_out, lens_out = chunk(*a, **kw)
        return cache, keys, Watched(toks_out), lens_out

    monkeypatch.setattr(eng, "_chunk", watched)
    return asked


def test_with_tracing_off_the_engine_records_and_stamps_nothing(
        engine, monkeypatch):
    import jax

    recorded = []
    monkeypatch.setattr(tracing, "_ON", False)
    monkeypatch.setattr(tracing, "record_span",
                        lambda *a, **kw: recorded.append(a))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    Counting.entered = 0
    asked = watch_blocks(engine, monkeypatch)
    queued = []
    monkeypatch.setattr(engine, "_q_chunks", type("Q", (list,), {
        "append": lambda self, e: (queued.append(e), list.append(self, e))
    })())
    for ctx, stream, _t in serve(engine, 3, traced=False):
        assert stream.trace is None and stream._stage is None
    assert recorded == []
    assert Counting.entered == 0
    # nothing of the device's account either: no chunk got an ordinal, no
    # block was asked whether it was ready, no counter was kept or read
    assert queued and all(seq is None for *_rest, seq in queued)
    assert asked == []
    assert (engine._chunks_begun, engine._chunks_done,
            engine._splices_at_chunk) == (0, 0, 0)
    assert not engine._prefills_since and not engine._prefill_in_call
    assert engine.splices == 3


def account(spans):
    """(chunks by ordinal, reads that carry an ordinal, the other reads,
    prefills) of the spans caught."""
    chunks = sorted((s for s in spans if s["n"] == "engine.dispatch_chunk"),
                    key=lambda s: s["at"]["seq"])
    syncs = [s for s in spans if s["n"] == "engine.host_sync"]
    return (chunks, [s for s in syncs if "seq" in s["at"]],
            [s for s in syncs if "seq" not in s["at"]],
            [s for s in spans if s["n"] == "engine.prefill"])


def settle(eng):
    deadline = time.monotonic() + WAIT_S
    while time.monotonic() < deadline and (
            eng.num_active or eng._q_chunks or eng._pending_firsts):
        time.sleep(0.01)
    time.sleep(0.05)  # the pass that read the last block records its spans


def test_every_chunk_has_an_ordinal_and_its_read_names_it(engine, spans,
                                                          monkeypatch):
    """`seq` on `engine.dispatch_chunk` counts the engine's chunks from 0
    without a hole, and the `engine.host_sync` that read a chunk's block
    names it (`seq`, `tokens`) and stamps the block's arrival alone
    (`block_ready`, `block_waited`), before the hand-overs' first tokens
    (`firsts_ready`, `firsts_waited`), inside its own interval."""
    asked = watch_blocks(engine, monkeypatch)
    serve(engine, 5, max_tokens=20)
    settle(engine)
    chunks, reads, others, _prefills = account(spans)
    assert [c["at"]["seq"] for c in chunks] == list(range(len(chunks)))
    assert len(chunks) >= 10 and len(asked) == len(reads) == len(chunks)
    by_seq = {c["at"]["seq"]: c for c in chunks}
    assert chunks[0]["at"]["in_flight"] == 0
    for c in chunks:
        assert 0 <= c["at"]["in_flight"] < 4  # PIPELINE_DEPTH
    for r in reads:
        at = r["at"]
        assert at["tokens"] == by_seq[at["seq"]]["at"]["tokens"]
        assert r["a"] <= at["block_ready"] <= r["b"]
        assert at["block_waited"] in (True, False)
        assert by_seq[at["seq"]]["b"] <= at["block_ready"]
        if "firsts_ready" in at:
            assert at["block_ready"] <= at["firsts_ready"] <= r["b"]
            assert at["firsts_waited"] in (True, False)
    assert sorted(r["at"]["seq"] for r in reads) == list(range(len(chunks)))
    # a read of first tokens only names no chunk and stamps no block
    for r in others:
        assert not {"tokens", "block_ready", "block_waited"} & set(r["at"])
        assert r["a"] <= r["at"]["firsts_ready"] <= r["b"]
    # a first token read beside a chunk's block says which
    firsts = [s for s in spans if s["n"] == "engine.first_token"]
    assert len(firsts) == 5
    beside = {r["at"]["seq"] for r in reads if "firsts_ready" in r["at"]}
    assert {s["at"]["sync_seq"] for s in firsts
            if "sync_seq" in s["at"]} == beside


def test_a_chunk_counts_the_admission_programs_enqueued_ahead_of_it(
        engine, spans):
    """`prefill_buckets_ahead` (`"8:2,16:1"`) and `places_ahead` on
    `engine.dispatch_chunk` are what the device was handed since the chunk
    before: every hand-over's `place` and every prefill is ahead of exactly
    one chunk, and a prefill's `after_seq` says which: the next one, but
    where the prefill program's call ran beside that chunk's dispatch,
    which the chunk then says (`prefills_beside`) and nobody guesses."""
    streams = []
    for plen in (3, 11, 20, 5, 40, 9):
        tracing._ctx.set((f"{plen:032x}", f"{plen:016x}"))
        streams.append(engine.submit(
            list(range(1, plen + 1)), SamplingParams(
                max_tokens=14, temperature=0.7, top_k=8, seed=plen)))
        time.sleep(0.02)
    tracing._ctx.set(None)
    assert all(len(s.tokens()) == 14 for s in streams)
    settle(engine)
    chunks, _reads, _others, prefills = account(spans)
    assert len(prefills) == 6 == engine.cache_stats()["splices"]
    assert sum(c["at"]["places_ahead"] for c in chunks) == 6
    ahead = [{int(b): int(k) for b, k in (
        part.split(":") for part in c["at"]["prefill_buckets_ahead"].split(",")
        if part)} for c in chunks]
    assert sum(sum(a.values()) for a in ahead) == 6
    assert sum(b * k for a in ahead for b, k in a.items()) == sum(
        p["at"]["bucket"] for p in prefills) == 8 + 16 + 32 + 8 + 64 + 16
    assert not engine._prefills_since and not engine._prefill_in_call
    unsure = {c["at"]["seq"] for c in chunks if "prefills_beside" in c["at"]}
    assert all(c["at"].get("prefills_beside", True) is True for c in chunks)
    # chunk k has ahead of it the prefills enqueued after chunk k - 1: a
    # prefill is counted by the chunk after its `after_seq`, or later if
    # that chunk's dispatch ran beside its call
    counted = 0
    for c, a in zip(chunks, ahead):
        k = c["at"]["seq"]
        counted += sum(a.values())
        before = [p["at"] for p in prefills if p["at"]["after_seq"] < k]
        sure = [at for at in before if at["after_seq"] + 1 not in unsure]
        assert len(sure) <= counted <= len(before), (k, counted, prefills)
    assert all(-1 <= p["at"]["after_seq"] < len(chunks) for p in prefills)
    assert prefills[0]["at"]["after_seq"] == -1


def test_with_tracing_on_every_phase_is_an_annotation(engine, spans,
                                                      monkeypatch):
    import jax

    names = []

    class Named(Counting):
        def __enter__(self):
            names.append(self.name)
            return self

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Named)
    serve(engine, 2)
    assert {"engine.admit", "engine.dispatch", "engine.sync",
            "engine.deliver", "engine.prefill_dispatch"} <= set(names)
    assert names.count("engine.prefill_dispatch") == 2


def test_a_profile_of_a_traced_engine_holds_its_phases_in_a_host_plane(
        engine, spans, tmp_path):
    import jax

    serve(engine, 1)  # builds the programs outside the profile
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        serve(engine, 2, max_tokens=16)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    found.setdefault(ev.name, []).append((plane.name, ev))
    assert {"engine.dispatch", "engine.sync", "engine.admit",
            "engine.deliver", "engine.prefill_dispatch"} <= set(found)
    assert {plane for plane, _ev in found["engine.sync"]} == {"/host:CPU"}
    # engine.dispatch carries the wall clock, so that the spans' clock and
    # the trace's can be tied together from the trace alone
    plane, ev = found["engine.dispatch"][0]
    wall_ns = dict(ev.stats)["wall_ns"]
    assert abs(wall_ns - time.time_ns()) < 600e9
    assert ev.duration_ns > 0


def op_names(lowered) -> list:
    return re.findall(r'op_name="([^"]+)"', lowered.compile().as_text())


def scopes_of(lowered) -> set:
    """Every component of every operation's name in a compiled program."""
    return {part for name in op_names(lowered) for part in name.split("/")}


def in_branches(lowered, *scopes) -> bool:
    """Some operation inside a branch of a `lax.switch` carries these
    scopes, in this order, before the branch's own name: the walk's
    branches (ops/decode_attention.py `over_kv_prefix`) sit inside them."""
    pattern = "/.*".join(scopes) + r"/.*branch_\d+_fun/"
    return any(re.search(pattern, name) for name in op_names(lowered))


def test_a_chunks_kv_bound_follows_the_live_slots_only(engine, spans):
    """A long request retires while a short one decodes on: the bound on
    the `engine.dispatch_chunk` spans falls to the short one's rows, though
    the retired slot's device-side length is stale and keeps growing; the
    rows walked are the bound's prefix; and both requests' greedy tokens
    are the whole-cache engine's."""
    import numpy as np

    from ray_tpu.llm import LLMEngine
    from ray_tpu.ops.decode_attention import kv_prefix_rows

    long_prompt, short_prompt = list(range(1, 45)), [5, 6, 7]
    greedy = dict(temperature=0.0)
    tracing._ctx.set(("a" * 32, "b" * 16))
    long = engine.submit(long_prompt, SamplingParams(max_tokens=4, **greedy))
    short = engine.submit(short_prompt,
                          SamplingParams(max_tokens=24, **greedy))
    tracing._ctx.set(None)
    got_long, got_short = long.tokens(), short.tokens()
    chunks = sorted((s for s in spans if s["n"] == "engine.dispatch_chunk"),
                    key=lambda s: s["a"])
    bounds = [s["at"]["kv_bound"] for s in chunks]
    max_seq = CFG["max_seq"]
    assert all(s["at"]["kv_rows"] == kv_prefix_rows(s["at"]["kv_bound"],
                                                    max_seq) for s in chunks)
    # While the long request lives, its rows set the bound: 44 and at most
    # 4 steps (its first token comes from the prefill, so 3 would do; the
    # first chunk is sized before that token is delivered) ...
    assert len(long_prompt) < max(bounds) <= len(long_prompt) + 4 <= max_seq
    # ... and once it has retired the short one's 3 + 23 rows do, in a
    # prefix the long one's rows would not fit
    assert bounds[-1] == len(short_prompt) + 23
    assert chunks[-1]["at"]["kv_rows"] < len(long_prompt)
    ref = LLMEngine(LLMConfig(**CFG, params={"params": engine.params}))
    for prompt, got in ((long_prompt, got_long), (short_prompt, got_short)):
        want = ref.generate(np.asarray([prompt]), len(got))[0, len(prompt):]
        assert got == want.tolist()


def test_the_decode_steps_parts_carry_their_names_in_the_program(engine):
    """What `attn_dev_share` reads from a device trace: operation metadata,
    so the program computes the same with or without the names."""
    import jax.numpy as jnp

    engine._cache = engine._init_cache()
    chunk = engine._chunk.lower(
        engine.params, engine._cache, engine._toks_dev, engine._lens_dev,
        engine._keys, engine._temps_dev, engine._topks_dev,
        engine._topps_dev, 2, False, jnp.int32(9))
    assert {"decode_attention", "mlp", "lm_head",
            "sampler"} <= scopes_of(chunk)
    assert in_branches(chunk, "decode_attention")
    prefill = engine._prefill.lower(
        engine.params, jnp.zeros((1, 8), jnp.int32), 3)
    assert {"prefill_attention", "mlp", "lm_head"} <= scopes_of(prefill)


def test_the_latent_and_expert_parts_carry_their_names_in_the_program():
    """The scopes a model with latent attention and expert layers adds to
    the four above, in its decode step and in its prefill."""
    import jax.numpy as jnp

    arch = {"model_type": "kimi_k2", "intermediate_size": 96,
            "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16,
            "moe_intermediate_size": 32, "n_routed_experts": 8,
            "n_shared_experts": 1, "num_experts_per_tok": 2,
            "first_k_dense_replace": 1, "norm_topk_prob": True,
            "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
            "rms_norm_eps": 1e-5, "rope_theta": 50000, "rope_scaling": None,
            "tie_word_embeddings": False}
    eng = ContinuousEngine(LLMConfig(**CFG, arch=arch, experts_held=4),
                           max_batch=2, decode_chunk=4)
    try:
        eng._cache = eng._init_cache()
        chunk = eng._chunk.lower(
            eng.params, eng._cache, eng._toks_dev, eng._lens_dev, eng._keys,
            eng._temps_dev, eng._topks_dev, eng._topps_dev, 2, False,
            jnp.int32(9))
        new = {"mla_attention", "moe_router", "moe_experts", "shared_expert"}
        assert new | {"decode_attention", "mlp", "lm_head",
                      "sampler"} <= scopes_of(chunk)
        assert in_branches(chunk, "mla_attention", "decode_attention")
        prefill = eng._prefill.lower(
            eng.params, jnp.zeros((1, 8), jnp.int32), 3)
        assert new | {"prefill_attention", "mlp",
                      "lm_head"} <= scopes_of(prefill)
    finally:
        eng.shutdown()


def test_the_window_gate_and_norm_parts_carry_their_names_in_the_program():
    """The scopes a model with window and full layers, query/key norms and
    a gated attention adds (`model_type` `afmoe`), in its decode step and in
    its prefill; the walk's branches sit inside each kind of layer's."""
    import jax.numpy as jnp

    arch = {"model_type": "afmoe", "num_key_value_heads": 2, "head_dim": 16,
            "layer_types": ["sliding_attention", "full_attention"],
            "sliding_window": 16, "num_dense_layers": 1, "num_experts": 8,
            "num_experts_per_tok": 2, "num_shared_experts": 1,
            "moe_intermediate_size": 32, "intermediate_size": 96,
            "score_func": "sigmoid", "route_norm": True, "route_scale": 2.5,
            "mup_enabled": True, "rope_theta": 10000, "rms_norm_eps": 1e-5,
            "rope_scaling": None, "tie_word_embeddings": False}
    eng = ContinuousEngine(LLMConfig(**CFG, arch=arch, experts_held=4),
                           max_batch=2, decode_chunk=4)
    try:
        eng._cache = eng._init_cache()
        chunk = eng._chunk.lower(
            eng.params, eng._cache, eng._toks_dev, eng._lens_dev, eng._keys,
            eng._temps_dev, eng._topks_dev, eng._topps_dev, 2, False,
            jnp.int32(9))
        new = {"full_attention", "window_attention", "attn_gate", "qk_norm"}
        assert new | {"decode_attention", "moe_router", "moe_experts",
                      "shared_expert", "mlp", "lm_head",
                      "sampler"} <= scopes_of(chunk)
        assert in_branches(chunk, "window_attention", "decode_attention")
        assert in_branches(chunk, "full_attention", "decode_attention")
        prefill = eng._prefill.lower(
            eng.params, jnp.zeros((1, 32), jnp.int32), 20)
        assert new | {"prefill_attention", "mlp",
                      "lm_head"} <= scopes_of(prefill)
    finally:
        eng.shutdown()


def test_the_shortcut_and_identity_parts_carry_names_and_count_in_the_spans(
        spans):
    """The scopes a layer with its expert layer on a shortcut adds
    (`model_type` `longcat_flash`): `moe_shortcut` around the branch from
    the first half's feed-forward input to the join, `moe_zero_experts`
    around the identity experts' part, the two dense halves `mlp_0` and
    `mlp_1` beside the scopes the latent and expert layers had; and its
    `engine.host_sync` spans carry the expert layers' four counters beside
    the rows (as every expert layer's do since PR 47)."""
    import jax.numpy as jnp

    arch = {"model_type": "longcat_flash", "attention_method": "MLA",
            "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16,
            "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
            "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32,
            "n_routed_experts": 8, "zero_expert_num": 4,
            "zero_expert_type": "identity", "moe_topk": 3,
            "routed_scaling_factor": 6, "rope_theta": 1e7,
            "rms_norm_eps": 1e-5}
    eng = ContinuousEngine(LLMConfig(**CFG, arch=arch, experts_held=4),
                           max_batch=2, decode_chunk=4)
    try:
        eng._cache = eng._init_cache()
        chunk = eng._chunk.lower(
            eng.params, eng._cache, eng._toks_dev, eng._lens_dev, eng._keys,
            eng._temps_dev, eng._topks_dev, eng._topps_dev, 2, False,
            jnp.int32(9))
        new = {"moe_shortcut", "moe_zero_experts", "mlp_0", "mlp_1"}
        assert new | {"mla_attention", "decode_attention", "moe_router",
                      "moe_experts", "lm_head",
                      "sampler"} <= scopes_of(chunk)
        assert in_branches(chunk, "mla_attention", "decode_attention")
        # the expert layer sits inside the shortcut's scope, the dense
        # halves do not
        names = op_names(chunk)
        assert any("moe_shortcut/moe/moe_router" in n for n in names)
        assert not any("moe_shortcut/mlp_" in n for n in names)
        prefill = eng._prefill.lower(
            eng.params, jnp.zeros((1, 8), jnp.int32), 3)
        assert new | {"prefill_attention", "moe_router", "moe_experts",
                      "lm_head"} <= scopes_of(prefill)
        tracing._ctx.set(("5" * 32, "6" * 16))
        stream = eng.submit([3, 4, 5, 6], SamplingParams(temperature=0.0,
                                                         max_tokens=7))
        tracing._ctx.set(None)
        assert len(stream.tokens()) == 7
        counted = [s["at"] for s in spans if s["n"] == "engine.host_sync"
                   and s["at"].get("moe_steps")]
        assert counted
        for at in counted:
            assert {"moe_rows", "moe_rows_busiest", "moe_picks",
                    "moe_zero_picks", "moe_touched", "moe_fetched"} <= set(at)
            # every slot of the batch x selections x expert layers x steps
            assert at["moe_picks"] == at["moe_steps"] * 2 * 3 * CFG["n_layers"]
            assert 0 <= at["moe_zero_picks"] <= at["moe_picks"]
            assert at["moe_touched"] <= min(
                at["moe_rows"], at["moe_steps"] * 4 * CFG["n_layers"])
            # the CPU's arm is the dense one: every held expert is read
            assert at["moe_fetched"] == at["moe_steps"] * 4 * CFG["n_layers"]
    finally:
        eng.shutdown()


def test_the_state_layers_parts_carry_their_names_in_the_program():
    """The scopes a model with gated delta-rule layers beside latent
    attention adds (`model_type` `kimi_linear`): the recurrence in its
    decode step, the chunk scan in its prefill; the latent walk's branches
    still sit inside the latent layers'."""
    import jax.numpy as jnp

    arch = {"model_type": "kimi_linear",
            "linear_attn_config": {
                "kda_layers": [1], "full_attn_layers": [2], "num_heads": 4,
                "head_dim": 16, "short_conv_kernel_size": 4},
            "kv_lora_rank": 16, "q_lora_rank": None, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
            "num_experts": 8, "num_experts_per_token": 2,
            "num_shared_experts": 1, "moe_intermediate_size": 32,
            "intermediate_size": 96, "first_k_dense_replace": 1,
            "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
            "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5,
            "rope_scaling": None, "tie_word_embeddings": False}
    eng = ContinuousEngine(LLMConfig(**CFG, arch=arch, experts_held=4),
                           max_batch=2, decode_chunk=4)
    try:
        eng._cache = eng._init_cache()
        chunk = eng._chunk.lower(
            eng.params, eng._cache, eng._toks_dev, eng._lens_dev, eng._keys,
            eng._temps_dev, eng._topks_dev, eng._topps_dev, 2, False,
            jnp.int32(9))
        new = {"kda_attention", "kda_conv", "kda_gate", "kda_out_gate"}
        assert new | {"kda_recurrence", "mla_attention", "decode_attention",
                      "moe_router", "moe_experts", "shared_expert", "mlp",
                      "lm_head", "sampler"} <= scopes_of(chunk)
        assert "kda_chunk_scan" not in scopes_of(chunk)
        assert in_branches(chunk, "mla_attention", "decode_attention")
        prefill = eng._prefill.lower(
            eng.params, jnp.zeros((1, 32), jnp.int32), 20)
        assert new | {"kda_chunk_scan", "prefill_attention", "mlp",
                      "lm_head"} <= scopes_of(prefill)
        assert "kda_recurrence" not in scopes_of(prefill)
    finally:
        eng.shutdown()


def test_a_capture_keeps_the_python_tracer_off_and_the_host_tracer_on(
        monkeypatch):
    import jax

    from ray_tpu._private import telemetry

    seen = {}

    def start_trace(log_dir, profiler_options=None, **kw):
        seen["options"] = profiler_options

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    rep = telemetry.jax_profile(0.05)
    assert rep["mode"] == "jax" and rep["files"] == 0
    assert seen["options"].python_tracer_level == 0
    assert seen["options"].host_tracer_level > 0


def test_the_host_sync_histogram_is_named_for_what_it_measures():
    from ray_tpu.util import metrics

    assert metrics.LLM_HOST_SYNC_SECONDS._name == "rt_llm_host_sync_seconds"
    assert not hasattr(metrics, "DECODE_STEP_SECONDS")
