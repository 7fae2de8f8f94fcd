"""Chaos: pipeline-parallel LLM decode under stage death (ISSUE 18,
README "Pipeline-parallel serving" failure contract).

SIGKILLing a stage actor mid-generation must (a) end EVERY open GenStream
with an attributed DagStageError naming the stage — never a hang on a
consumer draining tokens, (b) land the `dag_stage_death` event in the
PR 14 event plane, and (c) leave the engine SERVING: it tears the dead
graph down, rebuilds fresh stage actors, and a fresh generate() succeeds.
Consecutive-failure accounting resets on any completed invocation, so a
single chaos kill never eats into the rebuild budget of a later one."""

import os
import signal
import time

import pytest

import ray_tpu
from ray_tpu.exceptions import DagStageError, GetTimeoutError
from ray_tpu.llm import LLMConfig
from ray_tpu.llm.engine import SamplingParams

DEADLINE_S = 25.0  # detection budget: runtime death detection + one poll

CFG_KW = dict(vocab_size=128, d_model=32, n_layers=2, n_heads=2,
              max_seq=256)


def _wait(pred, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = pred()
        if out:
            return out
        time.sleep(0.2)
    raise TimeoutError(f"timed out waiting for {what}")


def _drain_bounded(stream, budget_s=60.0) -> list:
    """Drain a GenStream with a hard wall — a hang is a test FAILURE with
    a named deadline, not a pytest timeout. Engine errors propagate. The
    wall is `budget_s` for the whole stream: a token that takes longer than
    one 5 s poll (the first one after a rebuild, on a loaded host) is waited
    for again. `GenStream.next` reports a poll that ran out as
    GetTimeoutError, not as the queue's Empty."""
    toks = []
    deadline = time.monotonic() + budget_s
    while time.monotonic() < deadline:
        try:
            toks.append(stream.next(timeout=5))
        except GetTimeoutError:
            continue
        except StopIteration:
            return toks
    pytest.fail(f"stream did not finish within {budget_s}s "
                f"({len(toks)} tokens seen) — shed-not-stall is broken")


def test_stage_sigkill_attributes_streams_then_engine_rebuilds(
        ray_start_4cpu):
    from ray_tpu.llm.pipeline import PipelinedEngine
    from ray_tpu.util import state

    pipe = PipelinedEngine(LLMConfig(**CFG_KW), n_stages=2, max_batch=4,
                           microbatch=2)
    try:
        # Healthy steady state first (also arms the rebuild-budget reset:
        # completed invocations zero the consecutive-failure count).
        warm = pipe.generate([[1, 2]], SamplingParams(temperature=0.0,
                                                      max_tokens=4))
        assert len(warm[0]) == 4

        dag_id = pipe._dag.dag_id
        # Kill the LAST stage: its death must propagate upstream through
        # the driver's head-of-line wait, not just break its own edge.
        victim_pid = ray_tpu.get(pipe._actors[-1].pid.remote(), timeout=30)

        streams = [pipe.submit([1, 2, 3],
                               SamplingParams(temperature=0.0,
                                              max_tokens=200))
                   for _ in range(3)]
        # Mid-generation for real: first token out before the kill.
        streams[0].next(timeout=30)
        t0 = time.monotonic()
        os.kill(victim_pid, signal.SIGKILL)

        for s in streams:
            with pytest.raises(DagStageError) as ei:
                while True:
                    s.next(timeout=DEADLINE_S + 10)
            e = ei.value
            assert e.stage and "step" in e.stage, (
                f"error does not name the stage: {e}")
            assert "died" in str(e)
        detect_s = time.monotonic() - t0
        assert detect_s < DEADLINE_S, (
            f"stream attribution took {detect_s:.1f}s "
            f"(> {DEADLINE_S}s deadline)")

        # Event plane saw the death, entity-linked to the dead graph.
        evs = _wait(
            lambda: [e for e in state.list_events(entity=dag_id)
                     if e["kind"] == "dag_stage_death"] or None,
            what="dag_stage_death event")
        assert "step" in evs[0]["attrs"]["stage"]

        # The engine rebuilt and RESUMED: a fresh request completes with
        # the same greedy tokens the pre-chaos model produced (new stage
        # actors, same seed, same shards).
        s2 = pipe.submit([1, 2], SamplingParams(temperature=0.0,
                                                max_tokens=4))
        assert _drain_bounded(s2, budget_s=90.0) == warm[0]
        assert pipe.num_active == 0
    finally:
        pipe.shutdown()
    # Shutdown after chaos is clean: no open streams, no stage actors.
    assert pipe._actors == [] and pipe._dag is None


def test_rebuild_waits_for_the_killed_stages_chips(shutdown_only):
    """On a cluster with chips every stage holds one, and a rebuild needs
    them all again: it waits until the killed stages' chips have come back
    instead of refusing (or starting a stage on the CPU)."""
    from ray_tpu.llm.pipeline import PipelinedEngine

    ray_tpu.init(num_cpus=4, num_tpus=2)
    pipe = PipelinedEngine(LLMConfig(**CFG_KW), n_stages=2, max_batch=4,
                           microbatch=2)
    try:
        assert ray_tpu.available_resources().get("TPU", 0) == 0
        sp = SamplingParams(temperature=0.0, max_tokens=4)
        warm = pipe.generate([[1, 2]], sp)
        old_pids = ray_tpu.get([a.pid.remote() for a in pipe._actors],
                               timeout=30)
        stream = pipe.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                       max_tokens=200))
        stream.next(timeout=30)
        os.kill(old_pids[0], signal.SIGKILL)
        with pytest.raises(DagStageError):
            while True:
                stream.next(timeout=DEADLINE_S + 10)
        assert _drain_bounded(pipe.submit([1, 2], sp), budget_s=90.0) == warm[0]
        new_pids = ray_tpu.get([a.pid.remote() for a in pipe._actors],
                               timeout=30)
        assert not set(new_pids) & set(old_pids)
        assert ray_tpu.available_resources().get("TPU", 0) == 0
    finally:
        pipe.shutdown()


def test_shutdown_mid_generation_never_hangs(ray_start_4cpu):
    """shutdown() with streams open ends every stream promptly (engine
    shut down => streams end; a consumer blocked in next() is released)."""
    from ray_tpu.llm.pipeline import PipelinedEngine

    pipe = PipelinedEngine(LLMConfig(**CFG_KW), n_stages=2, max_batch=4,
                           microbatch=2)
    s = pipe.submit([3, 4], SamplingParams(temperature=0.0,
                                           max_tokens=200))
    s.next(timeout=30)  # generation is live
    t0 = time.monotonic()
    pipe.shutdown()
    # The stream ends (StopIteration) rather than waiting out 200 tokens.
    _drain_bounded(s, budget_s=30.0)
    assert time.monotonic() - t0 < 30.0
    with pytest.raises(RuntimeError, match="shut down"):
        pipe.submit([1], SamplingParams(max_tokens=2))
