"""Pipeline-parallel LLM decode on the compiled DAG plane (ISSUE 18).

Pins the tentpole contracts: stage slicing is an exact partition of the
single-process model (greedy decode is bit-identical between the 2-stage
PipelinedEngine and ContinuousEngine at the same seed), steady-state
activations ride device-object edges as placeholders with ZERO resolve
RPCs, the stage collective group is pre-negotiated at graph-build time
(no controller KV rendezvous), and the engine is a drop-in behind the
OpenAI serving surface. Satellite pins ride along: the flash-attention
tile clamp for the bench shape and the bench's fallback-flag (never
negative TFLOP/s) contract.
"""

import json
import time
import types
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm import LLMConfig
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams
from ray_tpu.llm.pipeline import stage_layer_split, stage_param_slice

# Small enough for seconds-scale CPU tests; d_model=64 x microbatch=4 puts
# the decode activation (4*1*64 f32 = 1KiB) exactly at the device-edge
# placeholder threshold, so the zero-RPC path is exercised for real.
CFG_KW = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
              max_seq=64)


# ------------------------------------------------------- stage slicing unit
def test_stage_layer_split_balanced_remainder_early():
    assert stage_layer_split(4, 2) == [(0, 1), (2, 3)]
    # Remainder layers land on the EARLIEST stages (the last stage already
    # carries final_norm + the tied head + the sampler).
    assert stage_layer_split(7, 3) == [(0, 1, 2), (3, 4), (5, 6)]
    assert stage_layer_split(3, 3) == [(0,), (1,), (2,)]
    with pytest.raises(ValueError, match="n_stages"):
        stage_layer_split(2, 3)
    with pytest.raises(ValueError, match="n_stages"):
        stage_layer_split(2, 0)


def test_stage_param_slice_global_names():
    params = {"tok_emb": "E", "final_norm": "N",
              **{f"layer_{i}": f"L{i}" for i in range(4)}}
    first = stage_param_slice(params, (0, 1), first=True, last=False)
    last = stage_param_slice(params, (2, 3), first=False, last=True)
    # Layer keys keep their GLOBAL names: a shard is a strict subtree of
    # the full checkpoint, not a renumbered copy.
    assert first == {"tok_emb": "E", "layer_0": "L0", "layer_1": "L1"}
    assert last == {"tok_emb": "E", "final_norm": "N",
                    "layer_2": "L2", "layer_3": "L3"}
    mid = stage_param_slice(params, (1,), first=False, last=False)
    assert mid == {"layer_1": "L1"}
    # Shards partition the layers exactly — nothing dropped, nothing
    # duplicated across a 2-way split.
    split = stage_layer_split(4, 2)
    layer_keys = [k for s, layers in enumerate(split)
                  for k in stage_param_slice(params, layers, s == 0, s == 1)
                  if k.startswith("layer_")]
    assert sorted(layer_keys) == sorted(f"layer_{i}" for i in range(4))


# ------------------------------------------------------------ engine parity
def test_pipeline_greedy_parity_with_single_process(ray_start_4cpu):
    """Greedy decode through the 2-stage pipeline is BIT-IDENTICAL to the
    single-process engine at the same seed — pipelining is a partition of
    the same model, not an approximation of it."""
    from ray_tpu.llm.pipeline import PipelinedEngine

    single = ContinuousEngine(LLMConfig(**CFG_KW), max_batch=4,
                              decode_chunk=4)
    pipe = PipelinedEngine(LLMConfig(**CFG_KW), n_stages=2, max_batch=4,
                           microbatch=2)
    try:
        prompts = [[1, 2, 3], [9, 8], [17], [4, 5, 6, 7]]
        sp = SamplingParams(temperature=0.0, max_tokens=12)
        want = single.generate(prompts, sp)
        got = pipe.generate(prompts, sp)
        assert got == want
        # And again — stage KV caches must reset cleanly between rounds.
        assert pipe.generate(prompts, sp) == want
    finally:
        pipe.shutdown()
        single.shutdown()


def test_pipeline_stages_never_run_on_cpu_beside_chips(shutdown_only):
    """On a cluster with chips each stage asks for one and the process that
    builds the pipeline holds none: the engine refuses to start rather than
    let a stage run on the CPU unannounced, or wait for a chip that its own
    parent holds."""
    import jax

    from ray_tpu.llm.openai import build_openai_app
    from ray_tpu.llm.pipeline import PipelinedEngine

    def build():
        return PipelinedEngine(LLMConfig(**CFG_KW), n_stages=2, max_batch=4,
                               microbatch=2)

    # Fewer chips than stages.
    ray_tpu.init(num_cpus=2, num_tpus=1)
    with pytest.raises(RuntimeError, match="1 of the cluster's 1 are free"):
        build()
    ray_tpu.shutdown()

    # Enough chips in the cluster, but one is held (here by another actor,
    # as the parent replica's own grant would be): free chips are counted,
    # not the cluster's total.
    ray_tpu.init(num_cpus=2, num_tpus=2)

    @ray_tpu.remote(num_cpus=0, num_tpus=1)
    class Holder:
        def ping(self):
            return True

    holder = Holder.remote()
    assert ray_tpu.get(holder.ping.remote(), timeout=60)
    with pytest.raises(RuntimeError, match="1 of the cluster's 2 are free"):
        build()

    # A builder that has a chip open itself (its JAX backend is the TPU).
    ray_tpu.kill(holder)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="holds a TPU chip itself"):
            build()

    # And the serve entry point says so before anything is deployed.
    with pytest.raises(ValueError, match="without num_tpus"):
        build_openai_app(LLMConfig(**CFG_KW), pipeline_stages=2,
                         ray_actor_options={"num_tpus": 1})


def test_pipeline_sampled_decode_and_active_count(ray_start_4cpu):
    from ray_tpu.llm.pipeline import PipelinedEngine

    pipe = PipelinedEngine(LLMConfig(**CFG_KW), n_stages=2, max_batch=4,
                           microbatch=2)
    try:
        sp = SamplingParams(temperature=0.8, top_k=20, max_tokens=10,
                            seed=7)
        outs = pipe.generate([[1, 2], [3, 4], [5, 6]], sp)
        for toks in outs:
            assert len(toks) == 10
            assert all(0 <= t < CFG_KW["vocab_size"] for t in toks)
        assert pipe.num_active == 0
        with pytest.raises(ValueError, match="max_seq"):
            pipe.submit(list(range(60)), SamplingParams(max_tokens=60))
    finally:
        pipe.shutdown()


def test_pipeline_zero_rpc_steady_state(ray_start_4cpu):
    """The zero-RPC proof, from the stages' own resolve counters: over a
    post-warmup decode window, activation placeholders flow on every
    inter-stage edge (edge_pins > 0), every consumer resolve lands in the
    local device store (store_hits > 0), and NO resolve takes an
    export/fetch RPC."""
    from ray_tpu.llm.pipeline import PipelinedEngine

    pipe = PipelinedEngine(LLMConfig(**CFG_KW), n_stages=2, max_batch=8,
                           microbatch=4)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=16)
        pipe.generate([[1, 2, 3]] * 8, sp)  # warm: jits + channel loops
        pipe.reset_pipeline_stats()
        pipe.generate([[i + 1, i + 2] for i in range(8)], sp)
        stats = pipe.pipeline_stats()
        assert stats["edge_pins"] > 0, (
            f"no placeholders pinned on activation edges: {stats}")
        assert stats["store_hits"] > 0, stats
        assert stats["resolve_rpcs"] == 0, (
            f"steady-state decode took resolve RPCs: {stats}")
        # Per-stage occupancy counters feed the rt_llm_pp_* gauges and
        # `ray-tpu top`'s PP% column: both stages did real work.
        assert len(stats["stages"]) == 2
        for s in stats["stages"]:
            assert s["steps"] > 0 and s["busy_s"] > 0
    finally:
        pipe.shutdown()


def test_occupancy_snapshot_windowed_per_consumer():
    """occupancy_snapshot is windowed PER CONSUMER: the first call anchors
    (0.0), later calls report busy fraction of wall time since that
    consumer's previous call — telemetry and metrics drains don't steal
    each other's windows."""
    from ray_tpu.llm import pipeline as pp

    stage = "pp-test-occ"
    pp._occ_record(stage, 0.0)
    assert pp.occupancy_snapshot("occ-a")[stage] == 0.0  # anchor
    pp.occupancy_snapshot("occ-b")  # anchor a second consumer
    pp._occ_record(stage, 0.04)
    time.sleep(0.08)
    frac_a = pp.occupancy_snapshot("occ-a")[stage]
    assert 0.0 < frac_a <= 1.0
    # Consumer b's window covers the same busy time independently.
    frac_b = pp.occupancy_snapshot("occ-b")[stage]
    assert 0.0 < frac_b <= 1.0
    # a's window restarted at its last call: immediately re-reading
    # reports ~0 busy fraction, not the cumulative one.
    assert pp.occupancy_snapshot("occ-a")[stage] < frac_a


# --------------------------------------------- pre-negotiated stage group
def test_prenegotiated_group_skips_kv_rendezvous(ray_start_4cpu):
    """init_prenegotiated_group: the coordinator gathers addresses ONCE
    and pushes the full rank->addr map; joining publishes nothing to the
    controller KV (no `col/<group>/addr/<rank>` keys ever exist) and the
    group still allreduces correctly."""
    from ray_tpu._private.worker import global_worker

    @ray_tpu.remote
    class PreWorker:
        def addr(self):
            from ray_tpu._private.worker import global_worker as gw

            return tuple(gw().server_addr)

        def join(self, world, rank, addrs, group):
            from ray_tpu.util import collective as col

            col.init_prenegotiated_group(world, rank, addrs, group,
                                         connect=True)
            return True

        def allreduce(self, value, group):
            from ray_tpu.util import collective as col

            return col.allreduce(np.asarray(value, np.float32),
                                 group_name=group)

    ws = [PreWorker.remote() for _ in range(2)]
    addrs = {r: ray_tpu.get(w.addr.remote(), timeout=60)
             for r, w in enumerate(ws)}
    g = "pre-dag"
    assert ray_tpu.get([w.join.remote(2, r, addrs, g)
                        for r, w in enumerate(ws)], timeout=60) == [True] * 2
    out = ray_tpu.get([w.allreduce.remote([float(r), 1.0], g)
                       for r, w in enumerate(ws)], timeout=120)
    for o in out:
        np.testing.assert_allclose(o, [0.0 + 1.0, 2.0])
    # The rendezvous namespace never saw this group: membership was
    # compile-time wiring, not controller KV polling.
    keys = global_worker().kv("keys", ns="collective",
                              prefix=f"col/{g}/addr")["keys"]
    assert keys == [], f"pre-negotiated group leaked rendezvous keys: {keys}"


def test_prenegotiated_group_validates_address_map(ray_start_2cpu):
    from ray_tpu.util import collective as col

    with pytest.raises(ValueError, match="address map"):
        col.init_prenegotiated_group(2, 0, {0: ("h", 1)}, "pre-bad")
    with pytest.raises(ValueError, match="address map"):
        col.init_prenegotiated_group(2, 0, {0: ("h", 1), 2: ("h", 2)},
                                     "pre-bad2")


# ------------------------------------------------- OpenAI drop-in surface
def test_openai_serve_over_pipeline_engine(ray_start_4cpu):
    """build_openai_app(pipeline_stages=2) swaps the pipeline engine in
    behind the SAME streaming surface: completions work over HTTP and
    /v1/stats reports the stage count."""
    import socket

    from ray_tpu import serve
    from ray_tpu.llm.openai import build_openai_app

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    app = build_openai_app(LLMConfig(**CFG_KW), model_id="pp-llm",
                           max_batch=4, default_max_tokens=8,
                           pipeline_stages=2)
    serve.run(app, route_prefix="/", port=port)
    try:
        base = f"http://127.0.0.1:{port}"
        body = json.dumps({"prompt": "hi", "max_tokens": 5,
                           "temperature": 0.0}).encode()
        req = urllib.request.Request(
            f"{base}/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            out = json.loads(r.read())
        assert len(out["token_ids"]) == 5
        assert out["choices"][0]["finish_reason"] == "length"
        with urllib.request.urlopen(f"{base}/v1/stats", timeout=30) as r:
            stats = json.loads(r.read())
        assert stats["pipeline_stages"] == 2
        # Each stage says where it runs, from inside its own process.
        assert [st["stage"] for st in stats["stages"]] == ["pp0", "pp1"]
        assert len({st["pid"] for st in stats["stages"]} | {stats["pid"]}) == 3
        assert all(st["platform"] == "cpu" for st in stats["stages"])
    finally:
        serve.shutdown()


# ------------------------------------------------------- satellite pins
def test_flash_attention_bench_shape_tiles():
    """The bench shape (b4 s2048 h8 d128) derives valid TPU tiles — the
    (8, 128) sublane/lane clamp that un-broke the flash-attention lane.
    Explicit caller blocks are preferences, re-clamped the same way."""
    from ray_tpu.ops.flash_attention import derive_blocks

    assert derive_blocks(2048, 2048) == (512, 1024)
    # Minimum-tile shapes resolve to the minimum tile, not a violation.
    assert derive_blocks(8, 128) == (8, 128)
    # Caller preferences above the sequence re-clamp to valid divisors.
    assert derive_blocks(16, 256, block_q=1024, block_k=1024) == (16, 256)
    with pytest.raises(ValueError, match="sublane"):
        derive_blocks(7, 128)
    with pytest.raises(ValueError, match="lane"):
        derive_blocks(8, 64)


def _fake_tpu_devices(monkeypatch):
    import jax

    monkeypatch.setattr(
        jax, "devices",
        lambda backend=None: [types.SimpleNamespace(platform="tpu")])


def test_flash_bench_kernel_failure_fails_the_lane(monkeypatch):
    """A kernel that rejects the bench shape (or fails to compile) fails
    the chip lane, and with it the run: it is neither logged as "skipped"
    nor turned into a fallback detail, and no TFLOP/s number is recorded."""
    import bench
    from ray_tpu.ops import flash_attention as fa_mod

    _fake_tpu_devices(monkeypatch)

    def reject(*a, **k):
        raise ValueError("no divisor aligned to the TPU lane tile")

    monkeypatch.setattr(fa_mod, "flash_attention", reject)
    results, details = {}, {}
    with pytest.raises(ValueError, match="lane tile"):
        bench._bench_flash_attention(results, details)
    assert "flash_attention_tflops" not in results
    assert "flash_attention" not in details


def test_bench_unknown_device_kind_is_an_error():
    """The peaks table is keyed by device_kind; a kind that is not in it
    raises instead of dropping the utilization line."""
    import types

    import bench

    peak, kind = bench.tpu_peak_flops(
        types.SimpleNamespace(device_kind="TPU v5 lite"))
    assert peak == 197e12 and kind == "TPU v5 lite"
    with pytest.raises(KeyError, match="TPU_PEAK_BF16"):
        bench.tpu_peak_flops(types.SimpleNamespace(device_kind="TPU v9"))


def test_flash_bench_fallback_flag_on_nonmonotonic_timing(monkeypatch):
    """A timing window where the long chain is not slower than the short
    one (noise-dominated link) must yield the fallback flag, NEVER a
    negative TFLOP/s (the r05 bench regression)."""
    import bench
    from ray_tpu.ops import flash_attention as fa_mod

    _fake_tpu_devices(monkeypatch)
    # Identity "kernel": traces fine on CPU so the lane reaches timing.
    monkeypatch.setattr(fa_mod, "flash_attention",
                        lambda q, k, v, causal=True: q)
    # Frozen clock: every measured duration is 0 -> per_call <= 0.
    monkeypatch.setattr(bench.time, "perf_counter", lambda: 0.0)
    results, details = {}, {}
    bench._bench_flash_attention(results, details)
    assert "flash_attention_tflops" not in results
    assert details["flash_attention"]["fallback"] is True
    assert "non-monotonic" in details["flash_attention"]["reason"]
