"""The expert layer's decode step on the chip (README "Serving hot loop";
`ops/expert_decode.py` `occupied_experts`): one Pallas kernel whose weight
reads follow the held experts that got a row.

Here, on the CPU in interpret mode: the kernel against the dense arm's own
arithmetic, with the untouched experts' weights NaN (a NaN that was read
would show), and against the sequence of roundings and float32 sums the
chip's dense arm makes, to the bit, at the four cells' rows and held
experts; the rule, which keeps the dense arm everywhere but in a decode
step on the chip; the layer (both routers) and the engine through the
kernel against themselves through the dense arm, and the counters
`moe_touched` and `moe_fetched` either way. What the TPU's compiler makes
of it is tests/test_v5e_compile.py's; the two arms bit for bit on the chip
at the four cells' shapes is `chip_smoke.py`'s `kernels` phase."""

import functools
import inspect
import time
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from ray_tpu._private import tracing
from ray_tpu.llm import LLMConfig
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams
from ray_tpu.models import moe
from ray_tpu.models.published import model_config
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.ops import attention
from ray_tpu.ops import expert_decode as ed

N, D, F, HELD = 16, 256, 384, 6

#: name -> the held experts that get rows, and how many rows each
CASES = {
    "no_expert_touched": ([], 0),
    "one_expert": ([2], 3),
    "all_experts": ([0, 1, 2, 3, 4, 5], 5),
    "the_first_place_untouched": ([1, 3, 4], 2),
    "the_first_and_the_last": ([0, 5], 1),
    # every row of the batch but two selects no held expert
    "rows_whose_gates_are_all_zero": ([4], 2),
}


def dense_arm(x, gates, w_gate, w_up, w_down):
    """`models/moe.py`'s dense arm, line for line, over rows [N, D]."""
    xc = x[:, None]
    gate_h = nn.silu(jnp.einsum("bsd,edf->ebsf", xc, w_gate))
    up_h = jnp.einsum("bsd,edf->ebsf", xc, w_up)
    expert_out = jnp.einsum("ebsf,efd->ebsd", gate_h * up_h, w_down)
    return jnp.einsum("ebsd,bse->bsd", expert_out,
                      gates[:, None].astype(x.dtype))[:, 0]


def stacks(dtype, n=N, d=D, f=F, held=HELD):
    """Rows of near-unit entries and experts whose products are near-unit
    too, from one seed."""
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(keys[0], (n, d), dtype)
    w_gate, w_up, w_down = (
        (jax.random.normal(key, shape) / shape[1] ** 0.5).astype(dtype)
        for key, shape in zip(keys[1:], [(held, d, f), (held, d, f),
                                         (held, f, d)]))
    return x, w_gate, w_up, w_down


def exact_reciprocal(monkeypatch) -> None:
    """The kernel's `silu` takes the chip's approximate reciprocal, as the
    dense arm's fusion does there (the same bits, `chip_smoke.py`).
    Interpret mode stands a bf16 division in for it, 2**-8 off; here it is
    the division itself."""
    monkeypatch.setattr(ed.pl, "reciprocal", lambda v, approx=False: 1.0 / v)


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 0.04),
                                       (jnp.float32, 1e-4)],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 384)],
                         ids=["blocks_2x3", "one_block_a_matrix"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_dense_arms_sum_and_reads_no_untouched_expert(
        case, blocks, dtype, tol, monkeypatch):
    """The dense arm's result within the operands' rounding (the CPU's
    compiler rounds the dense arm's five lines elsewhere than the chip's,
    whose roundings the kernel makes), in the operands' dtype, the number
    of experts read, and a finite result with every untouched expert's
    three matrices NaN."""
    exact_reciprocal(monkeypatch)
    monkeypatch.setattr(ed, "BLOCK_BYTES",
                        blocks[0] * F * jnp.dtype(dtype).itemsize)
    assert ed.expert_blocks(D, F, dtype) == blocks
    touched, each = CASES[case]
    rng = np.random.default_rng(len(case))
    gates = np.zeros((N, HELD), np.float32)
    for e in touched:
        gates[rng.choice(N, size=each, replace=False), e] = rng.uniform(
            0.1, 1.0, each)
    rows_here = jnp.asarray((gates > 0).sum(0), jnp.int32)
    x, *weights = stacks(dtype)
    want = dense_arm(x, jnp.asarray(gates), *weights).astype(jnp.float32)
    unread = jnp.asarray([e not in touched for e in range(HELD)])
    poisoned = [jnp.where(unread[:, None, None], jnp.nan, w) for w in weights]
    got, fetched = ed.occupied_experts(x, jnp.asarray(gates), rows_here,
                                       *poisoned, interpret=True)
    assert int(fetched) == len(touched)
    assert got.shape == (N, D) and got.dtype == dtype
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=tol,
                               rtol=tol)
    if not touched:
        assert not np.asarray(got).any()


def test_touched_first_lists_the_experts_with_a_row_before_the_others():
    order, n = ed.touched_first(jnp.asarray([0, 3, 0, 0, 1, 7, 0], jnp.int32))
    assert (order.tolist(), int(n)) == ([1, 4, 5, 0, 2, 3, 6], 3)
    order, n = ed.touched_first(jnp.zeros((4,), jnp.int32))
    assert (order.tolist(), int(n)) == ([0, 1, 2, 3], 0)


# ------------------------------------------------------ the rounding contract
def passes(a, b):
    """`a @ b` in float32, the contraction one lane tile after another: the
    order of the matrix unit's passes, which is the dense arm's on the chip
    and the kernel's (`ed._passes`)."""
    out = jnp.zeros((*a.shape[:-1], b.shape[-1]), jnp.float32)
    for at in range(0, a.shape[-1], ed.LANES):
        out = out + jnp.matmul(a[..., at:at + ed.LANES],
                               b[..., at:at + ed.LANES, :],
                               preferred_element_type=jnp.float32)
    return out


def chip_sequence(x, gates, w_gate, w_up, w_down):
    """What the chip's compiler makes of the dense arm's five lines (read
    from its optimised program and measured bit for bit, PERF.md section 6,
    PR 47): rounded to the operands' dtype are `x W_gate`, `silu` of it
    times the float32 `x W_up`, each row's gate, and the result; an
    expert's output stays float32 and the experts are summed in float32,
    ascending."""
    def rounded(v):
        return v.astype(x.dtype).astype(jnp.float32)

    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_gate.shape[0]):
        gate = rounded(passes(x, w_gate[e]))
        h = (gate * (1.0 / (1.0 + jnp.exp(-gate)))
             * passes(x, w_up[e])).astype(x.dtype)
        out = out + passes(h, w_down[e]) * rounded(gates[:, e:e + 1])
    return out.astype(x.dtype)


def pr43_sequence(x, gates, w_gate, w_up, w_down):
    """The kernel's arithmetic before this contract (PR 43): the float32
    gate applied BEFORE the down projection, nothing rounded but the gated
    hidden rows, the experts summed in float32. Closer to float32 than the
    dense arm, and another program's routing (ISSUE 47)."""
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_gate.shape[0]):
        h = (jax.nn.silu(passes(x, w_gate[e])) * passes(x, w_up[e])
             * gates[:, e:e + 1]).astype(x.dtype)
        out = out + passes(h, w_down[e])
    return out


#: cell -> the rows of its decode step and the experts it holds, as served,
#: at a QUARTER of its widths (hidden, expert): the kernel's blocks and
#: passes are whole lane tiles either way, and the four stacks stay 100 MB
CELLS = {"kimi_k2": (32, 1792, 512, 12), "trinity_mini": (16, 512, 256, 16),
         "kimi_linear": (64, 768, 256, 16), "longcat_flash": (32, 1536, 512, 16)}
#: name -> the share of the held experts that get rows
TOUCHED = {"none": 0.0, "one": None, "some": 0.5, "all": 1.0}


def routed(n, held, share, router, seed=0):
    """Gates [n, held] float32 as a router of the kind makes them: a
    sigmoid's normalised scores times 2.5, or a softmax's times 6, for
    about a quarter of the rows of each touched expert."""
    rng = np.random.default_rng(seed)
    touched = ([int(rng.integers(held))] if share is None else
               rng.choice(held, size=round(held * share),
                          replace=False).tolist())
    gates = np.zeros((n, held), np.float32)
    for e in touched:
        rows = rng.choice(n, size=max(1, n // 4), replace=False)
        gates[rows, e] = (rng.uniform(0.05, 0.4, rows.size) * 2.5
                          if router == "sigmoid" else
                          rng.uniform(0.002, 0.05, rows.size) * 6.0)
    return jnp.asarray(gates), touched


@pytest.mark.parametrize("router", ["sigmoid", "softmax"])
@pytest.mark.parametrize("touched", list(TOUCHED))
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_kernel_makes_the_chips_dense_arm_to_the_bit(cell, touched,
                                                         router, monkeypatch):
    """At each cell's rows and held experts, in bf16, for 0, 1, half and
    all of the held experts touched and both routers' gates: the kernel IS
    the chip's dense sequence, bit for bit, in blocks of several passes;
    and within the CPU compiler's own roundings of the dense arm."""
    exact_reciprocal(monkeypatch)
    n, d, f, held = CELLS[cell]
    monkeypatch.setattr(ed, "BLOCK_BYTES", 128 * max(d, f) * 2)
    td, tf = ed.expert_blocks(d, f, jnp.bfloat16)
    assert td > ed.LANES and d // td > 1 and f // tf > 1  # passes, blocks
    x, *weights = stacks(jnp.bfloat16, n, d, f, held)
    gates, which = routed(n, held, TOUCHED[touched], router)
    rows_here = jnp.asarray((np.asarray(gates) > 0).sum(0), jnp.int32)
    got, fetched = ed.occupied_experts(x, gates, rows_here, *weights,
                                       interpret=True)
    assert int(fetched) == len(which) and got.dtype == jnp.bfloat16
    got = np.asarray(got, np.float32)
    want = np.asarray(chip_sequence(x, gates, *weights), np.float32)
    cpu = np.asarray(dense_arm(x, gates, *weights), np.float32)
    step = 2.0 ** (np.floor(np.log2(max(np.abs(cpu).max(), 1e-30))) - 7)
    # (the CPU's compiler may fuse the weighted sum's multiply and add in
    # one of the two: an output in 50,000 is then the next bf16 value)
    assert (got != want).mean() < 1e-4 and np.abs(got - want).max() < step
    assert np.abs(got - cpu).max() <= 2 * step


def test_the_rounding_contract_is_the_dense_arms_and_not_pr43s(monkeypatch):
    """Seeded near-unit inputs at Kimi K2's rows: the kernel and the chip's
    dense sequence are the same bits, so they agree to less than one bf16
    step of the largest output everywhere; PR 43's arithmetic, closer to
    float32, rounds elsewhere in over a fifth of the outputs, by a whole
    bf16 step of a near-unit output (2**-7) in some: the difference that
    served Kimi K2 another token."""
    exact_reciprocal(monkeypatch)
    n, d, f, held = CELLS["kimi_k2"]
    x, *weights = stacks(jnp.bfloat16, n, d, f, held)
    gates, _ = routed(n, held, 1.0, "sigmoid", seed=1)
    rows_here = jnp.asarray((np.asarray(gates) > 0).sum(0), jnp.int32)
    got, _ = ed.occupied_experts(x, gates, rows_here, *weights,
                                 interpret=True)
    got = np.asarray(got, np.float32)
    want = np.asarray(chip_sequence(x, gates, *weights), np.float32)
    before = np.asarray(pr43_sequence(x, gates, *weights).astype(
        jnp.bfloat16), np.float32)
    step = 2.0 ** (np.floor(np.log2(np.abs(want).max())) - 7)
    assert np.abs(got - want).max() < step and (got != want).mean() < 1e-4
    assert (before != want).mean() > 0.2
    assert np.abs(before - want).max() >= 2.0 ** -7


# ------------------------------------------------------------------ the rule
def as_the_chip(monkeypatch) -> None:
    """The expert rule's question about the backend answered as the chip
    would (its own `rule` only: the attention dispatchers keep the CPU's
    answer), and the kernel in interpret mode (`exact_reciprocal`)."""
    exact_reciprocal(monkeypatch)
    monkeypatch.setattr(ed, "rule", types.SimpleNamespace(
        on_tpu=lambda: True, mesh_refusal=attention.mesh_refusal,
        NOT_ASKED=attention.NOT_ASKED))
    monkeypatch.setattr(moe, "occupied_experts", functools.partial(
        ed.occupied_experts, interpret=True))


@pytest.mark.parametrize("name,x,d_ff,serving,mesh,why", [
    ("longcat_flash_decode_step", (32, 1, 6144), 2048, True, 0, None),
    ("kimi_k2_decode_step", (32, 1, 7168), 2048, True, 0, None),
    ("trinity_mini_decode_step", (16, 1, 2048), 1024, True, 0, None),
    ("kimi_linear_decode_step", (64, 1, 2304), 1024, True, 0, None),
    ("toy_in_float32_tiles", (8, 1, 128), 128, True, 0, "sublane"),
    ("a_prefill_bucket", (1, 64, 6144), 2048, True, 0, "decode step"),
    ("training", (32, 1, 6144), 2048, False, 0, "decode step"),
    ("experts_of_no_whole_lane_tile", (32, 1, 6144), 2000, True, 0,
     "lane tiles"),
    ("a_model_width_of_no_whole_lane_tile", (32, 1, 6100), 2048, True, 0,
     "lane tiles"),
    ("nine_slots", (9, 1, 6144), 2048, True, 0, "sublane"),
    ("tp_mesh", (32, 1, 6144), 2048, True, 2, "not partitioned"),
])
def test_the_rule(name, x, d_ff, serving, mesh, why, monkeypatch):
    """On the CPU nothing is asked. On the chip the kernel is taken for a
    decode step (one position a slot, `serving`) of ANY expert layer, a
    sigmoid router's as a router's with identity experts, with experts and
    rows of whole tiles and no mesh of several devices in context; the
    reason for the dense arm otherwise. Nothing else decides: the rule has
    no word on what the layer counts."""
    assert "counts_touched" not in inspect.signature(
        ed.occupied_refusal).parameters
    ask = functools.partial(ed.occupied_refusal, x, d_ff, serving=serving)
    assert ask() == attention.NOT_ASKED
    as_the_chip(monkeypatch)
    if mesh:
        with Mesh(np.array(jax.devices()[:mesh]), ("tp",)):
            got = ask()
    else:
        got = ask()
    if name == "toy_in_float32_tiles":
        assert ask(dtype=jnp.float32) is None  # eight rows: a float32 tile
    assert (got is None) if why is None else (why in got), got


# ----------------------------------------------------------------- the layer
LAYER = dict(vocab_size=64, d_model=128, n_layers=1, n_heads=2, d_ff=128,
             max_seq=16, dtype=jnp.float32, param_dtype=jnp.float32,
             moe_experts=16, moe_top_k=4, moe_d_ff=128, moe_scoring="softmax",
             moe_norm_topk=False, moe_routed_scale=6.0, moe_score_bias=True,
             experts_held=4, first_expert=4)


def layer_out(cfg, x, serving):
    net = moe.MoE(cfg)
    params = net.init(jax.random.PRNGKey(0), x)["params"]
    # a router whose scores differ, so that the held experts get rows
    params = {**params, "router": params["router"] * 40.0}
    y, out = net.apply({"params": params}, x, serving=serving,
                       mutable=["stats"])
    return y, {k: np.asarray(v) for k, v in out["stats"].items()}


#: router -> what `LAYER` becomes: the softmax router with identity experts
#: (LongCat-Flash), the sigmoid router with normalised weights and a shared
#: expert (Kimi K2, Trinity-Mini, Kimi Linear)
ROUTERS = {"softmax_with_identity_experts": dict(moe_zero_experts=8),
           "sigmoid": dict(moe_scoring="sigmoid", moe_norm_topk=True,
                           moe_routed_scale=2.5, moe_shared_experts=1)}


@pytest.mark.parametrize("router", list(ROUTERS))
@pytest.mark.parametrize("name", ["the_cpu", "a_tp_mesh", "serving_is_false"])
def test_the_dense_arm_stays_where_the_rule_says_and_fetches_every_expert(
        name, router, monkeypatch):
    """Each refusal keeps the dense arm's result to the bit, the kernel is
    never called, and of the four of `picks`, which every expert layer sows,
    `moe_touched` is the held experts that got a row, at most the rows and
    at least the rows over the batch, and `moe_fetched` every held expert."""
    cfg = TransformerConfig(**{**LAYER, **ROUTERS[router]})
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 1, 128), jnp.float32)
    serving = name != "serving_is_false"
    want, _ = layer_out(cfg, x, serving)
    if name != "the_cpu":
        as_the_chip(monkeypatch)

    def never(*args, **kwargs):
        raise AssertionError(f"the occupied kernel under {name}")

    monkeypatch.setattr(moe, "occupied_experts", never)
    if name == "a_tp_mesh":
        with Mesh(np.array(jax.devices()[:2]), ("tp",)):
            got, stats = layer_out(cfg, x, serving)
    else:
        got, stats = layer_out(cfg, x, serving)
    np.testing.assert_array_equal(got, want)
    picks, zero_picks, touched, fetched = stats["picks"].tolist()
    rows = int(stats["expert_rows"].sum())
    assert picks == 8 * 4 and (zero_picks > 0) == (cfg.moe_zero_experts > 0)
    assert touched == int((stats["expert_rows"] > 0).sum())
    assert rows / 8 <= touched <= min(rows, 4) and fetched == 4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("router", list(ROUTERS))
def test_the_layer_through_the_kernel_is_the_layer_through_the_dense_arm(
        router, dtype, monkeypatch):
    """A decode step of sixteen rows under either router: the same sum (in
    bf16 within the CPU compiler's roundings of the dense arm), the identity
    or shared experts' part on top as before, the other counters the dense
    arm's and `moe_fetched` the experts that got a row."""
    cfg = TransformerConfig(**{**LAYER, **ROUTERS[router], "dtype": dtype})
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 1, 128), jnp.float32)
    want, dense = layer_out(cfg, x, True)
    as_the_chip(monkeypatch)
    got, stats = layer_out(cfg, x, True)
    tol = 1e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)
    touched = int((stats["expert_rows"] > 0).sum())
    assert 0 < touched <= 4
    assert stats["picks"].tolist() == [*dense["picks"][:3], touched]
    assert dense["picks"][3] == 4


# ---------------------------------------------------------------- the engine
SERVED = LLMConfig(
    vocab_size=96, d_model=128, n_layers=2, n_heads=2, max_seq=64,
    dtype="float32", seed=0, experts_held=4, first_expert=4,
    arch={"model_type": "longcat_flash", "attention_method": "MLA",
          "attention_bias": False, "q_lora_rank": 24, "kv_lora_rank": 32,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
          "ffn_hidden_size": 128, "expert_ffn_hidden_size": 128,
          "n_routed_experts": 16, "zero_expert_num": 8,
          "zero_expert_type": "identity", "moe_topk": 4,
          "routed_scaling_factor": 6, "rope_theta": 10000000,
          "rms_norm_eps": 1e-5})


def served(monkeypatch):
    """Ten greedy requests on eight slots: their tokens, and the expert
    counters of every chunk read."""
    counted = []

    def record_span(trace_id, span_id, parent, name, kind, start, end,
                    attrs=None):
        if name == "engine.host_sync" and (attrs or {}).get("moe_steps"):
            counted.append(attrs)

    monkeypatch.setattr(tracing, "_ON", True)
    monkeypatch.setattr(tracing, "record_span", record_span)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 96, size=n).tolist()
               for n in (5, 30, 17, 9, 3, 24, 12, 21, 7, 14)]
    eng = ContinuousEngine(SERVED, max_batch=8, decode_chunk=4)
    try:
        model = model_config(SERVED)
        assert (model.held_experts, model.moe_zero_experts) == (4, 8)
        tracing._ctx.set(("5" * 32, "6" * 16))  # (spans need a parent)
        streams = [eng.submit(p, SamplingParams(max_tokens=m,
                                                temperature=0.0))
                   for p, m in zip(prompts, (12, 6, 9, 20, 5, 16, 8, 3, 11,
                                             14))]
        tracing._ctx.set(None)
        tokens = [s.tokens() for s in streams]
        deadline = time.monotonic() + 60
        while eng.num_active and time.monotonic() < deadline:
            time.sleep(0.01)
        return tokens, counted, eng.cache_stats()
    finally:
        eng.shutdown()


def test_the_engine_serves_the_same_tokens_through_the_kernel(monkeypatch):
    """The tokens with the kernel forced, in interpret mode, are the dense
    arm's; under the kernel every chunk's `moe_fetched` is its
    `moe_touched`, under the dense arm every held expert of every layer
    and step; `/v1/stats` carries both totals."""
    want, dense, st = served(monkeypatch)
    assert dense and all(
        at["moe_fetched"] == 4 * 2 * at["moe_steps"] >= at["moe_touched"]
        for at in dense)
    assert st["moe_fetched_total"] == sum(at["moe_fetched"] for at in dense)
    assert st["moe_touched_total"] == sum(at["moe_touched"] for at in dense)
    as_the_chip(monkeypatch)
    got, kernel, st = served(monkeypatch)
    assert got == want
    assert kernel and all(at["moe_fetched"] == at["moe_touched"]
                          for at in kernel)
    assert 0 < st["moe_fetched_total"] == st["moe_touched_total"] < sum(
        4 * 2 * at["moe_steps"] for at in kernel)
