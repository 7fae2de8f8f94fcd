"""The sampler selects where it used to sort (`llm/sampler.py`
`_make_sampler`, `_kth_largest`): the kept set of every row, ties included,
is the one the sort-based sampler kept, the same key draws the same token,
and the lowered decode program holds one full-width sort, on the path a
nucleus takes, none on the path a top_k alone takes.

The oracle below is the sampler as it stood before the selection, two sorts
of the whole vocabulary a step, kept here word for word (its masked logits
returned beside its tokens)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams
from ray_tpu.llm.sampler import (_kept_logits, _kth_largest, _make_sampler,
                                 _sampler_path)

W = 128  # a lane tile: the widths a selection by candidates would turn on
VOCAB = 1000
TOP_KS = (1, 8, 50, W, W + 1, 0)
TOP_PS = (1e-9, 0.5, 0.9, 1.0)
ROWS = ("normal", "bf16", "equal", "neg_inf")


def oracle(vocab: int):
    def sample(logits, keys, temp, top_k, top_p):
        greedy = jnp.argmax(logits, axis=-1)
        lt = logits / jnp.maximum(temp, 1e-6)[:, None]
        sorted_lt = jnp.sort(lt, axis=-1)[:, ::-1]
        k_eff = jnp.clip(jnp.where(top_k > 0, top_k, vocab), 1, vocab)
        kth = jnp.take_along_axis(sorted_lt, (k_eff - 1)[:, None], axis=-1)
        lt = jnp.where(lt < kth, -jnp.inf, lt)
        probs = jax.nn.softmax(lt, axis=-1)
        sp = jnp.sort(probs, axis=-1)[:, ::-1]
        csum = jnp.cumsum(sp, axis=-1)
        # smallest prefix whose mass reaches top_p (always keeps the top
        # token: csum - sp is 0 for it)
        keep = (csum - sp) < top_p[:, None]
        min_keep = jnp.min(jnp.where(keep, sp, jnp.inf), axis=-1,
                           keepdims=True)
        lt = jnp.where(probs < min_keep, -jnp.inf, lt)
        sampled = jax.vmap(jax.random.categorical)(keys, lt)
        return jnp.where(temp <= 0.0, greedy, sampled).astype(jnp.int32), lt

    return sample


def row_of(kind: str, rng) -> np.ndarray:
    """One row of logits [VOCAB]. `bf16`: rounded to bfloat16 and of few
    distinct values, so the k-th largest has many equals; `equal`: one value
    everywhere; `neg_inf`: every fourth entry -inf, and fewer finite ones in
    its last stretch than a top_k of 50 asks for."""
    x = rng.normal(size=VOCAB).astype(np.float32)
    if kind == "bf16":
        x = np.asarray(jnp.asarray(np.round(x * 4) / 4, jnp.bfloat16)
                       .astype(jnp.float32))
    elif kind == "equal":
        x = np.full(VOCAB, 0.25, np.float32)
    elif kind == "neg_inf":
        x[::4] = -np.inf
    return x


def batch(rows, temps, top_ks, top_ps, seed=0):
    rng = np.random.default_rng(seed)
    n = len(rows)
    return (jnp.asarray(np.stack([row_of(kind, rng) for kind in rows])),
            jax.vmap(jax.random.PRNGKey)(
                jnp.arange(seed, seed + n, dtype=jnp.uint32)),
            jnp.asarray(temps, jnp.float32), jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(top_ps, jnp.float32))


SAMPLE = jax.jit(_make_sampler(VOCAB))
KEPT = jax.jit(_kept_logits)
ORACLE = jax.jit(oracle(VOCAB))


def agree(logits, keys, temp, top_k, top_p, live=None):
    """The rows somebody reads keep the oracle's set and draw its token."""
    want_tok, want_lt = ORACLE(logits, keys, temp, top_k, top_p)
    got_tok = SAMPLE(logits, keys, temp, top_k, top_p, live)
    got_lt = KEPT(logits, temp, top_k, top_p, live)
    read = np.ones(len(temp), bool) if live is None else np.asarray(live)
    sampled = read & (np.asarray(temp) > 0.0)
    # where a row samples, the masked logits are the oracle's to the bit
    np.testing.assert_array_equal(np.asarray(got_lt)[sampled],
                                  np.asarray(want_lt)[sampled])
    np.testing.assert_array_equal(np.asarray(got_tok)[read],
                                  np.asarray(want_tok)[read])
    return np.asarray(got_lt)


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("top_p", TOP_PS)
@pytest.mark.parametrize("top_k", TOP_KS)
def test_a_row_keeps_the_sorting_samplers_set_and_draws_its_token(
        top_k, top_p, rows):
    """One setting in a batch that also holds a greedy row, a row of the
    benchmark's traffic (top_k 50, top_p 1), a row that needs no order and
    a STALE row (its occupant left: top_k off, a nucleus on, which alone
    would ask for a sort) beside the live ones."""
    kinds = [rows, "normal", "bf16", rows, "normal"]
    temps = [0.7, 0.0, 0.7, 1.3, 1.0]
    top_ks = [top_k, 5, 50, 0, 0]
    top_ps = [top_p, 0.3, 1.0, 1.0, 0.4]
    live = jnp.asarray([True, True, True, True, False])
    for seed in (0, 1):
        args = batch(kinds, temps, top_ks, top_ps, seed)
        kept = agree(*args, live)
        assert np.isfinite(kept[0]).sum() >= 1  # the top token always stays
        agree(*args)  # and with every row read: the stale one counts


@pytest.mark.parametrize("case", [
    "every_row_greedy", "ties_wider_than_the_candidates",
    "top_k_of_the_whole_vocabulary", "one_row"])
def test_the_samplers_edges(case):
    if case == "every_row_greedy":
        args = batch(["normal", "bf16"], [0.0, -1.0], [50, 0], [0.5, 1.0])
    elif case == "ties_wider_than_the_candidates":
        # 400 equal values at the k-th place under a nucleus: more kept
        # than the selection has candidates, so the step sorts
        args = list(batch(["normal", "normal"], [1.0, 0.7], [50, 50],
                          [0.9, 1.0]))
        x = np.asarray(args[0]).copy()
        x[0, np.argsort(x[0])[-420:-20]] = 1.5
        args[0] = jnp.asarray(x)
        kept = agree(*args)
        assert np.isfinite(kept[0]).sum() > W
        return
    elif case == "top_k_of_the_whole_vocabulary":
        args = batch(["normal", "bf16"], [0.7, 0.7], [VOCAB, VOCAB + 7],
                     [1.0, 0.8])
    else:
        args = batch(["bf16"], [0.7], [50], [1.0])
    agree(*args)


PATHS = {
    # rows of (temperature, top_k, top_p, live) -> sorts in the step
    "the_benchmarks_traffic": ([(0.7, 50, 1.0, True)] * 3, 0),
    "no_row_needs_an_order": ([(0.7, 0, 1.0, True), (1.0, VOCAB, 1.0, True),
                               (0.0, 0, 0.5, True)], 0),
    "a_top_k_of_any_size": ([(0.7, W + 1, 1.0, True), (0.7, 1, 1.0, True),
                             (0.7, VOCAB - 1, 1.0, True)], 0),
    "a_nucleus": ([(0.7, 50, 1.0, True), (0.7, 50, 0.9, True)], 1),
    "a_bare_nucleus": ([(0.7, 50, 1.0, True), (1.0, 0, 0.4, True)], 1),
    "a_stale_row_with_a_nucleus": ([(0.7, 50, 1.0, True),
                                    (1.0, 0, 0.4, False)], 0),
    "a_greedy_row_with_a_nucleus": ([(0.7, 50, 1.0, True),
                                     (0.0, 0, 0.4, True)], 0),
    "equal_logits": ([(0.7, 50, 1.0, True)], 0),
    "equal_logits_under_a_nucleus": ([(0.7, 50, 0.9, True)], 1),
}


@pytest.mark.parametrize("case", PATHS)
def test_which_rows_send_a_step_to_the_sort(case, monkeypatch):
    """Counted where it runs: `jnp.sort` reports from inside the branch
    that holds it."""
    rows, want = PATHS[case]
    sorts = []

    def counted_sort(x, *a, **kw):
        jax.debug.callback(lambda: sorts.append(1))
        return sort(x, *a, **kw)

    sort = jnp.sort
    monkeypatch.setattr(jnp, "sort", counted_sort)
    temps, top_ks, top_ps, live = zip(*rows)
    kinds = ["equal" if case.startswith("equal") else "bf16"] * len(rows)
    args = batch(kinds, temps, top_ks, top_ps)
    got = jax.jit(_make_sampler(VOCAB))(*args, jnp.asarray(live))
    jax.effects_barrier()
    monkeypatch.undo()
    assert len(sorts) == want
    read = np.asarray(live)
    np.testing.assert_array_equal(np.asarray(got)[read],
                                  np.asarray(ORACLE(*args)[0])[read])


@pytest.mark.parametrize("rows", ROWS + ("signs",))
def test_the_kth_largest_is_the_sorts(rows):
    """Every k of every kind of row, against the sorted row: ties, -inf,
    both zeros and negative values among them."""
    rng = np.random.default_rng(3)
    if rows == "signs":
        x = np.concatenate([rng.normal(size=VOCAB - 6).astype(np.float32),
                            np.float32([0.0, -0.0, np.inf, -np.inf,
                                        1e-45, -1e-45])])
    else:
        x = row_of(rows, rng)
    ks = jnp.arange(1, VOCAB + 1, dtype=jnp.int32)
    tiled = jnp.broadcast_to(jnp.asarray(x), (VOCAB, VOCAB))
    got = np.asarray(jax.jit(_kth_largest)(tiled, ks))[:, 0]
    np.testing.assert_array_equal(got, np.sort(x)[::-1])


def test_the_host_names_the_path_the_device_takes():
    """`_sampler_path` is the static half of the device's own rule."""
    sp = SamplingParams
    assert _sampler_path([sp(temperature=0.0, top_k=0, top_p=0.5)]) == "greedy"
    assert _sampler_path([sp(top_k=50), sp(temperature=0.0, top_p=0.1),
                          sp(top_k=W + 1), sp()]) == "select"
    assert _sampler_path([sp(top_k=50), sp(top_k=50, top_p=0.9)]) == "sort"
    assert _sampler_path([sp(top_p=0.9)]) == "sort"


def lowered_chunk(eng, greedy: bool) -> str:
    return eng._chunk.lower(
        eng.params, eng._cache, eng._toks_dev, eng._lens_dev, eng._keys,
        eng._temps_dev, eng._topks_dev, eng._topps_dev, 2, greedy,
        jnp.int32(9), np.ones(eng.max_batch, bool)).as_text(debug_info=True)


def test_the_sampled_chunk_sorts_the_vocabulary_once_at_most():
    """Text of the lowered module: one full-width sort in the whole sampled
    chunk program, under the sampler's `nucleus` scope; the selection's
    scope holds none, nor a `top_k`, nor a loop; the greedy program holds
    no sort."""
    cfg = LLMConfig(vocab_size=512, d_model=64, n_layers=2, n_heads=4,
                    max_seq=64)
    eng = ContinuousEngine(cfg, max_batch=2, decode_chunk=4)
    try:
        eng._cache = eng._init_cache()
        text = lowered_chunk(eng, greedy=False)
        greedy = lowered_chunk(eng, greedy=True)
    finally:
        eng.shutdown()
    # jnp.sort is a function of its own in the module: one body, one call
    assert text.count('"stablehlo.sort"') == 1
    assert len(re.findall(r"call @sort\w*\(%\w+\) : "
                          r"\(tensor<2x512xf32>\)", text)) == 1
    assert "chlo.top_k" not in text
    names = re.findall(r'loc\("(sampler/[^"]+)"', text)
    in_select = [n for n in names if "/select/" in n]
    assert in_select and not [n for n in in_select
                              if "sort" in n or "while" in n]
    assert [n for n in names if "/nucleus/" in n and "sort)" in n]
    assert not [n for n in names if "sort)" in n and "/nucleus/" not in n]
    assert "stablehlo.sort" not in greedy
