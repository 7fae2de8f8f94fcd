"""The ragged decode attention (README "Serving hot loop";
`ops/decode_attention.py` `ragged_decode_attention`): one Pallas kernel
that stops every slot at its own length, reads nothing of a free slot, and
takes the cache leaf `[slots, rows, KV heads, row]` as it lies.

Here, on the CPU in interpret mode (tests/test_ops.py's way): the kernel
against the dense masked attention over the whole cache for a head of its
own and for grouped heads, full leaves and rings, the lengths at which a
block begins and ends, and free rows; the dispatcher's rule; and the engine
serving the same tokens through the kernel as through the XLA walk. What
the TPU's compiler makes of it is tests/test_v5e_compile.py's."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams
from ray_tpu.models import mla
from ray_tpu.ops import attention

# (the module: `ray_tpu.ops.decode_attention` the attribute may be the
# function, which the package pins over it on first use)
da = importlib.import_module("ray_tpu.ops.decode_attention")

#: (a slot's last block is fetched in pieces of GRANULE rows)
ROWS, BLOCK, GRANULE = 64, 16, 4

#: name -> (query heads, key/value heads, head size, cache row)
FAMILIES = {
    # Phi-3: a head of its own, 96 wide in rows of 128 (zeros beyond 96)
    "heads_of_96_in_rows_of_128": (32, 32, 96, 128),
    # Trinity-Mini: eight query heads a key/value head of 128
    "32_heads_on_4_of_128": (32, 4, 128, 128),
    # Ouro: a head of its own, as wide as its row
    "16_heads_of_128": (16, 16, 128, 128),
}

#: name -> (each slot's length as the device holds it, its `live` mark).
#: A leaf is a ring where a length can pass its rows: the rows of the last
#: `rows` positions are all of it, in whatever order.
CASES = {
    "full_leaf_one_row_a_blocks_edge_one_past_it_all_rows": (
        [1, BLOCK, BLOCK + 1, ROWS], [True] * 4),
    "ring_not_yet_wrapped": ([ROWS - 1, 3 * BLOCK, 2, ROWS - BLOCK + 1],
                             [True] * 4),
    "ring_exactly_full": ([ROWS] * 4, [True] * 4),
    "ring_wrapped_twice": ([2 * ROWS + 5, 2 * ROWS, 3 * ROWS - 1, ROWS + 1],
                           [True] * 4),
    "free_row_whose_stale_length_exceeds_the_rows": (
        [7, 10 * ROWS, BLOCK + 1, ROWS], [True, False, True, True]),
    "free_rows_first_and_last": ([9 * ROWS, 2 * BLOCK, 5, 4 * ROWS],
                                 [False, True, True, False]),
    "all_rows_free": ([5, 10 * ROWS, BLOCK, ROWS], [False] * 4),
    # (the work list at its longest: `slots x blocks` entries)
    "every_slot_shows_every_row": ([ROWS] * 4, [True] * 4),
    "stops_at_whole_blocks_and_one_row_past_them": (
        [2 * BLOCK, 2 * BLOCK + 1, 3 * BLOCK, 3 * BLOCK + 1], [True] * 4),
    "stops_at_whole_pieces_and_one_row_past_them": (
        [GRANULE, GRANULE + 1, BLOCK + 3 * GRANULE, BLOCK + 3 * GRANULE + 1],
        [True] * 4),
    "a_blocks_last_piece_and_the_row_before_it": (
        [BLOCK - GRANULE, BLOCK - GRANULE + 1, BLOCK - 1, ROWS - 1],
        [True, True, True, False]),
}


def on_the_chip(monkeypatch, block_bytes: int) -> None:
    """The dispatcher's questions answered as the chip would, for an engine
    here on the CPU: the backend is a TPU (one patch for both dispatchers),
    the ragged kernel runs in interpret mode in row blocks of `block_bytes`
    of K (`BLOCK_BYTES`, the one place a block comes from), and the
    prefill keeps its XLA form (the flash kernel has tests of its own)."""
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "kernel_refusal",
                        lambda *shapes, **kw: attention.NOT_ASKED)
    monkeypatch.setattr(da, "ragged_decode_attention", functools.partial(
        da.ragged_decode_attention, interpret=True))
    monkeypatch.setattr(da, "BLOCK_BYTES", block_bytes)
    monkeypatch.setattr(da, "GRANULE_BYTES", block_bytes // 4)


def leaves(family: str, dtype, poison=(), stops=None):
    hq, kv, d, row = FAMILIES[family]
    keys = jax.random.split(jax.random.PRNGKey(len(family)), 3)
    q = jax.random.normal(keys[0], (4, hq, d), dtype)
    wide = ((0, 0),) * 3 + ((0, row - d),)
    k, v = (jnp.pad(jax.random.normal(key, (4, ROWS, kv, d), dtype), wide)
            for key in keys[1:])
    for slot in poison:  # what a free row's stale steps may have left
        k, v = k.at[slot].set(jnp.nan), v.at[slot].set(jnp.nan)
    if stops is not None:  # and an earlier occupant above a live slot's stop
        past = (jnp.arange(ROWS)[None, :] >= jnp.asarray(stops)[:, None])[
            ..., None, None]
        k, v = jnp.where(past, jnp.nan, k), jnp.where(past, jnp.nan, v)
    return q, k, v, d


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_kernel_reads_each_slots_own_rows_and_none_of_a_free_one(
        family, case, dtype, monkeypatch):
    """Against `_xla_decode_attention` over the rows a slot shows
    (`min(length, rows)`). A free row's cache is never read: filled with
    NaN here, it leaves its neighbours' outputs as they were, and its own
    output, which nobody reads, is zeros. Nor does a row past a live slot's
    stop reach the output, fetched (the rest of its piece) or not: every
    such row is NaN here too, K and V."""
    lens, live = CASES[case]
    free = [i for i, seated in enumerate(live) if not seated]
    q, k, v, d = leaves(family, dtype, poison=free, stops=lens)
    # (block and piece are derived from the row's bytes: BLOCK and GRANULE
    # rows of this leaf)
    row_bytes = k.shape[2] * k.shape[3] * k.dtype.itemsize
    monkeypatch.setattr(da, "BLOCK_BYTES", BLOCK * row_bytes)
    monkeypatch.setattr(da, "GRANULE_BYTES", GRANULE * row_bytes)
    assert da.row_block(k.shape, k.dtype) == BLOCK
    assert da.row_granule(k.shape, k.dtype) == GRANULE
    got = da.ragged_decode_attention(
        q, k, v, jnp.asarray(lens, jnp.int32), jnp.asarray(live),
        interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    assert np.all(got[free] == 0.0)
    seated = [i for i, s in enumerate(live) if s]
    if not seated:
        return
    shown = jnp.minimum(jnp.asarray(lens, jnp.int32), ROWS)
    clean = lambda leaf: jnp.nan_to_num(leaf[..., :d])  # noqa: E731
    want = np.asarray(_xla_f32(q, clean(k), clean(v), shown))
    # bf16: the output's own rounding, and where heads share key/value
    # heads the probabilities' too (V's dtype, as the grouped XLA walk)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[seated], want[seated], atol=tol, rtol=0)


def _xla_f32(q, k, v, lengths):
    return da._xla_decode_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        lengths)


def test_without_a_live_mask_every_row_counts_and_the_block_is_derived():
    """`live` left out (a caller that knows no free rows): every slot
    shows its own rows. The row block comes from the leaf's row bytes: a
    short leaf is one block, Phi-3's rows of 32 heads of 128 go 128 a
    block and Trinity-Mini's of 4 heads 1024, both a MiB of K."""
    q, k, v, d = leaves("32_heads_on_4_of_128", jnp.float32)
    lens = jnp.asarray([1, 17, 40, 64], jnp.int32)
    got = da.ragged_decode_attention(q, k, v, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_xla_f32(q, k, v, lens)), atol=2e-5)
    assert da.row_block(k.shape, k.dtype) == ROWS
    assert da.row_block((8, 2048, 32, 128), jnp.bfloat16) == 128
    assert da.row_block((16, 8192, 4, 128), jnp.bfloat16) == 1024
    assert da.row_block((16, 2048, 4, 128), jnp.bfloat16) == 1024
    assert da.row_block((2, 8 * 1031, 32, 128), jnp.bfloat16) == 8
    assert da.row_block((2, 2 * 1031, 32, 128), jnp.bfloat16) is None
    # a slot's last block goes in pieces of 128 KiB of K: an eighth of it
    assert da.row_granule((8, 2048, 32, 128), jnp.bfloat16) == 16
    assert da.row_granule((16, 2048, 16, 128), jnp.bfloat16) == 32
    assert da.row_granule((16, 8192, 4, 128), jnp.bfloat16) == 128
    assert da.row_granule(k.shape, k.dtype) == ROWS  # (a short leaf: one)
    assert da.row_granule((2, 8 * 1031, 32, 128), jnp.bfloat16) == 8
    assert da.row_granule((2, 2 * 1031, 32, 128), jnp.bfloat16) is None


#: name -> each slot's visible rows (0: a free slot), leaves of four blocks
WORK_LISTS = {
    "all_slots_free_an_entry_each": [0, 0, 0],
    "one_slot_one_entry": [BLOCK - 1],
    "every_slot_full_slots_x_blocks_entries": [4 * BLOCK] * 3,
    "free_slots_between_and_a_blocks_edge": [0, BLOCK, 0, BLOCK + 1, 0],
    "one_row_and_all_rows": [1, 4 * BLOCK, 2 * BLOCK],
}


@pytest.mark.parametrize("name", list(WORK_LISTS))
def test_the_work_list_against_a_plain_loop(name):
    """`work_list`: one entry for each row block of a slot that holds a
    visible row, slot after slot, and one for a slot that shows none."""
    stops = WORK_LISTS[name]
    want = [(slot, at) for slot, stop in enumerate(stops)
            for at in range(max(-(-stop // BLOCK), 1))]
    slot, at, ends = da.work_list(jnp.asarray(stops, jnp.int32), BLOCK, 4)
    assert slot.shape == at.shape == (len(stops) * 4,)
    assert int(ends[-1]) == len(want)
    assert list(zip(np.asarray(slot)[:len(want)].tolist(),
                    np.asarray(at)[:len(want)].tolist())) == want


@pytest.mark.parametrize("name,q,leaf,mesh,why", [
    ("phi3", (8, 32, 96), (8, 2048, 32, 128), None, None),
    ("trinity_ring", (16, 32, 128), (16, 2048, 4, 128), None, None),
    ("rows_of_96", (8, 32, 96), (8, 2048, 32, 96), None, "lane tiles"),
    ("heads_do_not_group", (8, 6, 128), (8, 256, 4, 128), None, "multiple"),
    ("no_block", (2, 32, 128), (2, 2 * 1031, 32, 128), None, "no block"),
    ("tp_mesh", (8, 32, 96), (8, 2048, 32, 128), 2, "not partitioned"),
])
def test_the_dispatchers_rule(name, q, leaf, mesh, why, monkeypatch):
    """`walk_refusal`: off a TPU nothing is asked; on one (the question
    about the backend answered as the chip would) the kernel takes a leaf
    of whole lane tiles whose heads group and whose rows have a block,
    with no mesh of several devices in context."""
    from jax.sharding import Mesh

    assert da.walk_refusal(q, leaf) == attention.NOT_ASKED
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    if mesh:
        with Mesh(np.array(jax.devices()[:mesh]), ("tp",)):
            reason = da.walk_refusal(q, leaf)
    else:
        reason = da.walk_refusal(q, leaf)
    assert (reason is None) if why is None else (why in reason), reason


# ---------------------------------------------------------------------------
# The engine through the kernel.

#: Heads of 128 (a cache row of whole lane tiles without a chip to ask).
MHA = LLMConfig(vocab_size=128, d_model=256, n_layers=2, n_heads=2,
                max_seq=64, dtype="float32")
#: Trinity-Mini's block at a toy size: a window layer (a ring of 16 rows)
#: and a full one, 4 heads on 2 key/value heads of 128.
SWA = LLMConfig(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, max_seq=64,
    dtype="float32", experts_held=4,
    arch={"model_type": "afmoe", "head_dim": 128, "num_key_value_heads": 2,
          "sliding_window": 16,
          "layer_types": ["sliding_attention", "full_attention"],
          "intermediate_size": 96, "moe_intermediate_size": 32,
          "num_experts": 8, "num_experts_per_tok": 2,
          "num_shared_experts": 1, "num_dense_layers": 1,
          "route_scale": 1.5, "route_norm": True, "score_func": "sigmoid",
          "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
          "mup_enabled": True, "tie_word_embeddings": False})


def run(cfg, prompts, budgets, mesh=None):
    eng = ContinuousEngine(cfg, max_batch=3, decode_chunk=4, mesh=mesh)
    try:
        streams = [eng.submit(p, SamplingParams(max_tokens=m,
                                                temperature=0.0))
                   for p, m in zip(prompts, budgets)]
        return ([s.tokens() for s in streams],
                {**eng.cache_stats(), "attention": eng._decode_form})
    finally:
        eng.shutdown()


@pytest.mark.parametrize("name", ["mha", "swa"])
def test_the_engine_serves_the_same_tokens_through_the_kernel(
        name, monkeypatch):
    """Ten greedy requests of mixed lengths on three slots (rows change
    hands, slots stand free, a ring wraps): the tokens with the kernel
    forced, in interpret mode, are the XLA walk's, every decode step is
    counted under the kernel, and the walked share is the live slots' own
    rows rounded up to the row block."""
    cfg = {"mha": MHA, "swa": SWA}[name]
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 128, size=n).tolist()
               for n in (5, 40, 17, 30, 9, 3, 33, 12, 21, 7)]
    budgets = [12, 6, 9, 20, 5, 16, 8, 3, 11, 14]
    want, xla = run(cfg, prompts, budgets)
    assert xla["decode_steps"] > 0 and xla["decode_steps_kernel"] == 0
    # blocks of 16 rows of 2 float32 heads of 128, so that a full leaf of
    # 64 rows is four of them (the ring of 16 rows is one)
    on_the_chip(monkeypatch, 16 * 2 * 128 * 4)
    got, kernel = run(cfg, prompts, budgets)
    assert got == want
    # (which chunks a run dispatches depends on when its requests arrive:
    # the two runs' counters are not compared with each other)
    assert kernel["decode_steps_kernel"] == kernel["decode_steps"] > 0
    for k in kernel["cache_kinds"].values():
        # a slot's rows rounded up to a piece of 4 rows (the ring of 16 is
        # one block of 4 pieces too): under a piece more
        assert k["live_share"] <= k["walk_share"] < (
            k["live_share"] + 4 / k["rows"])
    assert kernel["kv_walk_share"] < 1.0


def test_an_engine_given_a_tp_mesh_keeps_the_walk_in_what_it_traces(
        monkeypatch):
    """Where the chip would take the kernel (heads of 128, the backend
    question answered as the chip would), an engine given a `tp` mesh
    traces its decode step with that mesh in context: the rule its counters
    asked is the rule the trace asks, both keep the XLA walk (Mosaic
    refuses a kernel in a program GSPMD partitions), the kernel is never
    called, and the tokens are the unsharded engine's. Without a mesh the
    same engine takes the kernel."""
    from jax.sharding import Mesh

    prompts = [list(range(1, n)) for n in (6, 41, 18)]
    budgets = [9, 6, 12]
    want, _ = run(MHA, prompts, budgets)
    on_the_chip(monkeypatch, 16 * 2 * 128 * 4)
    _, alone = run(MHA, prompts[:1], budgets[:1])
    assert alone["attention"] == "kernel"
    assert alone["decode_steps_kernel"] == alone["decode_steps"] > 0

    def never(*args, **kwargs):
        raise AssertionError("the ragged kernel under a `tp` mesh")

    monkeypatch.setattr(da, "ragged_decode_attention", never)
    got, sharded = run(MHA, prompts, budgets,
                       mesh=Mesh(np.array(jax.devices()[:2]), ("tp",)))
    assert got == want
    assert sharded["attention"] == "xla"
    assert sharded["decode_steps"] > 0 == sharded["decode_steps_kernel"]



# ---------------------------------------------------------------------------
# Latent rows (`ragged_latent_attention`): a kernel and a rule of their own.

#: Kimi's latent row: `c_kv` of 512 beside a rotary key of 64, in the row
#: of 640 the chip's compiler asks for (`_probe_cache_row`).
RANK, ROT, ROW = 512, 64, 640
WIDTH = RANK + ROT

#: name -> (each slot's visible rows, its `live` mark); a latent leaf is
#: never a ring, so a live slot's length stays within the rows.
LATENT_CASES = {
    "one_row_a_blocks_edge_one_past_it_all_rows": (
        [1, BLOCK, BLOCK + 1, ROWS], [True] * 4),
    "each_blocks_last_row_and_the_row_before": (
        [2 * BLOCK, 2 * BLOCK - 1, 3 * BLOCK, ROWS - 1], [True] * 4),
    "free_row_whose_stale_length_exceeds_the_rows": (
        [7, 10 * ROWS, BLOCK + 1, ROWS], [True, False, True, True]),
    "free_rows_first_and_last": ([9 * ROWS, 2 * BLOCK, 5, 4 * ROWS],
                                 [False, True, True, False]),
    "free_rows_between_live_ones": ([ROWS, 3, 9 * ROWS, 2 * BLOCK + 3],
                                    [True, False, False, True]),
    "all_rows_free": ([5, 10 * ROWS, BLOCK, ROWS], [False] * 4),
}


def latent_leaf(heads: int, dtype, poison=()):
    keys = jax.random.split(jax.random.PRNGKey(heads), 2)
    q = jax.random.normal(keys[0], (4, heads, WIDTH), dtype)
    rows = jnp.pad(jax.random.normal(keys[1], (4, ROWS, WIDTH), dtype),
                   ((0, 0), (0, 0), (0, ROW - WIDTH)))
    for slot in poison:  # what a free row's stale steps may have left
        rows = rows.at[slot].set(jnp.nan)
    return q, rows


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(LATENT_CASES))
@pytest.mark.parametrize("heads", [64, 32], ids=["kimi_k2_64_heads",
                                                 "kimi_linear_32_heads"])
def test_the_latent_kernel_reads_each_slots_own_rows_and_none_of_a_free_one(
        heads, case, dtype, monkeypatch):
    """Against `models/mla.py` `_latent_attention` over the whole leaf, 64
    and 32 heads on one row of 640 (rank 512, rotary 64, 64 of zeros). A
    free row's leaf is never read: filled with NaN here, it leaves its
    neighbours' outputs as they were, and its own output is zeros."""
    lens, live = LATENT_CASES[case]
    free = [i for i, seated in enumerate(live) if not seated]
    seated = [i for i, s in enumerate(live) if s]
    q, rows = latent_leaf(heads, dtype, poison=free)
    monkeypatch.setattr(da, "BLOCK_BYTES",
                        BLOCK * ROW * jnp.dtype(dtype).itemsize)
    assert da.latent_block(rows.shape, rows.dtype) == BLOCK
    scale = 0.1147
    got = da.ragged_latent_attention(
        q, rows, jnp.asarray(lens, jnp.int32), jnp.asarray(live), rank=RANK,
        scale=scale, interpret=True)
    assert got.shape == (4, heads, RANK) and got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    assert np.all(got[free] == 0.0)
    if not seated:
        return
    pos = jnp.asarray(lens, jnp.int32)[:, None] - 1
    want = np.asarray(mla._latent_attention(
        q[..., :RANK], q[:, None, :, RANK:], jnp.nan_to_num(rows), pos, RANK,
        WIDTH, scale), np.float32)
    # bf16: the output's own rounding and the probabilities' (the cache's
    # dtype in both forms, rounded after sums taken in another order)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[seated], want[seated], atol=tol, rtol=0)


def test_the_latent_block_is_derived_from_the_rows_bytes():
    """`latent_block`: `row_block`'s rule for a row of one head. Both
    cells' leaves (4096 rows of 640 bf16 values) go 512 rows a block; a
    short leaf is one block; and `live` left out counts every slot."""
    assert da.latent_block((32, 4096, 640), jnp.bfloat16) == 512
    assert da.latent_block((64, 4096, 640), jnp.bfloat16) == 512
    assert da.latent_block((64, 4096, 640), jnp.float32) == 256
    assert da.latent_block((8, 256, 640), jnp.bfloat16) == 256
    assert da.latent_block((2, 8 * 1031, 640), jnp.bfloat16) == 8
    assert da.latent_block((2, 2 * 1031, 640), jnp.bfloat16) is None
    q, rows = latent_leaf(8, jnp.float32)
    lens = jnp.asarray([1, 17, 40, 64], jnp.int32)
    got = da.ragged_latent_attention(q, rows, lens, rank=RANK, scale=0.1,
                                     interpret=True)
    want = mla._latent_attention(q[..., :RANK], q[:, None, :, RANK:], rows,
                                 lens[:, None] - 1, RANK, WIDTH, 0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    with pytest.raises(ValueError, match="latent_refusal"):
        da.ragged_latent_attention(q, rows[:, :, :WIDTH - 8], lens, rank=RANK,
                                   scale=0.1, interpret=True)


@pytest.mark.parametrize("name,leaf,rank,mesh,why", [
    ("kimi_k2", (32, 4096, 640), 512, None, None),
    ("kimi_linear", (64, 4096, 640), 512, None, None),
    ("a_row_of_576", (32, 4096, 576), 512, None, "lane tiles"),
    ("c_kv_of_96", (8, 256, 128), 96, None, "lane tiles"),
    ("no_block", (2, 2 * 1031, 640), 512, None, "no block"),
    ("tp_mesh", (32, 4096, 640), 512, 2, "not partitioned"),
])
def test_the_latent_rule(name, leaf, rank, mesh, why, monkeypatch):
    """`latent_refusal`: off a TPU nothing is asked; on one (the question
    about the backend answered as the chip would) the kernel takes a row
    of whole lane tiles, which the row as the model has it (576) is not
    until the engine has widened it, whose rows have a block, with no mesh
    of several devices in context."""
    from jax.sharding import Mesh

    assert da.latent_refusal(leaf, rank) == attention.NOT_ASKED
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    if mesh:
        with Mesh(np.array(jax.devices()[:mesh]), ("tp",)):
            reason = da.latent_refusal(leaf, rank)
    else:
        reason = da.latent_refusal(leaf, rank)
    assert (reason is None) if why is None else (why in reason), reason


#: Kimi K2's block at a toy size with the latent row at its PUBLISHED
#: width (512 + 64): two latent layers, the second with experts.
MLA = LLMConfig(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, max_seq=64,
    dtype="float32", experts_held=4,
    arch={"model_type": "kimi_k2", "intermediate_size": 96,
          "q_lora_rank": 24, "kv_lora_rank": RANK, "qk_nope_head_dim": 16,
          "qk_rope_head_dim": ROT, "v_head_dim": 16,
          "moe_intermediate_size": 32, "n_routed_experts": 8,
          "n_shared_experts": 1, "num_experts_per_tok": 2,
          "first_k_dense_replace": 1, "norm_topk_prob": True,
          "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
          "rms_norm_eps": 1e-5, "rope_theta": 50000, "rope_scaling": None,
          "tie_word_embeddings": False})
#: Kimi Linear's: state layers beside a latent one without a position.
KDA_MLA = LLMConfig(
    vocab_size=128, d_model=64, n_layers=4, n_heads=4, max_seq=64,
    dtype="float32", experts_held=4,
    arch={"model_type": "kimi_linear",
          "linear_attn_config": {
              "kda_layers": [1, 2, 3], "full_attn_layers": [4],
              "num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4},
          "kv_lora_rank": RANK, "q_lora_rank": None, "qk_nope_head_dim": 16,
          "qk_rope_head_dim": ROT, "v_head_dim": 16, "mla_use_nope": True,
          "num_experts": 8, "num_experts_per_token": 2,
          "num_shared_experts": 1, "moe_intermediate_size": 32,
          "intermediate_size": 128, "first_k_dense_replace": 1,
          "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
          "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
          "tie_word_embeddings": False, "hidden_act": "silu",
          "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1,
          "num_nextn_predict_layers": 0, "rope_scaling": None})


def latent_on_the_chip(monkeypatch, block_rows: int) -> None:
    """`on_the_chip` for an engine with latent layers: the compiler's
    answer about the row stands in (`_probe_cache_row`: 576 values go in
    rows of 640), and the latent kernel runs in interpret mode in blocks of
    `block_rows` float32 rows."""
    on_the_chip(monkeypatch, block_rows * ROW * 4)
    monkeypatch.setattr(ContinuousEngine, "_probe_cache_row",
                        lambda self, make_chunk: ROW)
    monkeypatch.setattr(mla, "ragged_latent_attention", functools.partial(
        da.ragged_latent_attention, interpret=True))


@pytest.mark.parametrize("name", ["mla", "kda_mla"])
def test_the_engine_serves_the_same_tokens_through_the_latent_kernel(
        name, monkeypatch):
    """The `mha` engines' test above for a model with latent layers (and
    for one with state layers beside them): ten greedy requests of mixed
    lengths on three slots, the tokens through the latent kernel, forced in
    interpret mode, are the walk's; every decode step is counted under the
    kernel and the walked share is the live slots' own rows rounded up to
    the block, where the walk's is its quarter prefixes'."""
    cfg = {"mla": MLA, "kda_mla": KDA_MLA}[name]
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 128, size=n).tolist()
               for n in (5, 40, 17, 30, 9, 3, 33, 12, 21, 7)]
    budgets = [12, 6, 9, 20, 5, 16, 8, 3, 11, 14]
    want, xla = run(cfg, prompts, budgets)
    assert xla["attention"] == "xla" and xla["cache_kind"] == "latent"
    assert xla["decode_steps"] > 0 and xla["decode_steps_kernel"] == 0
    latent_on_the_chip(monkeypatch, 16)
    got, kernel = run(cfg, prompts, budgets)
    assert got == want
    assert kernel["attention"] == "kernel"
    assert kernel["decode_steps_kernel"] == kernel["decode_steps"] > 0
    assert f"[3, 64, {ROW}]" in kernel["cache_layout"]
    full = kernel["cache_kinds"]["full"]
    assert full["live_share"] <= full["walk_share"] < (
        full["live_share"] + 16 / 64)
    assert kernel["kv_walk_share"] == full["walk_share"] < 1.0


def test_a_latent_engine_given_a_tp_mesh_keeps_the_walk(monkeypatch):
    """The latent twin of the `mha` engine's test above: with a `tp` mesh
    in context the rule the counters asked is the rule the trace asks,
    both keep `_latent_walk`, and the kernel is never called."""
    from jax.sharding import Mesh

    prompts = [list(range(1, n)) for n in (6, 41, 18)]
    budgets = [9, 6, 12]
    want, _ = run(MLA, prompts, budgets)
    latent_on_the_chip(monkeypatch, 16)

    def never(*args, **kwargs):
        raise AssertionError("the latent kernel under a `tp` mesh")

    monkeypatch.setattr(mla, "ragged_latent_attention", never)
    got, sharded = run(MLA, prompts, budgets,
                       mesh=Mesh(np.array(jax.devices()[:2]), ("tp",)))
    assert got == want
    assert sharded["attention"] == "xla"
    assert sharded["decode_steps"] > 0 == sharded["decode_steps_kernel"]
