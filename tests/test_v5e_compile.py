"""The serving programs compiled for a v5e chip that is described, not
attached (no chip time, nothing runs): what the TPU's compiler makes of the
KV cache at a program's boundary. The CPU's default layout is the decode
loop's own, so tier-1's other tests cannot see a conversion there.

Every test that loads the TPU's compiler lives in this one file, and the
topology is described inside a fixture: only the worker that is handed this
file loads the library (README "Serving hot loop"; the `on-chip-measurement`
guide, section 2).
"""

import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.llm import LLMConfig  # noqa: E402
from ray_tpu.llm.engine import ContinuousEngine  # noqa: E402
from tools import lowered  # noqa: E402

#: Phi-3-mini's heads (96 wide, which the chip pads to 128) at a size that
#: compiles in seconds: 4 heads, 2 layers, 8 slots of 256 positions.
CFG = LLMConfig(vocab_size=512, d_model=384, n_layers=2, n_heads=4,
                max_seq=256, dtype="bfloat16")
MAX_BATCH = 8


@pytest.fixture(scope="module")
def chips():
    try:
        return lowered.described_chips()
    except Exception as e:  # noqa: BLE001 - no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(chips):
    return chips[0]


def build_compiled(chip, cfg=None, mesh=None, max_batch=None,
                   decode_chunk=4) -> ContinuousEngine:
    """`tools/lowered.py` `build_compiled` at this file's sizes."""
    return lowered.build_compiled(
        chip, cfg or CFG, max_batch=max_batch or MAX_BATCH,
        decode_chunk=decode_chunk, mesh=mesh)


def test_v5e_chunk_program_takes_and_returns_the_cache_without_a_copy(chip):
    """The compiler wants the cache row-major in tiles of 128 lanes, which
    a head of 96 does not fill; with rows of 128 that is the default layout
    and the chunk program converts nothing at its boundary."""
    eng = build_compiled(chip)
    assert eng.model.cfg.cache_row == 128
    st = eng.cache_stats()
    assert st["cache_boundary_copies"] == 0
    assert st["cache_layout"].startswith(
        f"bfloat16[{MAX_BATCH}, {CFG.max_seq}, {CFG.n_heads}, 128] "
        f"Layout(major_to_minor=(0, 1, 2, 3), tiling=(")
    # a prefill's slice (its bucket's rows) arrives at the same width, so
    # placing it is a plain update of a slot's first rows
    cache = eng._cache_spec
    one = jax.eval_shape(eng._prefill.jitted, eng.params,
                         jax.ShapeDtypeStruct((1, 64), jnp.int32), 5)[1]
    assert {leaf.shape for leaf in jax.tree.leaves(one)} == {
        (1, 64, CFG.n_heads, 128)}
    row = lambda dtype, *dims: jax.ShapeDtypeStruct(  # noqa: E731
        (MAX_BATCH, *dims), dtype)
    mirrors = (row(jnp.int32), row(jnp.int32), row(jnp.uint32, 2),
               row(jnp.float32), row(jnp.int32), row(jnp.float32))
    placed = eng._place.lower(
        cache, one, mirrors, jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((3,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.float32)).compile()
    assert not re.findall(r"= \w+\[%d,%d,%d,\d+\]\S* copy\("
                          % (MAX_BATCH, CFG.max_seq, CFG.n_heads),
                          placed.as_text())


def test_v5e_counter_sees_the_conversions_of_head_wide_rows(
        chip, monkeypatch):
    """What the engine did before it asked: rows as wide as a head, whose
    default layout has the positions minor-most, are converted on the way
    into the chunk program and back on the way out, once each per leaf."""
    monkeypatch.setattr(ContinuousEngine, "_probe_cache_row",
                        lambda self, make_chunk: 0)  # the answer not asked
    eng = build_compiled(chip)
    assert eng.model.cfg.cache_row == 0
    st = eng.cache_stats()
    assert st["cache_boundary_copies"] == 2 * 2 * CFG.n_layers
    assert "96] Layout(major_to_minor=(0, 2, 3, 1)" in st["cache_layout"]


#: Kimi K2's latent row (kv_lora_rank 512 + qk_rope_head_dim 64 = 576
#: values) and head dims at a hidden size and depth that compile in seconds.
LATENT = LLMConfig(
    vocab_size=512, d_model=256, n_layers=2, n_heads=2, max_seq=256,
    dtype="bfloat16", experts_held=4,
    arch={"model_type": "kimi_k2", "intermediate_size": 512,
          "q_lora_rank": 128, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128,
          "moe_intermediate_size": 128, "n_routed_experts": 16,
          "n_shared_experts": 1, "num_experts_per_tok": 4,
          "first_k_dense_replace": 1, "norm_topk_prob": True,
          "routed_scaling_factor": 2.827, "scoring_func": "sigmoid",
          "rms_norm_eps": 1e-5, "rope_theta": 50000,
          "rope_scaling": {"type": "yarn", "factor": 64, "beta_fast": 32,
                           "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                           "original_max_position_embeddings": 4096},
          "tie_word_embeddings": False})


def test_v5e_latent_cache_rows_are_widened_to_their_tiles(chip):
    """The question `_probe_cache_row` puts for K and V it puts for a latent
    leaf too: 576 values come back as rows of 640 (five tiles of 128
    lanes), and the chunk program of the latent-attention, expert-layer
    model then takes and returns its cache without a copy; a prefill hands
    on its bucket's rows at that width."""
    eng = build_compiled(chip, cfg=LATENT)
    assert eng.model.cfg.cache_row == 640
    st = eng.cache_stats()
    assert st["cache_kind"] == "latent"
    assert st["cache_boundary_copies"] == 0
    assert st["cache_layout"].startswith(
        f"bfloat16[{MAX_BATCH}, {LATENT.max_seq}, 640] "
        f"Layout(major_to_minor=(0, 1, 2), tiling=(")
    assert st["cache_bytes"] == 2 * MAX_BATCH * LATENT.max_seq * 640 * 2
    assert (st["experts_held"], st["experts_published"]) == (4, 16)
    one = jax.eval_shape(eng._prefill.jitted, eng.params,
                         jax.ShapeDtypeStruct((1, 64), jnp.int32), 5)[1]
    assert {leaf.shape for leaf in jax.tree.leaves(one)} == {(1, 64, 640)}
    # the chunk's token block carries the held experts' row counts: 4
    # counts in one more column of 8 slots
    block = jax.eval_shape(
        eng._chunk.jitted, *eng._chunk_shapes(eng.params, eng._cache_spec, False))[2]
    assert block.shape == (MAX_BATCH, 4 + 1)


#: Trinity-Mini's attention and expert layers at the PUBLISHED widths (hidden
#: 2048, 32 query heads on 4 key/value heads of 128, window 2048, 16 of 128
#: experts of 1024 held, dense layers of 6144, 8192 positions a slot) and one
#: period of its depth: layers 0-3, two dense and two expert, three window
#: layers and a full one. (All 32 layers compile in 38 s and a minute for the
#: 8192-row prefill: PERF.md section 4.)
SWA = LLMConfig(
    vocab_size=25024, d_model=2048, n_layers=4, n_heads=32, max_seq=8192,
    dtype="bfloat16", experts_held=16,
    arch={"model_type": "afmoe", "num_key_value_heads": 4, "head_dim": 128,
          "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
          "sliding_window": 2048, "num_dense_layers": 2, "num_experts": 128,
          "num_experts_per_tok": 8, "num_shared_experts": 1,
          "moe_intermediate_size": 1024, "intermediate_size": 6144,
          "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
          "mup_enabled": True, "rope_theta": 10000, "rms_norm_eps": 1e-5,
          "rope_scaling": None, "tie_word_embeddings": False})


def test_v5e_window_and_full_leaves_of_four_heads_cross_without_a_copy(chip):
    """A leaf of FOUR key/value heads of 128: its default layout on the
    v5e is row-major in tiles of (4, 128), no padding (tiles of 16 heads
    would hold four times the bytes), and it is the decode loop's own: the
    chunk program converts nothing at its boundary, for the rings and the
    full leaves alike. A prefill of 8192 rows hands on 8192 rows of a full
    leaf and the whole ring of a window leaf; placing them copies no leaf;
    each program fits the chip beside what is resident."""
    eng = build_compiled(chip, cfg=SWA)
    assert eng.model.cfg.cache_row == 0  # a head of 128 fills its lanes
    st = eng.cache_stats()
    assert st["cache_boundary_copies"] == 0
    row = 4 * 128 * 2  # one position's K or V of a layer, bf16
    assert st["cache_kinds"]["full"]["bytes"] == 2 * MAX_BATCH * 8192 * row
    assert st["cache_kinds"]["window"]["bytes"] == (3 * 2 * MAX_BATCH * 2048
                                                    * row)
    layouts = st["cache_layout"].split("; ")
    assert [lay.split(" Layout(")[0] for lay in layouts] == [
        f"bfloat16[{MAX_BATCH}, 2048, 4, 128]",
        f"bfloat16[{MAX_BATCH}, 8192, 4, 128]"]
    assert all("major_to_minor=(0, 1, 2, 3), tiling=((4, 128), (2, 1))" in lay
               for lay in layouts)
    on_chip = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.int32, sharding=SingleDeviceSharding(chip))
    prefill = eng._prefill.lower(eng.params, on_chip(1, 8192),
                                 on_chip()).compile()
    mem = prefill.memory_analysis()
    resident = mem.argument_size_in_bytes  # the parameters
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 2.5e9
    one = jax.eval_shape(eng._prefill.jitted, eng.params,
                         jax.ShapeDtypeStruct((1, 8192), jnp.int32), 5)[1]
    assert sorted({leaf.shape for leaf in jax.tree.leaves(one)}) == [
        (1, 2048, 4, 128), (1, 8192, 4, 128)]
    row_of = lambda dtype, *dims: jax.ShapeDtypeStruct(  # noqa: E731
        (MAX_BATCH, *dims), dtype)
    mirrors = (row_of(jnp.int32), row_of(jnp.int32), row_of(jnp.uint32, 2),
               row_of(jnp.float32), row_of(jnp.int32), row_of(jnp.float32))
    placed = eng._place.lower(
        eng._cache_spec, one, mirrors, jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((3,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.float32)).compile()
    assert not re.findall(r"= \w+\[%d,\d+,4,128\]\S* copy\(" % MAX_BATCH,
                          placed.as_text())
    assert placed.memory_analysis().temp_size_in_bytes < 1e6
    chunk = eng._chunk.lower(*eng._chunk_shapes(
        eng.params, eng._cache_spec, False)).compile().memory_analysis()
    assert abs(chunk.argument_size_in_bytes
               - (resident + st["cache_bytes"])) < 1e6
    assert chunk.temp_size_in_bytes < 1e9


#: Kimi Linear's mixers and expert layers at the PUBLISHED widths (hidden
#: 2304; KDA 32 heads of 128 with a filter of 4; MLA 32 heads of 128 + 64
#: over a latent of 512; 16 of 256 experts of 1024 held, the dense layer of
#: 9216; 4096 positions a slot) and one period of its depth: layers 0-3,
#: three KDA layers (the first dense) and a latent one. (All 16 layers: the
#: chunk program compiles in 30 s, the 2048-row prefill in 17 s, 8.13 GB of
#: arguments and 0.11 GB of temporaries: PERF.md section 4.)
KDA = LLMConfig(
    vocab_size=20480, d_model=2304, n_layers=4, n_heads=32, max_seq=4096,
    dtype="bfloat16", experts_held=16,
    arch={"model_type": "kimi_linear",
          "linear_attn_config": {
              "kda_layers": [1, 2, 3], "full_attn_layers": [4],
              "num_heads": 32, "head_dim": 128, "short_conv_kernel_size": 4},
          "kv_lora_rank": 512, "q_lora_rank": None, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128, "mla_use_nope": True,
          "num_experts": 256, "num_experts_per_token": 8,
          "num_shared_experts": 1, "moe_intermediate_size": 1024,
          "intermediate_size": 9216, "first_k_dense_replace": 1,
          "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
          "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
          "rope_scaling": None, "tie_word_embeddings": False})
KDA_SLOTS = 64


@pytest.fixture(scope="module")
def kda_engine(chip):
    return build_compiled(chip, cfg=KDA, max_batch=KDA_SLOTS,
                          decode_chunk=16)


def test_v5e_chunk_program_updates_the_state_of_64_slots_in_place(kda_engine):
    """The 16-step chunk program at 64 slots: the float32 state `[64, 32,
    128, 128]` crosses its boundary in its default layout (row-major, tiles
    of (8, 128): the loop's own), no leaf of any kind is copied whole, the
    donated cache is the result's buffer (no second copy of the state), the
    latent row found through the model's first layer that keeps rows is
    widened to 640, and the only loop is the scan over the steps: none
    inside a decode step (`trace_reduce.loop_steps` counts a step by its most
    often started operation)."""
    eng = kda_engine
    assert eng.model.cfg.cache_row == 640
    st = eng.cache_stats()
    assert st["cache_boundary_copies"] == 0
    state = 32 * 128 * 128 * 4 + 3 * 32 * 128 * 4 + 3 * 12288 * 2
    assert st["cache_kinds"]["state"] == {
        "layers": 3, "leaves": 9, "bytes_per_slot": 3 * state,
        "bytes": KDA_SLOTS * 3 * state}
    assert st["cache_kinds"]["full"]["bytes"] == KDA_SLOTS * 4096 * 640 * 2
    layouts = st["cache_layout"].split("; ")
    assert [lay.split(" Layout(")[0] for lay in layouts] == [
        "bfloat16[64, 3, 12288]", "bfloat16[64, 4096, 640]",
        "float32[64, 3, 4096]", "float32[64, 32, 128, 128]"]
    assert "major_to_minor=(0, 1, 2, 3), tiling=((8, 128),)" in layouts[3]
    compiled = eng._chunk.lower(*eng._chunk_shapes(
        eng.params, eng._cache_spec, False)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= st["cache_bytes"]
    assert mem.temp_size_in_bytes < 0.5e9
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) == 1
    # the latent walk, and the sampler's two (a nucleus? a top_k?)
    assert len(re.findall(r" conditional\(", text)) == 3
    # one sort of the vocabulary, the nucleus's own (the routers sort 256)
    assert len(re.findall(r"= \(f32\[64,20480\]\S*, s32\[64,20480\]\S*\) "
                          r"sort\(", text)) == 1
    assert not re.findall(r"= f32\[%d,32,128,128\]\S* copy\(" % KDA_SLOTS,
                          text)
    # S is read ONCE a layer a step: the pass that applies the last token's
    # pending correction and writes S also takes this token's two sums
    fused = re.findall(r"= \(f32\[64,32,128\]\S*, f32\[64,32,128\]\S*, "
                       r"f32\[64,32,128,128\]\S*\) fusion\(", text)
    assert len(fused) == 3


def test_v5e_prefill_of_2048_rows_hands_on_a_state_and_place_replaces_it(
        chip, kda_engine):
    """The longest bucket of the cell: the chunk scan and the expanded
    latent attention fit beside what is resident; the program hands on a
    state and a tail whole and 2048 latent rows at the cache's width;
    placing them copies no leaf and needs no room of its own."""
    eng = kda_engine
    on_chip = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.int32, sharding=SingleDeviceSharding(chip))
    prefill = eng._prefill.lower(eng.params, on_chip(1, 2048),
                                 on_chip()).compile()
    mem = prefill.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 1e9
    assert len(re.findall(r" while\(", prefill.as_text())) >= 3  # the scans
    one = jax.eval_shape(eng._prefill.jitted, eng.params,
                         jax.ShapeDtypeStruct((1, 2048), jnp.int32), 5)[1]
    assert sorted({leaf.shape for leaf in jax.tree.leaves(one)}) == [
        (1, 3, 4096), (1, 3, 12288), (1, 32, 128, 128), (1, 2048, 640)]
    row_of = lambda dtype, *dims: jax.ShapeDtypeStruct(  # noqa: E731
        (KDA_SLOTS, *dims), dtype)
    mirrors = (row_of(jnp.int32), row_of(jnp.int32), row_of(jnp.uint32, 2),
               row_of(jnp.float32), row_of(jnp.int32), row_of(jnp.float32))
    placed = eng._place.lower(
        eng._cache_spec, one, mirrors, jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((3,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.float32)).compile()
    assert not re.findall(
        r"= \w+\[%d,(32,128,128|3,4096|3,12288|4096,640)\]\S* copy\(" % KDA_SLOTS,
        placed.as_text())
    assert placed.memory_analysis().temp_size_in_bytes < 1e6
    assert placed.memory_analysis().alias_size_in_bytes >= eng.cache_stats()[
        "cache_bytes"]


def test_v5e_prefill_of_6144_rows_goes_in_its_neighbours_tiles(chip):
    """The bucket between 4096 and 8192 (a prompt of 5,000 is padded to it):
    its window layers go in the tiles of 512 queries against 2,560 keys that
    both neighbours use, its full layers in tiles of 256; it fits beside
    what is resident, hands on 6144 rows of a full leaf and the whole ring,
    and placing them copies no leaf."""
    eng = build_compiled(chip, cfg=SWA)
    assert [eng._bucket(n) for n in (4096, 5000, 6144, 6145)] == [
        4096, 6144, 6144, 8192]
    on_chip = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.int32, sharding=SingleDeviceSharding(chip))
    prefill = eng._prefill.lower(eng.params, on_chip(1, 6144),
                                 on_chip()).compile()
    text = prefill.as_text()
    assert "f32[1,4,8,512,2560]" in text and "f32[1,4,8,256,6400]" in text
    mem = prefill.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 2.5e9
    one = jax.eval_shape(eng._prefill.jitted, eng.params,
                         jax.ShapeDtypeStruct((1, 6144), jnp.int32), 5)[1]
    assert sorted({leaf.shape for leaf in jax.tree.leaves(one)}) == [
        (1, 2048, 4, 128), (1, 6144, 4, 128)]
    row_of = lambda dtype, *dims: jax.ShapeDtypeStruct(  # noqa: E731
        (MAX_BATCH, *dims), dtype)
    mirrors = (row_of(jnp.int32), row_of(jnp.int32), row_of(jnp.uint32, 2),
               row_of(jnp.float32), row_of(jnp.int32), row_of(jnp.float32))
    placed = eng._place.lower(
        eng._cache_spec, one, mirrors, jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((3,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.float32)).compile()
    assert not re.findall(r"= \w+\[%d,\d+,4,128\]\S* copy\(" % MAX_BATCH,
                          placed.as_text())
    assert placed.memory_analysis().temp_size_in_bytes < 1e6


def test_v5e_prefill_of_4096_rows_keeps_its_scores_in_the_kernel(
        chip, monkeypatch):
    """On the chip (the dispatcher's question about the backend answered as
    the chip would: this process is held to the CPU) Trinity's prefill of
    4096 rows is one Mosaic kernel a layer, window and full alike, with the
    prompt's length prefetched; no float32 tile of scores `[1, 4 key/value
    heads, 8 heads each, queries, keys]` is left in the program, which the
    XLA form of the same bucket holds; and the temporaries shrink with
    them."""
    from ray_tpu.ops import attention

    on_chip = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.int32, sharding=SingleDeviceSharding(chip))
    scores = r"f32\[1,4,8,\d+,\d+\]"
    xla = build_compiled(chip, cfg=SWA)
    assert xla._prefill_form(4096) == "xla"
    before = xla._prefill.lower(xla.params, on_chip(1, 4096),
                                on_chip()).compile()
    assert re.search(scores, before.as_text())
    assert "tpu_custom_call" not in before.as_text()
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    eng = build_compiled(chip, cfg=SWA)
    assert eng._prefill_form(4096) == "kernel"
    after = eng._prefill.lower(eng.params, on_chip(1, 4096),
                               on_chip()).compile()
    text = after.as_text()
    assert not re.search(scores, text)
    assert len(re.findall(r" custom-call\(.*tpu_custom_call", text)) \
        == SWA.n_layers
    assert (after.memory_analysis().temp_size_in_bytes
            < before.memory_analysis().temp_size_in_bytes - 150e6)
    # heads of 96 are no whole lane tile: Phi-3's prefill stays the XLA form
    assert build_compiled(chip)._prefill_form(128) == "xla"


def test_v5e_prefill_sharded_over_tp_keeps_the_unpartitioned_kernel_out(
        chips, monkeypatch):
    """An engine given a `tp` mesh traces its prefill with that mesh in
    context, and the dispatcher's rule then takes the XLA form: Mosaic
    refuses to lower a kernel into a program that GSPMD partitions
    ("cannot be automatically partitioned"), and nothing wraps this one in
    a shard_map. Heads of 128 on four chips: the program compiles, holds no
    kernel, and the engine counts its rows as the XLA form's."""
    import numpy as np
    from jax.sharding import Mesh

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cfg = LLMConfig(vocab_size=512, d_model=1024, n_layers=2, n_heads=8,
                    max_seq=256, dtype="bfloat16")
    mesh = Mesh(np.array(chips), ("tp",))
    eng = build_compiled(chips[0], cfg=cfg, mesh=mesh)
    assert eng._prefill_form(128) == "xla"
    everywhere = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.int32, sharding=NamedSharding(mesh, P()))
    text = eng._prefill.lower(eng.params, everywhere(1, 128),
                              everywhere()).compile().as_text()
    assert "tpu_custom_call" not in text
    # the same engine on one chip takes the kernel
    assert build_compiled(chips[0], cfg=cfg)._prefill_form(128) == "kernel"


def test_v5e_chunk_sharded_over_tp_keeps_the_unpartitioned_kernel_out(
        chips, monkeypatch):
    """The chunk program's twin of the prefill's test above: an engine
    given a `tp` mesh traces its decode step with that mesh in context, so
    the rule the trace asks (`walk_refusal`) is the rule `_decode_blocks`
    asked, and both keep the XLA walk: the chunk compiles for four chips
    over K and V leaves sharded on the head axis, holds no Mosaic call and
    one `conditional` a layer, and the engine names its steps `xla`. (Traced
    without the mesh, the ragged kernel went into a program that GSPMD
    partitions while the counters said `xla`.) The same engine on one chip
    takes the kernel."""
    import numpy as np
    from jax.sharding import Mesh

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cfg = LLMConfig(vocab_size=512, d_model=1024, n_layers=2, n_heads=8,
                    max_seq=256, dtype="bfloat16")
    mesh = Mesh(np.array(chips), ("tp",))
    eng = build_compiled(chips[0], cfg=cfg, mesh=mesh)
    assert (eng._kernel_blocks, eng._decode_form) == ({}, "xla")
    everywhere = lambda s: s if getattr(  # noqa: E731
        s, "sharding", None) is not None or not hasattr(s, "shape") else (
        jax.ShapeDtypeStruct(s.shape, s.dtype,
                             sharding=NamedSharding(mesh, P())))
    shapes = eng._chunk_shapes(eng.params, eng._cache_spec, True)
    text = eng._chunk.lower(*(jax.tree.map(everywhere, a)
                              for a in shapes)).compile().as_text()
    assert "tpu_custom_call" not in text
    assert len(re.findall(r" conditional\(", text)) == cfg.n_layers
    one = build_compiled(chips[0], cfg=cfg)
    assert (one._kernel_blocks, one._decode_form) == ({"full": 256}, "kernel")
    assert one._kernel_pieces == {"full": 64}  # 128 KiB of 8 heads of 128


def moved_rows(eng, text: str, ops: str = "copy|slice") -> list:
    """The operations of the compiled `text`, outside any fusion, whose
    RESULT has the dimensions of one of the engine's cache leaves or of
    any prefix of its rows: `[slots, n, ...rest]`, n <= rows. Such an
    operation has a buffer of its own: the rows are moved."""
    found = {}  # by place in the text: a prefix of two kinds of leaf once
    for shape in {leaf.shape for leaf in jax.tree.leaves(eng._cache_spec)}:
        slots, leaf_rows, *rest = shape
        dims = ",".join([str(slots), r"(\d+)"] + [str(d) for d in rest])
        for m in re.finditer(r"= \w+\[%s\]\S* (%s)\(" % (dims, ops), text):
            if int(m.group(1)) <= leaf_rows:
                found[m.start()] = (m.group(2),
                                    (slots, int(m.group(1)), *rest))
    return list(found.values())


@pytest.mark.parametrize("name", ["kv", "latent", "swa"])
def test_v5e_bounded_chunk_program_branches_once_a_layer_and_copies_no_rows(
        chip, name):
    """The chunk program the scheduler dispatches (with a `kv_bound`) in
    its XLA form (this process is held to the CPU, so the dispatchers ask
    for no kernel: each family takes its own on the chip, in the tests
    below): one `conditional` a layer, whose branches read their
    prefix of the cache where it lies. Written as an einsum over a slice,
    the v5e's compiler fed each branch a transposed COPY of the K prefix,
    and cutting the latent's rotary columns inside a tile made it copy the
    whole leaf in every branch (PERF.md section 6, PR 29): no copy of a
    leaf, whole or any prefix of its rows, may be in the text. Nor a
    `slice` given a buffer of its own, which is how the ledger's traces
    saw the grouped walk move its prefixes (`slice bf16[16,6144,4,128]`,
    PERF.md section 6, PRs 33-34) where a search for `copy(` saw nothing:
    the grouped XLA walk still has them, K and V of every prefix in every
    layer, and that is what the ragged kernel took off the chip's path (PR
    37).
    Without the bound the program has no `conditional`, as before there
    was one."""
    from ray_tpu.ops.decode_attention import kv_prefixes

    cfg = {"kv": CFG, "latent": LATENT, "swa": SWA}[name]
    eng = build_compiled(chip, cfg=cfg)
    assert eng.cache_boundary_copies == 0  # whole leaves, branches included
    assert eng._kernel_blocks == {}
    # (the greedy program: the sampled one's sampler has branches of its own)
    shapes = eng._chunk_shapes(eng.params, eng._cache_spec, True)
    assert shapes[-2].shape == () and shapes[-2].dtype == jnp.int32
    text = eng._chunk.lower(*shapes).compile().as_text()
    assert len(re.findall(r" conditional\(", text)) == cfg.n_layers
    assert "tpu_custom_call" not in text
    moved = moved_rows(eng, text)
    assert not [m for m in moved if m[0] == "copy"], moved
    if name == "swa":
        # K and V of every prefix short of the whole, in each of the
        # three window layers (rings of 2048 rows) and in the full one
        assert sorted(moved) == sorted(
            ("slice", (MAX_BATCH, rows, 4, 128))
            for leaf_rows, layers in ((2048, 3), (8192, 1))
            for rows in kv_prefixes(leaf_rows) if rows < leaf_rows
            for _leaf in range(2 * layers)), moved
    else:
        assert not moved, moved
    unbounded = eng._chunk.lower(*shapes[:-2]).compile().as_text()
    assert " conditional(" not in unbounded


@pytest.mark.parametrize("name", ["kv", "swa"])
def test_v5e_chunk_program_with_the_ragged_kernel_moves_no_rows(
        chip, name, monkeypatch):
    """On the chip (the dispatcher's question about the backend answered
    as the chip would) the bounded step of the `mha` family is the ragged
    kernel: ONE Mosaic call a layer in the step's body, handed the leaf as
    it lies; no `conditional` for attention; no loop inside the step (the
    chunk's own `while` is the only one: `trace_reduce.loop_steps` counts
    an operation NAME's starts inside a `jit_chunk` execution, and a Pallas
    call is one `custom-call` a layer a step whose grid lives inside
    Mosaic); and outside the fusions no `copy`, `slice`, `transpose` or
    `bitcast-convert` whose result has a leaf's dimensions or any prefix of
    its rows. The engine counts its decode steps under the kernel."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cfg = {"kv": CFG, "swa": SWA}[name]
    eng = build_compiled(chip, cfg=cfg)
    assert eng.cache_boundary_copies == 0
    assert eng._kernel_blocks == {
        "kv": {"full": 256},  # a short leaf of 4 heads: one block
        "swa": {"full": 1024, "window": 1024}}[name]
    # a slot's last block is fetched in pieces of 128 KiB of K
    assert eng._kernel_pieces == dict.fromkeys(eng._kernel_blocks, 128)
    text = eng._chunk.lower(*eng._chunk_shapes(
        eng.params, eng._cache_spec, True)).compile().as_text()
    assert len(re.findall(r" custom-call\(.*tpu_custom_call", text)) \
        == cfg.n_layers
    assert " conditional(" not in text
    assert len(re.findall(r" while\(", text)) == 1
    assert not moved_rows(eng, text,
                          "copy|slice|transpose|bitcast-convert"), name
    # K and V go into the call as the step's own update of the leaf left
    # them, in HBM: the compiler stages no leaf in its fast memory ahead of
    # the call (left to itself it copied Trinity's rings whole, free slots
    # and all: `copy-start` / `copy-done` of `[slots, 2048, 4, 128]`)
    for line in re.findall(r" custom-call\((.*?)\), custom_call_target="
                           r"\"tpu_custom_call\"", text):
        # (the work list's length, stop, slot, at, q, K, V)
        *_, k, v = line.split(", ")
        assert "copy" not in k and "copy" not in v, line
    assert not moved_rows(eng, text, "copy-start|copy-done"), name


#: A layer of two latent attentions with its expert layer on a shortcut and
#: identity experts among the router's outputs (`model_type` `longcat_flash`)
#: at Kimi K2's latent widths: ONE layer, two latent leaves.
SCMOE = LLMConfig(
    vocab_size=512, d_model=256, n_layers=1, n_heads=2, max_seq=256,
    dtype="bfloat16", experts_held=4,
    arch={"model_type": "longcat_flash", "attention_method": "MLA",
          "q_lora_rank": 128, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128,
          "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
          "ffn_hidden_size": 512, "expert_ffn_hidden_size": 128,
          "n_routed_experts": 16, "zero_expert_num": 8,
          "zero_expert_type": "identity", "moe_topk": 4,
          "routed_scaling_factor": 6, "rope_theta": 1e7,
          "rms_norm_eps": 1e-5})


@pytest.mark.parametrize("name", ["latent", "kda", "scmoe"])
def test_v5e_latent_chunk_program_with_the_ragged_kernel_moves_no_rows(
        chip, name, monkeypatch):
    """The twin of the test above for the `mla` family, at Kimi K2's
    latent widths (two latent layers of a short leaf) and at Kimi Linear's
    published widths (64 slots of 4096 rows, one latent layer in four), and
    for a layer that holds TWO latent attentions (PR 42: a call a LEAF): on
    the chip the bounded step of a latent layer is `ragged_latent_attention`,
    ONE Mosaic call a latent layer in the step's body, handed the leaf
    `[slots, rows, 640]` as the step's own update left it, in HBM; no
    `conditional` (the walk's `lax.switch` over quarter prefixes is off the
    path), no loop but the chunk's own, and outside the fusions no `copy`,
    `slice`, `transpose` or `bitcast-convert` whose result has a leaf's
    dimensions or any prefix of its rows. The row as the model has it (576)
    is refused, so the engine's question to the compiler is still put
    through the walk and still answered 640. The engine counts its decode
    steps under the kernel."""
    import types

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cfg, slots, layers, block = {"latent": (LATENT, MAX_BATCH, 2, 256),
                                 "kda": (KDA, KDA_SLOTS, 1, 512),
                                 "scmoe": (SCMOE, MAX_BATCH, 2, 256)}[name]
    eng = build_compiled(chip, cfg=cfg, max_batch=slots)
    assert eng.model.cfg.cache_row == 640
    assert eng.cache_boundary_copies == 0
    assert (eng._kernel_blocks, eng._decode_form) == ({"full": block},
                                                      "kernel")
    text = eng._chunk.lower(*eng._chunk_shapes(
        eng.params, eng._cache_spec, True)).compile().as_text()
    kernels = re.findall(r"%([a-z_]+)[.\d]* = \S+ custom-call\((.*?)\), "
                         r"custom_call_target=\"tpu_custom_call\"", text)
    calls = [operands for kernel, operands in kernels
             if kernel == "_ragged_latent"]
    assert len(calls) == layers
    # since PR 47 the expert layers' decode step is a Mosaic call too, one
    # an expert layer whose shapes are whole tiles (Kimi Linear's published
    # widths; the two toys' experts of 128 on eight slots are refused)
    assert len(kernels) - len(calls) == {"kda": 3}.get(name, 0)
    assert all(kernel == "occupied_experts" for kernel, _ in kernels
               if kernel != "_ragged_latent")
    assert " conditional(" not in text
    assert len(re.findall(r" while\(", text)) == 1
    latent = [leaf for leaf in jax.tree.leaves(eng._cache_spec)
              if leaf.shape[1:] == (cfg.max_seq, 640)]
    assert len(latent) == layers
    # (the latent leaves only: a state leaf's filter tail is sliced by its
    # own layer, which is no business of this kernel's)
    assert not moved_rows(
        types.SimpleNamespace(_cache_spec=latent), text,
        "copy|slice|transpose|bitcast-convert|copy-start|copy-done"), name
    for line in calls:
        # (the grid's length, stop, slot, at, held, q, the leaf)
        assert "copy" not in line.split(", ")[-1], line


def test_v5e_longcat_chunk_program_reads_its_experts_through_the_kernel(
        chip, monkeypatch):
    """`longcat-flash-ep32-4l` as the benchmark deploys it (its own file:
    hidden 6144, 4 layers of two latent attentions and one expert layer of
    16 held experts of 2048, 32 slots of 4096 positions), the 16-step
    greedy chunk program: a step holds the 8 latent kernels and ONE
    `occupied_experts` call an expert layer (PR 43: 12 Mosaic calls), in
    one `while`, the chunk's own (no loop over experts), without a
    `conditional`; each call is handed the three expert stacks `[16, 6144,
    2048]` as the program's parameters lie, no copy, transpose or
    conversion of a stack anywhere, and the temporaries stay what they
    were with the dense arm (18.4 MB, PR 42)."""
    import json
    import os

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "longcat-flash-ep32-4l.json")) as f:
        cfg = LLMConfig(**json.load(f)["llm_config"])
    eng = build_compiled(chip, cfg=cfg, max_batch=32, decode_chunk=16)
    assert (eng._kernel_blocks, eng._decode_form) == ({"full": 512},
                                                      "kernel")
    compiled = eng._chunk.lower(*eng._chunk_shapes(
        eng.params, eng._cache_spec, True)).compile()
    text = compiled.as_text()
    calls = re.findall(r" custom-call\((.*?)\), custom_call_target="
                       r"\"tpu_custom_call\"", text)
    experts = [line for line in text.splitlines()
               if " custom-call(" in line and "occupied_experts" in line]
    assert (len(calls), len(experts)) == (12, 4)
    assert " conditional(" not in text
    assert len(re.findall(r" while\(", text)) == 1
    stack = r"bf16\[16,(6144,2048|2048,6144)\]"
    for line in experts:
        # (the grid's length, order, n_touched, the rows, the gates, then
        # the three stacks: the loop's own tuple elements)
        operands = re.search(r"custom-call\((.*?)\), custom_call_target",
                             line).group(1).split(", ")
        assert all("get-tuple-element" in op for op in operands[-3:]), line
        assert len(re.findall(stack, line)) == 3, line
    assert not re.findall(
        r"= %s\S* (?:copy|transpose|bitcast-convert|copy-start|slice)\("
        % stack, text)
    memory = compiled.memory_analysis()
    assert abs(memory.argument_size_in_bytes - 11.69e9) < 0.01e9
    assert memory.temp_size_in_bytes < 22e6


#: A small caller of each kernel's entry point, as source, and the (dtype,
#: *dims) it is lowered with (whole tiles, so that Mosaic takes them).
BF16, I32 = jnp.bfloat16, jnp.int32
KERNEL_CALLERS = {
    "flash_attention": (
        "from ray_tpu.ops.flash_attention import flash_attention\n"
        "def caller(q, k, v):\n"
        "    return flash_attention(q, k, v, causal=True)\n",
        [(BF16, 1, 256, 4, 128)] * 3),
    "ragged_decode_attention": (
        "from ray_tpu.ops.decode_attention import ragged_decode_attention\n"
        "def caller(q, k, v, lengths):\n"
        "    return ragged_decode_attention(q, k, v, lengths)\n",
        [(BF16, 8, 8, 128), (BF16, 8, 512, 4, 128), (BF16, 8, 512, 4, 128),
         (I32, 8)]),
    "ragged_latent_attention": (
        "from ray_tpu.ops.decode_attention import ragged_latent_attention\n"
        "def caller(q, latents, lengths):\n"
        "    return ragged_latent_attention(q, latents, lengths, rank=512,\n"
        "                                   scale=0.1)\n",
        [(BF16, 8, 16, 640), (BF16, 8, 512, 640), (I32, 8)]),
    "occupied_experts": (
        "from ray_tpu.ops.expert_decode import occupied_experts\n"
        "def caller(x, gates, rows_here, w_gate, w_up, w_down):\n"
        "    return occupied_experts(x, gates, rows_here, w_gate, w_up,\n"
        "                            w_down)\n",
        [(BF16, 16, 1024), (jnp.float32, 16, 4), (I32, 4),
         (BF16, 4, 1024, 512), (BF16, 4, 1024, 512), (BF16, 4, 512, 1024)]),
}


def lowered_from_line(chip, kernel: str, line: int) -> str:
    """The text of `kernel`'s caller lowered for `chip`, the caller compiled
    from source that begins at `line`. (The kernels' own jits keep their
    first trace, and with it the first caller's frames: cleared.)"""
    source, shapes = KERNEL_CALLERS[kernel]
    scope: dict = {}
    exec(compile("\n" * line + source, f"<a caller of {kernel}>", "exec"),
         scope)
    jax.clear_caches()
    text = jax.jit(scope["caller"]).lower(*(
        jax.ShapeDtypeStruct(dims, dtype, sharding=SingleDeviceSharding(chip))
        for dtype, *dims in shapes)).as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("kernel, frames", [
    *((kernel, 1) for kernel in KERNEL_CALLERS), ("occupied_experts", 10)])
def test_v5e_kernel_program_does_not_name_its_callers_lines(
        chip, kernel, frames):
    """A program that holds a Mosaic kernel is the same text, and so the
    same compile-cache entry, wherever in its file the caller stands:
    `compile_cache.program_identity` (the engine's build calls it, `apply`
    writes it into every spawn environment) leaves a location one frame, the
    kernel body's own in `ops/*.py`. With JAX's ten frames put back the
    payload names the caller's line again, so this test can tell."""
    from ray_tpu._private import compile_cache

    compile_cache.program_identity()
    assert jax.config.jax_traceback_in_locations_limit == 1
    jax.config.update("jax_traceback_in_locations_limit", frames)
    try:
        same = (lowered_from_line(chip, kernel, 0)
                == lowered_from_line(chip, kernel, 3))
    finally:
        compile_cache.program_identity()
    assert same == (frames == 1)


# ---------------------------------------------------------------------------
# EVA attention (`model_type` `evabyte`): leaves of two kinds in one layer.

#: EvaByte's attention at its PUBLISHED widths (32 heads of 128, a window of
#: 2048 rows that starts over, one summary row for every 16 positions, 16384
#: positions a slot, SwiGLU 11008, a head of 8 x 320 columns) at two of its
#: layers.
EVA = LLMConfig(
    vocab_size=320, d_model=4096, n_layers=2, n_heads=32, max_seq=16384,
    dtype="bfloat16",
    arch={"model_type": "evabyte", "attention_class": "eva", "chunk_size": 16,
          "window_size": 2048, "num_chunks": None, "num_key_value_heads": 32,
          "intermediate_size": 11008, "hidden_act": "silu",
          "attention_bias": False, "rope_theta": 100000,
          "rope_scaling": None, "rms_norm_eps": 1e-5,
          "norm_add_unit_offset": True, "fp32_skip_add": True,
          "fp32_logits": True, "num_pred_heads": 8,
          "tie_word_embeddings": False})


def test_v5e_eva_chunk_program_walks_two_kinds_of_leaf_and_copies_no_rows(
        chip):
    """Each layer keeps a window leaf and a summaries leaf, both crossing
    the chunk program's boundary in their default layout; the bounded step
    walks each to a static prefix of its own (two `conditional`s a layer)
    where it lies, writes the position's row and, where a chunk ends, the
    chunk's summary, and copies no leaf, whole or any prefix of its rows.
    (Gathered with a vmapped `dynamic_slice`, the chunk's last 16 rows made
    the v5e's compiler copy every window leaf into another layout in every
    step: PERF.md section 6, PR 49.) This process is held to the CPU, so
    the dispatcher asks for no kernel: on the chip the two walks are ONE
    (the tests below)."""
    eng = build_compiled(chip, cfg=EVA, max_batch=16, decode_chunk=16)
    assert eng.model.cfg.cache_row == 0
    assert eng.cache_boundary_copies == 0
    assert (eng._kernel_blocks, eng._decode_form) == ({}, "xla")
    kinds = eng.cache_stats()["cache_kinds"]
    assert {k: (v["layers"], v["leaves"], v["rows"]) for k, v in kinds.items()
            } == {"window": (2, 4, 2048), "chunks": (2, 4, 1024)}
    assert "bfloat16[16, 1024, 32, 128]" in eng.cache_layout
    assert "bfloat16[16, 2048, 32, 128]" in eng.cache_layout
    shapes = eng._chunk_shapes(eng.params, eng._cache_spec, True)
    compiled = eng._chunk.lower(*shapes).compile()
    text = compiled.as_text()
    assert len(re.findall(r" conditional\(", text)) == 2 * EVA.n_layers
    assert "tpu_custom_call" not in text
    assert not [m for m in moved_rows(eng, text) if m[0] == "copy"]
    # the block the tokens ride in: 16 steps and one column of counters
    assert jax.eval_shape(eng._chunk.jitted, *shapes)[2].shape == (16, 16 + 1)
    # a prefill of 6144 rows hands on ONE window's rows and a summary row
    # for every 16 positions of its bucket
    one = jax.eval_shape(eng._prefill.jitted, eng.params,
                         jax.ShapeDtypeStruct((1, 6144), jnp.int32), 5000)[1]
    assert sorted({leaf.shape for leaf in jax.tree.leaves(one)}) == [
        (1, 384, 32, 128), (1, 2048, 32, 128)]


def test_v5e_eva_chunk_program_with_the_two_leaf_kernel_moves_no_rows(
        chip, monkeypatch):
    """On the chip (the dispatcher's question about the backend answered as
    the chip would) the bounded step of an "eva" layer is
    `ragged_two_leaf_attention`: ONE Mosaic call a layer in the step's body
    where two `conditional`s of `over_kv_prefix` stood, handed all four
    leaves as the step's own update of the window left them, in HBM; no
    loop but the chunk's own (`trace_reduce.loop_steps`); no leaf copied
    into another layout (`cache_boundary_copies` 0, PR 49's pin), and
    outside the fusions no `copy`, `slice`, `transpose` or
    `bitcast-convert` of a leaf or of any prefix of its rows but the 16
    rows a finished chunk is pooled from, which the XLA form gathers too.
    The engine counts its decode steps under the kernel, in blocks of 128
    rows of both leaves."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    eng = build_compiled(chip, cfg=EVA, max_batch=16, decode_chunk=16)
    assert eng.cache_boundary_copies == 0
    assert (eng._kernel_blocks, eng._decode_form) == (
        {"window": 128, "chunks": 128}, "kernel")
    text = eng._chunk.lower(*eng._chunk_shapes(
        eng.params, eng._cache_spec, True)).compile().as_text()
    calls = re.findall(r"%([a-z_]+)[.\d]* = \S+ custom-call\((.*?)\), "
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert [kernel for kernel, _ in calls] == [
        "_ragged_two_leaf"] * EVA.n_layers
    assert " conditional(" not in text
    assert len(re.findall(r" while\(", text)) == 1
    moved = moved_rows(eng, text, "copy|slice|transpose|bitcast-convert|"
                                  "copy-start|copy-done")
    assert set(moved) <= {("transpose", (16, 16, 32, 128))}, moved
    for _, operands in calls:
        # (the grid's length, six tables, q, K, V, Kbar, Vbar)
        *_, k, v, kbar, vbar = operands.split(", ")
        assert not any("copy" in leaf for leaf in (k, v, kbar, vbar)), operands


def test_v5e_eva_chunk_sharded_over_tp_keeps_the_two_leaf_kernel_out(
        chips, monkeypatch):
    """`test_v5e_chunk_sharded_over_tp_keeps_the_unpartitioned_kernel_out`
    for an "eva" model: given a `tp` mesh the engine traces its step with
    that mesh in context, `two_leaf_refusal` answers the trace what it
    answered `_decode_blocks`, and the chunk compiles for four chips over
    window and summaries leaves sharded on the head axis with the two
    walks in it (two `conditional`s a layer) and no Mosaic call."""
    import numpy as np
    from jax.sharding import Mesh

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    mesh = Mesh(np.array(chips), ("tp",))
    eng = build_compiled(chips[0], cfg=EVA, mesh=mesh, max_batch=16,
                         decode_chunk=16)
    assert (eng._kernel_blocks, eng._decode_form) == ({}, "xla")
    everywhere = lambda s: s if getattr(  # noqa: E731
        s, "sharding", None) is not None or not hasattr(s, "shape") else (
        jax.ShapeDtypeStruct(s.shape, s.dtype,
                             sharding=NamedSharding(mesh, P())))
    shapes = eng._chunk_shapes(eng.params, eng._cache_spec, True)
    text = eng._chunk.lower(*(jax.tree.map(everywhere, a)
                              for a in shapes)).compile().as_text()
    assert "tpu_custom_call" not in text
    assert len(re.findall(r" conditional\(", text)) == 2 * EVA.n_layers


def test_v5e_eva_prefill_past_a_window_keeps_its_scores_in_the_kernel(
        chip, chips, monkeypatch):
    """On the chip (the dispatchers' question about the backend answered as
    the chip would) the 6144-row prefill program of `evabyte-pp4-8l`, the
    bucket of the cell's check prompt of 5000 bytes, attends through
    `two_source_attention`: ONE Mosaic call a layer for its three windows,
    the prompt's length prefetched, no loop over tiles and no float32 tile
    of scores `[1, 32 heads, 512 queries, keys]`, which the tile scan of
    the same bucket (what this process, held to the CPU, is given) writes
    to memory at 2,432 keys a query. The engine says `kernel` for every
    bucket the mix reaches and for the flash kernel's up to a window, and
    `xla` for all of them off the chip. Under a `tp` mesh of the four
    chips the rule refuses and the tile scan is there."""
    import json

    import numpy as np
    from jax.sharding import Mesh

    from ray_tpu.ops import attention

    on_chip = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.int32, sharding=SingleDeviceSharding(chip))
    scores = r"f32\[1,32,512,\d+\]"
    buckets = (2048, 4096, 6144, 8192, 12288)
    off = build_compiled(chip, cfg=EVA, max_batch=16, decode_chunk=16)
    assert [off._prefill_form(b) for b in buckets] == ["xla"] * 5
    before = off._prefill.lower(off.params, on_chip(1, 6144),
                                on_chip()).compile()
    assert "f32[1,32,512,2432]" in before.as_text()
    assert "tpu_custom_call" not in before.as_text()
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "evabyte-pp4-8l.json")) as f:
        config = json.load(f)
    app = config["app_kwargs"]
    eng = build_compiled(chip, cfg=LLMConfig(**config["llm_config"]),
                         max_batch=app["max_batch"],
                         decode_chunk=app["decode_chunk"])
    assert [eng._prefill_form(b) for b in buckets] == ["kernel"] * 5
    after = eng._prefill.lower(eng.params, on_chip(1, 6144),
                               on_chip()).compile()
    text = after.as_text()
    calls = re.findall(r"%([a-z_]+)[.\d]* = \S+ custom-call\(.*?\), "
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert calls == ["two_source_attention"] * 8
    assert not re.search(scores, text)
    assert " while(" not in text
    # the score tiles were the program's largest temporaries: eight layers
    # without them need less than two layers with them
    assert (after.memory_analysis().temp_size_in_bytes
            < before.memory_analysis().temp_size_in_bytes - 100e6)
    mesh = Mesh(np.array(chips), ("tp",))
    sharded = build_compiled(chips[0], cfg=EVA, mesh=mesh, max_batch=16,
                             decode_chunk=16)
    assert [sharded._prefill_form(b) for b in buckets] == ["xla"] * 5
    everywhere = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.int32, sharding=NamedSharding(mesh, P()))
    text = sharded._prefill.lower(sharded.params, everywhere(1, 6144),
                                  everywhere()).compile().as_text()
    assert "tpu_custom_call" not in text and " while(" in text
    assert re.search(r"f32\[1,8,512,2432\]", text)  # 8 heads a chip


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_v5e_evabyte_as_benchmarked_fits_the_chip(chip, form, monkeypatch):
    """`benchmark/configs/evabyte-pp4-8l.json` as the cell runs it, 8 layers
    and 16 slots: the 16-step chunk program and the longest prefill the mix
    reaches (12288 rows), each beside everything else the device holds,
    within the chip's 16 GB, with the two walks (what this process, held to
    the CPU, is given) and as the chip builds it: the two-leaf kernel, one
    Mosaic call a layer and no `conditional` from `over_kv_prefix`, and in
    the prefill one two-source call a layer for its six windows.
    Compile-only: no parameter is made."""
    import json

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: form == "kernel")
    # (up to a window the prefill keeps the form this file's other sizes
    # were read with; past one, the two-source rule answers with `form`)
    monkeypatch.setattr(attention, "kernel_refusal",
                        lambda *shapes, **kw: attention.NOT_ASKED)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "evabyte-pp4-8l.json")) as f:
        config = json.load(f)
    app = config["app_kwargs"]
    eng = build_compiled(chip, cfg=LLMConfig(**config["llm_config"]),
                         max_batch=app["max_batch"],
                         decode_chunk=app["decode_chunk"])
    assert eng.cache_boundary_copies == 0
    cache = eng.cache_stats()["cache_bytes"]
    assert round(cache / 1e9, 2) == 6.44

    def held(compiled):
        m = compiled.memory_analysis()
        return (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)

    chunk = eng._chunk.lower(*eng._chunk_shapes(
        eng.params, eng._cache_spec, False)).compile()
    assert 9.7e9 < held(chunk) < 11.5e9  # weights 3.26 + cache 6.44 + temps
    assert eng._decode_form == form
    text = chunk.as_text()
    assert len(re.findall(r" custom-call\(.*tpu_custom_call", text)) == (
        8 if form == "kernel" else 0)
    # (the sampled program: the sampler has branches of its own)
    assert len(re.findall(r" conditional\(.*decode_attention", text)) == (
        0 if form == "kernel" else 2 * 8)
    on_chip = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.int32, sharding=SingleDeviceSharding(chip))
    prefill = eng._prefill.lower(eng.params, on_chip(1, 12288),
                                 on_chip()).compile()
    assert len(re.findall(r" custom-call\(.*tpu_custom_call",
                          prefill.as_text())) == (8 if form == "kernel" else 0)
    # beside the cache, the chunk program's temporaries and a quarter of
    # the cache in parked slices (`_park_budget`)
    assert held(prefill) + held(chunk) - 3.26e9 + cache / 4 < 15e9


# ---------------------------------------------------------------------------
# A looped stack (`model_type` `ouro`): the layers run several times a token.

def test_v5e_ouro_as_benchmarked_walks_32_leaf_pairs_where_they_lie(
        chip, monkeypatch):
    """`benchmark/configs/ouro-2.6b-8l.json` as the cell runs it, 8 layers
    run 4 times a token and 16 slots, as the chip builds it: the 16-step
    chunk program's step is 32 layer bodies WRITTEN OUT, each with one
    Mosaic call (the `mha` family's ragged kernel) handed its pass's own K
    and V where they lie: no `copy`, `slice`, `transpose` or
    `bitcast-convert` whose result has a leaf's dimensions or any prefix of
    its rows, no leaf staged ahead of a call, and NO loop inside the step
    (the chunk's own `while` is the only one: `trace_reduce.loop_steps`
    counts the most often started operation name inside a `jit_chunk`
    execution as its steps, so a loop over the passes would make every
    step count four times). The programs fit the chip beside a quarter of
    the cache in parked slices. Compile-only: no parameter is made."""
    import json

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "ouro-2.6b-8l.json")) as f:
        config = json.load(f)
    app = config["app_kwargs"]
    eng = build_compiled(chip, cfg=LLMConfig(**config["llm_config"]),
                         max_batch=app["max_batch"],
                         decode_chunk=app["decode_chunk"])
    assert eng.cache_boundary_copies == 0
    assert (eng._kernel_blocks, eng._decode_form) == ({"full": 256}, "kernel")
    assert eng._kernel_pieces == {"full": 32}  # 128 KiB of 16 heads of 128
    full = eng.cache_stats()["cache_kinds"]["full"]
    assert (full["layers"], full["leaves"], full["passes"], full["rows"]) == (
        8, 64, 4, 2048)
    assert round(full["bytes"] / 1e9, 2) == 8.59
    assert sorted(eng.params) == sorted(
        ["tok_emb", "lm_head", "final_norm", "exit_gate"]
        + [f"layer_{i}" for i in range(8)])  # ONE set of weights

    def held(compiled):
        m = compiled.memory_analysis()
        return (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)

    chunk = eng._chunk.lower(*eng._chunk_shapes(
        eng.params, eng._cache_spec, False)).compile()
    assert 9.8e9 < held(chunk) < 10.6e9  # weights 1.22 + cache 8.59 + temps
    text = chunk.as_text()
    calls = re.findall(r" custom-call\((.*?)\), custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert len(calls) == 4 * 8
    for line in calls:  # (the work list's length, stop, slot, at, q, K, V)
        *_, k, v = line.split(", ")
        assert "copy" not in k and "copy" not in v, line
    assert len(re.findall(r" while\(", text)) == 1
    # (the sampled program: the sampler has branches of its own)
    assert not re.findall(r" conditional\(.*decode_attention", text)
    assert not moved_rows(eng, text, "copy|slice|transpose|bitcast-convert"
                                     "|copy-start|copy-done")
    on_chip = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.int32, sharding=SingleDeviceSharding(chip))
    prefill = eng._prefill.lower(eng.params, on_chip(1, 512),
                                 on_chip()).compile()
    assert eng._prefill_form(512) == "kernel"
    assert len(re.findall(r" custom-call\(.*tpu_custom_call",
                          prefill.as_text())) == 4 * 8
    assert " while(" not in prefill.as_text()
    # a prefill of 512 rows hands on 512 rows of all 64 leaves
    assert eng._slice_bytes(512) == 64 * 512 * 16 * 128 * 2
    assert eng._park_budget == full["bytes"] // 4
    assert held(prefill) + held(chunk) - 1.225e9 + full["bytes"] / 4 < 15e9


#: sha256 (first 16 hex digits) of the lowered texts of the six older
#: families' bounded decode step and padded prefill (this file's small
#: configurations, the XLA forms this process, held to the CPU, is given:
#: a kernel's payload names its file by its path) as the PARENT commit of
#: the PR that brought the looped stack lowered them, taken there with
#: `model_texts`. With `ut_steps` 1 every older model is the program it was.
#: Whoever changes a model's step on purpose takes the digests again.
PARENT_MODEL_TEXTS = {
    "CFG": ("2b6caf2debfc03df", "944a55043376bd38"),
    "LATENT": ("330a15cb907e0e4a", "76b1f3e186072919"),
    "SWA": ("c0658ac94bf0ecc3", "cdf881d54c379bf4"),
    "KDA": ("792b1f6eab233891", "4131f226d58542b2"),
    "SCMOE": ("82fd1a9d78f8c0ea", "2ac08893b209dc96"),
    "EVA": ("00f120a2aa60f557", "ff757b158ab71e49")}


def model_texts(chip, cfg, slots=4, bucket=256):
    """The lowered texts of a model's bounded decode step and of its padded
    prefill for `chip`, from shapes."""
    from ray_tpu.models.published import model_config
    from ray_tpu.models.transformer import Transformer

    model = Transformer(model_config(cfg))
    on_chip = lambda dtype, *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=SingleDeviceSharding(chip))
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda leaf: on_chip(leaf.dtype, *leaf.shape), tree)
    params = placed(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    toks = jnp.zeros((slots, 1), jnp.int32)
    cache = placed(jax.eval_shape(
        lambda p: model.apply({"params": p}, toks, positions=toks,
                              decode=True, mutable=["cache"])[1]["cache"],
        params))

    def step(p, c, t, pos, kb, live):
        return model.apply({"params": p, "cache": c}, t, positions=pos,
                           decode=True, kv_bound=kb, live=live,
                           mutable=["cache"])

    def prefill(p, t, plen):
        return model.apply({"params": p}, t,
                           positions=jnp.arange(bucket)[None], decode=True,
                           prompt_len=jnp.reshape(plen, (1,)),
                           mutable=["cache"])

    i32 = jnp.int32
    return (jax.jit(step).lower(
        params, cache, on_chip(i32, slots, 1), on_chip(i32, slots, 1),
        on_chip(i32), on_chip(jnp.bool_, slots)).as_text(),
        jax.jit(prefill).lower(params, on_chip(i32, 1, bucket),
                               on_chip(i32)).as_text())


@pytest.mark.parametrize("name", list(PARENT_MODEL_TEXTS))
def test_v5e_older_families_lower_to_the_text_they_had_before_the_loop(
        chip, name):
    texts = model_texts(chip, globals()[name])
    assert "tpu_custom_call" not in "".join(texts)
    assert tuple(hashlib.sha256(text.encode()).hexdigest()[:16]
                 for text in texts) == PARENT_MODEL_TEXTS[name]


def test_the_line_a_ring_layers_kernel_programs_name_has_not_moved():
    """A kernel's payload names the line that FIRST traced a jitted helper
    its body reuses: every chunk and kernel-prefill program of a model with
    window layers carries `models/transformer.py:304`, the ring's `pos %
    rows` (read from the payload's bytes at PR 53, whose first form moved it
    by 17 lines and so changed 18 of Trinity-Mini's program texts;
    `tools/lowered.py` on both trees shows it, a digest here cannot: the
    payload also names the checkout's path). New code goes to that file's
    END."""
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "ray_tpu", "models",
            "transformer.py")) as f:
        lines = f.read().split("\n")
    assert lines[303].strip() == "at = pos % rows if self.window else pos"


# ---------------------------------------------------------------------------
# Generation by diffusion over blocks (`model_type` `sdar_moe`): a step is a
# forward of L positions a slot.

def test_v5e_sdar_as_benchmarked_steps_a_block_a_slot_where_the_rows_lie(
        chip, monkeypatch):
    """`benchmark/configs/sdar-30b-a3b-pp8-6l.json` as the cell runs it, 6
    layers, all 128 experts, the whole vocabulary and 32 slots, as the chip
    builds it: the 16-FORWARD chunk program is one `while` (no loop inside
    a forward: `trace_reduce.loop_steps` counts the most often started
    operation of a `jit_chunk` execution as its steps) whose step holds one
    Mosaic call a layer, the `mha` family's ragged kernel handed 4 x 8 query
    rows a key/value head and K and V where they lie: no `copy`, `slice`,
    `transpose` or `bitcast-convert` whose result has a leaf's dimensions or
    any prefix of its rows. Its two `conditional`s are the sampler's. The
    prefill of a bucket the flash kernel can tile is causal BY BLOCKS in a
    kernel of its own and hands on no logits: the last layer's attention
    and experts, whose output nobody reads, are not in the program. The
    programs fit the chip beside a quarter of the cache in parked slices.
    Compile-only: no parameter is made."""
    import json

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "sdar-30b-a3b-pp8-6l.json")) as f:
        config = json.load(f)
    app = config["app_kwargs"]
    eng = build_compiled(chip, cfg=LLMConfig(**config["llm_config"]),
                         max_batch=app["max_batch"],
                         decode_chunk=app["decode_chunk"])
    assert eng.cache_boundary_copies == 0
    assert (eng._kernel_blocks, eng._decode_form) == ({"full": 1024}, "kernel")
    assert eng._kernel_pieces == {"full": 128}  # 128 KiB of 4 heads of 128
    full = eng.cache_stats()["cache_kinds"]["full"]
    assert (full["layers"], full["leaves"], full["rows"],
            full["block_length"]) == (6, 12, 2048, 4)
    assert round(full["bytes"] / 1e9, 2) == 0.81

    def held(compiled):
        m = compiled.memory_analysis()
        return (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)

    chunk = eng._chunk.lower(*eng._chunk_shapes(
        eng.params, eng._cache_spec, False)).compile()
    assert 9.6e9 < held(chunk) < 10.6e9  # weights 8.72 + cache 0.81 + temps
    text = chunk.as_text()
    calls = re.findall(r" custom-call\((.*?)\), custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert len(calls) == 6
    for line in calls:  # (the work list's length, stop, slot, at, q, K, V)
        *_, q, k, v = line.split(", ")
        assert "copy" not in k and "copy" not in v, line
    assert "[32,128,128]" in text  # 4 positions x 32 heads a slot
    assert len(re.findall(r" while\(", text)) == 1
    assert len(re.findall(r" conditional\(", text)) == 2
    assert not re.findall(r" conditional\(.*decode_attention", text)
    # (what has 4 rows a slot is the open block's NEW keys and values on
    # their way into the leaf, not rows of it)
    assert not [m for m in moved_rows(
        eng, text, "copy|slice|transpose|bitcast-convert|copy-start"
                   "|copy-done") if m[1][1] != 4]
    on_chip = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.int32, sharding=SingleDeviceSharding(chip))
    assert [eng._prefill_form(b) for b in (64, 128, 1024)] == [
        "xla", "kernel", "kernel"]  # (64 keys are no lane tile)
    assert eng._bucket(1023) == 1024 and eng._bucket(1027) == 1024
    prefill = eng._prefill.lower(eng.params, on_chip(1, 1024),
                                 on_chip()).compile()
    assert len(re.findall(r" custom-call\(.*tpu_custom_call",
                          prefill.as_text())) == 6 - 1
    slices = jax.eval_shape(eng._prefill.jitted, eng.params, on_chip(1, 1024), 5)
    assert {leaf.shape for leaf in jax.tree.leaves(slices)} == {
        (1, 1024, 4, 128)}  # slices alone: no logits
    assert eng._slice_bytes(1024) == 12 * 1024 * 4 * 128 * 2
    assert held(prefill) + held(chunk) - 8.72e9 + full["bytes"] / 4 < 15e9
