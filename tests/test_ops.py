"""Attention kernels: Pallas flash (interpret mode on CPU) and ring
attention over a virtual sp mesh axis, both vs the XLA reference.

reference has no attention kernels (delegates to torch/vLLM); these are
TPU-native and tested against ray_tpu.ops.attention._xla_attention.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _xla_attention
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.ring_attention import ring_attention


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_numerics(causal):
    rng = np.random.RandomState(0)
    b, sq, sk, h, d = 2, 256, 256, 4, 64
    q = jnp.asarray(rng.randn(b, sq, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, sk, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, sk, h, d), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    ref = _xla_attention(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_flash_attention_gqa_and_cross_lengths():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 128, 8, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 384, 2, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 384, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    ref = _xla_attention(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_flash_attention_rejects_bad_shapes():
    q = jnp.zeros((1, 100, 4, 64))  # 100 not divisible by any block
    with pytest.raises(ValueError):
        flash_attention(q, q, q, block_q=128, block_k=128, interpret=True)


def test_flash_attention_accepts_bench_shape():
    """The microbench config (b4 s2048 h8 d128) must pass block-shape
    selection — auto-derived lane-aligned blocks, no ValueError (r05
    regression: a hard-coded block pair rejected the flagship shape and the
    bench silently fell back to XLA)."""
    b, s, h, d = 4, 2048, 8, 128
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    # eval_shape traces the full kernel call (shape checks + pallas_call
    # spec construction) without paying the interpret-mode compute.
    out = jax.eval_shape(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=True), q, q, q)
    assert out.shape == (b, s, h, d)


def test_flash_attention_block_derivation_clamps_to_valid_tiles():
    """derive_blocks is the single derivation path (auto AND explicit
    preferences): the bench shape must land on the tuned 512/1024, explicit
    oversized blocks clamp to aligned divisors instead of slipping through
    min() as tile-violating remnants (r05: 'blocks 8/8 violate TPU
    tiling'), and infeasible shapes raise the fallback reason."""
    from ray_tpu.ops.flash_attention import derive_blocks

    # The microbench shape (b4 s2048 h8 d128) selects the Pallas path with
    # the tuned blocks.
    assert derive_blocks(2048, 2048) == (512, 1024)
    # Explicit blocks are preferences: clamped to aligned divisors.
    assert derive_blocks(256, 256, 1024, 1024) == (256, 256)
    assert derive_blocks(2048, 2048, 100, 1000) == (64, 512)
    # A short k sequence can never produce a sub-128 block_k: it raises
    # (XLA fallback) with the reason, not a tile-violating 8/8 pair.
    with pytest.raises(ValueError, match="lane tile"):
        derive_blocks(8, 8)
    with pytest.raises(ValueError, match="lane tile"):
        derive_blocks(256, 64, 128, 128)
    with pytest.raises(ValueError, match="sublane tile"):
        derive_blocks(100, 256)


def test_flash_attention_explicit_blocks_clamped_numerics():
    """An explicit block preference larger than the sequence still runs
    (clamped), matching the XLA reference."""
    rng = np.random.RandomState(7)
    b, s, h, d = 1, 256, 2, 32
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    out = flash_attention(q, q, q, causal=True, block_q=512, block_k=1024,
                          interpret=True)
    ref = _xla_attention(q, q, q, causal=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_flash_attention_auto_blocks():
    """Auto-derived blocks: lane-aligned divisors of Sq/Sk, numerics still
    matching the XLA reference; shapes with no aligned divisor raise."""
    from ray_tpu.ops.flash_attention import auto_block

    assert auto_block(2048, 512, 8) == 512
    assert auto_block(2048, 1024, 128) == 1024
    assert auto_block(640, 512, 8) == 320
    assert auto_block(640, 1024, 128) == 640
    assert auto_block(16, 512, 8) == 16
    assert auto_block(64, 1024, 128) is None  # < one lane tile
    assert auto_block(100, 512, 8) is None  # not sublane-alignable
    rng = np.random.RandomState(3)
    b, s, h, d = 1, 256, 2, 64
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    out = flash_attention(q, q, q, causal=True, interpret=True)  # auto blocks
    ref = _xla_attention(q, q, q, causal=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def _dense_float64(q, k, v, causal, window):
    """Masked dense softmax attention in float64: query row i stands at key
    position i + (Sk - Sq) and sees key j iff 0 <= that - j (< window)."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    (_, sq, hq, d), (_, sk, hkv, _) = q.shape, k.shape
    k, v = (np.repeat(x, hq // hkv, axis=2) for x in (k, v))
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    back = (np.arange(sq)[:, None] + sk - sq) - np.arange(sk)[None, :]
    seen = np.ones_like(back, bool)
    if causal:
        seen = back >= 0
    if window:
        seen &= back < window
    scores = np.where(seen, scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize(
    "rows, heads, kv_heads, d, window, blocks, q_len", [
        (256, 32, 4, 128, 0, (64, 128), None),  # Trinity's heads, full
        (256, 32, 4, 128, 16, (64, 128), None),  # a window inside a block
        (384, 8, 2, 128, 200, (64, 128), None),  # rows no multiple of it
        (256, 8, 2, 128, 1000, (64, 128), None),  # longer than the call
        (256, 8, 8, 128, 0, (128, 128), None),  # one head a key/value head
        (256, 8, 8, 128, 48, (64, 128), None),
        (256, 4, 4, 96, 0, (128, 128), None),  # heads of 96, interpreted
        (512, 8, 1, 128, 130, (64, 128), [300, 70]),  # prompts end inside
        (512, 16, 4, 128, 0, (128, 256), [512, 1]),  # a block, or at once
        (640, 8, 2, 128, 256, (None, None), [401]),  # blocks of 320 x 640
    ])
def test_flash_kernel_is_the_masked_dense_softmax(rows, heads, kv_heads, d,
                                                   window, blocks, q_len):
    """The one kernel, interpreted on the CPU, against a dense float64
    softmax under the mask `prefill_attention` applies: full causal and
    windowed layers, query heads grouped on fewer key/value heads (never
    repeated), windows inside a block, across blocks and longer than the
    call, and prompts that end inside a block: rows before `q_len` are
    right, query blocks wholly past it come back as zeros. (On the chip a
    head of 96 is refused by `unsupported_reason`; interpreted, the kernel
    takes it.)"""
    rng = np.random.RandomState(rows + heads + window)
    b = 1 if q_len is None else len(q_len)
    q = jnp.asarray(rng.randn(b, rows, heads, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, rows, kv_heads, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, rows, kv_heads, d), jnp.float32)
    got = np.array(flash_attention(
        q, k, v, causal=True, window=window, block_q=blocks[0],
        block_k=blocks[1], interpret=True,
        q_len=None if q_len is None else jnp.asarray(q_len, jnp.int32)))
    want = _dense_float64(q, k, v, True, window)
    block_q = blocks[0] or 320
    for i, n in enumerate(q_len or []):
        assert not got[i, -(-n // block_q) * block_q:].any()
        got[i, n:] = want[i, n:] = 0.0
    assert np.abs(got - want).max() < 5e-6


@pytest.mark.parametrize("q_shape, k_shape, kw, reason", [
    # Trinity's prefill buckets, window and full layers: the kernel
    ((1, 4096, 32, 128), (1, 4096, 4, 128), {"window": 2048}, None),
    ((1, 6144, 32, 128), (1, 6144, 4, 128), {}, None),
    ((1, 128, 32, 128), (1, 128, 4, 128), {"window": 2048}, None),
    # Phi-3's heads of 96 are no whole lane tile; a bucket under one tile
    ((1, 512, 32, 96), (1, 512, 32, 96), {}, "head size 96"),
    ((1, 64, 32, 128), (1, 64, 4, 128), {}, "Sk=64 has no divisor"),
    ((1, 100, 32, 128), (1, 256, 4, 128), {}, "Sq=100 has no divisor"),
    ((1, 256, 6, 128), (1, 256, 4, 128), {}, "Hq=6 not a multiple"),
    ((1, 256, 8, 128), (1, 128, 8, 128), {}, "Sq=256 > Sk=128"),
    ((1, 256, 8, 128), (1, 256, 8, 128),
     {"causal": False, "window": 8}, "window without causal"),
])
def test_the_dispatchers_rule_for_a_shape(q_shape, k_shape, kw, reason):
    """`kernel_refusal` is the one rule: here, on the CPU, nobody asks for
    the kernel; asked for it, the answer is the kernel's own
    `unsupported_reason`, and `dot_product_attention` follows it to the XLA
    form without touching the kernel (which could not be lowered here)."""
    from ray_tpu.ops.attention import (NOT_ASKED, dot_product_attention,
                                       kernel_refusal)

    assert kernel_refusal(q_shape, k_shape, **kw) == NOT_ASKED
    got = kernel_refusal(q_shape, k_shape, use_pallas=True, **kw)
    assert (got is None) if reason is None else (reason in got), got
    if reason and ("has no divisor" in reason or "head size" in reason):
        small = lambda shape: jnp.ones(  # noqa: E731 - 2 heads' worth
            (1, shape[1], shape[2], shape[3]), jnp.float32)
        out = dot_product_attention(small(q_shape), small(k_shape),
                                    small(k_shape), use_pallas=True, **kw)
        assert out.shape == q_shape


@pytest.mark.parametrize("window", [0, 24])
def test_on_the_cpu_the_door_leads_to_the_xla_form(window):
    """Causal attention of a call over its own rows, with a window or
    without: off the chip `dot_product_attention` IS `prefill_attention`,
    whatever `q_len` says."""
    from ray_tpu.ops.attention import dot_product_attention, prefill_attention

    rng = np.random.RandomState(window)
    q = jnp.asarray(rng.randn(2, 128, 8, 128), jnp.float32)
    k, v = (jnp.asarray(rng.randn(2, 128, 2, 128), jnp.float32)
            for _ in range(2))
    got = dot_product_attention(q, k, v, causal=True, window=window,
                                q_len=jnp.asarray([100, 7], jnp.int32))
    want = prefill_attention(q, k, v, window)
    assert float(jnp.max(jnp.abs(got - want))) == 0.0
    np.testing.assert_allclose(
        np.asarray(want), _dense_float64(q, k, v, True, window), atol=5e-6)
    with pytest.raises(ValueError, match="own rows"):
        dot_product_attention(q, k[:, :64], v[:, :64], window=8)


def test_attention_dispatch_states_its_rule_once(caplog):
    """The choice is made up front from the shapes (the kernel's own block
    derivation) and stated once per distinct reason at INFO; a shape the
    kernel cannot tile takes the XLA path without touching the kernel."""
    import logging

    from ray_tpu.ops import attention as attn_mod
    from ray_tpu.ops.attention import dot_product_attention

    attn_mod._stated.clear()
    q_bad_sq = jnp.ones((1, 100, 2, 128), jnp.float32)  # Sq not 8-alignable
    q_small = jnp.ones((1, 64, 2, 128), jnp.float32)  # Sk < one lane tile

    def stated():
        return [r.message for r in caplog.records if "XLA path" in r.message]

    with caplog.at_level(logging.INFO, logger="ray_tpu.ops.attention"):
        out = dot_product_attention(q_bad_sq, q_bad_sq, q_bad_sq,
                                    use_pallas=True)
        assert len(stated()) == 1 and "sublane tile" in stated()[0]
        dot_product_attention(q_small, q_small, q_small, use_pallas=True)
        assert len(stated()) == 2 and "lane tile" in stated()[1]
        # repeat of the first reason: stated once
        dot_product_attention(q_bad_sq, q_bad_sq, q_bad_sq, use_pallas=True)
        assert len(stated()) == 2
    ref = _xla_attention(q_bad_sq, q_bad_sq, q_bad_sq, causal=True)
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0


def test_attention_kernel_error_propagates():
    """Nothing stands between the dispatcher and the kernel: at a shape the
    kernel takes, its failure (here: Mosaic cannot compile for the CPU) is
    the caller's error, not a quiet switch to XLA."""
    from ray_tpu.ops.attention import dot_product_attention

    q = jnp.ones((1, 256, 2, 128), jnp.float32)
    with pytest.raises(Exception, match="[Ii]nterpret mode"):
        dot_product_attention(q, q, q, use_pallas=True)


@pytest.mark.parametrize("window", [0, 40])
def test_attention_gradient_takes_xla_path_by_rule(caplog, window):
    """The flash kernel has no VJP. Under differentiation the dispatcher
    takes the XLA path for forward and backward — by a rule it states, in
    either order of jit and grad, for a window layer as for a full one. On the CPU the kernel cannot be lowered
    at all (test_attention_kernel_error_propagates), so a gradient that
    comes out right here never ran it."""
    import logging

    from ray_tpu.ops import attention as attn_mod
    from ray_tpu.ops.attention import dot_product_attention

    attn_mod._stated.clear()
    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.randn(1, 128, 2, 128), jnp.float32)
               for _ in range(3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    dispatched = loss(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True, window=window, use_pallas=True,
        q_len=jnp.asarray([100], jnp.int32)))
    from ray_tpu.ops.attention import prefill_attention
    want = jax.grad(loss(lambda q, k, v: prefill_attention(
        q, k, v, window)), argnums=(0, 1, 2))(q, k, v)
    if not window:
        for w, x in zip(want, jax.grad(loss(lambda q, k, v: _xla_attention(
                q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(np.asarray(w), np.asarray(x),
                                       atol=1e-5)
    with caplog.at_level(logging.INFO, logger="ray_tpu.ops.attention"):
        got = jax.jit(jax.grad(dispatched, argnums=(0, 1, 2)))(q, k, v)
        got2 = jax.grad(jax.jit(dispatched), argnums=(0, 1, 2))(q, k, v)
    for g, g2, w in zip(got, got2, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)
        np.testing.assert_allclose(np.asarray(g2), np.asarray(w), atol=1e-5)
    said = [r.message for r in caplog.records if "no VJP" in r.message]
    assert len(said) == 1


def plain_decode_attention(q, k, v, lengths):
    """One sequence and one head at a time, in float64: softmax over the
    sequence's own rows of q . K / sqrt(d), times V. Rows of the cache
    wider than q's head are cut to it."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    b, hq, d = q.shape
    group = hq // k.shape[2]
    out = np.zeros((b, hq, d))
    for i, n in enumerate(lengths):
        for h in range(hq):
            scores = k[i, :n, h // group, :d] @ q[i, h] / np.sqrt(d)
            weights = np.exp(scores - scores.max())
            out[i, h] = weights / weights.sum() @ v[i, :n, h // group, :d]
    return out


def decode_case(hq, hkv, d, row, s=256, seed=6):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(3, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(3, s, hkv, row), jnp.float32)
    v = jnp.asarray(rng.randn(3, s, hkv, row), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)], ids=["mha", "gqa"])
def test_decode_attention_matches_a_plain_softmax(hq, hkv):
    """The whole-cache walk (no bound: what `LLMEngine` and the pipeline's
    stages build) at ragged lengths: one row, mid-cache, every row."""
    from ray_tpu.ops.decode_attention import decode_attention

    q, k, v = decode_case(hq, hkv, d=32, row=32)
    lens = [1, 130, 256]
    out = decode_attention(q, k, v, jnp.asarray(lens, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(out), plain_decode_attention(q, k, v, lens), atol=2e-5)


def test_decode_attention_bounded_over_wide_rows_matches_a_plain_softmax():
    """The serving walk as the engine calls it on the chip: heads of 96 in
    rows of 128 (`cache_row`), stopped by a traced bound at a prefix of the
    cache (192 of 256 rows here). What lies beyond the head in a row, and
    beyond the bound in the cache, is never read into the result."""
    from ray_tpu.ops.decode_attention import decode_attention, kv_prefix_rows

    q, k, v = decode_case(4, 4, d=96, row=128)
    lens = [1, 100, 130]
    assert kv_prefix_rows(max(lens), 256) == 192
    walk = jax.jit(lambda kb: decode_attention(
        q, k, v, jnp.asarray(lens, jnp.int32), kv_bound=kb))
    np.testing.assert_allclose(
        np.asarray(walk(jnp.int32(max(lens)))),
        plain_decode_attention(q, k, v, lens), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(causal):
    """4-way sp sharding on the CPU mesh: ring attention must equal
    single-device attention on the gathered sequence."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("sp",))
    rng = np.random.RandomState(2)
    b, s, h, d = 2, 64, 2, 16
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    out = jax.jit(ring)(q, k, v)
    ref = _xla_attention(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_full(causal):
    """4-way Ulysses sequence parallelism (all-to-all head sharding) must
    equal single-device attention on the gathered sequence."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from ray_tpu.ops.ulysses import ulysses_attention

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("sp",))
    rng = np.random.RandomState(5)
    b, s, h, d = 2, 64, 4, 16  # h divisible by the 4-way axis
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

    uly = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp",
                                          causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    out = jax.jit(uly)(q, k, v)
    ref = _xla_attention(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
