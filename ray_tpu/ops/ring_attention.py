"""Ring attention: sequence-parallel attention over a mesh axis.

Each device holds a [B, S/n, H, D] shard of q/k/v along the sequence axis.
k/v shards rotate around the ring via `ppermute` while every device folds
the visiting chunk into its queries' online-softmax state (m, l, acc in
f32), so the full [Sq, Sk] score matrix never exists anywhere and the k/v
memory per device stays O(S/n) — the long-context mechanism SURVEY §7
step 11 calls for (the reference has no equivalent; it delegates long
context to vLLM). Designed for use inside shard_map over the 'sp' mesh
axis; collectives ride ICI.

Causality uses GLOBAL positions: shard i's queries own rows
[i*S/n, (i+1)*S/n); the chunk visiting at step s carries the keys of shard
(i - s) mod n, so whole future chunks contribute nothing (their
exp(-inf)=0) and the math matches single-device causal attention exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def ring_attention(q, k, v, *, axis_name: str, causal: bool = True):
    """q/k/v: local shards [B, S_local, H, D] of a sequence sharded over
    `axis_name`. Returns the local output shard [B, S_local, H, D]. Call
    inside shard_map/pjit with q/k/v sharded on the sequence axis."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_local, hq, d = q.shape
    _, _, hkv, _ = k.shape
    rep = hq // hkv
    scale = d ** -0.5
    # GQA stays folded as a group dim [b, s, hkv, rep, d]: k/v ride the
    # ring at their NATIVE hkv width (repeating them would multiply every
    # ppermute transfer and per-device kv residency by hq/hkv).
    qf = q.astype(jnp.float32).reshape(b, s_local, hkv, rep, d)

    q_pos = idx * s_local + jax.lax.broadcasted_iota(
        jnp.int32, (s_local, s_local), 0)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        m, l, acc, k_cur, v_cur = carry
        owner = (idx - s) % n  # whose keys are visiting this step
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k_cur.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = owner * s_local + jax.lax.broadcasted_iota(
                jnp.int32, (s_local, s_local), 1)
            mask = k_pos <= q_pos  # [s_local, s_local] global causal
            sc = jnp.where(mask[None, None, None], sc, jnp.float32(-jnp.inf))
        m_cur = jnp.max(sc, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        # Guard -inf - -inf (rows with no visible keys in this chunk).
        p = jnp.exp(sc - jnp.where(jnp.isinf(m_new), 0.0, m_new))
        p = jnp.where(jnp.isinf(m_new), 0.0, p)
        alpha = jnp.exp(m - m_new)
        alpha = jnp.where(jnp.isinf(m) & jnp.isinf(m_new), 0.0, alpha)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, v_cur.astype(jnp.float32))
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, acc_new, k_nxt, v_nxt), None

    m0 = jnp.full((b, hkv, rep, s_local, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, rep, s_local, 1), jnp.float32)
    acc0 = jnp.zeros((b, hkv, rep, s_local, d), jnp.float32)
    # The outputs vary over the sp axis (they depend on axis_index); the
    # constant initial carries must be marked varying too or scan rejects
    # the carry type under shard_map.
    m0, l0, acc0 = (jax.lax.pcast(x, axis_name, to="varying")
                    for x in (m0, l0, acc0))
    (m, l, acc, _k, _v), _ = jax.lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(n))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l).astype(q.dtype)  # [B, Hkv, rep, Sq_local, D]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s_local, hq, d)


# `ray_tpu.ops.ring_attention` names BOTH this submodule and the lazily
# re-exported function in the package namespace; importing this module
# rebinds the package attribute to the module object (import machinery
# setattr), which would turn `ray_tpu.ops.ring_attention(q, k, v)` into a
# TypeError depending on import order. Making the module itself callable
# keeps both access patterns working in every order.
import sys as _sys
import types as _types


class _CallableModule(_types.ModuleType):
    def __call__(self, *args, **kwargs):
        return ring_attention(*args, **kwargs)


_sys.modules[__name__].__class__ = _CallableModule
