"""A layer with its expert layer on a shortcut (ScMoE; LongCat-Flash).

One published layer holds TWO halves of latent attention + dense SwiGLU and
ONE expert layer. The expert layer reads the first half's feed-forward input
and its output joins the residual stream only at the layer's end:

    a0 = h  + MLA_0(norm(h))       u0 = norm(a0)
    m  = MoE(u0)                   # the shortcut branch: read here ...
    b0 = a0 + SwiGLU_0(u0)
    a1 = b0 + MLA_1(norm(b0))      u1 = norm(a1)
    h' = a1 + SwiGLU_1(u1) + m     # ... joined here

Nothing consumes `m` for half a layer. In a deployment that is where the
exchange of expert parallelism hides (dispatch and combine run under the
second attention and feed-forward); on one chip it is freedom the compiler
may use to fetch expert weights under the dense half. The layer keeps two
latent cache leaves, `attn_0/latent` and `attn_1/latent`.
"""

from __future__ import annotations

import jax

from ray_tpu.models.layers import RMSNorm, SwiGLU
from ray_tpu.models.mla import MLA
from ray_tpu.models.moe import MoE


def shortcut_layer(cfg, x, positions, decode, kv_bound, live):
    """The layer's arithmetic, called inside `Block.__call__` (the modules
    made here are that Block's children). Their names are the one-half
    layer's with the half's number, so `param_specs` reaches every leaf by
    the rule it has."""
    norm = lambda name: RMSNorm(cfg.norm_eps, name=name)  # noqa: E731

    def attend(j, h):
        return MLA(cfg, name=f"attn_{j}")(
            norm(f"attn_norm_{j}")(h), positions, decode, kv_bound=kv_bound,
            live=live)

    x = x + attend(0, x)
    u = norm("mlp_norm_0")(x)
    with jax.named_scope("moe_shortcut"):
        m = MoE(cfg, name="moe")(u, serving=decode)
    x = x + SwiGLU(cfg, name="mlp_0")(u)
    x = x + attend(1, x)
    x = x + SwiGLU(cfg, name="mlp_1")(norm("mlp_norm_1")(x))
    with jax.named_scope("moe_shortcut"):
        return x + m
