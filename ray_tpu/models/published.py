"""A served model's `TransformerConfig` from its published `config.json`:
the single place its shape is derived, so that `ContinuousEngine`,
`LLMEngine`, the pipeline's stages and the benchmark's references agree bit
for bit. It reads attributes of `cfg` (an `llm.LLMConfig`: the six sizes
that are RUN, `arch`, the share of the routed experts held) and knows which
published key spells which field, one arm a `model_type` (`_ARMS`). Every
key that bears on the arithmetic is either built or refused here; what no
key states is the published modelling code's, and the ISSUE an arm's
docstring names lists each such choice under `assumed`.
"""

import collections


def model_config(cfg):
    """LLMConfig -> TransformerConfig: without `cfg.arch` the Llama-style
    block of the six sizes, with it what the published keys say."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    sizes = dict(vocab_size=cfg.vocab_size, d_model=cfg.d_model,
                 n_layers=cfg.n_layers, n_heads=cfg.n_heads,
                 max_seq=cfg.max_seq, dtype=jnp.dtype(cfg.dtype))
    arch = cfg.arch
    if arch is None:
        if cfg.experts_held or cfg.first_expert:
            raise ValueError("experts_held / first_expert need an `arch` "
                             "with routed experts")
        return TransformerConfig(
            n_kv_heads=cfg.n_heads, d_ff=int(cfg.d_model * 8 / 3) // 8 * 8,
            **sizes)
    kind = arch.get("model_type")
    if kind not in _ARMS:
        raise ValueError(f"no model is built for model_type {kind!r}")
    return TransformerConfig(**_ARMS[kind](cfg, arch),
                             norm_eps=float(arch["rms_norm_eps"]), **sizes)


def _refuse_unbuilt(arch: dict, want: dict) -> None:
    """`want`: the keys whose one built value an arm takes for granted."""
    odd = {k: arch[k] for k, v in want.items() if arch.get(k, v) != v}
    if odd:
        raise ValueError(f"not built: {odd} (built: {want})")


def _latent(cfg, arch: dict) -> dict:
    """Latent attention's widths: every family that has it spells them so."""
    if arch.get("num_key_value_heads", cfg.n_heads) != cfg.n_heads:
        raise ValueError("latent attention has one latent for all heads: "
                         "num_key_value_heads must equal the heads")
    return dict(n_kv_heads=cfg.n_heads,
                q_lora_rank=int(arch["q_lora_rank"] or 0),
                kv_lora_rank=int(arch["kv_lora_rank"]),
                qk_nope_head_dim=int(arch["qk_nope_head_dim"]),
                qk_rope_head_dim=int(arch["qk_rope_head_dim"]),
                v_head_dim=int(arch["v_head_dim"]))


def _experts(cfg, published: int, **fields) -> dict:
    """The routed experts: `fields` as the family's keys spell them, this
    device's share of the `published`, and selection by score + a correction
    bias (`noaux_tc`'s `e_score_correction_bias`, afmoe's `expert_bias`)."""
    held = cfg.experts_held or published
    if not 0 <= cfg.first_expert <= published - held:
        raise ValueError(
            f"experts [{cfg.first_expert}, {cfg.first_expert + held}) are "
            f"not among the {published} published")
    return dict(moe_experts=published, moe_score_bias=True, experts_held=held,
                first_expert=cfg.first_expert, **fields)


def _deepseek_v3(cfg, arch: dict) -> dict:
    """The fields of a `model_type: kimi_k2` or `deepseek_v3` decoder beyond
    the six sizes: latent attention in every layer under YaRN-scaled rotary
    frequencies, leading dense layers, then sigmoid-routed experts beside
    shared ones (ISSUE 28)."""
    from ray_tpu.models.transformer import YarnScaling

    _refuse_unbuilt(arch, {
        "hidden_act": "silu", "attention_bias": False, "n_group": 1,
        "topk_group": 1, "topk_method": "noaux_tc", "moe_layer_freq": 1,
        "num_nextn_predict_layers": 0})
    return dict(
        _latent(cfg, arch), d_ff=int(arch["intermediate_size"]),
        rope_theta=float(arch["rope_theta"]),
        tie_embeddings=bool(arch["tie_word_embeddings"]),
        mixers=("mla",) * cfg.n_layers,
        rope_yarn=YarnScaling.from_config(arch.get("rope_scaling")),
        **_experts(cfg, int(arch["n_routed_experts"]),
                   moe_top_k=int(arch["num_experts_per_tok"]),
                   moe_d_ff=int(arch["moe_intermediate_size"]),
                   moe_scoring=arch["scoring_func"],
                   moe_norm_topk=bool(arch["norm_topk_prob"]),
                   moe_routed_scale=float(arch["routed_scaling_factor"]),
                   moe_shared_experts=int(arch["n_shared_experts"] or 0),
                   moe_first_layer=int(arch["first_k_dense_replace"])))


def _afmoe(cfg, arch: dict) -> dict:
    """The fields of a `model_type: afmoe` decoder (Arcee's Trinity family)
    beyond the six sizes: grouped-query attention with a published head
    size, window and full layers by `layer_types`, query/key norms, a
    sigmoid gate on the attention's output, rotary embedding on the window
    layers only, four norms a layer, the embedding scaled by sqrt(d) under
    `mup_enabled`, leading dense layers, then sigmoid-routed experts beside
    shared ones (ISSUE 32)."""
    _refuse_unbuilt(arch, {
        "hidden_act": "silu", "n_group": 1, "topk_group": 1,
        "num_expert_groups": 1, "num_limited_groups": 1,
        "rope_scaling": None, "attention_bias": False})
    kinds = list(arch["layer_types"])[:cfg.n_layers]
    if len(kinds) < cfg.n_layers or set(kinds) - {"sliding_attention",
                                                  "full_attention"}:
        raise ValueError(f"layer_types must name {cfg.n_layers} layers as "
                         f"sliding_attention or full_attention: {kinds}")
    kv_heads = int(arch["num_key_value_heads"])
    if cfg.n_heads % kv_heads:
        raise ValueError(f"{cfg.n_heads} heads do not share {kv_heads} "
                         f"key/value heads evenly")
    return dict(
        n_kv_heads=kv_heads, head_size=int(arch["head_dim"]),
        d_ff=int(arch["intermediate_size"]),
        rope_theta=float(arch["rope_theta"]),
        tie_embeddings=bool(arch["tie_word_embeddings"]),
        sliding_window=int(arch["sliding_window"]),
        window_layers=tuple(k == "sliding_attention" for k in kinds),
        rope_window_only=True, qk_norm=True, attn_gate=True,
        sandwich_norm=True,
        emb_scale=(float(cfg.d_model) ** 0.5 if arch.get("mup_enabled")
                   else 1.0),
        **_experts(cfg, int(arch["num_experts"]),
                   moe_top_k=int(arch["num_experts_per_tok"]),
                   moe_d_ff=int(arch["moe_intermediate_size"]),
                   moe_scoring=arch["score_func"],
                   moe_norm_topk=bool(arch["route_norm"]),
                   moe_routed_scale=float(arch["route_scale"]),
                   moe_shared_experts=int(arch["num_shared_experts"] or 0),
                   moe_first_layer=int(arch["num_dense_layers"])))


def _kimi_linear(cfg, arch: dict) -> dict:
    """The fields of a `model_type: kimi_linear` decoder (Moonshot's Kimi
    Linear) beyond the six sizes: gated delta-rule layers (`models/kda.py`)
    and latent-attention layers without a position (`mla_use_nope`), each
    named once by `linear_attn_config`'s two lists (counted from 1), leading
    dense layers, then sigmoid-routed experts beside shared ones (ISSUE
    34)."""
    _refuse_unbuilt(arch, {
        "hidden_act": "silu", "num_expert_group": 1, "topk_group": 1,
        "moe_layer_freq": 1, "num_nextn_predict_layers": 0,
        "rope_scaling": None})
    latent = _latent(cfg, arch)
    lin = arch["linear_attn_config"]
    kda, full = list(lin["kda_layers"]), list(lin["full_attn_layers"])
    named = collections.Counter(kda + full)
    if any(named[i] != 1 for i in range(1, cfg.n_layers + 1)):
        raise ValueError(
            f"kda_layers and full_attn_layers must name each of the layers "
            f"1..{cfg.n_layers} exactly once: {kda}, {full}")
    return dict(
        latent, d_ff=int(arch["intermediate_size"]),
        rope_theta=float(arch.get("rope_theta", 10000.0)),
        tie_embeddings=bool(arch["tie_word_embeddings"]),
        mixers=tuple("kda" if i in kda else "mla"
                     for i in range(1, cfg.n_layers + 1)),
        mla_rope=not arch["mla_use_nope"],
        kda_heads=int(lin["num_heads"]), kda_head_dim=int(lin["head_dim"]),
        kda_conv=int(lin["short_conv_kernel_size"]),
        **_experts(cfg, int(arch["num_experts"]),
                   moe_top_k=int(arch["num_experts_per_token"]),
                   moe_d_ff=int(arch["moe_intermediate_size"]),
                   moe_scoring=arch["moe_router_activation_func"],
                   moe_norm_topk=bool(arch["moe_renormalize"]),
                   moe_routed_scale=float(arch["routed_scaling_factor"]),
                   moe_shared_experts=int(arch["num_shared_experts"] or 0),
                   moe_first_layer=int(arch["first_k_dense_replace"])))


def _longcat_flash(cfg, arch: dict) -> dict:
    """The fields of a `model_type: longcat_flash` decoder (Meituan's
    LongCat-Flash) beyond the six sizes: every layer two latent attentions
    and two dense SwiGLUs with ONE expert layer on a shortcut across the
    second half (`models/scmoe.py`); a softmax router over the routed experts
    AND `zero_expert_num` identity experts, selection by score + correction
    bias, weights the scores times `routed_scaling_factor`, not renormalised;
    the two low-rank scale corrections of its latent attention; plain rotary
    frequencies (ISSUE 42)."""
    _refuse_unbuilt(arch, {
        "hidden_act": "silu", "attention_bias": False,
        "attention_method": "MLA", "zero_expert_type": "identity",
        "rope_scaling": None, "norm_topk_prob": False, "router_bias": False})
    latent = _latent(cfg, arch)

    def lora_scale(on, rank) -> float:
        """(hidden / rank)^0.5 where the model says so (and has the rank)."""
        return (cfg.d_model / rank) ** 0.5 if on and rank else 1.0

    return dict(
        latent, d_ff=int(arch["ffn_hidden_size"]),
        rope_theta=float(arch["rope_theta"]),
        tie_embeddings=bool(arch.get("tie_word_embeddings", False)),
        mixers=("mla",) * cfg.n_layers,
        mla_q_scale=lora_scale(arch.get("mla_scale_q_lora"),
                               latent["q_lora_rank"]),
        mla_kv_scale=lora_scale(arch.get("mla_scale_kv_lora"),
                                latent["kv_lora_rank"]),
        **_experts(cfg, int(arch["n_routed_experts"]),
                   moe_top_k=int(arch["moe_topk"]),
                   moe_d_ff=int(arch["expert_ffn_hidden_size"]),
                   moe_scoring="softmax", moe_norm_topk=False,
                   moe_routed_scale=float(arch["routed_scaling_factor"]),
                   moe_zero_experts=int(arch["zero_expert_num"]),
                   moe_shortcut=True))


def _evabyte(cfg, arch: dict) -> dict:
    """The fields of a `model_type: evabyte` decoder (EvaByte, a byte-level
    model) beyond the six sizes: EVA attention in every layer
    (`models/eva.py`: aligned windows of `window_size` positions seen
    exactly, one summary for every `chunk_size` positions behind them, one
    softmax), no grouping, norms whose weight is 1 + g, a float32 residual
    stream, an untied head of `num_pred_heads` x vocabulary columns of which
    the first head is the next byte (ISSUE 49). `mixedp_attn`, `lazy_init`,
    `init_fn`, `init_std` and `init_cutoff_factor` bear on training only and
    are not read."""
    _refuse_unbuilt(arch, {
        "attention_class": "eva", "num_chunks": None, "rope_scaling": None,
        "attention_bias": False, "hidden_act": "silu",
        "norm_add_unit_offset": True, "fp32_skip_add": True,
        "fp32_logits": True, "tie_word_embeddings": False})
    window, chunk = int(arch["window_size"]), int(arch["chunk_size"])
    if chunk < 1 or window % chunk:
        raise ValueError(f"not built: chunk_size {chunk} does not divide "
                         f"window_size {window}")
    if cfg.max_seq > window and cfg.max_seq % window:
        raise ValueError(f"not built: {cfg.max_seq} positions a slot are "
                         f"not whole windows of {window}")
    if arch.get("num_key_value_heads", cfg.n_heads) != cfg.n_heads:
        raise ValueError("not built: EVA's pooling vectors are one pair a "
                         "head: num_key_value_heads must equal the heads")
    return dict(
        n_kv_heads=cfg.n_heads, head_size=int(arch.get("head_dim") or 0),
        d_ff=int(arch["intermediate_size"]),
        rope_theta=float(arch["rope_theta"]), tie_embeddings=False,
        mixers=("eva",) * cfg.n_layers, eva_window=window, eva_chunk=chunk,
        eva_pool_std=float(arch.get("pool_init_std", 1.0)),
        norm_unit_offset=True, residual_f32=True,
        pred_heads=int(arch.get("num_pred_heads", 1)))


def _ouro(cfg, arch: dict) -> dict:
    """The fields of a `model_type: ouro` decoder (ByteDance's Ouro, a looped
    language model) beyond the six sizes: the WHOLE stack applied
    `total_ut_steps` times a token with one set of weights, the final norm
    inside that loop, an exit gate after it (`models/transformer.py`
    `ExitGate`); full attention in every layer at a published head size, no
    bias; four norms a layer (each sublayer's output is normed before it
    joins the stream); SwiGLU; an untied head (ISSUE 53). Leaving the loop
    early (`early_exit_threshold` under 1: slots of one batch that run
    different numbers of passes) is not built and refused. `max_window_layers`
    bears on nothing while no layer has a window and is not read."""
    _refuse_unbuilt(arch, {
        "early_exit_threshold": 1.0, "use_sliding_window": False,
        "sliding_window": None, "rope_scaling": None, "hidden_act": "silu",
        "attention_bias": False})
    steps = int(arch["total_ut_steps"])
    if steps < 1:
        raise ValueError(f"not built: total_ut_steps {steps} (at least 1)")
    kinds = list(arch["layer_types"])[:cfg.n_layers]
    if len(kinds) < cfg.n_layers or set(kinds) - {"full_attention"}:
        raise ValueError(f"not built: layer_types must name {cfg.n_layers} "
                         f"layers as full_attention: {kinds}")
    kv_heads = int(arch["num_key_value_heads"])
    if kv_heads < 1 or cfg.n_heads % kv_heads:
        raise ValueError(f"not built: {cfg.n_heads} heads do not share "
                         f"{kv_heads} key/value heads evenly")
    return dict(
        n_kv_heads=kv_heads, head_size=int(arch["head_dim"]),
        d_ff=int(arch["intermediate_size"]),
        rope_theta=float(arch["rope_theta"]),
        tie_embeddings=bool(arch["tie_word_embeddings"]),
        sandwich_norm=True, ut_steps=steps)


def _sdar_moe(cfg, arch: dict) -> dict:
    """The fields of a `model_type: sdar_moe` decoder (JetLM's SDAR, a
    block-diffusion language model) beyond the six sizes: a Qwen3-MoE layer
    (grouped-query attention at a published head size, ONE query and one key
    norm weight for all heads, rotary embedding, no bias; every layer's
    feed-forward a softmax router over all experts, the selected weights
    renormalised, no shared expert; an untied head) whose attention goes by
    BLOCKS of `block_length` positions (`models/transformer.py`
    `BlockAttention`), and the five values of its generation, which no key of
    the published `config.json` states and `arch` therefore has to
    (`Denoising`; ISSUE 58). `intermediate_size` is the width of a dense
    layer the model does not have (`mlp_only_layers` empty,
    `decoder_sparse_step` 1) and `max_window_layers` bears on nothing while
    no layer has a window: neither is read."""
    from ray_tpu.models.transformer import Denoising

    _refuse_unbuilt(arch, {
        "attention_bias": False, "rope_scaling": None,
        "use_sliding_window": False, "sliding_window": None,
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "norm_topk_prob": True, "tie_word_embeddings": False,
        "hidden_act": "silu"})
    kv_heads = int(arch["num_key_value_heads"])
    if kv_heads < 1 or cfg.n_heads % kv_heads:
        raise ValueError(f"not built: {cfg.n_heads} heads do not share "
                         f"{kv_heads} key/value heads evenly")
    length, steps = int(arch["block_length"]), int(arch["denoising_steps"])
    strategy = arch["remasking_strategy"]
    if strategy not in ("low_confidence_dynamic", "low_confidence_static"):
        raise ValueError(f"not built: remasking_strategy {strategy!r} (built: "
                         f"low_confidence_dynamic, low_confidence_static)")
    if length < 1 or not 1 <= steps <= length or cfg.max_seq % length:
        raise ValueError(
            f"not built: blocks of {length} positions in {steps} denoising "
            f"steps over {cfg.max_seq} positions a slot (a step frees at "
            f"least one position, and a slot holds whole blocks)")
    mask = int(arch["mask_token_id"])
    if not 0 <= mask < cfg.vocab_size:
        raise ValueError(f"not built: mask_token_id {mask} is not among the "
                         f"{cfg.vocab_size} tokens")
    experts = _experts(cfg, int(arch["num_experts"]),
                       moe_top_k=int(arch["num_experts_per_tok"]),
                       moe_d_ff=int(arch["moe_intermediate_size"]),
                       moe_scoring="softmax", moe_norm_topk=True)
    return dict(
        experts, moe_score_bias=False,  # selection by the scores alone
        n_kv_heads=kv_heads, head_size=int(arch["head_dim"]),
        d_ff=int(arch["intermediate_size"]),
        rope_theta=float(arch["rope_theta"]), tie_embeddings=False,
        qk_norm=True, block_length=length,
        denoising=Denoising(
            steps=steps, strategy=strategy,
            threshold=float(arch["confidence_threshold"]), mask_token=mask))


_ARMS = {"evabyte": _evabyte, "sdar_moe": _sdar_moe, "kimi_k2": _deepseek_v3, "deepseek_v3": _deepseek_v3,
         "afmoe": _afmoe, "kimi_linear": _kimi_linear,
         "longcat_flash": _longcat_flash, "ouro": _ouro}
