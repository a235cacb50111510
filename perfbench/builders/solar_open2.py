"""Configuration file -> the program's model object for ``model_type``
solar_open2 (``deepspeed_tpu.models.solar_open2``: delta-rule layers with a
gate a key channel and gated GQA layers with no positional encoding in one
stack, a sigmoid gate over routed experts of which this chip holds a share
beside a shared expert in every layer). Published keys keep their published
names in the configuration file; this is the one place they meet the
program's."""


def sizes(cfg):
    """Published keys -> the sizes the benchmark's own arithmetic uses.
    ``n_experts`` is the experts HELD here; the router's width is
    ``n_experts_published``. ``vocab_size`` is the slice's rows: the traffic
    draws its ids from it. ``max_seq_len`` is ``assumed.served_positions``:
    the model has no position table, so the published 1,048,576 positions
    cost nothing and bound nothing but the block tables' length and the
    reference's input, which the runner pads to this."""
    if cfg["model_type"] != "solar_open2":
        raise ValueError(
            f"builders/solar_open2 cannot build {cfg['model_type']!r}")
    L, gqa = cfg["num_hidden_layers"], cfg["gqa_layers"]
    kda = cfg["linear_attn_config"]
    return dict(
        n_layer=L, n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_model=cfg["hidden_size"], d_ff=cfg["moe_intermediate_size"],
        n_full=len(gqa), n_linear=L - len(gqa), n_sparse=L,
        linear_heads=kda["num_heads"], linear_dk=kda["head_dim"],
        linear_dv=kda["head_dim"],
        linear_conv=kda["short_conv_kernel_size"],
        # the decay is a vector over the key channels (kda_*): what
        # pbench/kda.py's count of the rule asks before it reads
        linear_gate="channel",
        moe_d_ff=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts"],
        n_experts_published=cfg["published_n_routed_experts"],
        experts_offset=cfg["experts_offset"],
        top_k=cfg["num_experts_per_tok"],
        vocab_size=cfg["vocab_size"], vocab_rows=cfg["vocab_size"],
        activation=cfg["assumed"]["hidden_act"],
        max_seq_len=min(cfg["assumed"]["served_positions"],
                        cfg["max_position_embeddings"]))


def model(cfg, **overrides):
    """The program's model for this configuration; with no ``overrides``
    (``SolarOpen2Config`` field names) every knob keeps its default."""
    from deepspeed_tpu.models.solar_open2 import (SolarOpen2,
                                                  SolarOpen2Config)

    s = sizes(cfg)
    if cfg["tie_word_embeddings"] or s["activation"] != "silu" \
            or cfg["use_rope"] \
            or (cfg["linear_attn_config"]["num_kv_heads"]
                or s["linear_heads"]) != s["linear_heads"] \
            or not cfg["use_gqa_gate"] or cfg["kda_use_full_proj"] \
            or not cfg["norm_topk_prob"] or cfg["first_k_dense_replace"] \
            or cfg["n_shared_experts"] != 1:
        raise ValueError(
            "models/solar_open2.py has an untied head, SwiGLU experts, no "
            "rotary, as many KDA key heads as value heads, an output gate "
            "on its GQA layers, low-rank KDA gates, a renormalised gate and "
            "experts beside one shared expert in every layer")
    return SolarOpen2(SolarOpen2Config(**{**dict(
        vocab_size=s["vocab_rows"], max_seq_len=s["max_seq_len"],
        n_layer=s["n_layer"], gqa_layers=tuple(cfg["gqa_layers"]),
        d_model=s["d_model"], n_head=s["n_head"],
        n_kv_heads=s["n_kv_head"], d_head=s["d_head"],
        linear_heads=s["linear_heads"], linear_dk=s["linear_dk"],
        linear_dv=s["linear_dv"], linear_conv=s["linear_conv"],
        gate_rank=cfg["assumed"]["kda_gate_rank"],
        allow_neg_eigval=cfg["kda_allow_neg_eigval"],
        moe_d_ff=s["moe_d_ff"], n_routed_experts=s["n_experts_published"],
        n_shared_experts=cfg["n_shared_experts"], moe_top_k=s["top_k"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        experts_offset=s["experts_offset"], experts_held=s["n_experts"],
        rms_eps=cfg["rms_norm_eps"], dtype="bfloat16"), **overrides}))
