"""Configuration file -> the program's model object for ``model_type``
olmo_hybrid (``deepspeed_tpu.models.olmo_hybrid``: gated delta-rule layers
and full-attention layers in one stack). Published keys keep their published
names in the configuration file; this is the one place they meet the
program's."""


def sizes(cfg):
    """Published keys -> the sizes the benchmark's own arithmetic uses.
    ``max_seq_len`` is ``assumed.served_positions``: the model has no
    position table, so the published 65,536 positions cost nothing and bound
    nothing but the block tables' length and the reference's input, which
    the runner pads to this."""
    if cfg["model_type"] != "olmo_hybrid":
        raise ValueError(
            f"builders/olmo_hybrid cannot build {cfg['model_type']!r}")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kinds = cfg["layer_types"]
    return dict(
        n_layer=cfg["num_hidden_layers"], n_head=h,
        n_kv_head=cfg["num_key_value_heads"], d_head=d // h, d_model=d,
        d_ff=cfg["intermediate_size"],
        n_linear=kinds.count("linear_attention"),
        n_full=kinds.count("full_attention"),
        linear_heads=cfg["linear_num_value_heads"],
        linear_dk=cfg["linear_key_head_dim"],
        linear_dv=cfg["linear_value_head_dim"],
        linear_conv=cfg["linear_conv_kernel_dim"],
        vocab_size=cfg["vocab_size"], vocab_rows=cfg["vocab_size"],
        activation=cfg["hidden_act"],
        max_seq_len=min(cfg["assumed"]["served_positions"],
                        cfg["max_position_embeddings"]))


def model(cfg, **overrides):
    """The program's model for this configuration; with no ``overrides``
    (``OlmoHybridConfig`` field names) every knob keeps its default."""
    from deepspeed_tpu.models.olmo_hybrid import (OlmoHybrid,
                                                  OlmoHybridConfig)

    s = sizes(cfg)
    if cfg["tie_word_embeddings"] or cfg["attention_bias"] \
            or cfg["hidden_act"] != "silu" \
            or cfg["rope_parameters"]["rope_theta"] is not None \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"] \
            or len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError(
            "models/olmo_hybrid.py has an untied head, no bias, a SwiGLU, "
            "no positional encoding, as many K/V heads as query heads in "
            "both kinds of layer, and one layer_types entry a layer")
    return OlmoHybrid(OlmoHybridConfig(
        layer_types=tuple(cfg["layer_types"]), n_head=s["n_head"],
        d_model=s["d_model"], d_ff=s["d_ff"],
        linear_heads=s["linear_heads"], linear_dk=s["linear_dk"],
        linear_dv=s["linear_dv"], linear_conv=s["linear_conv"],
        allow_neg_eigval=cfg["linear_allow_neg_eigval"],
        max_seq_len=s["max_seq_len"], vocab_size=s["vocab_rows"],
        rms_eps=cfg["rms_norm_eps"], dtype="bfloat16", **overrides))
