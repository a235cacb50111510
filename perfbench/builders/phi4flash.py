"""Configuration file -> the program's model object for ``model_type``
phi4flash (``deepspeed_tpu.models.phi4flash``: Mamba, windowed and full
differential attention, Gated Memory Units and cross-decoder layers in one
stack). Published keys keep their published names in the configuration
file; this is the one place they meet the program's."""


def sizes(cfg):
    """Published keys -> the sizes the benchmark's own arithmetic uses.
    ``max_seq_len`` is ``assumed.served_positions``: the model has no
    position table, so the published 262,144 positions cost nothing and
    bound nothing but the block tables' length and the reference's input,
    which the runner pads to this."""
    if cfg["model_type"] != "phi4flash":
        raise ValueError(
            f"builders/phi4flash cannot build {cfg['model_type']!r}")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    a = cfg["assumed"]
    return dict(
        n_layer=cfg["num_hidden_layers"], n_head=h,
        n_kv_head=cfg["num_key_value_heads"], d_head=d // h, d_model=d,
        d_ff=cfg["intermediate_size"], window=cfg["sliding_window"],
        ssm_state=a["ssm_state_size"], ssm_conv=a["ssm_conv_kernel"],
        ssm_expand=a["ssm_expand"], vocab_size=cfg["vocab_size"],
        vocab_rows=cfg["vocab_size"], activation=cfg["hidden_act"],
        max_seq_len=min(a["served_positions"],
                        cfg["max_position_embeddings"]))


def model(cfg, **overrides):
    """The program's model for this configuration; with no ``overrides``
    (``Phi4FlashConfig`` field names) every knob keeps its default."""
    from deepspeed_tpu.models.phi4flash import Phi4Flash, Phi4FlashConfig

    s = sizes(cfg)
    if not cfg["tie_word_embeddings"] or cfg["mlp_bias"] \
            or cfg["lm_head_bias"] or cfg["mb_per_layer"] != 2 \
            or cfg["hidden_act"] != "silu":
        raise ValueError(
            "models/phi4flash.py has a tied head without bias, a SwiGLU "
            "without bias, and a Mamba mixer every second layer")
    return Phi4Flash(Phi4FlashConfig(
        n_layer=s["n_layer"], n_head=s["n_head"], n_kv_heads=s["n_kv_head"],
        d_model=s["d_model"], d_ff=s["d_ff"], sliding_window=s["window"],
        ssm_state=s["ssm_state"], ssm_conv=s["ssm_conv"],
        ssm_expand=s["ssm_expand"], max_seq_len=s["max_seq_len"],
        vocab_size=s["vocab_rows"], ln_eps=cfg["layer_norm_eps"],
        dtype="bfloat16", **overrides))
