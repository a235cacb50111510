"""Configuration file -> the program's model objects for ``model_type``
olmoe (``deepspeed_tpu.models.olmoe``, a Mixtral-shaped block on
``models/llama.py``). Published keys keep their published names in the
configuration file; this is the one place they meet the program's."""


def sizes(cfg):
    """Published keys -> the sizes the benchmark's own arithmetic uses."""
    if cfg["model_type"] != "olmoe":
        raise ValueError(f"builders/olmoe cannot build {cfg['model_type']!r}")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        n_layer=cfg["num_hidden_layers"], n_head=h,
        n_kv_head=cfg["num_key_value_heads"], d_head=d // h, d_model=d,
        d_ff=cfg["intermediate_size"], n_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"], vocab_size=cfg["vocab_size"],
        vocab_rows=cfg.get("padded_vocab_rows",
                           cfg["assumed"]["padded_vocab_rows"]),
        activation=cfg["hidden_act"],
        max_seq_len=cfg["max_position_embeddings"])


def model(cfg, **overrides):
    """The program's model for this configuration; with no ``overrides``
    (``OLMoEConfig`` field names) every knob keeps its default.

    ``init`` of the object returned is the program's own
    ``init_served``: the SERVED tree (each layer's experts an array of its
    own), which is what the inference engines hold and what
    ``perfbench/aot.py`` therefore has to compile. aot.py takes its shapes
    from ``eval_shape(model.init)``, and the program's ``init`` gives the
    training tree (experts stacked for ``lax.scan``), which no serving cell
    ever holds. The engine itself never calls ``init`` for this family
    (``inference/utils.shard_params`` runs ``init_served`` too)."""
    from deepspeed_tpu.models.olmoe import OLMoE, OLMoEConfig

    s = sizes(cfg)
    if cfg["tie_word_embeddings"] or cfg["attention_bias"] \
            or cfg["clip_qkv"] is not None or cfg["rope_scaling"] is not None:
        raise ValueError("models/olmoe.py has an untied head, no attention "
                         "bias, no clip_qkv and plain rope")
    m = OLMoE(OLMoEConfig(
        n_layer=s["n_layer"], n_head=s["n_head"], n_kv_heads=s["n_kv_head"],
        d_model=s["d_model"], d_ff=s["d_ff"], num_experts=s["n_experts"],
        moe_top_k=s["top_k"], norm_topk_prob=cfg["norm_topk_prob"],
        max_seq_len=s["max_seq_len"], vocab_size=s["vocab_rows"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        dtype="bfloat16", **overrides))
    m.init = m.init_served
    return m
