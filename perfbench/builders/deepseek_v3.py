"""Configuration file -> the program's model object for ``model_type``
deepseek_v3 (``deepspeed_tpu.models.deepseek_v3``: trainable latent
attention, a sigmoid ``noaux_tc`` gate over routed experts of which this
chip holds a share, shared experts). Published keys keep their published
names in the configuration file; this is the one place they meet the
program's."""


def sizes(cfg):
    """Published keys -> the sizes the benchmark's own arithmetic uses.
    ``n_routed_experts`` is the experts HELD here; the router's width is
    ``published_n_routed_experts``. ``vocab_size`` is the slice's rows: the
    traffic draws its ids from it. ``max_seq_len`` is
    ``assumed.trained_positions``, the job's sequence length: rotary needs
    no table, so the published 32,768 positions cost nothing and bound
    nothing."""
    if cfg["model_type"] != "deepseek_v3":
        raise ValueError(
            f"builders/deepseek_v3 cannot build {cfg['model_type']!r}")
    dense = cfg["first_k_dense_replace"]
    return dict(
        n_layer=cfg["num_hidden_layers"], n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"],
        d_head=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        n_dense=dense, n_sparse=cfg["num_hidden_layers"] - dense,
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], moe_d_ff=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts"],
        n_experts_published=cfg["published_n_routed_experts"],
        n_shared_experts=cfg["n_shared_experts"],
        experts_offset=cfg["experts_offset"],
        top_k=cfg["num_experts_per_tok"],
        vocab_size=cfg["vocab_size"], vocab_rows=cfg["vocab_size"],
        activation=cfg["hidden_act"],
        max_seq_len=min(cfg["assumed"]["trained_positions"],
                        cfg["max_position_embeddings"]))


def model(cfg, **overrides):
    """The program's model for this configuration; ``overrides`` are the
    sizes a job file pins (``DeepseekV3Config`` field names), with none
    every knob keeps its default."""
    from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3,
                                                  DeepseekV3Config)

    s = sizes(cfg)
    if cfg["tie_word_embeddings"] or cfg["attention_bias"] \
            or cfg["hidden_act"] != "silu" or cfg["rope_scaling"] is not None \
            or cfg["scoring_func"] != "sigmoid" \
            or cfg["topk_method"] != "noaux_tc" \
            or not cfg["norm_topk_prob"] or cfg["moe_layer_freq"] != 1 \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or cfg["qk_head_dim"] != s["d_head"]:
        raise ValueError(
            "models/deepseek_v3.py has an untied head, no bias, a SwiGLU, "
            "plain rotary, the noaux_tc sigmoid gate renormalised, experts "
            "in every layer past the leading dense ones, and one latent a "
            "token for all heads")
    return DeepseekV3(DeepseekV3Config(**{**dict(
        vocab_size=s["vocab_rows"], max_seq_len=s["max_seq_len"],
        n_layer=s["n_layer"], first_k_dense=s["n_dense"],
        d_model=s["d_model"], n_head=s["n_head"],
        q_lora_rank=s["q_lora_rank"], kv_lora_rank=s["kv_lora_rank"],
        qk_nope_head_dim=s["qk_nope_head_dim"],
        qk_rope_head_dim=s["qk_rope_head_dim"], v_head_dim=s["v_head_dim"],
        d_ff=s["d_ff"], moe_d_ff=s["moe_d_ff"],
        n_routed_experts=s["n_experts_published"],
        n_shared_experts=s["n_shared_experts"], moe_top_k=s["top_k"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        experts_offset=s["experts_offset"], experts_held=s["n_experts"],
        rope_theta=float(cfg["rope_theta"]),
        rope_interleave=cfg["rope_interleave"],
        rms_eps=cfg["rms_norm_eps"], dtype="bfloat16"), **overrides}))


def blocks(model):
    """-> ``forward(params, ids (B, T)) -> (B, T, D)``: the program's own
    blocks one after the other with no ``jax.checkpoint`` round them, for
    a count that must see inside them (nothing is differentiated there; a
    value cannot leave a checkpointed block but through its results, and
    layers that are alike share one trace under it). The rotary tables are
    ``DeepseekV3.apply``'s, line for line: ``perfbench/tests/
    test_held_rows.py`` holds this equal to ``apply(return_hidden=True)``."""
    import jax.numpy as jnp
    cfg = model.config

    def forward(params, ids):
        B, T = ids.shape
        dr = cfg.qk_rope_head_dim
        f = cfg.rope_theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
        ang = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.float32)[None, :, None] * f,
            (B, T, dr // 2))
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x = params["wte"][ids]
        for p in params["layers"]:
            x = model._block(x, p, cos, sin)
        return x

    return forward


def traced_counters(model, s):
    """What of a traced step only this family's routing can say: the rows
    its router sends the held experts and the held experts that get one
    (``train_moe_experts_roofline``'s floor). ``runners/train.py`` asks any
    builder for this hook, once, in a traced run: ``warm(params, batch)``
    in set-up; ``count(params, batch)`` before the first traced step,
    ``keep(params, batch)`` before every later one, each with the
    parameters the step is about to use; ``counters(at) -> {counter:
    number}`` once the profiler has closed."""
    from pbench import mla_moe
    return mla_moe.HeldRows(blocks(model), s)
