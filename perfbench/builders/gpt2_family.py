"""Configuration file -> the program's model objects, for the families that
run through ``deepspeed_tpu.models.gpt2`` (``model_type`` gpt2 and opt).

The only place the benchmark names the program's model classes. A later PR
that adds a family adds a builder file and names it in its configuration's
``builder`` key.
"""


def sizes(cfg):
    """Published keys -> the sizes the benchmark's own arithmetic uses."""
    if cfg["model_type"] == "gpt2":
        d = cfg["n_embd"]
        out = dict(n_layer=cfg["n_layer"], n_head=cfg["n_head"], d_model=d,
                   d_ff=4 * d, max_seq_len=cfg["n_positions"])
    elif cfg["model_type"] == "opt":
        out = dict(n_layer=cfg["num_hidden_layers"],
                   n_head=cfg["num_attention_heads"],
                   d_model=cfg["hidden_size"], d_ff=cfg["ffn_dim"],
                   max_seq_len=cfg["max_position_embeddings"])
    else:
        raise ValueError(f"gpt2_family cannot build {cfg['model_type']!r}")
    out["vocab_size"] = cfg["vocab_size"]
    out["vocab_rows"] = cfg.get("padded_vocab_rows",
                                cfg["assumed"]["padded_vocab_rows"])
    out["activation"] = {"gelu_new": "gelu", "relu": "relu"}[
        cfg["activation_function"]]
    out["d_head"] = out["d_model"] // out["n_head"]
    out["n_kv_head"] = out["n_head"]
    return out


def model(cfg, **overrides):
    """The program's model for this configuration. ``overrides`` are the
    kernel-set pins of a job file (``GPT2Config`` field names); with none,
    every knob of the program keeps its default."""
    from deepspeed_tpu.models import GPT2, GPT2Config
    from deepspeed_tpu.models.opt import OPT, OPTConfig
    s = sizes(cfg)
    if s["d_ff"] != 4 * s["d_model"]:
        raise ValueError("models/gpt2.py fixes d_ff at 4 * d_model")
    cls, cfg_cls = {"gpt2": (GPT2, GPT2Config),
                    "opt": (OPT, OPTConfig)}[cfg["model_type"]]
    return cls(cfg_cls(
        n_layer=s["n_layer"], n_head=s["n_head"], d_model=s["d_model"],
        max_seq_len=s["max_seq_len"], vocab_size=s["vocab_rows"],
        dtype="bfloat16", **overrides))

