"""HuggingFace checkpoint ingestion: safetensors/bin -> model param trees.

Counterpart of the reference's HF loaders — the v2 serving stack's
``HuggingFaceCheckpointEngine``
(/root/reference/deepspeed/inference/v2/checkpoint/huggingface_engine.py:16)
and the v1 sharded loader
(/root/reference/deepspeed/inference/engine.py:331
``load_model_with_checkpoint``). TPU-first differences: weights land as
numpy/jax arrays mapped into each family's FUNCTIONAL param tree (stacked
per-layer tensors under ``blocks``), not injected into torch modules; TP
sharding then falls out of ``model.partition_specs()`` + device_put — no
per-family policy classes are needed beyond the key mapping itself.

Entry points:
  read_hf_state_dict(model_dir)  -> {name: np.ndarray}
  load_pretrained(model_dir, ...) -> (model, params)   # dispatch on
                                                       # config model_type
  convert_<family>(hf_cfg, sd, dtype) -> (config, params)

Supported model_type values: gpt2, opt, llama, mistral, qwen2, phi,
falcon, mixtral, olmoe, bloom, gptj, gpt_neo, gpt_neox, internlm. Weights load
from *.safetensors (single or index-sharded) or pytorch_model.bin
(torch CPU).
"""

import json
import os

import numpy as np

__all__ = ["read_hf_state_dict", "read_hf_config", "load_pretrained",
           "CONVERTERS"]


# --------------------------------------------------------------------- I/O
def read_hf_config(model_dir):
    with open(os.path.join(model_dir, "config.json")) as f:
        return json.load(f)


def _load_safetensors(path):
    from safetensors.numpy import load_file
    try:
        return load_file(path)
    except Exception:
        # bf16 tensors round-trip through torch (numpy has no bf16)
        from safetensors.torch import load_file as tload
        return {k: _to_np(v) for k, v in tload(path).items()}


def _to_np(t):
    import torch
    if t.dtype == torch.bfloat16:
        # keep values exactly: bf16 -> fp32 numpy
        return t.to(torch.float32).numpy()
    return t.numpy()


def read_hf_state_dict(model_dir):
    """Read all weights under ``model_dir`` into {name: np.ndarray}."""
    idx = os.path.join(model_dir, "model.safetensors.index.json")
    single = os.path.join(model_dir, "model.safetensors")
    binf = os.path.join(model_dir, "pytorch_model.bin")
    sd = {}
    if os.path.exists(idx):
        with open(idx) as f:
            files = sorted(set(json.load(f)["weight_map"].values()))
        for fn in files:
            sd.update(_load_safetensors(os.path.join(model_dir, fn)))
    elif os.path.exists(single):
        sd.update(_load_safetensors(single))
    elif os.path.exists(binf):
        import torch
        raw = torch.load(binf, map_location="cpu", weights_only=True)
        sd.update({k: _to_np(v) for k, v in raw.items()})
    else:
        raise FileNotFoundError(
            f"no model.safetensors(.index.json) or pytorch_model.bin "
            f"under {model_dir}")
    return sd


def _stack(layers, key):
    return np.stack([l[key] for l in layers])


# --------------------------------------------------------- family converters
def convert_gpt2(hf, sd, dtype="bfloat16"):
    """HF gpt2 (Conv1D weights are stored (in, out) — no transpose)."""
    from ..models.gpt2 import GPT2Config
    pre = "transformer." if "transformer.wte.weight" in sd else ""
    L = hf["n_layer"]
    cfg = GPT2Config(vocab_size=hf["vocab_size"],
                     max_seq_len=hf["n_positions"], n_layer=L,
                     n_head=hf["n_head"], d_model=hf["n_embd"],
                     dtype=dtype)
    g = lambda k: sd[pre + k]
    layers = [{
        "ln1_scale": g(f"h.{i}.ln_1.weight"),
        "ln1_bias": g(f"h.{i}.ln_1.bias"),
        "wqkv": g(f"h.{i}.attn.c_attn.weight"),
        "bqkv": g(f"h.{i}.attn.c_attn.bias"),
        "wo": g(f"h.{i}.attn.c_proj.weight"),
        "bo": g(f"h.{i}.attn.c_proj.bias"),
        "ln2_scale": g(f"h.{i}.ln_2.weight"),
        "ln2_bias": g(f"h.{i}.ln_2.bias"),
        "wup": g(f"h.{i}.mlp.c_fc.weight"),
        "bup": g(f"h.{i}.mlp.c_fc.bias"),
        "wdown": g(f"h.{i}.mlp.c_proj.weight"),
        "bdown": g(f"h.{i}.mlp.c_proj.bias"),
    } for i in range(L)]
    params = {
        "wte": g("wte.weight"),
        "wpe": g("wpe.weight"),
        "lnf_scale": g("ln_f.weight"),
        "lnf_bias": g("ln_f.bias"),
        "blocks": {k: _stack(layers, k) for k in layers[0]},
    }
    return cfg, _model_cast(params, cfg, dtype)


def convert_opt(hf, sd, dtype="bfloat16"):
    """HF OPT: linear weights are (out, in) -> transpose; positions are
    offset by 2 padding rows (sliced off here, reference
    module_inject/containers/opt.py handles the same detail)."""
    from ..models.opt import OPTConfig
    if hf.get("word_embed_proj_dim", hf["hidden_size"]) != hf["hidden_size"] \
            or not hf.get("do_layer_norm_before", True):
        raise ValueError(
            "only standard pre-LN OPT variants are supported (opt-350m's "
            "word_embed_proj_dim / post-LN layout is not)")
    pre = "model.decoder." if "model.decoder.embed_tokens.weight" in sd \
        else "decoder."
    L = hf["num_hidden_layers"]
    D = hf["hidden_size"]
    cfg = OPTConfig(vocab_size=hf["vocab_size"],
                    max_seq_len=hf["max_position_embeddings"],
                    n_layer=L, n_head=hf["num_attention_heads"],
                    d_model=D, dtype=dtype)
    g = lambda k: sd[pre + k]

    def qkv(i):
        ws = [g(f"layers.{i}.self_attn.{m}_proj.weight").T
              for m in ("q", "k", "v")]
        bs = [g(f"layers.{i}.self_attn.{m}_proj.bias")
              for m in ("q", "k", "v")]
        return np.concatenate(ws, axis=1), np.concatenate(bs)

    layers = []
    for i in range(L):
        wqkv, bqkv = qkv(i)
        layers.append({
            "ln1_scale": g(f"layers.{i}.self_attn_layer_norm.weight"),
            "ln1_bias": g(f"layers.{i}.self_attn_layer_norm.bias"),
            "wqkv": wqkv, "bqkv": bqkv,
            "wo": g(f"layers.{i}.self_attn.out_proj.weight").T,
            "bo": g(f"layers.{i}.self_attn.out_proj.bias"),
            "ln2_scale": g(f"layers.{i}.final_layer_norm.weight"),
            "ln2_bias": g(f"layers.{i}.final_layer_norm.bias"),
            "wup": g(f"layers.{i}.fc1.weight").T,
            "bup": g(f"layers.{i}.fc1.bias"),
            "wdown": g(f"layers.{i}.fc2.weight").T,
            "bdown": g(f"layers.{i}.fc2.bias"),
        })
    params = {
        "wte": g("embed_tokens.weight"),
        "wpe": g("embed_positions.weight")[2:],   # drop the 2 pad slots
        "lnf_scale": g("final_layer_norm.weight"),
        "lnf_bias": g("final_layer_norm.bias"),
        "blocks": {k: _stack(layers, k) for k in layers[0]},
    }
    return cfg, _model_cast(params, cfg, dtype)


def _llama_like(hf, sd, cfg, dtype, *, pre="model.", qkv_bias=False,
                proj_bias=False, o_bias=False, gated=True, ln=False,
                fused_qkv=False,
                shared_ln=False, mlp_names=("gate_proj", "up_proj",
                                            "down_proj"),
                o_name="o_proj", moe=False, layer_prefix="layers",
                moe_names=("block_sparse_moe", "w1", "w3", "w2"),
                qk_norm=False):
    L = cfg.n_layer
    H, KVH, hd = cfg.n_head, cfg.n_kv_heads, cfg.d_head
    g = lambda k: sd[pre + k]

    def maybe(k):
        return sd.get(pre + k)

    layers = []
    for i in range(L):
        lp = f"{layer_prefix}.{i}."
        e = {}
        if fused_qkv:
            # falcon-style fused query_key_value with MQA tail: rows are
            # [q (H*hd), k (KVH*hd), v (KVH*hd)] in the (out, in) weight
            w = g(lp + "self_attention.query_key_value.weight").T
            e["wq"] = w[:, :H * hd]
            e["wk"] = w[:, H * hd:(H + KVH) * hd]
            e["wv"] = w[:, (H + KVH) * hd:]
            e["wo"] = g(lp + "self_attention.dense.weight").T
        else:
            e["wq"] = g(lp + "self_attn.q_proj.weight").T
            e["wk"] = g(lp + "self_attn.k_proj.weight").T
            e["wv"] = g(lp + "self_attn.v_proj.weight").T
            e["wo"] = g(lp + f"self_attn.{o_name}.weight").T
        if qkv_bias:
            e["bq"] = g(lp + "self_attn.q_proj.bias")
            e["bk"] = g(lp + "self_attn.k_proj.bias")
            e["bv"] = g(lp + "self_attn.v_proj.bias")
        if proj_bias or o_bias:
            e["bo"] = g(lp + f"self_attn.{o_name}.bias")
        if qk_norm:                           # olmoe: over the whole q, k
            e["q_norm"] = g(lp + "self_attn.q_norm.weight")
            e["k_norm"] = g(lp + "self_attn.k_norm.weight")
        if moe:
            E = cfg.num_experts
            block = moe_names[0]              # mixtral | olmoe ("mlp")
            e["moe_gate"] = g(lp + f"{block}.gate.weight").T
            for ours, theirs in zip(("moe_w1", "moe_w3", "moe_w2"),
                                    moe_names[1:]):
                e[ours] = np.stack([
                    g(lp + f"{block}.experts.{j}.{theirs}.weight").T
                    for j in range(E)])
        elif gated:
            gate_n, up_n, down_n = mlp_names
            e["wgate"] = g(lp + f"mlp.{gate_n}.weight").T
            e["wup"] = g(lp + f"mlp.{up_n}.weight").T
            e["wdown"] = g(lp + f"mlp.{down_n}.weight").T
        else:
            up_n, down_n = mlp_names
            e["wup"] = g(lp + f"mlp.{up_n}.weight").T
            e["wdown"] = g(lp + f"mlp.{down_n}.weight").T
            if proj_bias:
                e["bup"] = g(lp + f"mlp.{up_n}.bias")
                e["bdown"] = g(lp + f"mlp.{down_n}.bias")
        if ln:
            ln1 = "input_layernorm" if maybe(lp + "input_layernorm.weight") \
                is not None else "ln_attn"
            e["rms1"] = g(lp + f"{ln1}.weight")
            e["b1"] = g(lp + f"{ln1}.bias")
            if shared_ln:
                # falcon-7b/phi parallel block: ONE input LN feeds both
                # branches; the tree keeps both slots, tied at load
                e["rms2"], e["b2"] = e["rms1"], e["b1"]
            else:
                e["rms2"] = g(lp + "post_attention_layernorm.weight")
                e["b2"] = g(lp + "post_attention_layernorm.bias")
        else:
            e["rms1"] = g(lp + "input_layernorm.weight")
            e["rms2"] = g(lp + "post_attention_layernorm.weight")
        layers.append(e)

    params = {"blocks": {k: _stack(layers, k) for k in layers[0]}}
    return params, g, maybe


def convert_llama(hf, sd, dtype="bfloat16"):
    from ..models.llama import Llama, LlamaConfig
    window = hf.get("sliding_window") or 0
    if window >= hf["max_position_embeddings"]:
        window = 0                      # window never binds: full causal
    qkv_bias = bool(hf.get("attention_bias", False))
    cfg = LlamaConfig(
        qkv_bias=qkv_bias, sliding_window=window,
        vocab_size=hf["vocab_size"],
        max_seq_len=hf["max_position_embeddings"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads",
                          hf["num_attention_heads"]),
        d_model=hf["hidden_size"], d_ff=hf["intermediate_size"],
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_eps=hf.get("rms_norm_eps", 1e-5),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        dtype=dtype)
    params, g, maybe = _llama_like(hf, sd, cfg, dtype, qkv_bias=qkv_bias)
    params["wte"] = g("embed_tokens.weight")
    params["norm_f"] = g("norm.weight")
    if not cfg.tie_embeddings:
        params["lm_head"] = sd["lm_head.weight"]
    return cfg, _model_cast(params, cfg, dtype)


def convert_qwen2(hf, sd, dtype="bfloat16"):
    from ..models.qwen import QwenConfig
    cfg = QwenConfig(
        vocab_size=hf["vocab_size"],
        max_seq_len=hf["max_position_embeddings"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads",
                          hf["num_attention_heads"]),
        d_model=hf["hidden_size"], d_ff=hf["intermediate_size"],
        rope_theta=hf.get("rope_theta", 1000000.0),
        rms_eps=hf.get("rms_norm_eps", 1e-6),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        dtype=dtype)
    params, g, maybe = _llama_like(hf, sd, cfg, dtype, qkv_bias=True)
    params["wte"] = g("embed_tokens.weight")
    params["norm_f"] = g("norm.weight")
    if not cfg.tie_embeddings:
        params["lm_head"] = sd["lm_head.weight"]
    return cfg, _model_cast(params, cfg, dtype)


def convert_phi(hf, sd, dtype="bfloat16"):
    from ..models.phi import PhiConfig
    cfg = PhiConfig(
        vocab_size=hf["vocab_size"],
        max_seq_len=hf["max_position_embeddings"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads")
        or hf["num_attention_heads"],
        d_model=hf["hidden_size"], d_ff=hf["intermediate_size"],
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_eps=hf.get("layer_norm_eps", 1e-5),
        rotary_pct=hf.get("partial_rotary_factor", 0.4),
        dtype=dtype)
    params, g, maybe = _llama_like(
        hf, sd, cfg, dtype, qkv_bias=True, proj_bias=True, gated=False,
        ln=True, shared_ln=True, mlp_names=("fc1", "fc2"), o_name="dense")
    params["wte"] = g("embed_tokens.weight")
    params["norm_f"] = g("final_layernorm.weight")
    params["norm_f_b"] = g("final_layernorm.bias")
    params["lm_head"] = sd["lm_head.weight"]
    params["lm_head_b"] = sd["lm_head.bias"]
    return cfg, _model_cast(params, cfg, dtype)


def convert_falcon(hf, sd, dtype="bfloat16"):
    """All three HF falcon generations. The fused query_key_value weight
    has three row layouts (HF modeling_falcon.py ``_split_heads``):

      new_decoder_architecture (40b/180b/11b): grouped per KV head —
        rows reshape to (KVH, G+2, hd) with G = H // KVH queries then
        that group's k and v;
      old arch, multi_query (7b): flat [q (H*hd) | k (hd) | v (hd)];
      old arch, no multi_query (falcon-rw): per-head interleave (H, 3, hd).

    Norms likewise: new arch carries ln_attn + ln_mlp (one per parallel
    branch) unless num_ln_in_parallel_attn == 1; falcon-rw
    (parallel_attn=False) carries standard input/post_attention norms;
    7b shares one input LN between branches. Detected from the state
    dict so sub-variants (falcon2-11b single-LN) load correctly."""
    from ..models.falcon import FalconConfig
    n_head = hf["num_attention_heads"]
    H = n_head
    D = hf["hidden_size"]
    hd = D // H
    L = hf["num_hidden_layers"]
    new_arch = bool(hf.get("new_decoder_architecture", False))
    multi_query = bool(hf.get("multi_query", True))
    # mirror HF FalconConfig.num_kv_heads resolution exactly
    KVH = hf.get("num_kv_heads", n_head) if new_arch \
        else (1 if multi_query else n_head)
    parallel = bool(hf.get("parallel_attn", True))
    alibi = bool(hf.get("alibi", False))
    has_bias = bool(hf.get("bias", False))
    cfg = FalconConfig(
        vocab_size=hf["vocab_size"],
        max_seq_len=hf.get("max_position_embeddings", 2048),
        n_layer=L, n_head=n_head, n_kv_heads=KVH,
        d_model=D, d_ff=hf.get("ffn_hidden_size") or 4 * D,
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_eps=hf.get("layer_norm_epsilon", 1e-5),
        parallel_block=parallel, alibi=alibi, alibi_inv_norm=alibi,
        qkv_bias=has_bias, proj_bias=has_bias,
        tie_embeddings=True, dtype=dtype)
    pre = "transformer."
    g = lambda k: sd[pre + k]

    def split_qkv(w):
        """(D, fused) -> wq (D, H*hd), wk/wv (D, KVH*hd); also splits the
        fused bias when given a 1-D array (leading axis is the fused
        dim either way)."""
        lead = w.shape[:-1]                 # (D,) for weights, () for bias
        if new_arch:
            G = H // KVH
            t = w.reshape(*lead, KVH, G + 2, hd)
            q = t[..., :, :G, :].reshape(*lead, H * hd)
            k = t[..., :, G, :].reshape(*lead, KVH * hd)
            v = t[..., :, G + 1, :].reshape(*lead, KVH * hd)
        elif multi_query:
            q = w[..., :H * hd]
            k = w[..., H * hd:(H + 1) * hd]
            v = w[..., (H + 1) * hd:]
        else:
            t = w.reshape(*lead, H, 3, hd)
            q = t[..., :, 0, :].reshape(*lead, H * hd)
            k = t[..., :, 1, :].reshape(*lead, H * hd)
            v = t[..., :, 2, :].reshape(*lead, H * hd)
        return q, k, v

    layers = []
    for i in range(L):
        lp = f"h.{i}."
        wq, wk, wv = split_qkv(g(lp + "self_attention.query_key_value"
                                 ".weight").T)
        e = {"wq": wq, "wk": wk, "wv": wv,
             "wo": g(lp + "self_attention.dense.weight").T,
             "wup": g(lp + "mlp.dense_h_to_4h.weight").T,
             "wdown": g(lp + "mlp.dense_4h_to_h.weight").T}
        if has_bias:
            e["bq"], e["bk"], e["bv"] = split_qkv(
                g(lp + "self_attention.query_key_value.bias"))
            e["bo"] = g(lp + "self_attention.dense.bias")
            e["bup"] = g(lp + "mlp.dense_h_to_4h.bias")
            e["bdown"] = g(lp + "mlp.dense_4h_to_h.bias")
        if pre + lp + "ln_attn.weight" in sd:      # new arch, 2 norms
            e["rms1"] = g(lp + "ln_attn.weight")
            e["b1"] = g(lp + "ln_attn.bias")
            e["rms2"] = g(lp + "ln_mlp.weight")
            e["b2"] = g(lp + "ln_mlp.bias")
        else:
            e["rms1"] = g(lp + "input_layernorm.weight")
            e["b1"] = g(lp + "input_layernorm.bias")
            if pre + lp + "post_attention_layernorm.weight" in sd:
                e["rms2"] = g(lp + "post_attention_layernorm.weight")
                e["b2"] = g(lp + "post_attention_layernorm.bias")
            else:                                  # 7b: one shared LN
                e["rms2"], e["b2"] = e["rms1"], e["b1"]
        layers.append(e)

    params = {"blocks": {k: _stack(layers, k) for k in layers[0]}}
    params["wte"] = g("word_embeddings.weight")
    params["norm_f"] = g("ln_f.weight")
    params["norm_f_b"] = g("ln_f.bias")
    if has_bias:
        params["lm_head_b"] = np.zeros((hf["vocab_size"],), np.float32)
    return cfg, _model_cast(params, cfg, dtype)


def convert_gptj(hf, sd, dtype="bfloat16"):
    """HF gptj: separate unbiased q/k/v/out projections, biased
    fc_in/fc_out MLP, one shared input LN per layer (tied into both
    branch slots), biased untied lm_head, interleaved partial rotary
    (reference module_inject/containers/gptj.py)."""
    from ..models.gptj import GPTJConfig
    L = hf["n_layer"]
    D = hf["n_embd"]
    hd = D // hf["n_head"]
    cfg = GPTJConfig(
        vocab_size=hf["vocab_size"], max_seq_len=hf["n_positions"],
        n_layer=L, n_head=hf["n_head"], n_kv_heads=hf["n_head"],
        d_model=D, d_ff=hf.get("n_inner") or 4 * D,
        rms_eps=hf.get("layer_norm_epsilon", 1e-5),
        # HF configs may carry an explicit "rotary_dim": null — that
        # means full-head rotary, same as the key being absent (but an
        # explicit 0 stays 0: rotate nothing)
        rotary_pct=(hd if hf.get("rotary_dim") is None
                    else hf["rotary_dim"]) / hd,
        dtype=dtype)
    pre = "transformer."
    g = lambda k: sd[pre + k]
    layers = []
    for i in range(L):
        lp = f"h.{i}."
        e = {
            "wq": g(lp + "attn.q_proj.weight").T,
            "wk": g(lp + "attn.k_proj.weight").T,
            "wv": g(lp + "attn.v_proj.weight").T,
            "wo": g(lp + "attn.out_proj.weight").T,
            "wup": g(lp + "mlp.fc_in.weight").T,
            "bup": g(lp + "mlp.fc_in.bias"),
            "wdown": g(lp + "mlp.fc_out.weight").T,
            "bdown": g(lp + "mlp.fc_out.bias"),
            "rms1": g(lp + "ln_1.weight"),
            "b1": g(lp + "ln_1.bias"),
        }
        e["rms2"], e["b2"] = e["rms1"], e["b1"]  # shared-LN parallel block
        layers.append(e)
    params = {
        "blocks": {k: _stack(layers, k) for k in layers[0]},
        "wte": g("wte.weight"),
        "norm_f": g("ln_f.weight"),
        "norm_f_b": g("ln_f.bias"),
        "lm_head": sd["lm_head.weight"],
        "lm_head_b": sd["lm_head.bias"],
    }
    return cfg, _model_cast(params, cfg, dtype)


def convert_gpt_neo(hf, sd, dtype="bfloat16"):
    """HF gpt_neo: gpt2-family blocks with nn.Linear weights
    (transposed at load), NO qkv bias (zero rows in the fused bqkv), NO
    score scaling, and the attention_types global/local layer pattern
    (reference module_inject/containers/gptneo.py)."""
    from ..models.gpt_neo import GPTNeoConfig
    L = hf["num_layers"]
    D = hf["hidden_size"]
    inner = hf.get("intermediate_size") or 4 * D
    if inner != 4 * D:
        raise ValueError(
            f"gpt_neo intermediate_size {inner} != 4*hidden {4 * D}: the "
            f"GPT2 family derives d_ff as 4*d_model")
    # expand attention_types [[['global','local'], k], ...] -> per-layer
    # windows (0 = global)
    pattern = []
    for kinds, reps in hf.get("attention_types",
                              [[["global"], L]]):
        pattern.extend(kinds * reps)
    if len(pattern) != L:
        raise ValueError(f"attention_types expands to {len(pattern)} "
                         f"layers, config has {L}")
    win = hf.get("window_size", 256)
    windows = tuple(win if k == "local" else 0 for k in pattern)
    cfg = GPTNeoConfig(
        vocab_size=hf["vocab_size"],
        max_seq_len=hf["max_position_embeddings"], n_layer=L,
        n_head=hf["num_heads"], d_model=D,
        attn_layer_windows=() if not any(windows) else windows,
        dtype=dtype)
    pre = "transformer."
    g = lambda k: sd[pre + k]
    layers = []
    for i in range(L):
        lp = f"h.{i}."
        wq = g(lp + "attn.attention.q_proj.weight").T
        wk = g(lp + "attn.attention.k_proj.weight").T
        wv = g(lp + "attn.attention.v_proj.weight").T
        layers.append({
            "ln1_scale": g(lp + "ln_1.weight"),
            "ln1_bias": g(lp + "ln_1.bias"),
            "wqkv": np.concatenate([wq, wk, wv], axis=1),
            "bqkv": np.zeros((3 * D,), np.float32),
            "wo": g(lp + "attn.attention.out_proj.weight").T,
            "bo": g(lp + "attn.attention.out_proj.bias"),
            "ln2_scale": g(lp + "ln_2.weight"),
            "ln2_bias": g(lp + "ln_2.bias"),
            "wup": g(lp + "mlp.c_fc.weight").T,
            "bup": g(lp + "mlp.c_fc.bias"),
            "wdown": g(lp + "mlp.c_proj.weight").T,
            "bdown": g(lp + "mlp.c_proj.bias"),
        })
    params = {
        "wte": g("wte.weight"),
        "wpe": g("wpe.weight"),
        "lnf_scale": g("ln_f.weight"),
        "lnf_bias": g("ln_f.bias"),
        "blocks": {k: _stack(layers, k) for k in layers[0]},
    }
    return cfg, _model_cast(params, cfg, dtype)


def convert_gpt_neox(hf, sd, dtype="bfloat16"):
    """HF gpt_neox / pythia: fused query_key_value is INTERLEAVED per
    head ((H, 3, hd) rows, megatron layout — reference
    module_inject/containers/gptneox.py notes the same split), biases
    on qkv/dense/MLP, bias-free untied embed_out, use_parallel_residual
    with two independent branch norms."""
    from ..models.gpt_neox import GPTNeoXConfig
    L = hf["num_hidden_layers"]
    D = hf["hidden_size"]
    H = hf["num_attention_heads"]
    hd = D // H
    cfg = GPTNeoXConfig(
        vocab_size=hf["vocab_size"],
        max_seq_len=hf["max_position_embeddings"], n_layer=L,
        n_head=H, n_kv_heads=H, d_model=D,
        d_ff=hf.get("intermediate_size") or 4 * D,
        rope_theta=hf.get("rotary_emb_base", 10000.0),
        rms_eps=hf.get("layer_norm_eps", 1e-5),
        rotary_pct=hf.get("rotary_pct", 0.25),
        parallel_block=hf.get("use_parallel_residual", True),
        mlp_act={"gelu": "gelu", "gelu_new": "gelu_tanh",
                 "gelu_fast": "gelu_tanh"}.get(
            hf.get("hidden_act", "gelu"), "gelu"),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        dtype=dtype)
    pre = "gpt_neox."
    g = lambda k: sd[pre + k]

    def deinterleave(w):
        """(..., 3*D) fused qkv with per-head (H, 3, hd) layout ->
        q/k/v (..., D) each; works for the (D, 3D) weight (transposed
        from HF's (3D, D)) and the (3D,) bias alike."""
        lead = w.shape[:-1]
        t = w.reshape(*lead, H, 3, hd)
        return tuple(t[..., :, j, :].reshape(*lead, D) for j in range(3))

    layers = []
    for i in range(L):
        lp = f"layers.{i}."
        wq, wk, wv = deinterleave(
            g(lp + "attention.query_key_value.weight").T)
        bq, bk, bv = deinterleave(g(lp + "attention.query_key_value.bias"))
        layers.append({
            "wq": wq, "wk": wk, "wv": wv,
            "bq": bq, "bk": bk, "bv": bv,
            "wo": g(lp + "attention.dense.weight").T,
            "bo": g(lp + "attention.dense.bias"),
            "wup": g(lp + "mlp.dense_h_to_4h.weight").T,
            "bup": g(lp + "mlp.dense_h_to_4h.bias"),
            "wdown": g(lp + "mlp.dense_4h_to_h.weight").T,
            "bdown": g(lp + "mlp.dense_4h_to_h.bias"),
            "rms1": g(lp + "input_layernorm.weight"),
            "b1": g(lp + "input_layernorm.bias"),
            "rms2": g(lp + "post_attention_layernorm.weight"),
            "b2": g(lp + "post_attention_layernorm.bias"),
        })
    params = {
        "blocks": {k: _stack(layers, k) for k in layers[0]},
        "wte": g("embed_in.weight"),
        "norm_f": g("final_layer_norm.weight"),
        "norm_f_b": g("final_layer_norm.bias"),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = sd["embed_out.weight"]
    return cfg, _model_cast(params, cfg, dtype)


def convert_internlm(hf, sd, dtype="bfloat16"):
    """HF internlm (v1): the llama block with learned biases on the
    q/k/v AND output projections when config ``bias`` is true
    (reference module_inject/containers/internlm.py)."""
    from ..models.internlm import InternLMConfig
    has_bias = bool(hf.get("bias", True))
    cfg = InternLMConfig(
        vocab_size=hf["vocab_size"],
        max_seq_len=hf["max_position_embeddings"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads",
                          hf["num_attention_heads"]),
        d_model=hf["hidden_size"], d_ff=hf["intermediate_size"],
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_eps=hf.get("rms_norm_eps", 1e-6),
        qkv_bias=has_bias, o_bias=has_bias,
        tie_embeddings=hf.get("tie_word_embeddings", False),
        dtype=dtype)
    params, g, maybe = _llama_like(hf, sd, cfg, dtype, qkv_bias=has_bias,
                                   o_bias=has_bias)
    params["wte"] = g("embed_tokens.weight")
    params["norm_f"] = g("norm.weight")
    if not cfg.tie_embeddings:
        params["lm_head"] = sd["lm_head.weight"]
    return cfg, _model_cast(params, cfg, dtype)


def convert_mixtral(hf, sd, dtype="bfloat16"):
    from ..models.mixtral import MixtralConfig
    cfg = MixtralConfig(
        vocab_size=hf["vocab_size"],
        max_seq_len=hf["max_position_embeddings"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads",
                          hf["num_attention_heads"]),
        d_model=hf["hidden_size"], d_ff=hf["intermediate_size"],
        rope_theta=hf.get("rope_theta", 1000000.0),
        rms_eps=hf.get("rms_norm_eps", 1e-5),
        num_experts=hf["num_local_experts"],
        moe_top_k=hf.get("num_experts_per_tok", 2),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        dtype=dtype)
    params, g, maybe = _llama_like(hf, sd, cfg, dtype, moe=True)
    params["wte"] = g("embed_tokens.weight")
    params["norm_f"] = g("norm.weight")
    if not cfg.tie_embeddings:
        params["lm_head"] = sd["lm_head.weight"]
    # router stays fp32 (routing is precision-sensitive)
    return cfg, _model_cast(params, cfg, dtype,
                            fp32_keys=("moe_gate",))


def convert_olmoe(hf, sd, dtype="bfloat16"):
    """HF olmoe: mixtral's block with QK-norm over the whole q and k
    projections, un-renormalised top-k weights (``norm_topk_prob``) and
    the experts under ``mlp.experts.{j}.{gate,up,down}_proj``."""
    from ..models.olmoe import OLMoEConfig
    if hf.get("clip_qkv") is not None or hf.get("attention_bias"):
        raise ValueError("olmoe with clip_qkv or attention_bias is not "
                         "supported (the published 1B-7B has neither)")
    cfg = OLMoEConfig(
        vocab_size=hf["vocab_size"],
        max_seq_len=hf["max_position_embeddings"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads",
                          hf["num_attention_heads"]),
        d_model=hf["hidden_size"], d_ff=hf["intermediate_size"],
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_eps=hf.get("rms_norm_eps", 1e-5),
        num_experts=hf["num_experts"],
        moe_top_k=hf.get("num_experts_per_tok", 8),
        norm_topk_prob=hf.get("norm_topk_prob", False),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        dtype=dtype)
    params, g, _ = _llama_like(
        hf, sd, cfg, dtype, moe=True, qk_norm=True,
        moe_names=("mlp", "gate_proj", "up_proj", "down_proj"))
    params["wte"] = g("embed_tokens.weight")
    params["norm_f"] = g("norm.weight")
    if not cfg.tie_embeddings:
        params["lm_head"] = sd["lm_head.weight"]
    return cfg, _model_cast(params, cfg, dtype, fp32_keys=("moe_gate",))


def convert_bloom(hf, sd, dtype="bfloat16"):
    """HF bloom: fused query_key_value is INTERLEAVED per head — rows
    group as (H, 3, hd), unlike falcon's [q..., k, v] layout."""
    from ..models.bloom import BloomConfig
    H = hf["n_head"]
    D = hf["hidden_size"]
    hd = D // H
    L = hf["n_layer"]
    cfg = BloomConfig(
        vocab_size=hf["vocab_size"], max_seq_len=2048, n_layer=L,
        n_head=H, n_kv_heads=H, d_model=D, d_ff=4 * D,
        rms_eps=hf.get("layer_norm_epsilon", 1e-5), dtype=dtype)
    pre = "transformer." if "transformer.word_embeddings.weight" in sd \
        else ""
    g = lambda k: sd[pre + k]

    layers = []
    for i in range(L):
        lp = f"h.{i}."
        w = g(lp + "self_attention.query_key_value.weight").T  # (D, 3Hhd)
        b = g(lp + "self_attention.query_key_value.bias")
        w = w.reshape(D, H, 3, hd)
        b = b.reshape(H, 3, hd)
        layers.append({
            "rms1": g(lp + "input_layernorm.weight"),
            "b1": g(lp + "input_layernorm.bias"),
            "wq": w[:, :, 0].reshape(D, H * hd),
            "wk": w[:, :, 1].reshape(D, H * hd),
            "wv": w[:, :, 2].reshape(D, H * hd),
            "bq": b[:, 0].reshape(H * hd),
            "bk": b[:, 1].reshape(H * hd),
            "bv": b[:, 2].reshape(H * hd),
            "wo": g(lp + "self_attention.dense.weight").T,
            "bo": g(lp + "self_attention.dense.bias"),
            "rms2": g(lp + "post_attention_layernorm.weight"),
            "b2": g(lp + "post_attention_layernorm.bias"),
            "wup": g(lp + "mlp.dense_h_to_4h.weight").T,
            "bup": g(lp + "mlp.dense_h_to_4h.bias"),
            "wdown": g(lp + "mlp.dense_4h_to_h.weight").T,
            "bdown": g(lp + "mlp.dense_4h_to_h.bias"),
        })
    params = {
        "wte": g("word_embeddings.weight"),
        "embed_ln_s": g("word_embeddings_layernorm.weight"),
        "embed_ln_b": g("word_embeddings_layernorm.bias"),
        "norm_f": g("ln_f.weight"),
        "norm_f_b": g("ln_f.bias"),
        # bloom's tied head has no bias; proj_bias adds the slot
        "lm_head_b": np.zeros((hf["vocab_size"],), np.float32),
        "blocks": {k: _stack(layers, k) for k in layers[0]},
    }
    return cfg, _model_cast(params, cfg, dtype)


CONVERTERS = {
    "gpt2": convert_gpt2,
    "opt": convert_opt,
    "llama": convert_llama,
    "mistral": convert_llama,      # same weight tree; sliding_window is
                                   # converted and honored by all paths
    "qwen2": convert_qwen2,
    "phi": convert_phi,
    "falcon": convert_falcon,
    "mixtral": convert_mixtral,
    "olmoe": convert_olmoe,
    "bloom": convert_bloom,
    "gptj": convert_gptj,
    "gpt_neo": convert_gpt_neo,
    "gpt_neox": convert_gpt_neox,
    "internlm": convert_internlm,
}

_MODEL_CLASSES = {
    "gpt2": ("..models.gpt2", "GPT2"),
    "opt": ("..models.opt", "OPT"),
    "llama": ("..models.llama", "Llama"),
    "mistral": ("..models.llama", "Llama"),
    "qwen2": ("..models.qwen", "Qwen"),
    "phi": ("..models.phi", "Phi"),
    "falcon": ("..models.falcon", "Falcon"),
    "mixtral": ("..models.mixtral", "Mixtral"),
    "olmoe": ("..models.olmoe", "OLMoE"),
    "bloom": ("..models.bloom", "Bloom"),
    "gptj": ("..models.gptj", "GPTJ"),
    "gpt_neo": ("..models.gpt_neo", "GPTNeo"),
    "gpt_neox": ("..models.gpt_neox", "GPTNeoX"),
    "internlm": ("..models.internlm", "InternLM"),
}


def _model_cast(params, cfg, dtype, fp32_keys=()):
    """Cast the numpy tree to the model dtype ON HOST (fp32_keys stay
    f32). bf16 works as a host dtype via ml_dtypes. Returning host
    arrays — not committed jax arrays — is load-bearing: device
    placement is deferred to ``shard_params``/``device_put`` so
    ZeRO-Inference can quantize and TP serving can shard models whose
    full bf16 tree would not fit one chip (reference loads to torch CPU
    for the same reason, inference/engine.py:331)."""
    import jax.numpy as jnp
    dt = np.dtype(jnp.dtype(dtype))

    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        keep = any(k in fp32_keys for k in path)
        return np.asarray(tree).astype(np.float32 if keep else dt,
                                       copy=False)
    return walk(params)


def load_pretrained(model_dir, dtype="bfloat16"):
    """Load an HF checkpoint directory -> (model, params).

    The model is one of this repo's functional families; params are in
    the family's stacked-layer tree, cast to ``dtype``. Dispatches on
    config.json model_type.
    """
    import importlib
    hf = read_hf_config(model_dir)
    mt = hf.get("model_type")
    if mt not in CONVERTERS:
        raise ValueError(
            f"unsupported model_type {mt!r}; supported: "
            f"{sorted(CONVERTERS)}")
    sd = read_hf_state_dict(model_dir)
    cfg, params = CONVERTERS[mt](hf, sd, dtype=dtype)
    mod_name, cls_name = _MODEL_CLASSES[mt]
    mod = importlib.import_module(mod_name, package=__package__)
    return getattr(mod, cls_name)(cfg), params
