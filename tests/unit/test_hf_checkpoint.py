"""HF checkpoint ingestion parity: our converted models must reproduce
transformers' logits on the same weights.

Mirrors the reference's HF-model inference tests
(tests/unit/inference/test_inference.py model matrix) — but stronger:
instead of golden strings, exact logit parity vs the torch forward on a
randomly initialized model of each supported family, saved and reloaded
through the real safetensors path (no network; models are constructed
from config classes offline).
"""

import numpy as np
import pytest

import jax.numpy as jnp

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")

from deepspeed_tpu.checkpoint.hf import load_pretrained  # noqa: E402

# compile-heavy: excluded from the fast core set (pytest -m 'not slow')
pytestmark = pytest.mark.slow



def _roundtrip(tmp_path, hf_model, inputs, atol=2e-3):
    """Save hf_model, ingest via load_pretrained, compare logits fp32."""
    d = str(tmp_path / "model")
    hf_model.save_pretrained(d, safe_serialization=True)
    hf_model.eval()
    with torch.no_grad():
        ref = hf_model(torch.tensor(inputs)).logits.float().numpy()

    model, params = load_pretrained(d, dtype="float32")
    logits = np.asarray(model.apply(params, jnp.asarray(inputs)),
                        np.float32)
    np.testing.assert_allclose(logits, ref, atol=atol, rtol=1e-3)
    return model, params


@pytest.fixture
def inputs():
    rng = np.random.RandomState(0)
    return rng.randint(0, 200, (2, 24)).astype(np.int32)


class TestHFIngestion:
    def test_gpt2(self, tmp_path, inputs):
        cfg = transformers.GPT2Config(
            vocab_size=512, n_positions=64, n_embd=64, n_layer=2, n_head=4)
        _roundtrip(tmp_path, transformers.GPT2LMHeadModel(cfg), inputs)

    def test_opt(self, tmp_path, inputs):
        cfg = transformers.OPTConfig(
            vocab_size=512, hidden_size=64, num_hidden_layers=2,
            ffn_dim=256, num_attention_heads=4,
            max_position_embeddings=64, do_layer_norm_before=True,
            word_embed_proj_dim=64, activation_function="relu")
        _roundtrip(tmp_path, transformers.OPTForCausalLM(cfg), inputs)

    def test_llama(self, tmp_path, inputs):
        cfg = transformers.LlamaConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            attention_bias=False, tie_word_embeddings=False)
        _roundtrip(tmp_path, transformers.LlamaForCausalLM(cfg), inputs)

    def test_llama_attention_bias(self, tmp_path, inputs):
        cfg = transformers.LlamaConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            attention_bias=True, tie_word_embeddings=False)
        model = transformers.LlamaForCausalLM(cfg)
        # random (not zero) biases so dropping them would fail the parity
        with torch.no_grad():
            for layer in model.model.layers:
                for m in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                          layer.self_attn.v_proj):
                    m.bias.normal_(std=0.5)
        _roundtrip(tmp_path, model, inputs)

    def test_mistral_sliding_window_parity(self, tmp_path):
        # seq (48) > window (16): the window binds, HF masks beyond it —
        # our converted model must reproduce the windowed logits
        cfg = transformers.MistralConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            sliding_window=16, attn_implementation="eager")
        rng = np.random.RandomState(1)
        long_inputs = rng.randint(0, 500, (2, 48)).astype(np.int32)
        model, params = _roundtrip(
            tmp_path, transformers.MistralForCausalLM(cfg), long_inputs)
        assert model.config.sliding_window == 16

    def test_mistral_sliding_window_off(self, tmp_path, inputs):
        cfg = transformers.MistralConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            sliding_window=None)
        _roundtrip(tmp_path, transformers.MistralForCausalLM(cfg), inputs)

    def test_qwen2(self, tmp_path, inputs):
        cfg = transformers.Qwen2Config(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            tie_word_embeddings=False)
        _roundtrip(tmp_path, transformers.Qwen2ForCausalLM(cfg), inputs)

    def test_phi(self, tmp_path, inputs):
        cfg = transformers.PhiConfig(
            vocab_size=512, hidden_size=64, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=64,
            partial_rotary_factor=0.5, hidden_act="gelu_new")
        _roundtrip(tmp_path, transformers.PhiForCausalLM(cfg), inputs)

    def test_falcon(self, tmp_path, inputs):
        cfg = transformers.FalconConfig(
            vocab_size=512, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, multi_query=True,
            new_decoder_architecture=False, parallel_attn=True,
            bias=False, alibi=False, tie_word_embeddings=True)
        _roundtrip(tmp_path, transformers.FalconForCausalLM(cfg), inputs)

    def test_falcon_new_arch(self, tmp_path, inputs):
        # 40b/180b layout: grouped qkv de-interleave ((KVH, G+2, hd))
        # + separate ln_attn/ln_mlp per parallel branch
        cfg = transformers.FalconConfig(
            vocab_size=512, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_kv_heads=2, multi_query=False,
            new_decoder_architecture=True, parallel_attn=True,
            bias=False, alibi=False, tie_word_embeddings=True)
        model = transformers.FalconForCausalLM(cfg)
        # distinct branch norms so tying them would fail the parity
        with torch.no_grad():
            for layer in model.transformer.h:
                layer.ln_attn.weight.normal_(1.0, 0.3)
                layer.ln_mlp.weight.normal_(1.0, 0.3)
        _roundtrip(tmp_path, model, inputs)

    def test_falcon_rw(self, tmp_path, inputs):
        # falcon-rw layout: sequential block, per-head qkv interleave,
        # ALiBi, linear biases
        cfg = transformers.FalconConfig(
            vocab_size=512, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, multi_query=False,
            new_decoder_architecture=False, parallel_attn=False,
            bias=True, alibi=True, tie_word_embeddings=True)
        _roundtrip(tmp_path, transformers.FalconForCausalLM(cfg), inputs)

    def test_bloom_alibi(self, tmp_path, inputs):
        cfg = transformers.BloomConfig(
            vocab_size=512, hidden_size=64, n_layer=2, n_head=4)
        _roundtrip(tmp_path, transformers.BloomForCausalLM(cfg), inputs)

    def test_mixtral(self, tmp_path, inputs):
        cfg = transformers.MixtralConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            num_local_experts=4, num_experts_per_tok=2,
            tie_word_embeddings=False)
        _roundtrip(tmp_path, transformers.MixtralForCausalLM(cfg), inputs)

    def test_olmoe(self, tmp_path, inputs):
        # QK-norm over the whole projection, top-k weights as the softmax
        # gave them; the same logits through the benchmark's plain
        # reference, which is thereby held to HF's own implementation
        cfg = transformers.OlmoeConfig(
            vocab_size=512, hidden_size=64, intermediate_size=32,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=64,
            num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
            tie_word_embeddings=False)
        hf_model = transformers.OlmoeForCausalLM(cfg)
        with torch.no_grad():
            for layer in hf_model.model.layers:
                layer.self_attn.q_norm.weight.normal_(1.0, 0.3)
                layer.self_attn.k_norm.weight.normal_(1.0, 0.3)
        model, params = _roundtrip(tmp_path, hf_model, inputs)
        assert not model.config.norm_topk_prob and model.config.qk_norm
        import os
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), "perfbench"))
        from pbench import common as pb_common
        reference = pb_common.load_module("references", "olmoe")
        want = np.asarray(model.apply(params, jnp.asarray(inputs)))
        got = np.asarray(reference.logits(
            params, inputs, n_head=4, activation="silu", top_k=2))
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-3)

    def test_gptj(self, tmp_path, inputs):
        # shared-LN parallel block, interleaved (rotate_every_two)
        # partial rotary, biased fc/lm_head over plain q/k/v/out
        cfg = transformers.GPTJConfig(
            vocab_size=512, n_positions=64, n_embd=64, n_layer=2,
            n_head=4, rotary_dim=8, n_inner=None)
        _roundtrip(tmp_path, transformers.GPTJForCausalLM(cfg), inputs)

    def test_gpt_neo(self, tmp_path, inputs):
        # unscaled scores + alternating global/local attention layers
        # (seq 24 > window 8 so the local mask binds)
        cfg = transformers.GPTNeoConfig(
            vocab_size=512, max_position_embeddings=64, hidden_size=64,
            num_layers=2, num_heads=4, window_size=8,
            attention_types=[[["global", "local"], 1]])
        _roundtrip(tmp_path, transformers.GPTNeoForCausalLM(cfg), inputs)

    def test_gpt_neox(self, tmp_path, inputs):
        # per-head-interleaved fused qkv de-interleave, two-LN parallel
        # residual, biased blocks with a bias-free embed_out
        cfg = transformers.GPTNeoXConfig(
            vocab_size=512, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=256,
            max_position_embeddings=64, rotary_pct=0.5,
            use_parallel_residual=True)
        _roundtrip(tmp_path, transformers.GPTNeoXForCausalLM(cfg), inputs)

    def test_gpt_neox_sequential(self, tmp_path, inputs):
        # pythia-style use_parallel_residual=False loads as sequential
        cfg = transformers.GPTNeoXConfig(
            vocab_size=512, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=256,
            max_position_embeddings=64, rotary_pct=0.25,
            use_parallel_residual=False)
        _roundtrip(tmp_path, transformers.GPTNeoXForCausalLM(cfg), inputs)

    def test_internlm(self, tmp_path, inputs):
        # InternLM v1 = llama + biased q/k/v/o (its config says
        # bias: true). transformers has no offline InternLM class, so
        # build the equivalent HF llama (attention_bias biases exactly
        # q/k/v/o), save it, and rewrite the dir as an internlm
        # checkpoint: model_type + internlm config keys; weight names
        # are identical (model.layers.N.self_attn...)
        import json
        import os
        cfg = transformers.LlamaConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=64,
            attention_bias=True, tie_word_embeddings=False)
        model = transformers.LlamaForCausalLM(cfg)
        with torch.no_grad():
            for layer in model.model.layers:
                for m in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                          layer.self_attn.v_proj, layer.self_attn.o_proj):
                    m.bias.normal_(std=0.5)
        d = str(tmp_path / "model")
        model.save_pretrained(d, safe_serialization=True)
        model.eval()
        with torch.no_grad():
            ref = model(torch.tensor(inputs)).logits.float().numpy()
        with open(os.path.join(d, "config.json")) as f:
            c = json.load(f)
        c["model_type"] = "internlm"
        c["bias"] = True
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump(c, f)
        m, params = load_pretrained(d, dtype="float32")
        logits = np.asarray(m.apply(params, jnp.asarray(inputs)),
                            np.float32)
        np.testing.assert_allclose(logits, ref, atol=2e-3, rtol=1e-3)
        from deepspeed_tpu.models.internlm import InternLM
        assert isinstance(m, InternLM)

    def test_serve_real_weights_greedy_parity(self, tmp_path, inputs):
        # end to end: HF dir -> build_hf_engine (v2 paged serving) ->
        # greedy decode must reproduce transformers' greedy continuation
        from deepspeed_tpu.inference import build_hf_engine
        cfg = transformers.GPT2Config(
            vocab_size=512, n_positions=96, n_embd=64, n_layer=2, n_head=4)
        hf_model = transformers.GPT2LMHeadModel(cfg)
        d = str(tmp_path / "model")
        hf_model.save_pretrained(d, safe_serialization=True)
        hf_model.eval()

        prompt = inputs[:1, :16]
        with torch.no_grad():
            ref = hf_model.generate(
                torch.tensor(prompt), max_new_tokens=8, do_sample=False,
                pad_token_id=0)[0, 16:].numpy()

        eng = build_hf_engine(d, dtype="float32")
        rid = eng.put(prompt[0].tolist(), max_new_tokens=8,
                      temperature=0.0)
        while not eng.is_done(rid):
            eng.step()
        got = np.asarray(eng.get(rid))
        np.testing.assert_array_equal(got, ref)

    def test_save_16bit_model_roundtrip_gpt2(self, tmp_path, inputs):
        # train (ZeRO-2) -> save_16bit_model -> transformers loads the
        # exported dir -> logits match the engine's own forward
        # (reference engine.py:3625 save_16bit_model)
        import deepspeed_tpu
        from deepspeed_tpu.models import GPT2, GPT2Config
        from deepspeed_tpu.utils import groups
        groups.reset()
        cfg = GPT2Config(n_layer=2, n_head=4, d_model=64, max_seq_len=64,
                         vocab_size=512, remat=False, dtype="float32")
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2(cfg),
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "steps_per_print": 0,
                    "zero_optimization": {"stage": 2}})
        bsz = engine.config.train_batch_size
        batch = {"input_ids": np.tile(inputs[:1, :32], (bsz, 1))}
        for _ in range(2):
            engine.train_batch(batch)
        d = str(tmp_path / "export")
        engine.save_16bit_model(d, dtype="float32")
        ours = np.asarray(
            engine.model.apply(engine.state["params"],
                               jnp.asarray(inputs)), np.float32)
        hf = transformers.GPT2LMHeadModel.from_pretrained(d)
        hf.eval()
        with torch.no_grad():
            theirs = hf(torch.tensor(inputs)).logits.float().numpy()
        np.testing.assert_allclose(theirs, ours, atol=2e-3, rtol=1e-3)
        groups.reset()

    def test_export_llama_roundtrip(self, tmp_path, inputs):
        # init -> export_hf -> transformers load -> logit parity (the
        # inverse of convert_llama, GQA + untied head)
        import jax
        from deepspeed_tpu.checkpoint.hf_export import export_hf
        from deepspeed_tpu.models import Llama, LlamaConfig
        cfg = LlamaConfig(n_layer=2, n_head=4, n_kv_heads=2, d_model=64,
                          max_seq_len=64, vocab_size=512, remat=False,
                          dtype="float32")
        model = Llama(cfg)
        params = model.init(jax.random.key(3))
        d = str(tmp_path / "export")
        export_hf(model, params, d, dtype="float32")
        ours = np.asarray(model.apply(params, jnp.asarray(inputs)),
                          np.float32)
        hf = transformers.LlamaForCausalLM.from_pretrained(d)
        hf.eval()
        with torch.no_grad():
            theirs = hf(torch.tensor(inputs)).logits.float().numpy()
        np.testing.assert_allclose(theirs, ours, atol=2e-3, rtol=1e-3)
        # and back through our own loader (full circle)
        m2, p2 = load_pretrained(d, dtype="float32")
        again = np.asarray(m2.apply(p2, jnp.asarray(inputs)), np.float32)
        np.testing.assert_allclose(again, ours, atol=1e-4)

    def test_export_gpt_neox_roundtrip(self, tmp_path, inputs):
        # exercises the per-head qkv re-interleave inverse
        import jax
        from deepspeed_tpu.checkpoint.hf_export import export_hf
        from deepspeed_tpu.models import GPTNeoX, GPTNeoXConfig
        cfg = GPTNeoXConfig(n_layer=2, n_head=4, n_kv_heads=4, d_model=64,
                            max_seq_len=64, vocab_size=512, remat=False,
                            rotary_pct=0.5, dtype="float32")
        model = GPTNeoX(cfg)
        params = model.init(jax.random.key(4))
        # distinct non-zero biases so a broken qkv bias re-interleave
        # (e.g. concatenation instead of per-head interleave) fails
        r = np.random.RandomState(7)
        for k in ("bq", "bk", "bv", "bo", "bup", "bdown"):
            params["blocks"][k] = jnp.asarray(
                r.normal(0, 0.5, params["blocks"][k].shape), jnp.float32)
        d = str(tmp_path / "export")
        export_hf(model, params, d, dtype="float32")
        ours = np.asarray(model.apply(params, jnp.asarray(inputs)),
                          np.float32)
        hf = transformers.GPTNeoXForCausalLM.from_pretrained(d)
        hf.eval()
        with torch.no_grad():
            theirs = hf(torch.tensor(inputs)).logits.float().numpy()
        np.testing.assert_allclose(theirs, ours, atol=2e-3, rtol=1e-3)

    def test_unsupported_type_raises(self, tmp_path):
        import json
        import os
        d = tmp_path / "model"
        os.makedirs(d)
        with open(d / "config.json", "w") as f:
            json.dump({"model_type": "t5"}, f)
        with pytest.raises(ValueError, match="unsupported model_type"):
            load_pretrained(str(d))


class TestGPTJNullRotaryDim:
    """HF configs may carry an explicit ``"rotary_dim": null`` — that
    means full-head rotary (same as the key being absent), and must not
    crash the converter with a None / int division."""

    def _convert(self, hf_extra):
        from deepspeed_tpu.checkpoint.hf import convert_gptj
        L, D, H, V, T = 2, 64, 4, 128, 32
        F = 4 * D
        hf = dict({"n_layer": L, "n_embd": D, "n_head": H,
                   "vocab_size": V, "n_positions": T}, **hf_extra)
        r = np.random.RandomState(0)
        sd = {"transformer.wte.weight": r.randn(V, D).astype(np.float32),
              "transformer.ln_f.weight": np.ones(D, np.float32),
              "transformer.ln_f.bias": np.zeros(D, np.float32),
              "lm_head.weight": r.randn(V, D).astype(np.float32),
              "lm_head.bias": np.zeros(V, np.float32)}
        for i in range(L):
            lp = f"transformer.h.{i}."
            for nm in ("q_proj", "k_proj", "v_proj", "out_proj"):
                sd[lp + f"attn.{nm}.weight"] = \
                    r.randn(D, D).astype(np.float32)
            sd[lp + "mlp.fc_in.weight"] = r.randn(F, D).astype(np.float32)
            sd[lp + "mlp.fc_in.bias"] = np.zeros(F, np.float32)
            sd[lp + "mlp.fc_out.weight"] = r.randn(D, F).astype(np.float32)
            sd[lp + "mlp.fc_out.bias"] = np.zeros(D, np.float32)
            sd[lp + "ln_1.weight"] = np.ones(D, np.float32)
            sd[lp + "ln_1.bias"] = np.zeros(D, np.float32)
        return convert_gptj(hf, sd, dtype="float32")

    def test_null_rotary_dim_means_full_head(self):
        cfg_null, _ = self._convert({"rotary_dim": None})
        cfg_abs, _ = self._convert({})
        assert cfg_null.rotary_pct == 1.0
        assert cfg_abs.rotary_pct == 1.0

    def test_explicit_rotary_dim_still_partial(self):
        cfg, _ = self._convert({"rotary_dim": 8})
        assert cfg.rotary_pct == 8 / 16

    def test_zero_rotary_dim_means_no_rotary(self):
        cfg, _ = self._convert({"rotary_dim": 0})
        assert cfg.rotary_pct == 0.0
