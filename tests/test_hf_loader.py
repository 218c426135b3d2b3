"""HF Llama checkpoint loader: LOGIT PARITY against transformers' own
forward pass on a randomly initialized tiny Llama — the strongest
possible check that weight mapping, transposes, RoPE convention, GQA
grouping, and norms all line up."""

import numpy as np
import pytest

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")


@pytest.fixture(scope="module")
def tiny_hf_checkpoint(tmp_path_factory):
    cfg = transformers.LlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=64,
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(cfg)
    model.eval()
    path = tmp_path_factory.mktemp("hf-llama")
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


def test_hf_config_mapping(tiny_hf_checkpoint):
    from seldon_tpu.servers.hf_loader import load_hf_checkpoint

    path, _ = tiny_hf_checkpoint
    params, cfg = load_hf_checkpoint(path, dtype="float32")
    assert cfg.n_layers == 3 and cfg.n_heads == 4 and cfg.n_kv_heads == 2
    assert params["blocks"]["wq"].shape == (3, 64, 64)
    assert params["blocks"]["wk"].shape == (3, 64, 32)  # GQA: 2 kv heads
    assert params["blocks"]["w_gate"].shape == (3, 64, 128)
    assert params["lm_head"].shape == (64, 128)


def test_hf_logit_parity(tiny_hf_checkpoint):
    import dataclasses

    import jax.numpy as jnp

    from seldon_tpu.models import forward
    from seldon_tpu.servers.hf_loader import load_hf_checkpoint

    path, model = tiny_hf_checkpoint
    params, cfg = load_hf_checkpoint(path, dtype="float32")
    cfg = dataclasses.replace(cfg, dtype="float32")

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 128, size=(2, 10))
    with torch.no_grad():
        hf_logits = model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(forward(params, jnp.asarray(tokens), cfg))
    # f32 end-to-end: tight tolerance proves the mapping is exact.
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)


def test_hf_decode_matches_teacher_forcing(tiny_hf_checkpoint):
    """Greedy cached decode on the loaded weights equals transformers'
    greedy generate — the full serving path on an HF checkpoint."""
    import dataclasses

    import jax.numpy as jnp

    from seldon_tpu.models import transformer
    from seldon_tpu.servers.hf_loader import load_hf_checkpoint

    path, model = tiny_hf_checkpoint
    params, cfg = load_hf_checkpoint(path, dtype="float32")
    cfg = dataclasses.replace(cfg, dtype="float32")

    prompt = [[5, 17, 99, 3]]
    with torch.no_grad():
        hf_out = model.generate(
            torch.tensor(prompt), max_new_tokens=6, do_sample=False,
            pad_token_id=0,
        ).numpy()[0, 4:].tolist()

    cache = transformer.init_cache(cfg, 1, 32)
    logits, cache = transformer.prefill(
        params, jnp.asarray(prompt, jnp.int32), jnp.array([4]), cache, cfg
    )
    toks = [int(jnp.argmax(logits[0]))]
    pos = jnp.array([4], jnp.int32)
    for _ in range(5):
        lg, cache = transformer.decode_step(
            params, jnp.array([toks[-1]], jnp.int32), pos, cache, cfg
        )
        toks.append(int(jnp.argmax(lg[0])))
        pos = pos + 1
    assert toks == hf_out, (toks, hf_out)


def test_rejects_non_llama(tmp_path):
    import json

    from seldon_tpu.servers.hf_loader import config_from_hf

    with pytest.raises(ValueError):
        config_from_hf({"model_type": "gpt2"})


def test_jaxserver_serves_hf_checkpoint(tiny_hf_checkpoint):
    """JAXServer end-to-end on an HF checkpoint directory: load -> engine
    -> generate."""
    from seldon_tpu.servers.jaxserver import JAXServer

    path, _ = tiny_hf_checkpoint
    srv = JAXServer(model_uri=path, max_slots=2, max_seq_len=48)
    srv.load()
    try:
        out = srv.generate({"prompt": "ab", "max_new_tokens": 4, "seed": 1})
        assert out["completion_tokens"] >= 1
        assert srv.cfg.n_layers == 3  # config came from config.json
    finally:
        srv.engine.stop()


# ---------------------------------------------------------------------------
# RoPE scaling (Llama-3.1/3.2 long-context checkpoints)
# ---------------------------------------------------------------------------


def test_rope_scaling_llama3_matches_transformers():
    """inv_freq parity with transformers' _compute_llama3_parameters —
    the formula long-context Llama-3.1+ checkpoints declare. Ignoring it
    produces subtly wrong logits at every position (ADVICE r2)."""
    from seldon_tpu.models import transformer
    from seldon_tpu.servers.hf_loader import config_from_hf

    hf = {
        "model_type": "llama",
        "vocab_size": 128,
        "hidden_size": 64,
        "intermediate_size": 128,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "max_position_embeddings": 131072,
        "rope_theta": 500000.0,
        "rope_scaling": {
            "rope_type": "llama3",
            "factor": 8.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 8192,
        },
    }
    cfg = config_from_hf(hf)
    assert cfg.rope_scaling_type == "llama3"
    ours = np.asarray(transformer.rope_frequencies(cfg))

    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

    hf_cfg = transformers.LlamaConfig(**hf)
    theirs, att = ROPE_INIT_FUNCTIONS["llama3"](hf_cfg, device="cpu")
    assert att == 1.0  # llama3 scheme has no attention scaling
    np.testing.assert_allclose(ours, theirs.numpy(), rtol=1e-6)
    # And the scaling actually bites: lowest frequency slowed ~8x.
    unscaled = 1.0 / (500000.0 ** (np.arange(8, dtype=np.float64) / 8))
    assert ours[-1] < unscaled[-1] / 4


def test_rope_scaling_linear_and_unknown():
    from seldon_tpu.models import transformer
    from seldon_tpu.models.config import get_config
    from seldon_tpu.servers.hf_loader import config_from_hf

    base = {
        "model_type": "llama", "vocab_size": 128, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "rope_theta": 10000.0,
    }
    lin = config_from_hf({**base, "rope_scaling": {"type": "linear", "factor": 4.0}})
    plain = config_from_hf(base)
    np.testing.assert_allclose(
        np.asarray(transformer.rope_frequencies(lin)),
        np.asarray(transformer.rope_frequencies(plain)) / 4.0,
        rtol=1e-6,
    )
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf(
            {**base, "rope_scaling": {"rope_type": "yarn", "factor": 2.0}}
        )
    # rope_type=default passes through unscaled.
    dflt = config_from_hf(
        {**base, "rope_scaling": {"rope_type": "default"}}
    )
    assert dflt.rope_scaling_type is None


# ---------------------------------------------------------------------------
# The LFM2 family (lfm2, lfm2_moe): the patterned tree
# ---------------------------------------------------------------------------


def test_lfm2_dense_sibling_logit_parity_with_transformers(tmp_path):
    """`Lfm2ForCausalLM` (the dense sibling the installed transformers
    carries) is an independent check of the short conv, the QK-norm
    attention, `embedding_norm` and the tied head: its own forward pass
    against ours on its own random weights, through the loader."""
    import dataclasses

    import jax.numpy as jnp

    from seldon_tpu.models import forward
    from seldon_tpu.servers.hf_loader import load_hf_checkpoint

    if not hasattr(transformers, "Lfm2ForCausalLM"):
        pytest.skip("this transformers has no Lfm2ForCausalLM")
    types = ["conv", "conv", "full_attention", "conv", "conv", "full_attention"]
    hf_cfg = transformers.Lfm2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=2,
        layer_types=types, block_auto_adjust_ff_dim=False,
        max_position_embeddings=64)
    torch.manual_seed(0)
    model = transformers.Lfm2ForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    params, cfg = load_hf_checkpoint(str(tmp_path), dtype="float32")
    assert cfg.layer_types == tuple(types) and cfg.qk_norm and cfg.tie_embeddings
    assert (cfg.d_ff, cfg.conv_kernel, cfg.n_experts) == (128, 3, 0)
    cfg = dataclasses.replace(cfg, dtype="float32")
    tokens = np.random.default_rng(0).integers(0, 128, size=(2, 12))
    with torch.no_grad():
        hf_logits = model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(forward(params, jnp.asarray(tokens), cfg))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)


def _save_lfm2_moe(path, params, cfg):
    """Our patterned tree under the published lfm2_moe tensor names."""
    import json

    from safetensors.numpy import save_file

    from seldon_tpu.models import transformer as T

    def t(a, transpose=False):
        a = np.asarray(a, np.float32)
        return np.ascontiguousarray(a.T if transpose else a)

    out = {"model.embed_tokens.weight": t(params["embed"]),
           "model.embedding_norm.weight": t(params["final_norm"])}
    for i in range(cfg.n_layers):
        lp, pre = T.layer_params(params, cfg, i), f"model.layers.{i}."
        out[pre + "operator_norm.weight"] = t(lp["op_norm"])
        out[pre + "ffn_norm.weight"] = t(lp["ff_norm"])
        if cfg.op_kind(i) == "conv":
            out[pre + "conv.in_proj.weight"] = t(lp["conv_in"], True)
            out[pre + "conv.out_proj.weight"] = t(lp["conv_out"], True)
            out[pre + "conv.conv.weight"] = t(lp["conv_w"], True)[:, None, :]
        else:
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
                                 ("wo", "out_proj")):
                out[pre + f"self_attn.{theirs}.weight"] = t(lp[ours], True)
            out[pre + "self_attn.q_layernorm.weight"] = t(lp["q_norm"])
            out[pre + "self_attn.k_layernorm.weight"] = t(lp["k_norm"])
        if cfg.ff_sparse(i):
            out[pre + "feed_forward.gate.weight"] = t(lp["router"], True)
            out[pre + "feed_forward.expert_bias"] = t(lp["router_bias"])
            for e in range(cfg.n_experts):
                for ours, theirs in (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2")):
                    out[pre + f"feed_forward.experts.{e}.{theirs}.weight"] = \
                        t(lp[ours][e], True)
        else:
            for ours, theirs in (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2")):
                out[pre + f"feed_forward.{theirs}.weight"] = t(lp[ours], True)
    save_file(out, str(path / "model.safetensors"))
    with open(path / "config.json", "w") as f:
        json.dump({
            "model_type": "lfm2_moe", "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "moe_intermediate_size": cfg.expert_width,
            "num_hidden_layers": cfg.n_layers, "layer_types": list(cfg.layer_types),
            "num_dense_layers": cfg.n_dense_layers,
            "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
            "max_position_embeddings": cfg.max_seq_len, "norm_eps": cfg.rms_norm_eps,
            "rope_parameters": {"rope_theta": cfg.rope_theta, "rope_type": "default"},
            "conv_L_cache": cfg.conv_kernel, "conv_bias": False,
            "num_experts": cfg.n_experts, "num_experts_per_tok": cfg.n_experts_per_token,
            "use_expert_bias": True, "norm_topk_prob": True,
            "routed_scaling_factor": 1.0, "eos_token_id": cfg.eos_token_id,
        }, f)


def test_lfm2_moe_checkpoint_maps_the_published_names_onto_the_patterned_tree(tmp_path):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from seldon_tpu.models import forward
    from seldon_tpu.models import transformer as T
    from seldon_tpu.models.config import get_config
    from seldon_tpu.servers.hf_loader import config_from_hf, load_hf_checkpoint

    cfg = get_config("tiny-lfm2", dtype="float32")
    params = T.init_params(cfg, jax.random.key(0))
    _save_lfm2_moe(tmp_path, params, cfg)
    loaded, got = load_hf_checkpoint(str(tmp_path), dtype="float32")
    assert dataclasses.replace(got, dtype="float32") == cfg  # every field, the pattern too
    same = jax.tree.map(lambda a, b: bool(jnp.all(a == b)), loaded, params)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    assert all(jax.tree.leaves(same))
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, size=(1, 9)))
    np.testing.assert_array_equal(np.asarray(forward(loaded, toks, cfg)),
                                  np.asarray(forward(params, toks, cfg)))
    # a layer without its experts is named, not served
    import os
    from safetensors.numpy import load_file, save_file
    part = load_file(str(tmp_path / "model.safetensors"))
    del part["model.layers.3.feed_forward.experts.2.w3.weight"]
    save_file(part, str(tmp_path / "model.safetensors"))
    with pytest.raises(ValueError, match="layer 3: w_up has experts"):
        load_hf_checkpoint(str(tmp_path), dtype="float32")
    with pytest.raises(ValueError, match="conv_bias"):
        config_from_hf({"model_type": "lfm2", "conv_bias": True})
    assert os.path.exists(tmp_path / "config.json")


def test_lfm2_dense_width_follows_the_model_codes_adjustment():
    from seldon_tpu.servers.hf_loader import config_from_hf

    base = {"model_type": "lfm2", "vocab_size": 65536, "hidden_size": 2560,
            "intermediate_size": 12288, "num_hidden_layers": 4,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "full_attn_idxs": [2]}
    cfg = config_from_hf(base)  # Lfm2MLP: int(2 * 12288 / 3) rounded up to 256
    assert cfg.d_ff == 8192
    assert cfg.layer_types == ("conv", "conv", "full_attention", "conv")
    assert config_from_hf(dict(base, block_auto_adjust_ff_dim=False)).d_ff == 12288
