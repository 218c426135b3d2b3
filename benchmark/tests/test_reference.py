"""The plain reference against the program's own forward pass, at tiny
size on the CPU (on the chip run.py does it at published widths)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
import reference
from seldon_tpu.models import transformer
from seldon_tpu.models.config import get_config
from seldon_tpu.models.quantize import init_params_int8

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAM = family.load(BENCH, {})  # the family of the two configurations


def file_keys(cfg):
    """A program preset under the key names a configuration file has."""
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "num_local_experts": cfg.n_experts,
            "num_experts_per_tok": cfg.n_experts_per_token}


@pytest.mark.parametrize("preset", ["tiny", "tiny-moe"])
def test_reference_agrees_with_the_program_at_tiny_size(preset):
    # float32 compute in the program too, so the tolerance is tight:
    # what is left is the order of summation.
    cfg = get_config(preset, weight_dtype="int8", dtype="float32")
    params = init_params_int8(cfg, jax.random.key(3))
    toks = jax.random.randint(jax.random.key(4), (40,), 0, cfg.vocab_size)
    want = FAM.forward_logits(params, toks, file_keys(cfg))
    with jax.default_matmul_precision("highest"):
        got = transformer.forward(params, toks[None], cfg)[0]
    assert got.shape == want.shape == (40, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


def test_logit_gaps_are_zero_for_the_references_own_argmax_and_positive_otherwise():
    cfg = get_config("tiny-moe", weight_dtype="int8")
    params, keys = init_params_int8(cfg, jax.random.key(0)), file_keys(cfg)
    prompt = [5, 9, 200, 31, 77]
    toks = []
    for _ in range(4):  # greedy by the reference itself
        seq = jnp.asarray(prompt + toks, jnp.int32)
        toks.append(int(jnp.argmax(FAM.forward_logits(params, seq, keys)[-1])))
    assert reference.logit_gaps(FAM, params, keys, [(prompt, toks)]) == ([0.0] * 4, [])
    wrong = [(t + 1) % cfg.vocab_size for t in toks]
    assert max(reference.logit_gaps(FAM, params, keys, [(prompt, wrong[:1])])[0]) > 0.0


def test_the_control_is_a_coarser_model_and_the_criterion_is_the_files():
    cfg = get_config("tiny", weight_dtype="int8")
    params, keys = init_params_int8(cfg, jax.random.key(0)), file_keys(cfg)
    seq = jnp.asarray([5, 9, 200, 31, 77, 3, 8], jnp.int32)
    fine = FAM.forward_logits(params, seq, keys)
    assert float(jnp.max(jnp.abs(FAM.forward_logits(params, seq, keys, control=False) - fine))) == 0.0
    assert float(jnp.max(jnp.abs(FAM.forward_logits(params, seq, keys, control=True) - fine))) > 1e-3
    assert FAM.CONTROL == "int4 grid"
    toks = [int(t) for t in jnp.argmax(fine[4:], axis=-1)]
    gaps, control = reference.logit_gaps(FAM, params, keys, [(list(seq[:5]), toks)], control=True)
    assert len(gaps) == len(control) == 3 and min(control) >= 0.0
    par = {"epsilon": 0.5, "min_share_within": 0.75, "epsilon_all": 2.0}
    assert reference.judge([0.0, 0.1, 0.4, 1.9], par) == (True, 0.75, 0)
    assert reference.judge([0.0, 0.1, 0.6, 0.7], par) == (False, 0.5, 0)    # too few within
    assert reference.judge([0.0, 0.1, 0.4, 2.1], par) == (False, 0.75, 1)   # one beyond all
    assert reference.judge([0.0, 0.6], {"epsilon": 0.5}) == (False, 0.5, 1)  # no share: all
    few = dict(par, min_share_within=0.5, max_over_epsilon_all=1)           # a stated few may
    assert reference.judge([0.0, 0.1, 0.4, 9.0], few) == (True, 0.75, 1)
    assert reference.judge([0.0, 0.1, 2.1, 9.0], few) == (False, 0.5, 2)
    assert reference.limits({"epsilon": 0.5}) == {
        "epsilon": 0.5, "min_share_within": 1.0, "epsilon_all": 0.5, "max_over_epsilon_all": 0}
