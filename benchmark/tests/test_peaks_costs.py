import json
import os

import pytest

import costs
import family
import peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
fam = family.load(BENCH, {})  # the closed forms of the two configurations' family


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_mistral_arithmetic_matches_the_published_sizes():
    cfg = _cfg("mistral-7b-v0.3")
    params = cfg["num_hidden_layers"] * (fam.attn_params_per_layer(cfg) + fam.expert_params(cfg))
    assert params == pytest.approx(6.98e9, rel=0.01)  # 32 x 218 M
    assert fam.kv_bytes_per_token(cfg) == 2 * 32 * 8 * (128 + 2)  # int8 + a bf16 scale
    assert fam.weight_bytes(cfg) == pytest.approx(7.1e9, rel=0.02)


def test_mixtral_needs_two_experts_a_token_but_reads_what_it_routes_to():
    cfg = _cfg("mixtral-8x7b")
    per_layer = fam.attn_params_per_layer(cfg) + 2 * fam.expert_params(cfg)
    assert fam.flops_per_token(cfg) == 2.0 * (5 * per_layer + 4096 * 32000)
    assert fam.experts_touched(cfg, 1) == pytest.approx(2.0)
    assert 7.9 < fam.experts_touched(cfg, 64) <= 8.0
    assert fam.weight_bytes(cfg, 2.0) < fam.weight_bytes(cfg)
    assert fam.kv_bytes_per_token(cfg) == 2 * 5 * 8 * 128 * 2


def test_roofline_sides():
    cfg = _cfg("mistral-7b-v0.3")
    pk = peaks.peaks_for("TPU v5 lite")
    _, side = costs.least_seconds(*fam.decode_step_cost(cfg, 4, 400), pk)
    assert side == "memory"   # a few rows stream all the weights
    t, side = costs.least_seconds(*fam.decode_step_cost(cfg, 512, 400), pk)
    assert side == "compute" and t > 0.03  # hundreds of rows turn it compute-bound
