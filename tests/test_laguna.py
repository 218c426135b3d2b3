"""A stack whose attention layers are of two kinds in one pattern
(ModelConfig.layer_types of "sliding_attention" beside "full_attention":
a head count, a rotary table and a KV kind of its own each; the window
kind's KV a ring as long as the window), a per-head output gate, softmax
routing scaled by a factor beside a shared expert, and a prefill that
attends by blocks of keys, on the CPU at `tiny-laguna` size: against the
benchmark's plain reference (benchmark/families/laguna.py), through the
cache across the window's edge and two wraps of the ring, through the
engine, both kernels interpreted at the published head shapes, and what
counts the two kinds apart."""

import dataclasses
import functools
import importlib.util
import json
import logging
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_tpu.models import transformer as T
from seldon_tpu.models.config import ModelConfig, get_config
from seldon_tpu.ops import decode_attention as da
from seldon_tpu.ops import moe_dispatch
from seldon_tpu.ops import prefill_attention as pa
from seldon_tpu.servers import engine as engine_mod
from seldon_tpu.servers.engine import (
    EngineConfig,
    InferenceEngine,
    SamplingParams,
)
from tests.pallas_interpret import pallas_interpret

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FULL, SLIDING = "full_attention", "sliding_attention"
W = 8  # tiny-laguna's window


@functools.lru_cache(maxsize=None)
def _family():
    """benchmark/families/laguna.py, the family's file."""
    spec = importlib.util.spec_from_file_location(
        "family_laguna", os.path.join(ROOT, "benchmark", "families", "laguna.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fam():
    return _family()


def file_keys(cfg: ModelConfig) -> dict:
    """A program config under the key names a configuration file of the
    laguna family has."""
    return {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_attention_heads_per_layer": [cfg.heads(t) for t in cfg.layer_types],
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_seq_len,
        "rms_norm_eps": cfg.rms_norm_eps, "attention_bias": False,
        "tie_word_embeddings": False, "gating": True,
        "sliding_window": cfg.sliding_window,
        "num_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.n_experts_per_token,
        "moe_intermediate_size": cfg.d_ff_expert,
        "shared_expert_intermediate_size": cfg.d_ff_shared,
        "moe_routed_scaling_factor": cfg.router_scale,
        "moe_apply_router_weight_on_input": False,
        "layer_types": list(cfg.layer_types),
        "mlp_layer_types": ["dense"] * cfg.n_dense_layers
        + ["sparse"] * (cfg.n_layers - cfg.n_dense_layers),
        "rope_parameters": {
            FULL: {"rope_theta": cfg.rope_theta, "rope_type": "yarn",
                   "factor": cfg.rope_scaling_factor,
                   "original_max_position_embeddings":
                       cfg.rope_scaling_original_max_position,
                   "beta_fast": cfg.rope_scaling_beta_fast,
                   "beta_slow": cfg.rope_scaling_beta_slow,
                   "attention_factor": cfg.rope_attention_factor
                   or 0.1 * math.log(cfg.rope_scaling_factor) + 1.0,
                   "partial_rotary_factor": cfg.rotary_share},
            SLIDING: {"rope_type": "default", "rope_theta": cfg.rope_theta_window,
                      "partial_rotary_factor": 1}},
        "serving": {"weight_dtype": "bf16", "kv_cache_dtype": "bf16"},
    }


# -- the configuration ----------------------------------------------------------

def test_the_preset_is_off_every_easy_case():
    cfg = get_config("tiny-laguna")
    assert cfg.layer_types == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert (cfg.heads(FULL), cfg.heads(SLIDING), cfg.n_kv_heads) == (6, 8, 2)
    assert cfg.head_dim * cfg.n_heads != cfg.d_model  # 96 and 128 of 64
    assert (cfg.n_attn_layers, cfg.n_window_layers, cfg.n_sparse_layers) == (2, 3, 4)
    assert cfg.sliding_window == W < cfg.max_seq_len
    assert cfg.rotary_share == 0.5 and cfg.rope_scaling_type == "yarn"
    assert cfg.rope_theta != cfg.rope_theta_window
    assert cfg.attn_gate and cfg.router_scale == 2.5 and cfg.d_ff_shared
    assert not cfg.tie_embeddings


def test_layer_plan_keeps_the_kinds_apart_and_scans_the_window_layers_once():
    cfg = get_config("tiny-laguna")
    plan = T.layer_plan(cfg)
    assert [(s.kinds, s.reps) for s in plan] == [
        (((FULL, False),), 1), (((SLIDING, True),), 3), (((FULL, True),), 1)]
    assert [(s.attn_start, s.window_start) for s in plan] == [(0, 0), (1, 0), (1, 3)]
    # the published depth: layer 0, then a period of four, nine and three quarters times
    deep = dataclasses.replace(
        cfg, n_layers=9, layer_types=(FULL,) + (SLIDING,) * 3 + (FULL, SLIDING,
                                                                 SLIDING, SLIDING, FULL))
    assert [(s.reps, len(s.kinds)) for s in T.layer_plan(deep.validate())] == [(1, 1), (2, 4)]
    tree = T.init_params(cfg, jax.random.key(0))["segments"]
    assert tree[0][0]["wq"].shape == (1, 64, 6 * 16) and tree[0][0]["wa"].shape == (1, 64, 6)
    assert tree[1][0]["wq"].shape == (3, 64, 8 * 16) and tree[1][0]["wa"].shape == (3, 64, 8)
    assert tree[1][0]["wk"].shape == tree[0][0]["wk"].shape[:0] + (3, 64, 2 * 16)
    assert tree[1][0]["shared_up"].shape == (3, 64, 32)
    assert "router" not in tree[0][0] and tree[2][0]["router"].shape == (1, 64, 16)


def test_config_refuses_what_is_not_built_by_name():
    with pytest.raises(AssertionError, match="sliding_attention layers need sliding_window"):
        get_config("tiny-laguna", sliding_window=0)
    with pytest.raises(AssertionError, match="at least one full_attention layer"):
        get_config("tiny-laguna", layer_types=(SLIDING,) * 5)
    with pytest.raises(AssertionError, match="query heads that divide by n_kv_heads"):
        get_config("tiny-laguna", n_heads_window=7)
    with pytest.raises(AssertionError, match="qk_norm and key_mult are not built"):
        get_config("tiny-laguna", qk_norm=True)
    with pytest.raises(AssertionError, match="even number of rotated dims"):
        get_config("tiny-laguna", rotary_share=0.45)
    with pytest.raises(AssertionError, match="sliding_attention layers only"):
        get_config("tiny-lfm2", attn_gate=True)
    with pytest.raises(AssertionError, match="sliding_attention layers only"):
        get_config("tiny-lfm2", rotary_share=0.5)
    for field, value in (("sliding_window", 8), ("n_heads_window", 4), ("attn_gate", True),
                         ("rope_scaling_type", "yarn"), ("router_scale", 2.5)):
        with pytest.raises(AssertionError, match="need layer_types"):
            get_config("tiny", **{field: value})


# -- rotary by kind ---------------------------------------------------------------

def test_yarn_frequencies_and_the_half_rotation_against_values_by_hand(fam):
    """d = 8 rotated dims of a head of 16, theta 5e5, factor 8, original
    16, beta_fast 4, beta_slow 1:
    dim(r) = 8 ln(16 / (2 pi r)) / (2 ln 5e5) is -0.138 at r = 4 (floor
    -1, clamped to 0) and 0.285 at r = 1 (ceil 1): low 0, high 1, so
    ramp = (0, 1, 1, 1): pair 0 keeps its frequency 1, the other three
    are divided by 8. attention_factor 0.1 ln 8 + 1."""
    cfg = get_config("tiny-laguna")
    theta = 500000.0
    by_hand = [1.0, theta ** -0.25 / 8, theta ** -0.5 / 8, theta ** -0.75 / 8]
    inv, mscale = T.rope_by_kind(cfg, FULL)
    np.testing.assert_allclose(np.asarray(inv), by_hand, rtol=1e-6)
    assert mscale == pytest.approx(1.2079441541679836)
    assert fam.yarn_inv_freq(8, theta, 8.0, 16, 4.0, 1.0) == pytest.approx(by_hand, rel=1e-12)
    inv_w, one = T.rope_by_kind(cfg, SLIDING)
    np.testing.assert_allclose(np.asarray(inv_w), [10000.0 ** -(i / 8) for i in range(8)],
                               rtol=1e-6)
    assert one == 1.0
    # position 3 of one head: dims 0..3 pair with 4..7, dims 8..15 pass through
    x = jnp.arange(1.0, 17.0).reshape(1, 1, 1, 16)
    got = np.asarray(T.apply_rope(x, jnp.asarray([[3]]), inv, mscale))[0, 0, 0]
    for i, f in enumerate(by_hand):
        c, s = mscale * math.cos(3 * f), mscale * math.sin(3 * f)
        assert got[i] == pytest.approx((i + 1) * c - (i + 5) * s, rel=1e-5)
        assert got[i + 4] == pytest.approx((i + 5) * c + (i + 1) * s, rel=1e-5)
    np.testing.assert_array_equal(got[8:], np.arange(9.0, 17.0))
    # the published numbers: 64 rotated dims, ramp from pair 2 to pair 16
    pub = fam.yarn_inv_freq(64, 5e5, 64.0, 4096, 64.0, 1.0)
    assert pub[2] == pytest.approx(5e5 ** (-4 / 64)) and pub[16] == pytest.approx(
        5e5 ** (-32 / 64) / 64) and pub[1] == pytest.approx(5e5 ** (-2 / 64))
    assert 0.1 * math.log(64) + 1 == pytest.approx(1.4158883083359672)


# -- the cache by kind ------------------------------------------------------------

def test_cache_spec_holds_the_window_kind_as_a_ring_of_the_windows_length():
    from seldon_tpu.servers import cost_model as cm

    cfg = get_config("tiny-laguna")
    spec = T.cache_spec(cfg, 4, 64)
    assert set(spec) == {"k", "v", "kw", "vw"}
    assert spec["k"].shape == (2, 4, 1, 64, 32) and spec["k"].kind == "kv"
    assert spec["kw"].shape == spec["vw"].shape == (3, 4, 1, W, 32)
    assert spec["kw"].kind == "kv_window" and spec["kw"].time_axis is None
    assert T.cache_bytes(cfg, 4, 64) == {"kv": 2 * 2 * 4 * 64 * 32 * 2,
                                         "kv_window": 2 * 3 * 4 * W * 32 * 2}
    assert T.cache_bytes(cfg, 4, 64)["kv_window"] == T.cache_bytes(cfg, 4, 32)["kv_window"]
    # cost_model's closed forms are the spec, per kind
    assert cm.kv_bytes_per_token(cfg) * 4 * 64 == T.cache_bytes(cfg, 4, 64)["kv"]
    assert cm.window_bytes_per_slot(cfg) * 4 == T.cache_bytes(cfg, 4, 64)["kv_window"]
    assert cm.state_bytes_per_slot(cfg) == cm.window_bytes_per_slot(cfg)
    assert cm.attn_flops(cfg, 1, 20) == 4 * 16 * (6 * 20 * 2 + 8 * W * 3)
    assert cm.causal_attn_flops(cfg, 5) == 4 * 16 * 15 * (6 * 2 + 8 * 3)   # under the window
    assert cm.causal_attn_flops(cfg, 12) == 4 * 16 * (6 * 2 * 78 + 8 * 3 * (36 + 4 * 8))
    tree = T.init_params(cfg, jax.random.key(0))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    assert 0.95 * held < cm.weight_bytes(cfg) <= held  # routers are float32, norms noise
    # the published widths at 32 x 4096: 1.07 GB on the two full layers, 0.20 on the three rings
    with open(os.path.join(ROOT, "benchmark", "configs", "laguna-xs.2.json")) as f:
        raw = json.load(f)
    pub = ModelConfig(**_family().model_config_kwargs(raw)).validate()
    by_kind = T.cache_bytes(pub, 32, 4096)
    assert by_kind == {"kv": 2 * 2 * 32 * 4096 * 1024 * 2, "kv_window": 2 * 3 * 32 * 512 * 1024 * 2}
    assert T.cache_spec(pub, 32, 4096)["kw"].shape == (3, 32, 1, 512, 1024)


def test_ring_rows_hold_the_newest_position_of_each_residue():
    x = jnp.arange(20.0).reshape(1, 20, 1, 1) * jnp.ones((2, 1, 1, 1))
    ring = np.asarray(T._ring_rows(x, jnp.asarray([13, 5]), W))[:, 0, :, 0]
    np.testing.assert_array_equal(ring[0], [8, 9, 10, 11, 12, 5, 6, 7])
    np.testing.assert_array_equal(ring[1][:5], [0, 1, 2, 3, 4])  # rows 5.. not reached


# -- against the plain reference ------------------------------------------------

def _logits(fam, params, cfg, toks, **changed):
    keys = file_keys(cfg)
    for path, value in changed.items():
        at = keys
        *front, last = path.split("__")
        for k in front:
            at = at[k]
        at[last] = value
    return fam.forward_logits(params, toks, keys)


def test_program_equals_the_plain_reference_in_float32(fam):
    """37 positions, more than four windows: the blocked prefill's band,
    both rotary tables, unequal head counts, the gate, the scaled routed
    sum and the shared expert against the reference's own loops."""
    cfg = get_config("tiny-laguna", dtype="float32")
    params = T.init_params(cfg, jax.random.key(3))
    toks = jax.random.randint(jax.random.key(4), (37,), 0, cfg.vocab_size)
    want = _logits(fam, params, cfg, toks)
    with jax.default_matmul_precision("highest"):
        got = T.forward(params, toks[None], cfg)[0]
    assert got.shape == want.shape == (37, cfg.vocab_size)
    assert float(jnp.std(want)) > 0.5  # logits of the scale the other presets have
    # float32 sums in another order (blocks of keys, grouped experts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("what,changed", [
    ("the window one wider", {"sliding_window": W + 1}),
    ("the full kind's rotation over all 16 dims",
     {"rope_parameters__full_attention__partial_rotary_factor": 1.0}),
    ("no attention factor on cos and sin",
     {"rope_parameters__full_attention__attention_factor": 1.0}),
    ("the window kind's base on the full kind's table",
     {"rope_parameters__sliding_attention__rope_theta": 500000.0}),
    ("the routed sum not scaled", {"moe_routed_scaling_factor": 1.0}),
])
def test_the_comparison_sees_each_mechanism(fam, what, changed):
    """The reference with one mechanism altered no longer agrees with the
    program, by far more than the comparison's tolerance: a program that
    made the same mistake would fail the test above."""
    cfg = get_config("tiny-laguna", dtype="float32")
    params = T.init_params(cfg, jax.random.key(3))
    toks = jax.random.randint(jax.random.key(4), (37,), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = T.forward(params, toks[None], cfg)[0]
    off = _logits(fam, params, cfg, toks, **changed)
    assert float(jnp.max(jnp.abs(got - off))) > 0.02, what


def test_the_gate_and_the_shared_expert_are_neither_dead(fam):
    cfg = get_config("tiny-laguna", dtype="float32")
    params = T.init_params(cfg, jax.random.key(3))
    toks = jax.random.randint(jax.random.key(4), (21,), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        base = T.forward(params, toks[None], cfg)[0]
        for name in ("wa", "shared_down"):
            cut = {**params, "segments": tuple(
                tuple({k: (v * 0 if k == name else v) for k, v in lp.items()} for lp in seg)
                for seg in params["segments"])}
            # wa = 0 is a gate of one half on every head: not the gate
            assert float(jnp.max(jnp.abs(T.forward(cut, toks[None], cfg)[0] - base))) > 0.02, name


def _through_the_cache(params, cfg, seqs, plens, bucket=32, window_len=64):
    """Logits of each row at positions plen-1 .. len(seq)-1: ONE prefill of
    the rows' prompts (right-padded to the bucket) scattered into a slab of
    another length, then one decode step per further token, every row at
    its own position."""
    B, S = seqs.shape
    plens = jnp.asarray(plens)
    head = jnp.pad(seqs, ((0, 0), (0, max(0, bucket - S))))[:, :bucket]
    pad = jnp.where(jnp.arange(bucket)[None, :] < plens[:, None], head, 0)
    prefill = jax.jit(T.prefill, static_argnums=(4,))
    decode = jax.jit(T.decode_step, static_argnums=(4,))
    logits, sub = prefill(params, pad, plens, T.init_cache(cfg, B, bucket), cfg)
    cache = T.cache_scatter_slots(cfg, T.init_cache(cfg, B, window_len), sub,
                                  jnp.arange(B), bucket)
    out, pos = [[logits[b]] for b in range(B)], plens
    for _ in range(S - int(min(plens))):
        live = pos < S
        tok = seqs[jnp.arange(B), jnp.minimum(pos, S - 1)]
        logits, cache = decode(params, tok, jnp.minimum(pos, S - 1), cache, cfg)
        for b in range(B):
            if bool(live[b]):
                out[b].append(logits[b])
        pos = pos + live
    return [jnp.stack(o).astype(jnp.float32) for o in out]


@pytest.mark.parametrize("plen", [3, W - 1, W, W + 1, 20])
def test_prefill_then_decode_through_the_ring_equals_the_reference_in_float32(fam, plen):
    """Prompts under, at and over the window; decoding crosses pos = W - 1,
    W, W + 1 and runs past 3 W, so the ring wraps at least twice after a
    prefill that has itself wrapped (plen 20). Logits at every later
    position are the reference's, which has no cache and no ring."""
    cfg = get_config("tiny-laguna", dtype="float32")
    params = T.init_params(cfg, jax.random.key(1))
    seq = jax.random.randint(jax.random.key(5), (1, 30), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        (got,) = _through_the_cache(params, cfg, seq, [plen])
    want = _logits(fam, params, cfg, seq[0])[plen - 1:]
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4, rtol=3e-4)


def test_an_admission_group_of_mixed_lengths_leaves_each_row_its_own_ring(fam):
    """Four rows of one bucket, shorter than, as long as and longer than
    the window: each row's ring is cut at its OWN length, not at the
    bucket's, and decode then runs the rows side by side at four
    positions (one of them wrapping while another has not reached the
    window)."""
    cfg = get_config("tiny-laguna", dtype="float32")
    params = T.init_params(cfg, jax.random.key(2))
    seqs = jax.random.randint(jax.random.key(6), (4, 28), 0, cfg.vocab_size)
    plens = [2, W, 13, 27]
    with jax.default_matmul_precision("highest"):
        got = _through_the_cache(params, cfg, seqs, plens)
    for b, plen in enumerate(plens):
        want = _logits(fam, params, cfg, seqs[b])[plen - 1:]
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want),
                                   atol=3e-4, rtol=3e-4)


def test_bf16_through_the_ring_stays_near_the_reference_and_the_control_does_not(fam):
    """The served precision by the benchmark's own measure (the
    reference's logit gap between its best token and the program's greedy
    token); the control (matrices on the float8 e4m3 grid) has to fail the
    same limit. 0.25 of a logit spread of ~1: bf16 rounds a residual
    stream of 64 values to 3 digits and top-4 routing flips near ties."""
    cfg = get_config("tiny-laguna")
    gaps, control_gaps = [], []
    for seed in range(2):
        params = T.init_params(cfg, jax.random.key(seed))
        seq = jax.random.randint(jax.random.key(100 + seed), (1, 36), 0, cfg.vocab_size)
        plen = (5, 20)[seed]
        (got,) = _through_the_cache(params, cfg, seq, [plen])
        want = _logits(fam, params, cfg, seq[0])[plen - 1:]
        coarse = fam.forward_logits(params, seq[0], file_keys(cfg), control=True)[plen - 1:]
        top, at = jnp.max(want, axis=-1), jnp.arange(want.shape[0])
        gaps += [float(g) for g in top - want[at, jnp.argmax(got, axis=-1)]]
        control_gaps += [float(g) for g in top - want[at, jnp.argmax(coarse, axis=-1)]]
    within = sum(g <= 0.25 for g in gaps) / len(gaps)
    control_within = sum(g <= 0.25 for g in control_gaps) / len(control_gaps)
    assert within >= 0.9, (within, sorted(gaps)[-5:])
    assert control_within < within - 0.08, (control_within, within)


def test_the_softmax_router_carries_its_scale_and_at_one_multiplies_nothing():
    x = jax.random.normal(jax.random.key(0), (5, 16))
    w = jax.random.normal(jax.random.key(1), (16, 12))
    idx, plain = moe_dispatch.route(x, w, None, top_k=3, router="softmax")
    idx2, scaled = moe_dispatch.route(x, w, None, top_k=3, router="softmax", scale=2.5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx2))
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(plain), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(plain).sum(-1), 1.0, rtol=1e-6)
    p = jax.nn.softmax(x @ w, axis=-1)  # over all 12, renormalised over the chosen
    top = jnp.take_along_axis(p, idx, axis=-1)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(top / top.sum(-1, keepdims=True)),
                               rtol=1e-5)
    text = lambda **kw: jax.jit(lambda a, b: moe_dispatch.route(
        a, b, None, top_k=3, router="softmax", **kw)).lower(x, w).as_text()
    assert text() == text(scale=1.0) != text(scale=2.5)


# -- the two kernels, interpreted -------------------------------------------------

def _naive(q, k, v, Dh, window):
    G, S, HD = q.shape
    Hkv = k.shape[2] // Dh
    q5 = q.reshape(G, S, Hkv, HD // Dh // Hkv, Dh).astype(jnp.float32)
    k4, v4 = (t.reshape(G, S, Hkv, Dh).astype(jnp.float32) for t in (k, v))
    s = jnp.einsum("bskgd,btkd->bkgst", q5, k4) * Dh ** -0.5
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bkgst,btkd->bskgd", w, v4).reshape(G, S, HD)


@pytest.mark.parametrize("S,window,block,Gq", [
    (64, 0, 16, 6), (64, 24, 16, 8), (64, 16, 16, 6), (32, 40, 16, 2), (48, 7, 16, 3)],
    ids=["causal-6-heads-a-kv", "band-1.5-blocks-8-heads", "band-1-block", "band-over-S",
         "band-under-a-block"])
def test_prefill_attention_by_key_blocks_is_attention(S, window, block, Gq):
    """The Pallas kernel (interpreted) and the scan in XLA against scores
    held whole, at heads of 128 lanes: 6 and 8 query heads a KV head (the
    published 48 / 8 and 64 / 8), bands that end inside a block, span
    several, or are wider than the sequence; a row whose prompt ends
    inside the bucket is exact up to its length and zeros past its last
    block."""
    Dh, Hkv, G = 128, 2, 2
    ks = jax.random.split(jax.random.key(S + window), 3)
    q = jax.random.normal(ks[0], (G, S, Hkv * Gq * Dh), jnp.float32)
    k = jax.random.normal(ks[1], (G, S, Hkv * Dh), jnp.float32)
    v = jax.random.normal(ks[2], (G, S, Hkv * Dh), jnp.float32)
    want = _naive(q, k, v, Dh, window)
    scan = pa.blocked(q, k, v, head_dim=Dh, window=window, block=block)
    np.testing.assert_allclose(np.asarray(scan), np.asarray(want), atol=2e-5, rtol=2e-5)
    short = S // 2 + 3
    with pallas_interpret():
        got = pa.kernel(q, k, v, jnp.asarray([S, short], jnp.int32), head_dim=Dh,
                        window=window, block=block)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got[1, :short]), np.asarray(want[1, :short]),
                               atol=2e-5, rtol=2e-5)
    past = -(-short // block) * block
    assert past >= S or float(jnp.max(jnp.abs(got[1, past:]))) == 0.0
    assert pa.fits(4096, 128) and pa.fits(32, 128) and not pa.fits(4096, 64)
    assert pa._steps(4096, 256, 256, 512) == 3 and pa._steps(4096, 256, 256, 0) == 16
    assert not pa.applies(4096, 128)  # off a TPU the scan in XLA runs


@pytest.mark.parametrize("live", [(), (3,), (0, 1, 3, 4, 5, 7), tuple(range(8))],
                         ids=["none", "one", "several", "all"])
def test_the_decode_kernel_reads_a_ring_as_the_einsums_do(live):
    """ops/decode_attention over rings of 256 rows at 64 query heads on 8
    KV heads of 128: slots short of the ring, exactly at it, one past it
    and after many wraps. The kernel walks min(pos, ring) rows and leaves
    out row pos % ring; the einsums mask the same rows of the whole ring."""
    B, ring, Hkv, Dh, G = 8, 256, 8, 128, 8
    ks = jax.random.split(jax.random.key(11), 5)
    bf16 = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, 1, Hkv * G, Dh)).astype(bf16)
    kf = jax.random.normal(ks[1], (B, 1, Hkv, Dh)).astype(bf16)
    vf = (0.25 * jax.random.normal(ks[2], (B, 1, Hkv, Dh))).astype(bf16)
    cache = {"k": jax.random.normal(ks[3], (2, B, 1, ring, Hkv * Dh), bf16),
             "v": 0.25 * jax.random.normal(ks[4], (2, B, 1, ring, Hkv * Dh), bf16)}
    pos = jnp.asarray([0, 5, 255, 256, 257, 511, 512, 1000], jnp.int32)
    active = jnp.zeros((B,), bool).at[jnp.asarray(live, jnp.int32)].set(True)
    sched = da.schedule(active, pos, ring, 128, ring=True)
    assert int(da.tokens_read(sched)) == sum(
        -(-min(int(pos[b]), ring) // 128) * 128 for b in live)
    s_ = jnp.arange(ring)[None, None, :]
    mask = (s_ < pos[:, None, None]) & (s_ != (pos % ring)[:, None, None])
    want = T.gqa_attention_decode(q, cache["k"][1], cache["v"][1], kf, vf, mask)
    with pallas_interpret():
        got, *_ = da.attend(q, kf, vf, cache, jnp.asarray(1), sched)
    rows = np.asarray(live, np.int32)
    np.testing.assert_allclose(np.asarray(got, np.float32)[rows],
                               np.asarray(want, np.float32)[rows], atol=2e-2, rtol=2e-2)
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))


def test_decode_step_with_both_kernels_gives_the_einsums_logits(monkeypatch):
    """The whole step with the slab AND the rings read by the kernel
    (decode_attention.applies forced, interpreted), at head shapes the
    kernel reads (heads of 128, a ring of 128), against the step on the
    einsums: the work lists of the two kinds, the layer indices into each
    and the ring's skipped row."""
    cfg = get_config("tiny-laguna", head_dim=128, d_model=128, sliding_window=128,
                     max_seq_len=512, dtype="float32")  # bf16 would flip a top-4 near a tie
    params = T.init_params(cfg, jax.random.key(0))
    B = 4
    cache = jax.tree.map(
        lambda a: (0.3 * jax.random.normal(jax.random.key(a.size % 97), a.shape)).astype(a.dtype),
        T.init_cache(cfg, B, 256))
    pos = jnp.asarray([3, 128, 129, 250], jnp.int32)
    tok = jnp.asarray([5, 6, 7, 8], jnp.int32)
    live = jnp.asarray([True, True, False, True])
    want, cache_e = T.decode_step(params, tok, pos, cache, cfg, live=live)
    counts_e = T.decode_kv_counts(cfg, cache, live, pos)
    monkeypatch.setattr(da, "applies", da.reads)
    with pallas_interpret():
        got, cache_k = T.decode_step(params, tok, pos, cache, cfg, live=live)
        counts_k = T.decode_kv_counts(cfg, cache, live, pos)
    rows = np.asarray([0, 1, 3])
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want)[rows], atol=2e-3)
    for key in cache:  # the same rows written: the slab at pos, a ring at pos % 128
        np.testing.assert_allclose(np.asarray(cache_k[key], np.float32)[:, rows],
                                   np.asarray(cache_e[key], np.float32)[:, rows], atol=2e-3)
    np.testing.assert_array_equal(np.asarray(cache_e["kw"][:, 1, 0, 1:]),
                                  np.asarray(cache["kw"][:, 1, 0, 1:]))  # pos 128 -> row 0
    for key in ("k", "v", "kw", "vw"):  # the kernel writes no row of the slot that is not live
        np.testing.assert_array_equal(np.asarray(cache_k[key][:, 2]), np.asarray(cache[key][:, 2]))
        assert not np.array_equal(np.asarray(cache_e[key][:, 2]), np.asarray(cache[key][:, 2]))
    # [read, held, rows written, slots x layers] over both kinds, then window read / held /
    # unwindowed, full read / held
    assert list(np.asarray(counts_e)) == [2 * 4 * 256 + 3 * 4 * 128, 2 * 4 * 256 + 3 * 4 * 128,
                                          5 * 4, 5 * 4,
                                          3 * 4 * 128, 3 * 4 * 128, 3 * (3 + 128 + 250),
                                          2 * 4 * 256, 2 * 4 * 256]
    full = 2 * 3 * 256   # a slab of 256 float32 rows is one block a live slot
    ring = 3 * 3 * 128   # and a ring of 128: min(pos, 128) in whole blocks
    assert list(np.asarray(counts_k)) == [full + ring, counts_e[1], 5 * 3, 5 * 4, ring,
                                          counts_e[5], counts_e[6], full, counts_e[8]]


# -- through the engine -----------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = get_config("tiny-laguna", dtype="float32")
    params = T.init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, max_seq_len=64, prompt_buckets=(32,), decode_chunk=4,
        adaptive_chunk=False))
    eng.start()
    yield eng, params, cfg
    eng.stop()


def test_engine_admits_and_decodes_through_both_kinds_of_kv(served, fam, caplog):
    """Six requests over two slots: every slot is reused by a request of
    another length (a ring that has wrapped is handed to a prompt shorter
    than the window), groups pad unequal prompts to one bucket, and decode
    chunks of 4 steps cross pos = 8 inside one lax.scan (prompts of 5, 6
    and 7). Teacher-forced on each completion the plain reference ranks
    every token the engine chose first, to within float32's order of
    summation."""
    eng, params, cfg = served
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(2, cfg.vocab_size, size=n)) for n in (5, 6, 14, 20, 7, 30)]
    with caplog.at_level(logging.INFO, logger="seldon_tpu.access"):
        queues = [eng.submit(p, SamplingParams(max_new_tokens=12, temperature=0.0))
                  for p in prompts]
        for p, q in zip(prompts, queues):
            toks = []
            while (item := q.get(timeout=120)) is not None:
                assert "error" not in item, item
                toks += item["tokens"]
            assert 1 <= len(toks) <= 12
            logits = _logits(fam, params, cfg, jnp.asarray(p + toks[:-1], jnp.int32))[len(p) - 1:]
            gaps = jnp.max(logits, -1) - logits[jnp.arange(len(toks)), jnp.asarray(toks)]
            assert float(jnp.max(gaps)) < 1e-3, (toks, gaps)
    snap = eng.stats.snapshot()
    steps = snap["moe_sparse_layer_steps"] // cfg.n_sparse_layers
    assert steps > 0 and snap["moe_sparse_layer_steps"] % cfg.n_sparse_layers == 0
    # off a TPU the einsums read all they hold: two slabs of 64, three rings of 8
    assert snap["attn_full_tokens_held"] == snap["attn_full_tokens_read"] == steps * 2 * 2 * 64
    assert snap["attn_window_tokens_held"] == snap["attn_window_tokens_read"] == steps * 3 * 2 * W
    assert snap["attn_kv_tokens_held"] == snap["attn_full_tokens_held"] \
        + snap["attn_window_tokens_held"] == snap["attn_kv_tokens_read"]
    assert snap["attn_window_tokens_unwindowed"] > snap["attn_window_tokens_held"] // 4
    assert snap["attn_prefill_tokens"] == {32: sum(len(p) for p in prompts)}
    assert eng.cache_bytes() == T.cache_bytes(cfg, 2, 64)
    assert set(eng.cache_bytes()) == {"kv", "kv_window"}
    lines = [json.loads(r.getMessage().split(" ", 1)[1]) for r in caplog.records
             if r.getMessage().startswith("request ")]
    assert len(lines) == 6
    for name in engine_mod.KV_COUNTERS + engine_mod.WINDOW_COUNTERS + engine_mod.MOE_COUNTERS:
        assert isinstance(lines[-1][name], int), name
    assert "moe_assignments_held" not in lines[-1]
    assert set(lines[-1]["attn_prefill_tokens"]) == {"32"}
    assert engine_mod.chunk_counter_names(cfg) == (
        engine_mod.SAMPLER_COUNTERS + engine_mod.KV_COUNTERS + engine_mod.WINDOW_COUNTERS
        + engine_mod.MOE_COUNTERS)
    assert engine_mod.chunk_counter_names(get_config("tiny-nemotron")) == \
        engine_mod.CHUNK_COUNTERS
    assert engine_mod.chunk_counter_names(get_config("tiny")) == \
        engine_mod.SAMPLER_COUNTERS + engine_mod.KV_COUNTERS


def test_an_admission_group_is_bounded_by_its_tokens(monkeypatch):
    """max_admit rows of 1024 tokens: with a row's 1024 read as 16, four
    waiting prompts of the bucket of 32 are admitted two and two, those of
    the bucket of 16 four at once, as today."""
    from seldon_tpu.servers import shape_lattice

    monkeypatch.setattr(shape_lattice, "ADMIT_ROW_TOKENS", 16)
    spec = lambda sb: shape_lattice.admit_cap(4, 4, sb)
    assert (spec(16), spec(32), spec(64)) == (4, 2, 1)
    cfg = get_config("tiny-laguna", dtype="float32")
    params = T.init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, max_seq_len=64, prompt_buckets=(16, 32), max_admit=4,
        decode_chunk=4, adaptive_chunk=False))
    groups = []
    real = eng._dispatch_admit_group
    monkeypatch.setattr(eng, "_dispatch_admit_group",
                        lambda group, *key: groups.append((key[0], len(group)))
                        or real(group, *key))
    long, short = [3] * 20, [3] * 9
    queues = [eng.submit(p, SamplingParams(max_new_tokens=2, temperature=0.0))
              for p in (long, long, long, long)]
    eng.start()
    try:
        for q in queues:
            while q.get(timeout=120) is not None:
                pass
        assert groups == [(32, 2), (32, 2)]
        eng.stop()
        groups.clear()
    finally:
        eng.stop()
    eng2 = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, max_seq_len=64, prompt_buckets=(16, 32), max_admit=4,
        decode_chunk=4, adaptive_chunk=False))
    real2 = eng2._dispatch_admit_group
    monkeypatch.setattr(eng2, "_dispatch_admit_group",
                        lambda group, *key: groups.append((key[0], len(group)))
                        or real2(group, *key))
    queues = [eng2.submit(short, SamplingParams(max_new_tokens=2, temperature=0.0))
              for _ in range(4)]
    eng2.start()
    try:
        for q in queues:
            while q.get(timeout=120) is not None:
                pass
        assert groups == [(16, 4)]
    finally:
        eng2.stop()


@pytest.mark.parametrize("path,kw", [
    ("paged_kv", dict(paged_kv=True)),
    ("prefix_cache", dict(prefix_cache=True)),
    ("chunked_prefill", dict(chunked_prefill=True)),
    ("spec_decode", dict(spec_decode=True, paged_kv=True)),
    ("heal", dict(heal=True)),
    ("tp > 1", dict(tp=2)),
])
def test_the_opt_in_engine_paths_refuse_the_window_kind_by_name(path, kw):
    cfg = get_config("tiny-laguna")
    params = T.init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="the window kind of KV") as e:
        InferenceEngine(params, cfg, EngineConfig(
            max_slots=2, max_seq_len=64, prompt_buckets=(16, 32), **kw))
    assert path in str(e.value) and "sliding_attention layers' ring" in str(e.value)


@pytest.mark.parametrize("what,call", [
    ("training", lambda p, c, t: __import__(
        "seldon_tpu.models.train", fromlist=["loss_fn"]).loss_fn(p, t, jnp.ones_like(t), c)),
    ("paged decode", lambda p, c, t: T.paged_decode_step(
        p, t[:, 0], jnp.zeros((2,), jnp.int32), {}, jnp.zeros((2, 1), jnp.int32), c)),
    ("paged KV pool", lambda p, c, t: T.init_paged_cache(c, 4, 16)),
    ("tensor-parallel", lambda p, c, t: T.decode_step(
        p, t[:, 0], jnp.zeros((2,), jnp.int32), T.init_cache(c, 2, 8), c, tp=object())),
])
def test_the_model_functions_that_know_no_ring_refuse_it_by_name(what, call):
    cfg = get_config("tiny-laguna")
    params = T.init_params(cfg, jax.random.key(0))
    with pytest.raises(NotImplementedError, match="ring of keys and values") as e:
        call(params, cfg, jnp.ones((2, 4), jnp.int32))
    assert what in str(e.value)


def test_jaxserver_serves_the_preset_with_every_parameter_at_its_default(monkeypatch):
    """The unit as the benchmark's launcher starts it: a preset name and
    nothing else about the model. /metadata gives the kinds' fields and the
    window kind's cache at the window's length; /metrics and the HBM ledger
    count the two kinds apart."""
    from seldon_tpu.servers.jaxserver import JAXServer

    monkeypatch.setenv("HBM_LEDGER", "1")
    srv = JAXServer(preset="tiny-laguna", max_slots=2, max_seq_len=48)
    srv.load()
    try:
        out = srv.generate({"prompt": "abcdefghijk", "max_new_tokens": 9, "temperature": 0.0})
        assert out["completion_tokens"] >= 1
        md = json.loads(json.dumps(srv.init_metadata()))
        got = md["config"]
        want = _family().model_config_kwargs(file_keys(srv.cfg))
        assert {k: got[k] for k in want} == {**want, "rope_attention_factor": 0.0}
        assert (got["sliding_window"], got["n_heads_window"], got["attn_gate"]) == (W, 8, True)
        assert md["cache_bytes"] == T.cache_bytes(srv.cfg, 2, 48)
        assert md["cache_bytes"]["kv_window"] == 2 * 3 * 2 * W * 32 * 2  # not x 48
        gauges = {}
        for m in srv.metrics():
            gauges.setdefault(m["key"], []).append(m)
        one = lambda key: gauges[key][0]["value"]
        assert one("jaxserver_attn_window_tokens_held") > 0
        assert one("jaxserver_attn_kv_tokens_held") == one("jaxserver_attn_full_tokens_held") \
            + one("jaxserver_attn_window_tokens_held")
        assert one("jaxserver_attn_window_tokens_unwindowed") > 0
        assert [(m["tags"], m["value"]) for m in gauges["jaxserver_attn_prefill_tokens"]] == \
            [({"bucket": "32"}, 11.0)]  # the prompt's 11 bytes
        cats = srv.engine.debug_hbm()["categories"]
        assert cats["kv_window"]["bytes"] == md["cache_bytes"]["kv_window"]
        assert cats["kv_cache"]["bytes"] == md["cache_bytes"]["kv"]
    finally:
        srv.engine.stop()
    # a stack without such layers exports the counters at 0 and no bucket yet
    assert "attn_window_tokens_read" in engine_mod.EngineStats().snapshot()
