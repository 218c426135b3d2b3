"""A stack of single-block layers (ModelConfig.layer_types of "mamba" /
"attention" / "moe": a Mamba-2 mixer, an attention without rotary
embedding or a sparse feed-forward ALONE per layer; un-gated relu^2
experts of which the program holds a share, a shared expert; the SSM
state beside KV) on the CPU at `tiny-nemotron` size: against the
benchmark's plain reference (benchmark/families/nemotron_h.py), through
the cache, through the engine, and what the opt-in paths do with it
(refuse, by name)."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_tpu.models import transformer as T
from seldon_tpu.models.config import ModelConfig, get_config
from seldon_tpu.ops import moe_dispatch
from seldon_tpu.servers.engine import (
    EngineConfig,
    InferenceEngine,
    SamplingParams,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERIOD = ("mamba", "moe", "mamba", "moe", "mamba", "attention", "moe")
LETTER = {"mamba": "M", "moe": "E", "attention": "*"}


@pytest.fixture(scope="module")
def fam():
    spec = importlib.util.spec_from_file_location(
        "family_nemotron_h",
        os.path.join(ROOT, "benchmark", "families", "nemotron_h.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def file_keys(cfg: ModelConfig) -> dict:
    """A program config under the key names a configuration file of the
    nemotron_h family has."""
    return {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "num_hidden_layers": cfg.n_layers,
        "hybrid_override_pattern": "".join(LETTER[t] for t in cfg.layer_types),
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_seq_len,
        "layer_norm_epsilon": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": cfg.tie_embeddings,
        "mamba_num_heads": cfg.ssm_heads, "mamba_head_dim": cfg.ssm_head_dim,
        "n_groups": cfg.ssm_groups, "ssm_state_size": cfg.ssm_state,
        "conv_kernel": cfg.conv_kernel, "chunk_size": cfg.ssm_chunk,
        "use_conv_bias": True, "mlp_hidden_act": "relu2",
        "n_routed_experts": cfg.experts_held, "router_width": cfg.n_experts,
        "num_experts_per_tok": cfg.n_experts_per_token,
        "norm_topk_prob": cfg.router_norm_topk,
        "routed_scaling_factor": cfg.router_scale,
        "moe_intermediate_size": cfg.expert_width,
        "moe_shared_expert_intermediate_size": cfg.d_ff_shared,
        "n_shared_experts": 1,
        "serving": {"weight_dtype": "bf16", "kv_cache_dtype": "bf16",
                    "ssm_state_dtype": "float32",
                    "experts_held_from": cfg.expert_first},
    }


def two_periods(**kw) -> ModelConfig:
    return get_config("tiny-nemotron", n_layers=14, layer_types=PERIOD * 2, **kw)


# -- the plan, the config, the cache --------------------------------------------

def test_layer_plan_scans_the_period_of_seven_and_counts_layers_by_kind():
    cfg = two_periods()
    (seg,) = T.layer_plan(cfg)
    assert (len(seg.kinds), seg.reps) == (7, 2)
    assert [op for op, _ in seg.kinds] == list(PERIOD)
    assert not any(sparse for _, sparse in seg.kinds)  # no feed-forward of their own
    assert (cfg.n_mamba_layers, cfg.n_attn_layers, cfg.n_sparse_layers,
            cfg.n_conv_layers) == (6, 2, 6, 0)
    one = get_config("tiny-nemotron")
    assert [(len(s.kinds), s.reps, s.ssm_start, s.attn_start)
            for s in T.layer_plan(one)] == \
        [(2, 2, 0, 0), (1, 1, 2, 0), (1, 1, 3, 0), (1, 1, 3, 1)]
    # the published 52 layers: MEMEM*E five times over, then the tail
    pub = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    full = dataclasses.replace(
        cfg, n_layers=52,
        layer_types=tuple({v: k for k, v in LETTER.items()}[c] for c in pub)
    ).validate()
    assert (full.n_mamba_layers, full.n_sparse_layers, full.n_attn_layers) == (23, 23, 6)
    plan = T.layer_plan(full)
    assert (len(plan[0].kinds), plan[0].reps) == (7, 5)
    assert sum(len(s.kinds) * s.reps for s in plan) == 52


def test_head_dim_is_a_field_that_defaults_to_the_quotient():
    tiny = get_config("tiny")
    assert tiny.head_dim == tiny.d_model // tiny.n_heads
    # a preset whose heads are the quotient follows d_model / n_heads
    assert get_config("tiny", d_model=128).head_dim == 128 // tiny.n_heads
    cfg = get_config("tiny-nemotron")
    assert cfg.head_dim == 32 != cfg.d_model // cfg.n_heads  # stated, and kept
    assert get_config("tiny-nemotron", vocab_size=300).head_dim == 32
    assert dataclasses.asdict(cfg)["head_dim"] == 32  # what /metadata serves
    assert (cfg.ssm_inner, cfg.ssm_conv_dim, cfg.experts_held) == (64, 64 + 2 * 2 * 16, 4)


def test_cache_spec_holds_the_ssm_state_in_float32_beside_kv():
    cfg = two_periods()
    spec = T.cache_spec(cfg, 4, 32)
    assert set(spec) == {"k", "v", "ssm", "ssm_conv"}
    assert spec["k"].shape == (2, 4, 1, 32, cfg.n_kv_heads * cfg.head_dim)
    assert spec["ssm"].shape == (6, 4, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    assert spec["ssm"].dtype == jnp.float32 and spec["ssm"].time_axis is None
    assert spec["ssm_conv"].shape == (6, 4, cfg.conv_kernel - 1, cfg.ssm_conv_dim)
    assert spec["ssm_conv"].dtype == jnp.bfloat16
    assert (spec["ssm"].kind, spec["ssm_conv"].kind) == ("ssm", "ssm_conv")
    cache = T.init_cache(cfg, 4, 32)
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == \
        {k: (e.shape, e.dtype) for k, e in spec.items()}
    assert T.cache_bytes(cfg, 4, 32) == {
        "kv": 2 * 2 * 4 * 32 * cfg.n_kv_heads * cfg.head_dim * 2,
        "ssm": 6 * 4 * 4 * 16 * 16 * 4,
        "ssm_conv": 6 * 4 * 3 * 128 * 2}


def test_config_refuses_what_single_block_layers_do_not_mix_with():
    with pytest.raises(AssertionError, match="do not mix"):
        get_config("tiny-nemotron", layer_types=PERIOD[:6] + ("conv",))
    with pytest.raises(AssertionError, match="do not mix"):
        get_config("tiny-nemotron", n_dense_layers=1)
    with pytest.raises(AssertionError, match="moe layers need n_experts"):
        get_config("tiny-nemotron", n_experts=0, n_experts_held=0)
    with pytest.raises(AssertionError, match="mamba layers need ssm_heads"):
        get_config("tiny-nemotron", ssm_groups=3)
    with pytest.raises(AssertionError, match="must lie among"):
        get_config("tiny-nemotron", expert_first=6)
    with pytest.raises(AssertionError, match="unknown ff_act"):
        get_config("tiny-nemotron", ff_act="gelu")
    with pytest.raises(AssertionError, match="need layer_types"):
        get_config("tiny-moe", d_ff_shared=32)
    with pytest.raises(AssertionError, match="need layer_types"):
        get_config("tiny", rotary=False)


# -- against the plain reference ----------------------------------------------

@pytest.mark.parametrize("make", [lambda **kw: get_config("tiny-nemotron", **kw),
                                  two_periods], ids=["one-period", "two-periods"])
def test_program_equals_the_plain_reference_in_float32(fam, make):
    """The chunked scan (chunks of 8 over 37 positions: the last chunk is
    padded) against the reference's token-by-token recurrence, the
    dispatch over the held experts against its loop over them."""
    cfg = make(dtype="float32")
    params = T.init_params(cfg, jax.random.key(3))
    toks = jax.random.randint(jax.random.key(4), (37,), 0, cfg.vocab_size)
    want = fam.forward_logits(params, toks, file_keys(cfg))
    with jax.default_matmul_precision("highest"):
        got = T.forward(params, toks[None], cfg)[0]
    assert got.shape == want.shape == (37, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("length", [1, 5, 8, 13, 16, 27])
def test_chunked_scan_is_the_recurrence_at_lengths_off_the_chunk(length):
    """_ssd_scan (chunk 8) against _ssm_update applied position by
    position: outputs at every position and the state after the last."""
    B, H, P, G, N = 2, 4, 8, 2, 16
    ks = jax.random.split(jax.random.key(length), 5)
    x = jax.random.normal(ks[0], (B, length, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, length, H)))
    b = jax.random.normal(ks[2], (B, length, G, N))
    c = jax.random.normal(ks[3], (B, length, G, N))
    a_log = jnp.log(jax.random.uniform(ks[4], (H,), minval=1.0, maxval=16.0))
    with jax.default_matmul_precision("highest"):
        y, last = T._ssd_scan(x, dt, a_log, b, c, 8)
        # the middle one of three layers' states is the one stepped
        state, ys = jnp.ones((3, B, H, P, N)).at[1].set(0.0), []
        for t in range(length):
            yt, state = T._ssm_update(state, jnp.asarray(1), x[:, t], dt[:, t], a_log,
                                      b[:, t], c[:, t])
            ys.append(yt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.stack(ys, 1)),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(last), np.asarray(state[1]), atol=1e-4, rtol=1e-4)
    assert bool(jnp.all(state[0] == 1.0)) and bool(jnp.all(state[2] == 1.0))


@pytest.mark.parametrize("dims", [(3, 2, 4, 16, 2, 16), (2, 2, 64, 64, 8, 128)],
                         ids=["tiny", "published-heads"])
def test_the_update_kernel_is_the_update_in_jax_numpy(dims):
    """ops/ssm_update.py: the Pallas kernel (interpreted here) against the
    same arithmetic in jax.numpy, at tiny-nemotron's head shapes and at the
    published ones; the layers it was not pointed at come back untouched."""
    from seldon_tpu.ops import ssm_update
    from tests.pallas_interpret import pallas_interpret

    Lm, B, H, P, G, N = dims
    ks = jax.random.split(jax.random.key(0), 5)
    args = (jax.random.normal(ks[0], (Lm, B, H, P, N)), jnp.asarray(1, jnp.int32),
            jax.random.uniform(ks[1], (B, H)), jax.random.normal(ks[2], (B, H, P)),
            jax.random.normal(ks[3], (B, G, N)).astype(jnp.bfloat16),
            jax.random.normal(ks[4], (B, G, N)).astype(jnp.bfloat16))
    with jax.default_matmul_precision("highest"):
        want_y, want = ssm_update._xla(*args)
        with pallas_interpret():
            got_y, got = jax.jit(ssm_update._pallas)(*args)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(args[0][0]))
    assert float(jnp.max(jnp.abs(got[1] - args[0][1]))) > 0.1


def _through_the_cache(params, cfg, seq, plen):
    """Logits at positions plen-1 .. len(seq)-1: prefill of seq[:plen]
    (right-padded to 32), then one decode step per further token."""
    pad = jnp.zeros((1, 32), jnp.int32).at[0, :plen].set(seq[:plen])
    cache = T.init_cache(cfg, 1, 64)
    prefill = jax.jit(T.prefill, static_argnums=(4,))
    decode = jax.jit(T.decode_step, static_argnums=(4,))
    logits, cache = prefill(params, pad, jnp.asarray([plen]), cache, cfg)
    out = [logits[0]]
    for t in range(plen, len(seq)):
        logits, cache = decode(params, seq[t:t + 1], jnp.asarray([t]), cache, cfg)
        out.append(logits[0])
    return jnp.stack(out).astype(jnp.float32)


@pytest.mark.parametrize("plen", [3, 9, 20])
def test_prefill_then_decode_through_the_cache_equals_the_reference_in_float32(fam, plen):
    """Prefill by the chunked scan hands decode a state that the
    recurrence carries on: logits at every later position are the
    reference's, whose mixer never chunks and carries no cache."""
    cfg = get_config("tiny-nemotron", dtype="float32")
    params = T.init_params(cfg, jax.random.key(1))
    seq = jax.random.randint(jax.random.key(5), (30,), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = _through_the_cache(params, cfg, seq, plen)
    want = fam.forward_logits(params, seq, file_keys(cfg))[plen - 1:]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4, rtol=3e-4)


def test_bf16_through_the_cache_stays_near_the_reference_and_the_control_does_not(fam):
    """The served precision against the float32 reference by the
    benchmark's own measure (the reference's logit gap between its best
    token and the program's greedy token); the control (the layers'
    matrices on the float8 e4m3 grid) has to fail the same limit."""
    cfg = two_periods()
    keys = file_keys(cfg)
    gaps, control_gaps = [], []
    for seed in range(2):
        params = T.init_params(cfg, jax.random.key(seed))
        seq = jax.random.randint(jax.random.key(100 + seed), (36,), 0, cfg.vocab_size)
        plen = (9, 20)[seed]
        got = _through_the_cache(params, cfg, seq, plen)
        want = fam.forward_logits(params, seq, keys)[plen - 1:]
        coarse = fam.forward_logits(params, seq, keys, control=True)[plen - 1:]
        top = jnp.max(want, axis=-1)
        at = jnp.arange(want.shape[0])
        gaps += [float(g) for g in top - want[at, jnp.argmax(got, axis=-1)]]
        control_gaps += [float(g) for g in top - want[at, jnp.argmax(coarse, axis=-1)]]
    limit, share = 0.25, 0.9
    within = sum(g <= limit for g in gaps) / len(gaps)
    control_within = sum(g <= limit for g in control_gaps) / len(control_gaps)
    assert within >= share, (within, sorted(gaps)[-5:])
    assert control_within < within - 0.08, (control_within, within)


def test_state_after_prefill_is_the_state_decode_builds_token_by_token():
    cfg = get_config("tiny-nemotron", dtype="float32")
    params = T.init_params(cfg, jax.random.key(0))
    seq = jax.random.randint(jax.random.key(1), (1, 11), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        _, whole = T.prefill(params, seq, jnp.asarray([11]), T.init_cache(cfg, 1, 32), cfg)
        _, step = T.prefill(params, seq[:, :1], jnp.asarray([1]),
                            T.init_cache(cfg, 1, 32), cfg)
        for t in range(1, 11):
            _, step = T.decode_step(params, seq[0, t:t + 1], jnp.asarray([t]), step, cfg)
    for key in ("ssm", "ssm_conv"):
        np.testing.assert_allclose(np.asarray(whole[key]), np.asarray(step[key]),
                                   atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(whole["k"][:, :, :, :11]),
                               np.asarray(step["k"][:, :, :, :11]), atol=1e-4, rtol=1e-4)
    assert float(jnp.max(jnp.abs(whole["ssm"]))) > 0.0
    assert whole["ssm"].dtype == jnp.float32


def test_right_padded_rows_take_their_state_at_their_own_last_real_token():
    """Rows of one admission group share a bucket; each row's logits, SSM
    state, conv state and KV are what it gets prefilled alone at its own
    length: the pads stepped no state and entered no conv state."""
    cfg = get_config("tiny-nemotron", dtype="float32")
    params = T.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(2), (3, 16), 0, cfg.vocab_size)
    plens = jnp.asarray([5, 16, 1])
    with jax.default_matmul_precision("highest"):
        logits, cache = T.prefill(params, toks, plens, T.init_cache(cfg, 3, 32), cfg)
        for r, n in enumerate([5, 16, 1]):
            alone_l, alone = T.prefill(params, toks[r:r + 1, :n], jnp.asarray([n]),
                                       T.init_cache(cfg, 1, 32), cfg)
            np.testing.assert_allclose(np.asarray(logits[r]), np.asarray(alone_l[0]),
                                       atol=1e-4, rtol=1e-4)
            for key in ("ssm", "ssm_conv"):
                np.testing.assert_allclose(np.asarray(cache[key][:, r]),
                                           np.asarray(alone[key][:, 0]),
                                           atol=1e-4, rtol=1e-4)
            np.testing.assert_allclose(np.asarray(cache["k"][:, r, :, :n]),
                                       np.asarray(alone["k"][:, 0, :, :n]),
                                       atol=1e-4, rtol=1e-4)


def test_decode_replaces_the_ssm_state_of_every_mamba_layer_and_writes_one_kv_row():
    cfg = two_periods()
    params = T.init_params(cfg, jax.random.key(0))
    cache = jax.tree.map(lambda a: a + 1, T.init_cache(cfg, 3, 8))
    pos = jnp.asarray([2, 5, 0])
    _, new = T.decode_step(params, jnp.asarray([3, 4, 5]), pos, cache, cfg)
    assert new["ssm"].shape == cache["ssm"].shape and new["ssm"].dtype == jnp.float32
    moved = np.asarray(new["ssm"] != cache["ssm"]).any(axis=(2, 3, 4))  # [Lm, B]
    assert moved.all()
    moved = np.asarray(new["k"] != cache["k"]).any(axis=(2, 4))  # [La, B, T]
    want = np.zeros_like(moved)
    want[:, np.arange(3), np.asarray(pos)] = True
    np.testing.assert_array_equal(moved, want)


# -- the share of the experts ---------------------------------------------------

def _sparse_layer(cfg, key):
    """One "moe" layer's weights of `cfg` and its merged expert stacks."""
    one = dataclasses.replace(cfg, n_layers=1, layer_types=("moe",)).validate()
    (lp,), = T.init_params(one, key)["segments"]
    lp = {k: v[0] for k, v in lp.items()}
    return one, lp


def test_two_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """A layer that holds experts 0-3, one that holds 4-7 and the shared
    expert counted once add up to the layer that holds all 8: each share
    routes over all 8 and normalises over every chosen expert, and what
    the absent experts would have added is left out, no more."""
    whole_cfg = get_config("tiny-nemotron", dtype="float32", n_experts_held=0)
    one, lp = _sparse_layer(whole_cfg, jax.random.key(7))
    h = jax.random.normal(jax.random.key(8), (2, 9, one.d_model), jnp.float32)
    live = jnp.ones((2, 9), bool).at[1, 6:].set(False)

    def run(cfg, lo, hi):
        part = dict(lp, w_up=lp["w_up"][lo:hi], w_down=lp["w_down"][lo:hi])
        experts = {k: part[k] for k in ("w_up", "w_down")}
        with jax.default_matmul_precision("highest"):
            return T._sparse_ff(h, part, experts, jnp.zeros((), jnp.int32), cfg, live)

    with jax.default_matmul_precision("highest"):
        x = h.reshape(18, -1)
        shared = (jnp.square(jax.nn.relu(x @ lp["shared_up"])) @ lp["shared_down"]
                  ).reshape(h.shape)
    uncut, st = run(one, 0, 8)
    lower, st_lo = run(dataclasses.replace(one, n_experts_held=4, expert_first=0), 0, 4)
    upper, st_hi = run(dataclasses.replace(one, n_experts_held=4, expert_first=4), 4, 8)
    np.testing.assert_allclose(np.asarray(lower + upper - shared), np.asarray(uncut),
                               atol=1e-5, rtol=1e-5)
    assert float(jnp.max(jnp.abs(lower - shared))) > 1e-3  # the shares are not empty
    assert float(jnp.max(jnp.abs(upper - shared))) > 1e-3
    # every live row chose top-2 of the 8; each assignment is held by one share
    chosen = 15 * one.n_experts_per_token
    assert int(st["assignments"]) == int(st_lo["assignments"]) == \
        int(st_hi["assignments"]) == chosen
    assert int(st_lo["held"]) + int(st_hi["held"]) == chosen
    assert 0 < int(st_lo["held"]) < chosen
    assert int(st_lo["touched"]) <= 4 and int(st_hi["touched"]) <= 4
    assert int(st_lo["touched"]) + int(st_hi["touched"]) == int(st["touched"])


def test_an_assignment_to_an_expert_not_held_goes_nowhere_as_a_dead_row_does():
    E, D, F, K, N = 8, 16, 8, 2, 12
    k = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(k[0], (N, D))
    idx, w = moe_dispatch.route(x, jax.random.normal(k[1], (D, E)), None, top_k=K,
                                router="sigmoid", scale=2.5, norm_eps=1e-20)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 2.5, rtol=1e-6)
    wu, wd = jax.random.normal(k[2], (E, F, D)), jax.random.normal(k[3], (E, F, D))
    got, st = moe_dispatch.dispatch_experts(
        x, idx, w, None, wu[2:6], wd[2:6], n_experts=4, first=2)
    want = jnp.zeros((N, D))
    for e in range(2, 6):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        want += w_e[:, None] * (jnp.square(jax.nn.relu(x @ wu[e].T)) @ wd[e])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)
    held = int(jnp.sum((idx >= 2) & (idx < 6)))
    assert (int(st["assignments"]), int(st["held"])) == (N * K, held)
    assert int(st["touched"]) == len(set(np.asarray(idx).ravel().tolist()) & {2, 3, 4, 5})


def test_decode_reports_routing_the_held_share_and_the_mamba_layers_run():
    cfg = get_config("tiny-nemotron")
    assert T.routing_width(cfg) == 5 and T.routing_width(get_config("tiny-lfm2")) == 3
    params = T.init_params(cfg, jax.random.key(0))
    cache = T.init_cache(cfg, 4, 16)
    tok, pos = jnp.asarray([3, 4, 5, 6]), jnp.asarray([2, 2, 2, 2])
    live = jnp.asarray([True, False, False, True])
    _, _, routing = T.decode_step(params, tok, pos, cache, cfg, live=live,
                                  return_routing=True)
    layers, touched, assigned, held, ssm = (int(v) for v in routing)
    assert (layers, ssm) == (cfg.n_sparse_layers, cfg.n_mamba_layers) == (3, 3)
    assert assigned == layers * 2 * cfg.n_experts_per_token
    assert 0 <= held <= assigned and touched <= min(held, layers * cfg.experts_held)


# -- through the engine ---------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = get_config("tiny-nemotron", dtype="float32")
    params = T.init_params(cfg, jax.random.key(0))
    # one bucket and one chunk length: four programs to compile
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, max_seq_len=64, prompt_buckets=(32,), decode_chunk=4,
        adaptive_chunk=False))
    eng.start()
    yield eng, params, cfg
    eng.stop()


def test_engine_prefill_and_decode_through_the_slab_follow_the_reference(served, fam):
    """Six requests over two slots: every slot is reused twice by a
    request of another length, admission groups pad unequal prompts to
    one bucket, and the decode chunk steps the SSM state in the slab.
    Teacher-forced on each completion, the plain reference (float32, the
    recurrence, no cache) ranks every token the engine chose first, to
    within float32's order of summation: no request saw another's state,
    its own padding's, or a stale one."""
    eng, params, cfg = served
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(2, cfg.vocab_size, size=n)) for n in (5, 9, 14, 20, 7, 30)]
    queues = [eng.submit(p, SamplingParams(max_new_tokens=6, temperature=0.0))
              for p in prompts]
    keys = file_keys(cfg)
    for p, q in zip(prompts, queues):
        toks = []
        while (item := q.get(timeout=120)) is not None:
            assert "error" not in item, item
            toks += item["tokens"]
        assert 1 <= len(toks) <= 6
        seq = jnp.asarray(p + toks[:-1], jnp.int32)
        logits = fam.forward_logits(params, seq, keys)[len(p) - 1:]
        gaps = jnp.max(logits, -1) - logits[jnp.arange(len(toks)), jnp.asarray(toks)]
        assert float(jnp.max(gaps)) < 1e-3, (toks, gaps)
    snap = eng.stats.snapshot()
    # counted when a chunk's results reach the host; decode_steps at dispatch
    assert 0 < snap["ssm_layer_steps"] <= snap["decode_steps"] * cfg.n_mamba_layers
    assert snap["ssm_layer_steps"] % cfg.n_mamba_layers == 0
    assert snap["moe_sparse_layer_steps"] == snap["ssm_layer_steps"]  # 3 and 3 a step
    assert 0 < snap["moe_assignments_held"] < snap["moe_assignments"]
    assert snap["moe_experts_touched"] <= snap["moe_assignments_held"]
    assert eng.cache_bytes() == T.cache_bytes(cfg, 2, 64)
    assert set(eng.cache_bytes()) == {"kv", "ssm", "ssm_conv"}


@pytest.mark.parametrize("path,kw", [
    ("paged_kv", dict(paged_kv=True)),
    ("prefix_cache", dict(prefix_cache=True)),
    ("chunked_prefill", dict(chunked_prefill=True)),
    ("spec_decode", dict(spec_decode=True, paged_kv=True)),
    ("heal", dict(heal=True)),
    ("tp > 1", dict(tp=2)),
])
@pytest.mark.parametrize("preset,names", [
    ("tiny-nemotron", "SSM state beside KV)"),
    # the SSM state in the attention's own layer: said by the kind's name
    ("tiny-falcon-h1", "SSM state beside KV, both in each attention_mamba layer)"),
])
def test_the_opt_in_engine_paths_refuse_the_ssm_state_by_name(path, kw, preset, names):
    cfg = get_config(preset)
    params = T.init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="SSM state beside KV") as e:
        InferenceEngine(params, cfg, EngineConfig(
            max_slots=2, max_seq_len=64, prompt_buckets=(16, 32), **kw))
    assert path in str(e.value) and names in str(e.value)


@pytest.mark.parametrize("what,call", [
    ("training", lambda p, c, t: __import__(
        "seldon_tpu.models.train", fromlist=["loss_fn"]).loss_fn(p, t, jnp.ones_like(t), c)),
    ("paged decode", lambda p, c, t: T.paged_decode_step(
        p, t[:, 0], jnp.zeros((2,), jnp.int32), {}, jnp.zeros((2, 1), jnp.int32), c)),
    ("paged KV pool", lambda p, c, t: T.init_paged_cache(c, 4, 16)),
    ("tensor-parallel", lambda p, c, t: T.decode_step(
        p, t[:, 0], jnp.zeros((2,), jnp.int32), T.init_cache(c, 2, 8), c, tp=object())),
])
@pytest.mark.parametrize("preset,names", [
    ("tiny-nemotron", "the Mamba-2 layers' SSM and conv state"),
    ("tiny-falcon-h1", "the attention_mamba layers' SSM and conv state"),
])
def test_the_model_functions_that_know_no_ssm_state_refuse_it_by_name(what, call, preset, names):
    cfg = get_config(preset)
    params = T.init_params(cfg, jax.random.key(0))
    toks = jnp.ones((2, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="SSM and conv state") as e:
        call(params, cfg, toks)
    assert what in str(e.value) and names in str(e.value)
    assert T.fixed_state_names(cfg).startswith(names)


@pytest.mark.parametrize("preset", ["tiny-nemotron", "tiny-falcon-h1"])
def test_tp_sharding_refuses_single_block_layers(preset):
    from seldon_tpu.models import tp_sharding

    with pytest.raises(ValueError, match="patterned stack"):
        tp_sharding.validate(get_config(preset), 2)


def test_cost_model_closed_forms_equal_the_cache_spec_and_the_tree():
    from seldon_tpu.servers import cost_model as cm

    cfg = two_periods()
    assert cm.kv_bytes_per_token(cfg) == 2 * 2 * cfg.n_kv_heads * cfg.head_dim * 2
    assert cm.state_bytes_per_slot(cfg) == 6 * (4 * 16 * 16 * 4 + 3 * 128 * 2)
    by_kind = T.cache_bytes(cfg, 3, 20)
    assert by_kind["kv"] == 3 * 20 * cm.kv_bytes_per_token(cfg)
    assert by_kind["ssm"] + by_kind["ssm_conv"] == 3 * cm.state_bytes_per_slot(cfg)
    tree = T.init_params(cfg, jax.random.key(0))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    # the held experts, the shared one, the mixers, the attentions, the
    # embedding and the head at bf16; norms, routers, biases, taps are noise
    assert 0.93 * held < cm.weight_bytes(cfg) <= held
    # attention is priced at the stated head width, not d_model / n_heads
    assert cm.attn_flops(cfg, 1, 10) == 4 * cfg.n_heads * 32 * 10 * 2
    lfm2 = get_config("tiny-lfm2")
    assert cm.attn_flops(lfm2, 1, 10) == 4 * lfm2.d_model * 10 * lfm2.n_attn_layers


def test_jaxserver_serves_the_preset_with_every_parameter_at_its_default(monkeypatch):
    """The unit, as the benchmark's launcher starts it: a preset name and
    nothing else about the model. /metadata gives the new fields and the
    cache by kind; /metrics and the HBM ledger carry the new counters and
    categories."""
    from seldon_tpu.servers.jaxserver import JAXServer

    monkeypatch.setenv("HBM_LEDGER", "1")
    srv = JAXServer(preset="tiny-nemotron", max_slots=2, max_seq_len=48)
    srv.load()
    try:
        out = srv.generate({"prompt": "ab", "max_new_tokens": 5, "temperature": 0.0})
        assert out["completion_tokens"] >= 1
        md = json.loads(json.dumps(srv.init_metadata()))
        got = md["config"]
        assert got["layer_types"] == list(PERIOD)
        assert (got["head_dim"], got["rotary"], got["ff_act"], got["d_ff_shared"],
                got["n_experts"], got["n_experts_held"], got["expert_first"]) == \
            (32, False, "relu2", 96, 8, 4, 0)
        assert (got["ssm_heads"], got["ssm_head_dim"], got["ssm_groups"],
                got["ssm_state"], got["ssm_chunk"]) == (4, 16, 2, 16, 8)
        assert md["cache_bytes"] == T.cache_bytes(srv.cfg, 2, 48)
        assert set(md["cache_bytes"]) == {"kv", "ssm", "ssm_conv"}
        gauges = {m["key"]: m["value"] for m in srv.metrics()}
        assert 0 < gauges["jaxserver_ssm_layer_steps"] \
            <= gauges["jaxserver_decode_steps"] * srv.cfg.n_mamba_layers
        assert 0 <= gauges["jaxserver_moe_assignments_held"] \
            <= gauges["jaxserver_moe_assignments"]
        cats = srv.engine.debug_hbm()["categories"]
        assert cats["ssm_state"]["bytes"] == md["cache_bytes"]["ssm"]
        assert cats["ssm_conv_state"]["bytes"] == md["cache_bytes"]["ssm_conv"]
        assert cats["kv_cache"]["bytes"] == md["cache_bytes"]["kv"]
        assert "conv_state" not in cats
    finally:
        srv.engine.stop()
