"""The patterned stack (ModelConfig.layer_types: short-conv and attention
operators by layer, leading dense feed-forwards then token -> expert
dispatch, sigmoid router, QK-norm, conv state beside KV) on the CPU at
`tiny-lfm2` size: against the benchmark's plain reference
(benchmark/families/lfm2.py), through the cache, through the engine, and
what the opt-in paths do with it (refuse, by name)."""

import dataclasses
import importlib.util
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
TWO_PERIODS = ("conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv", "conv", "conv")


@pytest.fixture(scope="module")
def fam():
    spec = importlib.util.spec_from_file_location(
        "family_lfm2", os.path.join(ROOT, "benchmark", "families", "lfm2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def file_keys(cfg: ModelConfig) -> dict:
    """A program config under the key names a configuration file of the
    lfm2 family has."""
    return {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": cfg.expert_width,
        "num_hidden_layers": cfg.n_layers, "layer_types": list(cfg.layer_types),
        "num_dense_layers": cfg.n_dense_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_seq_len, "norm_eps": cfg.rms_norm_eps,
        "rope_parameters": {"rope_theta": cfg.rope_theta},
        "conv_L_cache": cfg.conv_kernel, "conv_bias": False,
        "num_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.n_experts_per_token,
        "use_expert_bias": cfg.router_bias,
        "norm_topk_prob": cfg.router_norm_topk,
        "routed_scaling_factor": cfg.router_scale,
        "assumed": {"tie_word_embeddings": cfg.tie_embeddings,
                    "qk_norm": cfg.qk_norm},
        "serving": {"weight_dtype": "bf16", "kv_cache_dtype": "bf16"},
    }


def two_periods(**kw) -> ModelConfig:
    return get_config("tiny-lfm2", n_layers=10, layer_types=TWO_PERIODS, **kw)


# -- the plan ----------------------------------------------------------------

def test_layer_plan_scans_by_period_and_counts_layers_by_kind():
    cfg = two_periods()
    plan = T.layer_plan(cfg)
    assert [(len(s.kinds), s.reps) for s in plan] == [(1, 2), (4, 2)]
    assert plan[1].kinds == (("full_attention", True), ("conv", True),
                             ("conv", True), ("conv", True))
    assert (plan[1].first_layer, plan[1].attn_start, plan[1].conv_start) == (2, 0, 2)
    assert (cfg.n_attn_layers, cfg.n_conv_layers, cfg.n_sparse_layers) == (2, 8, 8)
    # the published 40 layers: 2 dense conv, 9 whole periods, (attention, conv)
    full = ModelConfig(
        n_layers=40, n_experts=64, n_experts_per_token=4, n_dense_layers=2,
        layer_types=["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9
        + ["full_attention", "conv"]).validate()
    assert [(len(s.kinds), s.reps) for s in T.layer_plan(full)] == \
        [(1, 2), (4, 9), (1, 1), (1, 1)]
    assert hash(full) == hash(dataclasses.replace(full))  # a list is stored as a tuple


def test_cache_spec_holds_kv_for_attention_layers_and_state_for_conv_layers():
    cfg = two_periods()
    spec = T.cache_spec(cfg, 4, 32)
    # one row a token: all its KV heads side by side (kv_heads_per_row)
    assert spec["k"].shape == spec["v"].shape == (2, 4, 1, 32, cfg.n_kv_heads * cfg.head_dim)
    assert spec["conv"].shape == (8, 4, cfg.conv_kernel - 1, cfg.d_model)
    assert (spec["k"].kind, spec["k"].time_axis, spec["conv"].kind,
            spec["conv"].time_axis) == ("kv", 3, "conv", None)
    cache = T.init_cache(cfg, 4, 32)
    assert {k: v.shape for k, v in cache.items()} == {k: e.shape for k, e in spec.items()}
    by_kind = T.cache_bytes(cfg, 4, 32)
    assert by_kind == {"kv": 2 * spec["k"].shape[0] * 4 * 2 * 32 * 16 * 2,
                       "conv": 8 * 4 * 2 * 64 * 2}
    # the homogeneous stack: every layer holds KV, no other kind
    assert set(T.cache_spec(get_config("tiny"), 2, 16)) == {"k", "v"}
    assert set(T.cache_spec(get_config("tiny", kv_cache_dtype="int8"), 2, 16)) == \
        {"k", "v", "k_scale", "v_scale"}


def test_config_refuses_what_the_patterned_stack_does_not_have():
    with pytest.raises(AssertionError, match="layer_types names"):
        get_config("tiny-lfm2", n_layers=7)
    with pytest.raises(AssertionError, match="unknown layer_types"):
        get_config("tiny-lfm2", layer_types=("conv",) * 5 + ("sliding",))
    with pytest.raises(AssertionError, match="bf16"):
        get_config("tiny-lfm2", weight_dtype="int8")
    with pytest.raises(AssertionError, match="need layer_types"):
        get_config("tiny-moe", router="sigmoid")


# -- against the plain reference ----------------------------------------------

@pytest.mark.parametrize("make", [lambda **kw: get_config("tiny-lfm2", **kw), two_periods],
                         ids=["one-period", "two-periods"])
def test_program_equals_the_plain_reference_in_float32(fam, make):
    # float32 compute in the program too: what is left is the order of
    # summation, so the tolerance is tight.
    cfg = make(dtype="float32")
    params = T.init_params(cfg, jax.random.key(3))
    toks = jax.random.randint(jax.random.key(4), (40,), 0, cfg.vocab_size)
    want = fam.forward_logits(params, toks, file_keys(cfg))
    with jax.default_matmul_precision("highest"):
        got = T.forward(params, toks[None], cfg)[0]
    assert got.shape == want.shape == (40, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


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


def test_prefill_then_decode_in_bf16_stays_near_the_reference_and_the_control_does_not(fam):
    """The served precision (bf16 weights and compute, through the cache)
    against the float32 reference's full forward pass, position by
    position, by the benchmark's own measure: the reference's logit gap
    between its best token and the program's greedy token. Logits have a
    spread of 1 here and the best stands near 2.9. bf16 rounds at 2^-8
    relative, which moves a logit by a few hundredths (and by tenths at
    the odd position where a router's fourth and fifth scores swap):
    over six seeds the sound gaps were <= 0.13, 94 % of them 0. The
    control (the layers' matrices on the float8 e4m3 grid, 2^-4) moves
    logits by 0.8 to 3.3 and picks tokens the reference ranks 0.5 to 2.4
    down: 76 % of its gaps lay within 0.25. So: 95 % within 0.25, which
    the served precision passes and the control has to fail."""
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
        # and logit by logit (read: 0.13 to 0.61 against the control's 0.85 to 3.3)
        assert float(jnp.max(jnp.abs(got - want))) < 0.75
    limit, share = 0.25, 0.95
    within = sum(g <= limit for g in gaps) / len(gaps)
    control_within = sum(g <= limit for g in control_gaps) / len(control_gaps)
    assert within >= share, (within, sorted(gaps)[-5:])
    assert control_within < share - 0.08, control_within


def test_conv_state_after_prefill_is_the_state_decode_builds_token_by_token():
    cfg = get_config("tiny-lfm2", dtype="float32")
    params = T.init_params(cfg, jax.random.key(0))
    seq = jax.random.randint(jax.random.key(1), (1, 11), 0, cfg.vocab_size)
    whole = T.init_cache(cfg, 1, 32)
    _, whole = T.prefill(params, seq, jnp.asarray([11]), whole, cfg)
    step = T.init_cache(cfg, 1, 32)
    _, step = T.prefill(params, seq[:, :1], jnp.asarray([1]), step, cfg)
    for t in range(1, 11):
        _, step = T.decode_step(params, seq[0, t:t + 1], jnp.asarray([t]), step, cfg)
    np.testing.assert_allclose(np.asarray(whole["conv"]), np.asarray(step["conv"]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(whole["k"][:, :, :, :11]),
                               np.asarray(step["k"][:, :, :, :11]), atol=1e-5, rtol=1e-5)
    # a one-token prompt: the state is one input behind a zero
    one = T.init_cache(cfg, 1, 32)
    _, one = T.prefill(params, seq[:, :1], jnp.asarray([1]), one, cfg)
    assert float(jnp.max(jnp.abs(one["conv"][:, :, 0]))) == 0.0
    assert float(jnp.max(jnp.abs(one["conv"][:, :, 1]))) > 0.0


@pytest.mark.parametrize("side", [1, 2, 4])
def test_decode_attention_over_rows_of_several_heads_is_the_head_major_one(side):
    """cache rows that hold `side` KV heads side by side (the patterned
    stack stores all of a token's heads in one row) against one head a
    row, in float32: same weighted values for every query head."""
    B, T_, Hkv, G, Dh = 3, 16, 4, 2, 8
    ks = jax.random.split(jax.random.key(7), 5)
    q = jax.random.normal(ks[0], (B, 1, Hkv * G, Dh))
    ck, cv = (jax.random.normal(k, (B, Hkv, T_, Dh)) for k in ks[1:3])
    kf, vf = (jax.random.normal(k, (B, 1, Hkv, Dh)) for k in ks[3:5])
    mask_lt = jnp.arange(T_)[None, None, :] < jnp.asarray([5, 16, 1])[:, None, None]

    def rows(c):  # [B, Hkv, T, Dh] -> [B, Hkv / side, T, side * Dh]
        return c.reshape(B, Hkv // side, side, T_, Dh).transpose(
            0, 1, 3, 2, 4).reshape(B, Hkv // side, T_, side * Dh)
    want = T.gqa_attention_decode(q, ck, cv, kf, vf, mask_lt)
    got = T.gqa_attention_decode(q, rows(ck), rows(cv), kf, vf, mask_lt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
    # what prefill and decode write is that row: head h at lanes h * Dh
    fresh = T._kv_rows(kf, side)
    np.testing.assert_array_equal(
        np.asarray(fresh[:, 0, 0, :Dh]), np.asarray(kf[:, 0, 0]))
    np.testing.assert_array_equal(
        np.asarray(fresh[:, 0, -1, -Dh:]), np.asarray(kf[:, 0, -1]))


def test_decode_writes_each_layers_token_row_and_no_other():
    """The step's scatter indexes layer, slot and position: after one
    decode step every attention layer's row at (slot, pos) is new and
    nothing else of the slab moved."""
    cfg = two_periods()
    params = T.init_params(cfg, jax.random.key(0))
    cache = jax.tree.map(lambda a: a + 1, T.init_cache(cfg, 3, 8))
    pos = jnp.asarray([2, 5, 0])
    _, new = T.decode_step(params, jnp.asarray([3, 4, 5]), pos, cache, cfg)
    for key in ("k", "v"):
        moved = np.asarray(new[key] != cache[key]).any(axis=(2, 4))  # [La, B, T]
        want = np.zeros_like(moved)
        want[:, np.arange(3), np.asarray(pos)] = True
        np.testing.assert_array_equal(moved, want)


def test_right_padded_rows_take_their_state_at_their_own_length():
    """Rows of one admission group share a bucket; each row's logits and
    caches are what it gets prefilled alone at its own length."""
    cfg = get_config("tiny-lfm2", dtype="float32")
    params = T.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(2), (3, 16), 0, cfg.vocab_size)
    plens = jnp.asarray([5, 16, 1])
    logits, cache = T.prefill(params, toks, plens, T.init_cache(cfg, 3, 32), cfg)
    for r, n in enumerate([5, 16, 1]):
        alone_l, alone = T.prefill(params, toks[r:r + 1, :n], jnp.asarray([n]),
                                   T.init_cache(cfg, 1, 32), cfg)
        np.testing.assert_allclose(np.asarray(logits[r]), np.asarray(alone_l[0]),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(cache["conv"][:, r]),
                                   np.asarray(alone["conv"][:, 0]), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(cache["k"][:, r, :, :n]),
                                   np.asarray(alone["k"][:, 0, :, :n]), atol=1e-5, rtol=1e-5)


# -- the router and the dispatch ----------------------------------------------

def test_sigmoid_router_selects_with_the_bias_and_weights_without_it():
    # one token, 4 experts, top-2: router logits 2, 1, 0, -1 (x = e0)
    x = jnp.asarray([[1.0, 0.0]])
    w = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 0.0, 0.0]])
    s = jax.nn.sigmoid(jnp.asarray([2.0, 1.0, 0.0, -1.0]))
    idx, wt = moe_dispatch.route(x, w, None, top_k=2, router="sigmoid")
    assert sorted(idx[0].tolist()) == [0, 1]
    # the bias lifts expert 3 over experts 1 and 2: selected {0, 3}
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.6])
    idx, wt = moe_dispatch.route(x, w, bias, top_k=2, router="sigmoid")
    assert sorted(idx[0].tolist()) == [0, 3]
    by_expert = dict(zip(idx[0].tolist(), wt[0].tolist()))
    total = float(s[0] + s[3]) + 1e-6
    assert by_expert[0] == pytest.approx(float(s[0]) / total, rel=1e-6)
    assert by_expert[3] == pytest.approx(float(s[3]) / total, rel=1e-6)  # no 0.6 in it
    # unnormalised and scaled: the raw scores times the factor
    idx, wt = moe_dispatch.route(x, w, bias, top_k=2, router="sigmoid",
                                 norm_topk=False, scale=2.5)
    by_expert = dict(zip(idx[0].tolist(), wt[0].tolist()))
    assert by_expert[3] == pytest.approx(2.5 * float(s[3]), rel=1e-6)


def test_dispatch_with_the_softmax_router_is_moe_block():
    """The new layer computes Mixtral's block (dense mixing), so moving
    that path onto it (ROADMAP A6) is a swap."""
    cfg = get_config("tiny-moe", dtype="float32")
    bp = {k: v[0] for k, v in T.init_params(cfg, jax.random.key(0))["blocks"].items()}
    x = jax.random.normal(jax.random.key(1), (3, 7, cfg.d_model), jnp.float32)
    want, _ = T.moe_block(x, bp, cfg)
    flat = x.reshape(21, cfg.d_model)
    idx, w = moe_dispatch.route(flat, bp["router"], None,
                                top_k=cfg.n_experts_per_token, router="softmax")
    got, stats = moe_dispatch.dispatch_experts(
        flat, idx, w, bp["w_gate"], bp["w_up"], bp["w_down"], n_experts=cfg.n_experts)
    np.testing.assert_allclose(np.asarray(got.reshape(3, 7, -1)), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert int(stats["assignments"]) == 21 * cfg.n_experts_per_token


def test_rows_that_are_not_live_route_nowhere_and_layers_share_one_stack():
    E, D, F, K, N = 8, 16, 8, 2, 12
    k = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(k[0], (N, D))
    router = jax.random.normal(k[1], (D, E))
    wg, wu = jax.random.normal(k[2], (3, E, D, F)), jax.random.normal(k[3], (3, E, D, F))
    wd = jax.random.normal(k[4], (3, E, F, D))
    idx, w = moe_dispatch.route(x, router, None, top_k=K, router="sigmoid")
    live = jnp.arange(N) < 5
    merged = [a.reshape((3 * E,) + a.shape[2:]) for a in (wg, wu, wd)]
    for layer in range(3):
        want, st = moe_dispatch.dispatch_experts(
            x, idx, w, wg[layer], wu[layer], wd[layer], live, n_experts=E)
        got, st2 = jax.jit(moe_dispatch.dispatch_experts, static_argnames="n_experts")(
            x, idx, w, *merged, live, n_experts=E, layer=jnp.asarray(layer))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
        assert float(jnp.max(jnp.abs(got[5:]))) == 0.0
        assert int(st["assignments"]) == int(st2["assignments"]) == 5 * K
        assert int(st["touched"]) == len(set(np.asarray(idx[:5]).ravel().tolist()))


def test_decode_reports_what_routing_did_for_live_rows_only():
    cfg = get_config("tiny-lfm2")
    params = T.init_params(cfg, jax.random.key(0))
    cache = T.init_cache(cfg, 4, 16)
    tok, pos = jnp.asarray([3, 4, 5, 6]), jnp.asarray([2, 2, 2, 2])
    live = jnp.asarray([True, False, False, True])
    _, _, routing = T.decode_step(params, tok, pos, cache, cfg, live=live,
                                  return_routing=True)
    layers, touched, assigned = (int(v) for v in routing)
    assert layers == cfg.n_sparse_layers == 4
    assert assigned == layers * 2 * cfg.n_experts_per_token
    assert layers * cfg.n_experts_per_token <= touched <= assigned
    # a homogeneous stack reports zeros
    tiny = get_config("tiny")
    out = T.decode_step(T.init_params(tiny, jax.random.key(0)), tok, pos,
                        T.init_cache(tiny, 4, 16), tiny, return_routing=True)
    assert out[2].tolist() == [0, 0, 0]


# -- through the engine ---------------------------------------------------------

def _greedy_reference(params, cfg, prompt, n, width=40):
    """Greedy continuation by the full forward pass (causal: the padding
    behind the last token changes nothing, and one width compiles once)."""
    fwd = jax.jit(lambda p, t: T.forward(p, t, cfg))
    seq, out = list(prompt), []
    for _ in range(n):
        padded = jnp.asarray([seq + [0] * (width - len(seq))])
        tok = int(jnp.argmax(fwd(params, padded)[0, len(seq) - 1]))
        out.append(tok)
        seq.append(tok)
        if tok == cfg.eos_token_id:
            break
    return out


@pytest.fixture(scope="module")
def served():
    cfg = get_config("tiny-lfm2", dtype="float32")
    params = T.init_params(cfg, jax.random.key(0))
    # one bucket and one chunk length: four programs to compile
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, max_seq_len=64, prompt_buckets=(32,), decode_chunk=4,
        adaptive_chunk=False))
    eng.start()
    yield eng, params, cfg
    eng.stop()


def test_engine_serves_groups_of_unequal_length_and_reuses_slots(served):
    """Six requests over two slots: every slot is reused twice, by a
    request of another length, and admission groups pad unequal prompts
    to one bucket. Each completion is the full forward pass's greedy
    continuation, so no request saw another's conv state or its padding."""
    eng, params, cfg = served
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(2, cfg.vocab_size, size=n)) for n in (5, 9, 14, 20, 7, 30)]
    queues = [eng.submit(p, SamplingParams(max_new_tokens=6, temperature=0.0))
              for p in prompts]
    for p, q in zip(prompts, queues):
        toks = []
        while (item := q.get(timeout=120)) is not None:
            assert "error" not in item, item
            toks += item["tokens"]
        want = _greedy_reference(params, cfg, p, 6)
        assert toks[:len(want)] == want
    snap = eng.stats.snapshot()
    # counted when a chunk's results reach the host; decode_steps at dispatch
    assert 0 < snap["moe_sparse_layer_steps"] <= snap["decode_steps"] * cfg.n_sparse_layers
    assert snap["moe_sparse_layer_steps"] % cfg.n_sparse_layers == 0
    assert 0 < snap["moe_experts_touched"] <= snap["moe_assignments"]
    assert eng.cache_bytes() == T.cache_bytes(cfg, 2, 64)


@pytest.mark.parametrize("path,kw", [
    ("paged_kv", dict(paged_kv=True)),
    ("prefix_cache", dict(prefix_cache=True)),
    ("chunked_prefill", dict(chunked_prefill=True)),
    ("spec_decode", dict(spec_decode=True, paged_kv=True)),
    ("heal", dict(heal=True)),
    ("tp > 1", dict(tp=2)),
])
def test_the_opt_in_engine_paths_refuse_a_patterned_stack_by_name(path, kw):
    cfg = get_config("tiny-lfm2")
    params = T.init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="patterned stack") as e:
        InferenceEngine(params, cfg, EngineConfig(
            max_slots=2, max_seq_len=64, prompt_buckets=(16, 32), **kw))
    assert path in str(e.value)


@pytest.mark.parametrize("what,call", [
    ("training", lambda p, c, t: __import__(
        "seldon_tpu.models.train", fromlist=["loss_fn"]).loss_fn(p, t, jnp.ones_like(t), c)),
    ("paged decode", lambda p, c, t: T.paged_decode_step(
        p, t[:, 0], jnp.zeros((2,), jnp.int32), {}, jnp.zeros((2, 1), jnp.int32), c)),
    ("paged KV pool", lambda p, c, t: T.init_paged_cache(c, 4, 16)),
    ("suffix prefill", lambda p, c, t: T.prefill_with_prefix(
        p, t, jnp.asarray([4, 4]), {"k": jnp.zeros((1, 2, 2, 4, 16))},
        jnp.asarray([2, 2]), c)),
    ("tensor-parallel", lambda p, c, t: T.decode_step(
        p, t[:, 0], jnp.zeros((2,), jnp.int32), T.init_cache(c, 2, 8), c, tp=object())),
])
def test_the_model_functions_that_know_no_conv_state_refuse_it_by_name(what, call):
    cfg = get_config("tiny-lfm2")
    params = T.init_params(cfg, jax.random.key(0))
    toks = jnp.ones((2, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="patterned stack") as e:
        call(params, cfg, toks)
    assert what in str(e.value)


def test_tp_sharding_refuses_a_patterned_stack():
    from seldon_tpu.models import tp_sharding

    with pytest.raises(ValueError, match="patterned stack"):
        tp_sharding.validate(get_config("tiny-lfm2"), 2)


def test_cost_model_counts_kv_for_attention_layers_and_state_for_the_rest():
    from seldon_tpu.servers import cost_model as cm

    cfg = two_periods()
    per_kv_layer = 2 * cfg.n_kv_heads * cfg.head_dim * 2
    assert cm.kv_bytes_per_token(cfg) == 2 * per_kv_layer  # 2 of 10 layers
    assert cm.state_bytes_per_slot(cfg) == 8 * 2 * cfg.d_model * 2
    assert T.cache_bytes(cfg, 3, 20) == {"kv": 3 * 20 * cm.kv_bytes_per_token(cfg),
                                         "conv": 3 * cm.state_bytes_per_slot(cfg)}
    tree = T.init_params(cfg, jax.random.key(0))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    # matrices at bf16 + the embedding once (tied); norms, routers, biases and taps are noise
    assert 0.97 * held < cm.weight_bytes(cfg) <= held
    dense = get_config("tiny")
    assert cm.kv_bytes_per_token(dense) == dense.n_layers * 2 * 2 * 16 * 2
    assert cm.state_bytes_per_slot(dense) == 0


def test_jaxserver_serves_the_preset_with_every_parameter_at_its_default():
    """The unit, as the benchmark's launcher starts it: a preset name and
    nothing else about the model. /metadata gives the pattern back as a
    list (what the harness compares after a JSON round trip) and the
    cache by kind; /metrics carries the routing counters."""
    import json

    from seldon_tpu.servers.jaxserver import JAXServer

    srv = JAXServer(preset="tiny-lfm2", max_slots=2, max_seq_len=48)
    srv.load()
    try:
        out = srv.generate({"prompt": "ab", "max_new_tokens": 5, "temperature": 0.0})
        assert out["completion_tokens"] >= 1
        md = json.loads(json.dumps(srv.init_metadata()))
        assert md["config"]["layer_types"] == list(get_config("tiny-lfm2").layer_types)
        assert md["config"]["router"] == "sigmoid" and md["config"]["d_ff_expert"] == 32
        assert md["cache_bytes"] == T.cache_bytes(srv.cfg, 2, 48)
        assert set(md["cache_bytes"]) == {"kv", "conv"}
        gauges = {m["key"]: m["value"] for m in srv.metrics()}
        steps = gauges["jaxserver_moe_sparse_layer_steps"]
        # counted when a chunk's results reach the host; decode_steps at dispatch
        assert 0 < steps <= gauges["jaxserver_decode_steps"] * srv.cfg.n_sparse_layers
        assert steps % srv.cfg.n_sparse_layers == 0
        assert 0 < gauges["jaxserver_moe_experts_touched"] <= gauges["jaxserver_moe_assignments"]
    finally:
        srv.engine.stop()
