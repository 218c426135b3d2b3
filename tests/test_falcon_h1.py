"""A stack whose every layer runs an attention AND a Mamba-2 mixer on the
same normed input, summed into the residual, then a dense SwiGLU
(ModelConfig.layer_types of "attention_mamba": KV and an SSM state in the
SAME layer; fixed scalar multipliers on the embedding, the logits and the
projections) on the CPU at `tiny-falcon-h1` size: against the benchmark's
plain reference (benchmark/families/falcon_h1.py), through the cache,
through the engine, the two kernels at the published head and state
shapes, and what counts the layer under both kinds of state."""

import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_tpu.models import transformer as T
from seldon_tpu.models.config import ModelConfig, get_config
from seldon_tpu.servers.engine import (
    EngineConfig,
    InferenceEngine,
    SamplingParams,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KIND = "attention_mamba"
MULTS = ("embed_mult", "logits_mult", "attn_in_mult", "attn_out_mult", "key_mult",
         "ssm_in_mult", "ssm_out_mult", "mlp_gate_mult", "mlp_down_mult")


@functools.lru_cache(maxsize=None)
def _family():
    """benchmark/families/falcon_h1.py, the family's file."""
    spec = importlib.util.spec_from_file_location(
        "family_falcon_h1",
        os.path.join(ROOT, "benchmark", "families", "falcon_h1.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fam():
    return _family()


def file_keys(cfg: ModelConfig) -> dict:
    """A program config under the key names a configuration file of the
    falcon_h1 family has."""
    return {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_seq_len,
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": None, "tie_word_embeddings": cfg.tie_embeddings,
        "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
        "mamba_n_groups": cfg.ssm_groups, "mamba_d_state": cfg.ssm_state,
        "mamba_d_conv": cfg.conv_kernel, "mamba_chunk_size": cfg.ssm_chunk,
        "mamba_d_ssm": cfg.ssm_inner, "mamba_conv_bias": True,
        "mamba_rms_norm": True, "mamba_norm_before_gate": False,
        "embedding_multiplier": cfg.embed_mult,
        "lm_head_multiplier": cfg.logits_mult,
        "attention_in_multiplier": cfg.attn_in_mult,
        "attention_out_multiplier": cfg.attn_out_mult,
        "key_multiplier": cfg.key_mult,
        "ssm_in_multiplier": cfg.ssm_in_mult,
        "ssm_out_multiplier": cfg.ssm_out_mult,
        "ssm_multipliers": list(cfg.ssm_mults),
        "mlp_multipliers": [cfg.mlp_gate_mult, cfg.mlp_down_mult],
        "serving": {"weight_dtype": "bf16", "kv_cache_dtype": "bf16",
                    "ssm_state_dtype": "float32"},
    }


# -- the plan, the config, the cache --------------------------------------------

def test_the_preset_is_off_every_easy_case():
    """5 query heads a KV head, 2 SSM groups, heads whose total width is
    not d_model, a state wider than a head, and no multiplier at 1."""
    cfg = get_config("tiny-falcon-h1")
    assert cfg.q_per_kv == 5 and cfg.ssm_groups == 2
    assert cfg.n_heads * cfg.head_dim != cfg.d_model
    assert cfg.ssm_state != cfg.ssm_head_dim
    assert len(cfg.ssm_mults) == 5 and all(m != 1.0 for m in cfg.multipliers)
    assert len(cfg.multipliers) == len(MULTS) + 5
    # a list is stored as a tuple, and a JSON round trip gives the list back
    again = ModelConfig(**json.loads(json.dumps(dataclasses.asdict(cfg)))).validate()
    assert again == cfg and isinstance(again.ssm_mults, tuple)


def test_layer_plan_and_counts_hold_the_layer_under_both_kinds_of_state():
    cfg = get_config("tiny-falcon-h1")
    (seg,) = T.layer_plan(cfg)
    assert (seg.kinds, seg.reps) == (((KIND, False),), 3)
    assert (cfg.n_attn_layers, cfg.n_mamba_layers, cfg.n_conv_layers,
            cfg.n_sparse_layers) == (3, 3, 0, 0)
    assert not cfg.single_blocks and T.routing_width(cfg) == 5
    # the published depth is one segment too, and a mixed list advances
    # both starts for the same layer
    full = dataclasses.replace(cfg, n_layers=72, layer_types=(KIND,) * 72).validate()
    assert [(len(s.kinds), s.reps) for s in T.layer_plan(full)] == [(1, 72)]
    mixed = dataclasses.replace(
        cfg, n_layers=5, layer_types=("full_attention", KIND, KIND, "full_attention", KIND)
    ).validate()
    assert (mixed.n_attn_layers, mixed.n_mamba_layers) == (5, 3)
    assert [(s.first_layer, s.attn_start, s.ssm_start) for s in T.layer_plan(mixed)] == \
        [(0, 0, 0), (1, 1, 0), (3, 3, 2), (4, 4, 2)]


def test_cache_spec_gives_the_layer_kv_and_an_ssm_state_and_its_conv_inputs():
    cfg = get_config("tiny-falcon-h1")
    spec = T.cache_spec(cfg, 4, 32)
    assert set(spec) == {"k", "v", "ssm", "ssm_conv"}
    assert spec["k"].shape == spec["v"].shape == (3, 4, 1, 32, cfg.n_kv_heads * cfg.head_dim)
    assert spec["ssm"].shape == (3, 4, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    assert spec["ssm"].dtype == jnp.float32 and spec["ssm"].time_axis is None
    assert spec["ssm_conv"].shape == (3, 4, cfg.conv_kernel - 1, cfg.ssm_conv_dim)
    assert spec["ssm_conv"].dtype == jnp.bfloat16
    assert T.cache_bytes(cfg, 4, 32) == {
        "kv": 2 * 3 * 4 * 32 * 2 * 16 * 2,
        "ssm": 3 * 4 * 4 * 16 * 32 * 4,
        "ssm_conv": 3 * 4 * 3 * (64 + 2 * 2 * 32) * 2}
    # the published widths: [5, B, 1, T, 512], [5, B, 32, 128, 256] float32, [5, B, 3, 5120]
    with open(os.path.join(ROOT, "benchmark", "configs", "falcon-h1-34b-instruct.json")) as f:
        raw = json.load(f)
    pub = ModelConfig(**_family().model_config_kwargs(raw)).validate()
    spec = T.cache_spec(pub, 64, 1024)
    assert spec["k"].shape == (5, 64, 1, 1024, 512)
    assert spec["ssm"].shape == (5, 64, 32, 128, 256) and spec["ssm"].dtype == jnp.float32
    assert spec["ssm_conv"].shape == (5, 64, 3, 5120)


def test_config_says_the_new_kind_by_name_where_it_refuses():
    with pytest.raises(AssertionError, match="attention_mamba layers need ssm_heads"):
        get_config("tiny-falcon-h1", ssm_groups=3)
    with pytest.raises(AssertionError, match="conv / full_attention / attention_mamba"):
        get_config("tiny-falcon-h1", layer_types=(KIND, KIND, "mamba"))
    with pytest.raises(AssertionError, match="ssm_mults is the five"):
        get_config("tiny-falcon-h1", ssm_mults=(0.5, 0.5))
    with pytest.raises(AssertionError, match="ssm_mults is the five"):
        get_config("tiny-lfm2", ssm_mults=(1.0,) * 5)
    with pytest.raises(AssertionError, match="attention_mamba layers only"):
        get_config("tiny-lfm2", attn_out_mult=0.5)
    for name in MULTS:
        with pytest.raises(AssertionError, match="need layer_types"):
            get_config("tiny", **{name: 0.5})
    with pytest.raises(AssertionError, match="unknown layer_types"):
        get_config("tiny-falcon-h1", layer_types=("attention+mamba",) * 3)


# -- against the plain reference ----------------------------------------------

@pytest.mark.parametrize("layers", [3, 5])
def test_program_equals_the_plain_reference_in_float32(fam, layers):
    """The chunked scan (chunks of 8 over 37 positions: the last chunk is
    padded), the attention beside it and every multiplier against the
    reference's token-by-token recurrence and its own attention."""
    cfg = get_config("tiny-falcon-h1", dtype="float32", n_layers=layers,
                     layer_types=(KIND,) * layers)
    params = T.init_params(cfg, jax.random.key(3))
    toks = jax.random.randint(jax.random.key(4), (37,), 0, cfg.vocab_size)
    want = fam.forward_logits(params, toks, file_keys(cfg))
    with jax.default_matmul_precision("highest"):
        got = T.forward(params, toks[None], cfg)[0]
    assert got.shape == want.shape == (37, cfg.vocab_size)
    assert float(jnp.std(want)) > 0.5  # logits of the scale the other presets have
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("name", MULTS + tuple(f"ssm_mults[{i}]" for i in range(5)))
def test_every_multiplier_acts_where_the_reference_applies_it(fam, name):
    """One multiplier changed in the program and in the reference's keys
    alike, on the SAME weights: the two still agree, and the logits have
    moved, so the multiplier is neither dropped nor applied elsewhere."""
    base = get_config("tiny-falcon-h1", dtype="float32")
    if name.startswith("ssm_mults"):
        i = int(name[-2])
        mults = list(base.ssm_mults)
        mults[i] *= 1.7
        cfg = dataclasses.replace(base, ssm_mults=tuple(mults))
    else:
        cfg = dataclasses.replace(base, **{name: getattr(base, name) * 1.7})
    params = T.init_params(base, jax.random.key(3))
    toks = jax.random.randint(jax.random.key(4), (19,), 0, base.vocab_size)
    was = fam.forward_logits(params, toks, file_keys(base))
    want = fam.forward_logits(params, toks, file_keys(cfg))
    with jax.default_matmul_precision("highest"):
        got = T.forward(params, toks[None], cfg.validate())[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4, rtol=3e-4)
    assert float(jnp.max(jnp.abs(want - was))) > 0.02, name


def test_seeded_weights_are_drawn_against_the_multipliers():
    """A matrix whose input or output a multiplier scales is drawn at the
    usual scale over that multiplier, so the product is what a stack
    without multipliers has."""
    cfg = get_config("tiny-falcon-h1", dtype="float32", d_model=320, head_dim=16, d_ff=640)
    (lp,), = T.init_params(cfg, jax.random.key(0))["segments"]
    D, Di, GN = cfg.d_model, cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
    std = lambda w: float(jnp.std(w))
    near = lambda got, want: abs(got / want - 1.0) < 0.08
    into = D ** -0.5
    assert near(std(lp["wq"]) * cfg.attn_in_mult, into)
    assert near(std(lp["wk"]) * cfg.attn_in_mult * cfg.key_mult, into)
    assert near(std(lp["w_gate"]) * cfg.mlp_gate_mult, into)
    assert near(std(lp["w_up"]), into)
    cols = np.cumsum([0, Di, Di, GN, GN])
    for (a, b), m in zip(zip(cols[:-1], cols[1:]), cfg.ssm_mults):
        assert near(std(lp["ssm_in"][..., a:b]) * cfg.ssm_in_mult * m, into)
    assert near(std(lp["ssm_dt_in"]) * cfg.ssm_in_mult * cfg.ssm_mults[4], into)
    damp = (2 * cfg.n_layers) ** -0.5
    assert near(std(lp["wo"]) * cfg.attn_out_mult, damp * (cfg.n_heads * cfg.head_dim) ** -0.5)
    assert near(std(lp["ssm_out"]) * cfg.ssm_out_mult, damp * Di ** -0.5)
    assert near(std(lp["w_down"]) * cfg.mlp_down_mult, damp * cfg.d_ff ** -0.5)


def test_neither_branch_is_dead(fam):
    """The attention zeroed, or the mixer, moves the logits by far more
    than the tolerance the program is held to: both carry a share of the
    residual stream (with the usual draws and the small published
    multipliers both would vanish beside the embedding)."""
    cfg = get_config("tiny-falcon-h1", dtype="float32")
    params = T.init_params(cfg, jax.random.key(3))
    toks = jax.random.randint(jax.random.key(4), (24,), 0, cfg.vocab_size)
    keys = file_keys(cfg)
    whole = fam.forward_logits(params, toks, keys)
    for branches, zeroed in (((False, True), "wo"), ((True, False), "ssm_out")):
        part = fam.forward_logits(params, toks, keys, branches=branches)
        assert float(jnp.max(jnp.abs(part - whole))) > 0.5, branches
        # the program with that branch's output projection zeroed is the
        # reference with the branch switched off
        (lp,), = params["segments"]
        cut = {**params, "segments": (({**lp, zeroed: jnp.zeros_like(lp[zeroed])},),)}
        with jax.default_matmul_precision("highest"):
            got = T.forward(cut, toks[None], cfg)[0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(part), atol=2e-4, rtol=2e-4)


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
    """Prefill (attention over the bucket, the chunked scan beside it)
    hands decode KV and a state in the same layer; logits at every later
    position are the reference's, which carries no cache."""
    cfg = get_config("tiny-falcon-h1", dtype="float32")
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
    cfg = get_config("tiny-falcon-h1", n_layers=5, layer_types=(KIND,) * 5)
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
    """Prefill's scan against decode's recurrence, and prefill's K/V
    against the rows decode writes, for the same layer."""
    cfg = get_config("tiny-falcon-h1", dtype="float32")
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
    for key in ("k", "v"):
        np.testing.assert_allclose(np.asarray(whole[key][:, :, :, :11]),
                                   np.asarray(step[key][:, :, :, :11]), atol=1e-4, rtol=1e-4)
    assert float(jnp.max(jnp.abs(whole["ssm"]))) > 0.0 and whole["ssm"].dtype == jnp.float32
    assert float(jnp.min(jnp.max(jnp.abs(whole["k"][:, 0, 0, :11]), axis=-1))) > 0.0


def test_right_padded_rows_take_their_state_at_their_own_last_real_token():
    cfg = get_config("tiny-falcon-h1", dtype="float32")
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


def test_decode_steps_every_layers_state_and_writes_one_kv_row_in_the_same_layer():
    cfg = get_config("tiny-falcon-h1")
    params = T.init_params(cfg, jax.random.key(0))
    cache = jax.tree.map(lambda a: a + 1, T.init_cache(cfg, 3, 8))
    pos = jnp.asarray([2, 5, 0])
    _, new, routing = T.decode_step(params, jnp.asarray([3, 4, 5]), pos, cache, cfg,
                                    return_routing=True)
    assert [int(v) for v in routing] == [0, 0, 0, 0, cfg.n_layers]  # 3 mixers a step
    assert np.asarray(new["ssm"] != cache["ssm"]).any(axis=(2, 3, 4)).all()  # [Lm, B]
    assert np.asarray(new["ssm_conv"] != cache["ssm_conv"]).any(axis=(2, 3)).all()
    moved = np.asarray(new["k"] != cache["k"]).any(axis=(2, 4))  # [La, B, T]
    want = np.zeros_like(moved)
    want[:, np.arange(3), np.asarray(pos)] = True
    np.testing.assert_array_equal(moved, want)
    held, read, written, slots = (int(v) for v in T.decode_kv_counts(cfg, cache, None, pos))
    assert held == read == cfg.n_layers * 3 * 8  # off a TPU the einsums read all they hold
    assert written == slots == cfg.n_layers * 3  # and the scatter writes a row of every slot


# -- the two kernels at the published shapes --------------------------------------

@pytest.mark.parametrize("dims", [(2, 2, 32, 128, 2, 256), (3, 2, 4, 16, 2, 32)],
                         ids=["published-32x128x256", "tiny"])
def test_the_update_kernel_is_the_update_in_jax_numpy_at_this_state_block(dims):
    """ops/ssm_update.py (interpreted here) at one slot's block of
    [32, 128, 256] float32 = 4 MB, two groups of 16 heads, 256 lanes."""
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
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=2e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(args[0][0]))
    assert float(jnp.max(jnp.abs(got[1] - args[0][1]))) > 0.1


@pytest.mark.parametrize("live", [(), (3,), (0, 1, 3, 4, 5, 7), tuple(range(8))],
                         ids=["none", "one", "several", "all"])
def test_the_attention_kernel_matches_the_einsums_at_twenty_query_heads(live):
    """ops/decode_attention.attend (interpreted here) at H = 20, Hkv = 4,
    heads of 128: 5 queries a KV head, a head count that is neither a
    power of two nor a multiple of the sublane tile, rows of 512 lanes."""
    from seldon_tpu.ops import decode_attention as da
    from seldon_tpu.ops.decode_attention import ATTEND_ATOL
    from tests.pallas_interpret import pallas_interpret

    B, Tw, layers, Hkv, Dh, G = 8, 512, 2, 4, 128, 5
    H, C = Hkv * G, Hkv * Dh
    ks = jax.random.split(jax.random.key(7), 5)
    bf16 = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, 1, H, Dh)).astype(bf16)
    kf = jax.random.normal(ks[1], (B, 1, Hkv, Dh)).astype(bf16)
    vf = (0.25 * jax.random.normal(ks[2], (B, 1, Hkv, Dh))).astype(bf16)
    cache = {"k": jax.random.normal(ks[3], (layers, B, 1, Tw, C), bf16),
             "v": 0.25 * jax.random.normal(ks[4], (layers, B, 1, Tw, C), bf16)}
    assert da.block_size((5, 64, 1, 1024, C), Dh, 2) == 512  # the cell's slab: 2 items a window
    block = 256  # two items in this test's window
    pos = jnp.array([0, 1, block - 1, block, block + 1, Tw - 1, 300, 77])
    active = jnp.zeros((B,), bool).at[jnp.array(live, int)].set(True)
    with pallas_interpret():
        got, *_ = jax.jit(lambda: da.attend(q, kf, vf, cache, jnp.int32(1),
                                            da.schedule(active, pos, Tw, block)))()
    want = T.gqa_attention_decode(
        q, cache["k"][1], cache["v"][1], kf, vf,
        jnp.arange(Tw)[None, None, :] < pos[:, None, None])
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == (B, 1, H * Dh) and np.isfinite(got).all()
    past = np.asarray(active & (pos > 0))
    np.testing.assert_allclose(got[past], want[past], atol=ATTEND_ATOL, rtol=0)
    alone = np.asarray(jnp.repeat(vf[:, 0], G, axis=1).reshape(B, 1, H * Dh), np.float32)
    np.testing.assert_array_equal(got[~past], alone[~past])


# -- through the engine ---------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = get_config("tiny-falcon-h1", dtype="float32")
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
    one bucket, and the decode chunk steps the SSM state and reads the KV
    of the same layer in the slab. Teacher-forced on each completion, the
    plain reference ranks every token the engine chose first, to within
    float32's order of summation: no request saw another's state or KV,
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
    # counted when a chunk's results reach the host; decode_steps at dispatch.
    # The layer counts once a step under each kind: 3 mixers, 3 layers of KV
    assert 0 < snap["ssm_layer_steps"] <= snap["decode_steps"] * cfg.n_layers
    assert snap["ssm_layer_steps"] % cfg.n_layers == 0
    steps = snap["ssm_layer_steps"] // cfg.n_layers
    assert snap["attn_kv_tokens_held"] == steps * cfg.n_layers * 2 * 64
    assert snap["attn_kv_tokens_read"] == snap["attn_kv_tokens_held"]  # off a TPU
    assert snap["moe_sparse_layer_steps"] == 0
    assert eng.cache_bytes() == T.cache_bytes(cfg, 2, 64)
    assert set(eng.cache_bytes()) == {"kv", "ssm", "ssm_conv"}


def test_cost_model_closed_forms_equal_the_cache_spec_and_the_tree():
    """A layer counted under both kinds is counted once for each:
    nowhere twice, nowhere not at all."""
    from seldon_tpu.servers import cost_model as cm

    cfg = get_config("tiny-falcon-h1", n_layers=5, layer_types=(KIND,) * 5)
    assert cm.kv_bytes_per_token(cfg) == 2 * 5 * cfg.n_kv_heads * cfg.head_dim * 2
    assert cm.state_bytes_per_slot(cfg) == 5 * (4 * 16 * 32 * 4 + 3 * 192 * 2)
    by_kind = T.cache_bytes(cfg, 3, 20)
    assert by_kind["kv"] == 3 * 20 * cm.kv_bytes_per_token(cfg)
    assert by_kind["ssm"] + by_kind["ssm_conv"] == 3 * cm.state_bytes_per_slot(cfg)
    tree = T.init_params(cfg, jax.random.key(0))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    # the attentions, the mixers, the SwiGLUs, the embedding and the head
    # at bf16; norms, biases, taps, A_log, D are noise
    assert 0.97 * held < cm.weight_bytes(cfg) <= held
    assert cm.attn_flops(cfg, 1, 10) == 4 * cfg.n_heads * cfg.head_dim * 10 * 5


def test_jaxserver_serves_the_preset_with_every_parameter_at_its_default(monkeypatch):
    """The unit, as the benchmark's launcher starts it: a preset name and
    nothing else about the model. /metadata gives the multipliers and the
    cache by kind; /metrics and the HBM ledger count the layer under both
    kinds of state."""
    from seldon_tpu.servers.jaxserver import JAXServer

    monkeypatch.setenv("HBM_LEDGER", "1")
    srv = JAXServer(preset="tiny-falcon-h1", max_slots=2, max_seq_len=48)
    srv.load()
    try:
        out = srv.generate({"prompt": "ab", "max_new_tokens": 5, "temperature": 0.0})
        assert out["completion_tokens"] >= 1
        md = json.loads(json.dumps(srv.init_metadata()))
        got = md["config"]
        assert got["layer_types"] == [KIND] * 3
        want = _family().model_config_kwargs(file_keys(srv.cfg))
        assert {k: got[k] for k in want} == want  # what run.check_metadata compares
        assert got["ssm_mults"] == list(srv.cfg.ssm_mults)
        assert md["cache_bytes"] == T.cache_bytes(srv.cfg, 2, 48)
        assert set(md["cache_bytes"]) == {"kv", "ssm", "ssm_conv"}
        gauges = {m["key"]: m["value"] for m in srv.metrics()}
        assert 0 < gauges["jaxserver_ssm_layer_steps"] \
            <= gauges["jaxserver_decode_steps"] * srv.cfg.n_layers
        assert gauges["jaxserver_attn_kv_tokens_held"] == \
            gauges["jaxserver_ssm_layer_steps"] * 2 * 48  # as many layers of KV as mixers
        cats = srv.engine.debug_hbm()["categories"]
        assert cats["ssm_state"]["bytes"] == md["cache_bytes"]["ssm"]
        assert cats["ssm_conv_state"]["bytes"] == md["cache_bytes"]["ssm_conv"]
        assert cats["kv_cache"]["bytes"] == md["cache_bytes"]["kv"]
        assert "conv_state" not in cats
    finally:
        srv.engine.stop()
