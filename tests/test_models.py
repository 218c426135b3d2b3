"""Model-stack tests on the virtual 8-device CPU mesh (conftest.py).

Mirrors the reference's tier-1 strategy (SURVEY.md §4): in-process, no
cluster, deterministic tiny fixtures.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from seldon_tpu.models import (
    ModelConfig,
    get_config,
    init_params,
    forward,
    prefill,
    decode_step,
    init_cache,
)
from seldon_tpu.models.generate import generate
from seldon_tpu.models.sampling import sample
from seldon_tpu.models.train import make_optimizer, make_sharded_train_step
from seldon_tpu.parallel import (
    MeshPlan,
    make_mesh,
    param_pspecs,
    shard_tree,
)

CFG = get_config("tiny")


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(0))


def test_forward_shapes(params):
    tokens = jnp.ones((2, 8), dtype=jnp.int32)
    logits = forward(params, tokens, CFG)
    assert logits.shape == (2, 8, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_forward_causality(params):
    """Changing a future token must not affect earlier logits."""
    key = jax.random.key(1)
    t1 = jax.random.randint(key, (1, 8), 0, CFG.vocab_size)
    t2 = t1.at[0, 7].set((t1[0, 7] + 1) % CFG.vocab_size)
    l1 = forward(params, t1, CFG)
    l2 = forward(params, t2, CFG)
    np.testing.assert_allclose(l1[0, :7], l2[0, :7], rtol=1e-5)


def test_prefill_decode_matches_forward(params):
    """Incremental decoding must reproduce teacher-forced logits."""
    key = jax.random.key(2)
    S = 6
    tokens = jax.random.randint(key, (2, S), 2, CFG.vocab_size)
    full = forward(params, tokens, CFG)  # [B,S,V]

    cache = init_cache(CFG, 2, 16)
    lens = jnp.array([S, S], dtype=jnp.int32)
    pf_logits, cache = prefill(params, tokens, lens, cache, CFG)
    np.testing.assert_allclose(pf_logits, full[:, S - 1], rtol=2e-2, atol=2e-2)

    # Feed the next token through decode_step; compare against forward on
    # the extended sequence.
    nxt = jnp.argmax(pf_logits, axis=-1).astype(jnp.int32)
    step_logits, cache = decode_step(
        params, nxt, jnp.array([S, S], jnp.int32), cache, CFG
    )
    ext = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    full_ext = forward(params, ext, CFG)
    np.testing.assert_allclose(step_logits, full_ext[:, S], rtol=5e-2, atol=5e-2)


def test_prefill_ragged_rows(params):
    """Right-padded rows take logits at their own last real token."""
    t_a = jnp.array([[5, 6, 7, 0, 0, 0]], dtype=jnp.int32)
    lens = jnp.array([3], dtype=jnp.int32)
    cache = init_cache(CFG, 1, 8)
    ragged, _ = prefill(params, t_a, lens, cache, CFG)
    # Same prompt without padding:
    cache2 = init_cache(CFG, 1, 8)
    exact, _ = prefill(
        params, t_a[:, :3], jnp.array([3], jnp.int32), cache2, CFG
    )
    np.testing.assert_allclose(ragged, exact, rtol=2e-2, atol=2e-2)


def test_generate_shapes_and_eos(params):
    tokens = jnp.array([[4, 5, 6, 0], [7, 8, 0, 0]], dtype=jnp.int32)
    lens = jnp.array([3, 2], dtype=jnp.int32)
    B = 2
    out, out_lens = generate(
        params,
        tokens,
        lens,
        jax.random.key(0),
        jnp.zeros((B,)),  # greedy
        jnp.zeros((B,), jnp.int32),
        jnp.ones((B,)),
        CFG,
        8,
    )
    assert out.shape == (2, 8)
    assert out_lens.shape == (2,)
    assert bool(jnp.all(out_lens >= 1)) and bool(jnp.all(out_lens <= 8))
    # Greedy generation is deterministic.
    out2, _ = generate(
        params, tokens, lens, jax.random.key(9),
        jnp.zeros((B,)), jnp.zeros((B,), jnp.int32), jnp.ones((B,)), CFG, 8,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_sampling_topk_topp():
    logits = jnp.array([[10.0, 9.0, 1.0, 0.0]])
    # top_k=1 == greedy regardless of temperature.
    tok = sample(
        logits, jax.random.key(0), jnp.array([5.0]), jnp.array([1]),
        jnp.array([1.0]),
    )
    assert int(tok[0]) == 0
    # top_p tiny keeps only the argmax.
    tok = sample(
        logits, jax.random.key(1), jnp.array([5.0]), jnp.array([0]),
        jnp.array([1e-6]),
    )
    assert int(tok[0]) == 0
    # temperature 0 = greedy.
    tok = sample(
        logits, jax.random.key(2), jnp.array([0.0]), jnp.array([0]),
        jnp.array([1.0]),
    )
    assert int(tok[0]) == 0


def test_moe_forward():
    cfg = get_config("tiny-moe")
    p = init_params(cfg, jax.random.key(0))
    logits = forward(p, jnp.ones((2, 4), jnp.int32), cfg)
    assert logits.shape == (2, 4, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_sharded_forward_matches_single(params):
    """TP+DP sharded forward == unsharded forward (GSPMD correctness)."""
    mesh = make_mesh(MeshPlan(dp=2, tp=2))
    sharded = shard_tree(params, param_pspecs(CFG), mesh)
    tokens = jax.random.randint(jax.random.key(3), (4, 8), 0, CFG.vocab_size)
    ref = forward(params, tokens, CFG)
    tok_sh = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    out = jax.jit(lambda p, t: forward(p, t, CFG))(sharded, tok_sh)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-2,
                               atol=2e-2)


@pytest.mark.parametrize("plan", [
    MeshPlan(dp=2, tp=2, sp=2),
    MeshPlan(dp=1, tp=2, sp=1, ep=2),
])
def test_train_step_sharded(plan):
    cfg = get_config("tiny-moe" if plan.ep > 1 else "tiny")
    mesh = make_mesh(plan)
    opt = make_optimizer(total_steps=10)
    init_fn, step_fn = make_sharded_train_step(mesh, cfg, opt)
    state = init_fn(jax.random.key(0))
    B, S = 4, 16
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    mask = jnp.ones((B, S), jnp.float32)
    losses = []
    for _ in range(3):
        state, metrics = step_fn(state, tokens, mask)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    # Overfit signal: loss decreases on a repeated batch.
    assert losses[-1] < losses[0]


def test_int8_kv_cache_matches_bf16_decode():
    """kv_cache_dtype='int8': teacher-forced decode logits must track the
    bf16 cache step-by-step (per-token-head symmetric quantization).

    Teacher forcing (same token sequence through both paths) rather than
    comparing greedy outputs: a random-init tiny model has near-uniform
    logits where argmax gaps (~1e-3) sit below even well-behaved
    quantization error, so exact token equality is tie-breaking luck, not
    a fidelity signal. Per-step relative logit error IS the signal — the
    measured error of the factored-scale decode path is <0.005/step."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from seldon_tpu.models import get_config, init_params, transformer

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    prompt = jnp.array([[5, 6, 7, 8]], jnp.int32)
    forced = [5, 9, 3, 200, 77, 13, 42, 250]

    def run(c):
        cache = transformer.init_cache(c, 1, 32)
        if c.kv_cache_dtype == "int8":
            assert cache["k"].dtype == jnp.int8
            # One row a token, one scale a (token, head).
            L, B, one, T, C = cache["k"].shape
            assert (one, C) == (1, c.n_kv_heads * c.head_dim)
            assert cache["k_scale"].shape == (L, B, c.n_kv_heads, T)
        logits, cache = transformer.prefill(
            params, prompt, jnp.array([4]), cache, c
        )
        lgs = [logits]
        pos = jnp.array([4], jnp.int32)
        for t in forced:
            lg, cache = transformer.decode_step(
                params, jnp.array([t], jnp.int32), pos, cache, c
            )
            lgs.append(lg)
            pos = pos + 1
        return lgs

    ref = run(cfg)
    quant = run(dataclasses.replace(cfg, kv_cache_dtype="int8"))
    # Prefill never reads the cache -> exactly equal logits at step 0.
    assert float(jnp.max(jnp.abs(ref[0] - quant[0]))) == 0.0
    for i, (a, b) in enumerate(zip(ref[1:], quant[1:])):
        rel = float(jnp.max(jnp.abs(a - b))) / float(jnp.max(jnp.abs(a)))
        assert rel < 0.02, (i, rel)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("side", [1, 2, 8])
def test_decode_attention_reads_rows_of_heads_as_the_plain_one(side, kv_dtype):
    """gqa_attention_decode over cache rows that hold `side` of the 8 KV
    heads side by side ([B, 8 / side, T, side * Dh]: 8 is the slab,
    cache_spec; 1 the paged pool's view), in float32, against attention
    written out per head on the dequantized cache. int8 KV keeps its
    scales [B, Hkv, T], per (token, head), and neighbouring heads of a
    row differ in scale by 100 x: a query that took its neighbour's
    scale would be off by that factor."""
    from seldon_tpu.models import transformer as T

    B, T_, Hkv, G, Dh = 3, 16, 8, 2, 8
    ks = jax.random.split(jax.random.key(11), 7)
    q = jax.random.normal(ks[0], (B, 1, Hkv * G, Dh))
    kf, vf = (jax.random.normal(k, (B, 1, Hkv, Dh)) for k in ks[3:5])
    plen = jnp.asarray([5, 16, 1])
    mask_lt = jnp.arange(T_)[None, None, :] < plen[:, None, None]
    scales = {}
    if kv_dtype == "int8":
        ck, cv = (jax.random.randint(k, (B, Hkv, T_, Dh), -127, 128
                                     ).astype(jnp.int8) for k in ks[1:3])
        per_head = jnp.where(jnp.arange(Hkv) % 2 == 0, 1.0, 100.0)
        for name, k in (("k_scale", ks[5]), ("v_scale", ks[6])):
            scales[name] = (
                0.01 * per_head[None, :, None]
                * jax.random.uniform(k, (B, Hkv, T_), minval=0.5, maxval=1.5)
            ).astype(jnp.bfloat16)
        dk = ck.astype(jnp.float32) * scales["k_scale"].astype(
            jnp.float32)[..., None]
        dv = cv.astype(jnp.float32) * scales["v_scale"].astype(
            jnp.float32)[..., None]
    else:
        ck, cv = (jax.random.normal(k, (B, Hkv, T_, Dh)) for k in ks[1:3])
        dk, dv = ck, cv

    def rows(c):  # [B, Hkv, T, Dh] -> [B, Hkv / side, T, side * Dh]
        return c.reshape(B, Hkv // side, side, T_, Dh).transpose(
            0, 1, 3, 2, 4).reshape(B, Hkv // side, T_, side * Dh)

    got = T.gqa_attention_decode(q, rows(ck), rows(cv), kf, vf, mask_lt,
                                 **scales)
    # Plain attention, head by head: the visible cache columns, then
    # the fresh token's own.
    want = np.zeros((B, Hkv * G, Dh), np.float32)
    for b in range(B):
        n = int(plen[b])
        for h in range(Hkv * G):
            kv = h // G
            keys = np.concatenate([np.asarray(dk[b, kv, :n]),
                                   np.asarray(kf[b, 0, kv])[None]])
            vals = np.concatenate([np.asarray(dv[b, kv, :n]),
                                   np.asarray(vf[b, 0, kv])[None]])
            sc = keys @ np.asarray(q[b, 0, h]) / np.sqrt(Dh)
            w = np.exp(sc - sc.max())
            want[b, h] = (w / w.sum()) @ vals
    np.testing.assert_allclose(
        np.asarray(got).reshape(B, Hkv * G, Dh), want, atol=2e-5, rtol=2e-4)


def test_int8_kv_cache_engine_end_to_end():
    """The continuous-batching engine serves with a quantized cache."""
    import dataclasses

    import jax
    import numpy as np

    from seldon_tpu.models import get_config, init_params
    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    cfg = dataclasses.replace(get_config("tiny"), kv_cache_dtype="int8")
    params = init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(max_slots=4, max_seq_len=64, prompt_buckets=(16,),
                     max_admit=2, decode_chunk=4),
    )
    eng.start()
    try:
        out = eng.generate_blocking(
            [5, 6, 7], SamplingParams(max_new_tokens=12, seed=0)
        )
        assert len(out["token_ids"]) >= 1
        assert out["ttft_ms"] is not None
    finally:
        eng.stop()


def test_int8_weight_quantization_close_to_bf16():
    """Weight-only int8 (per-output-channel scales): forward logits stay
    close and greedy decode matches on tiny geometry; works for dense
    AND MoE blocks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_tpu.models import forward, get_config, init_params
    from seldon_tpu.models.quantize import is_quantized, quantize_params

    for preset in ("tiny", "tiny-moe"):
        cfg = get_config(preset)
        params = init_params(cfg, jax.random.key(0))
        q = quantize_params(params)
        assert is_quantized(q) and not is_quantized(params)
        assert q["blocks"]["wq"].dtype == jnp.int8
        assert q["embed"].dtype == jnp.int8
        tokens = jax.random.randint(jax.random.key(1), (2, 12), 0,
                                    cfg.vocab_size)
        ref = np.asarray(forward(params, tokens, cfg), np.float32)
        out = np.asarray(forward(q, tokens, cfg), np.float32)
        denom = np.abs(ref).max() + 1e-6
        rel = np.abs(ref - out).max() / denom
        assert rel < 0.08, (preset, rel)
        # Rank agreement at the argmax (what greedy decode consumes).
        agree = (ref.argmax(-1) == out.argmax(-1)).mean()
        assert agree > 0.9, (preset, agree)


@pytest.mark.parametrize("S", [1, 4])
@pytest.mark.parametrize("weights", ["int8", "bf16"])
def test_qkv_fence_moves_no_value(weights, S):
    """transformer._qkv fences the flat wq and wk products of a decode
    step (S == 1) so that the TPU compiler keeps them flat; at S == 1
    and at S == 4 it returns what the unfenced expression returns, to
    the bit, for an int8 and a bf16 tree."""
    from seldon_tpu.models import transformer
    from seldon_tpu.models.quantize import quantize_params

    cfg = CFG
    tree = init_params(cfg, jax.random.key(0))
    if weights == "int8":
        tree = quantize_params(tree)
    bp = jax.tree.map(lambda a: a[1], tree["blocks"])
    B, Hkv, Dh = 3, cfg.n_kv_heads, cfg.head_dim
    h = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model),
                          jnp.float32).astype(tree["final_norm"].dtype)
    positions = jnp.arange(7, 7 + S)[None, :].repeat(B, 0)
    inv_freq = transformer.rope_frequencies(cfg)

    def unfenced(h, bp):
        q = transformer._qdot(h, bp, "wq", cfg).reshape(B, S, cfg.n_heads, Dh)
        k = transformer._qdot(h, bp, "wk", cfg).reshape(B, S, Hkv, Dh)
        v = transformer._qdot(h, bp, "wv", cfg).reshape(B, S, Hkv, Dh)
        return (transformer.apply_rope(q, positions, inv_freq),
                transformer.apply_rope(k, positions, inv_freq), v)

    got = jax.jit(lambda h, bp: transformer._qkv(
        h, bp, cfg, positions, inv_freq))(h, bp)
    want = jax.jit(unfenced)(h, bp)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
    text = jax.jit(lambda h, bp: transformer._qkv(
        h, bp, cfg, positions, inv_freq)).lower(h, bp).as_text()
    assert text.count("optimization_barrier") == (2 if S == 1 else 0)


def test_w8a8_matches_bf16_math():
    """act_dtype='int8' (W8A8: dynamic per-token A8 + s8 x s8 matmuls):
    logits stay close to the int8-weight/bf16-math path and greedy
    argmax mostly agrees. Also: act_dtype is a NO-OP on unquantized
    weights (the _qdot fallback is the same contraction)."""
    import dataclasses

    import jax
    import numpy as np

    from seldon_tpu.models import forward, get_config, init_params
    from seldon_tpu.models.quantize import quantize_params

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    q = quantize_params(params)
    tokens = jax.random.randint(jax.random.key(1), (2, 12), 0,
                                cfg.vocab_size)
    cfg_a8 = dataclasses.replace(cfg, weight_dtype="int8",
                                 act_dtype="int8")
    ref = np.asarray(forward(q, tokens, cfg), np.float32)
    out = np.asarray(forward(q, tokens, cfg_a8), np.float32)
    denom = np.abs(ref).max() + 1e-6
    rel = np.abs(ref - out).max() / denom
    assert rel < 0.08, rel
    agree = (ref.argmax(-1) == out.argmax(-1)).mean()
    assert agree > 0.9, agree
    # bf16-weight params: act_dtype must be a no-op (falls back).
    plain = np.asarray(
        forward(params, tokens, dataclasses.replace(cfg, act_dtype="int8")),
        np.float32)
    base = np.asarray(forward(params, tokens, cfg), np.float32)
    np.testing.assert_allclose(plain, base, rtol=0, atol=0)


def test_w8a8_matches_bf16_math_decode_stepwise():
    """Teacher-forced decode with W8A8 matmuls tracks the
    int8-weight/bf16-math path step by step (same methodology and bars
    as the int8-KV acceptance test above: per-step relative logit
    error, not greedy-token luck)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from seldon_tpu.models import get_config, init_params, transformer
    from seldon_tpu.models.quantize import quantize_params

    cfg = dataclasses.replace(get_config("tiny"), weight_dtype="int8")
    params = quantize_params(init_params(get_config("tiny"),
                                         jax.random.key(0)))
    prompt = jnp.array([[5, 6, 7, 8]], jnp.int32)
    forced = [5, 9, 3, 200, 77, 13, 42, 250]

    def run(c):
        cache = transformer.init_cache(c, 1, 32)
        logits, cache = transformer.prefill(
            params, prompt, jnp.array([4]), cache, c
        )
        lgs = [logits]
        pos = jnp.array([4], jnp.int32)
        for t in forced:
            lg, cache = transformer.decode_step(
                params, jnp.array([t], jnp.int32), pos, cache, c
            )
            lgs.append(lg)
            pos = pos + 1
        return lgs

    ref = run(cfg)
    a8 = run(dataclasses.replace(cfg, act_dtype="int8"))
    for i, (a, b) in enumerate(zip(ref, a8)):
        rel = float(jnp.max(jnp.abs(a - b))) / float(jnp.max(jnp.abs(a)))
        assert rel < 0.05, (i, rel)


def test_w8a8_full_serving_path():
    """Engine decode with W8A8 matmuls + int8 KV end-to-end."""
    import dataclasses

    import jax

    from seldon_tpu.models import get_config, init_params
    from seldon_tpu.models.quantize import quantize_params
    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    cfg = dataclasses.replace(get_config("tiny"), weight_dtype="int8",
                              kv_cache_dtype="int8", act_dtype="int8")
    params = quantize_params(init_params(cfg, jax.random.key(0)))
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(max_slots=4, max_seq_len=64, prompt_buckets=(16,),
                     max_admit=2, decode_chunk=4),
    )
    eng.start()
    try:
        out = eng.generate_blocking(
            [5, 6, 7], SamplingParams(max_new_tokens=10, seed=0)
        )
        assert len(out["token_ids"]) >= 1
    finally:
        eng.stop()


def test_int8_weights_full_serving_path():
    """Engine decode on quantized weights (+ optionally quantized cache)."""
    import dataclasses

    import jax

    from seldon_tpu.models import get_config, init_params
    from seldon_tpu.models.quantize import quantize_params
    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    cfg = dataclasses.replace(get_config("tiny"), weight_dtype="int8",
                              kv_cache_dtype="int8")
    params = quantize_params(init_params(cfg, jax.random.key(0)))
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(max_slots=4, max_seq_len=64, prompt_buckets=(16,),
                     max_admit=2, decode_chunk=4),
    )
    eng.start()
    try:
        out = eng.generate_blocking(
            [5, 6, 7], SamplingParams(max_new_tokens=10, seed=0)
        )
        assert len(out["token_ids"]) >= 1
    finally:
        eng.stop()


def test_quantized_checkpoint_roundtrip(tmp_path):
    """save/load of an int8-quantized tree (skeleton must carry the
    *_scale leaves per config.json's weight_dtype)."""
    import dataclasses

    import jax
    import numpy as np

    from seldon_tpu.models import get_config, init_params
    from seldon_tpu.models.quantize import quantize_params
    from seldon_tpu.servers import checkpoint as ckpt

    cfg = dataclasses.replace(get_config("tiny"), weight_dtype="int8")
    params = quantize_params(init_params(cfg, jax.random.key(0)))
    # Idempotence: re-quantizing must be a no-op, not scale corruption.
    assert quantize_params(params) is params

    path = str(tmp_path / "ck")
    ckpt.save_checkpoint(path, params, cfg)
    restored, cfg2 = ckpt.load_checkpoint(path)
    assert cfg2.weight_dtype == "int8"
    np.testing.assert_array_equal(
        np.asarray(restored["blocks"]["wq"]),
        np.asarray(params["blocks"]["wq"]),
    )
    np.testing.assert_allclose(
        np.asarray(restored["blocks"]["wq_scale"]),
        np.asarray(params["blocks"]["wq_scale"]),
    )


def test_jaxserver_weight_dtype_override(tmp_path):
    """JAXServer(weight_dtype='int8') quantizes whatever the checkpoint
    loaded (the HF-bf16-on-disk -> int8-serving path)."""
    import jax
    import jax.numpy as jnp

    from seldon_tpu.servers.jaxserver import JAXServer

    srv = JAXServer(preset="tiny", max_slots=2, max_seq_len=48,
                    weight_dtype="int8")
    srv.load()
    try:
        assert srv.cfg.weight_dtype == "int8"
        assert srv.params["blocks"]["wq"].dtype == jnp.int8
        out = srv.generate({"prompt": "ab", "max_new_tokens": 4, "seed": 1})
        assert out["completion_tokens"] >= 1
    finally:
        srv.engine.stop()
