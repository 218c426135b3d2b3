"""graftkern: block-sparse ragged paged-attention kernel legs.

The contract under test (ops/ragged_paged_attention module doc):

 * the ops-level walkers (``partials_sparse``, and ``partials_pallas``
   under ``pallas_interpret()`` — the only way the Mosaic leg runs off
   a TPU) agree with the full-width ``partials_reference``
   oracle on every bound shape — empty, single-block,
   partially-filled-block, multi-block;
 * the masked-MATCHED two-pass walk (``sparse_max_sum`` +
   ``sparse_weighted_value``) reproduces the masked engine kernels'
   attention output BIT-EXACTLY — same term set, softmax weights
   rounded to the activation dtype, dequant pinned at a
   materialization boundary — for bf16 AND int8 pools;
 * ``ragged_wave`` / ``verify_wave`` under ``kernel="sparse"`` emit
   greedy token streams IDENTICAL to ``kernel="masked"`` across
   prefill / chunk-continuation / decode / verify rows, including the
   decode-only skip cond and the block-budget masked fallback;
   ``kernel="pallas"`` (interpreted on CPU) matches greedy tokens on the
   same waves and stays within :data:`RAGGED_LOGITS_ATOL` on raw
   logits;
 * the engine end to end: ``ragged_kernel="sparse"`` streams equal
   masked's bit for bit, the static lattice stays
   ``["deactivate", "ragged/C"]`` and nothing retraces live.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_tpu.models import spec_decode, transformer
from seldon_tpu.models import ragged_attention as ra
from seldon_tpu.models.config import PRESETS
from seldon_tpu.ops import ragged_paged_attention as rpa

from pallas_interpret import pallas_interpret

TINY = PRESETS["tiny"]
BLOCK, NBS = 8, 16
SMAX = BLOCK * NBS
B = 4


def _cfg(kv_dtype):
    return dataclasses.replace(TINY, kv_cache_dtype=kv_dtype)


def _pool_and_table(cfg, key, n_rows=B, nbs=NBS):
    """int8/bf16 paged pool with disjoint per-row tables (trash = 0)
    filled with quantized random normals on every block."""
    nb = n_rows * nbs + 1
    pool1 = transformer.init_paged_cache(cfg, nb, BLOCK)
    # init_paged_cache stacks layers; tests walk ONE layer slice.
    layer = {k: v[0] for k, v in pool1.items()}
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    raw_k = jax.random.normal(jax.random.fold_in(key, 1),
                              (nb, hkv, BLOCK, dh), jnp.float32)
    raw_v = jax.random.normal(jax.random.fold_in(key, 2),
                              (nb, hkv, BLOCK, dh), jnp.float32)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = transformer._quantize_kv(raw_k.astype(jnp.bfloat16))
        vq, vs = transformer._quantize_kv(raw_v.astype(jnp.bfloat16))
        layer = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        layer = {"k": raw_k.astype(layer["k"].dtype),
                 "v": raw_v.astype(layer["v"].dtype)}
    table = jnp.asarray(
        np.stack([1 + i * nbs + np.arange(nbs) for i in range(n_rows)])
        .astype(np.int32))
    return layer, table


def _combine(parts):
    """(m, l, acc) -> attention output, the partials' closed form."""
    m, l, acc = parts
    return acc / jnp.maximum(l, 1e-30)


# Empty row, partial block, exact block edge, multi-block: the bound
# shapes the walker's trip count and tail masking must each survive.
BOUNDS = np.array([0, 5, BLOCK, 61], np.int32)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_partials_sparse_matches_reference(kv_dtype):
    cfg = _cfg(kv_dtype)
    key = jax.random.key(0)
    layer, table = _pool_and_table(cfg, key)
    sq = 2
    q = jax.random.normal(
        jax.random.fold_in(key, 3),
        (B, sq, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads,
         cfg.head_dim), jnp.bfloat16)
    bound = jnp.broadcast_to(jnp.asarray(BOUNDS)[:, None], (B, sq))
    ref = _combine(rpa.partials_reference(q, layer, table, bound))
    got = _combine(rpa.partials_sparse(q, layer, table, bound))
    live = BOUNDS > 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live],
        np.asarray(ref, np.float32)[live], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_partials_pallas_interpret_matches_reference(kv_dtype):
    cfg = _cfg(kv_dtype)
    key = jax.random.key(1)
    layer, table = _pool_and_table(cfg, key)
    sq = 1
    q = jax.random.normal(
        jax.random.fold_in(key, 3),
        (B, sq, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads,
         cfg.head_dim), jnp.bfloat16)
    bound = jnp.broadcast_to(jnp.asarray(BOUNDS)[:, None], (B, sq))
    ref = _combine(rpa.partials_reference(q, layer, table, bound))
    with pallas_interpret():
        got = _combine(rpa.ragged_paged_partials(q, layer, table, bound,
                                                 mode="pallas"))
    live = BOUNDS > 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live],
        np.asarray(ref, np.float32)[live], atol=1e-4, rtol=1e-4)


def test_pallas_leg_raises_instead_of_falling_back(monkeypatch):
    """mode="pallas" means the Mosaic kernel or an error — never the
    jnp walker under its name. Off a TPU and outside the interpret
    context the lowering refuses; a kernel that fails for any other
    reason (monkeypatched) propagates too."""
    cfg = _cfg("bf16")
    key = jax.random.key(2)
    layer, table = _pool_and_table(cfg, key)
    q = jnp.zeros((B, 1, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads,
                   cfg.head_dim), jnp.bfloat16)
    bound = jnp.broadcast_to(jnp.asarray(BOUNDS)[:, None], (B, 1))
    with pytest.raises(ValueError, match="interpret mode"):
        rpa.ragged_paged_partials(q, layer, table, bound, mode="pallas")

    def boom(*a, **kw):
        raise RuntimeError("mosaic refused")

    monkeypatch.setattr(rpa, "partials_pallas", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        rpa.ragged_paged_partials(q, layer, table, bound, mode="pallas")


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_matched_two_pass_is_bit_exact_vs_masked_convention(kv_dtype):
    """The greedy-parity core: the two-pass walk folded with a fresh
    causal suffix must reproduce gqa_attention's prefix+suffix output
    to the BIT — this is what makes sparse-vs-masked streams identical
    rather than merely close."""
    cfg = _cfg(kv_dtype)
    key = jax.random.key(2)
    layer, table = _pool_and_table(cfg, key)
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // hkv
    sc = 4
    qr = jax.random.normal(jax.random.fold_in(key, 3),
                           (B, sc, hkv, g, dh), jnp.bfloat16)
    k_f = jax.random.normal(jax.random.fold_in(key, 4),
                            (B, sc, hkv, dh), jnp.bfloat16)
    v_f = jax.random.normal(jax.random.fold_in(key, 5),
                            (B, sc, hkv, dh), jnp.bfloat16)
    bound1 = jnp.asarray(BOUNDS)
    bound2 = jnp.broadcast_to(bound1[:, None], (B, sc)).astype(jnp.int32)
    smask = jnp.broadcast_to(
        jnp.tril(jnp.ones((sc, sc), bool))[None], (B, sc, sc))

    def masked():
        # _run_blocks_prefill_prefix's exact shape: gather the full
        # window, dequantize, CONCAT with the fresh suffix (the
        # materialization boundary that rounds the dequant), one
        # softmax-in-f32 / bf16-weight value einsum.
        view = {kk: jnp.moveaxis(layer[kk][table], 1, 2).reshape(
            (B, hkv, SMAX) + layer[kk].shape[3:]) for kk in layer}
        pk = view["k"].astype(qr.dtype)
        pv = view["v"].astype(qr.dtype)
        if "k_scale" in view:
            pk = pk * view["k_scale"][..., None].astype(qr.dtype)
            pv = pv * view["v_scale"][..., None].astype(qr.dtype)
        k_all = jnp.concatenate([pk.transpose(0, 2, 1, 3), k_f], axis=1)
        v_all = jnp.concatenate([pv.transpose(0, 2, 1, 3), v_f], axis=1)
        pmask = jnp.broadcast_to(
            jnp.arange(SMAX)[None, None, :] < bound1[:, None, None],
            (B, sc, SMAX))
        mask = jnp.concatenate([pmask, smask], axis=2)
        scores = jnp.einsum("bskgd,btkd->bkgst", qr, k_all,
                            preferred_element_type=jnp.float32) / (dh**0.5)
        scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
        w = jax.nn.softmax(scores.astype(jnp.float32),
                           axis=-1).astype(qr.dtype)
        return jnp.einsum("bkgst,btkd->bskgd", w, v_all)

    def sparse():
        s_f = jnp.einsum("bskgd,btkd->bkgst", qr, k_f,
                         preferred_element_type=jnp.float32) / (dh**0.5)
        s_f = jnp.where(smask[:, None, None, :, :], s_f, rpa.NEG_INF)
        m_p, l_p = rpa.sparse_max_sum(qr, layer, table, bound2,
                                      dequant=True)
        m_t = jnp.maximum(m_p, jnp.max(s_f, axis=-1, keepdims=True))
        p_f = jnp.exp(s_f - m_t)
        l_t = l_p * jnp.exp(m_p - m_t) + jnp.sum(p_f, axis=-1,
                                                 keepdims=True)
        acc = rpa.sparse_weighted_value(qr, layer, table, bound2,
                                        m_t, l_t, dequant=True)
        acc = acc + jnp.einsum(
            "bkgst,bktd->bkgsd", (p_f / l_t).astype(qr.dtype),
            v_f.transpose(0, 2, 1, 3).astype(qr.dtype),
            preferred_element_type=jnp.float32)
        return acc.astype(qr.dtype).transpose(0, 3, 1, 2, 4)

    want = np.asarray(jax.jit(masked)(), np.float32)
    got = np.asarray(jax.jit(sparse)(), np.float32)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Wave-level greedy parity (the smoke the bench gate rides on)
# ---------------------------------------------------------------------------


def _seed_row(cfg, params, pool, table, row, n, seed):
    """Prefill n tokens through the DENSE path and scatter the KV into
    the row's pool blocks; returns (pool, greedy next token)."""
    tks = jnp.asarray(
        np.random.default_rng(seed).integers(2, cfg.vocab_size,
                                             size=(1, n)), jnp.int32)
    cache = transformer.init_cache(cfg, 1, SMAX)
    logits, cache = transformer.prefill(
        params, tks, jnp.asarray([n], jnp.int32), cache, cfg)
    # The dense prefill fills slab rows; the pool holds KV by head.
    wr = transformer.kv_by_head(
        {k: cache[k][:, 0:1, :, :n] for k in cache}, cfg)
    pool = transformer.paged_scatter_tokens(
        pool, wr, table[row:row + 1], jnp.arange(n)[None, :])
    return pool, int(jnp.argmax(logits[0]))


def _wave_fixture(kv_dtype):
    """(cfg, params, table, state, wave-args): row0 cold prefill final,
    row1 chunk continuation, row2 mid-decode, row3 idle."""
    cfg = _cfg(kv_dtype)
    params = transformer.init_params(cfg, jax.random.key(0))
    pool = transformer.init_paged_cache(cfg, B * NBS + 1, BLOCK)
    table = jnp.asarray(
        np.stack([1 + i * NBS + np.arange(NBS) for i in range(B)])
        .astype(np.int32))
    sc = 8
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(2, cfg.vocab_size, size=(B * sc,)),
                       jnp.int32)
    pool, _ = _seed_row(cfg, params, pool, table, 1, 8, 101)
    pool, last2 = _seed_row(cfg, params, pool, table, 2, 37, 202)
    state = {
        "cache": pool,
        "last_tok": jnp.asarray([0, 0, last2, 0], jnp.int32),
        "pos": jnp.asarray([0, 0, 37, 0], jnp.int32),
        "active": jnp.asarray([False, False, True, False]),
        "temp": jnp.zeros((B,), jnp.float32),
        "top_k": jnp.zeros((B,), jnp.int32),
        "top_p": jnp.ones((B,), jnp.float32),
        "seeds": jnp.asarray([11, 22, 33, 44], jnp.int32),
        "remaining": jnp.asarray([0, 0, 3, 0], jnp.int32),
    }
    args = dict(
        tokens=toks,
        plens=jnp.asarray([6, 20, 0, 0], jnp.int32),
        starts=jnp.asarray([0, 8, SMAX, SMAX], jnp.int32),
        seeds=state["seeds"],
        temps=state["temp"],
        top_ks=state["top_k"],
        top_ps=state["top_p"],
        max_news=jnp.asarray([5, 5, 5, 5], jnp.int32),
        finals=jnp.asarray([True, False, False, False]),
        is_prefill=jnp.asarray([True, True, False, False]),
    )
    return cfg, params, table, state, args


def _run_wave(cfg, params, table, state, args, kernel, block_budget=0):
    st = jax.tree.map(lambda x: x, state)
    st2, first, fdone, toks, valid, _ = ra.ragged_wave(
        params, st, table, args["tokens"], args["plens"], args["starts"],
        args["seeds"], args["temps"], args["top_ks"], args["top_ps"],
        args["max_news"], args["finals"], args["is_prefill"], cfg,
        kernel=kernel, block_budget=block_budget)
    return dict(first=np.asarray(first), fdone=np.asarray(fdone),
                toks=np.asarray(toks), valid=np.asarray(valid),
                pos=np.asarray(st2["pos"]),
                last=np.asarray(st2["last_tok"]))


def _assert_wave_equal(m, s):
    live_pf = slice(0, 2)  # rows 0-1 are the prefill rows
    np.testing.assert_array_equal(m["first"][live_pf], s["first"][live_pf])
    np.testing.assert_array_equal(m["fdone"][live_pf], s["fdone"][live_pf])
    live = m["valid"][0]
    np.testing.assert_array_equal(m["toks"][0][live], s["toks"][0][live])
    np.testing.assert_array_equal(m["pos"], s["pos"])
    np.testing.assert_array_equal(m["last"], s["last"])


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_wave_sparse_matches_masked(kv_dtype):
    fix = _wave_fixture(kv_dtype)
    m = _run_wave(*fix, kernel="masked")
    s = _run_wave(*fix, kernel="sparse")
    _assert_wave_equal(m, s)


def test_wave_pallas_interpret_matches_masked():
    # int8 only: the fused-dequant leg is the one pallas exists for;
    # interpret-mode is too slow to sweep both dtypes here.
    fix = _wave_fixture("int8")
    m = _run_wave(*fix, kernel="masked")
    with pallas_interpret():
        p = _run_wave(*fix, kernel="pallas")
    _assert_wave_equal(m, p)


def test_wave_decode_only_skip_cond():
    """Decode-only waves take the lax.cond prefill skip; tokens must
    still match masked (which always runs its dead prefill leg)."""
    cfg, params, table, state, args = _wave_fixture("bf16")
    args = dict(args,
                plens=jnp.zeros((B,), jnp.int32),
                starts=jnp.full((B,), SMAX, jnp.int32),
                finals=jnp.zeros((B,), bool),
                is_prefill=jnp.zeros((B,), bool))
    m = _run_wave(cfg, params, table, state, args, kernel="masked")
    s = _run_wave(cfg, params, table, state, args, kernel="sparse")
    live = m["valid"][0]
    np.testing.assert_array_equal(m["toks"][0][live], s["toks"][0][live])
    np.testing.assert_array_equal(m["pos"], s["pos"])


def test_wave_block_budget_fallback():
    """block_budget=1 < the live walk's 5 blocks: the sparse leg must
    fall back to the masked head in-trace and reproduce it exactly."""
    fix = _wave_fixture("bf16")
    m = _run_wave(*fix, kernel="masked")
    s = _run_wave(*fix, kernel="sparse", block_budget=1)
    _assert_wave_equal(m, s)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_prefill_logits_within_atol(kv_dtype):
    """Raw-logit pin: sparse stays bit-exact on the prefill leg; pallas
    stays within the documented RAGGED_LOGITS_ATOL envelope."""
    cfg, params, table, state, args = _wave_fixture(kv_dtype)
    bound = jnp.where(args["is_prefill"], args["starts"],
                      0).astype(jnp.int32)
    toks2 = args["tokens"].reshape(B, -1)

    def masked():
        view = transformer.paged_prefix_view(state["cache"], table, NBS)
        return transformer.prefill_with_prefix(
            params, toks2, args["plens"], view, args["starts"], cfg)[0]

    def leg(kern):
        return ra._prefill_logits_sparse(
            params, toks2, args["plens"], args["starts"], bound,
            state["cache"], table, cfg, kern)[0]

    want = np.asarray(jax.jit(masked)(), np.float32)
    got_s = np.asarray(jax.jit(lambda: leg("sparse"))(), np.float32)
    with pallas_interpret():
        got_p = np.asarray(jax.jit(lambda: leg("pallas"))(), np.float32)
    live = np.asarray(args["is_prefill"])
    np.testing.assert_array_equal(got_s[live], want[live])
    assert np.abs(got_p[live] - want[live]).max() <= rpa.RAGGED_LOGITS_ATOL


# ---------------------------------------------------------------------------
# Verify-wave greedy parity (the spec leg)
# ---------------------------------------------------------------------------


def _verify_fixture(kv_dtype):
    cfg = _cfg(kv_dtype)
    params = transformer.init_params(cfg, jax.random.key(0))
    pool = transformer.init_paged_cache(cfg, B * NBS + 1, BLOCK)
    table = jnp.asarray(
        np.stack([1 + i * NBS + np.arange(NBS) for i in range(B)])
        .astype(np.int32))
    hist = [13, 21, 37, 5]
    last = []
    for i, n in enumerate(hist):
        pool, nxt = _seed_row(cfg, params, pool, table, i, n, 50 + i)
        last.append(nxt)
    state = {
        "cache": pool,
        "last_tok": jnp.asarray(last, jnp.int32),
        "pos": jnp.asarray(hist, jnp.int32),
        "active": jnp.asarray([True, True, True, False]),
        "temp": jnp.zeros((B,), jnp.float32),
        "top_k": jnp.zeros((B,), jnp.int32),
        "top_p": jnp.ones((B,), jnp.float32),
        "seeds": jnp.asarray([7, 8, 9, 10], jnp.int32),
        "remaining": jnp.asarray([10, 10, 10, 0], jnp.int32),
    }
    drafts = jnp.asarray(
        np.random.default_rng(99).integers(2, cfg.vocab_size, size=(B, 3)),
        jnp.int32)
    wave = jnp.asarray([True, True, True, False])
    return cfg, params, table, state, drafts, wave


def _run_verify(cfg, params, table, state, drafts, wave, kernel,
                block_budget=0):
    st = jax.tree.map(lambda x: x, state)
    st2, toks, valid, _ = spec_decode.verify_wave(
        params, st, table, drafts, wave, cfg, kernel=kernel,
        block_budget=block_budget)
    return dict(toks=np.asarray(toks), valid=np.asarray(valid),
                pos=np.asarray(st2["pos"]),
                last=np.asarray(st2["last_tok"]),
                active=np.asarray(st2["active"]))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_verify_sparse_matches_masked(kv_dtype):
    fix = _verify_fixture(kv_dtype)
    m = _run_verify(*fix, kernel="masked")
    s = _run_verify(*fix, kernel="sparse")
    liv = m["valid"]
    np.testing.assert_array_equal(m["toks"][liv], s["toks"][liv])
    np.testing.assert_array_equal(m["valid"], s["valid"])
    np.testing.assert_array_equal(m["pos"], s["pos"])
    np.testing.assert_array_equal(m["last"], s["last"])
    np.testing.assert_array_equal(m["active"], s["active"])


def test_verify_pallas_interpret_matches_masked():
    fix = _verify_fixture("int8")
    m = _run_verify(*fix, kernel="masked")
    with pallas_interpret():
        p = _run_verify(*fix, kernel="pallas")
    liv = m["valid"]
    np.testing.assert_array_equal(m["toks"][liv], p["toks"][liv])
    np.testing.assert_array_equal(m["valid"], p["valid"])


def test_verify_block_budget_fallback():
    fix = _verify_fixture("bf16")
    m = _run_verify(*fix, kernel="masked")
    s = _run_verify(*fix, kernel="sparse", block_budget=1)
    liv = m["valid"]
    np.testing.assert_array_equal(m["toks"][liv], s["toks"][liv])


# ---------------------------------------------------------------------------
# Engine end to end: greedy stream parity + the lattice stays collapsed
# ---------------------------------------------------------------------------


def test_engine_sparse_greedy_stream_parity_and_lattice(monkeypatch):
    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    monkeypatch.setenv("COMPILE_LEDGER", "1")
    cfg = _cfg("int8")
    params = transformer.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(29)
    lengths = [12, 26, 7]
    prompts = [rng.integers(3, cfg.vocab_size, size=(n,)).tolist()
               for n in lengths * 2]

    def run(kernel):
        ecfg = EngineConfig(
            max_slots=4, max_seq_len=64, prompt_buckets=(16, 32),
            max_admit=2, decode_chunk=4,
            paged_kv=True, kv_block=8, kv_pool_blocks=4 * 8 + 1,
            chunked_prefill=True, prefill_chunk=16, prefix_block=8,
            ragged=True, ragged_kernel=kernel)
        eng = InferenceEngine(params, cfg, ecfg)
        eng.warmup()
        eng.start()
        qs = [eng.submit(p, SamplingParams(
                  temperature=0.0, top_k=0, top_p=1.0,
                  max_new_tokens=6, seed=i))
              for i, p in enumerate(prompts)]
        streams = []
        for q in qs:
            toks = []
            while True:
                item = q.get(timeout=120)
                if item is None:
                    break
                assert "error" not in item, item
                toks.extend(item.get("tokens", []))
            streams.append(toks)
        comp = eng.debug_compile()
        static = eng.static_lattice()
        eng.stop()
        return streams, comp, static

    want, mcomp, mstatic = run("masked")
    got, scomp, sstatic = run("sparse")
    assert got == want, (got, want)
    assert all(s for s in want)  # every request actually streamed
    # the kernel string is closed over at jit time: same 2-key lattice
    # either way, and nothing compiled on the serving path.
    assert sstatic == ["deactivate", "ragged/16"], sstatic
    assert sstatic == mstatic
    assert scomp["live_retrace_count"] == 0, scomp["live_retraces"]
