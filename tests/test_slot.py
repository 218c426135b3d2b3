"""models/slot.py: the one place a slot's keys, termination, arming and
decode step are written, and the engine paths that call it.

 (a) the two key rules against the literal expressions;
 (b) the two termination rules on a table of edge rows;
 (c) a decode chunk of n steps is n chunks of one step, bit for bit;
 (d) the same request through every engine path yields one stream;
 (e) every path counts its sampler steps;
 and the guard of tests/_engine_fixture.py: the live stream is live.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _engine_fixture import LIVE_TOKENS, PROMPT, live_config

from seldon_tpu.models import init_params, slot, transformer
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

GREEDY = SamplingParams(temperature=0.0, max_new_tokens=LIVE_TOKENS)
SAMPLED = SamplingParams(temperature=0.8, top_k=8, seed=7,
                         max_new_tokens=LIVE_TOKENS)

PAGED = dict(paged_kv=True, kv_block=8, prefix_block=8)
CHUNKED = dict(chunked_prefill=True, prefill_chunk=8, prefix_block=8)
PREFIX = dict(prefix_cache=True, prefix_block=8)
PATHS = {
    "dense": {},
    "dense-sync": dict(async_fetch=False),
    "paged": PAGED,
    "chunked": CHUNKED,
    "paged+chunked": {**PAGED, **CHUNKED},
    "spec": dict(spec_decode=True, spec_k=4, **PAGED),
    # the compositions: the trie over each substrate, the synchronous
    # loop under the pool and under chunked prefill
    "prefix": PREFIX,
    "chunked+prefix": {**CHUNKED, **PREFIX},
    "paged+chunked+prefix": {**PAGED, **CHUNKED, **PREFIX},
    "paged-sync": dict(async_fetch=False, **PAGED),
    "chunked-sync": dict(async_fetch=False, **CHUNKED),
}


def _engine(cfg, tp=1, **ekw):
    params = init_params(cfg, jax.random.key(0))
    ekw.setdefault("max_slots", 4)
    ekw.setdefault("max_seq_len", 64)
    ekw.setdefault("prompt_buckets", (8, 32))
    if tp > 1:
        from seldon_tpu.servers import mesh_engine

        eng = mesh_engine.MeshEngine(params, cfg, EngineConfig(**ekw), tp=tp)
    else:
        eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
    eng.start()
    return eng


def _stream(cfg, sp, **ekw):
    """PROMPT's stream; a prefix engine is asked twice and answers with
    its second, warm admission."""
    eng = _engine(cfg, **ekw)
    try:
        for _ in range(2 if ekw.get("prefix_cache") else 1):
            out = eng.generate_blocking(PROMPT, sp)["token_ids"]
        return out
    finally:
        eng.stop()


# --- (a) keys ---------------------------------------------------------------


def test_keys_are_the_literal_fold_ins():
    seeds = jnp.array([0, 7, 2**32 - 1], jnp.uint32)
    at = jnp.array([1, 24, 63], jnp.int32)
    data = jax.random.key_data
    for i in range(3):
        s, p = seeds[i], at[i]
        assert np.array_equal(
            data(slot.first_key(seeds, at))[i],
            data(jax.random.fold_in(jax.random.key(s), p)))
        assert np.array_equal(
            data(slot.step_key(seeds, at))[i],
            data(jax.random.fold_in(jax.random.key(s), p + 1)))
    # One sequence by absolute position: the first token of a prompt of
    # p + 1 tokens and the decode step after position p share a key.
    assert np.array_equal(data(slot.first_key(seeds, at + 1)),
                          data(slot.step_key(seeds, at)))


# --- (b) termination --------------------------------------------------------

SMAX = 64
EOS = live_config().eos_token_id


@pytest.mark.parametrize("first,max_new,plen,want", [
    (5, 8, 10, False),           # an ordinary first token
    (EOS, 8, 10, True),          # EOS
    (5, 1, 10, True),            # a budget of one token
    (5, 0, 10, True),
    (5, 8, SMAX - 1, True),      # the prompt fills the window
    (5, 8, SMAX - 2, False),     # one position left
])
def test_first_done_edges(first, max_new, plen, want):
    got = slot.first_done(jnp.array([first]), jnp.array([max_new]),
                          jnp.array([plen]), SMAX, live_config())
    assert bool(got[0]) is want


@pytest.mark.parametrize("run,tok,remaining,pos,want", [
    (True, 5, 3, 30, False),          # mid-stream
    (True, EOS, 3, 30, True),         # EOS
    (True, 5, 0, 30, True),           # budget spent by this token
    (True, 5, 3, SMAX - 1, True),     # the window's last position
    (True, 5, 3, SMAX - 2, False),
    (False, EOS, 0, SMAX - 1, False),  # a dead row ends nothing
])
def test_step_done_edges(run, tok, remaining, pos, want):
    got = slot.step_done(jnp.array([run]), jnp.array([tok]),
                         jnp.array([remaining]), jnp.array([pos]), SMAX,
                         live_config())
    assert bool(got[0]) is want


# --- (c) n steps = n chunks of one step ------------------------------------


def _armed(cfg):
    """Params and a slot state with two requests admitted by the
    engine's own _admit_impl (one greedy, one sampled with top-k)."""
    params = init_params(cfg, jax.random.key(0))
    B, Smax = 4, 48
    state = slot.fresh(transformer.init_cache(cfg, B, Smax), B)
    toks = np.zeros((2, 16), np.int32)
    toks[0, :12] = np.arange(2, 14)
    toks[1, :7] = np.arange(30, 37)
    state, first, _ = InferenceEngine._admit_impl(
        params, state, jnp.asarray(toks), jnp.array([12, 7], jnp.int32),
        jnp.array([3, 7], jnp.uint32), jnp.array([0.0, 0.8], jnp.float32),
        jnp.array([0, 8], jnp.int32), jnp.array([1.0, 1.0], jnp.float32),
        jnp.array([20, 5], jnp.int32), jnp.array([2, 0], jnp.int32),
        cfg=cfg)
    return params, state


@pytest.mark.parametrize("preset,kv_dtype", [
    ("tiny", "bf16"), ("tiny", "int8"),
    ("tiny-moe", "bf16"), ("tiny-moe", "int8"),
    ("tiny-lfm2", "bf16"),  # a patterned stack stores bf16 only
])
def test_chunk_of_n_is_n_chunks_of_one(preset, kv_dtype):
    cfg = live_config(preset, kv_cache_dtype=kv_dtype)
    params, state = _armed(cfg)
    n = 6  # the sampled row's budget of 5 ends inside the chunk
    whole = InferenceEngine._chunk_impl(params, state, cfg=cfg, n_steps=n)
    toks, valid, counts = [], [], 0
    for _ in range(n):
        state, t, v, active, c = InferenceEngine._chunk_impl(
            params, state, cfg=cfg, n_steps=1)
        toks.append(t)
        valid.append(v)
        counts = counts + c
    stepped = (state, jnp.concatenate(toks), jnp.concatenate(valid),
               active, counts)
    assert jax.tree.structure(whole) == jax.tree.structure(stepped)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(stepped)):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)))
    valid = np.asarray(whole[2])
    assert valid[:, 2].all() and valid[:, 0].sum() == 4  # 5 less the first
    assert not valid[:, 1].any() and not valid[:, 3].any()
    assert int(whole[4][0]) == n


# --- (d) one request, every path, one stream --------------------------------


@pytest.fixture(scope="module")
def want():
    """The dense engine's streams, one per (preset, sampling)."""
    memo = {}

    def get(preset, name, sp):
        if (preset, name) not in memo:
            memo[preset, name] = _stream(live_config(preset), sp)
        return memo[preset, name]

    return get


@pytest.mark.parametrize("name,sp", [("greedy", GREEDY),
                                     ("sampled", SAMPLED)])
@pytest.mark.parametrize("preset,path", [
    *(("tiny", p) for p in PATHS if p != "dense"),
    # the opt-in paths refuse a patterned stack by name
    ("tiny-lfm2", "dense-sync"),
])
def test_every_path_answers_the_same(want, preset, path, name, sp):
    ref = want(preset, name, sp)
    assert len(ref) == LIVE_TOKENS
    assert _stream(live_config(preset), sp, **PATHS[path]) == ref


# --- (d') one slab: a prefill's KV lands where a decode step reads it --------

# The paths that move with the slab's layout (one row a token,
# transformer.cache_spec): the prefix cache scatters and gathers trie
# blocks of it, chunked prefill reads it back as its prefix, a
# tensor-parallel engine shards its rows by head group, and the
# speculative engine turns a cold prefill's rows by head for its pool.
SLAB_PATHS = {
    "prefix": PREFIX,
    "chunked": CHUNKED,
    "spec": dict(spec_decode=True, spec_k=4, **PAGED),
    "tp2": dict(tp=2),
}
SLAB_TOKENS = 24


def _slab_streams(cfg, n_requests=1, **ekw):
    """The greedy streams of PROMPT, sent n_requests times in turn."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=SLAB_TOKENS)
    eng = _engine(cfg, **ekw)
    try:
        out = [eng.generate_blocking(PROMPT, sp)["token_ids"]
               for _ in range(n_requests)]
        stats = eng.stats.snapshot()
    finally:
        eng.stop()
    return out, stats


@pytest.fixture(scope="module")
def slab_want():
    memo = {}

    def get(kv_dtype):
        if kv_dtype not in memo:
            cfg = live_config(kv_cache_dtype=kv_dtype)
            memo[kv_dtype] = _slab_streams(cfg)[0][0]
        return memo[kv_dtype]

    return get


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("path", sorted(SLAB_PATHS))
def test_prefilled_kv_lands_where_a_decode_step_reads_it(slab_want, path,
                                                          kv_dtype):
    """Admission, then 24 greedy tokens: every engine that scatters,
    gathers, shards or turns the slab's rows answers as the dense one
    does, bf16 and int8 KV (scales per head). The prefix engine is asked
    twice: its second admission is warm, prefix rows out of the trie."""
    ref = slab_want(kv_dtype)
    assert len(ref) == SLAB_TOKENS
    cfg = live_config(kv_cache_dtype=kv_dtype)
    got, stats = _slab_streams(cfg, 2 if path == "prefix" else 1,
                               **SLAB_PATHS[path])
    assert got == [ref] * len(got)
    if path == "prefix":
        assert stats["prefix_hits"] >= 1


# --- (e) every path counts its sampler steps --------------------------------


@pytest.mark.parametrize("path", [
    "paged", "paged+chunked", "spec", "prefix", "chunked+prefix",
    "paged+chunked+prefix", "paged-sync", "chunked-sync"])
def test_paths_count_sampler_steps(path):
    eng = _engine(live_config(), **PATHS[path])
    try:
        eng.generate_blocking(PROMPT, SAMPLED)
        snap = eng.stats.snapshot()
    finally:
        eng.stop()
    assert snap["sampler_steps"] >= LIVE_TOKENS - 1
    assert snap["sampler_masked_steps"] > 0  # top_k 8 asks for the sort
    assert snap["sampler_drawn_steps"] >= snap["sampler_masked_steps"]


# --- the fixture's guard ----------------------------------------------------


@pytest.mark.parametrize("path", ["dense", "paged"])
def test_live_stream_is_live(path):
    """tests/_engine_fixture.py: the greedy continuation of PROMPT under
    live_config() runs its whole budget and never meets EOS."""
    cfg = live_config()
    got = _stream(cfg, GREEDY, **PATHS[path])
    assert len(got) == LIVE_TOKENS
    assert cfg.eos_token_id not in got


# -- (f) which positions a denoising pass decides (cfg.gen_block) ------------

_T, _F = True, False
# (rule, k, threshold, known, confidences) -> decided this pass
TRANSFER = {
    "sequential takes the leftmost k": (
        "sequential", 2, None, [_F, _F, _F, _F], [.1, .9, .8, .7], [0, 1]),
    "sequential skips the decided": (
        "sequential", 2, None, [_T, _F, _T, _F], [.1, .9, .8, .7], [1, 3]),
    "sequential takes what is left": (
        "sequential", 2, None, [_T, _T, _T, _F], [.1, .9, .8, .7], [3]),
    "sequential ignores a threshold": (
        "sequential", 1, 0.5, [_F, _F, _F, _F], [.1, .9, .8, .7], [0]),
    "confidence takes the highest k": (
        "low_confidence", 2, None, [_F, _F, _F, _F], [.1, .9, .8, .7], [1, 2]),
    "confidence never takes a decided one": (
        "low_confidence", 2, None, [_F, _T, _F, _F], [.1, .9, .8, .7], [2, 3]),
    "ties go to the left": (
        "low_confidence", 2, None, [_F, _F, _F, _F], [.5, .5, .5, .5], [0, 1]),
    "ties go to the left past a decided one": (
        "low_confidence", 1, None, [_T, _F, _F, _F], [.9, .5, .5, .5], [1]),
    "k of one": (
        "low_confidence", 1, None, [_F, _F, _F, _F], [.1, .2, .8, .7], [2]),
    "a threshold that k pass takes all above it": (
        "low_confidence", 2, 0.6, [_F, _F, _F, _F], [.1, .9, .8, .7], [1, 2, 3]),
    "a threshold that fewer than k pass changes nothing": (
        "low_confidence", 2, 0.85, [_F, _F, _F, _F], [.1, .9, .8, .7], [1, 2]),
    "a threshold counts the undecided only": (
        "low_confidence", 2, 0.6, [_F, _T, _F, _F], [.1, .9, .8, .5], [2, 3]),
    "nothing left": (
        "low_confidence", 2, 0.1, [_T, _T, _T, _T], [.1, .9, .8, .7], []),
}


@pytest.mark.parametrize("case", list(TRANSFER))
def test_transfer_decides_by_rule_k_threshold_and_ties(case):
    rule, k, threshold, known, conf, want = TRANSFER[case]
    # two rows: the case, and a row with nothing decided (rows are
    # independent)
    take = slot.transfer(
        jnp.asarray([known, [False] * 4]), jnp.asarray([conf, conf]),
        k, rule, threshold)
    assert np.flatnonzero(np.asarray(take[0])).tolist() == want
    assert not (np.asarray(take[0]) & np.asarray(known)).any()


def test_first_block_is_the_prompts_tail_then_undecided():
    toks = jnp.asarray([[5, 6, 7, 8, 9, 10, 0, 0], [5, 6, 7, 8, 0, 0, 0, 0],
                        [5, 6, 7, 0, 0, 0, 0, 0]])
    blk = slot.first_block(toks, jnp.asarray([6, 4, 3]), 4)
    assert blk["blk_tok"].tolist() == [[9, 10, 0, 0], [0, 0, 0, 0], [5, 6, 7, 0]]
    assert blk["blk_known"].tolist() == [[_T, _T, _F, _F], [_F] * 4,
                                         [_T, _T, _T, _F]]
    assert blk["blk_skip"].tolist() == [2, 0, 3]
