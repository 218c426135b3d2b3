"""The block pool and chunked prefill TOGETHER (paged_kv + chunked_prefill),
and each alone, under traffic that mixes prompt lengths: the engine
every opt-in composition rides, pinned against the dense slab.

 * a concurrent burst of uneven prompts answers token for token as the
   dense engine does, bf16 and int8 KV, through the pool, through chunked
   prefill, through both, and through both under the prefix trie;
 * a warm prefix hit on the pool starts mid-prompt, shares blocks
   zero-copy and still answers as the dense engine;
 * the synchronous loop (async_fetch=False) answers the same;
 * an exhausted pool PREEMPTS: the victim gets the typed retriable
   error, the survivor's stream is exact, nothing leaks;
 * the names of the removed unified wave are unknown names.
"""

import dataclasses
import pathlib

import jax
import pytest

from _engine_fixture import LIVE_TOKENS, PROMPT, live_config

from seldon_tpu.models import init_params
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

GREEDY = SamplingParams(temperature=0.0, max_new_tokens=LIVE_TOKENS)

# Every packing a dispatch can see: whole chunks, a single final chunk,
# chunks and a tail of one token, a prompt shorter than a block.
MIXED = [
    PROMPT,               # 24 tokens: 3 full chunks
    list(range(30, 33)),  # 3 tokens: single final chunk
    list(range(40, 57)),  # 17 tokens: 2 chunks + a tail of 1
    [5, 9],               # 2 tokens
]

PAGED = dict(paged_kv=True, kv_block=8, prefix_block=8)
CHUNKED = dict(chunked_prefill=True, prefill_chunk=8, prefix_block=8)
BOTH = {**PAGED, **CHUNKED}
MODES = {
    "paged": PAGED,
    "chunked": CHUNKED,
    "paged+chunked": BOTH,
    "paged+chunked+prefix": dict(prefix_cache=True, **BOTH),
}


def _engine(cfg, **ekw):
    params = init_params(cfg, jax.random.key(0))
    ekw.setdefault("max_slots", 4)
    ekw.setdefault("max_seq_len", 64)
    ekw.setdefault("prompt_buckets", (8, 32))
    eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
    eng.start()
    return eng


def _collect(q, timeout=120):
    toks, err = [], None
    while True:
        item = q.get(timeout=timeout)
        if item is None:
            return toks, err
        if "error" in item:
            err = item
        else:
            toks.extend(item.get("tokens", []))


@pytest.fixture(scope="module")
def dense_want():
    """The dense engine's greedy streams of MIXED, each prompt alone,
    one engine a KV dtype."""
    memo = {}

    def get(kv_dtype):
        if kv_dtype not in memo:
            eng = _engine(live_config(kv_cache_dtype=kv_dtype))
            try:
                memo[kv_dtype] = [
                    eng.generate_blocking(p, GREEDY)["token_ids"]
                    for p in MIXED]
            finally:
                eng.stop()
        return memo[kv_dtype]

    return get


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("mode", sorted(MODES))
def test_mixed_burst_bit_identical_to_dense(dense_want, mode, kv_dtype):
    wants = dense_want(kv_dtype)
    assert all(len(w) == LIVE_TOKENS for w in wants)
    eng = _engine(live_config(kv_cache_dtype=kv_dtype), **MODES[mode])
    try:
        qs = [eng.submit(p, GREEDY) for p in MIXED]
        gots = []
        for q in qs:
            toks, err = _collect(q)
            assert err is None, err
            gots.append(toks)
        snap = eng.stats.snapshot()
        leaks = eng.debug_lifecycle_check()
    finally:
        eng.stop()
    assert gots == wants
    assert leaks == {}
    if "chunked" in mode:
        # every prompt token went through a chunk, at its exact length
        assert snap["prefill_chunk_tokens"] == sum(len(p) for p in MIXED)
    if "paged" in mode and "prefix" not in mode:
        assert snap["pool_blocks_used"] == 0  # (a trie keeps its blocks)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_prefix_warm_bit_identical_and_zero_copy(dense_want, kv_dtype):
    """The second admission is warm: it starts mid-prompt on its FIRST
    chunk, shares the trie's pool blocks and copies none."""
    want = dense_want(kv_dtype)[0]
    eng = _engine(live_config(kv_cache_dtype=kv_dtype), **BOTH,
                  prefix_cache=True)
    try:
        cold = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        warm = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        snap = eng.stats.snapshot()
    finally:
        eng.stop()
    assert cold == want
    assert warm == want
    assert snap["zero_copy_admissions"] >= 1
    assert snap["prefix_seed_copies"] == 0


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("mode", ["paged+chunked", "paged+chunked+prefix"])
def test_sync_fetch_loop_bit_identical(dense_want, mode, kv_dtype):
    """async_fetch=False runs _loop_sync (one wave of lookahead) in
    place of the fetch thread, over a burst of uneven prompts."""
    eng = _engine(live_config(kv_cache_dtype=kv_dtype), **MODES[mode],
                  async_fetch=False)
    try:
        qs = [eng.submit(p, GREEDY) for p in MIXED]
        outs = [_collect(q) for q in qs]
    finally:
        eng.stop()
    assert [e for _, e in outs] == [None] * len(MIXED)
    assert [t for t, _ in outs] == dense_want(kv_dtype)


def test_pool_exhaustion_preempts_and_survivor_is_exact():
    """Two 6-token streams in a pool of 3 usable blocks, admitted in
    one dispatch (a budget of two chunks; at one chunk a dispatch the
    second would stall at admission until the first had finished): a
    block each, and both need a second block at the same decode
    boundary. One takes the last free block; the other's growth finds
    the pool empty and one of the two is preempted with the typed
    retriable error. The survivor then fits (6 + 16 tokens: three
    blocks) and is exact."""
    cfg = live_config()
    sp = SamplingParams(temperature=0.0, max_new_tokens=16)
    prompts = [[2, 3, 5, 7, 11, 13], [4, 6, 8, 9, 10, 12]]
    dense = _engine(cfg)
    try:
        wants = [dense.generate_blocking(p, sp)["token_ids"]
                 for p in prompts]
    finally:
        dense.stop()
    assert all(len(w) == 16 for w in wants)

    eng = _engine(cfg, max_seq_len=32, kv_pool_blocks=4,
                  dispatch_token_budget=16, **BOTH)
    try:
        qs = [eng.submit(p, sp) for p in prompts]
        outs = [_collect(q) for q in qs]
        snap = eng.stats.snapshot()
        leaks = eng.debug_lifecycle_check()
    finally:
        eng.stop()
    errs = [e for _, e in outs if e is not None]
    assert len(errs) == 1, outs
    assert errs[0]["kind"] == "preempted", errs[0]
    assert errs[0]["retriable"] is True
    assert snap["preemptions"] >= 1
    (survivor,) = [i for i, (_, e) in enumerate(outs) if e is None]
    assert outs[survivor][0] == wants[survivor]
    assert leaks == {}
    assert snap["pool_blocks_used"] == 0


# --- the unified wave's names are unknown names ------------------------------

REMOVED_FIELDS = ("ragged", "ragged_chunk", "ragged_kernel",
                  "ragged_block_budget")
REMOVED_PARAMETERS = ("ragged", "ragged_chunk", "ragged_kernel")


@pytest.mark.parametrize("where,name", [
    *(("EngineConfig", n) for n in REMOVED_FIELDS),
    *(("JAXServer", n) for n in REMOVED_PARAMETERS),
])
def test_a_removed_name_is_an_unknown_name(where, name):
    """No shim accepts and ignores them: each is refused as any name the
    class never had is, and no knob of that name is registered or
    documented."""
    from seldon_tpu.servers.jaxserver import JAXServer
    from tools.graftlint import knob_registry

    cls = EngineConfig if where == "EngineConfig" else JAXServer
    with pytest.raises(TypeError, match="unexpected keyword") as unknown:
        cls(no_such_name=1)
    with pytest.raises(TypeError, match="unexpected keyword") as removed:
        cls(**{name: 1})
    assert str(removed.value) == str(unknown.value).replace(
        "no_such_name", name)
    if where == "EngineConfig":
        assert name not in {f.name for f in dataclasses.fields(EngineConfig)}
    assert not [k for k in knob_registry.KNOBS if "RAGGED" in k]
    doc = pathlib.Path(__file__).parents[1] / "docs" / "knobs.md"
    assert "ragged" not in doc.read_text().lower()
