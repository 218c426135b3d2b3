"""Paged KV cache (block pool + block-table attention): exactness,
zero-copy sharing, and allocator mechanics.

The load-bearing claims, in test form:
 * paged greedy decoding is BIT-IDENTICAL to the dense slab (bf16 AND
   int8 KV), one-shot and chunked, cold and through a warm prefix hit —
   the pool gather reads exactly the tokens the slab would;
 * warm admissions are ZERO-COPY: the prefix trie refcounts retained
   pool blocks instead of seeding a KV copy (prefix_seed_copies stays
   0), and a partially-filled shared block is copied ONCE (CoW) so the
   sharer never scribbles on the donor's tail;
 * paged_kv=False leaves the engine byte-identical to the dense build —
   no allocator, no pool gauges;
 * admission blocks on POOL exhaustion (pool_stalls), not slot
   exhaustion, and every stream still completes once blocks free up;
 * the pool's accounting invariant (used + free == total) holds through
   a full admit/decode/complete cycle, and the allocator's misuse
   guards + bookkeeping survive a randomized op fuzz (`fuzz` marker;
   FUZZ_EXAMPLES scales it up — see `make fuzz-alloc`).
"""

import dataclasses
import os
import random

import jax
import pytest

from seldon_tpu.models import init_params
from seldon_tpu.models.config import get_config
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers.block_pool import BlockAllocator
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

PROMPT = list(range(2, 26))  # 24 tokens
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=8)


def _engine(cfg, start=True, **ekw):
    params = init_params(cfg, jax.random.key(0))
    ekw.setdefault("max_slots", 4)
    ekw.setdefault("max_seq_len", 64)
    ekw.setdefault("prompt_buckets", (8, 32))
    eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
    if start:
        eng.start()
    return eng


def _dense_want(cfg, prompt=PROMPT):
    cold = _engine(cfg)
    try:
        return cold.generate_blocking(prompt, GREEDY)["token_ids"]
    finally:
        cold.stop()


# ---------------------------------------------------------------------------
# Bit-exactness vs the dense slab
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_bit_identical_one_shot_cold_and_warm(kv_dtype):
    """One-shot paged admission (cold AND through a warm prefix hit)
    matches the dense slab token-for-token; the warm hit shares blocks
    zero-copy instead of seeding a KV copy."""
    cfg = dataclasses.replace(get_config("tiny"), kv_cache_dtype=kv_dtype)
    want = _dense_want(cfg)

    eng = _engine(cfg, prompt_buckets=(16, 32), paged_kv=True, kv_block=16,
                  prefix_cache=True, prefix_block=8)
    try:
        cold = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        warm = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        snap = eng.stats.snapshot()
    finally:
        eng.stop()
    assert cold == want
    assert warm == want
    assert snap["prefix_hits"] == 1
    assert snap["zero_copy_admissions"] == 1
    # The dense prefix cache pays a KV copy to seed the warm slot; the
    # paged trie only bumps refcounts.
    assert snap["prefix_seed_copies"] == 0


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_bit_identical_chunked(kv_dtype):
    """Chunked prefill appends pool blocks as chunks land — cold and
    warm outputs still match the dense one-shot engine bit-for-bit."""
    cfg = dataclasses.replace(get_config("tiny"), kv_cache_dtype=kv_dtype)
    want = _dense_want(cfg)

    eng = _engine(cfg, paged_kv=True, kv_block=8, prefix_cache=True,
                  prefix_block=8, chunked_prefill=True, prefill_chunk=8)
    try:
        cold = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        warm = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        snap = eng.stats.snapshot()
    finally:
        eng.stop()
    assert cold == want
    assert warm == want
    assert snap["prefill_chunks"] == 4  # cold 3 (24/8) + warm suffix 1
    assert snap["prefix_hits"] == 1
    assert snap["prefix_seed_copies"] == 0


def test_paged_cow_on_partially_shared_block():
    """A warm hit whose match ends MID-block shares the full blocks
    zero-copy and copies the partial tail once (copy-on-write), so the
    sharer's suffix prefill never corrupts the donor's retained KV."""
    cfg = get_config("tiny")
    # 26-token shared prompt -> 3 prefix_block=8 trie spans (24 tokens);
    # the warm prompt matches all 24: one full kv_block=16 shared
    # zero-copy, tokens 16..23 live in a partially-filled block -> CoW.
    shared = list(range(2, 28))
    warm_prompt = shared + [30, 31]
    want_shared = _dense_want(cfg, shared)
    want_warm = _dense_want(cfg, warm_prompt)

    eng = _engine(cfg, prompt_buckets=(16, 32), paged_kv=True, kv_block=16,
                  prefix_cache=True, prefix_block=8)
    try:
        got_shared = eng.generate_blocking(shared, GREEDY)["token_ids"]
        got_warm = eng.generate_blocking(warm_prompt, GREEDY)["token_ids"]
        mid = eng.stats.snapshot()
        # The donor runs again AFTER the share: another warm hit (its
        # own partial tail CoWs too) whose continuation must be
        # unaffected by the first sharer's CoW'd writes.
        again = eng.generate_blocking(shared, GREEDY)["token_ids"]
        snap = eng.stats.snapshot()
    finally:
        eng.stop()
    assert got_shared == want_shared
    assert got_warm == want_warm
    assert again == want_shared
    assert mid["cow_copies"] == 1
    assert snap["cow_copies"] == 2
    assert snap["zero_copy_admissions"] >= 2
    assert snap["prefix_seed_copies"] == 0


# ---------------------------------------------------------------------------
# Off-switch, pool accounting, exhaustion
# ---------------------------------------------------------------------------


def test_paged_off_leaves_engine_untouched():
    cfg = get_config("tiny")
    eng = _engine(cfg)  # default: paged_kv=False
    try:
        assert not eng._paged
        eng.generate_blocking(PROMPT, GREEDY)
        snap = eng.stats.snapshot()
    finally:
        eng.stop()
    assert snap["pool_blocks_total"] == 0
    assert snap["zero_copy_admissions"] == 0
    assert snap["cow_copies"] == 0
    assert snap["pool_stalls"] == 0


def test_pool_accounting_returns_to_empty():
    """used + free == total at every observation point, and with no
    prefix cache every block returns to the free list at completion."""
    cfg = get_config("tiny")
    eng = _engine(cfg, prompt_buckets=(16, 32), paged_kv=True, kv_block=16)
    try:
        s0 = eng.stats.snapshot()
        assert s0["pool_blocks_used"] + s0["pool_blocks_free"] \
            == s0["pool_blocks_total"]
        eng.generate_blocking(PROMPT, GREEDY)
        s1 = eng.stats.snapshot()
    finally:
        eng.stop()
    assert s1["pool_blocks_used"] == 0
    assert s1["pool_blocks_free"] == s1["pool_blocks_total"]


def test_admission_stalls_on_pool_exhaustion_then_completes():
    """A pool sized for ONE stream forces the second submission to wait
    for the first to release its blocks: pool_stalls ticks, both
    streams still finish, and the outputs match the dense engine."""
    cfg = get_config("tiny")
    # 24-token prompts + 8 decode in a 32 window: exactly 2 blocks of 16
    # cover a stream's whole life, so admission's prompt reservation IS
    # the total need (no mid-decode growth -> no preemption pressure).
    p_a = list(range(2, 26))
    p_b = list(range(40, 64))
    want_a = _dense_want(cfg, p_a)
    want_b = _dense_want(cfg, p_b)

    eng = _engine(cfg, max_seq_len=32, prompt_buckets=(32,), paged_kv=True,
                  kv_block=16, kv_pool_blocks=3)  # trash + 2 usable
    try:
        qa = eng.submit(p_a, GREEDY)
        qb = eng.submit(p_b, GREEDY)

        def collect(q):
            toks = []
            while True:
                item = q.get(timeout=120)
                if item is None:
                    return toks
                assert "error" not in item, item
                toks.extend(item.get("tokens", []))

        got_a = collect(qa)
        got_b = collect(qb)
        snap = eng.stats.snapshot()
    finally:
        eng.stop()
    assert got_a == want_a
    assert got_b == want_b
    assert snap["pool_stalls"] >= 1


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------


def test_paged_config_validation():
    base = dict(paged_kv=True, kv_block=16, prefix_block=8,
                max_seq_len=64, prompt_buckets=(16, 32))
    with pytest.raises(ValueError, match="kv_block.*power of two"):
        EngineConfig(**{**base, "kv_block": 12, "prefix_block": 4})
    with pytest.raises(ValueError, match="multiple of.*prefix_block"):
        EngineConfig(**{**base, "kv_block": 8, "prefix_block": 16,
                        "prompt_buckets": (8, 32)})
    with pytest.raises(ValueError, match="max_seq_len.*multiple of"):
        EngineConfig(**{**base, "max_seq_len": 40})
    with pytest.raises(ValueError, match="prompt_buckets entry"):
        EngineConfig(**{**base, "prompt_buckets": (8, 32)})
    with pytest.raises(ValueError, match="prefill_chunk.*multiple of"):
        EngineConfig(**base, chunked_prefill=True, prefill_chunk=8)
    with pytest.raises(ValueError, match="kv_pool_blocks"):
        EngineConfig(**base, kv_pool_blocks=1)
    # The knobs only bite when paged_kv is on, and valid configs build.
    EngineConfig(kv_block=12)
    EngineConfig(**base)
    EngineConfig(**base, kv_pool_blocks=9)


# ---------------------------------------------------------------------------
# Randomized allocator property test (scaled up by `make fuzz-alloc`)
# ---------------------------------------------------------------------------


@pytest.mark.fuzz
def test_block_allocator_fuzz():
    """Shadow-model fuzz of BlockAllocator: random alloc / alloc_many /
    ref / unref interleavings (plus deliberate misuse) must keep the
    allocator's accounting identical to a plain dict model, and every
    misuse must raise instead of corrupting state."""
    n_examples = int(os.environ.get("FUZZ_EXAMPLES", "300"))
    rng = random.Random(0xB10C)

    for case in range(n_examples):
        num_blocks = rng.randint(2, 24)
        alloc = BlockAllocator(num_blocks)
        model = {}  # bid -> refcount (live blocks only)
        for _ in range(rng.randint(1, 60)):
            op = rng.random()
            if op < 0.35:
                bid = alloc.alloc()
                if len(model) == num_blocks - 1:
                    assert bid is None  # exhausted: no block invented
                else:
                    assert bid is not None and bid not in model
                    assert bid != BlockAllocator.TRASH
                    model[bid] = 1
            elif op < 0.50:
                n = rng.randint(0, num_blocks)
                got = alloc.alloc_many(n)
                if n > num_blocks - 1 - len(model):
                    assert got is None  # all-or-nothing: no partial grab
                else:
                    assert got is not None and len(set(got)) == n
                    for bid in got:
                        assert bid not in model
                        model[bid] = 1
            elif op < 0.70 and model:
                bid = rng.choice(list(model))
                alloc.ref(bid)
                model[bid] += 1
            elif op < 0.90 and model:
                bid = rng.choice(list(model))
                alloc.unref(bid)
                if model[bid] == 1:
                    del model[bid]
                else:
                    model[bid] -= 1
            else:  # misuse must raise and must not disturb accounting
                with pytest.raises(RuntimeError):
                    rng.choice([alloc.ref, alloc.unref])(
                        BlockAllocator.TRASH
                    )
                free = [b for b in range(1, num_blocks) if b not in model]
                if free:
                    with pytest.raises(RuntimeError):
                        rng.choice([alloc.ref, alloc.unref])(
                            rng.choice(free)
                        )
            # Invariants after EVERY op, checked against the model.
            snap = alloc.snapshot()
            assert snap["total"] == num_blocks - 1
            assert snap["used"] == len(model)
            assert snap["free"] == num_blocks - 1 - len(model)
            assert snap["used"] + snap["free"] == snap["total"]
            assert snap["shared"] == sum(1 for c in model.values() if c > 1)
            for bid, c in model.items():
                assert alloc.refcount(bid) == c
        # Drain: unref everything back; the free list must be whole.
        for bid, c in list(model.items()):
            for _ in range(c):
                alloc.unref(bid)
        assert alloc.free_count == num_blocks - 1
        assert alloc.live_count == 0
