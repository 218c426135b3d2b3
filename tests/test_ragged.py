"""graftragged (models/ragged_attention.py + engine._dispatch_ragged):
the single-variant unified wave, pinned against the bucketed engine.

The load-bearing claims, in test form:
 * greedy decoding under RAGGED is BIT-IDENTICAL to every ragged-off
   mode — dense slab, paged one-shot, paged+chunked, and a warm
   prefix-trie hit — for bf16 AND int8 KV, including a concurrent
   mixed-length burst;
 * one WAVE really is one DISPATCH: a hand-driven scheduler step packs
   a new admission, a mid-prefill continuation and live decode rows
   into a single ``("ragged", C)`` dispatch, and the compile ledger
   never sees a key outside the static lattice (zero live retraces);
 * the lattice COLLAPSES: ``static_lattice()`` is exactly
   {deactivate, ragged/C} (+cow under prefix_cache) — at most 2 (3)
   variants where the bucketed engine compiles a whole grid;
 * pool exhaustion under ragged PREEMPTS instead of wedging: the
   victim gets the typed retriable "preempted" error, survivors stay
   bit-exact, nothing leaks;
 * the sched ledger prices a wave as useful == packed (capacity is not
   padding — the ragged kernel walks real token counts), so
   padding_waste_frac ~ 0 under mixed traffic;
 * ragged=False leaves the engine byte-identical to the bucketed build,
   and EngineConfig rejects unusable ragged knob combinations.
"""

import dataclasses
import queue

import jax
import pytest

from seldon_tpu.models import init_params
from seldon_tpu.models.config import get_config
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

PROMPT = list(range(2, 26))  # 24 tokens
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=8)

# Mixed-length burst: one-chunk shorties, a chunk-aligned prompt, and a
# ragged mid-chunk tail — every packing shape a wave can see.
MIXED = [
    list(range(2, 26)),   # 24 tokens: 3 full chunks
    list(range(30, 33)),  # 3 tokens: single final chunk
    list(range(40, 57)),  # 17 tokens: 2 chunks + ragged tail of 1
    [5, 9],               # 2 tokens
]

# The ragged engine rides the paged + chunked substrate.
RAGGED = dict(paged_kv=True, chunked_prefill=True, prefill_chunk=8,
              prefix_block=8, kv_block=8, ragged=True)


def _engine(cfg, start=True, **ekw):
    params = init_params(cfg, jax.random.key(0))
    ekw.setdefault("max_slots", 4)
    ekw.setdefault("max_seq_len", 64)
    ekw.setdefault("prompt_buckets", (8, 32))
    eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
    if start:
        eng.start()
    return eng


def _want(cfg, prompt=PROMPT, **ekw):
    """Ragged-off reference output for one prompt under a given mode."""
    eng = _engine(cfg, **ekw)
    try:
        return eng.generate_blocking(prompt, GREEDY)["token_ids"]
    finally:
        eng.stop()


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


def _drain_now(q):
    toks = []
    while True:
        try:
            item = q.get_nowait()
        except queue.Empty:
            return toks, False
        if item is None:
            return toks, True
        assert "error" not in item, item
        toks.extend(item.get("tokens", []))


# ---------------------------------------------------------------------------
# Bit-exactness vs every ragged-off mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_ragged_bit_identical_to_dense_mixed_burst(kv_dtype):
    """A concurrent mixed-length burst through the ragged engine matches
    the dense slab token-for-token — the acceptance gate's exactness
    criterion, for both KV dtypes."""
    cfg = dataclasses.replace(get_config("tiny"), kv_cache_dtype=kv_dtype)
    wants = [_want(cfg, p) for p in MIXED]

    eng = _engine(cfg, **RAGGED)
    try:
        qs = [eng.submit(p, GREEDY) for p in MIXED]
        gots = []
        for q in qs:
            toks, err = _collect(q)
            assert err is None, err
            gots.append(toks)
        snap = eng.stats.snapshot()
    finally:
        eng.stop()
    assert gots == wants
    # The burst really took the ragged path: chunked-prefill accounting
    # ticked (46 prompt tokens packed as exact-length segments).
    assert snap["prefill_chunk_tokens"] == sum(len(p) for p in MIXED)


def test_ragged_bit_identical_to_paged_and_chunked():
    """ragged-on vs the two intermediate ragged-off modes (paged
    one-shot, paged+chunked) — all three agree with each other."""
    cfg = get_config("tiny")
    paged = [_want(cfg, p, paged_kv=True, kv_block=8, prefix_block=8)
             for p in MIXED]
    chunked = [
        _want(cfg, p, paged_kv=True, kv_block=8, chunked_prefill=True,
              prefill_chunk=8, prefix_block=8)
        for p in MIXED
    ]
    eng = _engine(cfg, **RAGGED)
    try:
        ragged = []
        for p in MIXED:
            toks, err = _collect(eng.submit(p, GREEDY))
            assert err is None, err
            ragged.append(toks)
    finally:
        eng.stop()
    assert ragged == paged
    assert ragged == chunked


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_ragged_prefix_warm_bit_identical_and_zero_copy(kv_dtype):
    """A warm prefix-trie resume under ragged: the second admission
    starts mid-prompt (starts > 0 on its FIRST wave), shares blocks
    zero-copy, and still matches the dense slab."""
    cfg = dataclasses.replace(get_config("tiny"), kv_cache_dtype=kv_dtype)
    want = _want(cfg)
    eng = _engine(cfg, **RAGGED, prefix_cache=True)
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


def test_ragged_sync_fetch_loop_bit_identical():
    """async_fetch=False exercises _loop_sync (the one-wave-lookahead
    pipeline) instead of the fetch-thread path."""
    cfg = get_config("tiny")
    want = _want(cfg)
    eng = _engine(cfg, **RAGGED, async_fetch=False)
    try:
        got = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
    finally:
        eng.stop()
    assert got == want


# ---------------------------------------------------------------------------
# Mechanics: mixed prefill + decode + continuation in ONE dispatch
# ---------------------------------------------------------------------------


def test_mixed_wave_is_one_dispatch(monkeypatch):
    """Hand-driven scheduler step (no engine thread): once a stream is
    decoding, submitting a long prompt and a shorty makes the next wave
    carry a NEW admission chunk + a FINAL admission + the live decode
    row in a single ("ragged", C) dispatch — and every key the compile
    ledger ever sees is statically declared (zero live retraces)."""
    monkeypatch.setenv("COMPILE_LEDGER", "1")
    cfg = get_config("tiny")
    eng = _engine(cfg, start=False, **RAGGED)
    eng.warmup()

    def step():
        with eng._book:
            work = eng._dispatch_once()
            if work is None:
                return False
            eng._process_boundary(*work)  # holds(_book), like the loop
        return True

    q_a = eng.submit(PROMPT, SamplingParams(
        temperature=0.0, max_new_tokens=16, seed=0))
    for _ in range(3):  # 24 tokens / chunk 8: wave 3 samples + decodes
        assert step()
    got_a, _ = _drain_now(q_a)
    assert got_a  # A is decoding

    q_b = eng.submit(list(range(40, 57)), GREEDY)  # 17 toks: mid-prefill
    q_c = eng.submit([5, 9], GREEDY)               # 2 toks: final chunk
    before = eng.stats.snapshot()
    assert step()
    snap = eng.stats.snapshot()

    # ONE dispatch carried: B's first (non-final) chunk + C's final
    # chunk + A's decode step.
    assert snap["decode_dispatches"] - before["decode_dispatches"] == 1
    assert snap["prefill_chunks"] - before["prefill_chunks"] == 2
    assert snap["prefill_chunk_tokens"] - before["prefill_chunk_tokens"] \
        == 8 + 2
    got_a, _ = _drain_now(q_a)
    assert got_a, "decode row starved by the admission wave"
    got_c, _ = _drain_now(q_c)
    assert got_c, "final-chunk row got no first token"
    _, b_done = _drain_now(q_b)
    assert not b_done  # B is mid-prefill: the wave was genuinely mixed

    # Drive everything to completion; the ledger must stay inside the
    # static lattice the whole time.
    for _ in range(64):
        if not step():
            break
    comp = eng.debug_compile()
    assert comp["live_retrace_count"] == 0, comp["live_retraces"]
    static = set(eng.static_lattice())
    assert {e["key"] for e in comp["lattice"]} <= static
    assert any(k.startswith("ragged/") for k in static)


# ---------------------------------------------------------------------------
# Lattice collapse + waste accounting
# ---------------------------------------------------------------------------


def test_static_lattice_collapses_to_two_variants():
    from seldon_tpu.servers import compile_ledger, shape_lattice

    def expect(eng):
        # Derived from the same closed form the engine warms up from —
        # PR 13/15 both shipped stale-pin fixes where this list was
        # hand-written; now only the *collapse bound* is asserted as a
        # literal, the key set itself comes from the lattice.
        keys = shape_lattice.dispatch_keys(eng.lattice_spec())
        return [compile_ledger.key_str(k)
                for k in shape_lattice.warmup_order(keys)]

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, max_seq_len=64, prompt_buckets=(8, 32), **RAGGED))
    static = eng.static_lattice()
    assert len(static) <= 2
    assert static == expect(eng)
    assert any(k.startswith("ragged/") for k in static)
    # Prefix cache adds only the CoW tail copy — still ≤ 3.
    eng2 = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, max_seq_len=64, prompt_buckets=(8, 32),
        prefix_cache=True, **RAGGED))
    static2 = eng2.static_lattice()
    assert len(static2) <= 3
    assert static2 == expect(eng2)
    assert "cow" in static2
    assert {k.split("/")[0] for k in static2} <= set(
        shape_lattice.FAMILY_TAGS)


def test_sched_ledger_prices_waves_as_zero_padding(monkeypatch):
    """Under SCHED_LEDGER=1 mixed traffic, every wave's cells == useful
    tokens (exact-length segments, no bucket rounding, no pow2 group
    replication): padding_waste_frac lands at ~0 — the acceptance
    criterion is ≤ 0.05, construction gives exactly 0."""
    monkeypatch.setenv("SCHED_LEDGER", "1")
    cfg = get_config("tiny")
    eng = _engine(cfg, **RAGGED)
    try:
        qs = [eng.submit(p, GREEDY) for p in MIXED]
        for q in qs:
            toks, err = _collect(q)
            assert err is None, err
        eng.drain(timeout=120)
        sched = eng.debug_sched()
    finally:
        eng.stop()
    assert sched["conservation"]["breaches"] == 0, (
        sched["conservation"]["last_breach"])
    assert sched["useful_tokens"] > 0
    assert sched["bucket_pad_tokens"] == 0
    assert sched["group_pad_tokens"] == 0
    assert sched["padding_waste_frac"] <= 0.05
    ragged_shapes = [e for e in sched["by_shape"]
                     if str(e["key"]).startswith("ragged/")]
    assert ragged_shapes, sched["by_shape"]
    assert all(e["cells"] == e["useful_tokens"] for e in ragged_shapes)


# ---------------------------------------------------------------------------
# Pool exhaustion: preempt, don't wedge
# ---------------------------------------------------------------------------


def test_pool_exhaustion_preempts_and_survivor_is_exact():
    """Two 6-token streams in a pool with 3 usable blocks: both admit
    (1 block each) and both need a second block at the same decode
    boundary. Slot 0 takes the last free block; slot 1's growth finds
    the pool empty and preempts — the victim gets the typed retriable
    error, the survivor finishes bit-exact, nothing leaks."""
    cfg = get_config("tiny")
    p_a = [2, 3, 5, 7, 11, 13]
    p_b = [4, 6, 8, 9, 10, 12]
    want_b = _want(cfg, p_b)

    eng = _engine(cfg, max_seq_len=32, kv_pool_blocks=4, **RAGGED)
    try:
        q_a = eng.submit(p_a, GREEDY)
        q_b = eng.submit(p_b, GREEDY)
        toks_a, err_a = _collect(q_a)
        toks_b, err_b = _collect(q_b)
        snap = eng.stats.snapshot()
        leaks = eng.debug_lifecycle_check()
    finally:
        eng.stop()
    # Exactly one stream lost the race for the second block.
    errs = [e for e in (err_a, err_b) if e is not None]
    assert len(errs) == 1, (err_a, err_b)
    assert errs[0]["kind"] == "preempted", errs[0]
    assert errs[0]["retriable"] is True
    assert snap["preemptions"] >= 1
    # The survivor (deterministically slot order's winner) is bit-exact.
    survivor = toks_b if err_a is not None else toks_a
    want = want_b if err_a is not None else _want(cfg, p_a)
    assert survivor == want
    assert leaks == {}
    assert snap["pool_blocks_used"] == 0


# ---------------------------------------------------------------------------
# Config validation + off-mode isolation
# ---------------------------------------------------------------------------


def test_ragged_config_validation():
    with pytest.raises(ValueError, match="ragged"):
        EngineConfig(ragged=True)  # needs the paged+chunked substrate
    with pytest.raises(ValueError, match="ragged"):
        EngineConfig(ragged=True, paged_kv=True, kv_block=8,
                     prefix_block=8)
    with pytest.raises(ValueError, match="power of two"):
        EngineConfig(ragged=True, paged_kv=True, chunked_prefill=True,
                     kv_block=8, prefill_chunk=8, prefix_block=8,
                     ragged_chunk=24)
    with pytest.raises(ValueError, match="kv_block"):
        EngineConfig(ragged=True, paged_kv=True, chunked_prefill=True,
                     kv_block=16, prefix_block=8, prefill_chunk=16,
                     ragged_chunk=8)
    # The defaults themselves are valid, and ragged_chunk=0 inherits
    # prefill_chunk.
    EngineConfig(ragged=True, paged_kv=True, chunked_prefill=True,
                 kv_block=8, prefill_chunk=8, prefix_block=8)


def test_ragged_off_leaves_engine_untouched():
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, max_seq_len=64, prompt_buckets=(8, 32)))
    assert not eng._ragged
    assert eng._jit_ragged is None
    assert not any(k.startswith("ragged") for k in eng.static_lattice())
