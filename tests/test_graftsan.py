"""graftsan (runtime concurrency sanitizer): witness + audit semantics.

The load-bearing claims, in test form:
 * env gating is fail-safe AND overhead-free: without GRAFTSAN=1 the
   engine keeps raw threading primitives, `_san is None`, and response
   queues are plain `queue.Queue` — nothing to pay on any hot path;
 * the lock-order witness raises on an injected inversion with a
   TWO-stack report (where the held lock was taken, where the violating
   acquisition happened), enforces the re-acquisition self-deadlock
   rule, and still allows legal RLock re-entry;
 * `assert_holds` is the runtime half of `# graftlint: holds(<lock>)`;
 * the boundary audit catches injected refcount drift in BOTH
   directions (phantom allocator ref = leak, phantom table ref = double
   free) and slot/free-list corruption — and the engine stays healthy
   once the injected damage is reverted;
 * TerminalQueue rejects anything put after the terminal sentinel;
 * greedy token output is BIT-IDENTICAL with the sanitizer on or off
   (the seeded perturbation is timing-only), and the perturbation
   streams are deterministic per seed with the same scheduler/fetcher
   RNG split as chaos;
 * the fuzz soak: >=200 mixed dense/paged/chunked requests under
   GRAFTSAN=1 finish with zero hung waiters, zero recorded violations,
   and a clean `debug_lifecycle_check()` (make fuzz-graftsan).
"""

import os
import queue
import random
import threading
import time

import jax
import pytest

from seldon_tpu.models import init_params
from seldon_tpu.models.config import get_config
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers import graftsan
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine
from seldon_tpu.servers.graftsan import (GraftsanViolation, Sanitizer,
                                         TerminalQueue)

PROMPT = list(range(2, 26))
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=8)

PAGED = dict(paged_kv=True, kv_block=16, kv_pool_blocks=12,
             prompt_buckets=(16, 32))
CHUNKED = dict(chunked_prefill=True, prefill_chunk=8, prefix_block=8)


def _engine(start=True, **ekw):
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    ekw.setdefault("max_slots", 4)
    ekw.setdefault("max_seq_len", 64)
    ekw.setdefault("prompt_buckets", (8, 32))
    eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
    if start:
        eng.start()
    return eng


@pytest.fixture
def san_env(monkeypatch):
    monkeypatch.setenv("GRAFTSAN", "1")
    monkeypatch.setenv("GRAFTSAN_SEED", "0")


# ---------------------------------------------------------------------------
# Gating + zero overhead when off
# ---------------------------------------------------------------------------


def test_from_env_gate(monkeypatch):
    monkeypatch.delenv("GRAFTSAN", raising=False)
    assert Sanitizer.from_env() is None
    monkeypatch.setenv("GRAFTSAN", "0")
    assert Sanitizer.from_env() is None
    monkeypatch.setenv("GRAFTSAN", "1")
    monkeypatch.setenv("GRAFTSAN_SEED", "7")
    san = Sanitizer.from_env()
    assert san is not None and san.seed == 7


def test_zero_overhead_when_unset(monkeypatch):
    monkeypatch.delenv("GRAFTSAN", raising=False)
    eng = _engine(start=False)
    assert eng._san is None
    assert not isinstance(eng._book, graftsan._OrderedLock)
    assert not isinstance(eng._rid_lock, graftsan._OrderedLock)
    assert not isinstance(eng.stats.lock, graftsan._OrderedLock)
    q = eng.submit(PROMPT, GREEDY)
    assert type(q) is queue.Queue  # not TerminalQueue


def test_instrumented_engine_structures(san_env):
    eng = _engine(start=False, **PAGED)
    assert isinstance(eng._san, Sanitizer)
    assert isinstance(eng._book, graftsan._OrderedLock)
    assert isinstance(eng._rid_lock, graftsan._OrderedLock)
    assert isinstance(eng.stats.lock, graftsan._OrderedLock)
    assert isinstance(eng._allocator._lock, graftsan._OrderedLock)
    q = eng.submit(PROMPT, GREEDY)
    assert isinstance(q, TerminalQueue)


# ---------------------------------------------------------------------------
# Lock-order witness
# ---------------------------------------------------------------------------


def test_documented_order_is_silent():
    san = Sanitizer()
    book = san.wrap_lock(threading.Lock(), "_book")
    rid = san.wrap_lock(threading.Lock(), "_rid_lock")
    trie = san.wrap_lock(threading.Lock(), "trie._lock")
    alloc = san.wrap_lock(threading.Lock(), "allocator._lock")
    with book:
        with rid:
            pass
        with trie:
            with alloc:
                pass
    assert san.violations == []


def test_order_witness_two_stack_report():
    san = Sanitizer()
    book = san.wrap_lock(threading.Lock(), "_book")
    stats = san.wrap_lock(threading.Lock(), "stats.lock")
    with stats:  # leaf held: acquiring ANYTHING under it is a violation
        with pytest.raises(GraftsanViolation) as ei:
            with book:
                pass
    v = ei.value.violation
    assert v.kind == "lock-order"
    assert "'_book'" in v.message and "'stats.lock'" in v.message
    assert "leaf" in v.message
    assert v.stack and v.other_stack  # both participating sites captured
    assert san.violations == [v]
    rendered = ei.value.args[0]
    assert "detected at" in rendered and "conflicting event" in rendered


def test_order_witness_rank_inversion():
    san = Sanitizer()
    book = san.wrap_lock(threading.Lock(), "_book")
    trie = san.wrap_lock(threading.Lock(), "trie._lock")
    with trie:
        with pytest.raises(GraftsanViolation, match="inverts"):
            with book:
                pass


def test_reacquisition_self_deadlock():
    san = Sanitizer()
    book = san.wrap_lock(threading.Lock(), "_book")
    with book:
        with pytest.raises(GraftsanViolation, match="self-deadlock"):
            book.acquire()


def test_rlock_reentry_is_legal():
    san = Sanitizer()
    lk = san.wrap_lock(threading.RLock(), "Engine._jit_lock")
    with lk:
        with lk:
            pass
    assert san.violations == []


def test_wrap_lock_is_idempotent():
    san = Sanitizer()
    lk = san.wrap_lock(threading.Lock(), "_book")
    assert san.wrap_lock(lk, "_book") is lk


def test_assert_holds():
    san = Sanitizer()
    book = san.wrap_lock(threading.Lock(), "_book")
    with book:
        san.assert_holds("_book")  # satisfied, silent
    with pytest.raises(GraftsanViolation) as ei:
        san.assert_holds("_book")
    assert ei.value.violation.kind == "holds"
    assert "holds(_book)" in ei.value.args[0] or "_book" in ei.value.args[0]


def test_held_stacks_are_per_thread():
    san = Sanitizer()
    book = san.wrap_lock(threading.Lock(), "_book")
    stats = san.wrap_lock(threading.Lock(), "stats.lock")
    errs = []

    def other():
        # This thread holds nothing: taking _book here is clean even
        # while the main thread holds the leaf.
        try:
            with book:
                pass
        except GraftsanViolation as e:  # pragma: no cover
            errs.append(e)

    with stats:
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive() and errs == []
    assert san.violations == []


# ---------------------------------------------------------------------------
# Terminal-item protocol
# ---------------------------------------------------------------------------


def test_terminal_queue_rejects_items_after_sentinel():
    san = Sanitizer()
    q = TerminalQueue(san)
    q.put({"tokens": [1]})
    q.put(None)
    with pytest.raises(GraftsanViolation) as ei:
        q.put({"tokens": [2]})
    v = ei.value.violation
    assert v.kind == "terminal"
    assert v.other_stack  # where the original sentinel was put
    with pytest.raises(GraftsanViolation, match="second terminal"):
        q.put(None)
    assert len(san.violations) == 2


# ---------------------------------------------------------------------------
# Boundary audits with injected damage
# ---------------------------------------------------------------------------


def test_slot_audit_catches_free_list_corruption(san_env):
    eng = _engine()
    try:
        eng.generate_blocking(PROMPT, GREEDY)
        with eng._book:
            eng._san.audit(eng)  # quiescent engine: clean
            eng._free.append(eng._free[0])  # inject a duplicate entry
            with pytest.raises(GraftsanViolation) as ei:
                eng._san.audit(eng)
            assert ei.value.violation.kind == "slot-audit"
            eng._free.pop()
            eng._san.violations.clear()
        eng.generate_blocking(PROMPT, GREEDY)  # engine still healthy
        assert eng.debug_lifecycle_check() == {}
    finally:
        eng.stop()


def test_refcount_audit_catches_injected_leak(san_env):
    eng = _engine(**PAGED)
    try:
        eng.generate_blocking(PROMPT, GREEDY)
        with eng._book:
            eng._san.audit(eng)
            # A ref the live tables know nothing about = leaked block.
            eng._allocator._refs[9999] = 1
            with pytest.raises(GraftsanViolation) as ei:
                eng._san.audit(eng)
            v = ei.value.violation
            assert v.kind == "refcount" and "leak" in v.message
            del eng._allocator._refs[9999]
            eng._san.violations.clear()
        eng.generate_blocking(PROMPT, GREEDY)
        assert eng.debug_lifecycle_check() == {}
    finally:
        eng.stop()


def test_refcount_audit_catches_injected_double_free(san_env):
    eng = _engine(**PAGED)
    try:
        q = eng.submit(PROMPT, SamplingParams(
            temperature=0.0, max_new_tokens=32))
        # Catch the request mid-decode: poll under _book until it is
        # admitted and owns blocks, then tamper + audit in the SAME
        # _book hold so it cannot complete underneath us.
        deadline = time.monotonic() + 120
        caught = False
        while not caught and time.monotonic() < deadline:
            with eng._book:
                with eng._rid_lock:
                    reqs = list(eng._requests.values())
                if reqs and reqs[0].block_ids:
                    caught = True
                    req = reqs[0]
                    # A table ref the allocator never granted = double
                    # free waiting to happen on release.
                    req.block_ids.append(7777)
                    with pytest.raises(GraftsanViolation) as ei:
                        eng._san.audit(eng)
                    v = ei.value.violation
                    assert v.kind == "refcount"
                    assert "double free" in v.message
                    req.block_ids.pop()
                    eng._san.violations.clear()
            if not caught:
                time.sleep(0.005)
        assert caught, "request never observed mid-decode"
        while q.get(timeout=120) is not None:
            pass
        assert eng.debug_lifecycle_check() == {}
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# Determinism: perturbation streams + bit-exact output
# ---------------------------------------------------------------------------


def test_perturb_streams_split_and_deterministic():
    a, b = Sanitizer(seed=3), Sanitizer(seed=3)
    for _ in range(50):
        a.perturb("dispatch")
        a.perturb("reap")
        b.perturb("dispatch")
        b.perturb("reap")
    # same seed, same sites -> same stream position
    assert a._sched_rng.random() == b._sched_rng.random()
    # boundary draws come from the independent fetcher stream: burning
    # them must not move the scheduler stream (chaos RNG-split rule)
    c, d = Sanitizer(seed=3), Sanitizer(seed=3)
    for _ in range(50):
        c.perturb("boundary")
    assert c._sched_rng.random() == d._sched_rng.random()
    assert c._fetch_rng.random() != d._fetch_rng.random()


@pytest.mark.parametrize("mode", ["dense", "paged", "chunked"])
def test_greedy_output_bit_identical_with_sanitizer(mode, monkeypatch):
    ekw = {"dense": {}, "paged": PAGED, "chunked": CHUNKED}[mode]
    monkeypatch.delenv("GRAFTSAN", raising=False)
    eng = _engine(**ekw)
    try:
        want = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
    finally:
        eng.stop()

    monkeypatch.setenv("GRAFTSAN", "1")
    monkeypatch.setenv("GRAFTSAN_SEED", "0")
    eng = _engine(**ekw)
    try:
        got = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        assert eng._san is not None
        assert eng._san.violations == []
    finally:
        eng.stop()
    # read after stop() has joined the fetcher: the audit runs after the
    # delivery that lets generate_blocking return, in the same boundary
    assert eng._san.audits > 0  # the boundary audit actually ran
    assert got == want


# ---------------------------------------------------------------------------
# Fuzz soak: mixed dense/paged/chunked under the sanitizer
# ---------------------------------------------------------------------------


def _run_soak(eng, n, seed, cancel_frac=0.1):
    """Submit n requests (sizes drawn main-thread from a fixed seed so
    a run replays exactly), consume each from its own waiter thread,
    cancel a fraction mid-stream. Returns (finished, hung)."""
    rng = random.Random(seed)
    threads = []

    def consume(q, want_cancel):
        sent = False
        while True:
            item = q.get(timeout=300)
            if item is None:
                return
            if want_cancel and not sent and "error" not in item:
                sent = True
                eng.cancel(q.rid)

    for i in range(n):
        plen = rng.choice((5, 8, 13, 21))
        prompt = [2 + (i + j) % 200 for j in range(plen)]
        sp = SamplingParams(temperature=0.0,
                            max_new_tokens=rng.choice((4, 8)))
        want_cancel = rng.random() < cancel_frac
        try:
            q = eng.submit(prompt, sp)
        except RuntimeError:  # shed under load: an outcome, not a hang
            continue
        t = threading.Thread(target=consume, args=(q, want_cancel),
                             daemon=True)
        t.start()
        threads.append(t)

    stop_by = time.monotonic() + 300
    hung = 0
    for t in threads:
        t.join(timeout=max(0.0, stop_by - time.monotonic()))
        if t.is_alive():
            hung += 1
    return len(threads), hung


@pytest.mark.fuzz
@pytest.mark.slow
@pytest.mark.parametrize("mode", ["dense", "paged", "chunked"])
def test_graftsan_soak_mixed(mode, monkeypatch):
    """>=200 requests across the three modes (make fuzz-graftsan): the
    sanitizer's witness + audits stay silent on the real engine, every
    waiter sees a sentinel, nothing leaks."""
    monkeypatch.setenv("GRAFTSAN", "1")
    seed = int(os.environ.get("GRAFTSAN_SEED", "0"))
    monkeypatch.setenv("GRAFTSAN_SEED", str(seed))
    n = max(1, int(os.environ.get("FUZZ_EXAMPLES", "210")) // 3)
    ekw = {"dense": {}, "paged": PAGED, "chunked": CHUNKED}[mode]
    eng = _engine(max_slots=8, max_queue=4 * n, **ekw)
    try:
        finished, hung = _run_soak(eng, n, seed=seed)
        assert hung == 0, f"{hung} waiters never saw a sentinel"
        assert finished > 0
        assert eng.drain(timeout=300) is True
        assert eng._san.audits > 0
        assert eng._san.violations == [], [
            v.render() for v in eng._san.violations]
        assert eng.debug_lifecycle_check() == {}
    finally:
        eng.stop()
