"""Deterministic fault injection (servers/chaos.py) against the engine.

The load-bearing claims, in test form:
 * CHAOS env gating is fail-safe: probabilities without the CHAOS=1
   master switch are inert, and the switch alone (all probs zero) is
   inert too;
 * an injected dispatch failure drives `_fail_all`: the waiter gets a
   typed internal error + sentinel (never a hang), the device/slot
   state is rebuilt, and the very next greedy request is bit-identical
   to pre-fault output — dense AND paged;
 * injected allocator exhaustion only delays paged admission (stall /
   preempt path) — requests still complete and nothing leaks;
 * the acceptance soak: a 200-request mixed run under seeded chaos +
   client deadlines + client cancels finishes with ZERO hung waiters,
   every request in exactly one outcome bucket, and an empty
   `debug_lifecycle_check()` after drain.

The long-haul version of the soak (FUZZ_EXAMPLES requests, paged too)
is marked fuzz+slow: `make fuzz-chaos` runs it, tier-1 does not.
"""

import os
import random
import threading
import time

import jax
import pytest

from seldon_tpu.models import init_params
from seldon_tpu.models.config import get_config
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers.chaos import ChaosConfig, ChaosMonkey
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

PROMPT = list(range(2, 26))
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=8)

PAGED = dict(paged_kv=True, kv_block=16, kv_pool_blocks=9,
             prompt_buckets=(16, 32))


def _engine(cfg=None, start=True, **ekw):
    cfg = cfg or get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    ekw.setdefault("max_slots", 4)
    ekw.setdefault("max_seq_len", 64)
    ekw.setdefault("prompt_buckets", (8, 32))
    eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
    if start:
        eng.start()
    return eng


def _collect(q, timeout=120):
    toks, err = 0, None
    while True:
        item = q.get(timeout=timeout)
        if item is None:
            return toks, err
        if "error" in item:
            err = item
        else:
            toks += len(item["tokens"])


# ---------------------------------------------------------------------------
# Env gating
# ---------------------------------------------------------------------------


def test_chaos_from_env_requires_master_switch(monkeypatch):
    monkeypatch.delenv("CHAOS", raising=False)
    monkeypatch.setenv("CHAOS_DISPATCH_FAIL", "0.5")
    assert ChaosConfig.from_env() is None  # knob without switch: inert

    monkeypatch.setenv("CHAOS", "1")
    cfg = ChaosConfig.from_env()
    assert cfg is not None and cfg.dispatch_fail == 0.5

    monkeypatch.setenv("CHAOS_DISPATCH_FAIL", "0")
    assert ChaosConfig.from_env() is None  # switch without knobs: inert


# ---------------------------------------------------------------------------
# _fail_all coverage via injected dispatch failure
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_dispatch_fault_fails_waiter_and_engine_recovers(paged):
    """Chaos certainty (dispatch_fail=1.0) mid-decode: the waiter gets
    a typed error, never hangs; chaos off again, the rebuilt device
    state serves bit-identical greedy output and nothing leaked."""
    # The fault is armed from this thread after the first token, so
    # the request must still have dispatches ahead of it then: 100
    # single-step ones. PROMPT's own greedy continuation ends at its
    # third token (EOS), which left two steps, about 2 ms, to arm in:
    # a race that a loaded machine lost.
    n_new, long_prompt = 100, list(range(5, 29))
    ekw = dict(decode_chunk=1, min_chunk=1, adaptive_chunk=False,
               max_seq_len=128)
    if paged:
        ekw.update(PAGED)
    eng = _engine(**ekw)
    try:
        want = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]

        q = eng.submit(long_prompt, SamplingParams(
            temperature=0.0, max_new_tokens=n_new))
        first = q.get(timeout=120)
        assert "error" not in first
        # Attribute store is atomic; the scheduler reads it per dispatch.
        eng._chaos = ChaosMonkey(ChaosConfig(seed=0, dispatch_fail=1.0))
        toks, err = _collect(q)
        assert err is not None, "faulted request must error, not complete"
        assert err["kind"] == "internal"
        assert eng._chaos.snapshot()["dispatch_faults"] >= 1
        assert len(first["tokens"]) + toks < n_new

        eng._chaos = None
        got = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        assert got == want, "post-_fail_all rebuild diverged from pre-fault"
        assert eng.debug_lifecycle_check() == {}
    finally:
        eng.stop()


def test_alloc_fault_stalls_or_preempts_never_wedges():
    """Injected pool exhaustion hits `_pool_reserve`: requests either
    complete (admission stalled, then retried) or are preempted with
    the typed retriable error — never hang, never leak."""
    eng = _engine(chaos=ChaosConfig(seed=0, alloc_fail=0.5), **PAGED)
    try:
        qs = [eng.submit([2 + i, 3 + i, 5 + i, 7 + i, 11 + i], GREEDY)
              for i in range(6)]
        done = 0
        for q in qs:
            toks, err = _collect(q)
            if err is None:
                assert 1 <= toks <= 8
                done += 1
            else:
                assert err["kind"] == "preempted", err
                assert err["retriable"] is True
        assert done >= 1, "alloc chaos starved every request"
        assert eng.chaos_counts()["alloc_faults"] >= 1
        assert eng.debug_lifecycle_check() == {}
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# Mixed soak: the acceptance run
# ---------------------------------------------------------------------------


def _run_soak(eng, n, seed, deadline_frac=0.1, cancel_frac=0.1):
    """Submit n requests with injected client behavior (deadlines,
    mid-stream cancels); classify every request into exactly one
    outcome. All randomness is main-thread, drawn before submit, so a
    fixed seed replays the same request stream."""
    rng = random.Random(seed)
    outcomes = {"completed": 0, "shed": 0, "deadline": 0,
                "cancelled": 0, "errored": 0}
    lock = threading.Lock()
    threads = []

    def record(kind):
        with lock:
            outcomes[kind] += 1

    def consume(q, want_cancel):
        err = None
        sent_cancel = False
        while True:
            item = q.get(timeout=300)
            if item is None:
                break
            if "error" in item:
                err = item
                continue
            if want_cancel and not sent_cancel:
                sent_cancel = True
                eng.cancel(q.rid)
        if err is None:
            record("completed")
        else:
            kind = err.get("kind", "internal")
            if kind in ("deadline", "cancelled"):
                record(kind)
            elif kind in ("capacity", "draining", "shutdown"):
                record("shed")
            else:
                record("errored")

    for i in range(n):
        plen = rng.choice((5, 8, 13, 21))
        prompt = [2 + (i + j) % 200 for j in range(plen)]
        dl = rng.choice((30, 80)) if rng.random() < deadline_frac else 0
        want_cancel = rng.random() < cancel_frac
        sp = SamplingParams(temperature=0.0,
                            max_new_tokens=rng.choice((4, 8)),
                            deadline_ms=dl)
        try:
            q = eng.submit(prompt, sp)
        except RuntimeError:  # EngineOverloaded / EngineDraining
            record("shed")
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
    return outcomes, hung


def _soak_engine(n, paged, seed):
    ekw = dict(
        max_slots=8,
        max_queue=4 * n,
        chaos=ChaosConfig(
            seed=seed,
            dispatch_fail=0.02,
            alloc_fail=0.05 if paged else 0.0,
            slow_boundary=0.05,
            slow_ms=2.0,
            disconnect=0.01,
        ),
    )
    if paged:
        ekw.update(PAGED)
    return _engine(**ekw)


def test_chaos_soak_200_requests_exactly_one_outcome():
    """Acceptance: 200 mixed requests under seeded chaos — zero hung
    waiters, one outcome each, accounting empty after drain."""
    n = 200
    eng = _soak_engine(n, paged=False, seed=0)
    try:
        outcomes, hung = _run_soak(eng, n, seed=0)
        assert hung == 0, f"{hung} waiters never saw a sentinel"
        assert sum(outcomes.values()) == n, outcomes
        assert outcomes["completed"] > 0, outcomes
        assert eng.drain(timeout=120) is True
        assert eng.debug_lifecycle_check() == {}
        faults = eng.chaos_counts()
        assert sum(faults.values()) > 0, "chaos never fired — soak is inert"
    finally:
        eng.stop()


@pytest.mark.fuzz
@pytest.mark.slow
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_chaos_soak_long_haul(paged):
    """FUZZ_EXAMPLES-scaled soak (make fuzz-chaos); CHAOS_SEED replays
    a fault sequence exactly."""
    n = int(os.environ.get("FUZZ_EXAMPLES", "500"))
    seed = int(os.environ.get("CHAOS_SEED", "0"))
    eng = _soak_engine(n, paged=paged, seed=seed)
    try:
        outcomes, hung = _run_soak(eng, n, seed=seed,
                                   deadline_frac=0.15, cancel_frac=0.15)
        assert hung == 0, f"{hung} waiters never saw a sentinel"
        assert sum(outcomes.values()) == n, outcomes
        assert eng.drain(timeout=300) is True
        assert eng.debug_lifecycle_check() == {}
    finally:
        eng.stop()
