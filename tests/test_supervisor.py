"""graftheal (servers/supervisor.py + engine recovery paths): replay-
based request resurrection, poison quarantine, dispatch watchdog and
the NaN/garbage sentinel.

The load-bearing claims, in test form:
 * HEAL env gating is fail-safe: knobs without the HEAL=1 master
   switch are inert, a heal-off engine keeps `_heal = None` and the
   raw `_fail_all` failure path;
 * resurrection is BIT-IDENTICAL: a mid-stream wave fault resurrects
   every innocent request and the delivered stream matches the
   fault-free reference token-for-token — dense / paged /
   paged+chunked / spec, bf16 AND int8 KV, greedy AND sampled (per-position sampling
   keys make the replayed continuation exact);
 * poison quarantine bisects: a seeded sticky request that
   deterministically wrecks every wave it rides is isolated in log2
   rounds and failed with ``kind="poison"`` (non-retriable) while
   every innocent completes bit-identically;
 * the dispatch watchdog turns a hung boundary fetch into a normal
   wave fault (WatchdogError -> resurrection) instead of a wedged
   scheduler; the sentinel quarantines out-of-vocab token ids before
   any reaches a client;
 * the retry budget is a hard ceiling: a permanently faulting device
   fails requests with retriable=False after `heal_max_retries`
   resurrections — no infinite replay loop;
 * nothing leaks: every scenario ends with an empty
   `debug_lifecycle_check()`, and the chaos+heal soak finishes with
   zero hung waiters, one outcome per request, and user-visible
   errors bounded by quarantined + retry-exhausted.

The long-haul soak (FUZZ_EXAMPLES requests) is marked fuzz+slow:
`make fuzz-chaos` runs it, tier-1 does not.
"""

import random
import threading
import time
import types

import jax
import numpy as np
import pytest

from _engine_fixture import LIVE_TOKENS, PROMPT, live_config

from seldon_tpu.models import init_params
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers import supervisor
from seldon_tpu.servers.chaos import ChaosConfig, ChaosMonkey
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine
from seldon_tpu.servers.supervisor import (
    HealSupervisor,
    SentinelError,
    WatchdogError,
)

# LIVE_TOKENS: a fault armed after the first item must find a wave still
# to be dispatched, whatever depth the scheduler runs ahead at.
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=LIVE_TOKENS)
SAMPLED = SamplingParams(temperature=0.9, top_k=8, top_p=0.95,
                         max_new_tokens=LIVE_TOKENS, seed=7)

# The resurrection matrix's serving modes (the migration gate: heal
# must not perturb any substrate it rides).
MODES = {
    "dense": dict(),
    "paged": dict(paged_kv=True, kv_block=16, kv_pool_blocks=9,
                  prompt_buckets=(16, 32)),
    "paged+chunked": dict(paged_kv=True, chunked_prefill=True,
                          prefill_chunk=8, prefix_block=8, kv_block=8),
    "spec": dict(spec_decode=True, spec_k=4, paged_kv=True, kv_block=8,
                 prefix_block=8),
}


def _engine(cfg=None, start=True, **ekw):
    cfg = cfg or live_config()
    params = init_params(cfg, jax.random.key(0))
    ekw.setdefault("max_slots", 4)
    ekw.setdefault("max_seq_len", 64)
    # 64 holds the prompt folded with all its tokens: how many were
    # delivered when a fault lands is the host's timing, and a fold past
    # the largest bucket cannot be resurrected at all.
    ekw.setdefault("prompt_buckets", (8, 32, 64))
    eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
    if start:
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


def _arm_one_shot_fault(eng, mk):
    """Install `mk` so its NEXT dispatch fault disarms chaos wholesale
    before raising — exactly one injected wave fault, then a clean
    engine (the attribute store is atomic; the scheduler re-reads
    `_chaos` per dispatch)."""
    orig = mk.on_dispatch

    def once(site, rids=()):
        eng._chaos = None
        orig(site, rids)

    mk.on_dispatch = once
    eng._chaos = mk


# ---------------------------------------------------------------------------
# Env gating + construction discipline
# ---------------------------------------------------------------------------


def test_heal_from_env_requires_master_switch(monkeypatch):
    monkeypatch.delenv("HEAL", raising=False)
    monkeypatch.setenv("HEAL_MAX_RETRIES", "7")
    assert supervisor.from_env() is None  # knob without switch: inert

    monkeypatch.setenv("HEAL", "1")
    sup = supervisor.from_env()
    assert sup is not None and sup.max_retries == 7

    monkeypatch.setenv("HEAL_WATCHDOG_MS", "25")
    assert supervisor.from_env().watchdog_ms == 25


def test_heal_build_prefers_config_over_env(monkeypatch):
    monkeypatch.delenv("HEAL", raising=False)
    off = types.SimpleNamespace(heal=False, heal_max_retries=4,
                                heal_watchdog_ms=0)
    assert supervisor.build(off) is None
    on = types.SimpleNamespace(heal=True, heal_max_retries=2,
                               heal_watchdog_ms=30)
    sup = supervisor.build(on)
    assert sup.max_retries == 2 and sup.watchdog_ms == 30


def test_heal_off_engine_has_no_supervisor(monkeypatch):
    monkeypatch.delenv("HEAL", raising=False)
    eng = _engine(start=False)
    assert eng._heal is None
    assert eng.debug_health() is None


def test_engine_config_rejects_unusable_heal_knobs():
    with pytest.raises(ValueError):
        EngineConfig(heal=True, heal_max_retries=0)
    with pytest.raises(ValueError):
        EngineConfig(heal=True, heal_watchdog_ms=-1)


# ---------------------------------------------------------------------------
# Policy unit tests (no engine: the supervisor sees only rids)
# ---------------------------------------------------------------------------


def test_plan_recovery_first_fault_resurrects_everyone():
    sup = HealSupervisor()
    v = sup.plan_recovery([3, 1, 2], now=0.0)
    assert v == {1: "resurrect", 2: "resurrect", 3: "resurrect"}
    assert sup.state == supervisor.RECOVERING


def test_plan_recovery_repeat_replay_is_penned_with_backoff():
    sup = HealSupervisor()
    sup.plan_recovery([1], now=0.0)
    v = sup.plan_recovery([1], now=0.0)
    # A lone recurring rid enters bisection probing itself — either
    # way the verdict must not be an immediate un-delayed resurrect
    # loop; backoff_s grows with the fault streak.
    assert v[1] in ("resurrect", "pen")
    assert sup.backoff_s() > 0.0
    b2 = sup.backoff_s()
    sup.plan_recovery([1], now=0.0)
    assert sup.backoff_s() >= b2  # exponential in the streak


def test_retry_budget_exhaustion_is_terminal():
    sup = HealSupervisor(max_retries=2)
    sup.plan_recovery([5, 6], 0.0)
    sup.plan_recovery([5, 6], 0.0)  # recurs: bisection probes rid 5
    v = sup.plan_recovery([5, 6], 0.0)
    # Rid 5 faulted while probed alone: convicted. Rid 6 charged its
    # third replay against a budget of 2: exhausted, not resurrected.
    assert v[5] == "poison"
    assert v[6] == "exhausted"
    assert sup.retry_exhausted == 1
    assert sup.state == supervisor.DEGRADED
    # Terminal bookkeeping forgets the budget.
    sup.note_done(6)
    assert 6 not in sup.retries


def test_lone_repeat_faulter_is_convicted_not_looped():
    """A single request that faults every wave it rides IS the poison
    case even with no cohort to bisect against: three faults alone
    convict it (probing itself, then recurring) — never an infinite
    resurrect loop."""
    sup = HealSupervisor(max_retries=8)
    sup.plan_recovery([5], 0.0)
    sup.plan_recovery([5], 0.0)
    v = sup.plan_recovery([5], 0.0)
    assert v[5] == "poison"
    assert sup.quarantined == 1 and sup.mode == "normal"


def test_bisection_convicts_the_recurring_faulter():
    sup = HealSupervisor(max_retries=8)
    sup.plan_recovery([1, 2], 0.0)  # fault 1: both resurrect
    v = sup.plan_recovery([1, 2], 0.0)  # fault 2: bisect begins
    assert sup.mode == "bisect"
    assert sorted(v.values()) == ["pen", "resurrect"]
    probe = next(r for r, verdict in v.items() if verdict == "resurrect")
    sup.pen_put(types.SimpleNamespace(
        rid=3 - probe, finished=False), 0.0)
    # Fault 3 recurs with only the probe live: convicted alone.
    v = sup.plan_recovery([probe], 0.0)
    assert v[probe] == "poison"
    assert sup.quarantined == 1 and sup.mode == "normal"
    assert sup.state == supervisor.DEGRADED
    # Conviction flips the penned innocent due for release.
    assert [r.rid for r in sup.pen_take(0.0)] == [3 - probe]


def test_bisection_progress_exonerates_and_advances():
    sup = HealSupervisor(max_retries=8)
    sup.plan_recovery([1, 2, 3, 4], 0.0)
    sup.plan_recovery([1, 2, 3, 4], 0.0)
    assert sup.mode == "bisect" and sup.probing == {1, 2}
    for rid in (3, 4):
        sup.pen_put(types.SimpleNamespace(rid=rid, finished=False), 0.0)
    sup.note_progress(1)
    assert sup.probing == {2}  # half-resolved: still waiting on 2
    sup.note_progress(2)
    # First half exonerated: the next suspects half is probed and its
    # pen entries flip due.
    assert sup.mode == "bisect" and sup.probing == {3}
    assert [r.rid for r in sup.pen_take(0.0)] == [3]
    sup.note_progress(3)
    assert sup.probing == {4}
    sup.note_progress(4)
    # Everyone exonerated: bisection exits, the pen drains.
    assert sup.mode == "normal" and not sup.suspects
    assert [r.rid for r in sup.pen_take(0.0)] == [4]


def test_bisection_note_done_resolves_probe_interest():
    sup = HealSupervisor(max_retries=8)
    sup.plan_recovery([1, 2], 0.0)
    sup.plan_recovery([1, 2], 0.0)
    probe = next(iter(sup.probing))
    sup.note_done(probe)  # probe finished (EOS) while under suspicion
    assert probe not in sup.suspects
    assert sup.probing == {3 - probe}


def test_pen_backoff_release_flush_and_finished_drop():
    sup = HealSupervisor()
    sup.plan_recovery([1], 0.0)
    sup.plan_recovery([1], 0.0)
    sup._exit_bisect_locked()  # force backoff-pen mode for the test
    sup.mode = "normal"
    r1 = types.SimpleNamespace(rid=1, finished=False)
    r2 = types.SimpleNamespace(rid=2, finished=False)
    sup.pen_put(r1, now=10.0)
    assert sup.pen_take(10.0) == []  # backoff not elapsed
    assert sup.pen_take(10.0 + supervisor._BACKOFF_MAX_S) == [r1]
    sup.pen_put(r2, now=10.0)
    assert sup.pen_take(10.0, flush=True) == [r2]  # drain releases all
    r3 = types.SimpleNamespace(rid=3, finished=True)
    sup.pen_put(r3, now=10.0)
    assert sup.pen_take(10.0, flush=True) == []  # reaped while penned
    assert sup.pen_empty()
    assert [r.rid for r in sup.pen_scan()] == []


def test_clean_boundary_streak_walks_back_to_healthy():
    sup = HealSupervisor()
    sup.plan_recovery([1], 0.0)
    assert sup.state == supervisor.RECOVERING and sup.pressure() == 0.5
    for _ in range(supervisor.CLEAN_BOUNDARIES_FOR_HEALTHY):
        sup.note_boundary_ok()
    assert sup.state == supervisor.HEALTHY and sup.pressure() == 0.0
    assert sup.consec_faults == 0


def test_watchdog_bounds_a_hung_fetch_and_recovers():
    sup = HealSupervisor(watchdog_ms=40)
    with pytest.raises(WatchdogError):
        sup.bounded_fetch(lambda: time.sleep(2.0))
    assert sup.watchdog_trips == 1
    # The wedged worker was abandoned wholesale: a fresh call gets a
    # fresh worker and the orphan result can never collide.
    assert sup.bounded_fetch(lambda: 7) == 7

    def boom():
        raise ValueError("from the fetch")

    with pytest.raises(ValueError):  # worker exceptions propagate
        sup.bounded_fetch(boom)
    assert sup.watchdog_trips == 1


def test_watchdog_zero_runs_inline():
    sup = HealSupervisor(watchdog_ms=0)
    assert sup.bounded_fetch(lambda: 11) == 11
    assert sup._wd_thread is None  # no helper thread was ever spawned


def test_sentinel_flags_out_of_vocab_ids():
    sup = HealSupervisor()
    ok_admit = [(np.array([3, 250]), np.array([1.0]))]
    sup.check_tokens(ok_admit, None, vocab_size=256)
    assert sup.sentinel_trips == 0
    with pytest.raises(SentinelError):
        sup.check_tokens(
            [(np.array([3, 1 << 30]), None)], None, vocab_size=256)
    with pytest.raises(SentinelError):
        sup.check_tokens([(np.array([-1]), None)], None, vocab_size=256)
    with pytest.raises(SentinelError):  # chunk-side tokens screened too
        sup.check_tokens([], (np.array([999]),), vocab_size=256)
    assert sup.sentinel_trips == 3


# ---------------------------------------------------------------------------
# Bit-identical resurrection: the migration gate
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("mode", sorted(MODES))
def test_resurrection_bit_identical_across_modes(mode, kv_dtype):
    """A mid-stream wave fault under HEAL: both live streams (greedy
    AND sampled) are resurrected and their delivered tokens match the
    fault-free reference exactly — per-position sampling keys make the
    replayed continuation bit-identical on every substrate x KV
    dtype."""
    cfg = live_config(kv_cache_dtype=kv_dtype)
    # A bucket that holds the prompt folded with all its tokens: how many
    # were delivered when the fault lands is the host's timing, and a
    # fold past the largest bucket cannot be resurrected at all.
    ekw = dict(MODES[mode])
    ekw["prompt_buckets"] = (*ekw.get("prompt_buckets", (8, 32)), 64)
    ref = _engine(cfg, **ekw)
    try:
        want_g = ref.generate_blocking(PROMPT, GREEDY)["token_ids"]
        want_s = ref.generate_blocking(PROMPT, SAMPLED)["token_ids"]
    finally:
        ref.stop()

    eng = _engine(cfg, heal=True, **ekw)
    try:
        qg = eng.submit(PROMPT, GREEDY)
        qs = eng.submit(PROMPT, SAMPLED)
        got_g = list(qg.get(timeout=120)["tokens"])
        got_s = list(qs.get(timeout=120)["tokens"])
        _arm_one_shot_fault(
            eng, ChaosMonkey(ChaosConfig(seed=0, dispatch_fail=1.0)))
        tg, eg = _collect(qg)
        ts, es = _collect(qs)
        assert eg is None and es is None, (eg, es)
        got_g += tg
        got_s += ts
        health = eng.debug_health()
        assert health["recoveries"] >= 1, \
            "the one-shot fault never fired — the gate is inert"
        assert health["resurrected"] >= 1
        assert eng.debug_lifecycle_check() == {}
    finally:
        eng.stop()
    assert got_g == want_g, "greedy resurrection diverged"
    assert got_s == want_s, "sampled resurrection diverged"


# ---------------------------------------------------------------------------
# Poison quarantine: bisection isolates the seeded culprit
# ---------------------------------------------------------------------------


def test_poison_bisection_isolates_sticky_culprit():
    """A sticky chaos fault pins rid 3: every decode wave it rides
    faults, deterministically. The bisection must convict exactly that
    request (kind="poison", non-retriable) while rids 1, 2 and 4 all
    complete bit-identically."""
    ref = _engine()
    try:
        want = ref.generate_blocking(PROMPT, GREEDY)["token_ids"]
    finally:
        ref.stop()

    eng = _engine(heal=True, heal_max_retries=8,
                  chaos=ChaosConfig(seed=0, sticky_rid=3))
    try:
        qs = [eng.submit(PROMPT, GREEDY) for _ in range(4)]
        results = [_collect(q, timeout=300) for q in qs]
        for i, (toks, err) in enumerate(results):
            rid = i + 1  # rids are assigned sequentially from 1
            if rid == 3:
                assert err is not None, "the sticky request completed?!"
                assert err["kind"] == "poison", err
                assert err["retriable"] is False
            else:
                assert err is None, (rid, err)
                assert toks == want, f"innocent rid {rid} diverged"
        health = eng.debug_health()
        assert health["quarantined"] == 1
        # The conviction marked the engine degraded; the innocents'
        # clean decode streak afterwards may already have walked the
        # state machine back (note_boundary_ok) — both are legal here,
        # what matters is the quarantine counter above is permanent.
        assert health["state"] in ("degraded", "healthy")
        assert health["mode"] == "normal"  # bisection resolved
        assert eng.chaos_counts()["sticky_faults"] >= 2
        assert eng.debug_lifecycle_check() == {}
        # The engine is fully live post-quarantine (rid 5 > sticky).
        assert eng.generate_blocking(PROMPT, GREEDY)["token_ids"] == want
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# Watchdog + sentinel at engine level
# ---------------------------------------------------------------------------


def test_watchdog_turns_hung_fetch_into_recovery():
    """One injected fetch hang, longer than heal_watchdog_ms: the wave
    is declared faulted and resurrected instead of wedging the
    scheduler — the stream still completes bit-identically."""
    ref = _engine()
    try:
        want = ref.generate_blocking(PROMPT, GREEDY)["token_ids"]
    finally:
        ref.stop()

    eng = _engine(heal=True, heal_watchdog_ms=60)
    try:
        q = eng.submit(PROMPT, GREEDY)
        got = list(q.get(timeout=120)["tokens"])
        mk = ChaosMonkey(ChaosConfig(seed=0, hang=1.0, hang_ms=1000))
        orig = mk.maybe_hang

        def once():
            eng._chaos = None  # one-shot: disarm before the sleep
            orig()

        mk.maybe_hang = once
        eng._chaos = mk
        toks, err = _collect(q)
        assert err is None, err
        got += toks
        health = eng.debug_health()
        assert health["watchdog_trips"] >= 1
        assert health["recoveries"] >= 1
        assert eng.debug_lifecycle_check() == {}
    finally:
        eng.stop()
    assert got == want, "post-watchdog resurrection diverged"


def test_sentinel_quarantines_corrupt_tokens_before_delivery():
    """One injected out-of-vocab token id in a fetched boundary: the
    sentinel trips recovery BEFORE the corrupt id reaches the client —
    the delivered stream is still exactly the reference."""
    ref = _engine()
    try:
        want = ref.generate_blocking(PROMPT, GREEDY)["token_ids"]
    finally:
        ref.stop()

    eng = _engine(heal=True)
    try:
        q = eng.submit(PROMPT, GREEDY)
        got = list(q.get(timeout=120)["tokens"])
        mk = ChaosMonkey(ChaosConfig(seed=0, nan_inject=1.0))
        orig = mk.poison_fetch

        def once(arrays):
            eng._chaos = None  # one-shot: disarm before poisoning
            orig(arrays)

        mk.poison_fetch = once
        eng._chaos = mk
        toks, err = _collect(q)
        assert err is None, err
        got += toks
        health = eng.debug_health()
        assert health["sentinel_trips"] >= 1
        assert health["recoveries"] >= 1
        assert eng.debug_lifecycle_check() == {}
    finally:
        eng.stop()
    assert got == want, "post-sentinel resurrection diverged"
    assert all(0 <= t < live_config().vocab_size for t in got), \
        "a corrupt token id reached the client"


# ---------------------------------------------------------------------------
# Retry budget at engine level
# ---------------------------------------------------------------------------


def test_retry_budget_exhaustion_fails_cleanly():
    """A permanently faulting device (dispatch_fail=1.0, never
    disarmed): resurrection retries up to heal_max_retries, then fails
    the request retriable=False — chaos off again, the engine serves
    bit-identical output and nothing leaked. (Budget 1 so exhaustion
    fires before the lone-faulter bisection can convict it as poison.)"""
    eng = _engine(heal=True, heal_max_retries=1)
    try:
        want = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        q = eng.submit(PROMPT, SamplingParams(
            temperature=0.0, max_new_tokens=40))
        first = q.get(timeout=120)
        assert "error" not in first
        eng._chaos = ChaosMonkey(ChaosConfig(seed=0, dispatch_fail=1.0))
        toks, err = _collect(q, timeout=300)
        assert err is not None, "exhausted request must error, not hang"
        assert err["kind"] == "internal"
        assert err["retriable"] is False
        assert "exhausted" in err["error"]
        health = eng.debug_health()
        assert health["retry_exhausted"] >= 1
        assert health["state"] == "degraded"

        eng._chaos = None
        assert eng.generate_blocking(PROMPT, GREEDY)["token_ids"] == want
        assert eng.debug_lifecycle_check() == {}
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# Chaos + heal soak: the acceptance invariants
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
                record("errored")  # internal/poison/preempted: visible

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


def _heal_soak_engine(n, paged, seed):
    ekw = dict(
        max_slots=8,
        max_queue=4 * n,
        heal=True,
        heal_max_retries=3,
        heal_watchdog_ms=250,
        chaos=ChaosConfig(
            seed=seed,
            dispatch_fail=0.02,
            alloc_fail=0.05 if paged else 0.0,
            slow_boundary=0.05,
            slow_ms=2.0,
            disconnect=0.01,
            nan_inject=0.01,
            hang=0.01,
            hang_ms=400.0,
        ),
    )
    if paged:
        ekw.update(paged_kv=True, kv_block=16, kv_pool_blocks=24,
                   prompt_buckets=(16, 32))
    return _engine(**ekw)


def _assert_soak_invariants(eng, outcomes, hung, n):
    assert hung == 0, f"{hung} waiters never saw a sentinel"
    assert sum(outcomes.values()) == n, outcomes
    assert outcomes["completed"] > 0, outcomes
    health = eng.debug_health()
    # The heal contract: a wave fault is not a user-visible error.
    # The only requests a healing engine may fail for engine-side
    # reasons are quarantined poisons, exhausted retries, and paged
    # preemptions (retriable capacity pushback, not a fault).
    preempted = eng.stats.snapshot().get("preemptions", 0)
    budget = (health["quarantined"] + health["retry_exhausted"]
              + preempted)
    assert outcomes["errored"] <= budget, (outcomes, health)
    assert eng.drain(timeout=120) is True
    assert eng.debug_lifecycle_check() == {}
    faults = eng.chaos_counts()
    assert sum(faults.values()) > 0, "chaos never fired — soak is inert"


def test_heal_soak_80_requests_bounded_visible_errors():
    """Tier-1 soak: 80 mixed requests under seeded chaos WITH heal —
    zero hung waiters, one outcome each, user-visible errors bounded
    by quarantine + budget exhaustion (+ preemption), empty accounting
    after drain."""
    n = 80
    eng = _heal_soak_engine(n, paged=False, seed=0)
    try:
        outcomes, hung = _run_soak(eng, n, seed=0)
        _assert_soak_invariants(eng, outcomes, hung, n)
    finally:
        eng.stop()


@pytest.mark.fuzz
@pytest.mark.slow
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_heal_soak_long_haul(paged):
    """FUZZ_EXAMPLES-scaled heal soak (make fuzz-chaos); CHAOS_SEED
    replays a fault sequence exactly."""
    import os

    n = int(os.environ.get("FUZZ_EXAMPLES", "300"))
    seed = int(os.environ.get("CHAOS_SEED", "0"))
    eng = _heal_soak_engine(n, paged=paged, seed=seed)
    try:
        outcomes, hung = _run_soak(eng, n, seed=seed,
                                   deadline_frac=0.15, cancel_frac=0.15)
        _assert_soak_invariants(eng, outcomes, hung, n)
    finally:
        eng.stop()
