"""How far the async scheduler runs ahead of the device (engine.
_loop_async, _DepthEstimator): one bound, on dispatched-and-unretired
waves, derived from the wave period and the host turn the engine
measures on itself (tiny configs, CPU).

No test here asserts a wall-clock rate: the estimator is checked as a
pure function and a state machine, the engine by what it counts."""

import sys
import threading
import time

import jax
import pytest

from seldon_tpu.models import init_params
from seldon_tpu.models.config import get_config
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers import engine as engine_mod
from seldon_tpu.servers.chaos import ChaosConfig
from seldon_tpu.servers.engine import (EngineConfig, InferenceEngine,
                                       _DepthEstimator, _pipeline_depth)

MODES = {
    "dense": {},
    "chunked": dict(chunked_prefill=True, prefill_chunk=8, prefix_block=8),
    "paged": dict(paged_kv=True, kv_block=8, prefix_block=8),
}
PROMPTS = [[3 + (i + j) % 200 for j in range(n)]
           for i, n in enumerate((5, 12, 20, 7, 9, 26, 14, 6))]
BUDGETS = (6, 30, 12, 24, 9, 30, 18, 27)  # a mixed burst


def _engine(**ekw):
    cfg = get_config("tiny")
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


def _burst(eng, rounds=1):
    """Submit the mixed burst `rounds` times; [(tokens, error)] in order."""
    outs = []
    for _ in range(rounds):
        qs = [eng.submit(p, SamplingParams(temperature=0.0,
                                           max_new_tokens=n))
              for p, n in zip(PROMPTS, BUDGETS)]
        outs.extend(_collect(q) for q in qs)
    return outs


# --- the estimator as a pure function ----------------------------------------

@pytest.mark.parametrize("period_ms, turn_ms, want", [
    (81.0, 5.0, 2),      # mixtral.chat: a 4-step chunk, a turn of 2-6 ms
    (135.0, 5.0, 2),     # mistral7b.chat
    (81.0, 39.0, 2),     # ... up to a turn of just under half a chunk
    (81.0, 42.0, 3),     # and three from there
    (28.0, 100.0, 5),    # a 1B model behind a tunnelled device: as before
    (10.0, 10.0, 3),
    (10.0, 14.0, 4),
    (1000.0, 0.0, 2),    # the floor: one running, one queued
    (0.001, 1000.0, 5),  # the ceiling: never deeper than the old bound
    (0.0, 1.0, 5),       # no period to speak of
])
def test_depth_from_period_and_turn(period_ms, turn_ms, want):
    assert _pipeline_depth(period_ms / 1e3, turn_ms / 1e3) == want


@pytest.mark.parametrize("period_ms, turn_ms", [(81.0, 5.0), (135.0, 5.0),
                                                (81.0, 6.0), (135.0, 2.0)])
def test_depth_does_not_hunt_under_jitter(period_ms, turn_ms):
    jitter = (0.8, 0.9, 1.0, 1.1, 1.2)
    assert {_pipeline_depth(period_ms * a / 1e3, turn_ms * b / 1e3)
            for a in jitter for b in jitter} == {2}


def _feed(est, period, turn, n, t0=0.0):
    """n waves retired `period` apart with another in flight, each
    letting a dispatch through that returns `turn` later."""
    t = t0
    for _ in range(n):
        t += period
        est.note_retire(t, None, True)
        est.note_turn(t + turn)
    return t


def test_estimator_holds_the_floor_until_it_has_samples():
    est = _DepthEstimator()
    assert est.depth() == 2
    _feed(est, 0.010, 0.014, engine_mod._DEPTH_SAMPLES)
    # the first retirement opens an interval and closes none
    assert est.period.n == engine_mod._DEPTH_SAMPLES - 1
    assert est.depth() == 2
    _feed(est, 0.010, 0.014, 1, t0=est.fetched_at)
    assert est.depth() == 4
    g = est.gauges()
    assert g["depth"] == 4
    assert g["wave_period_ms"] == pytest.approx(10.0)
    assert g["host_turn_ms"] == pytest.approx(14.0)


def test_estimator_follows_the_chunk_time_both_ways():
    est = _DepthEstimator()
    t = _feed(est, 0.081, 0.005, 40)
    assert est.depth() == 2
    t = _feed(est, 0.004, 0.005, 40, t0=t)  # the chunk shrinks: deeper
    assert est.depth() == 4
    _feed(est, 0.081, 0.005, 40, t0=t)      # and back
    assert est.depth() == 2


def test_estimator_counts_only_waves_the_device_paced():
    est = _DepthEstimator()
    est.note_retire(1.0, None, False)  # the pipeline ran empty after it
    est.note_retire(9.0, None, True)   # so this interval is idleness
    assert est.period.n == 0
    est.note_retire(9.1, None, True)
    assert (est.period.n, est.period.value) == (1, pytest.approx(0.1))
    est.note_retire(None, None, True)  # dropped unread: no pace either
    est.note_retire(20.0, None, True)
    assert est.period.n == 1
    # a device_get on finished data is the transfer; it joins the turn
    est.note_retire(20.1, 0.002, True)
    est.note_turn(20.103)
    assert est.host_turn_s() == pytest.approx(0.005)


def test_a_compile_in_a_turn_is_clipped_not_remembered_for_minutes():
    est = _DepthEstimator()
    t = _feed(est, 0.081, 0.005, 20)
    est.note_retire(t + 0.081, None, True)
    est.note_turn(t + 0.081 + 20.0)  # a 20 s compile inside _dispatch_once
    # one sample may deepen the pipeline, by one wave here
    assert est.depth() <= 3
    _feed(est, 0.081, 0.005, 4, t0=t + 0.081)
    assert est.depth() == 2


# --- the engine holds the bound ----------------------------------------------

def _spy(eng):
    """Record, at every dispatch, the waves in flight before it against
    the depth in force, and per request waves_ahead against the same."""
    seen = {"dispatch": [], "ahead": []}
    dispatch, record = eng._dispatch_once, eng._record_first_dispatch

    def dispatch_once():
        seen["dispatch"].append(
            (len(eng._inflight_waves), eng._depth_est.depth()))
        return dispatch()

    def record_first(group):
        fresh = [r for r in group if r.first_dispatch_at is None]
        record(group)
        depth = eng._depth_est.depth()
        seen["ahead"].extend((r.waves_ahead, depth) for r in fresh)

    eng._dispatch_once = dispatch_once
    eng._record_first_dispatch = record_first
    return seen


@pytest.mark.parametrize("mode", sorted(MODES))
def test_waves_in_flight_never_exceed_the_depth(mode):
    eng = _engine(**MODES[mode])
    seen = _spy(eng)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # scheduler, fetcher and submitter interleave
    try:
        outs = _burst(eng, rounds=3)
    finally:
        sys.setswitchinterval(interval)
        eng.stop()
    assert all(err is None for _, err in outs)
    assert seen["dispatch"] and len(seen["ahead"]) == len(outs)
    # a dispatch happens only with room: after it, at most `depth`
    assert all(n < depth for n, depth in seen["dispatch"])
    assert all(ahead <= depth - 1 for ahead, depth in seen["ahead"])
    assert all(2 <= depth <= 5 for _, depth in seen["dispatch"])
    assert eng._fetch_q.maxsize == 0  # one bound, not two
    g = eng.pipeline_gauges()
    assert set(g) == {"depth", "wave_period_ms", "host_turn_ms",
                      "chunk_steps"}
    assert g["chunk_steps"] == eng.chunk_sizes[0] <= 4
    assert 2 <= g["depth"] <= 5 and g["wave_period_ms"] > 0.0


@pytest.mark.parametrize("mode", sorted(MODES))
def test_greedy_streams_identical_at_depth_2_and_5(mode):
    streams = {}
    for depth in (2, 5):
        eng = _engine(**MODES[mode])
        eng._depth_est.depth = lambda depth=depth: depth  # pin the estimator
        seen = _spy(eng)
        try:
            outs = _burst(eng, rounds=2)
        finally:
            eng.stop()
        assert all(err is None for _, err in outs)
        assert all(n < depth for n, _ in seen["dispatch"])
        assert all(ahead <= depth - 1 for ahead, _ in seen["ahead"])
        streams[depth] = [toks for toks, _ in outs]
    assert [len(t) for t in streams[2]] == list(BUDGETS) * 2
    assert streams[2] == streams[5]


def test_sync_loop_reports_its_one_deep_pipeline():
    eng = _engine(async_fetch=False)
    try:
        assert all(err is None for _, err in _burst(eng))
        assert eng.pipeline_gauges()["depth"] == 1
    finally:
        eng.stop()


# --- stop and recovery while the scheduler waits at the bound ----------------

def _at_the_bound(eng):
    return (not eng._room.is_set()
            and len(eng._inflight_waves) >= eng._depth_est.depth())


def _wait_for(pred, timeout=60.0):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if pred():
            return True
        time.sleep(0.001)
    return False


def test_stop_returns_while_the_scheduler_waits_at_the_bound():
    # every boundary fetch sleeps 400 ms: the scheduler fills the
    # pipeline and waits on _room with the fetcher stalled
    eng = _engine(chaos=ChaosConfig(seed=0, slow_boundary=1.0, slow_ms=400))
    qs = [eng.submit(p, SamplingParams(temperature=0.0, max_new_tokens=30))
          for p in PROMPTS[:4]]
    sched, fetcher = eng._thread, eng._fetcher
    try:
        assert _wait_for(lambda: _at_the_bound(eng))
        t0 = time.perf_counter()
    finally:
        eng.stop()
    took = time.perf_counter() - t0
    assert not sched.is_alive() and not fetcher.is_alive()
    assert took < 10.0, "stop() sat out the scheduler's join timeout"
    # no waiter hangs: every request got its terminal item
    for q in qs:
        _, err = _collect(q, timeout=10)
        assert err is not None and err["retriable"]


@pytest.mark.parametrize("heal", [True, False])
def test_wave_fault_while_the_scheduler_waits_at_the_bound(heal):
    ref = _engine()
    try:
        want = [ref.generate_blocking(
            p, SamplingParams(temperature=0.0, max_new_tokens=30))["token_ids"]
            for p in PROMPTS[:4]]
    finally:
        ref.stop()

    eng = _engine(heal=heal,
                  chaos=ChaosConfig(seed=0, slow_boundary=1.0, slow_ms=30))
    fired = threading.Event()
    inner = eng._fetch_boundary

    def fetch_boundary(admits, chunk_handles):
        # the fetcher has just stalled; fault this wave only if the
        # scheduler is waiting on _room at this moment
        if not fired.is_set() and _at_the_bound(eng):
            fired.set()
            raise RuntimeError("injected wave fault at the depth bound")
        return inner(admits, chunk_handles)

    eng._fetch_boundary = fetch_boundary
    try:
        qs = [eng.submit(p, SamplingParams(temperature=0.0,
                                           max_new_tokens=30))
              for p in PROMPTS[:4]]
        outs = [_collect(q) for q in qs]
        assert fired.is_set(), "the scheduler never waited at the bound"
        if heal:  # resurrected, bit-identical
            assert [err for _, err in outs] == [None] * 4
            assert [toks for toks, _ in outs] == want
            assert eng.debug_health()["recoveries"] >= 1
        else:  # failed, retriable, nobody left hanging
            assert all(err is None or err["retriable"] for _, err in outs)
            assert any(err is not None for _, err in outs)
        # the scheduler was woken and serves on
        again = eng.generate_blocking(
            PROMPTS[0], SamplingParams(temperature=0.0, max_new_tokens=30))
        assert again["token_ids"] == want[0]
        assert eng.debug_lifecycle_check() == {}
    finally:
        eng.stop()
