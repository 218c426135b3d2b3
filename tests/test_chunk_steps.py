"""How long a chunk dispatched while slots are free is (engine.
_chunk_steps, _DepthEstimator.chunk_steps, InferenceEngine.
_size_low_rung): the fewest steps whose wave covers the host turn the
engine measures on itself, min_chunk at most, sized once (tiny configs,
CPU).

No test here asserts a wall-clock rate: the rule is checked as a pure
function, the engine with a planted estimator and by what it
dispatches."""

import types

import jax
import pytest

from seldon_tpu.models import init_params
from seldon_tpu.models.config import get_config
from seldon_tpu.servers import engine as engine_mod
from seldon_tpu.servers.engine import (EngineConfig, InferenceEngine,
                                       _chunk_steps, _DepthEstimator,
                                       _pipeline_depth)
from test_pipeline_depth import BUDGETS, MODES, _burst  # the same burst

# A decode step of each benchmark cell (ledger, PR 44 `step.decode_ms`)
# and the steps a chunk the rule gives behind the chip's typical turn
# (the median the engine logs: 1.68-2.07 ms, my chip runs, PR 47).
CELLS = {
    "falconh1.chat": (13.753, 1),
    "mistral7b.chat": (11.075, 1),
    "nemotron3.chat": (6.140, 2),
    "mixtral.chat": (3.340, 4),
    "lfm2.chat": (2.816, 4),
    "laguna.code": (2.016, 4),
}
TURN_MS = 1.9
# waves that fill the estimator's rings (the first opens an interval)
FULL = 4 * engine_mod._DEPTH_SAMPLES + 1


class _Planted(_DepthEstimator):
    """An estimator the engine's own clock cannot reach: its waves and
    turns are the ones `plant` feeds."""

    def note_retire(self, *a, **kw):
        pass

    def note_turn(self, *a, **kw):
        pass

    def plant(self, step_s, turn_s, n, steps=4):
        t = self.fetched_at or 0.0
        for _ in range(n):
            t += steps * step_s
            _DepthEstimator.note_retire(self, t, None, True, steps)
            _DepthEstimator.note_turn(self, t + turn_s)
        return self


def _engine(est=None, start=True, **ekw):
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    ekw.setdefault("max_slots", 4)
    ekw.setdefault("max_seq_len", 64)
    ekw.setdefault("prompt_buckets", (8, 32))
    eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
    if est is not None:
        eng._depth_est = est
    if start:
        eng.start()
    return eng


def _dispatched(eng):
    """Record the length of every decode chunk the engine dispatches."""
    seen, inner = [], eng._dispatch_decode_chunk

    def dispatch(n):
        seen.append(n)
        return inner(n)

    eng._dispatch_decode_chunk = dispatch
    return seen


# --- the rule as a pure function ---------------------------------------------

@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rule_at_the_cells_readings(cell):
    step_ms, want = CELLS[cell]
    # the estimator times 4-step waves until the rung is sized
    period_s, steps, turn_s = 4 * step_ms / 1e3, 4, TURN_MS / 1e3
    n = _chunk_steps(period_s / steps, turn_s, 4)
    assert n == want
    # ... and the shorter wave never costs one more wave dispatched ahead
    assert _pipeline_depth(n * step_ms / 1e3, turn_s) == 2


@pytest.mark.parametrize("step_ms, turn_ms, cap, want", [
    (13.753, 2.6, 4, 1),
    (13.753, 4.6, 4, 2),   # a slower host: two steps cover its turns
    (13.753, 20.0, 4, 4),  # never above the cap
    (13.753, 20.0, 8, 8),
    (5.9, 2.6, 4, 2),      # 11.8 ms cover 4.5 turns of 2.6
    (5.8, 2.6, 4, 4),      # 11.6 do not
    (6.14, 2.6, 2, 2),
    (6.14, 2.6, 1, 1),     # a cap of one step is one step
    (6.14, 2.6, 3, 2),
    (1.0, 2.6, 3, 3),      # a cap that is no power of two stays the cap
    (5.0, 0.0, 4, 1),      # no turn to cover
    (0.0, 2.6, 4, 4),      # no step to speak of
])
def test_rule_over_step_turn_and_cap(step_ms, turn_ms, cap, want):
    assert _chunk_steps(step_ms / 1e3, turn_ms / 1e3, cap) == want


@pytest.mark.parametrize("step_ms", [0.3, 1.0, 2.0, 3.3, 6.1, 11.0, 13.8, 40.0])
@pytest.mark.parametrize("turn_ms", [0.5, 2.6, 5.0, 12.0])
def test_the_depth_never_rises_to_pay_for_a_shorter_chunk(step_ms, turn_ms):
    """_CHUNK_COVER >= _DEPTH_MARGIN: wherever the rule shortens the
    chunk, the wave it leaves still covers the turns _pipeline_depth
    asks a queued wave to cover, so the depth stays at its floor."""
    assert engine_mod._CHUNK_COVER >= engine_mod._DEPTH_MARGIN
    n = _chunk_steps(step_ms / 1e3, turn_ms / 1e3, 4)
    if n < 4:
        assert _pipeline_depth(n * step_ms / 1e3, turn_ms / 1e3) == 2
    else:  # unchanged: whatever depth the 4-step wave had
        assert (_pipeline_depth(n * step_ms / 1e3, turn_ms / 1e3)
                == _pipeline_depth(4 * step_ms / 1e3, turn_ms / 1e3))


# --- the estimator's step ------------------------------------------------------

def test_estimator_sizes_nothing_until_step_and_turn_have_their_samples():
    est = _DepthEstimator()
    assert est.chunk_steps(4) is None
    # the first retirement opens an interval and closes none
    _Planted.plant(est, 0.0138, 0.0026, FULL - 1)
    assert len(est.steps) == FULL - 2 and len(est.turns) == FULL - 1
    assert est.chunk_steps(4) is None
    _Planted.plant(est, 0.0138, 0.0026, 1)
    assert est.chunk_steps(4) == 1
    assert est.step_s() == pytest.approx(0.0138)
    assert est.typical_turn_s() == pytest.approx(0.0026)


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("compiles", [0, 1, 6, 12])
def test_first_dispatches_in_the_warm_up_do_not_move_the_rung(cell, compiles):
    """A variant's first dispatch is a turn of seconds with the device
    dry behind it: a third of the samples may be such and the rung is
    the one the clean samples give."""
    step_ms, want = CELLS[cell]
    est = _DepthEstimator()
    t = 0.0
    for i in range(FULL):
        first = i % 3 == 1 and i // 3 < compiles
        t += 4 * step_ms / 1e3 + (9.0 if first else 0.0)
        est.note_retire(t, None, True, 4)  # the dry device reads as a step
        est.note_turn(t + (9.0 if first else TURN_MS / 1e3))
    assert est.chunk_steps(4) == want


def test_a_wave_that_carried_an_admission_times_no_step():
    est = _DepthEstimator()
    est.note_retire(1.0, None, True, 4)
    est.note_retire(1.2, None, True, 0)   # prefill + chunk: a period, no step
    assert (est.period.n, len(est.steps)) == (1, 0)
    est.note_retire(1.24, None, True, 4)
    assert (est.period.n, len(est.steps)) == (2, 1)
    assert est.step_s() == pytest.approx(0.01)
    est.note_retire(1.25, None, True, 1)  # a one-step wave is its own step
    assert list(est.steps) == pytest.approx([0.01, 0.01])


def test_engine_times_steps_by_waves_of_decode_alone():
    eng = _engine(start=False)
    seen = []
    eng._depth_est.note_retire = lambda *a: seen.append(a[3])
    toks = types.SimpleNamespace(shape=(4, 4))
    Wave = engine_mod._PendingWave
    for wave in (Wave([], (toks,), None, None),
                 Wave([("group", 0, 0, 0)], (toks,), None, None),
                 Wave([("group", 0, 0, 0)], None, None, None)):
        with eng._book:
            eng._inflight_waves.append(wave)
            eng._wave_retire(wave, 1.0, None)
    assert seen == [4, 0, 0]


# --- the engine sizes its low rung once ----------------------------------------

@pytest.mark.parametrize("step_ms, want, ladder", [
    (13.8, 1, (1, 4, 8)),
    (6.1, 2, (2, 4, 8)),
    (3.3, 4, (4, 8)),
])
def test_engine_sizes_the_rung_once_and_keeps_it(step_ms, want, ladder):
    est = _Planted()
    eng = _engine(est, max_slots=16)  # a burst leaves half the slots free
    seen = _dispatched(eng)
    try:
        assert eng.pipeline_gauges()["chunk_steps"] == 4
        assert all(err is None for _, err in _burst(eng))
        assert set(seen) == {4} and eng.chunk_sizes == (4, 8)
        est.plant(step_ms / 1e3, TURN_MS / 1e3, FULL)
        del seen[:]
        assert all(err is None for _, err in _burst(eng))
        assert set(seen) == {want} and eng.chunk_sizes == ladder
        assert eng.pipeline_gauges()["chunk_steps"] == want
        assert f"decode/{want}" in eng.static_lattice()
        # the step lengthens (eight live rows), then shortens: no new rung
        for later in (3 * step_ms, step_ms / 3):
            est.plant(later / 1e3, TURN_MS / 1e3, 40, steps=want)
            del seen[:]
            assert all(err is None for _, err in _burst(eng))
            assert set(seen) == {want} and eng.chunk_sizes == ladder
        assert eng.debug_lifecycle_check() == {}
    finally:
        eng.stop()


def test_dispatch_span_carries_the_rung_in_force(monkeypatch):
    spans = []

    class Span:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kw):
            spans.append((self.name, kw))

    monkeypatch.setattr(engine_mod.jax.profiler, "TraceAnnotation", Span)
    est = _Planted()
    eng = _engine(est, max_slots=16)
    try:
        assert all(err is None for _, err in _burst(eng))
        est.plant(0.0061, TURN_MS / 1e3, FULL)
        assert all(err is None for _, err in _burst(eng))
    finally:
        eng.stop()
    meta = [kw for name, kw in spans if name == "sched.dispatch"]
    assert meta and all(
        {"wave", "admits", "chunk_steps", "low_rung", "depth",
         "wave_period_ms", "host_turn_ms"} <= set(kw) for kw in meta)
    rungs = [kw["low_rung"] for kw in meta]
    # min_chunk until the estimator fills, the sized rung from then on
    assert rungs == sorted(rungs, reverse=True) and set(rungs) == {4, 2}
    assert all(kw["chunk_steps"] == kw["low_rung"] for kw in meta)


def _spec(**kw):
    return dict(spec_decode=True, spec_k=2, paged_kv=True, kv_block=8,
                prefix_block=8, **kw)


@pytest.mark.parametrize("case, ekw, samples", [
    ("too few samples", {}, FULL - 1),
    ("fixed chunk", dict(adaptive_chunk=False, decode_chunk=4), FULL),
    ("min_chunk is the top", dict(decode_chunk=4, min_chunk=4), FULL),
])
def test_engine_keeps_min_chunk(case, ekw, samples):
    est = _Planted().plant(0.0138, 0.0026, samples)
    eng = _engine(est, max_slots=16, **ekw)
    seen = _dispatched(eng)
    ladder = eng.chunk_sizes
    try:
        assert all(err is None for _, err in _burst(eng))
    finally:
        eng.stop()
    assert set(seen) == {4} and eng.chunk_sizes == ladder
    assert eng.pipeline_gauges()["chunk_steps"] == 4


@pytest.mark.parametrize("mode", sorted(MODES))
def test_the_sized_rung_joins_the_declared_lattice(monkeypatch, mode):
    """One chunk program more, compiled at its first dispatch: declared
    when it is chosen, so the compile ledger holds no live-retrace
    witness for it and the static lattice names it."""
    monkeypatch.setenv("COMPILE_LEDGER", "1")
    est = _Planted()
    eng = _engine(est, start=False, max_slots=16, **MODES[mode])
    eng.warmup()
    eng.start()
    try:
        before = eng.debug_compile()
        assert before["warmup_complete"] and "decode/1" not in {
            v["key"] for v in before["lattice"]}
        est.plant(0.0138, TURN_MS / 1e3, FULL)
        assert all(err is None for _, err in _burst(eng))
        after = eng.debug_compile()
    finally:
        eng.stop()
    rung = [v for v in after["lattice"] if v["key"] == "decode/1"]
    assert len(rung) == 1 and rung[0]["declared"] and rung[0]["dispatches"] > 1
    assert after["live_retrace_count"] == 0
    assert after["declared_variants"] == before["declared_variants"] + 1
    assert "decode/1" in eng.static_lattice()


def test_an_estimator_still_short_after_its_first_waves_keeps_min_chunk():
    """Sized in the engine's first waves or not at all: samples that
    trickle in later (a host-bound engine: the device paces few of its
    waves) compile nothing under load."""
    est = _Planted().plant(0.0138, TURN_MS / 1e3, FULL - 1)
    eng = _engine(est, max_slots=16)
    seen = _dispatched(eng)
    try:
        assert all(err is None for _, err in _burst(eng))
        assert not eng._rung_sized
        with eng._book:
            eng._wave_seq = 4 * est.steps.maxlen
        assert all(err is None for _, err in _burst(eng))
        assert eng._rung_sized
        est.plant(0.0138, TURN_MS / 1e3, FULL)  # too late
        assert all(err is None for _, err in _burst(eng))
    finally:
        eng.stop()
    assert set(seen) == {4} and eng.chunk_sizes == (4, 8)
    assert eng.pipeline_gauges()["chunk_steps"] == 4


@pytest.mark.parametrize("case, ekw", [
    ("sync loop", dict(async_fetch=False)),
    ("speculation", _spec()),
    ("speculation, async fetch off", _spec(async_fetch=False)),
])
def test_loops_that_feed_no_estimator_keep_min_chunk(case, ekw):
    """The estimator is the engine's own here, on the CPU's clock: these
    loops retire no registered wave, so it never fills."""
    eng = _engine(**ekw)
    ladder = eng.chunk_sizes
    try:
        assert all(err is None for _, err in _burst(eng, rounds=2))
        with eng._book:
            assert eng._depth_est.chunk_steps(4) is None
            assert not eng._depth_est.steps
    finally:
        eng.stop()
    assert eng.chunk_sizes == ladder
    assert eng.pipeline_gauges()["chunk_steps"] == ladder[0]


# --- the same tokens at every rung ---------------------------------------------

@pytest.mark.parametrize("mode", sorted(MODES))
def test_greedy_streams_identical_at_rungs_1_2_and_4(mode):
    streams = {}
    for want, step_ms in ((1, 13.8), (2, 6.1), (4, 3.3)):
        est = _Planted().plant(step_ms / 1e3, TURN_MS / 1e3, FULL)
        eng = _engine(est, **MODES[mode])
        seen = _dispatched(eng)
        try:
            outs = _burst(eng, rounds=2)
            assert eng.debug_lifecycle_check() == {}
        finally:
            eng.stop()
        assert all(err is None for _, err in outs)
        assert want in seen and set(seen) <= set(eng.chunk_sizes)
        assert eng.chunk_sizes[0] == want
        streams[want] = [toks for toks, _ in outs]
    assert [len(t) for t in streams[4]] == list(BUDGETS) * 2
    assert streams[1] == streams[2] == streams[4]


# --- the rest of the ladder stands ----------------------------------------------

class _Stub:  # occupancy is counted from non-None slot entries
    finished = False


@pytest.mark.parametrize("rung", [1, 2, 4])
@pytest.mark.parametrize("busy, want", [
    (60, 32),    # free below max_admit: saturated, the top rung
    (52, 8),     # free below a quarter of the pool: the mid rung
    (30, None),  # plenty free: the low rung
])
def test_saturated_rungs_stand_whatever_the_low_rung(rung, busy, want):
    step_ms = {1: 13.8, 2: 6.1, 4: 3.3}[rung]
    est = _Planted().plant(step_ms / 1e3, TURN_MS / 1e3, FULL)
    eng = _engine(est, start=False, max_slots=64, prompt_buckets=(8,),
                  decode_chunk=32, min_chunk=4, max_admit=8)
    assert eng.chunk_sizes == (4, 8, 32)
    with eng._book:
        eng._size_low_rung()
    assert eng.chunk_sizes == tuple(sorted({rung, 4, 8, 32}))
    eng._slots = [_Stub()] * busy + [None] * (64 - busy)
    assert eng._pick_chunk() == (want or rung)


@pytest.mark.parametrize("rung", [1, 2, 4])
@pytest.mark.parametrize("bias", [-1, 0, 1])
@pytest.mark.parametrize("busy", [60, 52, 30])
def test_pilot_bias_moves_one_rung_inside_the_compiled_ladder(rung, bias,
                                                              busy):
    step_ms = {1: 13.8, 2: 6.1, 4: 3.3}[rung]
    est = _Planted().plant(step_ms / 1e3, TURN_MS / 1e3, FULL)
    eng = _engine(est, start=False, max_slots=64, prompt_buckets=(8,),
                  decode_chunk=32, min_chunk=4, max_admit=8)
    with eng._book:
        eng._size_low_rung()
    eng._slots = [_Stub()] * busy + [None] * (64 - busy)
    sizes = eng.chunk_sizes
    plain = sizes.index(eng._pick_chunk())
    eng._pilot = types.SimpleNamespace(chunk_bias=lambda: bias)
    got = eng._pick_chunk()
    assert got in sizes and got in eng._jit_chunks
    assert sizes.index(got) == max(0, min(plain + bias, len(sizes) - 1))
