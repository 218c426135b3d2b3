"""Compile observatory tests: variant ledger, retrace witness, timing.

The load-bearing claims, in test form:
 * the ledger's state machine is right: pre-warmup dispatches implicitly
   declare their keys, ``warmup_done()`` seals the lattice, and only a
   FIRST post-warmup dispatch on an undeclared key yields a witness
   (cached re-dispatches never do); the witness list is capped but the
   count keeps going;
 * everything is env-gated with the None-attribute idiom: off by
   default, the engine carries no ledger, no timing list, and the raw
   dispatch path (``_observe`` False) — ``debug_compile()`` /
   ``debug_hbm()`` return None;
 * a warmed engine under traffic finishes with ``warmup_complete`` and
   ZERO live retraces — the compile-audit contract at unit scale;
 * skipping warmup and sealing an empty lattice makes the very first
   request pay visible retraces: witnesses carry the paying rid and a
   real compile_ms, and ``retrace`` records land in the flight
   recording;
 * ``DISPATCH_TIMING=1`` populates per-variant histograms in EngineStats
   and ``dispatch`` records that trace_view renders as variant lanes;
 * the Heisenberg check: greedy output is bit-identical with the FULL
   observatory on vs off — dense, paged, and chunked-prefill engines.
"""

import json

import jax
import pytest

from seldon_tpu.models import init_params
from seldon_tpu.models.config import get_config
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers import compile_ledger, flight_recorder
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

PROMPT = list(range(2, 26))
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=8)

PAGED = dict(paged_kv=True, kv_block=16, kv_pool_blocks=9,
             prompt_buckets=(16, 32))
CHUNKED = dict(decode_chunk=4, min_chunk=2, adaptive_chunk=False)

OBS_KNOBS = ("COMPILE_LEDGER", "HBM_LEDGER", "DISPATCH_TIMING",
             "FLIGHT_RECORDER")


def _engine(start=True, warmup=False, **ekw):
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    ekw.setdefault("max_slots", 4)
    ekw.setdefault("max_seq_len", 64)
    ekw.setdefault("prompt_buckets", (8, 32))
    eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
    if warmup:
        eng.warmup()
    if start:
        eng.start()
    return eng


# ---------------------------------------------------------------------------
# Ledger state machine (no engine)
# ---------------------------------------------------------------------------


def test_ledger_pre_warmup_dispatches_declare():
    led = compile_ledger.CompileLedger()
    assert led.dispatch(("admit", 32, 4), -1, 0.5) is None
    assert led.dispatch(("decode", 8), -1, 0.3) is None
    led.warmup_done()
    snap = led.snapshot()
    assert snap["warmup_complete"] is True
    assert snap["declared_variants"] == 2
    assert snap["live_retrace_count"] == 0
    # Warmup paid the first dispatch; nothing re-used yet.
    assert snap["warmup_coverage"] == 0.0
    assert snap["compile_s_total"] == pytest.approx(0.8)


def test_ledger_witness_only_on_first_undeclared_post_warmup():
    led = compile_ledger.CompileLedger()
    led.dispatch(("decode", 8), -1, 0.2)
    led.warmup_done()
    # Declared key: cached re-dispatch, never a witness.
    assert led.dispatch(("decode", 8), 3, 0.001) is None
    # Undeclared key: first dispatch is THE witness...
    w = led.dispatch(("admit", 32, 4), 7, 0.4)
    assert w is not None
    assert w["key"] == "admit/32/4"
    assert w["rid"] == 7
    assert w["compile_ms"] == pytest.approx(400.0)
    # ...and the now-cached variant stops witnessing.
    assert led.dispatch(("admit", 32, 4), 8, 0.001) is None
    snap = led.snapshot()
    assert snap["live_retrace_count"] == 1
    assert snap["live_retraces"][0]["key"] == "admit/32/4"
    # Coverage counts declared keys live traffic re-used.
    assert snap["warmup_coverage"] == 1.0
    lattice = {e["key"]: e for e in snap["lattice"]}
    assert lattice["decode/8"]["declared"] is True
    assert lattice["decode/8"]["dispatches"] == 2
    assert lattice["admit/32/4"]["declared"] is False
    assert lattice["admit/32/4"]["first_dispatch_ms"] == pytest.approx(400.0)


def test_ledger_witness_list_capped_count_not():
    led = compile_ledger.CompileLedger()
    led.warmup_done()
    for i in range(compile_ledger._MAX_WITNESSES + 10):
        assert led.dispatch(("k", i), i, 0.01) is not None
    snap = led.snapshot()
    assert snap["live_retrace_count"] == compile_ledger._MAX_WITNESSES + 10
    assert len(snap["live_retraces"]) == compile_ledger._MAX_WITNESSES


def test_explicit_declare_suppresses_witness():
    led = compile_ledger.CompileLedger()
    led.declare(("chunk", 128, 2, 16))
    led.warmup_done()
    assert led.dispatch(("chunk", 128, 2, 16), 1, 0.2) is None
    assert led.snapshot()["live_retrace_count"] == 0


def test_from_env_gating(monkeypatch):
    for var, mod in (("COMPILE_LEDGER", compile_ledger),):
        monkeypatch.delenv(var, raising=False)
        assert mod.from_env() is None
        monkeypatch.setenv(var, "0")
        assert mod.from_env() is None
        monkeypatch.setenv(var, "1")
        assert mod.from_env() is not None


def test_key_str():
    assert compile_ledger.key_str(("admit-prefix", 16, 32, 4)) == \
        "admit-prefix/16/32/4"
    assert compile_ledger.key_str(("cow",)) == "cow"


# ---------------------------------------------------------------------------
# Engine integration: off by default, warmed contract, retrace witness
# ---------------------------------------------------------------------------


def test_observatory_off_by_default(monkeypatch):
    for var in OBS_KNOBS:
        monkeypatch.delenv(var, raising=False)
    eng = _engine(start=False)
    assert eng._cledger is None
    assert eng._hbm is None
    assert eng._timing_on is False
    assert eng._observe is False
    assert eng.debug_compile() is None
    assert eng.debug_hbm() is None


def test_warmed_engine_serves_with_zero_retraces(monkeypatch):
    monkeypatch.setenv("COMPILE_LEDGER", "1")
    monkeypatch.setenv("DISPATCH_TIMING", "1")
    monkeypatch.setenv("FLIGHT_RECORDER", "1")
    eng = _engine(warmup=True)
    try:
        comp = eng.debug_compile()
        assert comp["warmup_complete"] is True
        assert comp["declared_variants"] >= 3  # admits + decode + deactivate
        assert comp["compile_s_total"] > 0.0
        for p in (PROMPT, [7, 8, 9], list(range(40, 60))):
            eng.generate_blocking(p, GREEDY)
        comp = eng.debug_compile()
        assert comp["live_retrace_count"] == 0, comp["live_retraces"]
        assert not [e for e in comp["lattice"] if not e["declared"]]
        assert comp["warmup_coverage"] > 0.0

        # Per-variant timing reached EngineStats with histogram mass.
        st = eng.stats.snapshot()
        timing = st["variant_timing"]
        assert timing, "DISPATCH_TIMING=1 populated no histograms"
        assert any(k.startswith("decode/") for k in timing), sorted(timing)
        for h in timing.values():
            assert h["count"] >= 1
            assert h["sum_ms"] > 0.0
            assert len(h["counts"]) == len(st["dispatch_edges_ms"]) + 1
            assert sum(h["counts"]) == h["count"]

        # ...and the flight recording carries dispatch records that
        # trace_view renders as lanes on the variants process.
        from tools import trace_view

        snap = eng.debug_timeline()
        kinds = {r["kind"] for r in snap["records"]}
        assert "dispatch" in kinds, kinds
        out = json.loads(json.dumps(trace_view.convert(snap)))
        lanes = [e for e in out["traceEvents"]
                 if e.get("pid") == trace_view._VARIANT_PID]
        assert any(e["ph"] == "X" for e in lanes)
        lane_names = {e["args"]["name"] for e in lanes
                      if e["ph"] == "M" and e["name"] == "thread_name"}
        assert lane_names, "no variant lane metadata"
    finally:
        eng.stop()


def test_unwarmed_shape_fires_retrace_witness(monkeypatch):
    """Skip warmup, seal the (empty) lattice by hand: the first request's
    dispatches are all live retraces — each witness carries the paying
    rid and the real compile wall time, and lands in the recording."""
    monkeypatch.setenv("COMPILE_LEDGER", "1")
    monkeypatch.setenv("FLIGHT_RECORDER", "1")
    eng = _engine(start=False)
    eng._cledger.warmup_done()  # nothing declared: everything retraces
    eng.start()
    try:
        eng.generate_blocking(PROMPT, GREEDY)
        comp = eng.debug_compile()
        assert comp["live_retrace_count"] >= 2  # admit + decode at least
        keys = {w["key"] for w in comp["live_retraces"]}
        assert any(k.startswith("admit") for k in keys), keys
        assert any(k.startswith("decode/") for k in keys), keys
        for w in comp["live_retraces"]:
            assert w["compile_ms"] > 0.0
        # The admission retrace names the request that paid for it.
        admits = [w for w in comp["live_retraces"]
                  if w["key"].startswith("admit")]
        assert any(w["rid"] >= 0 for w in admits), admits
        # Witnesses mirror into the flight recording.
        recs = [r for r in eng.debug_timeline()["records"]
                if r["kind"] == "retrace"]
        assert len(recs) == comp["live_retrace_count"]
        assert {r["detail"]["key"] for r in recs} == keys
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# trace_view: retrace instants + dispatch lanes from a synthetic ring
# ---------------------------------------------------------------------------


def test_trace_view_variant_lanes_and_retrace_instants():
    from tools import trace_view

    rec = flight_recorder.FlightRecorder(size=64)
    rec.record("submit", 1, {"prompt_tokens": 8})
    rec.record("admit", 1, {})
    rec.record("retrace", 1, {"key": "admit/32/4", "rid": 1,
                              "compile_ms": 812.0, "ts": 1.0})
    rec.record("dispatch", -1, {"variant": "admit/32/4", "ms": 812.0})
    rec.record("dispatch", -1, {"variant": "decode/8", "ms": 2.5})
    rec.record("dispatch", -1, {"variant": "decode/8", "ms": 2.4})
    # A wave's key uses the same stable slash rendering — repeated
    # waves share ONE lane named "verify/4".
    rec.record("dispatch", -1, {"variant": "verify/4", "ms": 3.0})
    rec.record("dispatch", -1, {"variant": "verify/4", "ms": 2.9})
    rec.record("terminal", 1, {"outcome": "ok"})

    out = json.loads(json.dumps(trace_view.convert(rec.snapshot())))
    events = out["traceEvents"]
    # Retrace: an instant on the paying request's track (engine process).
    retr = [e for e in events if e["name"] == "retrace"]
    assert len(retr) == 1 and retr[0]["ph"] == "i" and retr[0]["pid"] == 1

    lanes = [e for e in events if e.get("pid") == trace_view._VARIANT_PID]
    slices = [e for e in lanes if e["ph"] == "X"]
    assert len(slices) == 5
    # One lane (tid) per variant key, stable across repeats.
    by_name = {}
    for e in slices:
        by_name.setdefault(e["name"], set()).add(e["tid"])
    assert set(by_name) == {"admit/32/4", "decode/8", "verify/4"}
    assert all(len(tids) == 1 for tids in by_name.values())
    # Slices back-span from the sync point with the recorded duration.
    admit = next(e for e in slices if e["name"] == "admit/32/4")
    assert admit["dur"] == pytest.approx(812.0 * 1000.0)
    # Lane + process metadata present so Perfetto names the tracks.
    metas = [e for e in lanes if e["ph"] == "M"]
    assert {"seldon-tpu variants"} == {
        e["args"]["name"] for e in metas if e["name"] == "process_name"}
    assert {"admit/32/4", "decode/8", "verify/4"} == {
        e["args"]["name"] for e in metas if e["name"] == "thread_name"}


# ---------------------------------------------------------------------------
# Heisenberg check: full observatory must not change outputs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "ekw",
    [dict(), PAGED, CHUNKED],
    ids=["dense", "paged", "chunked"],
)
def test_greedy_output_bit_identical_with_observatory_on(ekw, monkeypatch):
    prompts = [PROMPT, [7, 8, 9], list(range(40, 60))]

    def run():
        eng = _engine(**dict(ekw))
        try:
            return [
                eng.generate_blocking(p, GREEDY)["token_ids"]
                for p in prompts
            ]
        finally:
            eng.stop()

    for var in OBS_KNOBS:
        monkeypatch.delenv(var, raising=False)
    want = run()

    for var in OBS_KNOBS:
        monkeypatch.setenv(var, "1")
    got = run()
    assert got == want, "compile/HBM/timing observatory changed output"
