"""Engine flight recorder + tracing parity tests.

The load-bearing claims, in test form:
 * the ring is bounded and lossy-oldest: wrap keeps the most recent
   `size` records, counts the drops, and snapshots oldest-first with an
   epoch pairing;
 * arming is env-gated and fail-safe (`FLIGHT_RECORDER=1`, size knob);
 * a live engine run leaves a readable timeline — submit/admit/boundary/
   terminal per request — that `tools/trace_view.py` converts into valid
   Perfetto trace_event JSON;
 * SLO accounting: deadline-carrying requests land in the margin
   histogram and met/missed counters; goodput is their ratio;
 * observability is free of Heisenberg effects: greedy output is
   bit-identical with tracing + recorder on vs off — dense, paged, AND
   chunked-prefill engines;
 * exactly-one-terminal-span parity: a chaos soak with tracing on emits
   exactly one `engine.request` span per accepted request, whatever the
   outcome (completed / deadline / cancelled / errored).
"""

import json
import random
import threading
import time

import jax
import pytest

from seldon_tpu.models import init_params
from seldon_tpu.models.config import get_config
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers import flight_recorder
from seldon_tpu.servers.chaos import ChaosConfig
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

PROMPT = list(range(2, 26))
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=8)

PAGED = dict(paged_kv=True, kv_block=16, kv_pool_blocks=9,
             prompt_buckets=(16, 32))
CHUNKED = dict(decode_chunk=4, min_chunk=2, adaptive_chunk=False)


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


# ---------------------------------------------------------------------------
# Ring semantics
# ---------------------------------------------------------------------------


def test_ring_wrap_keeps_newest_and_counts_drops():
    rec = flight_recorder.FlightRecorder(size=4)
    for i in range(7):
        rec.record("submit", rid=i, detail={"i": i})
    assert len(rec) == 4
    snap = rec.snapshot()
    assert snap["total_recorded"] == 7
    assert snap["dropped"] == 3
    # Oldest-first, and only the newest `size` survive the wrap.
    assert [r["rid"] for r in snap["records"]] == [3, 4, 5, 6]
    ts = [r["ts"] for r in snap["records"]]
    assert ts == sorted(ts)
    # Epoch pairing present so consumers can map to wall-clock.
    assert snap["epoch_wall"] > 0 and snap["epoch_mono"] > 0


def test_snapshot_is_stable_under_concurrent_append():
    """snapshot() while writers append: every returned record is intact
    (the ring stores immutable tuples; a torn window only affects WHICH
    records appear, never their fields)."""
    rec = flight_recorder.FlightRecorder(size=64)
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            rec.record("boundary", rid=-1, detail={"i": i})
            i += 1

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        for _ in range(50):
            snap = rec.snapshot()
            for r in snap["records"]:
                assert r["kind"] == "boundary"
                assert isinstance(r["detail"]["i"], int)
    finally:
        stop.set()
        t.join(timeout=10)


def test_from_env_gating(monkeypatch):
    monkeypatch.delenv("FLIGHT_RECORDER", raising=False)
    assert flight_recorder.from_env() is None
    monkeypatch.setenv("FLIGHT_RECORDER", "0")
    assert flight_recorder.from_env() is None
    monkeypatch.setenv("FLIGHT_RECORDER", "1")
    rec = flight_recorder.from_env()
    assert rec is not None and rec.size == 4096
    monkeypatch.setenv("FLIGHT_RECORDER_SIZE", "128")
    assert flight_recorder.from_env().size == 128


# ---------------------------------------------------------------------------
# trace_view conversion
# ---------------------------------------------------------------------------


def test_trace_view_converts_synthetic_snapshot():
    from tools import trace_view

    rec = flight_recorder.FlightRecorder(size=64)
    rec.record("submit", 1, {"prompt_tokens": 8, "deadline_ms": 0})
    rec.record("trie-miss", 1, {"matched_tokens": 0, "prompt_tokens": 8})
    rec.record("admit", 1, {"queue_wait_ms": 1.5})
    rec.record("boundary", -1, {"admits": 1, "chunk": 4, "active": 1})
    rec.record("terminal", 1, {"outcome": "ok", "n_generated": 4})
    rec.record("submit", 2, {"prompt_tokens": 8, "deadline_ms": 30})
    rec.record("terminal", 2, {"outcome": "deadline", "n_generated": 0})
    rec.record("submit", 3, {"prompt_tokens": 8, "deadline_ms": 0})

    out = json.loads(json.dumps(trace_view.convert(rec.snapshot())))
    events = out["traceEvents"]
    assert events, "conversion produced no events"
    assert {e["ph"] for e in events} <= {"X", "i", "C", "M"}
    names = [e["name"] for e in events]
    # Request 1: queued + running slices; request 2 never admitted.
    assert "queued" in names
    assert "running [ok]" in names
    assert "unadmitted [deadline]" in names
    # Request 3 is still open at the window end.
    assert "in-flight (window end)" in names
    # Boundary renders as instant + occupancy counter.
    assert "boundary" in names and "active_slots" in names
    # Durations are non-negative, timestamps in wall-clock microseconds.
    for e in events:
        if e["ph"] == "X":
            assert e["dur"] > 0
        if "ts" in e:
            assert e["ts"] > 0


def test_trace_view_rejects_non_snapshot(tmp_path, capsys):
    from tools import trace_view

    bad = tmp_path / "not_a_snapshot.json"
    bad.write_text(json.dumps({"foo": 1}))
    assert trace_view.main([str(bad)]) == 2


# ---------------------------------------------------------------------------
# Live engine timeline + SLO accounting
# ---------------------------------------------------------------------------


def test_engine_timeline_and_slo_accounting(monkeypatch):
    monkeypatch.setenv("FLIGHT_RECORDER", "1")
    eng = _engine()
    try:
        assert eng.debug_timeline() is not None
        # One plain request, one with a generous deadline (met), one with
        # an unmeetable deadline: submitted while the scheduler's lock is
        # held until its 1 ms has passed, so the first boundary that sees
        # it finds it expired (with warm compile caches a 4-token request
        # can otherwise finish inside a millisecond).
        eng.generate_blocking(PROMPT, GREEDY)
        eng.generate_blocking(
            PROMPT, SamplingParams(temperature=0.0, max_new_tokens=4,
                                   deadline_ms=60_000))
        with eng._book:
            q = eng.submit(PROMPT, SamplingParams(
                temperature=0.0, max_new_tokens=4, deadline_ms=1))
            expired = time.perf_counter() + 0.002
            while time.perf_counter() < expired:
                pass
        saw_deadline = False
        while True:
            item = q.get(timeout=120)
            if item is None:
                break
            if item.get("kind") == "deadline":
                saw_deadline = True
        assert saw_deadline

        snap = eng.debug_timeline()
        kinds = {r["kind"] for r in snap["records"]}
        assert {"submit", "admit", "boundary", "terminal"} <= kinds, kinds
        by_kind = {}
        for r in snap["records"]:
            by_kind.setdefault(r["kind"], []).append(r)
        assert len(by_kind["submit"]) == 3
        assert len(by_kind["terminal"]) == 3
        outcomes = {r["detail"]["outcome"] for r in by_kind["terminal"]}
        assert "ok" in outcomes and "deadline" in outcomes

        st = eng.stats.snapshot()
        assert st["deadline_met_total"] == 1
        assert st["deadline_missed_total"] == 1
        assert st["completed_no_deadline_total"] == 1
        assert st["goodput"] == 0.5
        # Histogram mass equals the deadline-carrying population, with
        # at least one negative-margin bucket filled by the miss.
        edges = st["deadline_margin_edges_ms"]
        counts = st["deadline_margin_counts"]
        assert len(counts) == len(edges) + 1
        assert sum(counts) == 2
        neg_mass = sum(c for e, c in zip(edges, counts) if e <= 0)
        assert neg_mass >= 1

        # The live snapshot converts cleanly.
        from tools import trace_view

        out = json.loads(json.dumps(trace_view.convert(snap)))
        assert out["traceEvents"]
        assert {e["ph"] for e in out["traceEvents"]} <= {"X", "i", "C", "M"}
    finally:
        eng.stop()


def test_recorder_disabled_by_default():
    eng = _engine(start=False)
    assert eng.debug_timeline() is None


# ---------------------------------------------------------------------------
# Heisenberg check: observability must not change outputs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "ekw",
    [dict(), PAGED, CHUNKED],
    ids=["dense", "paged", "chunked"],
)
def test_greedy_output_bit_identical_with_observability_on(
    ekw, tmp_path, monkeypatch
):
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

    monkeypatch.delenv("TRACING", raising=False)
    monkeypatch.delenv("FLIGHT_RECORDER", raising=False)
    want = run()

    monkeypatch.setenv("TRACING", "1")
    monkeypatch.setenv("TRACING_FILE", str(tmp_path / "spans.jsonl"))
    monkeypatch.setenv("FLIGHT_RECORDER", "1")
    got = run()
    assert got == want, "tracing/recorder changed greedy output"
    # The traced run actually traced (the parity is not vacuous).
    spans = (tmp_path / "spans.jsonl").read_text().splitlines()
    assert len(spans) >= len(prompts)


# ---------------------------------------------------------------------------
# Exactly-one-terminal-span parity under chaos
# ---------------------------------------------------------------------------


def test_chaos_soak_exactly_one_terminal_span(tmp_path, monkeypatch):
    """60 mixed requests under seeded chaos + deadlines + cancels, tracing
    on: every ACCEPTED request emits exactly one engine.request span, its
    outcome attribute matching the waiter-observed outcome bucket."""
    trace_file = tmp_path / "spans.jsonl"
    monkeypatch.setenv("TRACING", "1")
    monkeypatch.setenv("TRACING_FILE", str(trace_file))
    monkeypatch.setenv("FLIGHT_RECORDER", "1")

    n = 60
    eng = _engine(
        max_slots=8,
        max_queue=4 * n,
        chaos=ChaosConfig(seed=0, dispatch_fail=0.02, slow_boundary=0.05,
                          slow_ms=2.0, disconnect=0.01),
    )
    rng = random.Random(0)
    outcomes = {"completed": 0, "failed": 0}
    lock = threading.Lock()
    threads = []
    accepted = 0

    def consume(q, want_cancel):
        err, sent = None, False
        while True:
            item = q.get(timeout=300)
            if item is None:
                break
            if "error" in item:
                err = item
                continue
            if want_cancel and not sent:
                sent = True
                eng.cancel(q.rid)
        with lock:
            outcomes["completed" if err is None else "failed"] += 1

    try:
        for i in range(n):
            plen = rng.choice((5, 8, 13, 21))
            prompt = [2 + (i + j) % 200 for j in range(plen)]
            dl = rng.choice((30, 80)) if rng.random() < 0.15 else 0
            sp = SamplingParams(temperature=0.0,
                                max_new_tokens=rng.choice((4, 8)),
                                deadline_ms=dl)
            try:
                q = eng.submit(prompt, sp)
            except RuntimeError:
                continue
            accepted += 1
            t = threading.Thread(target=consume,
                                 args=(q, rng.random() < 0.15), daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads), "hung waiter"
        assert eng.drain(timeout=120) is True
    finally:
        eng.stop()

    spans = [json.loads(l) for l in trace_file.read_text().splitlines()]
    roots = [s for s in spans if s["name"] == "engine.request"]
    assert len(roots) == accepted, (
        f"{len(roots)} engine.request spans for {accepted} accepted "
        f"requests (outcomes: {outcomes})"
    )
    # One span per rid — no double emission through _fail_all/cancel/
    # deadline races.
    rids = [s["attributes"]["rid"] for s in roots]
    assert len(set(rids)) == len(rids)
    ok_spans = sum(1 for s in roots if s["attributes"]["outcome"] == "ok")
    assert ok_spans == outcomes["completed"], (ok_spans, outcomes)
    # Every non-completed span carries an ERROR status with its kind.
    for s in roots:
        if s["attributes"]["outcome"] != "ok":
            assert s["status"].startswith("ERROR"), s
    # The phase spans keep the same parity: one unit.executor_wait per
    # request, and engine.prefill cut in two at most once — only for a
    # request that got its first token.
    count = lambda name: sum(1 for s in spans if s["name"] == name)
    waits = [s["attributes"]["rid"] for s in spans
             if s["name"] == "unit.executor_wait"]
    assert sorted(waits) == sorted(rids)
    prefills = {s["span_id"] for s in spans if s["name"] == "engine.prefill"}
    cuts = [s for s in spans if s["name"] in ("engine.device_wait",
                                              "engine.first_token_held")]
    assert all(s["parent_id"] in prefills for s in cuts)
    assert count("engine.device_wait") == count("engine.first_token_held") \
        == count("engine.decode") <= len(prefills) <= accepted
