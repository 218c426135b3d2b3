"""TTFT from inside: the five instants on a request, what reads them
(timings, EngineStats, spans, the access line), the names of the engine's
device programs, and that none of it changes a token (tiny configs, CPU).

No test here asserts a wall-clock threshold: the instants are compared
with each other, never with a number of milliseconds."""

import asyncio
import json
import logging
import threading
import time

import jax
import numpy as np
import pytest

from seldon_tpu.core import tracing
from seldon_tpu.models import init_params
from seldon_tpu.models.config import get_config
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

MODES = {
    "dense": {},
    "chunked": dict(chunked_prefill=True, prefill_chunk=8, prefix_block=8),
    "paged": dict(paged_kv=True, kv_block=8, prefix_block=8),
    "paged+chunked": dict(paged_kv=True, kv_block=8, prefix_block=8,
                          chunked_prefill=True, prefill_chunk=8),
    "paged+chunked+prefix": dict(paged_kv=True, kv_block=8, prefix_block=8,
                                 chunked_prefill=True, prefill_chunk=8,
                                 prefix_cache=True),
    "sync": dict(async_fetch=False),
}
PHASES = ("executor_wait_ms", "queue_wait_ms", "device_wait_ms",
          "first_token_held_ms")
PROMPTS = [[3 + (i + j) % 200 for j in range(n)]
           for i, n in enumerate((5, 12, 20, 7, 9, 26))]


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


def _drain(q, timeout=120):
    """(items, error item or None) of one request's output queue."""
    items, err = [], None
    while True:
        item = q.get(timeout=timeout)
        if item is None:
            return items, err
        if "error" in item:
            err = item
        else:
            items.append(item)


def _submit_all(eng, params_of=lambda i: {}):
    """Submit PROMPTS back to back (later ones queue behind dispatched
    waves) and keep each _Request: the instants live on it."""
    qs, reqs = [], []
    for i, p in enumerate(PROMPTS):
        sp = SamplingParams(temperature=0.0, max_new_tokens=6,
                            received_at=time.perf_counter(),
                            **params_of(i))
        q = eng.submit(p, sp)
        with eng._rid_lock:
            reqs.append(eng._requests[q.rid])
        qs.append(q)
    return qs, reqs


@pytest.mark.parametrize("mode", sorted(MODES))
def test_five_instants_are_monotone_and_phases_sum(mode):
    eng = _engine(**MODES[mode])
    try:
        qs, reqs = _submit_all(eng)
        outs = [_drain(q) for q in qs]
    finally:
        eng.stop()
    for (items, err), req in zip(outs, reqs):
        assert err is None
        first = items[0]
        instants = [req.received_at, req.submitted_at,
                    req.first_dispatch_at, req.admit_ready_at,
                    req.first_token_at]
        assert all(t is not None for t in instants)
        assert instants == sorted(instants), instants
        tm = first["timings"]
        assert set(tm) == set(PHASES) | {"waves_ahead"}
        assert all(tm[k] >= 0.0 for k in PHASES)
        # the four phases are first_token_at - received_at, cut four ways
        whole = 1000.0 * (req.first_token_at - req.received_at)
        assert sum(tm[k] for k in PHASES) == pytest.approx(whole, abs=1e-6)
        assert whole == pytest.approx(
            first["ttft_ms"] + tm["executor_wait_ms"], abs=1e-6)
        assert isinstance(tm["waves_ahead"], int) and tm["waves_ahead"] >= 0
        # only the first item carries them
        assert all("timings" not in it for it in items[1:])
    ph = eng.stats.snapshot()["ttft_phases"]
    assert {k: c for k, (_, c) in ph.items()} == {
        k: len(PROMPTS) for k in PHASES + ("waves_ahead",)}
    for k in PHASES:
        assert ph[k][0] == pytest.approx(
            sum(o[0][0]["timings"][k] for o in outs), rel=1e-9, abs=1e-6)


def test_caller_without_a_transport_reads_no_executor_wait():
    eng = _engine()
    try:
        out = eng.generate_blocking(
            PROMPTS[0], SamplingParams(temperature=0.0, max_new_tokens=3))
        # a stamp from the future (another clock) is not believed either
        late = eng.generate_blocking(
            PROMPTS[1], SamplingParams(temperature=0.0, max_new_tokens=3,
                                       received_at=time.perf_counter() + 60))
    finally:
        eng.stop()
    assert out["timings"]["executor_wait_ms"] == 0.0
    assert late["timings"]["executor_wait_ms"] == 0.0


@pytest.mark.parametrize("mode", ["dense", "dense-depth2", "sync"])
def test_waves_ahead_is_the_depth_at_first_dispatch(mode):
    eng = _engine(**MODES[mode.split("-")[0]])
    if mode == "dense-depth2":  # pin the estimator, as both cells sit
        eng._depth_est.depth = lambda: 2
    seen = {}
    inner = eng._record_first_dispatch

    def spy(group):
        depth = len(eng._inflight_waves) + eng._sync_depth
        fresh = [r for r in group if r.first_dispatch_at is None]
        inner(group)
        for r in fresh:
            seen[r.rid] = depth

    eng._record_first_dispatch = spy
    try:
        qs, reqs = _submit_all(eng)
        for q in qs:
            _drain(q)
    finally:
        eng.stop()
    assert {r.rid: r.waves_ahead for r in reqs} == seen
    assert all(d >= 0 for d in seen.values())
    if mode == "dense":  # the async loop is never deeper than five
        assert max(seen.values()) <= 4
    else:  # one wave queued behind the running one / one undelivered
        assert set(seen.values()) <= {0, 1}


def test_span_tree_cuts_prefill_once_per_request():
    exp = tracing.InMemoryExporter()
    eng = _engine()
    eng._tracer = tracing.get_tracer("engine", exporter=exp)
    caller = tracing.SpanContext(trace_id="ab" * 16, span_id="cd" * 8)
    try:
        qs, reqs = _submit_all(
            eng, lambda i: {"traceparent": caller.to_traceparent()}
            if i % 2 else {})
        for q in qs:
            _drain(q)
    finally:
        eng.stop()
    by_name = {}
    for s in exp.spans:
        by_name.setdefault(s.name, []).append(s)
    n = len(PROMPTS)
    assert {k: len(v) for k, v in by_name.items()} == {
        "unit.executor_wait": n, "engine.request": n, "engine.queued": n,
        "engine.prefill": n, "engine.device_wait": n,
        "engine.first_token_held": n, "engine.decode": n}
    roots = {s.attributes["rid"]: s for s in by_name["engine.request"]}
    waits = {s.attributes["rid"]: s for s in by_name["unit.executor_wait"]}
    assert set(roots) == set(waits) == {r.rid for r in reqs}
    for req in reqs:
        root, wait = roots[req.rid], waits[req.rid]
        # siblings: same trace, same parent (the caller's span, or none)
        assert wait.trace_id == root.trace_id
        assert wait.parent_id == root.parent_id
        assert wait.end_ns <= root.start_ns + 1
        kids = {s.name: s for s in exp.spans if s.parent_id == root.span_id}
        assert set(kids) == {"engine.queued", "engine.prefill",
                             "engine.decode"}
        pre = kids["engine.prefill"]
        cut = {s.name: s for s in exp.spans if s.parent_id == pre.span_id}
        assert set(cut) == {"engine.device_wait", "engine.first_token_held"}
        dw, held = cut["engine.device_wait"], cut["engine.first_token_held"]
        assert (dw.start_ns, held.end_ns) == (pre.start_ns, pre.end_ns)
        assert dw.end_ns == held.start_ns
        assert dw.attributes == {"waves_ahead": req.waves_ahead}
    assert {s.parent_id for s in by_name["engine.request"]} == {
        None, "cd" * 8}


def test_tracing_changes_no_token_and_off_allocates_no_span(monkeypatch):
    def run(traced):
        eng = _engine()
        if traced:
            eng._tracer = tracing.get_tracer(
                "engine", exporter=tracing.InMemoryExporter())
        else:
            assert not eng._tracer.enabled
            monkeypatch.setattr(
                eng, "_emit_request_spans",
                lambda *a, **k: pytest.fail("span work with tracing off"))
        try:
            qs, _ = _submit_all(eng)
            return [[t for it in _drain(q)[0] for t in it["tokens"]]
                    for q in qs]
        finally:
            eng.stop()

    assert run(True) == run(False)
    assert tracing.get_tracer("engine").emit_span("x", 0, 1) is None


def test_one_access_line_per_finished_request_whatever_the_outcome(caplog):
    """ok; cancelled and deadline-expired in the queue (submitted before
    the scheduler starts, so both are decided at its first boundary);
    cancelled mid-decode; shed by a drain."""
    sp = lambda **kw: SamplingParams(temperature=0.0, **kw)
    eng = _engine(start=False, max_slots=2, max_seq_len=256)
    idle = _engine(start=False)
    with caplog.at_level(logging.INFO, logger="seldon_tpu.access"):
        try:
            ok = eng.submit(PROMPTS[0], sp(max_new_tokens=4))
            gone = eng.submit(PROMPTS[1], sp(max_new_tokens=4))
            late = eng.submit(PROMPTS[2], sp(max_new_tokens=4,
                                             deadline_ms=1))
            assert eng.cancel(gone.rid)
            with eng._rid_lock:
                deadline = eng._requests[late.rid].deadline
            while time.perf_counter() < deadline:
                pass
            eng.start()
            ok_items, ok_err = _drain(ok)
            (_, gone_err), (_, late_err) = _drain(gone), _drain(late)
            long = eng.submit(PROMPTS[3], sp(max_new_tokens=200))
            first = long.get(timeout=120)
            eng.cancel(long.rid)
            _, long_err = _drain(long)
            shed = idle.submit(PROMPTS[4], sp(max_new_tokens=4))
            idle.drain(timeout=0)  # no scheduler: sheds what is queued
            _, shed_err = _drain(shed)
        finally:
            eng.stop()
            idle.stop()
    lines = [r.getMessage() for r in caplog.records
             if r.name == "seldon_tpu.access"]
    assert all(ln.startswith("request {") for ln in lines)
    rows = [json.loads(ln[len("request "):]) for ln in lines]  # valid JSON
    mine = {row["rid"]: row for row in rows[:4]}
    assert len(rows) == 5 and len(mine) == 4  # exactly one line each
    assert ok_err is None
    assert (gone_err["kind"], late_err["kind"], shed_err["kind"]) == (
        "cancelled", "deadline", "draining")
    assert {rid: mine[rid]["outcome"] for rid in mine} == {
        ok.rid: "ok", gone.rid: "cancelled", late.rid: "deadline",
        # 200 tokens outlast a cancel sent at the first; if they ever did
        # not, the line still says what the waiter saw
        long.rid: long_err["kind"] if long_err else "ok"}
    assert rows[4]["outcome"] == "draining" and rows[4]["rid"] == shed.rid
    done = mine[ok.rid]
    assert done["prompt_tokens"] == len(PROMPTS[0])
    assert done["completion_tokens"] == sum(
        len(it["tokens"]) for it in ok_items)
    tm = ok_items[0]["timings"]
    for k in PHASES:
        assert done[k] == pytest.approx(tm[k], abs=1e-3)  # logged to the us
    assert done["waves_ahead"] == tm["waves_ahead"]
    assert done["decode_ms"] >= 0.0
    assert abs(done["received_unix"] - time.time()) < 3600
    # never dispatched: the phases it never reached read null
    for row in (mine[gone.rid], mine[late.rid], rows[4]):
        assert row["executor_wait_ms"] is not None
        assert [row[k] for k in PHASES[1:]] == [None] * 3
        assert row["waves_ahead"] is None and row["decode_ms"] is None
    assert "timings" in first
    assert mine[long.rid]["first_token_held_ms"] is not None
    # the sampler's running totals ride on every line, whatever its
    # outcome (tests/test_sampler_tiers.py reads them); a model without
    # token -> expert dispatch writes no routing counters
    for row in rows:
        assert row["sampler_steps"] >= row["sampler_drawn_steps"] \
            >= row["sampler_masked_steps"] == 0
        assert not any(k.startswith("moe_") for k in row)
    assert done["sampler_steps"] >= 1


def test_silenced_access_log_formats_nothing(monkeypatch):
    eng = _engine()
    monkeypatch.setattr(logging.getLogger("seldon_tpu.access"), "disabled",
                        True)
    monkeypatch.setattr(eng, "_log_access",
                        lambda *a: pytest.fail("line built while silenced"))
    try:
        assert eng.generate_blocking(
            PROMPTS[0], SamplingParams(temperature=0.0, max_new_tokens=3)
        )["token_ids"]
    finally:
        eng.stop()


# --- names on the device side ------------------------------------------------

JIT_NAMES = {
    "_jit_admit": "_admit_impl", "_jit_admit_sub": "_admit_impl",
    "_jit_admit_prefix": "_admit_prefix_impl",
    "_jit_admit_chunk": "_admit_chunk_impl",
    "_jit_admit_chunk_paged": "_paged_admit_chunk_impl",
    "_jit_seed_prefix": "_seed_prefix_impl",
    "_jit_admit_paged": "_paged_admit_impl", "_jit_cow": "_cow_copy_impl",
    "_jit_chunks": "_chunk_impl", "_jit_chunks_paged": "_paged_chunk_impl",
    "_jit_deactivate": "_deactivate_impl",
    "_jit_verify": "_verify_impl", "_jit_draft": "draft_tokens",
}


@pytest.mark.parametrize("ekw", [
    dict(prefix_cache=True, prefix_block=8),
    dict(prefix_cache=True, prefix_block=8, chunked_prefill=True,
         prefill_chunk=8),
    dict(paged_kv=True, kv_block=8, prefix_block=8, chunked_prefill=True,
         prefill_chunk=8),
    dict(paged_kv=True, kv_block=8, prefix_block=8, spec_decode=True,
         spec_k=2),
], ids=["dense-prefix", "chunked-prefix", "paged+chunked", "spec"])
def test_every_engine_jit_carries_its_methods_name(ekw):
    eng = _engine(start=False, **ekw)
    jits = {a: v for a, v in vars(eng).items()
            if a.startswith("_jit_") and v is not None}
    assert set(jits) <= set(JIT_NAMES), set(jits) - set(JIT_NAMES)
    for attr, j in jits.items():
        for fn in (j.values() if isinstance(j, dict) else [j]):
            assert fn.__name__ == JIT_NAMES[attr], (attr, fn.__name__)
    # and XLA's module is called after it: what a profile's "XLA Modules"
    # line and benchmark/xplane.py read
    n = eng.chunk_sizes[-1]
    chunk = (eng._jit_chunks_paged or eng._jit_chunks)[n]
    args = (eng.params, eng._state) + (
        (jax.numpy.asarray(eng._table_host),) if eng._paged else ())
    want = "_paged_chunk_impl" if eng._paged else "_chunk_impl"
    assert f"module @jit_{want} " in chunk.lower(*args).as_text()
    keep = np.ones((eng.ecfg.max_slots,), bool)
    assert "module @jit__deactivate_impl " in eng._jit_deactivate.lower(
        eng._state, keep).as_text()


def test_named_scopes_reach_the_compiled_program_as_metadata_only():
    """The model's named scopes are op metadata: the program a scope-free
    trace lowers to is the same text."""
    import contextlib
    from unittest import mock

    eng = _engine(start=False)
    n = eng.chunk_sizes[-1]
    named = eng._jit_chunks[n].lower(eng.params, eng._state)
    hlo = named.compile().as_text()
    for scope in ("attn/qkv", "attn/scores", "attn/out",
                  "attn/cache_update", "mlp", "lm_head", "sampler"):
        assert scope in hlo, scope
    moe = get_config("tiny-moe")
    p = init_params(moe, jax.random.key(0))
    e2 = InferenceEngine(p, moe, EngineConfig(
        max_slots=4, max_seq_len=64, prompt_buckets=(8, 32)))
    moe_hlo = e2._jit_chunks[n].lower(e2.params, e2._state).compile().as_text()
    assert "moe/router" in moe_hlo and "moe/experts" in moe_hlo
    with mock.patch.object(jax, "named_scope",
                           lambda name: contextlib.nullcontext()):
        bare = _engine(start=False)._jit_chunks[n].lower(
            eng.params, eng._state)
    assert bare.as_text() == named.as_text()


# --- the unit: timings in responses, /metadata, /metrics --------------------


@pytest.fixture(scope="module")
def rest_unit():
    from aiohttp import web

    from seldon_tpu.runtime.wrapper import build_rest_app
    from seldon_tpu.servers.jaxserver import JAXServer

    srv = JAXServer(preset="tiny", max_slots=4, max_seq_len=64)
    records = []
    handler = logging.Handler(level=logging.INFO)
    handler.emit = records.append
    log = logging.getLogger("seldon_tpu.access")
    old = log.level
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    srv.load()
    holder, started = {}, threading.Event()

    async def amain():
        runner = web.AppRunner(build_rest_app(srv))
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        holder["port"] = site._server.sockets[0].getsockname()[1]
        started.set()
        while not holder.get("stop"):
            await asyncio.sleep(0.05)
        await runner.cleanup()

    t = threading.Thread(target=lambda: asyncio.run(amain()), daemon=True)
    t.start()
    assert started.wait(60)
    yield srv, f"http://127.0.0.1:{holder['port']}", records
    holder["stop"] = True
    t.join(timeout=30)
    srv.engine.stop()
    log.removeHandler(handler)
    log.setLevel(old)


def _post(url, body):
    import urllib.request
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=120) as resp:
        raw = resp.read()
    return raw, 1000.0 * (time.perf_counter() - t0)


def _get(url):
    import urllib.request
    with urllib.request.urlopen(url, timeout=60) as resp:
        return resp.read().decode()


def _gauge(text, name):
    return [float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
            if ln.startswith(name + " ")][0]


def test_generate_and_first_stream_chunk_carry_timings(rest_unit):
    _, url, _ = rest_unit
    body = {"prompt_token_ids": PROMPTS[1], "max_new_tokens": 6,
            "temperature": 0.0,
            # a client's own stamp is overwritten by the transport: read
            # against this process's clock it would be its whole uptime
            "meta": {"tags": {"received_at": 0.0}}}
    raw, round_trip_ms = _post(url + "/generate", body)
    out = json.loads(raw)
    tm = out["timings"]
    assert set(tm) == set(PHASES) | {"waves_ahead"}
    assert sum(tm[k] for k in PHASES) == pytest.approx(
        out["ttft_ms"] + tm["executor_wait_ms"], rel=1e-5, abs=1e-3)
    assert 0.0 <= tm["executor_wait_ms"] <= round_trip_ms
    raw, _ = _post(url + "/generate_stream", body)
    chunks = [json.loads(ln) for ln in raw.splitlines() if ln.strip()]
    assert set(chunks[0]["timings"]) == set(PHASES) | {"waves_ahead"}
    assert chunks[0]["ttft_ms"] > 0.0
    assert all(not c.get("timings") for c in chunks[1:])
    assert [t for c in chunks for t in c["token_ids"]] == out["token_ids"]


def test_metadata_says_what_a_warm_up_would_have_to_discover(rest_unit):
    srv, url, _ = rest_unit
    eng = json.loads(_get(url + "/metadata"))["engine"]
    assert eng["max_admit"] == srv.engine.max_admit == 4
    assert eng["decode_chunk"] == list(srv.engine.chunk_sizes)
    assert eng["rest_workers"] == 8


def test_metrics_are_current_when_scraped(rest_unit):
    """A stream refreshes no gauge on its way out; the scrape itself does,
    so an idle unit's last request is counted."""
    srv, url, _ = rest_unit
    before = _gauge(_get(url + "/metrics"), "jaxserver_completed")
    _post(url + "/generate_stream",
          {"prompt_token_ids": PROMPTS[2], "max_new_tokens": 3,
           "temperature": 0.0})
    text = _get(url + "/metrics")
    assert _gauge(text, "jaxserver_completed") == before + 1
    snap = srv.engine.stats.snapshot()["ttft_phases"]
    for k in PHASES + ("waves_ahead",):
        assert _gauge(text, f"jaxserver_ttft_{k}_count") == snap[k][1]
        assert _gauge(text, f"jaxserver_ttft_{k}_sum") == pytest.approx(
            snap[k][0])
    # decode steps by the sampler's tier: greedy traffic, so all of the
    # steps a chunk has reported stand under "greedy"
    tiers = {t: _gauge(text.replace('{tier="%s"}' % t, ""),
                       "jaxserver_sampler_steps_total")
             for t in ("greedy", "drawn", "masked")}
    st = srv.engine.stats.snapshot()
    assert tiers == {"greedy": st["sampler_steps"], "drawn": 0.0,
                     "masked": 0.0}
    assert 0 < tiers["greedy"] <= _gauge(text, "jaxserver_decode_steps")
    # what bounds device_wait stands beside the sums
    assert 2 <= _gauge(text, "jaxserver_sched_depth") <= 5
    assert _gauge(text, "jaxserver_sched_wave_period_ms") >= 0.0
    assert _gauge(text, "jaxserver_sched_host_turn_ms") >= 0.0
    # ... and what its other terms are multiples of: the steps of a chunk
    # dispatched while slots are free, min_chunk or fewer
    assert (_gauge(text, "jaxserver_sched_chunk_steps")
            == srv.engine.chunk_sizes[0] <= srv.engine.ecfg.min_chunk)


def test_scrape_repeats_no_counter_of_the_unit():
    """Only gauges are taken on a scrape: a COUNTER entry is an event of a
    served request."""
    from seldon_tpu.runtime.metrics_server import ServerMetrics
    from seldon_tpu.runtime.wrapper import _absorb_user_metrics

    class Unit:
        def metrics(self):
            return [{"type": "COUNTER", "key": "unit_events", "value": 1},
                    {"type": "GAUGE", "key": "unit_level", "value": 7}]

    m = ServerMetrics()
    _absorb_user_metrics(m, Unit(), gauges_only=True)
    text = m.export()[0].decode()
    assert "unit_level 7.0" in text and "unit_events" not in text
    _absorb_user_metrics(m, Unit())
    assert "unit_events_total 1.0" in m.export()[0].decode()


def test_load_writes_one_startup_line(rest_unit):
    _, _, records = rest_unit
    lines = [r.getMessage() for r in records
             if r.getMessage().startswith("startup ")]
    assert len(lines) == 1
    row = json.loads(lines[0][len("startup "):])
    assert set(row) == {"since_process_start", "imports_device_s",
                        "weights_s", "weights_ready_s", "engine_s",
                        "warmup_s", "warmup_variants"}
    assert row["weights_ready_s"] == pytest.approx(
        row["imports_device_s"] + row["weights_s"], abs=2e-3)
    assert all(row[k] >= 0.0 for k in row if k.endswith("_s"))
    assert row["warmup_variants"] == 0  # this unit was loaded without warmup
