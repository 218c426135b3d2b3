#!/usr/bin/env python
"""CI roof audit: the MFU/MBU roofline observatory end to end.

Boots the tiny warmed JAXServer behind the real REST app with
``ROOF_LEDGER=1`` + ``FLIGHT_RECORDER=1``, polls ``/debug/roof`` on the
idle engine, drives it with a short closed-loop loadtester run, then
asserts the observatory contract in one pass:

 * ``/debug`` indexes every observability surface with its arming
   knob, and the roof reads armed;
 * idle engine -> ZERO attribution: no boundaries decomposed, no
   variants priced, empty totals;
 * after load, ``/debug/roof`` returns the documented schema, every
   variant's mfu/mbu sits in [0, 1] with the utilization of a
   device-timed priced variant strictly positive, and the bound label
   is one of compute/bandwidth/host;
 * the step decomposition re-sums: host-pre + device + host-post +
   overlap match the measured boundary wall within 1%, and the
   ledger's own ``audit()`` (run under ``_book`` at every dispatched
   boundary) reports zero breaches;
 * predicted vs measured stays sane: the roofline's total predicted_ms
   against the measured device_ms lands in a generous band (CPU smoke
   runs calibrate against the one-shot microbench, so only gross
   divergence — a broken formula or broken peaks — trips this);
 * the loadtester ledger carries the same roof numbers as the route
   (tolerant parity — trailing drain boundaries may tick after the
   loadtester's poll), and the jaxserver Prometheus surface exports
   the per-variant ``jaxserver_mfu`` / ``jaxserver_mbu`` gauges plus
   ``jaxserver_host_frac``;
 * boundary "roof" records reach the flight recorder and
   ``tools/trace_view.py`` renders the host/device lanes from them.

Run via ``make roof-audit`` (wired into ``make ci``); exits non-zero
with a one-line diagnosis on the first failed check.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys

# Frozen /debug/roof key sets — tests/test_debug_schema.py carries the
# same goldens; a mismatch here means the snapshot schema changed
# without updating its consumers.
ROOF_TOP_KEYS = frozenset({
    "enabled", "platform", "peaks", "tp", "boundaries", "waves", "step",
    "host_frac", "device_frac", "conservation", "variants", "totals",
})
ROOF_VARIANT_KEYS = frozenset({
    "key", "family", "dispatches", "flops", "bytes", "device_ms",
    "predicted_ms", "mfu", "mbu", "bound",
})
DEBUG_ROUTES = frozenset({
    "/debug/timeline", "/debug/compile", "/debug/hbm", "/debug/sched",
    "/debug/pilot", "/debug/roof", "/debug/health",
})


def _check(cond: bool, msg: str) -> None:
    if not cond:
        print(f"roof-audit FAIL: {msg}", file=sys.stderr)
        raise SystemExit(1)


def main(argv=None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["ROOF_LEDGER"] = "1"
    os.environ["FLIGHT_RECORDER"] = "1"

    import asyncio
    import threading
    import urllib.request

    from aiohttp import web

    from seldon_tpu.loadtester import main as lt_main
    from seldon_tpu.runtime.wrapper import build_rest_app
    from seldon_tpu.servers.jaxserver import JAXServer
    from tools import trace_view

    srv = JAXServer(preset="tiny", max_slots=4, max_seq_len=64, warmup=1)
    srv.load()

    holder, started = {}, threading.Event()

    async def amain() -> None:
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
    _check(started.wait(60), "REST app failed to start within 60s")
    url = f"http://127.0.0.1:{holder['port']}"

    def get(path: str) -> dict:
        with urllib.request.urlopen(url + path, timeout=10) as resp:
            return json.loads(resp.read())

    try:
        # --- /debug index: every surface listed, the roof armed ---------
        index = get("/debug")
        routes = {s["route"]: s for s in index["surfaces"]}
        _check(set(routes) == DEBUG_ROUTES,
               f"/debug index drifted: got {sorted(routes)}")
        for s in index["surfaces"]:
            _check(set(s) == {"route", "knob", "supported", "armed"},
                   f"/debug entry keys drifted: {sorted(s)}")
            _check(s["supported"], f"{s['route']} unsupported on JAXServer")
        _check(routes["/debug/roof"]["armed"],
               "ROOF_LEDGER=1 but /debug lists the roof unarmed")
        _check(routes["/debug/roof"]["knob"] == "ROOF_LEDGER",
               "roof surface lists the wrong arming knob")
        _check(routes["/debug/timeline"]["armed"],
               "FLIGHT_RECORDER=1 but /debug lists the timeline unarmed")

        # --- idle engine: zero attribution ------------------------------
        idle = get("/debug/roof")
        _check(set(idle) == ROOF_TOP_KEYS,
               f"/debug/roof keys drifted: got {sorted(idle)}")
        _check(idle["boundaries"] == 0,
               f"idle engine decomposed {idle['boundaries']} boundaries")
        _check(idle["variants"] == [], "idle engine priced variants")
        _check(idle["totals"]["dispatches"] == 0,
               "idle engine counted dispatches")
        _check(idle["peaks"]["tflops"] > 0.0 and idle["peaks"]["gbs"] > 0.0,
               f"degenerate peaks {idle['peaks']}")
        _check(idle["peaks"]["source"] in ("env", "table", "microbench"),
               f"unknown peak source {idle['peaks']['source']}")

        # --- load window ------------------------------------------------
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            lt_main([
                url, "--transport", "generate", "--clients", "4",
                "--seconds", "2", "--prompt", "hi",
                "--max-new-tokens", "4",
            ])
        ledger = json.loads(buf.getvalue().strip().splitlines()[-1])
        detail = ledger["detail"]
        _check(detail["errors"] == 0,
               f"loadtester saw {detail['errors']} transport errors")
        _check(detail["requests"] >= 1, "loadtester completed no requests")

        roof = get("/debug/roof")
        snap = get("/debug/timeline")
    finally:
        holder["stop"] = True
        t.join(timeout=10)

    # --- schema + per-variant roofline ---------------------------------
    _check(set(roof) == ROOF_TOP_KEYS,
           f"/debug/roof keys drifted: got {sorted(roof)}")
    _check(roof["boundaries"] > 0, "no boundaries decomposed under load")
    _check(roof["waves"] > 0, "no waves joined under load")
    _check(roof["variants"], "no variants priced under load")
    for v in roof["variants"]:
        _check(set(v) == ROOF_VARIANT_KEYS,
               f"variant keys drifted: {sorted(v)}")
        _check(0.0 <= v["mfu"] <= 1.0, f"{v['key']} mfu={v['mfu']}")
        _check(0.0 <= v["mbu"] <= 1.0, f"{v['key']} mbu={v['mbu']}")
        _check(v["bound"] in ("compute", "bandwidth", "host"),
               f"{v['key']} bound={v['bound']!r}")
        _check(v["dispatches"] >= 1, f"{v['key']} has zero dispatches")
        if v["device_ms"] > 0.0 and v["bytes"] > 0.0:
            _check(max(v["mfu"], v["mbu"]) > 0.0,
                   f"{v['key']} priced + timed but utilization is zero")
    tot = roof["totals"]
    _check(tot["dispatches"] == sum(v["dispatches"]
                                    for v in roof["variants"]),
           "totals dispatches != sum of variants")
    _check(abs(tot["device_ms"] - sum(v["device_ms"]
                                      for v in roof["variants"])) <= 0.5,
           "wave device time not conserved across variants")
    _check(0.0 <= tot["mfu"] <= 1.0 and 0.0 <= tot["mbu"] <= 1.0,
           f"totals utilization out of range: {tot}")
    _check(max(tot["mfu"], tot["mbu"]) > 0.0,
           "total utilization is zero after a real load window")

    # --- step decomposition conservation --------------------------------
    cons = roof["conservation"]
    _check(cons["checked"] > 0, "conservation audit never ran")
    _check(
        cons["breaches"] == 0,
        f"{cons['breaches']} conservation breaches: {cons['last_breach']}",
    )
    step = roof["step"]
    parts = (step["host_pre_ms"] + step["device_ms"]
             + step["host_post_ms"] + step["overlap_ms"])
    _check(
        abs(parts - step["wall_ms"]) <= max(1.0, 0.01 * step["wall_ms"]),
        f"step components {parts} != boundary wall {step['wall_ms']}",
    )
    _check(step["wall_ms"] > 0.0, "zero boundary wall after load")
    _check(0.0 <= roof["host_frac"] <= 1.0,
           f"host_frac out of range: {roof['host_frac']}")
    _check(0.0 <= roof["device_frac"] <= 1.0,
           f"device_frac out of range: {roof['device_frac']}")

    # --- predicted vs measured: generous CPU band ------------------------
    _check(tot["predicted_ms"] > 0.0, "roofline predicted zero total time")
    ratio = tot["predicted_ms"] / max(tot["device_ms"], 1e-9)
    _check(1e-4 < ratio < 1e4,
           f"predicted/measured ratio {ratio:.2e} outside sanity band "
           f"(predicted {tot['predicted_ms']} ms, "
           f"measured {tot['device_ms']} ms)")

    # --- loadtester ledger parity (tolerant: drain boundaries tick) ------
    for key in ("mfu", "mbu", "host_frac"):
        _check(key in detail, f"loadtester ledger missing roof {key}")
        _check(0.0 <= detail[key] <= 1.0,
               f"ledger {key}={detail[key]} out of range")
    _check(
        abs(detail["mfu"] - tot["mfu"]) <= max(0.01, 0.5 * tot["mfu"]),
        f"ledger mfu {detail['mfu']} != route {tot['mfu']}",
    )
    _check(detail.get("roof_conservation_breaches") == 0,
           f"ledger breaches = {detail.get('roof_conservation_breaches')}")

    # --- Prometheus surface ---------------------------------------------
    metrics = srv.metrics()
    gauges = {m["key"] for m in metrics}
    for key in ("jaxserver_mfu", "jaxserver_mbu", "jaxserver_host_frac",
                "jaxserver_roof_conservation_breaches"):
        _check(key in gauges, f"metrics() missing gauge {key}")
    mfu_variants = {m["tags"]["variant"] for m in metrics
                    if m["key"] == "jaxserver_mfu"}
    _check(mfu_variants == {v["key"] for v in roof["variants"]},
           f"jaxserver_mfu variants {sorted(mfu_variants)} != route")

    # --- flight recorder + trace_view host/device lanes ------------------
    roof_records = [r for r in snap.get("records", [])
                    if r["kind"] == "roof"]
    _check(roof_records, "no roof records in timeline")
    out = json.loads(json.dumps(trace_view.convert(snap)))
    lanes = {e["name"] for e in out["traceEvents"]
             if e["ph"] == "X" and e["pid"] == trace_view._ROOF_PID}
    _check("host-pre" in lanes and "fetch" in lanes,
           f"trace_view rendered no roof lanes (got {sorted(lanes)})")
    counters = {e["name"] for e in out["traceEvents"] if e["ph"] == "C"}
    _check("roof_host_ms" in counters,
           f"trace_view rendered no roof_host_ms counter (got {counters})")

    srv.engine.stop()

    print(json.dumps({
        "metric": "roof_audit",
        "value": 1,
        "detail": {
            "requests": detail["requests"],
            "platform": roof["platform"],
            "peak_source": roof["peaks"]["source"],
            "boundaries": roof["boundaries"],
            "variants": len(roof["variants"]),
            "mfu": tot["mfu"],
            "mbu": tot["mbu"],
            "host_frac": roof["host_frac"],
            "predicted_vs_measured": round(ratio, 4),
            "conservation_checked": cons["checked"],
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
