#!/usr/bin/env python
"""CI compile audit: the compile/device observatory end to end.

Boots the tiny warmed JAXServer behind the real REST app with
``COMPILE_LEDGER=1`` + ``HBM_LEDGER=1`` + ``DISPATCH_TIMING=1`` +
``FLIGHT_RECORDER=1``, drives it with a short closed-loop loadtester
run, then asserts the observatory contract in one pass:

 * ``/debug/compile`` returns the documented schema with
   ``warmup_complete`` true, **zero live retraces** — the regression
   tripwire for the static-shape lattice: any new dispatch site or
   bucketing change that compiles on the serving path fails CI here —
   and a dispatched-variant count within ``VARIANT_BUDGET``;
 * the loadtester ledger carries the same ``compile_variants`` /
   ``live_retraces`` numbers (the bench/ledger surface);
 * per-variant dispatch timing reached EngineStats and the flight
   recorder ("dispatch" records convert to variant lanes in
   ``tools/trace_view.py``);
 * ``/debug/hbm`` returns the documented schema with non-zero weight
   and KV-reservation bytes.

With ``--static-xcheck`` the audit additionally cross-checks the
runtime against graftflow's closed-form model: every key the engine
actually dispatched must be a member of ``engine.static_lattice()``
(the ``shape_lattice.dispatch_keys`` enumeration), and the declared
variant count must equal the static lattice size — i.e. warmup
declared exactly the statically-certified set, nothing ad hoc.
Before booting anything it also runs the graftnum certifier passes
(num-barrier / use-after-donate / einsum-broadcast + mask-dtype) over
``seldon_tpu/`` and fails if any finding survives the inline waivers:
a tree the audit is about to *measure* must already be numerics- and
lifetime-clean, or the measured bits aren't the contract bits.

A second, SPEC leg boots the same server under ``SPEC=1`` and asserts
the graftspec lattice contract: the pow2 ``verify/k`` ladder replaces
the ``decode/n`` chunk rungs (a verify wave dispatched, no decode
variant did), every dispatched key stays inside ``static_lattice()``,
and zero live retraces — speculation must not reopen the shape lattice
graftflow closed.

Run via ``make compile-audit`` (wired into ``make ci``); exits non-zero
with a one-line diagnosis on the first failed check.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

# Dispatched-variant ceiling for the tiny CPU config (2 prompt buckets
# x 3 admission group sizes + decode rungs + deactivate ~= 9 today).
# Roadmap items 1-2 drive this DOWN; raising it needs a written
# justification in the PR that does so.
VARIANT_BUDGET = 32

def _check(cond: bool, msg: str) -> None:
    if not cond:
        print(f"compile-audit FAIL: {msg}", file=sys.stderr)
        raise SystemExit(1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m tools.compile_audit")
    ap.add_argument(
        "--static-xcheck", action="store_true",
        help="also assert the runtime-dispatched key set is contained in "
             "engine.static_lattice() and that warmup declared exactly "
             "the static lattice (graftflow's closed-form model)")
    args = ap.parse_args(argv)

    if args.static_xcheck:
        # graftnum gate first: static, cheap, and a prerequisite — if
        # the tree has an uncertified fusion boundary or a use-after-
        # donate path, the runtime numbers below measure the bug.
        from pathlib import Path

        from tools.graftlint import core, donate, einsumcheck, numbarrier

        root = Path(__file__).resolve().parent.parent
        files = core.load_tree([root / "seldon_tpu"], root)
        ctx = core.Context(root)
        findings = core.run_passes(
            files, ctx, [numbarrier.run, donate.run, einsumcheck.run])
        for f in findings:
            print(f"compile-audit graftnum: {f.render()}", file=sys.stderr)
        _check(not findings,
               f"graftnum: {len(findings)} uncertified finding(s) in "
               "seldon_tpu/ — fix or waive inline before auditing")
        print(f"compile-audit: graftnum clean over {len(files)} file(s)")

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["COMPILE_LEDGER"] = "1"
    os.environ["HBM_LEDGER"] = "1"
    os.environ["DISPATCH_TIMING"] = "1"
    os.environ["FLIGHT_RECORDER"] = "1"

    import asyncio
    import threading
    import urllib.request

    from aiohttp import web

    from seldon_tpu.loadtester import main as lt_main
    from seldon_tpu.runtime.wrapper import build_rest_app
    from seldon_tpu.servers.jaxserver import JAXServer
    from tools import trace_view

    def _drive(**srv_kwargs):
        """Boot a warmed tiny server behind the REST app, run the
        short closed-loop loadtester mix, return (srv, loadtester
        ledger detail, /debug/compile, /debug/hbm, /debug/timeline)."""
        # warmup=1 is the point: the audit asserts the declared lattice
        # covers live traffic, so warmup must actually run.
        srv = JAXServer(preset="tiny", max_slots=4, max_seq_len=64,
                        warmup=1, **srv_kwargs)
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

        t = threading.Thread(target=lambda: asyncio.run(amain()),
                             daemon=True)
        t.start()
        _check(started.wait(60), "REST app failed to start within 60s")
        url = f"http://127.0.0.1:{holder['port']}"

        try:
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                lt_main([
                    url, "--transport", "generate", "--clients", "2",
                    "--seconds", "2", "--prompt", "hi",
                    "--max-new-tokens", "4",
                ])
            ledger = json.loads(buf.getvalue().strip().splitlines()[-1])
            detail = ledger["detail"]
            _check(detail["errors"] == 0,
                   f"loadtester saw {detail['errors']} transport errors")
            _check(detail["requests"] >= 1,
                   "loadtester completed no requests")

            with urllib.request.urlopen(f"{url}/debug/compile",
                                        timeout=10) as resp:
                comp = json.loads(resp.read())
            with urllib.request.urlopen(f"{url}/debug/hbm",
                                        timeout=10) as resp:
                hbm = json.loads(resp.read())
            with urllib.request.urlopen(f"{url}/debug/timeline",
                                        timeout=10) as resp:
                snap = json.loads(resp.read())
        finally:
            holder["stop"] = True
            t.join(timeout=10)
        return srv, detail, comp, hbm, snap

    srv, detail, comp, hbm, snap = _drive()

    # --- /debug/compile: schema + the zero-retrace gate -----------------
    for key in ("warmup_complete", "declared_variants",
                "dispatched_variants", "warmup_coverage",
                "compile_s_total", "live_retrace_count", "live_retraces",
                "lattice"):
        _check(key in comp, f"/debug/compile missing '{key}'")
    _check(comp["warmup_complete"], "warmup never sealed the lattice")
    _check(
        comp["live_retrace_count"] == 0,
        f"{comp['live_retrace_count']} live retraces after warmup: "
        f"{comp['live_retraces']}",
    )
    _check(comp["dispatched_variants"] >= 1, "no variants dispatched")
    _check(
        comp["dispatched_variants"] <= VARIANT_BUDGET,
        f"{comp['dispatched_variants']} variants exceed the "
        f"budget of {VARIANT_BUDGET}",
    )
    _check(comp["compile_s_total"] > 0.0, "zero cumulative compile time")
    undeclared = [e["key"] for e in comp["lattice"] if not e["declared"]]
    _check(not undeclared, f"undeclared lattice keys: {undeclared}")

    # --- --static-xcheck: runtime vs graftflow's closed-form lattice ----
    static_size = None
    if args.static_xcheck:
        static = set(srv.engine.static_lattice())
        static_size = len(static)
        dispatched = {e["key"] for e in comp["lattice"]}
        rogue = sorted(dispatched - static)
        _check(
            not rogue,
            f"runtime dispatched {len(rogue)} key(s) outside the static "
            f"lattice: {rogue}",
        )
        _check(
            comp["declared_variants"] == static_size,
            f"warmup declared {comp['declared_variants']} variants but "
            f"the static lattice holds {static_size} — warmup and "
            f"shape_lattice.dispatch_keys have drifted apart",
        )

    # --- loadtester ledger carries the compile counters -----------------
    _check(
        detail.get("compile_variants") == comp["dispatched_variants"],
        f"ledger compile_variants {detail.get('compile_variants')} != "
        f"/debug/compile {comp['dispatched_variants']}",
    )
    _check(detail.get("live_retraces") == 0,
           f"ledger live_retraces = {detail.get('live_retraces')}")

    # --- per-variant timing: stats histogram + recorder lanes -----------
    stats = srv.engine.stats.snapshot()
    timing = stats.get("variant_timing", {})
    _check(timing, "DISPATCH_TIMING=1 populated no variant histograms")
    _check(any(k.startswith("decode/") for k in timing),
           f"no decode variant timed (got: {sorted(timing)})")
    kinds = {r["kind"] for r in snap.get("records", [])}
    _check("dispatch" in kinds,
           f"no dispatch records in timeline (kinds: {sorted(kinds)})")
    out = json.loads(json.dumps(trace_view.convert(snap)))
    lanes = {
        e["args"]["name"] for e in out["traceEvents"]
        if e["ph"] == "M" and e.get("pid") == trace_view._VARIANT_PID
        and e["name"] == "thread_name"
    }
    _check(lanes, "trace_view rendered no per-variant lanes")

    # --- /debug/hbm: schema + non-trivial accounting --------------------
    for key in ("categories", "total_bytes", "total_high_bytes"):
        _check(key in hbm, f"/debug/hbm missing '{key}'")
    cats = hbm["categories"]
    for name in ("weights", "kv_cache", "kv_live", "workspace"):
        _check(name in cats, f"/debug/hbm missing category '{name}'")
    _check(cats["weights"]["bytes"] > 0, "zero weight bytes")
    _check(cats["kv_cache"]["bytes"] > 0, "zero KV reservation bytes")
    _check(cats["workspace"]["high_bytes"] > 0,
           "workspace high-watermark never moved")

    srv.engine.stop()

    # --- SPEC leg: the verify ladder stays inside the lattice -----------
    # graftspec replaces the decode-chunk rungs with the pow2
    # ("verify", k) ladder; the contract here is containment + zero
    # retraces, not a fixed count (the admission grid is still live).
    ssrv, sdetail, scomp, _, _ = _drive(spec=1)
    _check(scomp["warmup_complete"],
           "spec: warmup never sealed the lattice")
    _check(
        scomp["live_retrace_count"] == 0,
        f"spec: {scomp['live_retrace_count']} live retraces after "
        f"warmup: {scomp['live_retraces']}",
    )
    srogue = [e["key"] for e in scomp["lattice"] if not e["declared"]]
    _check(not srogue, f"spec: undeclared lattice keys: {srogue}")
    _check(
        any(e["key"].startswith("verify/") for e in scomp["lattice"]),
        f"spec: no verify/k variant dispatched "
        f"(got: {sorted(e['key'] for e in scomp['lattice'])})",
    )
    _check(
        not any(e["key"].startswith("decode/") for e in scomp["lattice"]),
        "spec: a decode/ chunk variant dispatched — the verify ladder "
        "should have replaced the decode rungs",
    )
    _check(
        sdetail.get("compile_variants") == scomp["dispatched_variants"],
        f"spec: ledger compile_variants "
        f"{sdetail.get('compile_variants')} != /debug/compile "
        f"{scomp['dispatched_variants']}",
    )
    spec_static_size = None
    if args.static_xcheck:
        sstatic = set(ssrv.engine.static_lattice())
        spec_static_size = len(sstatic)
        _check(
            any(k.startswith("verify/") for k in sstatic),
            f"spec: static lattice declares no verify family "
            f"({sorted(sstatic)})",
        )
        sdispatched = {e["key"] for e in scomp["lattice"]}
        sstray = sorted(sdispatched - sstatic)
        _check(
            not sstray,
            f"spec: runtime dispatched {len(sstray)} key(s) outside "
            f"the static lattice: {sstray}",
        )
        _check(
            scomp["declared_variants"] == spec_static_size,
            f"spec: warmup declared {scomp['declared_variants']} "
            f"variants but the static lattice holds {spec_static_size}",
        )
    ssrv.engine.stop()

    print(json.dumps({
        "metric": "compile_audit",
        "value": 1,
        "detail": {
            "requests": detail["requests"],
            "compile_variants": comp["dispatched_variants"],
            "declared_variants": comp["declared_variants"],
            "variant_budget": VARIANT_BUDGET,
            "live_retraces": comp["live_retrace_count"],
            "compile_s_total": comp["compile_s_total"],
            "warmup_coverage": comp["warmup_coverage"],
            "variant_lanes": sorted(lanes),
            "hbm_total_bytes": hbm["total_bytes"],
            "static_lattice": static_size,
            "spec_requests": sdetail["requests"],
            "spec_compile_variants": scomp["dispatched_variants"],
            "spec_live_retraces": scomp["live_retrace_count"],
            "spec_static_lattice": spec_static_size,
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
