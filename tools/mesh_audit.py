#!/usr/bin/env python
"""CI mesh audit: graftmesh tensor-parallel serving end to end.

Boots the tiny warmed JAXServer twice — once pinned to an explicit
single-chip mesh (``tp=1``), once as a ``TP=2`` group on the fake
8-device CPU mesh (``XLA_FLAGS=--xla_force_host_platform_device_count``
set here, matching tests/conftest.py) — with ``GRAFTSAN=1`` +
``SCHED_LEDGER=1`` + ``COMPILE_LEDGER=1`` + ``HBM_LEDGER=1`` +
``ROOF_LEDGER=1``, and asserts the graftmesh contract in one pass:

 * BIT-EXACT PARITY: the TP group reproduces the single-chip greedy
   streams token for token on a mixed-length prompt matrix (paged +
   chunked serving — pool, block tables and chunk admissions run SPMD);
 * ONE SEALED LATTICE serves the whole group: ``/debug/compile``
   reports the TP geometry (tp=2, mesh_devices=2), every dispatched
   variant sits inside ``static_lattice()``, and a real loadtester
   window produces ZERO live retraces — SPMD partitioning must not
   reopen the shape lattice, and the donated-state sharding pins mean
   jit cache keys cannot drift;
 * the books stay clean on the mesh: the sched ledger's four-way
   attribution re-sums with zero conservation breaches, the roof
   ledger decomposes boundaries with zero breaches and carries the
   per-chip ``tp`` field, and the runtime sanitizer reports zero
   lock-contract violations;
 * LEAK-FREE: after the load window drains, live KV bytes return to
   zero — TP sharding must not strand paged blocks;
 * PER-DEVICE HBM: ``/debug/hbm`` reports the mesh size, mesh-wide
   weight bytes equal per-device x devices, and the KV reservation
   shards exactly in half on its head axis.

Run via ``make mesh-audit`` (wired into ``make ci``); exits non-zero
with a one-line diagnosis on the first failed check.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys

# Mixed-length greedy parity matrix: lengths straddle the tiny server's
# prompt buckets so admission grouping, chunked tails and block-table
# growth all get exercised on the mesh.
PARITY_PROMPTS = [
    list(range(2, 2 + n)) for n in (4, 11, 24, 17)
]
PARITY_NEW = 12


def _check(cond: bool, msg: str) -> None:
    if not cond:
        print(f"mesh-audit FAIL: {msg}", file=sys.stderr)
        raise SystemExit(1)


def _streams(engine) -> list:
    """Greedy token streams for the parity matrix, in submit order."""
    from seldon_tpu.models.sampling import SamplingParams

    qs = [engine.submit(p, SamplingParams(
              temperature=0.0, top_k=0, top_p=1.0,
              max_new_tokens=PARITY_NEW, seed=i))
          for i, p in enumerate(PARITY_PROMPTS)]
    out = []
    for q in qs:
        toks = []
        while True:
            item = q.get(timeout=120)
            if item is None:
                break
            if "error" in item:
                raise RuntimeError(item["error"])
            toks.extend(item.get("tokens", []))
        out.append(toks)
    return out


def main(argv=None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # The TP group needs real (fake) devices; harmless if already set.
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    os.environ["TP"] = "2"  # the audited leg arms via the env knob
    os.environ["GRAFTSAN"] = "1"
    os.environ["SCHED_LEDGER"] = "1"
    os.environ["COMPILE_LEDGER"] = "1"
    os.environ["HBM_LEDGER"] = "1"
    os.environ["ROOF_LEDGER"] = "1"

    import asyncio
    import threading
    import urllib.request

    from aiohttp import web

    from seldon_tpu.loadtester import main as lt_main
    from seldon_tpu.runtime.wrapper import build_rest_app
    from seldon_tpu.servers.jaxserver import JAXServer

    SERVE = dict(preset="tiny", max_slots=4, max_seq_len=64, warmup=1,
                 paged_kv=1, chunked_prefill=1)

    # --- reference leg: same weights on an explicit single chip --------
    # (tp=1 unit param overrides the TP=2 env; init_seed-determined
    # weights are identical across the two boots.)
    ref = JAXServer(tp=1, **SERVE)
    ref.load()
    ref.engine.start()
    want = _streams(ref.engine)
    ref.engine.stop()
    del ref
    _check(all(len(s) >= 1 for s in want),
           "reference engine produced an empty stream")

    # --- audited leg: TP=2 through the real REST app --------------------
    srv = JAXServer(**SERVE)
    srv.load()
    _check(srv.tp == 2, "TP=2 env did not arm the jaxserver mesh path")
    _check(srv.engine.ecfg.tp == 2, "EngineConfig.tp did not pick up TP=2")

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
        # --- occupancy probe: ratchet the kv_live watermark --------------
        # HBM gauges are evaluated only at snapshot, so observe a slot
        # mid-stream once; the leak check after the drain then proves
        # live KV genuinely returned to zero rather than never moving.
        from seldon_tpu.models.sampling import SamplingParams

        # A stream of 40 tokens (this prompt's greedy stream meets no
        # EOS), looked at in this process at each delivery: a short one
        # has ended, and freed its blocks, before an HTTP round trip.
        q = srv.engine.submit(PARITY_PROMPTS[0], SamplingParams(
            temperature=0.0, max_new_tokens=40))
        live = 0
        while q.get(timeout=120) is not None:
            live = live or srv.engine.debug_hbm()[
                "categories"]["kv_live"]["bytes"]
        _check(live > 0,
               "no live KV bytes with an occupied slot on the mesh")

        # --- bit-exact parity ------------------------------------------
        got = _streams(srv.engine)
        _check(
            got == want,
            "TP group diverged from the single-chip greedy streams: "
            f"want {want} got {got}",
        )

        # --- loadtester window on the mesh -------------------------------
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            lt_main([
                url, "--transport", "generate", "--clients", "4",
                "--seconds", "2", "--prompt", "hi",
                "--max-new-tokens", "8",
            ])
        ledger = json.loads(buf.getvalue().strip().splitlines()[-1])
        detail = ledger["detail"]
        _check(detail["errors"] == 0,
               f"loadtester saw {detail['errors']} transport errors")
        _check(detail["requests"] >= 1, "loadtester completed no requests")

        srv.engine.drain(timeout=120)
        sched = get("/debug/sched")
        comp = get("/debug/compile")
        hbm = get("/debug/hbm")
        roof = get("/debug/roof")
    finally:
        holder["stop"] = True
        t.join(timeout=10)

    # --- one sealed lattice for the whole TP group -----------------------
    _check(comp["tp"] == 2, f"/debug/compile tp={comp['tp']}, want 2")
    _check(comp["mesh_devices"] == 2,
           f"/debug/compile mesh_devices={comp['mesh_devices']}, want 2")
    static = set(srv.engine.static_lattice())
    dispatched = {row["key"] for row in comp["lattice"]}
    _check(dispatched <= static,
           f"dispatched variants escaped the static lattice: "
           f"{sorted(dispatched - static)}")
    _check(comp["live_retrace_count"] == 0,
           f"{comp['live_retrace_count']} live retraces on the mesh: "
           f"{comp['live_retraces']}")
    _check(comp["warmup_complete"] is True, "warmup never sealed")

    # --- books stay clean on the mesh ------------------------------------
    cells = sched["dispatch_cells"]
    attributed = (sched["useful_tokens"] + sched["bucket_pad_tokens"]
                  + sched["group_pad_tokens"]
                  + sched["spec_rejected_tokens"])
    _check(attributed == cells,
           f"4-way attribution {attributed} != dispatched cells {cells}")
    cons = sched["conservation"]
    _check(cons["checked"] > 0, "conservation audit never ran")
    _check(cons["breaches"] == 0,
           f"{cons['breaches']} sched conservation breaches on the mesh: "
           f"{cons['last_breach']}")
    _check(roof["tp"] == 2, f"/debug/roof tp={roof['tp']}, want 2")
    _check(roof["boundaries"] > 0, "roof ledger observed no boundaries")
    rcons = roof["conservation"]
    _check(rcons["breaches"] == 0,
           f"{rcons['breaches']} roof conservation breaches on the mesh: "
           f"{rcons['last_breach']}")
    san = srv.engine._san
    _check(san is not None, "GRAFTSAN=1 but the engine has no sanitizer")
    _check(not san.violations,
           f"graftsan violations on the mesh: {san.violations}")

    # --- leak-free: live KV returns to zero after the drain --------------
    kv_live = hbm["categories"]["kv_live"]
    _check(kv_live["bytes"] == 0,
           f"{kv_live['bytes']} live KV bytes stranded after drain")
    _check(kv_live["high_bytes"] > 0,
           "kv_live watermark never moved — the window served nothing?")

    # --- per-device HBM accounting ---------------------------------------
    _check(hbm["devices"] == 2, f"/debug/hbm devices={hbm['devices']}")
    w = hbm["categories"]["weights"]
    _check(w["bytes"] == 2 * w["bytes_per_device"],
           f"weights mesh-wide {w['bytes']} != 2 x per-device "
           f"{w['bytes_per_device']}")
    kv = hbm["categories"]["kv_cache"]
    _check(kv["bytes_per_device"] == kv["bytes"] // 2,
           f"KV reservation did not shard in half: {kv}")
    _check(hbm["total_bytes_per_device"] < hbm["total_bytes"],
           "per-device total did not drop below the mesh-wide total")

    srv.engine.stop()

    print(json.dumps({
        "metric": "mesh_audit",
        "value": 1,
        "detail": {
            "tp": comp["tp"],
            "mesh_devices": comp["mesh_devices"],
            "requests": detail["requests"],
            "parity_streams": len(want),
            "declared_variants": comp["declared_variants"],
            "dispatched_variants": comp["dispatched_variants"],
            "live_retraces": comp["live_retrace_count"],
            "weights_bytes_per_device": w["bytes_per_device"],
            "kv_bytes_per_device": kv["bytes_per_device"],
            "sched_conservation_checked": cons["checked"],
            "roof_conservation_checked": rcons["checked"],
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
