"""Frozen-schema golden tests for the debug observatory snapshots.

``/debug/compile``, ``/debug/hbm``, ``/debug/sched``, ``/debug/pilot``,
``/debug/roof`` and ``/debug/health`` are consumed by parties that
never import this repo's dataclasses: the loadtester's ledger polls,
``tools/compile_audit.py`` / ``tools/sched_audit.py`` /
``tools/pilot_audit.py`` / ``tools/roof_audit.py`` /
``tools/heal_audit.py``, ``tools/probe_hbm``, and whatever dashboards
operators curl together.
Their schemas are frozen here as literal key sets.  If one of these
tests fails, you changed the wire contract: update the module
docstrings in ``seldon_tpu/servers/compile_ledger.py`` /
``hbm_ledger.py`` / ``sched_ledger.py`` / ``controller.py`` /
``cost_model.py``, the consumers above, AND these goldens in the same
PR — never just the golden.
"""

import json
import time

from seldon_tpu.models.config import get_config
from seldon_tpu.servers.compile_ledger import CompileLedger
from seldon_tpu.servers.controller import PilotController
from seldon_tpu.servers.cost_model import RoofLedger
from seldon_tpu.servers.hbm_ledger import HbmLedger
from seldon_tpu.servers.sched_ledger import SchedLedger
from seldon_tpu.servers.supervisor import HealSupervisor

# The documented /debug/compile schema, frozen.
COMPILE_TOP_KEYS = frozenset({
    "warmup_complete",
    "tp",
    "mesh_devices",
    "declared_variants",
    "dispatched_variants",
    "warmup_coverage",
    "compile_s_total",
    "live_retrace_count",
    "live_retraces",
    "lattice",
})
COMPILE_WITNESS_KEYS = frozenset({"key", "rid", "compile_ms", "ts"})
COMPILE_LATTICE_KEYS = frozenset({
    "key", "dispatches", "first_dispatch_ms", "declared",
})

# The documented /debug/hbm schema, frozen.
HBM_TOP_KEYS = frozenset({
    "categories", "devices", "total_bytes", "total_bytes_per_device",
    "total_high_bytes",
})
HBM_CATEGORY_KEYS = frozenset({
    "bytes", "bytes_per_device", "high_bytes", "static",
})

# The documented /debug/sched schema, frozen (tools/sched_audit.py
# carries the same top-level golden).
SCHED_TOP_KEYS = frozenset({
    "boundaries",
    "dispatch_boundaries",
    "idle_boundaries",
    "dispatch_cells",
    "useful_tokens",
    "bucket_pad_tokens",
    "group_pad_tokens",
    "spec_rejected_tokens",
    "frag_tokens",
    "budget_offered_tokens",
    "budget_used_tokens",
    "budget_starved_passes",
    "padding_waste_frac",
    "budget_utilization",
    "goodput_gap",
    "pool_stall_events",
    "pool_stall_requests",
    "preemptions",
    "preempted_tokens",
    "spec",
    "wait",
    "conservation",
    "by_shape",
})
SCHED_GAP_KEYS = frozenset({
    "bucket_pad_frac", "group_pad_frac", "spec_rejected_frac",
    "frag_frac", "idle_frac",
})
SCHED_SPEC_KEYS = frozenset({
    "drafted_tokens", "accepted_tokens", "rejected_tokens",
    "verify_waves", "acceptance_rate",
})
SCHED_WAIT_KEYS = frozenset({
    "requests", "total_ms", "pool_ms", "bucket_ms", "budget_ms",
    "sched_ms", "predicted_ms",
})
SCHED_CONSERVATION_KEYS = frozenset({"checked", "breaches", "last_breach"})
SCHED_SHAPE_KEYS = frozenset({
    "key", "dispatches", "cells", "useful_tokens", "bucket_pad_tokens",
    "group_pad_tokens", "spec_rejected_tokens",
})

# The documented /debug/pilot schema, frozen (tools/pilot_audit.py
# carries the same top-level + ledger-entry goldens).
PILOT_TOP_KEYS = frozenset({
    "enabled",
    "mode",
    "boundaries",
    "windows",
    "period_boundaries",
    "decisions_total",
    "decisions_by_knob",
    "knobs",
    "envelope",
    "edf",
    "counterfactual",
    "ledger",
})
PILOT_KNOB_KEYS = frozenset({
    "dispatch_token_budget", "max_admit", "chunk_bias", "spec_k",
})
PILOT_ENVELOPE_KEYS = frozenset({
    "budget_min", "budget_max", "admit_min", "admit_max", "bias_min",
    "bias_max", "speck_min", "speck_max",
})
PILOT_EDF_KEYS = frozenset({"inversions", "reorders", "expired_at_pop"})
PILOT_CF_KEYS = frozenset({"windows", "goodput_delta", "waste_frac_delta"})
PILOT_LEDGER_KEYS = frozenset({
    "ts", "knob", "old", "new", "rationale", "expected_effect",
    "signal_snapshot", "effect",
})
PILOT_EFFECT_KEYS = frozenset({"goodput_delta", "waste_frac_delta"})
PILOT_SIGNAL_KEYS = frozenset({
    "boundaries", "dispatch_cells", "useful_tokens", "frag_tokens",
    "budget_dispatches", "budget_starved_passes",
    "budget_offered_tokens", "budget_used_tokens", "pool_stall_events",
    "preemptions", "deadline_expired", "spec_drafted", "spec_accepted",
    "goodput", "queue_depth", "free_slots", "roof_backlog_ms",
    "heal_pressure",
})

# The documented /debug/health schema, frozen (graftheal's
# HealSupervisor.snapshot(); tools/heal_audit.py polls it).
HEALTH_TOP_KEYS = frozenset({
    "enabled",
    "state",
    "mode",
    "max_retries",
    "watchdog_ms",
    "resurrected",
    "quarantined",
    "watchdog_trips",
    "retry_exhausted",
    "sentinel_trips",
    "recoveries",
    "consecutive_faults",
    "clean_boundaries",
    "pen",
    "suspects",
    "probing",
    "pressure",
})

# The documented /debug/roof schema, frozen (tools/roof_audit.py
# carries the same top-level + variant goldens).
ROOF_TOP_KEYS = frozenset({
    "enabled",
    "platform",
    "peaks",
    "tp",
    "boundaries",
    "waves",
    "step",
    "host_frac",
    "device_frac",
    "conservation",
    "variants",
    "totals",
})
ROOF_PEAKS_KEYS = frozenset({"tflops", "gbs", "source"})
ROOF_STEP_KEYS = frozenset({
    "wall_ms", "host_pre_ms", "device_ms", "host_post_ms", "overlap_ms",
})
ROOF_CONSERVATION_KEYS = frozenset({"checked", "breaches", "last_breach"})
ROOF_VARIANT_KEYS = frozenset({
    "key", "family", "dispatches", "flops", "bytes", "device_ms",
    "predicted_ms", "mfu", "mbu", "bound",
})
ROOF_TOTALS_KEYS = frozenset({
    "dispatches", "flops", "bytes", "device_ms", "predicted_ms",
    "mfu", "mbu",
})


def _populated_compile_ledger() -> CompileLedger:
    """A ledger exercising every snapshot branch: declared + dispatched
    keys, a sealed lattice, and one live-retrace witness."""
    led = CompileLedger()
    led.declare(("admit", 64, 4, 1))
    led.declare(("verify", 4))  # a whole-batch wave's variant
    led.dispatch(("admit", 64, 4, 1), rid=-1, seconds=0.5)
    led.dispatch(("decode", 8), rid=-1, seconds=0.2)
    led.dispatch(("verify", 4), rid=-1, seconds=0.4)
    led.warmup_done()
    led.dispatch(("admit", 64, 4, 1), rid=1, seconds=0.001)  # cache hit
    led.dispatch(("verify", 4), rid=3, seconds=0.0)          # cache hit
    witness = led.dispatch(("admit", 128, 8, 1), rid=2, seconds=0.7)
    assert witness is not None  # undeclared post-seal => live retrace
    return led


def _populated_hbm_ledger() -> HbmLedger:
    led = HbmLedger()
    led.set_static("weights", 1 << 20)
    led.set_static("kv_cache", 1 << 18)
    led.gauge("kv_live", lambda: 4096)
    led.note_workspace(2048)
    return led


def _populated_sched_ledger() -> SchedLedger:
    """A ledger exercising every snapshot branch: admission + chunk
    groups, a starved budget pass, stalls/preempts, idle and dispatch
    boundaries, a decomposed queue wait, and a clean audit pass."""
    led = SchedLedger()
    led.note_group(("admit", 64, 4), 256, 100, 92, 64)
    led.note_group(("chunk", 128, 2, 0), 256, 200, 56, 0)
    # A graftspec verify wave: 2 rows x (k=4 drafts + 1) = 10 cells, 7
    # emitted tokens -> 5 accepted drafts, 3 rejected positions.
    led.note_group(("verify", 4), 10, 7, 0, 0, spec_rejected=3)
    led.note_spec(8, 5, 3)
    led.note_budget(512, 400, starved=True)
    led.note_pool_stall(7)
    led.note_bucket_defer(7)
    led.note_preempt(9, tokens=48)
    led.note_boundary()
    led.note_idle()
    now = time.perf_counter()
    led.note_first_dispatch(7, submitted_at=now - 0.05, now=now)
    led.audit()
    return led


def _populated_pilot() -> PilotController:
    """A controller exercising every snapshot branch: a bound envelope,
    an EDF reorder + expired pop, one budget decision with its effect
    window already measured (counterfactual filled)."""
    import collections as _c
    import types as _t

    pilot = PilotController()
    pilot.bind(chunked=True, prefill_chunk=8, max_slots=4, max_admit=4,
               dispatch_token_budget=8, spec=True, spec_rungs=(1, 2, 4))
    now = time.perf_counter()
    pilot.order_queue(_c.deque([
        _t.SimpleNamespace(deadline=now + 9.0, submitted_at=now),
        _t.SimpleNamespace(deadline=now + 1.0, submitted_at=now),
    ]))
    pilot.note_expired_pop()

    def _windows(sig):
        for _ in range(pilot.period):
            pilot.on_boundary(lambda: dict(sig))

    base = {
        "boundaries": 0, "dispatch_cells": 0, "useful_tokens": 0,
        "frag_tokens": 0, "budget_dispatches": 0,
        "budget_starved_passes": 0, "budget_offered_tokens": 0,
        "budget_used_tokens": 0, "pool_stall_events": 0,
        "preemptions": 0, "deadline_expired": 0, "spec_drafted": 0,
        "spec_accepted": 0, "goodput": 1.0,
        "queue_depth": 0, "free_slots": 4, "roof_backlog_ms": 0.0,
        "heal_pressure": 0.0,
    }
    _windows(base)  # window 1 only baselines
    starved = dict(base, budget_dispatches=4, budget_starved_passes=4,
                   budget_offered_tokens=32, budget_used_tokens=32,
                   queue_depth=6)
    _windows(starved)  # window 2: budget raise decision
    _windows(dict(starved, goodput=0.75))  # window 3: effect measured
    return pilot


def _populated_roof_ledger() -> RoofLedger:
    """A ledger exercising every snapshot branch: bound geometry with
    resolved peaks, priced waves across three families (one zero-flop
    family so the host/bandwidth bound split is exercised), a decomposed
    boundary, and a clean audit pass."""
    led = RoofLedger()
    led.bind(get_config("tiny"), max_slots=4, max_seq_len=64,
             kv_block=16, platform="cpu-golden")
    led.note_wave([("admit", 8, 2), ("cow",)], device_ms=5.0)
    led.note_wave([("decode", 8)], device_ms=20.0)
    led.note_step(host_pre_ms=1.0, device_ms=25.0, host_post_ms=2.0,
                  span_ms=30.0)
    led.audit()
    return led


def test_compile_snapshot_key_set_is_frozen():
    snap = _populated_compile_ledger().snapshot()
    assert set(snap) == COMPILE_TOP_KEYS
    assert snap["live_retraces"], "fixture must produce a witness"
    for w in snap["live_retraces"]:
        assert set(w) == COMPILE_WITNESS_KEYS
    assert snap["lattice"], "fixture must produce lattice entries"
    for entry in snap["lattice"]:
        assert set(entry) == COMPILE_LATTICE_KEYS


def test_compile_snapshot_value_kinds():
    snap = _populated_compile_ledger().snapshot()
    assert isinstance(snap["warmup_complete"], bool)
    assert isinstance(snap["declared_variants"], int)
    assert isinstance(snap["dispatched_variants"], int)
    assert isinstance(snap["warmup_coverage"], float)
    assert isinstance(snap["compile_s_total"], float)
    assert isinstance(snap["live_retrace_count"], int)
    for entry in snap["lattice"]:
        # Keys render as the canonical slash-joined string, not tuples.
        assert isinstance(entry["key"], str) and "/" in entry["key"]
        assert isinstance(entry["declared"], bool)
    # A wave family's key renders with the same stable slash form as
    # every other family — consumers key lanes/gates on the string.
    wave = [e for e in snap["lattice"] if e["key"] == "verify/4"]
    assert len(wave) == 1 and wave[0]["declared"] is True
    assert wave[0]["dispatches"] == 2


def test_compile_snapshot_empty_ledger_same_keys():
    # A never-touched ledger serves the SAME key set (consumers need no
    # existence checks), just with empty/zero values.
    snap = CompileLedger().snapshot()
    assert set(snap) == COMPILE_TOP_KEYS
    assert snap["lattice"] == [] and snap["live_retraces"] == []


def test_hbm_snapshot_key_set_is_frozen():
    snap = _populated_hbm_ledger().snapshot()
    assert set(snap) == HBM_TOP_KEYS
    assert snap["categories"], "fixture must produce categories"
    for cat in snap["categories"].values():
        assert set(cat) == HBM_CATEGORY_KEYS


def test_hbm_snapshot_value_kinds():
    snap = _populated_hbm_ledger().snapshot()
    cats = snap["categories"]
    assert cats["weights"]["static"] is True
    assert cats["kv_live"]["static"] is False
    assert cats["workspace"]["static"] is False
    assert isinstance(snap["total_bytes"], int)
    assert isinstance(snap["total_high_bytes"], int)
    assert snap["total_bytes"] == sum(c["bytes"] for c in cats.values())


def test_sched_snapshot_key_set_is_frozen():
    snap = _populated_sched_ledger().snapshot()
    assert set(snap) == SCHED_TOP_KEYS
    assert set(snap["goodput_gap"]) == SCHED_GAP_KEYS
    assert set(snap["spec"]) == SCHED_SPEC_KEYS
    assert set(snap["wait"]) == SCHED_WAIT_KEYS
    assert set(snap["conservation"]) == SCHED_CONSERVATION_KEYS
    assert snap["by_shape"], "fixture must produce shape entries"
    for entry in snap["by_shape"]:
        assert set(entry) == SCHED_SHAPE_KEYS


def test_sched_snapshot_value_kinds():
    snap = _populated_sched_ledger().snapshot()
    assert isinstance(snap["boundaries"], int)
    assert snap["boundaries"] == (snap["dispatch_boundaries"]
                                  + snap["idle_boundaries"])
    assert isinstance(snap["padding_waste_frac"], float)
    assert isinstance(snap["budget_utilization"], float)
    for frac in snap["goodput_gap"].values():
        assert isinstance(frac, float) and 0.0 <= frac <= 1.0
    for comp in snap["wait"].values():
        assert isinstance(comp, (int, float)) and comp >= 0
    # The fixture's audit() pass must have run clean.
    assert snap["conservation"]["checked"] == 1
    assert snap["conservation"]["breaches"] == 0
    assert snap["conservation"]["last_breach"] is None
    # Conservation restated from the snapshot itself — the four-way
    # split (graftspec adds rejected draft positions).
    assert (snap["useful_tokens"] + snap["bucket_pad_tokens"]
            + snap["group_pad_tokens"]
            + snap["spec_rejected_tokens"]) == snap["dispatch_cells"]
    # graftspec acceptance identity restated from the snapshot.
    spec = snap["spec"]
    assert (spec["accepted_tokens"] + spec["rejected_tokens"]
            == spec["drafted_tokens"])
    assert spec["verify_waves"] == 1
    assert isinstance(spec["acceptance_rate"], float)
    for entry in snap["by_shape"]:
        # Keys render as the canonical slash-joined string, not tuples.
        assert isinstance(entry["key"], str) and "/" in entry["key"]
    # The verify family's by_shape entry: stable "verify/k" key, and
    # a wave pads neither a bucket nor a group.
    wave = [e for e in snap["by_shape"] if e["key"] == "verify/4"]
    assert len(wave) == 1
    assert wave[0]["cells"] == 10 and wave[0]["useful_tokens"] == 7
    assert wave[0]["bucket_pad_tokens"] == 0
    assert wave[0]["group_pad_tokens"] == 0


def test_sched_snapshot_empty_ledger_same_keys():
    # A never-touched ledger serves the SAME key set (consumers need no
    # existence checks), just with empty/zero values.
    snap = SchedLedger().snapshot()
    assert set(snap) == SCHED_TOP_KEYS
    assert set(snap["goodput_gap"]) == SCHED_GAP_KEYS
    assert set(snap["spec"]) == SCHED_SPEC_KEYS
    assert set(snap["wait"]) == SCHED_WAIT_KEYS
    assert snap["by_shape"] == []
    assert snap["spec"]["drafted_tokens"] == 0
    assert snap["spec"]["acceptance_rate"] == 1.0
    assert snap["dispatch_cells"] == 0
    assert snap["padding_waste_frac"] == 0.0
    assert snap["budget_utilization"] == 1.0


def test_pilot_snapshot_key_set_is_frozen():
    snap = _populated_pilot().snapshot()
    assert set(snap) == PILOT_TOP_KEYS
    assert set(snap["decisions_by_knob"]) == PILOT_KNOB_KEYS
    assert set(snap["knobs"]) == PILOT_KNOB_KEYS
    assert set(snap["envelope"]) == PILOT_ENVELOPE_KEYS
    assert set(snap["edf"]) == PILOT_EDF_KEYS
    assert set(snap["counterfactual"]) == PILOT_CF_KEYS
    assert snap["ledger"], "fixture must produce a decision"
    for entry in snap["ledger"]:
        assert set(entry) == PILOT_LEDGER_KEYS
        assert set(entry["signal_snapshot"]) == PILOT_SIGNAL_KEYS
        # The fixture closed the effect window: the counterfactual half
        # of every entry is filled, with exactly the documented keys.
        assert set(entry["effect"]) == PILOT_EFFECT_KEYS


def test_pilot_snapshot_value_kinds():
    snap = _populated_pilot().snapshot()
    assert snap["enabled"] is True
    assert snap["mode"] == "auto"
    assert isinstance(snap["boundaries"], int)
    assert isinstance(snap["windows"], int)
    assert isinstance(snap["period_boundaries"], int)
    assert snap["decisions_total"] == sum(
        snap["decisions_by_knob"].values())
    for v in snap["knobs"].values():
        assert isinstance(v, int)
    for v in snap["envelope"].values():
        assert isinstance(v, int)
    for v in snap["edf"].values():
        assert isinstance(v, int)
    assert isinstance(snap["counterfactual"]["goodput_delta"], float)
    for entry in snap["ledger"]:
        assert isinstance(entry["ts"], float)
        assert isinstance(entry["old"], int)
        assert isinstance(entry["new"], int)
        assert entry["rationale"] and isinstance(entry["rationale"], str)
        assert entry["expected_effect"]
        for v in entry["signal_snapshot"].values():
            assert isinstance(v, (int, float))
    # Live knobs stay inside the envelope — restated from the snapshot.
    env, knobs = snap["envelope"], snap["knobs"]
    assert env["budget_min"] <= knobs["dispatch_token_budget"] \
        <= env["budget_max"]
    assert env["admit_min"] <= knobs["max_admit"] <= env["admit_max"]
    assert env["bias_min"] <= knobs["chunk_bias"] <= env["bias_max"]
    assert env["speck_min"] <= knobs["spec_k"] <= env["speck_max"]


def test_pilot_snapshot_empty_controller_same_keys():
    # A never-flown controller serves the SAME key set (consumers need
    # no existence checks), just with empty/zero values.
    pilot = PilotController()
    pilot.bind(chunked=True, prefill_chunk=8, max_slots=4, max_admit=4,
               dispatch_token_budget=8)
    snap = pilot.snapshot()
    assert set(snap) == PILOT_TOP_KEYS
    assert snap["boundaries"] == 0
    assert snap["decisions_total"] == 0
    assert snap["ledger"] == []


def test_roof_snapshot_key_set_is_frozen():
    snap = _populated_roof_ledger().snapshot()
    assert set(snap) == ROOF_TOP_KEYS
    assert set(snap["peaks"]) == ROOF_PEAKS_KEYS
    assert set(snap["step"]) == ROOF_STEP_KEYS
    assert set(snap["conservation"]) == ROOF_CONSERVATION_KEYS
    assert set(snap["totals"]) == ROOF_TOTALS_KEYS
    assert snap["variants"], "fixture must produce variant entries"
    for entry in snap["variants"]:
        assert set(entry) == ROOF_VARIANT_KEYS


def test_roof_snapshot_value_kinds():
    snap = _populated_roof_ledger().snapshot()
    assert snap["enabled"] is True
    assert snap["platform"] == "cpu-golden"
    assert snap["peaks"]["source"] in ("env", "table", "microbench")
    assert isinstance(snap["peaks"]["tflops"], float)
    assert snap["peaks"]["tflops"] > 0.0
    assert isinstance(snap["boundaries"], int) and snap["boundaries"] == 1
    assert isinstance(snap["waves"], int) and snap["waves"] == 2
    for v in snap["step"].values():
        assert isinstance(v, float) and v >= 0.0
    # Decomposition restated from the snapshot itself: the components
    # re-sum to the measured boundary wall (overlap absorbs the gap).
    step = snap["step"]
    parts = (step["host_pre_ms"] + step["device_ms"]
             + step["host_post_ms"] + step["overlap_ms"])
    assert abs(parts - step["wall_ms"]) <= max(1.0, 0.01 * step["wall_ms"])
    assert 0.0 <= snap["host_frac"] <= 1.0
    assert 0.0 <= snap["device_frac"] <= 1.0
    # The fixture's audit() pass must have run clean.
    assert snap["conservation"]["checked"] == 1
    assert snap["conservation"]["breaches"] == 0
    assert snap["conservation"]["last_breach"] is None
    seen_bounds = set()
    for entry in snap["variants"]:
        # Keys render as the canonical slash-joined string, not tuples.
        assert isinstance(entry["key"], str)
        assert entry["family"] == entry["key"].split("/")[0]
        assert 0.0 <= entry["mfu"] <= 1.0
        assert 0.0 <= entry["mbu"] <= 1.0
        assert entry["bound"] in ("compute", "bandwidth", "host")
        seen_bounds.add(entry["bound"])
        assert entry["dispatches"] >= 1
        assert entry["device_ms"] >= 0.0
    # The cow wave prices zero flops: it can never read compute-bound.
    cow = [e for e in snap["variants"] if e["family"] == "cow"]
    assert len(cow) == 1 and cow[0]["flops"] == 0.0
    assert cow[0]["bound"] in ("bandwidth", "host")
    tot = snap["totals"]
    assert tot["dispatches"] == sum(
        e["dispatches"] for e in snap["variants"])
    # Wave device time is conserved across the per-variant split.
    assert abs(tot["device_ms"] - sum(
        e["device_ms"] for e in snap["variants"])) < 0.01
    assert 0.0 <= tot["mfu"] <= 1.0
    assert 0.0 <= tot["mbu"] <= 1.0


def test_roof_snapshot_empty_ledger_same_keys():
    # A never-touched ledger serves the SAME key set (consumers need no
    # existence checks), just with empty/zero values.
    snap = RoofLedger().snapshot()
    assert set(snap) == ROOF_TOP_KEYS
    assert set(snap["peaks"]) == ROOF_PEAKS_KEYS
    assert set(snap["step"]) == ROOF_STEP_KEYS
    assert set(snap["totals"]) == ROOF_TOTALS_KEYS
    assert snap["variants"] == []
    assert snap["boundaries"] == 0 and snap["waves"] == 0
    assert snap["host_frac"] == 0.0 and snap["device_frac"] == 0.0
    assert snap["totals"]["mfu"] == 0.0


def _populated_supervisor() -> HealSupervisor:
    """A supervisor exercising every snapshot branch: one recovery
    (state leaves healthy), a resurrection counted, a penned repeat
    replay, and a bisection round in flight (suspects + probing
    non-empty)."""
    import types as _t

    sup = HealSupervisor(max_retries=4, watchdog_ms=50)
    now = time.perf_counter()
    # First fault over rids 1..3: everyone resurrects.
    v1 = sup.plan_recovery([1, 2, 3], now)
    assert set(v1.values()) == {"resurrect"}
    for _ in v1:
        sup.note_resurrected()
    # Second fault over the same cohort: bisection starts; the
    # non-probing half lands in the pen.
    v2 = sup.plan_recovery([1, 2, 3], now)
    assert "pen" in v2.values()
    for rid, verdict in sorted(v2.items()):
        if verdict == "pen":
            sup.pen_put(_t.SimpleNamespace(rid=rid, finished=False), now)
    return sup


def test_health_snapshot_key_set_is_frozen():
    snap = _populated_supervisor().snapshot()
    assert set(snap) == HEALTH_TOP_KEYS


def test_health_snapshot_value_kinds():
    snap = _populated_supervisor().snapshot()
    assert snap["enabled"] is True
    assert snap["state"] in ("healthy", "recovering", "degraded")
    assert snap["mode"] in ("normal", "bisect")
    assert isinstance(snap["max_retries"], int)
    assert isinstance(snap["watchdog_ms"], int)
    for k in ("resurrected", "quarantined", "watchdog_trips",
              "retry_exhausted", "sentinel_trips", "recoveries",
              "consecutive_faults", "clean_boundaries", "pen"):
        assert isinstance(snap[k], int) and snap[k] >= 0
    assert isinstance(snap["suspects"], list)
    assert isinstance(snap["probing"], list)
    # The fixture left a bisection in flight with a populated pen.
    assert snap["mode"] == "bisect"
    assert snap["suspects"] and snap["probing"]
    assert snap["pen"] >= 1
    assert snap["resurrected"] == 3 and snap["recoveries"] == 2
    # Pressure restates the state machine: recovering (no quarantine or
    # exhaustion happened) reads 0.5.
    assert snap["state"] == "recovering" and snap["pressure"] == 0.5


def test_health_snapshot_fresh_supervisor_same_keys():
    # A never-faulted supervisor serves the SAME key set (consumers
    # need no existence checks), just with empty/zero values.
    snap = HealSupervisor().snapshot()
    assert set(snap) == HEALTH_TOP_KEYS
    assert snap["state"] == "healthy" and snap["pressure"] == 0.0
    assert snap["mode"] == "normal"
    assert snap["suspects"] == [] and snap["probing"] == []
    assert snap["pen"] == 0 and snap["recoveries"] == 0


def test_snapshots_are_json_clean():
    # All snapshots must survive json.dumps untouched — they go over
    # the wire verbatim from the debug routes.
    comp = json.loads(json.dumps(_populated_compile_ledger().snapshot()))
    assert set(comp) == COMPILE_TOP_KEYS
    hbm = json.loads(json.dumps(_populated_hbm_ledger().snapshot()))
    assert set(hbm) == HBM_TOP_KEYS
    sched = json.loads(json.dumps(_populated_sched_ledger().snapshot()))
    assert set(sched) == SCHED_TOP_KEYS
    pilot = json.loads(json.dumps(_populated_pilot().snapshot()))
    assert set(pilot) == PILOT_TOP_KEYS
    roof = json.loads(json.dumps(_populated_roof_ledger().snapshot()))
    assert set(roof) == ROOF_TOP_KEYS
    heal = json.loads(json.dumps(_populated_supervisor().snapshot()))
    assert set(heal) == HEALTH_TOP_KEYS
