"""Benchmark entry: prints ONE JSON line for the driver.

Measures the CONTINUOUS-BATCHING ENGINE under concurrent load (the real
serving path, not bare `generate()`): N_REQ requests (prefill 128 +
decode up to 128) are submitted together to an InferenceEngine with
SLOTS decode lanes, on whatever accelerator is visible (the driver runs
this on one real TPU chip).

The HEADLINE preset is `llama3-8b` — the TRUE north-star geometry
(BASELINE.json: Llama-3-8B at 1000 req/s on a v5e-8 slice = 125
req/s/chip), int8 weights + int8 KV on one chip. That number is
HBM-roofline-bound: every decode step reads the full ~8 GB of int8
weights, so docs/benchmarking.md derives the per-chip ceiling alongside
the measurement. BENCH_PRESET=bench-1b selects the small-model proxy
whose per-chip weight traffic matches the TP8 deployment shard
(~1 GB/chip) — the configuration the 125 req/s/chip target actually
describes.

Reference baselines (SURVEY.md §6) measure the Java engine with a stub
model (12k req/s REST / 28k gRPC on n1-standard-16) — orchestrator-only,
no model compute; `bench_orchestrator.py` covers that comparison. This
one measures what the reference never could: real transformer serving
throughput per chip.
"""

from __future__ import annotations

import json
import os
import sys
import time

# Env overrides are for local smoke-testing only (e.g. BENCH_PRESET=tiny
# on CPU); the driver runs with the defaults.
PRESET = os.environ.get("BENCH_PRESET", "llama3-8b")
# Slot-count knees measured per preset: bench-1b 160 (96 -> 77 req/s,
# 160 -> 96, 192 -> 95, 256 -> 68: past ~160 the KV read outgrows the
# weight-read amortization); llama3-8b 192 (round-5 end-to-end ladder
# via tools/tune_8b, slots:admit:chunk -> req/s: 160:8:64 -> 32.0,
# 192:8:64 -> 32.1, 224:8:64 -> 25.7 (cliff), 192:16:64 -> 32.4 (best),
# 192:8:32 -> 32.1 — flat at the knee; docs/benchmarking.md derives why
# the residual gap to north star is the prefill-compute + weight-read
# interleave, not slot count).
SLOTS = int(os.environ.get("BENCH_SLOTS", 0)) or (
    192 if PRESET == "llama3-8b" else 160
)
N_REQ = int(os.environ.get("BENCH_NREQ", 0)) or 2 * SLOTS
MAX_ADMIT = int(os.environ.get("BENCH_ADMIT", 0)) or (
    16 if PRESET == "llama3-8b" else 8
)
PROMPT_LEN = int(os.environ.get("BENCH_PROMPT", 128))
NEW_TOKENS = int(os.environ.get("BENCH_NEW", 128))
DECODE_CHUNK = int(os.environ.get("BENCH_CHUNK", 64))  # 32 -> 0.78x, 64 -> 0.82x
# int8 KV + int8 weights is the default serving config. The round-2
# "int8 KV regresses with int8 weights" interaction was the carried-cache
# read-after-write materialization; with the pre-write head-major decode
# path (transformer.gqa_attention_decode) int8 KV is strictly fastest:
# 9.9 (bf16 kv) -> 7.9 ms/step at [160 slots, 257 window] on v5e.
# Quality pinned by tests (<0.5%/step teacher-forced logit error).
KV_DTYPE = os.environ.get("BENCH_KV", "int8")
ATTN = os.environ.get("BENCH_ATTN", "")
# Weight-only int8 (per-channel scales): faster than bf16 weights and
# half the footprint; quality pinned by tests. BENCH_WEIGHTS=bf16 reverts.
WEIGHTS = os.environ.get("BENCH_WEIGHTS", "int8")
# W8A8 matmul activations (round 5): decode is COMPUTE-bound past the
# slot knee and the v5e MXU runs s8 x s8 at double rate; dynamic
# per-token A8 meets the same tiny-geometry quality bars that admitted
# int8 weights/KV (tests/test_models.py::test_w8a8_*). BENCH_ACT=bf16
# reverts to bf16-math matmuls.
ACT = os.environ.get("BENCH_ACT", "int8")
# Prefix-cache phase (opt-in): runs a shared-prefix workload against a
# prefix_cache=True engine and records hit rate + cold-vs-warm admission
# TTFT in detail.prefix. Off by default: the headline workload uses
# i.i.d. random prompts where a prefix cache can only add overhead.
PREFIX = os.environ.get("BENCH_PREFIX", "0") == "1"
PREFIX_BLOCK = int(os.environ.get("BENCH_PREFIX_BLOCK", "16"))
PREFIX_NREQ = int(os.environ.get("BENCH_PREFIX_NREQ", "24"))
# Chunked-prefill phase (opt-in): p99 inter-token latency of short
# decode streams while ONE long-prompt interloper arrives mid-decode,
# measured with chunked_prefill off (the interloper's whole prefill
# stalls every stream) vs on (bounded chunks interleave with decode).
# Recorded in detail.chunked.
CHUNKED = os.environ.get("BENCH_CHUNKED", "0") == "1"
CHUNKED_STREAMS = int(os.environ.get("BENCH_CHUNKED_STREAMS", "6"))
CHUNKED_LONG_X = int(os.environ.get("BENCH_CHUNKED_LONG_X", "8"))
# Paged-KV phase (opt-in): concurrent short-decode streams at a FIXED KV
# HBM budget, dense slab vs paged pool. The dense engine reserves
# max_seq_len per slot, so short streams waste the window's tail; the
# paged engine carves the same token budget into kv_block blocks and
# admits until the POOL (not the slot count) runs out. Also records
# zero-copy warm admissions off the block trie. Recorded in detail.paged.
PAGED = os.environ.get("BENCH_PAGED", "0") == "1"
# Pilot phase: one mixed-deadline closed wave run twice at equal
# hardware — PILOT=1 (graftpilot auto-tuning + EDF) vs pilot off — so
# the bench line carries the controller's goodput delta, decision count
# and final knob values (tools/bench_compare.py gates slo_goodput
# higher-is-better and pilot_edf_inversions lower-is-better).
PILOT_PHASE = os.environ.get("BENCH_PILOT", "0") == "1"
# Spec phase: the same greedy closed wave run twice at equal hardware —
# graftspec speculative decoding (SPEC=1 semantics: draft k, verify in
# one wide wave) vs plain decode — so the bench line carries per-leg
# decode tok/s, the spec leg's acceptance rate and dispatches/token
# (tools/bench_compare.py gates spec_acceptance_rate higher-is-better
# and decode tok/s no-regression). BENCH_SPEC_DRAFT picks the drafter:
# "self" (default — the target's own weights, the CPU-smoke upper
# bound), "" for the host n-gram drafter, or a preset name ("bench-1b"
# on the 8B TPU run) for a resident draft model. Recorded in
# detail.spec.
SPEC_PHASE = os.environ.get("BENCH_SPEC", "0") == "1"
SPEC_K = int(os.environ.get("BENCH_SPEC_K", "4"))
SPEC_DRAFT = os.environ.get("BENCH_SPEC_DRAFT", "self")
# Mesh phase: the same greedy paged + chunked closed wave run twice at
# EQUAL engine config — an explicit single chip (tp=1) vs a BENCH_MESH_TP-way
# graftmesh tensor-parallel group (servers/mesh_engine.py exact-TP
# sharding) — so the bench line carries per-leg req/s and decode tok/s,
# the bit-exact parity assert (exact-TP shards only output dims, so the
# mesh leg must reproduce the single-chip stream token for token), and
# the per-device HBM deltas the sharding bought (weights / KV bytes per
# chip from the HBM ledger). On CPU smoke rigs run under
# XLA_FLAGS=--xla_force_host_platform_device_count=8; tp speedup on
# fake devices is NOT meaningful (one host executes all shards) — the
# phase's CPU value is the parity + per-device-HBM record
# (tools/bench_compare.py gates req/s no-regression and per-device KV
# bytes lower-is-better on real meshes). Recorded in detail.mesh.
MESH_PHASE = os.environ.get("BENCH_MESH", "0") == "1"
MESH_TP = int(os.environ.get("BENCH_MESH_TP", "2"))
# Heal phase: the same greedy closed wave run twice at equal hardware —
# clean, then under seeded CHAOS dispatch faults with graftheal
# supervised recovery on — so the bench line prices what a fault storm
# costs THROUGH the healer. Resurrection replays committed tokens with
# deterministic per-position sampling keys, so every stream the faulted
# leg completes must be bit-identical to the clean leg's (the assert IS
# the benchmark — a healer that resumes on the wrong token must fail
# here, not ship a number). tools/bench_compare.py gates
# goodput_retained_frac higher-is-better and user_visible_errors
# lower-exact. Recorded in detail.heal.
HEAL_PHASE = os.environ.get("BENCH_HEAL", "0") == "1"
HEAL_FAULT_P = float(os.environ.get("BENCH_HEAL_FAULT", "0.05"))
PAGED_DENSE_SLOTS = int(os.environ.get("BENCH_PAGED_DENSE_SLOTS", "4"))
PAGED_KV_BLOCK = int(os.environ.get("BENCH_PAGED_KV_BLOCK", "16"))
BASELINE_REQ_S_PER_CHIP = 125.0  # 1000 req/s north star / 8 chips


SLO_TTFT_MS = 100.0  # BASELINE.md north star: p50 TTFT < 100 ms
# SLO search defaults ON for the bench-1b proxy (where the TTFT claim
# is meaningful per-chip) and OFF for the 8B single-chip run — there
# the search costs ~15 min; the 8B line already reports saturation
# p50/p99 TTFT.
SLO_ENABLED = os.environ.get(
    "BENCH_SLO", "1" if PRESET == "bench-1b" else "0"
) == "1"
# The SLO search runs the SAME engine config as the throughput leg:
# occupancy-adaptive chunking (EngineConfig.adaptive_chunk) picks short
# chunks in the under-capacity latency regime and the full decode_chunk
# at saturation, so one engine holds both claims — the old
# chunk-4-for-SLO mode switch is gone. BENCH_SLO_CHUNK pins a fixed
# chunk for A/B comparison.
SLO_CHUNK = int(os.environ.get("BENCH_SLO_CHUNK", 0))  # 0 = adaptive

# The 8B headline run ALSO records the bench-1b deployment proxy
# (throughput + SLO search) as a trailing phase — one driver invocation
# then captures both the honest single-chip point and the
# TP8-deployment-shaped claim. BENCH_SECOND_PRESET= (empty) disables.
SECOND_PRESET = os.environ.get(
    "BENCH_SECOND_PRESET", "bench-1b" if PRESET == "llama3-8b" else ""
)
SECOND_SLOTS = int(os.environ.get("BENCH_SECOND_SLOTS", 0)) or 160
SECOND_SLO = os.environ.get("BENCH_SECOND_SLO", "1") == "1"


def _measure_slo(params, cfg, sp, slots: int = 0) -> dict:
    """Max sustained req/s with p50 TTFT under SLO_TTFT_MS.

    Open-loop Poisson arrivals (throughput-latency curves from closed
    loops lie: a closed loop self-throttles exactly when the server
    slows). Small decode chunks bound the admission wait: a request can
    only be admitted at a chunk boundary, so chunk=64 (456 ms of device
    work) can never hold a 100 ms TTFT — the scheduler trades ~10%
    throughput for boundary frequency here. Ladder-then-refine search."""
    import time as _time

    import numpy as np

    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    # Default (SLO_CHUNK=0): the throughput config itself — adaptive
    # chunking must hold the SLO without a mode switch.
    ecfg = EngineConfig(
        max_slots=slots or SLOTS,
        max_seq_len=PROMPT_LEN + NEW_TOKENS + 1,
        prompt_buckets=(PROMPT_LEN,),
        max_admit=8,
        decode_chunk=SLO_CHUNK or DECODE_CHUNK,
        adaptive_chunk=not SLO_CHUNK,
    )
    engine = InferenceEngine(params, cfg, ecfg)
    engine.warmup()
    engine.start()
    rng = np.random.default_rng(7)
    prompt = rng.integers(3, cfg.vocab_size, size=(PROMPT_LEN,)).tolist()

    def one_ttft(seed: int) -> float:
        q = engine.submit(prompt, sp(seed))
        first = q.get(timeout=120)
        ttft = first.get("ttft_ms", float("inf")) if first else float("inf")
        while first is not None:
            first = q.get()
        return ttft

    # Warm the dispatch path (first request eats lazy host-side setup),
    # then measure the UNLOADED TTFT floor. Where the floor itself
    # exceeds the 100 ms target the search runs against an effective
    # target of 1.5x the floor, so the result still says how much LOAD
    # the engine absorbs before TTFT degrades; both numbers are
    # reported.
    for i in range(3):
        one_ttft(900 + i)
    floor = float(np.median([one_ttft(910 + i) for i in range(5)]))
    target = max(SLO_TTFT_MS, 1.5 * floor)
    # The scheduler pays one host<->device round trip per boundary, and
    # under sustained load a request crosses ~2 of them before its first
    # token: if no rate holds the target, slo_req_s reports 0 and the
    # floor and the fixed-low-rate p50 below say what the rig allows.

    def run_rate(rate: float, duration: float = 10.0) -> float:
        """Returns p50 TTFT (ms) at `rate` req/s; inf if overloaded."""
        arrivals = []
        t = 0.0
        while t < duration:
            t += rng.exponential(1.0 / rate)
            arrivals.append(t)
        t0 = _time.perf_counter()
        queues = []
        for i, at in enumerate(arrivals):
            now = _time.perf_counter() - t0
            if at > now:
                _time.sleep(at - now)
            queues.append(
                engine.submit(prompt, sp(1000 + i))
            )
        ttfts = []
        overload = False
        deadline = _time.perf_counter() + 60.0
        for q in queues:
            first = None
            while first is None:
                try:
                    first = q.get(
                        timeout=max(0.1, deadline - _time.perf_counter())
                    )
                except Exception:
                    overload = True  # keep draining: the NEXT rate must
                    break            # start from an empty engine
            if first is not None and "ttft_ms" in first:
                ttfts.append(first["ttft_ms"])
            while first is not None:  # drain the remaining tokens
                item = q.get()
                if item is None:
                    break
        # Quiesce: the next rate must start from an empty engine, so wait
        # until every submitted request (drained or not) completed.
        while True:
            st = engine.stats.snapshot()
            if st["completed"] >= st["requests"]:
                break
            _time.sleep(0.2)
        if overload:
            return float("inf")
        # Steady-state: drop the warm-in fifth.
        ttfts = ttfts[len(ttfts) // 5:]
        return float(np.percentile(ttfts, 50)) if ttfts else float("inf")

    best = 0.0
    best_p50 = float("inf")
    rate = 5.0
    step_up = 1.6
    # Exponential ladder up, then one bisection refinement pass. A rung
    # failure gets ONE retry before it ends the climb: one latency spike
    # poisons a whole 10 s window, and a spurious first-rung failure
    # would otherwise bisect down to a nonsense near-zero answer.
    while rate <= 4.0 * BASELINE_REQ_S_PER_CHIP:
        p50 = run_rate(rate)
        if not p50 < target:
            p50 = run_rate(rate)
        if p50 < target:
            best, best_p50 = rate, p50
            rate *= step_up
        else:
            break
    lo, hi = best, rate
    for _ in range(3):
        if best == 0.0:
            break  # nothing held: report 0 honestly, don't bisect air
        mid = (lo + hi) / 2.0
        if mid <= best:
            break
        p50 = run_rate(mid)
        if p50 < target:
            best, best_p50, lo = mid, p50, mid
        else:
            hi = mid
    p50_low = run_rate(10.0, duration=8.0)
    # Deadline-attainment wave (closed loop, 16 requests): stamp a
    # generous deadline_ms on each so the run exercises the engine's SLO
    # accounting — the bench line then carries goodput and deadline-margin
    # stats from EngineStats, not just client-side TTFT percentiles.
    import dataclasses as _dc
    ddl_ms = max(int(10 * target), 2000)
    for q in [
        engine.submit(prompt, _dc.replace(sp(2000 + i), deadline_ms=ddl_ms))
        for i in range(16)
    ]:
        while q.get() is not None:
            pass
    st = engine.stats.snapshot()
    engine.stop()
    import math

    return {
        "p50_ttft_at_10rps_ms": (
            round(p50_low, 1) if math.isfinite(p50_low) else None
        ),
        "slo_req_s": round(best, 1),
        # None, not inf: json.dumps would emit non-standard `Infinity`
        # and break strict consumers of the bench line.
        "slo_p50_ttft_ms": (
            round(best_p50, 1) if math.isfinite(best_p50) else None
        ),
        "slo_target_ms": SLO_TTFT_MS,
        "slo_target_effective_ms": round(target, 1),
        "slo_unloaded_floor_ms": round(floor, 1),
        "slo_decode_chunk": SLO_CHUNK or f"adaptive<={DECODE_CHUNK}",
        # Engine-side SLO attainment from the deadline-stamped wave.
        "slo_goodput": round(st["goodput"], 4),
        "slo_deadline_met": st["deadline_met_total"],
        "slo_deadline_missed": st["deadline_missed_total"],
        "slo_margin_mean_ms": round(
            st["deadline_margin_sum_ms"]
            / max(st["deadline_met_total"] + st["deadline_missed_total"], 1),
            1,
        ),
    }


def _measure_pilot(params, cfg, sp) -> dict:
    """BENCH_PILOT phase: the same mixed-deadline closed wave through
    the same chunked-prefill engine config, once with PILOT=1 and once
    with the pilot off. The wave interleaves loose-deadline, tight-
    deadline and no-deadline requests (tight AFTER loose within each
    triple, so FIFO order carries real EDF inversions), and the tight
    TTL is calibrated off an unloaded probe request so the wave is
    achievable-but-pressured on any rig. Reports per-leg slo_goodput /
    deadline split, and for the pilot leg the decision count, final
    knob values and EDF counters from /debug/pilot's snapshot."""
    import dataclasses as _dc

    import numpy as np

    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    slots = min(SLOTS, 32)
    nreq = 3 * slots
    rng = np.random.default_rng(11)
    prompt = rng.integers(3, cfg.vocab_size, size=(PROMPT_LEN,)).tolist()

    def leg(pilot: bool) -> dict:
        prev = os.environ.get("PILOT")
        os.environ["PILOT"] = "1" if pilot else "0"
        try:
            engine = InferenceEngine(params, cfg, EngineConfig(
                max_slots=slots,
                max_seq_len=PROMPT_LEN + NEW_TOKENS + 1,
                prompt_buckets=(PROMPT_LEN,),
                max_admit=8,
                decode_chunk=DECODE_CHUNK,
                chunked_prefill=True,
                prefill_chunk=64,
            ))
        finally:
            if prev is None:
                os.environ.pop("PILOT", None)
            else:
                os.environ["PILOT"] = prev
        engine.warmup()
        engine.start()
        # Unloaded probe: calibrates the tight TTL to the rig instead
        # of hard-coding a wall time.
        t0 = time.perf_counter()
        q = engine.submit(prompt, sp(500))
        while q.get(timeout=300) is not None:
            pass
        t_one_ms = 1000.0 * (time.perf_counter() - t0)
        ddl_ms = max(2000, int(4.0 * t_one_ms * nreq / slots))
        queues = []
        for i in range(nreq):
            if i % 3 == 0:
                p = sp(3000 + i)  # no deadline: the EDF aging path
            elif i % 3 == 1:
                p = _dc.replace(sp(3000 + i), deadline_ms=4 * ddl_ms)
            else:  # tight submitted after loose: an EDF inversion
                p = _dc.replace(sp(3000 + i), deadline_ms=ddl_ms)
            queues.append(engine.submit(prompt, p))
        for q in queues:
            try:
                while q.get(timeout=300) is not None:
                    pass
            except Exception:
                pass  # expired requests end via the error item
        engine.drain(timeout=120)
        st = engine.stats.snapshot()
        psnap = engine.debug_pilot()
        engine.stop()
        out = {
            "slo_goodput": round(st["goodput"], 4),
            "deadline_met": st["deadline_met_total"],
            "deadline_missed": st["deadline_missed_total"],
            "deadline_expired": st["deadline_expired_total"],
            # Calibration constant, not a metric — named without "ms"
            # so bench_compare's latency substring gate skips it.
            "tight_deadline": ddl_ms,
        }
        if psnap is not None:
            out["pilot_decisions"] = psnap["decisions_total"]
            out["pilot_decisions_by_knob"] = psnap["decisions_by_knob"]
            out["final_knobs"] = psnap["knobs"]
            out["pilot_edf_inversions"] = psnap["edf"]["inversions"]
            out["pilot_expired_at_pop"] = psnap["edf"]["expired_at_pop"]
        return out

    return {"on": leg(True), "off": leg(False)}


def _build(preset: str):
    """(params, cfg) for one preset under the env dtype knobs."""
    import dataclasses

    import jax

    from seldon_tpu.models import get_config, init_params

    cfg = get_config(preset)
    if KV_DTYPE != "bf16":
        cfg = dataclasses.replace(cfg, kv_cache_dtype=KV_DTYPE)
    if ATTN:
        cfg = dataclasses.replace(cfg, attn_impl=ATTN)
    # Unconditional: BENCH_WEIGHTS must also be able to REVERT a preset
    # that ships int8.
    cfg = dataclasses.replace(cfg, weight_dtype=WEIGHTS)
    if WEIGHTS == "int8":
        cfg = dataclasses.replace(cfg, act_dtype=ACT)
    if cfg.weight_dtype == "int8":
        # Memory-aware init: generates straight into int8 buffers, so
        # llama3-8b geometry (16 GB bf16) inits on one 16 GB chip.
        from seldon_tpu.models.quantize import init_params_int8

        params = init_params_int8(cfg, jax.random.key(0))
    else:
        params = init_params(cfg, jax.random.key(0))
    return params, cfg


def _compile_counts(engine) -> dict:
    """Compile-ledger counters for a phase detail dict (COMPILE_LEDGER=1
    is the bench default): variant count, live retraces, cumulative
    compile seconds — so BENCH_*.json runs compare on compile behavior,
    not just throughput, and tools/bench_compare.py can gate
    live_retraces strictly. Empty when the ledger is off."""
    snap = engine.debug_compile()
    if snap is None:
        return {}
    return {
        "compile_variants": snap["dispatched_variants"],
        "live_retraces": snap["live_retrace_count"],
        "compile_s_total": round(snap["compile_s_total"], 3),
    }


def _sched_counts(engine, req_s: float = 0.0) -> dict:
    """Sched-ledger waste report for a phase detail dict (SCHED_LEDGER=1
    is the bench default): padding_waste_frac, the single goodput_gap
    scalar (pad + fragmentation share of offered capacity — lower is
    better, gated by tools/bench_compare.py), its per-cause breakdown,
    and — when `req_s` is supplied — the roofline headroom report:
    what the measured waste costs. With no bucket or group padding the
    ceiling is req_s / (1 - pad_frac); freeing the dense slab's HBM
    would avoid the pool stalls and preemptions this run actually
    paid. Empty when the ledger is off."""
    snap = engine.debug_sched()
    if snap is None:
        return {}
    gap = snap["goodput_gap"]
    pad_frac = snap["padding_waste_frac"]
    out = {
        "padding_waste_frac": round(pad_frac, 4),
        "goodput_gap": round(
            gap["bucket_pad_frac"] + gap["group_pad_frac"]
            + gap["frag_frac"] + gap.get("spec_rejected_frac", 0.0), 4
        ),
        "goodput_gap_breakdown": {k: round(v, 4) for k, v in gap.items()},
        "sched_conservation_breaches": snap["conservation"]["breaches"],
    }
    spec = snap.get("spec", {})
    if spec.get("verify_waves"):
        out["spec_acceptance_rate"] = round(spec["acceptance_rate"], 4)
        out["spec_drafted_tokens"] = spec["drafted_tokens"]
        out["spec_accepted_tokens"] = spec["accepted_tokens"]
    if req_s > 0.0:
        out["waste_roofline"] = {
            "padding_free_req_s": round(
                req_s / (1.0 - pad_frac) if pad_frac < 1.0 else req_s, 2
            ),
            "slab_deletion_stalls": snap["pool_stall_events"],
            "slab_deletion_preempted_tokens": snap["preempted_tokens"],
        }
    return out


def _roof_counts(engine, req_s: float = 0.0, prompt_len: int = 0,
                 max_new: int = 0) -> dict:
    """Roofline section for a phase detail dict (ROOF_LEDGER=1 is the
    bench default): achieved mfu/mbu against the platform peaks
    (higher is better, gated by tools/bench_compare.py), the host share
    of boundary wall time (lower is better — a rising host_frac says
    the scheduler, not the device, is the bottleneck), and — when the
    phase supplies its workload shape — the measured-over-predicted
    req/s ratio that reconciles _sched_counts' waste_roofline with
    hardware efficiency. Empty when the ledger is off."""
    snap = engine.debug_roof()
    if snap is None:
        return {}
    out = {
        "mfu": snap["totals"]["mfu"],
        "mbu": snap["totals"]["mbu"],
        "host_frac": snap["host_frac"],
        "roof_conservation_breaches": snap["conservation"]["breaches"],
    }
    if req_s > 0.0 and prompt_len > 0:
        est_ms = engine.roof_predict_ms(prompt_len, max_new)
        if est_ms and est_ms > 0.0:
            out["roof_predicted_req_s"] = round(1000.0 / est_ms, 2)
            out["predicted_vs_measured_req_s"] = round(
                req_s * est_ms / 1000.0, 4
            )
    return out


def _measure_throughput(params, cfg, slots: int, n_req: int, chunk: int,
                        admit: int = 8):
    """Saturated closed-loop wave -> (req_s, detail dict, sp factory)."""
    import jax
    import numpy as np

    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    ecfg = EngineConfig(
        max_slots=slots,
        # Tight cache window: prompt + completion + 1 slack slot. Decode
        # reads the whole window every step, so slack is pure HBM tax.
        max_seq_len=PROMPT_LEN + NEW_TOKENS + 1,
        prompt_buckets=(PROMPT_LEN,),
        max_admit=admit,
        decode_chunk=chunk,
    )
    engine = InferenceEngine(params, cfg, ecfg)
    engine.warmup()
    engine.start()

    rng = np.random.default_rng(0)
    prompts = rng.integers(3, cfg.vocab_size, size=(n_req, PROMPT_LEN))

    def sp(i: int) -> SamplingParams:
        # top_k=0/top_p=1: sample the full vocab — near-uniform logits on a
        # random-init model make premature EOS negligible (~1/V per step).
        return SamplingParams(
            temperature=0.7,
            top_k=0,
            top_p=1.0,
            max_new_tokens=NEW_TOKENS,
            seed=i,
        )

    # Settle run: a small closed-loop wave through the scheduler.
    for q in [engine.submit(prompts[i].tolist(), sp(i)) for i in range(8)]:
        while q.get() is not None:
            pass

    t0 = time.perf_counter()
    queues = [engine.submit(prompts[i].tolist(), sp(i)) for i in range(n_req)]
    total_toks = 0
    ttfts = []
    for q in queues:
        while True:
            item = q.get()
            if item is None:
                break
            if "error" in item:
                raise RuntimeError(item["error"])
            total_toks += len(item["tokens"])
            if "ttft_ms" in item:
                ttfts.append(item["ttft_ms"])
    dt = time.perf_counter() - t0
    comp = _compile_counts(engine)
    sched = _sched_counts(engine, req_s=n_req / dt)
    roof = _roof_counts(engine, req_s=n_req / dt,
                        prompt_len=PROMPT_LEN, max_new=NEW_TOKENS)
    engine.stop()

    detail = {
        "decode_tokens_per_s": round(total_toks / dt, 1),
        "total_tokens": total_toks,
        "p50_ttft_ms": round(float(np.percentile(ttfts, 50)), 1),
        "p99_ttft_ms": round(float(np.percentile(ttfts, 99)), 1),
        "device": str(jax.devices()[0]),
        **comp,
        **sched,
        **roof,
    }
    return n_req / dt, detail, sp


def _measure_prefix(params, cfg) -> dict:
    """Shared-prefix workload against a prefix_cache engine: hit rate,
    tokens saved, and cold-vs-warm admission latency (TTFT).

    Half the prompt is a shared block-aligned "system prompt"; requests
    run SEQUENTIALLY so TTFT isolates admission cost (prefill + scatter)
    from queueing. Cold rows use disjoint prefixes (every admission
    prefills the full prompt); warm rows share the prefix, so admission
    prefills only the suffix off the trie's retained KV."""
    import numpy as np

    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    shared = (PROMPT_LEN // 2 // PREFIX_BLOCK) * PREFIX_BLOCK
    ecfg = EngineConfig(
        max_slots=8,
        max_seq_len=PROMPT_LEN + 16 + 1,
        # Two buckets: full prompts (cold) and the uncached suffix (warm).
        prompt_buckets=(PROMPT_LEN - shared, PROMPT_LEN),
        max_admit=4,
        decode_chunk=DECODE_CHUNK,
        prefix_cache=True,
        prefix_block=PREFIX_BLOCK,
    )
    engine = InferenceEngine(params, cfg, ecfg)
    engine.warmup()
    engine.start()
    rng = np.random.default_rng(11)

    def sp(i: int) -> SamplingParams:
        return SamplingParams(temperature=0.7, max_new_tokens=8, seed=i)

    def one_ttft(prompt, i) -> float:
        q = engine.submit(prompt, sp(i))
        first = q.get(timeout=300)
        ttft = first.get("ttft_ms", float("inf")) if first else float("inf")
        while first is not None:
            first = q.get()
        return ttft

    def prompt_row(prefix_seed: int):
        r = np.random.default_rng(prefix_seed)
        pre = r.integers(3, cfg.vocab_size, size=(shared,))
        suf = rng.integers(3, cfg.vocab_size, size=(PROMPT_LEN - shared,))
        return np.concatenate([pre, suf]).tolist()

    # Dispatch warm-in (compiles are pre-paid by warmup; this pays the
    # lazy host-side setup exactly like _measure_slo does).
    for i in range(3):
        one_ttft(prompt_row(10_000 + i), 900 + i)

    cold = [one_ttft(prompt_row(20_000 + i), i)
            for i in range(PREFIX_NREQ)]
    s0 = engine.stats.snapshot()
    one_ttft(prompt_row(7), 500)  # seed the shared prefix into the trie
    warm = [one_ttft(prompt_row(7), 600 + i)
            for i in range(PREFIX_NREQ)]
    s1 = engine.stats.snapshot()
    engine.stop()

    hits = s1["prefix_hits"] - s0["prefix_hits"]
    cold_p50 = float(np.percentile(cold, 50))
    warm_p50 = float(np.percentile(warm, 50))
    return {
        "prefix_block": PREFIX_BLOCK,
        "shared_prefix_tokens": shared,
        "n_req": PREFIX_NREQ,
        "hit_rate": round(hits / (PREFIX_NREQ + 1), 3),
        "tokens_saved": int(s1["prefix_tokens_saved"]
                            - s0["prefix_tokens_saved"]),
        "evictions": int(s1["prefix_evictions"]),
        "cold_p50_ttft_ms": round(cold_p50, 1),
        "warm_p50_ttft_ms": round(warm_p50, 1),
        "warm_speedup": round(cold_p50 / warm_p50, 2) if warm_p50 else None,
    }


def _measure_chunked(params, cfg) -> dict:
    """Stall-free scheduling phase: CHUNKED_STREAMS short-prompt decode
    streams run steadily while ONE long prompt (CHUNKED_LONG_X x
    PROMPT_LEN tokens) arrives mid-decode. Client-side burst gaps after
    the interloper's arrival are the tail-ITL signal: uninterleaved, the
    whole long prefill runs before the next decode chunk (one gap spike
    ~ full prefill time per stream); chunked, at most
    dispatch_token_budget prefill tokens separate consecutive decode
    chunks, so the spike is bounded by one chunk. Same model, same
    traffic, chunked_prefill off vs on."""
    import queue as _q  # noqa: F401 — engine queues drive the streams
    import threading

    import numpy as np

    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    long_len = CHUNKED_LONG_X * PROMPT_LEN
    new_toks = max(32, NEW_TOKENS)
    rng = np.random.default_rng(17)
    shorts = [
        rng.integers(3, cfg.vocab_size, size=(PROMPT_LEN,)).tolist()
        for _ in range(CHUNKED_STREAMS)
    ]
    long_prompt = rng.integers(3, cfg.vocab_size, size=(long_len,)).tolist()

    def run(chunked: bool) -> float:
        ecfg = EngineConfig(
            max_slots=CHUNKED_STREAMS + 2,
            max_seq_len=long_len + new_toks + 1,
            prompt_buckets=(PROMPT_LEN, long_len),
            max_admit=4,
            decode_chunk=4,
            adaptive_chunk=False,  # fixed cadence isolates the stall
            chunked_prefill=chunked,
            prefill_chunk=PROMPT_LEN,
            dispatch_token_budget=PROMPT_LEN,
        )
        engine = InferenceEngine(params, cfg, ecfg)
        engine.warmup()
        engine.start()
        gaps: list = []  # (wall_time, gap_s) per burst, short streams
        glock = threading.Lock()
        first_burst = threading.Barrier(CHUNKED_STREAMS + 1)

        def consume(q):
            last = None
            waited = False
            while True:
                item = q.get()
                if item is None:
                    break
                if "error" in item:
                    raise RuntimeError(item["error"])
                now = time.perf_counter()
                if last is not None and item["tokens"]:
                    with glock:
                        gaps.append((now, now - last))
                last = now
                if not waited:
                    waited = True
                    first_burst.wait(timeout=300)

        threads = []
        for i, p in enumerate(shorts):
            q = engine.submit(
                p, SamplingParams(temperature=0.0, max_new_tokens=new_toks,
                                  seed=i)
            )
            t = threading.Thread(target=consume, args=(q,), daemon=True)
            t.start()
            threads.append(t)
        # Every stream has its first token: all are mid-decode when the
        # interloper lands — its prefill cost hits live streams only.
        first_burst.wait(timeout=300)
        t_long = time.perf_counter()
        lq = engine.submit(
            long_prompt,
            SamplingParams(temperature=0.0, max_new_tokens=8, seed=99),
        )
        for t in threads:
            t.join(timeout=300)
        while lq.get(timeout=300) is not None:
            pass
        snap = engine.stats.snapshot()
        comp = _compile_counts(engine)
        sched = _sched_counts(engine)
        roof = _roof_counts(engine)
        engine.stop()
        tail = [g for ts, g in gaps if ts >= t_long]
        run.last_snap = snap  # engine-side counters for the report
        run.last_comp = comp
        run.last_sched = sched
        run.last_roof = roof
        return 1000.0 * float(np.percentile(tail or [0.0], 99))

    base_p99 = run(chunked=False)
    chunked_p99 = run(chunked=True)
    snap = run.last_snap
    return {
        **run.last_comp,
        **run.last_sched,
        **run.last_roof,
        "streams": CHUNKED_STREAMS,
        "long_prompt_tokens": long_len,
        "prefill_chunk": PROMPT_LEN,
        "dispatch_token_budget": PROMPT_LEN,
        "baseline_p99_itl_ms": round(base_p99, 1),
        "chunked_p99_itl_ms": round(chunked_p99, 1),
        "p99_itl_speedup": (
            round(base_p99 / chunked_p99, 2) if chunked_p99 else None
        ),
        "prefill_chunks": int(snap["prefill_chunks"]),
        "budget_utilization": round(float(snap["budget_utilization"]), 3),
        "engine_itl_p99_ms": float(snap["itl_p99_ms"]),
    }


def _measure_paged(params, cfg) -> dict:
    """Fixed-KV-HBM concurrency phase: how many short-decode streams run
    at once on the SAME KV budget, dense slab vs paged pool.

    The dense engine reserves max_seq_len tokens per slot the moment a
    request is admitted, so its concurrency is slot-capped even when
    every stream writes a fraction of the window. The paged engine gets
    a pool holding exactly the dense slab's tokens (dense_slots x
    max_seq_len), carved into kv_block blocks, and 4x the slot count:
    admission stops at POOL exhaustion, not slot exhaustion, so short
    streams pack ~window/stream_tokens times denser. A warm leg on the
    paged engine then readmits one shared prompt and records zero-copy
    admissions (block refcounts, no KV copies) off the block trie."""
    import threading

    import numpy as np

    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    bs = PAGED_KV_BLOCK
    prompt_len = 2 * bs  # 2 blocks: warm readmission shares block 1 in full
    new_toks = min(NEW_TOKENS, 16)
    blocks_per_stream = -(-(prompt_len + new_toks + 1) // bs)
    # Window = 4x a short stream's footprint: the dense slab reserves it
    # whole per slot; the paged pool only hands out what streams write.
    smax = 4 * blocks_per_stream * bs
    pool_blocks = PAGED_DENSE_SLOTS * (smax // bs)  # dense slab's budget
    n_streams = min(4 * PAGED_DENSE_SLOTS, pool_blocks // blocks_per_stream)
    rng = np.random.default_rng(23)
    prompts = [rng.integers(3, cfg.vocab_size, size=(prompt_len,)).tolist()
               for _ in range(n_streams)]

    def run(paged: bool):
        pkw = dict(paged_kv=True, kv_block=bs,
                   kv_pool_blocks=pool_blocks + 1,  # +1: reserved trash
                   prefix_cache=True, prefix_block=bs) if paged else {}
        ecfg = EngineConfig(
            max_slots=4 * PAGED_DENSE_SLOTS if paged else PAGED_DENSE_SLOTS,
            max_seq_len=smax,
            prompt_buckets=(prompt_len,),
            max_admit=4,
            decode_chunk=4,
            **pkw,
        )
        engine = InferenceEngine(params, cfg, ecfg)
        engine.warmup()
        engine.start()
        peak = [0]
        done = threading.Event()

        def watch():  # occupancy gauge: live (unfinished) slots
            while not done.is_set():
                n = sum(1 for r in engine.live_requests()
                        if not r.finished)
                peak[0] = max(peak[0], n)
                time.sleep(0.001)

        w = threading.Thread(target=watch, daemon=True)
        w.start()
        t0 = time.perf_counter()
        qs = [engine.submit(p, SamplingParams(temperature=0.0,
                                              max_new_tokens=new_toks,
                                              seed=i))
              for i, p in enumerate(prompts)]
        for q in qs:
            while q.get(timeout=300) is not None:
                pass
        makespan = time.perf_counter() - t0
        done.set()
        w.join(timeout=5)
        return engine, peak[0], makespan

    dense_eng, dense_peak, dense_s = run(paged=False)
    dense_eng.stop()
    paged_eng, paged_peak, paged_s = run(paged=True)

    # Warm leg: seed one shared prompt into the block trie, then readmit
    # it — each warm admission refcounts the retained full blocks
    # instead of copying KV (the dense prefix cache's seed-copy path).
    shared = prompts[0]

    def drain(q):
        while q.get(timeout=300) is not None:
            pass

    drain(paged_eng.submit(shared, SamplingParams(temperature=0.0,
                                                  max_new_tokens=new_toks)))
    s0 = paged_eng.stats.snapshot()
    for i in range(4):
        drain(paged_eng.submit(shared, SamplingParams(
            temperature=0.0, max_new_tokens=new_toks, seed=100 + i)))
    s1 = paged_eng.stats.snapshot()
    comp = _compile_counts(paged_eng)
    sched = _sched_counts(paged_eng)
    roof = _roof_counts(paged_eng)
    paged_eng.stop()
    return {
        **comp,
        **sched,
        **roof,
        "kv_block": bs,
        "kv_pool_blocks": pool_blocks + 1,
        "dense_slots": PAGED_DENSE_SLOTS,
        "paged_slots": 4 * PAGED_DENSE_SLOTS,
        "window_tokens": smax,
        "stream_tokens": prompt_len + new_toks,
        "n_streams": n_streams,
        "dense_peak_concurrency": dense_peak,
        "paged_peak_concurrency": paged_peak,
        "concurrency_x": (round(paged_peak / dense_peak, 2)
                          if dense_peak else None),
        "dense_makespan_s": round(dense_s, 3),
        "paged_makespan_s": round(paged_s, 3),
        "zero_copy_admissions": int(s1["zero_copy_admissions"]
                                    - s0["zero_copy_admissions"]),
        "cow_copies": int(s1["cow_copies"] - s0["cow_copies"]),
        "prefix_seed_copies": int(s1["prefix_seed_copies"]),
        "pool_stalls": int(s1["pool_stalls"]),
    }


def _measure_spec(params, cfg) -> dict:
    """BENCH_SPEC phase: one greedy closed wave run twice at equal
    hardware — plain paged decode vs graftspec speculative decoding on
    the same substrate, same pool, same slots. Verification is
    exact-match against deterministic per-row sampling, so the spec leg
    must reproduce the plain leg's stream bit for bit; the phase
    asserts that, then prices what speculation bought: per-leg decode
    tok/s, the spec leg's dispatches/token (< 1.0 means verify waves
    genuinely compressed the decode loop) and windowed acceptance rate
    from the sched ledger's spec books."""
    import numpy as np

    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    bs = 16          # KV block
    new_toks = min(NEW_TOKENS, 16)
    slots = 8
    lengths = [24, 48, 96, 16]
    smax = 128  # max prompt 96 + 16 new + slack, block-aligned
    n_req = 3 * slots
    pool_blocks = slots * (smax // bs) + 1  # full residency + trash
    rng = np.random.default_rng(31)
    prompts = [
        rng.integers(3, cfg.vocab_size,
                     size=(lengths[i % len(lengths)],)).tolist()
        for i in range(n_req)
    ]

    if SPEC_DRAFT == "self":
        draft = (params, cfg)          # acceptance upper bound
    elif SPEC_DRAFT:
        draft = _build(SPEC_DRAFT)     # resident draft model
    else:
        draft = None                   # host n-gram drafter

    def leg(spec: bool):
        ecfg = EngineConfig(
            max_slots=slots,
            max_seq_len=smax,
            prompt_buckets=(32, 128),
            max_admit=4,
            decode_chunk=4,
            paged_kv=True, kv_block=bs, kv_pool_blocks=pool_blocks,
            spec_decode=spec, spec_k=SPEC_K if spec else 4,
        )
        engine = InferenceEngine(params, cfg, ecfg,
                                 draft=draft if spec else None)
        engine.warmup()
        engine.start()
        t0 = time.perf_counter()
        qs = [engine.submit(p, SamplingParams(
                  temperature=0.0, top_k=0, top_p=1.0,
                  max_new_tokens=new_toks, seed=i))
              for i, p in enumerate(prompts)]
        streams = []
        for q in qs:
            toks = []
            while True:
                item = q.get(timeout=300)
                if item is None:
                    break
                if "error" in item:
                    raise RuntimeError(item["error"])
                toks.extend(item.get("tokens", []))
            streams.append(toks)
        dt = time.perf_counter() - t0
        stats = engine.stats.snapshot()
        tok_s = stats["tokens_out"] / dt if dt else 0.0
        out = {
            "req_per_s": round(n_req / dt, 3),
            "decode_tok_s": round(tok_s, 1),
            "makespan_s": round(dt, 3),
            "dispatch_per_token": round(
                stats["decode_dispatches"] / max(1, stats["tokens_out"]), 4
            ),
            **_compile_counts(engine),
            **_sched_counts(engine),
            **_roof_counts(engine),
        }
        engine.stop()
        return out, streams

    plain, want = leg(spec=False)
    spec_leg, got = leg(spec=True)
    if got != want:  # the whole contract: speculation changes nothing
        raise RuntimeError("spec leg diverged from plain greedy stream")
    return {
        "k": SPEC_K,
        "drafter": SPEC_DRAFT or "ngram",
        "plain": plain,
        "spec": spec_leg,
        "bit_identical": True,
        "speedup": (round(spec_leg["decode_tok_s"] / plain["decode_tok_s"],
                          3) if plain["decode_tok_s"] else None),
        "acceptance_rate": spec_leg.get("spec_acceptance_rate"),
    }


def _measure_mesh(params, cfg) -> dict:
    """BENCH_MESH phase: the same greedy paged + chunked closed wave run
    twice at EQUAL engine config — an explicit single chip vs a MESH_TP-way
    graftmesh tensor-parallel group on the same substrate, same pool,
    same slots. Exact-TP shards only output dims (models/tp_sharding),
    so the mesh leg must reproduce the single-chip stream bit for bit;
    the phase asserts that, then prices what the mesh bought: per-leg
    req/s and decode tok/s, and the per-device HBM deltas (weights /
    KV bytes per chip) that are the actual reason to shard — a model
    that doesn't fit one chip fits tp chips."""
    import numpy as np

    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine
    from seldon_tpu.servers.mesh_engine import MeshEngine, device_budget

    tp = MESH_TP
    budget = device_budget()
    if budget < tp:
        raise RuntimeError(
            f"BENCH_MESH_TP={tp} but only {budget} devices visible "
            "(on CPU rigs set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=8)")
    # Per-device accounting is half the phase's point.
    os.environ.setdefault("HBM_LEDGER", "1")

    bs = 16          # KV block
    new_toks = min(NEW_TOKENS, 16)
    slots = 8
    lengths = [24, 48, 96, 16]
    smax = 128  # max prompt 96 + 16 new + slack, block-aligned
    n_req = 3 * slots
    pool_blocks = slots * (smax // bs) + 1  # full residency + trash
    rng = np.random.default_rng(47)
    prompts = [
        rng.integers(3, cfg.vocab_size,
                     size=(lengths[i % len(lengths)],)).tolist()
        for i in range(n_req)
    ]

    def leg(leg_tp: int):
        ecfg = EngineConfig(
            max_slots=slots,
            max_seq_len=smax,
            prompt_buckets=(32, 128),
            max_admit=4,
            decode_chunk=4,
            paged_kv=True, kv_block=bs, kv_pool_blocks=pool_blocks,
            chunked_prefill=True, prefill_chunk=32, prefix_block=bs,
        )
        if leg_tp > 1:
            engine = MeshEngine(params, cfg, ecfg, tp=leg_tp)
        else:
            engine = InferenceEngine(params, cfg, ecfg)
        engine.warmup()
        engine.start()
        t0 = time.perf_counter()
        qs = [engine.submit(p, SamplingParams(
                  temperature=0.0, top_k=0, top_p=1.0,
                  max_new_tokens=new_toks, seed=i))
              for i, p in enumerate(prompts)]
        streams = []
        for q in qs:
            toks = []
            while True:
                item = q.get(timeout=300)
                if item is None:
                    break
                if "error" in item:
                    raise RuntimeError(item["error"])
                toks.extend(item.get("tokens", []))
            streams.append(toks)
        dt = time.perf_counter() - t0
        stats = engine.stats.snapshot()
        out = {
            "req_per_s": round(n_req / dt, 3),
            "decode_tok_s": round(
                stats["tokens_out"] / dt if dt else 0.0, 1),
            "makespan_s": round(dt, 3),
            **_compile_counts(engine),
            **_sched_counts(engine),
            **_roof_counts(engine),
        }
        hbm = engine.debug_hbm()
        if hbm is not None:
            cats = hbm["categories"]
            out["hbm_devices"] = hbm["devices"]
            out["weights_bytes_per_device"] = (
                cats["weights"]["bytes_per_device"])
            out["kv_bytes_per_device"] = (
                cats["kv_cache"]["bytes_per_device"])
            out["total_bytes_per_device"] = hbm["total_bytes_per_device"]
        engine.stop()
        return out, streams

    single, want = leg(1)
    mesh, got = leg(tp)
    if got != want:  # the whole contract: sharding changes nothing
        raise RuntimeError("mesh leg diverged from single-chip greedy "
                           "stream")
    return {
        "tp": tp,
        "single": single,
        "mesh": mesh,
        "bit_identical": True,
        "speedup": (round(mesh["decode_tok_s"] / single["decode_tok_s"],
                          3) if single["decode_tok_s"] else None),
        "kv_per_device_frac": (
            round(mesh["kv_bytes_per_device"]
                  / single["kv_bytes_per_device"], 4)
            if single.get("kv_bytes_per_device") else None),
    }


def _measure_heal(params, cfg) -> dict:
    """BENCH_HEAL phase: the same greedy closed wave run twice at equal
    hardware — clean (no faults), then under seeded CHAOS dispatch
    faults with graftheal supervised recovery on. The healed leg's
    completed streams are asserted bit-identical to the clean leg's
    (replay-based resurrection with per-position sampling keys makes
    that the contract, not a hope), then the phase prices the storm:
    goodput_retained_frac — bit-identical completions over offered —
    user_visible_errors — streams that ended in an error item; under
    heal only quarantine and retry exhaustion may produce one — the
    supervisor's recovery counters, and per-leg req/s."""
    import numpy as np

    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.chaos import ChaosConfig
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    prompt_len = 32
    new_toks = min(NEW_TOKENS, 16)
    slots = 8
    n_req = 3 * slots
    rng = np.random.default_rng(37)
    prompts = [
        rng.integers(3, cfg.vocab_size, size=(prompt_len,)).tolist()
        for _ in range(n_req)
    ]

    def leg(healed: bool, chaotic: bool = True):
        ecfg = EngineConfig(
            max_slots=slots,
            # Headroom past prompt+decode: resurrection folds committed
            # tokens into the prompt, so the bucket list must hold
            # prompt_len + new_toks (next power of two) or a healed
            # request can't re-admit.
            max_seq_len=2 * prompt_len + 2 * new_toks,
            prompt_buckets=(prompt_len, 2 * prompt_len),
            max_admit=4,
            decode_chunk=4,
            heal=healed,
            heal_max_retries=3,
            chaos=(ChaosConfig(seed=13, dispatch_fail=HEAL_FAULT_P)
                   if chaotic else None),
        )
        engine = InferenceEngine(params, cfg, ecfg)
        engine.warmup()
        engine.start()
        t0 = time.perf_counter()
        qs = [engine.submit(p, SamplingParams(
                  temperature=0.0, top_k=0, top_p=1.0,
                  max_new_tokens=new_toks, seed=i))
              for i, p in enumerate(prompts)]
        streams, errors = [], []
        for q in qs:
            toks, err = [], None
            while True:
                item = q.get(timeout=300)
                if item is None:
                    break
                if "error" in item:
                    err = item
                    continue
                toks.extend(item.get("tokens", []))
            streams.append(toks)
            errors.append(err)
        dt = time.perf_counter() - t0
        out = {
            "req_per_s": round(n_req / dt, 3),
            "makespan_s": round(dt, 3),
            **_compile_counts(engine),
            **_sched_counts(engine),
        }
        health = engine.debug_health()
        chaos = engine.chaos_counts()
        engine.stop()
        return out, streams, errors, health, chaos

    clean, want, clean_errs, _, _ = leg(healed=False, chaotic=False)
    if any(clean_errs):
        raise RuntimeError(f"clean heal leg errored: {clean_errs}")
    # The _fail_all cliff: the SAME seeded storm with the supervisor
    # off — every fault wipes the whole in-flight cohort, which is what
    # the healed leg is priced against. Informational (the keys avoid
    # every bench_compare direction table): cross-run wave composition
    # shifts how many requests each fault catches, so gating the cliff
    # would flake, and its only job is showing the gap.
    cliff, cliff_got, cliff_errs, _, _ = leg(healed=False, chaotic=True)
    cliff_ok = sum(
        1 for i, (toks, err) in enumerate(zip(cliff_got, cliff_errs))
        if err is None and toks == want[i]
    )
    healed, got, errs, health, chaos = leg(healed=True)

    ok = 0
    for i, (toks, err) in enumerate(zip(got, errs)):
        if err is not None:
            continue
        if toks != want[i]:  # the whole contract: healing changes nothing
            raise RuntimeError(
                f"resurrected stream {i} diverged from the clean leg")
        ok += 1
    visible = sum(1 for e in errs if e is not None)
    sanctioned = (health or {}).get("quarantined", 0) \
        + (health or {}).get("retry_exhausted", 0)
    if visible > sanctioned:
        raise RuntimeError(
            f"{visible} user-visible errors but only {sanctioned} "
            "quarantined/exhausted — the healer leaked an innocent fault")
    return {
        "fault_p": HEAL_FAULT_P,
        "n_req": n_req,
        "clean": clean,
        "healed": healed,
        "unhealed": cliff,
        "bit_identical": True,
        "goodput_retained_frac": round(ok / n_req, 4),
        "user_visible_errors": visible,
        "unhealed_completed_frac": round(cliff_ok / n_req, 4),
        "unhealed_failed_streams": sum(
            1 for e in cliff_errs if e is not None),
        "req_s_retained_frac": (
            round(healed["req_per_s"] / clean["req_per_s"], 3)
            if clean["req_per_s"] else None),
        "dispatch_faults": (chaos or {}).get("dispatch_faults", 0),
        "recoveries": (health or {}).get("recoveries", 0),
        "resurrected": (health or {}).get("resurrected", 0),
        "quarantined": (health or {}).get("quarantined", 0),
        "retry_exhausted": (health or {}).get("retry_exhausted", 0),
        "watchdog_trips": (health or {}).get("watchdog_trips", 0),
    }


def main() -> None:
    import jax

    from seldon_tpu import device

    device.enable_compile_cache()
    on_tpu = device.on_tpu()
    dev = jax.devices()[0]
    if not on_tpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit(
            f"bench.py: no TPU — JAX found platform {dev.platform!r}. A "
            f"CPU smoke needs an explicit JAX_PLATFORMS=cpu, and prints "
            f"no per-chip metric."
        )
    # A rate is a per-chip rate only when a chip produced it.
    metric = "engine_req_per_s_per_chip" if on_tpu \
        else "engine_req_per_s_cpu_smoke"

    # Compile ledger on by default for bench runs: single-writer dict
    # stores off the hot path, and the counters it yields
    # (compile_variants / live_retraces) make bench records auditable
    # for retrace storms via tools/bench_compare.py.
    os.environ.setdefault("COMPILE_LEDGER", "1")
    os.environ.setdefault("SCHED_LEDGER", "1")
    os.environ.setdefault("ROOF_LEDGER", "1")

    params, cfg = _build(PRESET)
    req_s, detail, sp = _measure_throughput(
        params, cfg, SLOTS, N_REQ, DECODE_CHUNK, admit=MAX_ADMIT
    )

    # Every enabled phase runs to its end or raises: a failed phase is a
    # failed bench, not a note beside a number.
    if SLO_ENABLED:
        detail.update(_measure_slo(params, cfg, sp))
    if PREFIX:
        detail["prefix"] = _measure_prefix(params, cfg)
    if CHUNKED:
        detail["chunked"] = _measure_chunked(params, cfg)
    if PAGED:
        detail["paged"] = _measure_paged(params, cfg)
    if PILOT_PHASE:
        detail["pilot"] = _measure_pilot(params, cfg, sp)
    if SPEC_PHASE:
        detail["spec"] = _measure_spec(params, cfg)
    if MESH_PHASE:
        detail["mesh"] = _measure_mesh(params, cfg)
    if HEAL_PHASE:
        detail["heal"] = _measure_heal(params, cfg)

    # Second-preset phase: the 8B headline run also records the bench-1b
    # deployment proxy (throughput + SLO search) in detail.bench_1b —
    # the per-chip-traffic configuration the 125 req/s/chip target
    # actually describes.
    if SECOND_PRESET and SECOND_PRESET != PRESET:
        del params  # free the headline model's HBM before the next init
        p2, cfg2 = _build(SECOND_PRESET)
        req_s2, d2, sp2 = _measure_throughput(
            p2, cfg2, SECOND_SLOTS, 2 * SECOND_SLOTS, DECODE_CHUNK
        )
        d2["req_per_s"] = round(req_s2, 3)
        if on_tpu:
            d2["vs_baseline"] = round(
                req_s2 / BASELINE_REQ_S_PER_CHIP, 3)
        d2["slots"] = SECOND_SLOTS
        detail["bench_1b"] = d2
        if SECOND_SLO:
            d2.update(_measure_slo(p2, cfg2, sp2, slots=SECOND_SLOTS))

    # Where it ran, as JAX reports it (with peak memory where kept).
    detail["device"] = device.describe()
    record = {
        "metric": metric,
        "value": round(req_s, 3),
        "unit": (
            f"req/s (engine, {SLOTS} slots, {N_REQ} concurrent, "
            f"prefill{PROMPT_LEN}+decode{NEW_TOKENS}, {PRESET} "
            f"{cfg.weight_dtype} weights, {cfg.kv_cache_dtype} kv, "
            f"{dev.device_kind})"
        ),
        "detail": detail,
    }
    if on_tpu:  # the baseline is a per-chip rate; a CPU has no ratio to it
        record["vs_baseline"] = round(req_s / BASELINE_REQ_S_PER_CHIP, 3)
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
