#!/usr/bin/env python
"""Compare two bench JSON runs and fail on perf regressions.

Input files are either the wrapper a bench driver writes
(``{"n", "cmd", "rc", "tail", "parsed"}`` — the metric line lives under
``parsed``), a raw metric line
(``{"metric", "value", "detail": {...}}``), or a JSONL stream of metric
lines (the last complete one wins).

Every numeric scalar in the metric line is flattened to a dot path
(``value``, ``detail.p50_ttft_ms``, ``detail.bench_1b.req_per_s``, ...)
and compared base -> candidate with a direction heuristic:

 * lower-is-better:  names containing ``ms``, ``latency``, ``stall``,
   ``frag``, ``dropped``, ``error``, ``bytes_per_device`` (graftmesh:
   per-chip HBM the TP sharding is supposed to save), plus the exact
   waste metrics ``padding_waste_frac`` / ``goodput_gap`` (the sched
   ledger's lost-capacity fractions — checked before the ``goodput``
   substring would claim them as higher-is-better), graftroof's
   ``host_frac`` (scheduler overhead share of the boundary wall), and
   graftmesh's ``kv_per_device_frac`` (TP-leg per-chip KV bytes over
   the single-chip leg's — ~1/tp when the pool shards), and graftheal's
   ``user_visible_errors`` (streams a seeded fault storm still failed
   in front of the user — quarantine + retry exhaustion are the only
   sanctioned sources, so any rise is a recovery regression);
 * higher-is-better: names containing ``req_per_s``, ``req_s``,
   ``tokens_per_s``, ``tok_s``, ``speedup``, ``hit_rate``, ``goodput``,
   ``coverage``, ``acceptance_rate`` (graftspec: a better drafter keeps
   more of every verify wave), plus the headline ``value`` /
   ``vs_baseline``, graftroof's achieved ``mfu`` / ``mbu`` and
   graftheal's ``goodput_retained_frac`` (bit-identical completions
   over offered under the BENCH_HEAL fault storm); the
   exact leaf ``dispatch_per_token`` gates lower-is-better (verify
   waves compress the decode loop), and ``roof_predicted_req_s`` stays
   informational (it moves when the COST MODEL changes, not when the
   served binary regresses);
 * strict:           ``live_retraces`` and ``compile_variants`` — any
   increase over base fails regardless of tolerance (a retrace storm
   is a correctness-of-the-lattice bug, and the variant count is an
   exact closed-form property of the config, so even one stray
   variant is a real regression);
 * everything else is informational (printed, never gated).

A gated metric regresses when it moves the wrong way by more than the
tolerance (default 10%, ``--tol 0.05`` for 5%). Exit is non-zero iff
at least one gated metric regressed. Usage::

    make bench-compare BASE=base.json CAND=cand.json
    python -m tools.bench_compare base.json cand.json --tol 0.05

See docs/benchmarking.md ("Comparing runs") for how this slots into
the release flow.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional, Tuple

# Substring -> direction tables, checked against the LAST path segment
# so "detail.chunked.p50_ttft_ms" gates on "p50_ttft_ms".
_LOWER = ("ms", "latency", "stall", "frag", "dropped", "error",
          "inversions", "bytes_per_device")
_HIGHER = ("req_per_s", "req_s", "tokens_per_s", "tok_s", "speedup",
           "hit_rate", "goodput", "coverage", "acceptance_rate")
# Exact leaf-name matches for the headline numbers. graftroof's
# utilization gauges gate higher-is-better: a PR that drops achieved
# MFU/MBU at the same throughput spent more hardware for the same work.
# "goodput_retained_frac" is graftheal's: the share of a seeded fault
# storm's offered requests that still completed bit-identical to the
# clean leg — resurrection working less well shows up here first.
_HIGHER_EXACT = ("value", "vs_baseline", "mfu", "mbu",
                 "goodput_retained_frac")
# Exact lower-is-better leaves, checked BEFORE the substring tables:
# "goodput_gap" would otherwise match the higher-is-better "goodput"
# substring, and "padding_waste_frac" matches nothing ("frac" != "frag").
# "dispatch_per_token" is graftspec's compression metric — verify waves
# emitting more tokens per dispatch push it DOWN. "host_frac" is
# graftroof's scheduler-overhead share of the boundary wall.
# "kv_per_device_frac" is graftmesh's sharding dividend — the TP leg's
# per-chip KV bytes as a fraction of the single-chip leg's; exact-TP
# splits the head axis, so it should sit at ~1/tp and only rise if a
# regression stops the pool from sharding. "user_visible_errors" is
# graftheal's headline — streams a seeded fault storm still failed in
# front of the user; quarantine and retry exhaustion are its only
# sanctioned sources, so any rise is a recovery regression.
_LOWER_EXACT = ("padding_waste_frac", "goodput_gap", "dispatch_per_token",
                "host_frac", "kv_per_device_frac", "user_visible_errors")
# Model-side constants, never gated: "roof_predicted_req_s" moves when
# the COST MODEL changes, not when the served binary regresses.
_INFO_EXACT = ("roof_predicted_req_s",)
_STRICT = ("live_retraces", "compile_variants")


def load_metric(path: str) -> Dict[str, Any]:
    """Read one bench artifact; return the metric-line dict."""
    with open(path) as f:
        raw = f.read()
    try:
        obj = json.loads(raw)
    except ValueError:
        obj = None
    if isinstance(obj, dict):
        if isinstance(obj.get("parsed"), dict):  # driver wrapper
            return obj["parsed"]
        if "metric" in obj:  # raw metric line
            return obj
    # JSONL stream: last parseable metric line wins.
    last: Optional[Dict[str, Any]] = None
    for ln in raw.splitlines():
        ln = ln.strip()
        if not ln.startswith("{"):
            continue
        try:
            cand = json.loads(ln)
        except ValueError:
            continue
        if isinstance(cand, dict) and "metric" in cand:
            last = cand
    if last is None:
        raise SystemExit(f"bench-compare: {path} holds no metric line")
    return last


def flatten(obj: Any, prefix: str = "") -> Dict[str, float]:
    """Numeric scalars of a metric line, keyed by dot path."""
    out: Dict[str, float] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            path = f"{prefix}.{k}" if prefix else str(k)
            out.update(flatten(v, path))
    elif isinstance(obj, bool):
        pass  # True/False are flags, not measurements
    elif isinstance(obj, (int, float)):
        out[prefix] = float(obj)
    return out


def direction(path: str) -> str:
    """'lower' | 'higher' | 'strict' | 'info' for a flattened path."""
    leaf = path.rsplit(".", 1)[-1]
    if leaf in _INFO_EXACT:
        return "info"
    if leaf in _STRICT:
        return "strict"
    if leaf in _LOWER_EXACT:
        return "lower"
    if leaf in _HIGHER_EXACT:
        return "higher"
    if any(s in leaf for s in _HIGHER):
        return "higher"
    if any(s in leaf for s in _LOWER):
        return "lower"
    return "info"


def compare(base: Dict[str, float], cand: Dict[str, float],
            tol: float) -> Tuple[List[str], List[str]]:
    """Return (report lines, regression messages)."""
    lines: List[str] = []
    regressions: List[str] = []
    header = (f"{'metric':<44} {'base':>12} {'cand':>12} "
              f"{'delta':>8}  gate")
    lines.append(header)
    lines.append("-" * len(header))
    for path in sorted(set(base) | set(cand)):
        b, c = base.get(path), cand.get(path)
        d = direction(path)
        if b is None or c is None:
            # Say WHICH side is missing: a metric only in cand was added
            # by the candidate run; one only in base was removed by it.
            status = "added" if b is None else "removed"
            lines.append(f"{path:<44} {_fmt(b):>12} {_fmt(c):>12} "
                         f"{'--':>8}  {d} ({status})")
            continue
        delta = (c - b) / abs(b) if b else (0.0 if c == b else float("inf"))
        verdict = d
        if d == "strict" and c > b:
            verdict = "REGRESSION"
            regressions.append(
                f"{path}: {b:g} -> {c:g} (strict: no increase allowed)")
        elif d == "lower" and delta > tol:
            verdict = "REGRESSION"
            regressions.append(
                f"{path}: {b:g} -> {c:g} (+{delta:.1%} > {tol:.0%} tol, "
                f"lower is better)")
        elif d == "higher" and delta < -tol:
            verdict = "REGRESSION"
            regressions.append(
                f"{path}: {b:g} -> {c:g} ({delta:.1%} < -{tol:.0%} tol, "
                f"higher is better)")
        lines.append(f"{path:<44} {_fmt(b):>12} {_fmt(c):>12} "
                     f"{delta:>+7.1%}  {verdict}")
    return lines, regressions


def _fmt(v: Optional[float]) -> str:
    if v is None:
        return "--"
    return f"{v:g}" if abs(v) < 1e6 else f"{v:.3e}"


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description="diff two bench JSON runs; non-zero exit on regression")
    p.add_argument("base", help="baseline bench JSON")
    p.add_argument("cand", help="candidate bench JSON")
    p.add_argument("--tol", type=float, default=0.10,
                   help="relative tolerance for gated metrics "
                        "(default 0.10 = 10%%)")
    args = p.parse_args(argv)

    base_line = load_metric(args.base)
    cand_line = load_metric(args.cand)
    if base_line.get("metric") != cand_line.get("metric"):
        print(f"bench-compare: metric mismatch "
              f"({base_line.get('metric')} vs {cand_line.get('metric')}); "
              f"comparing anyway", file=sys.stderr)

    lines, regressions = compare(flatten(base_line), flatten(cand_line),
                                 args.tol)
    print(f"bench-compare: {args.base} -> {args.cand} "
          f"(tol {args.tol:.0%})")
    for ln in lines:
        print(ln)
    if regressions:
        print(f"\n{len(regressions)} regression(s):", file=sys.stderr)
        for r in regressions:
            print(f"  {r}", file=sys.stderr)
        return 1
    print("\nno regressions")
    return 0


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
