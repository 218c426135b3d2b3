"""Reduction of a jax.profiler trace (.xplane.pb) to the numbers the
per-layer metrics read. Run as a child of run.py (which stays off JAX):

    python3 benchmark/xplane.py <profile dir | file.xplane.pb>

prints one JSON object: busy_s (union of device-op intervals, averaged
over device planes), window_s (first device event to last), modules
{name: {count, total_s, median_s}} from the "XLA Modules" line (one event
per execution of a jitted program), the device ops that took most time,
every device op's total seconds by the program it ran in and its
cleaned name (ops_by_program: a reader that looks for a kernel
finds it whatever its rank, and apart from the op another program numbers
alike), and the longest idle gaps named by the program that ran next.

The engine jits functools.partial objects, which carry no name: XLA calls
every one of its programs "jit__unknown(<program id>)". Programs without
a name are told apart by their id and labelled by how often they ran: in
a serving slice the most-run program is the decode chunk
("unnamed_most_run"), the others are admissions ("unnamed_other"). Once
the program names its jits, the real names come through unchanged."""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import statistics
import sys
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# control-flow ops span their bodies, whose ops are listed themselves
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]*( |$|=)")
UNNAMED = re.compile(r"unknown|unnamed|lambda|partial")


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def union_s(intervals: List[Tuple[int, int]]) -> float:
    """Seconds covered by the union of [start, end) nanosecond intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def clean(name: str, width: int = 64) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", name)[:width].strip("_")


def module_name(name: str) -> str:
    """'jit__chunk_impl(1234)' -> '_chunk_impl'; a program without a name
    keeps its id: 'jit__unknown(1234)' -> '_unknown(1234)'."""
    base = name.split("(")[0]
    base = base[4:] if base.startswith("jit_") else base
    return name[4:] if UNNAMED.search(base) and name.startswith("jit_") else base


def label_unnamed(names: List[str], counts: Dict[str, int]) -> Dict[str, str]:
    """Programs without a name, labelled by how often they ran."""
    unnamed = sorted((n for n in names if UNNAMED.search(n)),
                     key=lambda n: -counts[n])
    return {n: ("unnamed_most_run" if i == 0 else "unnamed_other")
            for i, n in enumerate(unnamed)}


def reduce_planes(planes) -> Dict:
    """planes: [(plane name, [(line name, [(event name, start_ns, dur_ns)])])]"""
    busy, windows = [], []
    # (program, op name) -> duration: XLA numbers each program's
    # instructions from its own count, so two programs can hold a "gmm.5"
    ops: Dict[Tuple[str, str], int] = {}
    runs: List[Tuple[str, int]] = []      # (program, duration)
    raw_gaps: List[Tuple[int, str]] = []  # (gap, program that ran next)
    n_planes = 0
    for pname, lines in planes:
        if not DEVICE_PLANE.match(pname):
            continue
        lines = dict(lines)
        op_events = lines.get(OPS_LINE, [])
        if not op_events:
            continue
        n_planes += 1
        busy.append(union_s([(s, s + d) for _, s, d in op_events]))
        first = min(s for _, s, _ in op_events)
        last = max(s + d for _, s, d in op_events)
        windows.append((last - first) / 1e9)
        mod_events = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
        starts = [s for _, s, _ in mod_events]
        for name, s, d in op_events:
            if not CONTAINER.match(name):
                # a core runs one program at a time: the op is of the last
                # program that started before it ("" before the first)
                i = bisect.bisect_right(starts, s) - 1
                prog = module_name(mod_events[i][0]) if i >= 0 else ""
                ops[prog, name] = ops.get((prog, name), 0) + d
        for name, _, d in mod_events:
            runs.append((module_name(name), d))
        for (_, s0, d0), (name, s1, _) in zip(mod_events, mod_events[1:]):
            if s1 > s0 + d0:
                raw_gaps.append((s1 - (s0 + d0), module_name(name)))
    counts: Dict[str, int] = {}
    for n, _ in runs:
        counts[n] = counts.get(n, 0) + 1
    label = label_unnamed(list(counts), counts)
    mods: Dict[str, List[int]] = {}
    for n, d in runs:
        mods.setdefault(label.get(n, n), []).append(d)
    gaps = [(g, "gap_before_" + label.get(n, n)) for g, n in raw_gaps]
    if not n_planes:
        return {"busy_s": 0.0, "window_s": 0.0, "modules": {}, "device_ops": [],
                "ops_by_program": {}, "idle_gaps": [], "device_planes": 0}
    whole: Dict[str, int] = {}            # the ranking adds the programs up, as it did
    by_program: Dict[str, Dict[str, float]] = {}
    for (prog, n), d in ops.items():
        whole[n] = whole.get(n, 0) + d
        of = by_program.setdefault(label.get(prog, prog), {})
        of[clean(n)] = of.get(clean(n), 0.0) + d / 1e9
    ranked = sorted(whole.items(), key=lambda kv: -kv[1])
    return {
        "device_planes": n_planes,
        "busy_s": sum(busy) / n_planes,
        "window_s": sum(windows) / n_planes,
        "modules": {m: {"count": len(d), "total_s": sum(d) / 1e9,
                        "median_s": statistics.median(d) / 1e9}
                    for m, d in mods.items()},
        "device_ops": [[clean(n), d / 1e9] for n, d in ranked[:10]],
        "ops_by_program": by_program,
        "idle_gaps": [[clean(n), g / 1e9]
                      for g, n in sorted(gaps, reverse=True)[:10]],
    }


def read_planes(path: str):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(path))
    return [(p.name, [(ln.name, [(e.name, int(e.start_ns), int(e.duration_ns))
                                 for e in ln.events]) for ln in p.lines])
            for p in pd.planes]


def outline(path: str) -> List[str]:
    """Planes and lines with event counts: look at a trace by hand first."""
    return [f"{p} / {ln}: {len(ev)} events, e.g. {ev[0][0][:80] if ev else ''}"
            for p, lines in read_planes(path) for ln, ev in lines]


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[2] == "--outline":
        print("\n".join(outline(sys.argv[1])))
    else:
        print(json.dumps(reduce_planes(read_planes(sys.argv[1]))))
