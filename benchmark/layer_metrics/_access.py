"""What the access-log readers share (not a metric: no UNIT).

The unit writes one `request {json}` line per finished request and one
`startup {json}` line per load on the logger `seldon_tpu.access`
(docs/distributed-tracing.md); run.py sends the unit's output to
chiprun_out/benchmark/<cell>/unit.log, which outlives the unit. A unit
that writes no such lines (an older program) leaves every reader here
with nothing to read: None, and the metric is left out of the line."""

import bisect
import json
import os
import re
import time

import stats

LINE = re.compile(r"\b(request|startup) (\{.*\})\s*$")
PHASES = ("executor_wait_ms", "queue_wait_ms", "device_wait_ms",
          "first_token_held_ms")
MAX_MISCOUNT = 2  # requests an edge of the window may add or drop


def log_path(obs):
    """run.py's self.work: <checkout>/chiprun_out/benchmark/<cell name>."""
    name = (obs.cell or {}).get("name")
    if not name:
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, "chiprun_out", "benchmark", name, "unit.log")


def lines(obs, kind):
    """The parsed `kind {json}` lines of the cell's unit.log, in order."""
    path = log_path(obs)
    if path is None or not os.path.exists(path):
        return []
    out = []
    with open(path, errors="replace") as f:
        for ln in f:
            m = LINE.search(ln)
            if m and m.group(1) == kind:
                try:
                    out.append(json.loads(m.group(2)))
                except ValueError:
                    pass  # a torn line: the count check below notices
    return out


def window(obs):
    """The request lines of the measured window: received by the unit
    between obs.t0 and obs.t1 (the generator's perf_counter, turned into
    the unit's wall clock in this process, which is run.py's). None
    unless they are the sampled requests to within MAX_MISCOUNT."""
    if obs.t0 is None or obs.t1 is None or obs.samples is None:
        return None
    off = time.time() - time.perf_counter()
    rows = [r for r in lines(obs, "request")
            if isinstance(r.get("received_unix"), (int, float))
            and obs.t0 + off <= r["received_unix"] <= obs.t1 + off]
    if not rows or abs(len(rows) - len(obs.samples)) > MAX_MISCOUNT:
        return None
    return rows


def window_delta(obs, fields):
    """The growth over the measured window of the engine's running
    counters `fields`, which every access line carries as its request
    ended: last line minus first of the window's requests, in the order
    they ended (fields[0] only grows). None where fewer than two lines
    carry them or fields[0] did not move."""
    rows = [r for r in window(obs) or ()
            if all(isinstance(r.get(f), (int, float)) for f in fields)]
    if len(rows) < 2:
        return None
    rows.sort(key=lambda r: r[fields[0]])
    d = {f: rows[-1][f] - rows[0][f] for f in fields}
    return d if d[fields[0]] > 0 else None


def ended_unix(row):
    """When the request ended on the unit's wall clock: received plus
    every phase of its line (the engine stamps them from one clock)."""
    ms = [row.get(k) for k in PHASES + ("decode_ms",)]
    if not all(isinstance(v, (int, float)) for v in ms + [row.get("received_unix")]):
        return None
    return row["received_unix"] + sum(ms) / 1000.0


def slice_delta(obs, fields):
    """The growth of the running counters `fields` over the TRACED SLICE,
    the seconds the device times of obs.trace come from: each line gives
    the counters at the instant its request ended, and the counters at
    the slice's two ends are read off the line between the two requests
    that ended around each (live rows change little between two ends:
    only an admission moves them). The window's mean is no stand-in:
    live rows, and with them the experts read, differ by a quarter
    between one three-second slice and the next (unit.log holds one load
    of the unit, so its counters only grow). None where the lines do not
    reach both ends of the slice, or fields[0] did not move."""
    tr = obs.trace
    if not tr or not tr.get("slice"):
        return None
    off = time.time() - time.perf_counter()  # as window()
    a, b = (t + off for t in tr["slice"])
    pts = sorted((t,) + tuple(r[f] for f in fields)
                 for r in lines(obs, "request") for t in (ended_unix(r),)
                 if t is not None
                 and all(isinstance(r.get(f), (int, float)) for f in fields))
    times = [p[0] for p in pts]

    def at(t):
        j = bisect.bisect_left(times, t)
        if j == 0 or j == len(pts):
            return None
        (t0, *c0), (t1, *c1) = pts[j - 1], pts[j]
        w = (t - t0) / (t1 - t0) if t1 > t0 else 0.0
        return [x0 + w * (x1 - x0) for x0, x1 in zip(c0, c1)]
    ca, cb = at(a), at(b)
    if ca is None or cb is None:
        return None
    d = {f: y - x for f, x, y in zip(fields, ca, cb)}
    return d if d[fields[0]] > 0 else None


def mid80(obs, key):
    """10 %-trimmed mean of one field over the window's requests, the
    statistic of ttft_mid80_ms."""
    rows = window(obs)
    xs = [r[key] for r in rows or ()
          if isinstance(r.get(key), (int, float))]
    return stats.trimmed_mean(xs) if xs else None


def startup(obs, key):
    """One field of the unit's start-up line."""
    rows = lines(obs, "startup")
    v = rows[-1].get(key) if rows else None
    return float(v) if isinstance(v, (int, float)) else None
