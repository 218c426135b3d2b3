"""What the readers of the sparse block's metrics share (not a metric: no
UNIT).

The unit's `request {json}` access lines (unit.log, see _access.py) carry
the engine's running routing counters as each request ended, for a model
that dispatches tokens to experts: moe_sparse_layer_steps (sparse layers
run over all decode steps), moe_experts_touched (distinct experts those
layers read for live rows, summed) and moe_assignments. A program that
writes no such fields (another model, an older program) leaves every
reader here with nothing to read: None."""

import bisect
import re
import time

import _access
import _trace

FIELDS = ("moe_sparse_layer_steps", "moe_experts_touched", "moe_assignments")
# megablox's pallas_call, as benchmark/xplane.py cleans an XLA Ops event:
# "%gmm.3 = bf16[256,1536]{...} custom-call(" -> "gmm.3_bf16_256_1536_..."
GROUPED_OP = re.compile(r"^gmm(\.\d+)?_bf16_(\d+)_(\d+)_")


def experts_touched(obs):
    """Mean distinct experts one sparse layer reads per decode step."""
    d = _access.window_delta(obs, FIELDS)
    return d["moe_experts_touched"] / d["moe_sparse_layer_steps"] if d else None


def _ended_unix(row):
    """When the request ended on the unit's wall clock: received plus
    every phase of its line (the engine stamps them from one clock)."""
    ms = [row.get(k) for k in _access.PHASES + ("decode_ms",)]
    if not all(isinstance(v, (int, float)) for v in ms + [row.get("received_unix")]):
        return None
    return row["received_unix"] + sum(ms) / 1000.0


def slice_delta(obs):
    """The counters' growth over the TRACED SLICE, the seconds the device
    times of obs.trace come from: each line gives the running counters at
    the instant its request ended, and the counters at the slice's two
    ends are read off the line between the two requests that ended
    around each (live rows change little between two ends: only an
    admission moves them). The window's mean is no stand-in: live rows,
    and with them the experts read, differ by a quarter between one
    three-second slice and the next (unit.log holds one load of the unit,
    so its counters only grow). None where the lines do not reach both
    ends of the slice."""
    tr = obs.trace
    if not tr or not tr.get("slice"):
        return None
    off = time.time() - time.perf_counter()  # as _access.window
    a, b = (t + off for t in tr["slice"])
    pts = sorted((_ended_unix(r),) + tuple(r[f] for f in FIELDS)
                 for r in _access.lines(obs, "request")
                 if _ended_unix(r) is not None
                 and all(isinstance(r.get(f), (int, float)) for f in FIELDS))
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
    d = {f: y - x for f, x, y in zip(FIELDS, ca, cb)}
    return d if d[FIELDS[0]] > 0 else None


def decode_grouped_ops(obs):
    """[(seconds in the traced slice, output columns)] of the grouped
    expert products of the DECODE program: found by name among all the
    device ops that ran inside that program (_trace.program_ops), whatever
    their rank, at the row count of the slab's assignment list (slots x
    experts per token). An admission's products are another program's,
    also where its shortest group has as many rows and XLA numbers the
    instruction alike."""
    k = (obs.cfg or {}).get("num_experts_per_tok")
    if not k or not obs.slots:
        return []
    out = []
    for name, seconds in _trace.program_ops(obs, _trace.DECODE).items():
        m = GROUPED_OP.match(name)
        if m and int(m.group(2)) == obs.slots * k:
            out.append((seconds, int(m.group(3))))
    return out
