"""A knee sweep's reading of one benchmark run: completed requests per
second of the window and the median TTFT of the window's first and last
third, from the unit's access lines (chiprun_out/benchmark/<cell>/unit.log)
and the run's own output. A queue that grows through the window shows as
a last third well above the first.

    python3 tools/knee_thirds.py <cell> <run output file> <window seconds> <seed>
"""

import json
import re
import statistics
import sys

LINE = re.compile(r"\brequest (\{.*\})\s*$")


PROBE_LENS = [24, 60, 100, 120]  # benchmark/run.py's probes, sent alone and in this order


def the_loop(rows):
    """The access lines of the open loop: those between run.py's two sets
    of probes (before the lead-in, after the drain). client.run_open cuts
    the tail once the window's requests are done, so the loop's length is
    not the generator's."""
    lens = [r.get("prompt_tokens") for r in rows]
    at = [i for i in range(len(rows) - 3) if lens[i:i + 4] == PROBE_LENS]
    assert len(at) >= 2, f"found {len(at)} sets of probes in unit.log, need the two around the loop"
    return rows[at[-2] + 4:at[-1]]


def main(cell: str, out_file: str, seconds: str, seed: str) -> int:
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmark"))
    import traffic

    rows = []
    with open(f"chiprun_out/benchmark/{cell}/unit.log", errors="replace") as f:
        for ln in f:
            m = LINE.search(ln)
            if m:
                rows.append(json.loads(m.group(1)))
    rows.sort(key=lambda r: r["received_unix"])
    with open(out_file) as f:
        result = json.loads(f.read().strip().splitlines()[-1])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        mix = next(w["traffic"] for w in json.load(f)["workloads"] if w["name"] == cell)
    spec = traffic.load_traffic(os.path.join(root, "benchmark"), mix, cell)
    reqs = traffic.open_loop(spec, int(seed), float(seconds), 65536)
    phases = [r.phase for r in sorted(reqs, key=lambda r: r.due)]
    loop = the_loop(rows)
    assert len(loop) <= len(reqs), (len(loop), len(reqs))
    # the unit received them in the order they were due (one per 1/rate s)
    win = [r for r, ph in zip(loop, phases) if ph == "window"]
    lo, hi = win[0]["received_unix"], win[0]["received_unix"] + float(seconds)
    ttft = [r["queue_wait_ms"] + r["device_wait_ms"] + r["first_token_held_ms"]
            + r["executor_wait_ms"] for r in win]
    third = max(1, len(win) // 3)
    done = [r for r in loop
            if lo <= r["received_unix"] + (r["queue_wait_ms"] + r["device_wait_ms"]
                                           + r["first_token_held_ms"] + r["decode_ms"]) / 1000.0 <= hi]
    e2e = {k: v["value"] for k, v in result["metrics"].items()}
    print("KNEE " + json.dumps({
        "cell": cell, "offered_rps": spec["rate_rps"], "window_requests": len(win),
        "completed_rps": len(done) / float(seconds),
        "ttft_p50_first_third_ms": statistics.median(ttft[:third]),
        "ttft_p50_last_third_ms": statistics.median(ttft[-third:]),
        "correct": result["correct"], "failed": result["failed"], **e2e}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:5]))
