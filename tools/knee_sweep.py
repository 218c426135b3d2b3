"""A knee sweep of one benchmark cell in one chip call: the cell's own
traffic at each of the given rates (the cell's `cells/<cell>.json` is
rewritten for each run and put back at the end), one run of
benchmark/run.py a rate, and tools/knee_thirds.py's reading of each
(completed req/s, TTFT p50 of the window's first and last third).

    chiprun --timeout 3000 -- python3 tools/knee_sweep.py laguna.code 51 7 2,4,6,8

The knee is midway between the last rate whose TTFT p50 is flat over the
thirds and the first where it grows (PERF.md section 4). Everything lands
in chiprun_out/knee_sweep/<cell>/."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(cell: str, seconds: str, seed: str, rates: str) -> int:
    cell_file = os.path.join(ROOT, "benchmark", "cells", cell + ".json")
    with open(cell_file) as f:
        kept = f.read()
    out_dir = os.path.join(ROOT, "chiprun_out", "knee_sweep", cell)
    os.makedirs(out_dir, exist_ok=True)
    try:
        for i, rate in enumerate(float(r) for r in rates.split(",")):
            with open(cell_file, "w") as f:
                json.dump(dict(json.loads(kept), rate_rps=rate), f)
            out = os.path.join(out_dir, f"run_{rate}.out")
            with open(out, "w") as f:
                rc = subprocess.run(
                    [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
                     cell, "--seed", str(int(seed) + i), "--seconds", seconds, "--trace", "0"],
                    cwd=ROOT, stdout=f, stderr=subprocess.STDOUT).returncode
            print(f"[sweep] rate {rate}: run.py rc={rc}", flush=True)
            with open(out) as f:
                for ln in f.read().splitlines():
                    if any(w in ln for w in ("ttft_ms:", "tpot_ms:", "rows/step", "INCORRECT",
                                             "FAILED", "warm-up ", "load:")):
                        print("   " + ln[:300], flush=True)
            if rc == 0:
                subprocess.run([sys.executable, os.path.join(ROOT, "tools", "knee_thirds.py"),
                                cell, out, seconds, str(int(seed) + i)], cwd=ROOT)
    finally:
        with open(cell_file, "w") as f:
            f.write(kept)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:5]))
