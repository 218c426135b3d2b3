"""One run of a benchmark cell that also keeps what the unit answered:
every request's token ids, by the request's index in the cell's traffic,
written to chiprun_out/token_streams/<label>.json with a digest over all
of them. The benchmark itself keeps no tokens; greedy streams are a
function of the weights and the prompt, so two checkouts given the same
--seed can be compared stream for stream (`--compare a b`).

    python3 tools/bench_token_streams.py --root .scratch/parent --label parent \\
        --workload lfm2.chat --seed 3000000019 --seconds 51 --trace 0
    python3 tools/bench_token_streams.py --compare parent change

The run is benchmark/run.py's own, imported from the checkout --root
names (this one by default) and run unchanged; its result line is the
last line of stdout as ever.
"""

import argparse
import hashlib
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(os.path.dirname(HERE), "chiprun_out", "token_streams")


def run(root: str, label: str, rest: list) -> int:
    bench = os.path.join(os.path.abspath(root), "benchmark")
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(bench, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    summarise = mod.Run.summarise

    def keeping(self):
        rows = sorted(
            ({"idx": r.req.idx, "phase": r.req.phase, "ok": r.ok,
              "prompt_len": r.req.prompt_len, "tokens": list(r.tokens)}
             for r in self.obs.all_results), key=lambda d: d["idx"])
        digest = hashlib.sha256(json.dumps(rows).encode()).hexdigest()
        os.makedirs(OUT, exist_ok=True)
        with open(os.path.join(OUT, label + ".json"), "w") as f:
            json.dump({"label": label, "argv": rest, "sha256": digest,
                       "requests": rows}, f)
        print(f"[streams] {label}: {len(rows)} requests, "
              f"{sum(len(d['tokens']) for d in rows)} tokens, sha256 {digest}",
              file=sys.stderr, flush=True)
        return summarise(self)
    mod.Run.summarise = keeping
    return mod.main(rest)


def compare(a: str, b: str) -> int:
    def load(label):
        with open(os.path.join(OUT, label + ".json")) as f:
            return json.load(f)
    da, db = load(a), load(b)
    ra = {d["idx"]: d for d in da["requests"]}
    rb = {d["idx"]: d for d in db["requests"]}
    both = sorted(set(ra) & set(rb))  # the slower side sends more tail requests
    differing = {}
    for i in both:
        ta, tb = ra[i]["tokens"], rb[i]["tokens"]
        if ta != tb:
            n = next((j for j, (x, y) in enumerate(zip(ta, tb)) if x != y),
                     min(len(ta), len(tb)))
            differing[i] = {"first_difference_at": n,
                            "lengths": [len(ta), len(tb)]}
    print(json.dumps({
        a: da["sha256"], b: db["sha256"], "requests": [len(ra), len(rb)],
        "answered_by_both": len(both),
        "tokens_of_those": [sum(len(r[i]["tokens"]) for i in both)
                            for r in (ra, rb)],
        "identical_streams": len(both) - len(differing),
        "differing": differing}))
    return 1 if differing else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(HERE))
    ap.add_argument("--label", default="run")
    ap.add_argument("--compare", nargs=2, metavar="LABEL")
    args, rest = ap.parse_known_args(argv)
    if args.compare:
        return compare(*args.compare)
    return run(args.root, args.label, rest)


if __name__ == "__main__":
    sys.exit(main())
