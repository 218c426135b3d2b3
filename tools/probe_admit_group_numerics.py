"""Chip probe: does a request's greedy stream depend on the size of the
admission group it was prefilled in? One request of lfm2.chat's traffic
(--seed, --idx; weights from the seed as benchmark/run.py draws them),
sent alone and as 2 / 4 / 8 copies admitted in ONE group, same code and
weights throughout; compared with the streams tools/bench_token_streams.py
recorded, where chiprun_out/token_streams/<label>.json is at hand
(--labels a,b; chiprun does not ship chiprun_out, so compare here
afterwards from chiprun_out/group_numerics/result.json).

Found with it (PR 28): request 112 at seed 1618033988 answers one stream
alone and in groups of 4 and 8, another in a group of 2 (the bf16
prefill of `admit/512/2` rounds differently and the model's logits are
near ties) - which is how a parent and a change that differ only in
timing can differ in one stream of 152.

    chiprun -- python3 tools/probe_admit_group_numerics.py
    JAX_PLATFORMS=cpu python3 tools/probe_admit_group_numerics.py --rehearse
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def stream(q):
    toks = []
    while True:
        item = q.get(timeout=600)
        if item is None:
            return toks
        if "error" in item:
            raise RuntimeError(item)
        toks += item["tokens"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1618033988)
    ap.add_argument("--idx", type=int, default=112)
    ap.add_argument("--labels", default="")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: the tiny preset")
    args = ap.parse_args(argv)

    import traffic
    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.jaxserver import JAXServer

    if args.rehearse:
        srv = JAXServer(preset="tiny-lfm2", max_slots=8, max_seq_len=512, tp=1)
        vocab = 256
    else:
        import launcher

        path = os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b.json")
        with open(path) as f:
            cfg = json.load(f)
        srv = JAXServer(preset=launcher.register_preset(path), tp=1,
                        init_seed=args.seed % (2 ** 31 - 1), max_slots=64,
                        max_seq_len=1024, platform="tpu",
                        weight_dtype=cfg["serving"]["weight_dtype"])
        vocab = cfg["vocab_size"]
    spec = traffic.load_traffic(os.path.join(ROOT, "benchmark"), "chat",
                                "lfm2.chat", args.rehearse)
    req = next(r for r in traffic.open_loop(spec, args.seed, 51.0, vocab)
               if r.idx == args.idx)
    srv.load()
    eng = srv.engine
    # what the transports hand down for the benchmark's body
    sp = SamplingParams(temperature=0.0, top_p=0.0, top_k=0,
                        max_new_tokens=req.max_new)
    out = {"seed": args.seed, "idx": args.idx, "max_new": req.max_new,
           "prompt_len": len(req.prompt_ids), "groups": {}}
    try:
        for n in (1, 2, 4, 8, 1):
            # the scheduler dispatches under _book: all n arrive in one wave
            with eng._book:
                qs = [eng.submit(list(req.prompt_ids), sp) for _ in range(n)]
            streams = [stream(q) for q in qs]
            out["groups"].setdefault(str(n), []).append(streams[0])
            print(n, "copies agree among themselves:",
                  all(s == streams[0] for s in streams), streams[0][:6],
                  flush=True)
    finally:
        eng.stop()
    for label in filter(None, args.labels.split(",")):
        path = os.path.join(ROOT, "chiprun_out", "token_streams", label + ".json")
        with open(path) as f:
            want = next(r["tokens"] for r in json.load(f)["requests"]
                        if r["idx"] == args.idx)
        out[label] = {n: [s == want for s in ss]
                      for n, ss in out["groups"].items()}
        print(label, out[label], flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "group_numerics"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "group_numerics",
                           "result.json"), "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
