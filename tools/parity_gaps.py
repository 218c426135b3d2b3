"""Every position's gap of a benchmark parity job, for setting a
configuration's `parity` numbers: the engine's greedy tokens and the
family's lower-precision control against the plain reference
(benchmark/reference.logit_gaps), not reduced to the marker's counts.

    python3 tools/parity_gaps.py chiprun_out/benchmark/<cell>/parity_job.json [out.jsonl]

Run it after benchmark/run.py has left the job file (the unit has exited:
one process per chip); appends one JSON line {seed, config, gaps,
control_gaps, logit_std}."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, ROOT)


def main(job_file: str, out_file: str = "") -> int:
    import family
    import reference

    with open(job_file) as f:
        job = json.load(f)
    with open(job["config"]) as f:
        cfg = json.load(f)
    fam = family.load(os.path.join(ROOT, "benchmark"), cfg)
    params = fam.build_params(cfg, int(job["seed"]))
    gaps, control = reference.logit_gaps(fam, params, cfg, job["probes"], control=True)
    # the scale the gaps are read against: the reference's own spread of
    # logits over the vocabulary at the first probe's generated positions
    import jax.numpy as jnp
    prompt, toks = job["probes"][0]
    seq = jnp.asarray(list(prompt) + list(toks[:-1]), jnp.int32)
    std = float(jnp.std(fam.forward_logits(params, seq, cfg)[len(prompt) - 1:]))
    line = json.dumps({"seed": job["seed"], "config": cfg["name"], "gaps": gaps,
                       "control_gaps": control, "logit_std": std})
    print(line, flush=True)
    if out_file:
        os.makedirs(os.path.dirname(os.path.abspath(out_file)), exist_ok=True)
        with open(out_file, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
