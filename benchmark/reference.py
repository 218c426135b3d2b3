"""The parity check that `correct` depends on: the engine's greedy tokens
against the plain reference of the configuration's family.

What is the same for every architecture is here: the job file, the gaps,
the criterion, the marker. The architecture itself (the weights the unit
serves, the forward pass, its lower-precision twin) is the family's file,
benchmark/families/<family>.py (see benchmark/family.py), which this
program finds by the configuration's `family` key.

As a program (a child of run.py, started only after the unit has exited
and the chip is free):

    python3 benchmark/reference.py <job.json>

rebuilds the unit's weights (the family's build_params, the same seed),
runs the family's forward pass (float32, matmul precision "highest") over
each probe's prompt + the engine's greedy tokens, and checks position by
position that the engine's token has a reference logit within epsilon of
the reference's maximum (at a stated share of the positions, and within
epsilon_all at all but a stated few: the configuration file gives the
numbers, their reasons and the readings behind them). As a negative
control it then runs the family's lower-precision twin (for an int8 tree,
the layers' weights on an int4 grid), takes that model's greedy tokens at
the same positions and judges them by the same criterion: the marker
records whether the criterion tells the lower precision from the served
one. Writes the marker file the job names.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp


def logit_gaps(fam, params, cfg, probes, control=False):
    """For each (prompt, engine tokens): the gap between the reference's
    largest logit and the logit of the engine's token, at every generated
    position (teacher-forced on the engine's own tokens). `fam` is the
    family's module, `cfg` the configuration file. With control, also the
    gaps of the greedy tokens of the family's lower-precision twin, at
    the same positions."""
    gaps, coarse_gaps = [], []
    for prompt, toks in probes:
        seq = jnp.asarray(list(prompt) + list(toks[:-1]), jnp.int32)
        at = jnp.arange(len(toks))
        logits = fam.forward_logits(params, seq, cfg)[len(prompt) - 1:]
        top = jnp.max(logits, axis=-1)
        gaps.extend(float(g) for g in top - logits[at, jnp.asarray(toks, jnp.int32)])
        if control:
            coarse = fam.forward_logits(params, seq, cfg, control=True)[len(prompt) - 1:]
            coarse_gaps.extend(float(g) for g in top - logits[at, jnp.argmax(coarse, axis=-1)])
    return gaps, coarse_gaps


def limits(par):
    """The configuration's criterion as its numbers: epsilon, the share of
    the positions that has to lie within it (all, unless stated), the gap
    epsilon_all (epsilon unless stated) and how many positions may lie
    beyond that (none, unless stated)."""
    eps = float(par["epsilon"])
    return {"epsilon": eps, "min_share_within": float(par.get("min_share_within", 1.0)),
            "epsilon_all": float(par.get("epsilon_all", eps)),
            "max_over_epsilon_all": int(par.get("max_over_epsilon_all", 0))}


def judge(gaps, par):
    """The configuration's criterion -> (ok, share of the positions within
    epsilon, positions beyond epsilon_all)."""
    lim = limits(par)
    share = sum(1 for g in gaps if g <= lim["epsilon"]) / len(gaps)
    over = sum(1 for g in gaps if g > lim["epsilon_all"])
    ok = share >= lim["min_share_within"] and over <= lim["max_over_epsilon_all"]
    return bool(ok), share, over


def main(job_file: str) -> int:
    t0 = time.perf_counter()
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    sys.path.insert(0, os.path.dirname(here))
    with open(job_file) as f:
        job = json.load(f)
    with open(job["config"]) as f:
        cfg_file = json.load(f)

    import family
    from seldon_tpu import device

    device.enable_compile_cache()
    fam = family.load(here, cfg_file)
    params = fam.build_params(cfg_file, int(job["seed"]))
    gaps, control = logit_gaps(fam, params, cfg_file, job["probes"], control=True)
    par = cfg_file["parity"]
    ok, share, over = judge(gaps, par)
    passes, control_share, control_over = judge(control, par)
    dev = jax.devices()[0]
    marker = {
        "config": cfg_file["name"], "family": family.name_of(cfg_file),
        "config_sha": job["config_sha"],
        "ok": ok, **limits(par), "share_within": share, "over_epsilon_all": over,
        "max_gap": max(gaps), "positions": len(gaps),
        "argmax_agree": sum(1 for g in gaps if g == 0.0),
        "control": {"weights": fam.CONTROL, "rejected": not passes,
                    "share_within": control_share, "over_epsilon_all": control_over,
                    "max_gap": max(control)},
        "seed": job["seed"],
        "device": f"{dev.platform}/{dev.device_kind}",
        "seconds": time.perf_counter() - t0,
    }
    os.makedirs(os.path.dirname(job["marker"]), exist_ok=True)
    with open(job["marker"], "w") as f:
        json.dump(marker, f)
    print(json.dumps(marker), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
