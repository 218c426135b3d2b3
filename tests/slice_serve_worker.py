"""Subprocess worker for the multi-process SERVING proof
(tests/test_distributed.py::test_engine_serves_across_two_processes):
joins a 2-process jax.distributed "slice" (4 virtual CPU devices each),
builds an InferenceEngine whose params/cache shard over a mesh with the
TP axis SPANNING the two processes (attention psums cross the process
boundary — the v5e-16 deployment shape, SURVEY §5.8), generates real
completions, and prints them as one JSON line.

Determinism contract: in multi-process SPMD every process must enqueue
the SAME device programs in the same order, so all requests are
submitted BEFORE the scheduler starts — the first admission drain then
sees an identical FIFO on both processes, and every subsequent scheduler
decision depends only on device results (identical) — never on wall
timing."""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
)

import jax
import numpy as np


def main():
    coordinator = sys.argv[1]
    pid = int(sys.argv[2])
    nproc = int(sys.argv[3])

    from seldon_tpu.parallel import distributed

    cfg_slice = distributed.SliceConfig(
        coordinator=coordinator, num_processes=nproc, process_id=pid
    )
    assert distributed.ensure_initialized(cfg_slice)
    assert len(jax.devices()) == 4 * nproc

    from tests.slice_serve_common import run_engine

    toks = run_engine()
    print(json.dumps({"process_id": pid, "completions": toks}), flush=True)


if __name__ == "__main__":
    main()
