"""Diagnose the 8B decode superlinear step-cost cliff past ~160 slots.

Round-4 measurements (memory: tpu-bench-rig-quirks): decode step ms at
96/160/256/320 slots = 18.7/24.5/44.8/55.5 — linear KV growth predicts
~19/21/25/28, so something structural changes past ~192. Suspects:
  (a) HBM pressure: weights (8 GB int8) + KV (~17 MB/slot int8 at the
      257-token window) + activations crowd the 16 GB chip and XLA
      falls back to a worse layout or spills;
  (b) a batch-dim tiling boundary in the attention/matmul kernels
      (B=256 crossing a lane/sublane multiple changes the MXU tiling);
  (c) the int8 KV dequant scales turning into a separately-materialized
      broadcast at larger B.

Run ALONE on the real chip:  python -m tools.probe_slot_cliff [slots...]
For each slot count: compile the decode step, report (1) per-step wall
via slope timing, (2) the compiled HLO's peak memory + largest
allocations, (3) per-step cost SPLIT into attention-only vs MLP-only
variants to localize the superlinearity.

PROBE_PAGED=1 adds a paged-mode sweep at each slot count with the pool
sized to the SAME token budget as the dense slab, so the concurrent-
streams-vs-pool-size cliff is directly comparable: the paged step adds
the block-table gather on the KV read path, and this probe prices it
against the slab at every batch size.
"""

from __future__ import annotations

import sys

import jax
import numpy as np

from seldon_tpu.models import get_config
from seldon_tpu.models.quantize import init_params_int8
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine
from tools.timing import slope_time

PROMPT, NEW = 128, 128


def probe(params, cfg, slots: int, paged: bool = False) -> None:
    # Window padded to the kv_block grid under paged mode; the pool gets
    # the dense slab's exact token budget so the sweep compares layouts,
    # not HBM sizes.
    seq = PROMPT + NEW + 1
    pkw = {}
    if paged:
        seq = (seq + 15) & ~15
        pkw = dict(paged_kv=True, kv_block=16)
    ecfg = EngineConfig(
        max_slots=slots,
        max_seq_len=seq,
        prompt_buckets=(PROMPT,),
        max_admit=8,
        decode_chunk=1,  # single steps: isolate per-step cost
        min_chunk=1,  # keep the single-step rung valid (min <= decode)
        **pkw,
    )
    eng = InferenceEngine(params, cfg, ecfg)
    eng.warmup()
    if paged:
        chunk1 = eng._jit_chunks_paged[1]
        import jax.numpy as jnp

        table = jnp.asarray(eng.table_host_snapshot())

        def step(state):
            return chunk1(params, state, table)[0]
    else:
        chunk1 = eng._jit_chunks[1]  # decode_chunk=1 -> single-step rung

        def step(state):
            return chunk1(params, state)[0]

    # Slope-fit per-step time (a host sync's fixed cost swamps
    # per-call timing; chained calls cancel it).
    sec, state = slope_time(step, eng._state)
    peak = args = None
    try:
        if paged:
            comp = chunk1.lower(params, state, table).compile()
        else:
            comp = chunk1.lower(params, state).compile()
        mem = comp.memory_analysis()
        peak = getattr(mem, "temp_size_in_bytes", None)
        args = getattr(mem, "argument_size_in_bytes", None)
    except Exception:  # memory_analysis availability varies per backend
        pass
    mode = "paged" if paged else "dense"
    print(
        f"slots={slots:4d} [{mode}]  {sec*1e3:7.2f} ms/step  "
        f"temp={peak/1e9 if peak else float('nan'):6.2f} GB  "
        f"args={args/1e9 if args else float('nan'):6.2f} GB",
        flush=True,
    )


def main() -> None:
    import os

    slots_list = [int(s) for s in sys.argv[1:]] or [96, 160, 192, 224, 256]
    preset = os.environ.get("PROBE_PRESET", "llama3-8b")  # tiny = CPU smoke
    cfg = get_config(preset, kv_cache_dtype="int8", weight_dtype="int8")
    params = init_params_int8(cfg, jax.random.key(0))
    dev = jax.devices()[0]
    print(f"device: {dev}", flush=True)
    paged_too = os.environ.get("PROBE_PAGED", "0") == "1"
    for s in slots_list:
        probe(params, cfg, s)
        if paged_too:
            probe(params, cfg, s, paged=True)


if __name__ == "__main__":
    main()
