import re

UNIT = "%"
LAYER = "model step"
MOVES = "tpot_mid80_ms"

KERNELS = re.compile(r"^(ssm_update|decode_attention)")


def read(obs):
    """Share of the decode program's device time that its two mixers'
    kernels take, both of the traced slice: the device ops of
    `_chunk_impl` named `ssm_update...` (the Mamba-2 state update) and
    `decode_attention...` (attention over the live rows of the slab),
    over the device time of the program's executions (the trace's
    "XLA Modules" events). It says whether the block that sets this stack
    apart is where a step goes, or its projections, feed-forward and
    head. None where the decode program ran neither kernel in the slice
    (another model, or the jax.numpy branches off a TPU)."""
    import _trace
    ops = {n: s for n, s in _trace.program_ops(obs, _trace.DECODE).items()
           if KERNELS.match(n)}
    chunk = _trace.module(obs, _trace.DECODE)
    if not ops or not chunk or not chunk["total_s"]:
        return None
    took = sum(ops.values())
    print(f"[bench] h1.mixer_share.chat: {len(ops)} kernels took {took:.4f} s of the decode "
          f"program's {chunk['total_s']:.4f} s: "
          + ", ".join(f"{n[:32]} {s:.4f}" for n, s in sorted(ops.items(), key=lambda kv: -kv[1])),
          flush=True)
    return 100.0 * took / chunk["total_s"]
