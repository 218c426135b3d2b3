UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Least time the decode steps' Mamba-2 state updates need over their
    device time, both of the traced slice.

    Device time: every device op of the decode program that updates the
    state (_ssm.decode_update_ops: by the kernel's name, else by the
    state's shape). Need: the family's closed form for one layer's update
    over the slots the program holds (families/nemotron_h.py
    ssm_update_cost: the state read and written once, x, B, C, dt in, y
    out; the dense slab steps every slot, live or not), for every Mamba-2
    layer in every decode step of the slice (steps = executions of
    _chunk_impl x steps per chunk). None where no op carries the name or
    the shape."""
    import _ssm
    import _trace
    import costs
    fam, ops = obs.family, _ssm.decode_update_ops(obs)
    chunk, per = _trace.module(obs, _trace.DECODE), _trace.steps_per_dispatch(obs)
    if not ops or not chunk or not per or not obs.peaks or \
            not hasattr(fam, "ssm_update_cost"):
        return None
    layers = _ssm.state_dims(obs)[0]
    flops, bytes_ = fam.ssm_update_cost(obs.cfg, obs.slots)
    least, side = costs.least_seconds(flops, bytes_, obs.peaks)
    steps = chunk["count"] * per
    need, took = least * layers * steps, sum(ops.values())
    print(f"[bench] ssm.update_roofline.chat: {side}-bound, one layer's update over "
          f"{obs.slots} slots needs {1e6 * least:.1f} us ({flops / 1e6:.1f} MFLOP, "
          f"{bytes_ / 1e6:.1f} MB) x {layers} layers x {steps:.0f} steps = {need:.4f} s; "
          f"{len(ops)} ops took {took:.4f} s: "
          + ", ".join(f"{n[:40]} {s:.4f}" for n, s in sorted(ops.items(), key=lambda kv: -kv[1])),
          flush=True)
    return 100.0 * need / took
