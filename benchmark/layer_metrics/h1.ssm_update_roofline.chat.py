UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Least time the decode steps' state updates of a stack whose layers
    each hold a Mamba-2 mixer beside an attention need over their device
    time, both of the traced slice.

    Device time: every device op of the decode program that carries the
    update kernel's name (`ssm_update...` among _trace.program_ops of
    `_chunk_impl`; _ssm.KERNEL). Need: the family's closed form for one
    layer's update over the slots the program holds
    (families/falcon_h1.py ssm_update_cost: the state read and written
    once, x, B, C, dt in, y out; the dense slab steps every slot, live or
    not: the same work as ssm.update_roofline.chat counts, at this
    family's key names), for every layer that holds such a state
    (layer_counts "mamba") in every decode step of the slice (steps =
    executions of _chunk_impl x steps per chunk). None where no op
    carries the name (a program whose update is no kernel, another
    model) or the family prices no update."""
    import _ssm
    import _trace
    import costs
    fam = obs.family
    ops = {n: s for n, s in _trace.program_ops(obs, _trace.DECODE).items()
           if _ssm.KERNEL.match(n)}
    chunk, per = _trace.module(obs, _trace.DECODE), _trace.steps_per_dispatch(obs)
    if not ops or not chunk or not per or not obs.peaks or not obs.slots or \
            not hasattr(fam, "ssm_update_cost") or not hasattr(fam, "layer_counts"):
        return None
    try:
        layers = fam.layer_counts(obs.cfg).get("mamba")
        flops, bytes_ = fam.ssm_update_cost(obs.cfg, obs.slots)
    except (KeyError, TypeError):  # another family's key names
        return None
    if not layers:
        return None
    least, side = costs.least_seconds(flops, bytes_, obs.peaks)
    steps = chunk["count"] * per
    need, took = least * layers * steps, sum(ops.values())
    print(f"[bench] h1.ssm_update_roofline.chat: {side}-bound, one layer's update over "
          f"{obs.slots} slots needs {1e6 * least:.1f} us ({flops / 1e6:.1f} MFLOP, "
          f"{bytes_ / 1e6:.1f} MB) x {layers} layers x {steps:.0f} steps = {need:.4f} s; "
          f"{len(ops)} ops took {took:.4f} s: "
          + ", ".join(f"{n[:40]} {s:.4f}" for n, s in sorted(ops.items(), key=lambda kv: -kv[1])),
          flush=True)
    return 100.0 * need / took
