UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Least time the decode steps' state updates of a stack whose layers
    each hold a Mamba-2 mixer beside an attention need over their device
    time, both of the traced slice.

    Device time: every device op of the decode program that carries the
    update kernel's name (`ssm_update...` among _trace.program_ops of
    `_chunk_impl`; _ssm.KERNEL). Need (_ssm.update_roofline): the family's
    closed form for one layer's update (families/falcon_h1.py
    ssm_update_cost: the same work as ssm.update_roofline.chat counts, at
    this family's key names) over the slots that were LIVE in the same
    seconds, for every layer that holds such a state (layer_counts
    "mamba") in every decode step of the slice. None where no op carries
    the name (a program whose update is no kernel, another model) or the
    family prices no update."""
    import _ssm
    import _trace
    fam = obs.family
    if not hasattr(fam, "layer_counts"):
        return None
    ops = {n: s for n, s in _trace.program_ops(obs, _trace.DECODE).items()
           if _ssm.KERNEL.match(n)}
    try:
        layers = fam.layer_counts(obs.cfg).get("mamba")
    except (KeyError, TypeError):  # another family's key names
        return None
    return _ssm.update_roofline(obs, "h1.ssm_update_roofline.chat", ops, layers)
