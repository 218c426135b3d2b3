UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Least time the decode steps' Mamba-2 state updates need over their
    device time, both of the traced slice.

    Device time: every device op of the decode program that updates the
    state (_ssm.decode_update_ops: by the kernel's name, else by the
    state's shape). Need (_ssm.update_roofline): the family's closed form
    for one layer's update (families/nemotron_h.py ssm_update_cost: the
    state read and written once, x, B, C, dt in, y out) over the slots
    that were LIVE in the same seconds, for every Mamba-2 layer in every
    decode step of the slice. None where no op carries the name or the
    shape."""
    import _ssm
    dims = _ssm.state_dims(obs)
    return _ssm.update_roofline(obs, "ssm.update_roofline.chat",
                                _ssm.decode_update_ops(obs), dims[0]) if dims else None
