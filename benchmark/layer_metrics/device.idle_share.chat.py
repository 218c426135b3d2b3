UNIT = "%"
LAYER = "device"
MOVES = "tpot_mid80_ms"


def read(obs):
    """1 - union of device-op intervals over the traced slice of the window."""
    import _trace
    return _trace.idle_share(obs)
