UNIT = "ms"
LAYER = "model step"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Device time of one decode step (profiler trace, program _chunk_impl)."""
    import _trace
    s = _trace.decode_step_s(obs)
    return 1000.0 * s if s else None
