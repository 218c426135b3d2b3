UNIT = "ms"
LAYER = "model step"
MOVES = "ttft_mid80_ms"


def read(obs):
    """Device time of the admission (prefill) program per 1000 real prompt
    tokens admitted in the traced slice (profiler trace, _admit_impl)."""
    import _trace
    return _trace.prefill_ms_per_ktok(obs)
