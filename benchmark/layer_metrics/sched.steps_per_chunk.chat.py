UNIT = "steps"
LAYER = "scheduler"
MOVES = "ttft_mid80_ms"


def read(obs):
    """Decode steps per dispatched chunk: decode steps / decode dispatches,
    from the unit's /metrics counters over lead-in, window and tail. The
    length of the chunk the scheduler runs while slots are free: a first
    token's queue, its wait behind the chunk ahead and its hold are each
    that many steps."""
    import _trace
    return _trace.steps_per_dispatch(obs)
