UNIT = "rows"
LAYER = "scheduler"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Live rows per decode step: (tokens out - first tokens) / decode steps,
    from the unit's /metrics counters over lead-in, window and tail."""
    return obs.rows_per_step
