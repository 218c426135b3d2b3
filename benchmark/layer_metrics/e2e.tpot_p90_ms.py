UNIT = "ms"
LAYER = "end to end"
MOVES = "tpot_mid80_ms"


def read(obs):
    """The tail beside the judged trimmed mean (reported, not bounded)."""
    import stats
    return stats.percentile(obs.tpot_ms, 90) if obs.tpot_ms else None
