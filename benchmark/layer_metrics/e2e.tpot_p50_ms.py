UNIT = "ms"
LAYER = "end to end"
MOVES = "tpot_mid80_ms"


def read(obs):
    import stats
    return stats.percentile(obs.tpot_ms, 50) if obs.tpot_ms else None
