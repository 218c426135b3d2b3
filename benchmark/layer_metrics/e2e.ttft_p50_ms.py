UNIT = "ms"
LAYER = "end to end"
MOVES = "ttft_mid80_ms"


def read(obs):
    import stats
    return stats.percentile(obs.ttft_ms, 50) if obs.ttft_ms else None
