UNIT = "ms"
LAYER = "unit (REST hop)"
MOVES = "ttft_mid80_ms"


def read(obs):
    import stats
    return stats.percentile(obs.hop_ms, 50) if obs.hop_ms else None
