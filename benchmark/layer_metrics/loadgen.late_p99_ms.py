UNIT = "ms"
LAYER = "load generator"
MOVES = "ttft_mid80_ms"


def read(obs):
    """How late the generator sent, against when each request was due."""
    import stats
    return stats.percentile(obs.late_ms, 99) if obs.late_ms else None
