UNIT = "ms"
LAYER = "end to end"
MOVES = "ttft_mid80_ms"


def read(obs):
    """The tail beside the judged trimmed mean: at 51-71 requests a window
    5-7 of them set it, so it is reported, not bounded."""
    import stats
    return stats.percentile(obs.ttft_ms, 90) if obs.ttft_ms else None
