UNIT = "ms"
LAYER = "end to end"
MOVES = "ttft_mid80_ms"


def read(obs):
    """The client's TTFT minus the four phases the program stamps, each as
    its 10 %-trimmed mean: the hop, delivery to the socket and how late
    the generator ran. Trimmed means do not add exactly; far outside
    -10..30 ms a phase is unstamped or a clock is wrong."""
    import _access
    import stats
    inside = [_access.mid80(obs, k) for k in _access.PHASES]
    if any(v is None for v in inside) or not obs.ttft_ms:
        return None
    return stats.trimmed_mean(obs.ttft_ms) - sum(inside)
