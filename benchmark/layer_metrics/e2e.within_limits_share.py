UNIT = "%"
LAYER = "end to end"
MOVES = "ttft_mid80_ms"


def read(obs):
    """Share of requests sent whose TTFT and TPOT are within the limits of
    the traffic file (a failed request misses)."""
    lim = (obs.spec or {}).get("limit")
    if not lim or not obs.samples:
        return None
    import metrics
    ok = 0
    for r in obs.samples:
        tt = metrics.ttft_ms([r])[0]
        tp = metrics.tpot_ms([r])
        ok += tt <= lim["ttft_ms"] and (not tp or tp[0] <= lim["tpot_ms"])
    return 100.0 * ok / len(obs.samples)
