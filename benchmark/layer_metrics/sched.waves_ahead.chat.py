UNIT = "count"
LAYER = "scheduler"
MOVES = "ttft_mid80_ms"


def read(obs):
    """Mean number of waves dispatched and not yet retired when a window
    request's admission was dispatched (access log)."""
    import _access
    xs = [r["waves_ahead"] for r in _access.window(obs) or ()
          if isinstance(r.get("waves_ahead"), (int, float))]
    return sum(xs) / len(xs) if xs else None
