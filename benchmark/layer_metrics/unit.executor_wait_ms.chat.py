UNIT = "ms"
LAYER = "unit (REST hop)"
MOVES = "ttft_mid80_ms"


def read(obs):
    """Transport handler had the request -> engine.submit(): the wait for one
    of the REST wrapper's executor threads (access log, window requests)."""
    import _access
    return _access.mid80(obs, "executor_wait_ms")
