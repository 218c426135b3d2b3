UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_mid80_ms"


def read(obs):
    """engine.submit() -> the scheduler dispatched the request's admission
    (access log, window requests)."""
    import _access
    return _access.mid80(obs, "queue_wait_ms")
