UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_mid80_ms"


def read(obs):
    """First dispatch -> the admission's first token on the host: the device's
    queue of dispatched waves plus the prefill itself (access log)."""
    import _access
    return _access.mid80(obs, "device_wait_ms")
