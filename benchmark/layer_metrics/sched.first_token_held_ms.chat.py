UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_mid80_ms"


def read(obs):
    """First token on the host -> handed to the request's stream: the wait for
    the decode chunk it is delivered with (access log, window requests)."""
    import _access
    return _access.mid80(obs, "first_token_held_ms")
