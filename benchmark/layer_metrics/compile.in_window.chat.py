UNIT = "count"
LAYER = "compile"
MOVES = "ttft_mid80_ms"


def read(obs):
    """First dispatches (compile or cache load) between warm-up and drain.
    Any at all makes the run incorrect; the clean reading is 0."""
    return obs.compile_in_window
