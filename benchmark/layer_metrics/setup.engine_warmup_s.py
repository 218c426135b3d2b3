UNIT = "s"
LAYER = "compile"
MOVES = "setup_s"


def read(obs):
    """Seconds in the engine's own warmup() at load (start-up line); 0 for a
    unit started without it, whose variants compile in setup.warmup_s."""
    import _access
    return _access.startup(obs, "warmup_s")
