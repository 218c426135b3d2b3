UNIT = "s"
LAYER = "unit (REST hop)"
MOVES = "setup_s"


def read(obs):
    """The unit's process start -> its weights on the device: interpreter,
    imports, device init, building or loading the weights (start-up line)."""
    import _access
    return _access.startup(obs, "weights_ready_s")
