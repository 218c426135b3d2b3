UNIT = "experts"
LAYER = "model step"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Mean number of distinct experts (of the configuration's
    num_experts) a sparse layer reads in one PASS of a model that
    generates by diffusion over blocks, over the window: each live slot
    routes block_length positions, so one slot alone touches about
    block_length x experts per token of them. The unit's routing counters
    on its access lines (_moe.py). None for a model whose step is not a
    pass."""
    import _diff
    import _moe
    return _moe.experts_touched(obs) if _diff.block_length(obs) else None
