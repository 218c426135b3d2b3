UNIT = "experts"
LAYER = "model step"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Mean number of distinct experts (of the configuration's
    num_local_experts) a sparse block of the homogeneous stack reads for
    live rows in one decode step, over the window: the same routing
    counters, on the same access lines, that moe.experts_touched.chat
    reads for the patterned stack (benchmark/layer_metrics/_moe.py). A
    program whose sparse block runs every expert writes no such fields:
    nothing to read."""
    import _moe
    return _moe.experts_touched(obs)
