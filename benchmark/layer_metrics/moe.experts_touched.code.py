UNIT = "experts"
LAYER = "model step"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Mean number of distinct experts (of the configuration's 256) a
    sparse layer reads for live rows in one decode step, over the window:
    the unit's routing counters on its access lines
    (benchmark/layer_metrics/_moe.py). 8 a live row at most; a step that
    routed dead slab rows too would read nearly all of them."""
    import _moe
    return _moe.experts_touched(obs)
