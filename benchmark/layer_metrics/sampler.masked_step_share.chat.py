UNIT = "%"
LAYER = "model step"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Share of the window's decode steps in which the sampler ran its
    top-k / top-p mask (a sort, a softmax and a cumsum over [slots,
    vocabulary]) because a live row that samples asked for one: the
    unit's sampler counters on its access lines
    (benchmark/layer_metrics/_sampler.py). Greedy traffic reads 0
    whatever knobs its requests carry."""
    import _sampler
    return _sampler.share(obs, "sampler_masked_steps")
