UNIT = "%"
LAYER = "unit (REST hop)"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Responses shorter than asked (a sampled EOS: the unit has no ignore_eos)."""
    return obs.short_share
