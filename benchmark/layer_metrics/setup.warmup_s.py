UNIT = "s"
LAYER = "compile"
MOVES = "setup_s"


def read(obs):
    return obs.warmup_s
