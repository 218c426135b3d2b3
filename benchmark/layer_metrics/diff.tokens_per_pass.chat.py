UNIT = "tokens"
LAYER = "model step"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Tokens one live slot's pass emits, over the window: the growth of
    the unit's diff_tokens_out over that of diff_slot_passes on its
    access lines (_diff.py). block_length / (denoise_steps + 1) where
    every block is whole: a prompt's tail, a budget off the block and an
    EOS make it less. A program that writes no such fields reads nothing:
    None."""
    import _diff
    return _diff.tokens_per_pass(obs)
