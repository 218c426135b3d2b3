UNIT = "%"
LAYER = "model step"
MOVES = "tpot_mid80_ms"

FIELDS = ("sampler_steps", "diff_rows_scored")


def read(obs):
    """Share of a pass's rows (slots x block_length) that the head, the
    sampler and the confidence scored, over the window: the growth of
    the unit's diff_rows_scored over that of sampler_steps (the passes)
    x slots x block_length, on its access lines (_access.py). A pass
    scores the slots that hold an undecided position, moved to the
    front: none, a fixed few or every slot by their count
    (models/slot.block_step), so 100 x few / slots less the passes that
    scored nothing while the slab holds few requests. A program that
    writes no such field (one that scores every row in every pass, or
    another model) reads nothing: None."""
    import _access
    import _diff
    bk = _diff.block_length(obs)
    d = _access.window_delta(obs, FIELDS)
    if not d or not bk or not obs.slots:
        return None
    return 100.0 * d["diff_rows_scored"] / (
        d["sampler_steps"] * obs.slots * bk)
