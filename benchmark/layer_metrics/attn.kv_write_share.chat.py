UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mid80_ms"

FIELDS = ("attn_kv_rows_slots", "attn_kv_rows_written")


def read(obs):
    """Share of the K rows a scatter over every slot would write (slots
    x attention layers a decode step, slab and rings) that the window's
    decode steps wrote: the unit's two row counters on its access lines,
    counted on the device from the live slots and their positions
    (transformer.decode_kv_counts), last line of the window minus first
    (_access.window_delta). 100 where the step scatters the fresh token's
    row of every slot; live rows a step over slots where the
    decode-attention kernel writes the live slots' alone. A program that
    writes no such fields (the parent of PR 44) reads nothing: None."""
    import _access
    d = _access.window_delta(obs, FIELDS)
    return 100.0 * d["attn_kv_rows_written"] / d["attn_kv_rows_slots"] \
        if d else None
