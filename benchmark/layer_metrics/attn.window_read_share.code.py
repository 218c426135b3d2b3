UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mid80_ms"

FIELDS = ("attn_window_tokens_unwindowed", "attn_window_tokens_read")


def read(obs):
    """KV tokens the sliding-window layers' decode steps fetched over
    what the same live rows' positions hold (what those layers would
    read with no window): the unit's counters by attention kind on its
    access lines, counted on the device from the live slots and their
    positions (transformer.decode_kv_counts), last line of the window
    minus first (_access.window_delta). The decode-attention kernel reads
    whole blocks of min(position, 512) tokens of a ring: about a third at
    this mix's contexts; off a TPU the einsums read every slot's whole
    ring. A program that writes no such fields (one without the window
    kind) reads nothing: None."""
    import _access
    d = _access.window_delta(obs, FIELDS)
    return 100.0 * d["attn_window_tokens_read"] / d["attn_window_tokens_unwindowed"] \
        if d else None
