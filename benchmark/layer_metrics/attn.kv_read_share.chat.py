UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mid80_ms"

FIELDS = ("attn_kv_tokens_held", "attn_kv_tokens_read")


def read(obs):
    """Share of the KV the slab holds for the attention layers (slots x
    window x attention layers) that the window's decode steps fetched:
    the unit's two attention counters on its access lines, counted on
    the device from the live slots and their positions
    (transformer.decode_kv_counts), last line of the window minus first
    (_access.window_delta). 100 where the einsums score every slot's
    whole window; the live rows' share, in whole blocks, where the
    decode-attention kernel reads them alone. A program that writes no
    such fields (the parent of PR 37) reads nothing: None."""
    import _access
    d = _access.window_delta(obs, FIELDS)
    return 100.0 * d["attn_kv_tokens_read"] / d["attn_kv_tokens_held"] \
        if d else None
