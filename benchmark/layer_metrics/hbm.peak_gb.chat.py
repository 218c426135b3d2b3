UNIT = "GB"
LAYER = "block pool / HBM"
MOVES = "tpot_mid80_ms"


def read(obs):
    """The allocator's peak_bytes_in_use after the window (/metadata)."""
    return obs.memory_peak_bytes / 1e9 if obs.memory_peak_bytes else None
