"""From the operations and bytes the algorithm NEEDS for one call to the
least time the chip could take for it.

What a call needs is computed from shapes by the configuration's family
(benchmark/families/<family>.py: decode_step_cost and the closed forms
beneath it), at what the requests need (live rows, live context, the
experts routed to), not at what the engine dispatches; the peaks are
benchmark/peaks.py. What is the same for every architecture is here."""

from __future__ import annotations

from typing import Dict, Tuple


def least_seconds(flops: float, bytes_: float, peaks: Dict[str, float]) -> Tuple[float, str]:
    """Roofline time and the side that binds."""
    tc = flops / peaks["bf16_flops"]  # activations are bf16: the bf16 peak
    tm = bytes_ / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
