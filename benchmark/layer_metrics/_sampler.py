"""What the readers of the sampler's tiers share (not a metric: no UNIT).

The unit's `request {json}` access lines (unit.log, see _access.py) carry
the engine's running sampler counters as each request ended:
sampler_steps (decode steps whose tier the device reported),
sampler_drawn_steps (steps in which some live row sampled, so the batch
paid the divide and the Gumbel draw) and sampler_masked_steps (drawn
steps in which a sampling row asked for top-k / top-p, so the batch paid
the full-vocabulary sort, softmax and cumsum as well). A program that
writes no such fields (an older program) leaves every reader here with
nothing to read: None."""

import _access

FIELDS = ("sampler_steps", "sampler_drawn_steps", "sampler_masked_steps")


def share(obs, field):
    """Percent of the window's decode steps counted under `field`."""
    d = _access.window_delta(obs, FIELDS)
    return 100.0 * d[field] / d["sampler_steps"] if d else None
