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


def window_delta(obs):
    """The counters' growth over the measured window: last line minus
    first of the window's requests, in the order they ended."""
    rows = [r for r in _access.window(obs) or ()
            if all(isinstance(r.get(f), (int, float)) for f in FIELDS)]
    if len(rows) < 2:
        return None
    rows.sort(key=lambda r: r[FIELDS[0]])
    d = {f: rows[-1][f] - rows[0][f] for f in FIELDS}
    return d if d[FIELDS[0]] > 0 else None


def share(obs, field):
    """Percent of the window's decode steps counted under `field`."""
    d = window_delta(obs)
    return 100.0 * d[field] / d["sampler_steps"] if d else None
