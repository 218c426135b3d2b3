"""What the trace-reading metrics share (not a metric: no UNIT)."""


# The engine's programs by their names in the trace; until the program
# names its jits, by how often they ran (see benchmark/xplane.py).
DECODE = ("_chunk_impl", "unnamed_most_run")
ADMIT = ("_admit_impl", "unnamed_other")


def module(obs, names):
    tr = obs.trace
    for n in names if tr else ():
        if n in tr["modules"]:
            return tr["modules"][n]
    return None


def program_ops(obs, names):
    """{cleaned op name: seconds} of one program's device
    ops in the traced slice (xplane's ops_by_program), every op whatever
    its rank; {} where the trace holds no such program."""
    by = (obs.trace or {}).get("ops_by_program") or {}
    return next((by[n] for n in names if n in by), {})


def steps_per_dispatch(obs):
    return obs.decode_steps / obs.decode_dispatches if obs.decode_dispatches else None


def decode_step_s(obs):
    """Device seconds of one decode step: the median execution of the
    decode-chunk program over the steps a chunk holds."""
    m, n = module(obs, DECODE), steps_per_dispatch(obs)
    return m["median_s"] / n if m and n else None


def prefilled_in_slice(obs):
    """Prompt lengths of the requests whose first token fell inside the
    traced slice: their admission ran there."""
    tr = obs.trace
    if not tr:
        return []
    a, b = tr["slice"]
    return [r.req.prompt_len for r in obs.all_results or ()
            if r.ok and r.first is not None and a <= r.first <= b]


def prefill_ms_per_ktok(obs):
    m, lens = module(obs, ADMIT), prefilled_in_slice(obs)
    return 1e6 * m["total_s"] / sum(lens) if m and lens else None


def mean_live_context(obs):
    """Mean context a decode step attends over, weighted by the steps each
    sampled request decodes: prompt + half of its output."""
    ok = [r for r in obs.samples or () if r.ok and r.tokens]
    steps = sum(len(r.tokens) for r in ok)
    return sum(len(r.tokens) * (r.req.prompt_len + len(r.tokens) / 2.0)
               for r in ok) / steps if steps else None


def idle_share(obs):
    """1 - union of device-op intervals over the traced slice, in percent."""
    tr = obs.trace
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
