UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Least time the decode steps of the traced slice need over the
    device time of the decode program in the same slice.

    Need: the family's decode_step_cost at what the slice's traffic
    required (_need.py: the live rows and the experts their sparse layers
    read, by the unit's counters between the slice's two ends; the
    window's where the access lines stop short of it, and the line below
    says which), at the mean live context of the window's requests, by
    the v5e's peaks, for every decode step of the slice (executions of
    _chunk_impl x steps per chunk). Time: the total device seconds of
    _chunk_impl in the slice. A need at the window's mean rows over the
    median chunk of a slice read high by itself wherever a slice ran
    lighter than its window."""
    import inspect

    import _need
    import _trace
    import costs
    fam = obs.family
    chunk, per = _trace.module(obs, _trace.DECODE), _trace.steps_per_dispatch(obs)
    ctx = _trace.mean_live_context(obs)
    rows, whose = _need.rows(obs)
    if not chunk or not per or not rows or ctx is None or not obs.peaks or not fam:
        return None
    more, experts = {}, "no sparse layer priced"
    if "touched" in inspect.signature(fam.decode_step_cost).parameters:
        touched, its = _need.touched(obs)
        more = {"touched": touched}
        experts = "experts by the closed form" if touched is None else \
            f"{touched:.2f} experts a sparse layer counted in the {its}"
    flops, bytes_ = fam.decode_step_cost(obs.cfg, rows, ctx, **more)
    least, side = costs.least_seconds(flops, bytes_, obs.peaks)
    steps = chunk["count"] * per
    need, took = least * steps, chunk["total_s"]
    print(f"[bench] step.decode_roofline: {side}-bound, a step needs {1e3 * least:.3f} ms "
          f"({flops / 1e9:.1f} GFLOP, {bytes_ / 1e9:.2f} GB) at {rows:.3f} rows of the "
          f"{whose}, {experts}, context {ctx:.1f}; x {steps:.0f} steps of the slice = "
          f"{need:.4f} s, the decode program took {took:.4f} s "
          f"({1e3 * took / steps:.3f} ms a step; window's rows {obs.rows_per_step!r})",
          flush=True)
    return 100.0 * need / took
