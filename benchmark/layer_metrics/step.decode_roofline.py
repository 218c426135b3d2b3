UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Least time a decode step needs (the family's decode_step_cost: live
    rows, live context, what the step has to read; v5e peaks) over its
    device time."""
    import _trace
    import costs
    step, ctx = _trace.decode_step_s(obs), _trace.mean_live_context(obs)
    if not step or not obs.rows_per_step or ctx is None or not obs.peaks or not obs.family:
        return None
    flops, bytes_ = obs.family.decode_step_cost(obs.cfg, obs.rows_per_step, ctx)
    least, side = costs.least_seconds(flops, bytes_, obs.peaks)
    print(f"[bench] step.decode_roofline: {side}-bound, least {1e3 * least:.3f} ms "
          f"({flops / 1e9:.1f} GFLOP, {bytes_ / 1e9:.2f} GB) vs {1e3 * step:.3f} ms "
          f"(rows {obs.rows_per_step!r}, context {ctx!r}, step {step!r} s)", flush=True)
    return 100.0 * least / step
