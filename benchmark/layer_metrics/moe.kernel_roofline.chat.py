UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Least time the decode steps' grouped expert products need over
    their device time, both of the traced slice.

    Device time: every device op that ran inside the decode program and
    carries the grouped kernel's name at that program's shape
    (_moe.decode_grouped_ops, by name from the reduction's table of all
    ops by program: where the kernel ranks among them does not matter).
    Need: each listed op is one of the products of one position of the
    layer period, run once per repeat of the period in every decode step
    of the slice (steps = executions of _chunk_impl x steps per chunk;
    repeats = sparse layers x 3 / distinct decode products, which the
    family's layer pattern gives), at the family's closed form for one
    product (families/lfm2.py grouped_product_cost: live rows x k rows
    through one matrix, the matrices of the experts touched read once).
    Rows and experts touched are those of the SAME seconds, by the unit's
    counters (_moe.slice_delta): the experts a step reads follow its live
    rows, which a slice of three seconds holds a quarter more or fewer of
    than the window does, and a need reckoned at the window's mean over a
    time taken in the slice read 79 to 107 %. None when no op
    carries the name or the counters do not cover the slice."""
    import _moe
    import _trace
    import costs
    fam, ops = obs.family, _moe.decode_grouped_ops(obs)
    chunk, per = _trace.module(obs, _trace.DECODE), _trace.steps_per_dispatch(obs)
    k = (obs.cfg or {}).get("num_experts_per_tok")
    if not ops or not chunk or not per or not k or not obs.peaks or \
            not hasattr(fam, "grouped_product_cost"):
        return None
    d = _moe.slice_delta(obs)
    if not d:
        return None
    layer_steps = d["moe_sparse_layer_steps"]
    rows = d["moe_assignments"] / layer_steps / k
    touched = d["moe_experts_touched"] / layer_steps
    steps = chunk["count"] * per
    repeats = fam.sparse_period_repeats(obs.cfg)
    flops, bytes_ = fam.grouped_product_cost(obs.cfg, rows, touched)
    least, side = costs.least_seconds(flops, bytes_, obs.peaks)
    need = least * steps * repeats * len(ops)
    took = sum(s for s, _ in ops)
    print(f"[bench] moe.kernel_roofline.chat: {side}-bound, one product needs "
          f"{1e6 * least:.1f} us ({flops / 1e6:.1f} MFLOP, {bytes_ / 1e6:.1f} MB; in the "
          f"slice rows {rows:.2f}, experts touched {touched:.2f}), {len(ops)} listed "
          f"decode products x {steps:.0f} steps (the counters saw "
          f"{layer_steps / fam.layer_counts(obs.cfg)["sparse"]:.1f}) x {repeats} repeats need "
          f"{need:.4f} s, took {took:.4f} s", flush=True)
    return 100.0 * need / took
