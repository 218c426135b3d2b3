UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Least time the passes' grouped expert products need over their
    device time, both of the traced slice: moe.kernel_roofline.chat's
    arithmetic for a model whose decode step is a pass over block_length
    positions a slot.

    Device time: the device ops inside the decode program that carry the
    grouped kernel's name at a pass's shape, slots x block_length x
    experts per token assignment rows (_diff.block_grouped_ops). Need:
    each listed op is one of the products of the layer period, run once
    per repeat of the period in every pass of the slice (executions of
    _chunk_impl x passes per chunk), at the family's closed form for one
    product (grouped_product_cost) at the live token rows and the experts
    touched that the unit's counters saw in the SAME seconds
    (_moe.slice_delta). None when no op carries the name at that shape
    or the counters do not cover the slice."""
    import _diff
    import _moe
    import _trace
    import costs
    fam, ops = obs.family, _diff.block_grouped_ops(obs)
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
    passes = chunk["count"] * per
    repeats = fam.sparse_period_repeats(obs.cfg)
    flops, bytes_ = fam.grouped_product_cost(obs.cfg, rows, touched)
    least, side = costs.least_seconds(flops, bytes_, obs.peaks)
    need = least * passes * repeats * len(ops)
    took = sum(s for s, _ in ops)
    print(f"[bench] moe.block_kernel_roofline.chat: {side}-bound, one product needs "
          f"{1e6 * least:.1f} us ({flops / 1e6:.1f} MFLOP, {bytes_ / 1e6:.1f} MB; in the "
          f"slice token rows {rows:.2f}, experts touched {touched:.2f}), {len(ops)} listed "
          f"products x {passes:.0f} passes x {repeats} repeats need {need:.4f} s, "
          f"took {took:.4f} s", flush=True)
    return 100.0 * need / took
