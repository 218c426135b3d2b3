UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Least time the passes' decode attention needs over the device time
    of the decode-attention kernel, both of the traced slice.

    Device time: every device op inside the decode program that carries
    the kernel's name (seldon_tpu/ops/decode_attention.py, at block_length
    query positions a slot). Need: the family's attention_cost of the KV
    tokens the layers read, the K rows the committing slots wrote and the
    (slot, pass) pairs run in the SAME seconds, by the unit's counters
    (_access.slice_delta). Low by design at short contexts: a call costs a
    few microseconds a layer whatever it reads. None where no op carries
    the name, the family has no such closed form or the counters do not
    cover the slice."""
    import _access
    import _diff
    import costs
    fam, ops = obs.family, _diff.attention_ops(obs)
    if not ops or not obs.peaks or not hasattr(fam, "attention_cost") \
            or not _diff.block_length(obs):
        return None
    d = _access.slice_delta(obs, _diff.ATTN)
    if not d:
        return None
    flops, bytes_ = fam.attention_cost(
        obs.cfg, d["attn_kv_tokens_read"], d["attn_kv_rows_written"],
        d["diff_slot_passes"])
    need, side = costs.least_seconds(flops, bytes_, obs.peaks)
    took = sum(ops.values())
    print(f"[bench] diff.attn_roofline.chat: {side}-bound, {d['diff_slot_passes']:.0f} "
          f"slot passes read {d['attn_kv_tokens_read']:.0f} KV tokens and wrote "
          f"{d['attn_kv_rows_written']:.0f} rows: need {need:.5f} s "
          f"({flops / 1e9:.2f} GFLOP, {bytes_ / 1e9:.3f} GB), {len(ops)} ops took "
          f"{took:.5f} s", flush=True)
    return 100.0 * need / took
