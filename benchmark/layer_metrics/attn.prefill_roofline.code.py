import re

UNIT = "%"
LAYER = "kernels"
MOVES = "ttft_mid80_ms"

# the kernel's pallas_call, as benchmark/xplane.py cleans an XLA Ops event:
# "%prefill_attention.3 = bf16[...] custom-call(" -> "prefill_attention.3_bf16_..."
KERNEL_OP = re.compile(r"^prefill_attention(\.\d+)?_")
MIN_ADMISSIONS = 3


def read(obs):
    """Least time the traced slice's prefill attention needs at the
    matrix unit's bf16 peak over the device time of the prefill-attention
    kernel, both of the traced slice.

    Device time: every device op that ran inside the admission program
    and carries the kernel's name (seldon_tpu/ops/prefill_attention.py,
    `prefill_attention`), whatever its rank. Need: for each request whose
    admission ran in the slice, the family's closed form for the FLOPs of
    one prompt's attention products at the prompt's own length
    (families/laguna.py prefill_attention_flops: causal pairs on the full
    layers, pairs inside the window on the sliding ones, each at its
    kind's head count). What the kernel multiplies beyond that (a bucket's
    padding inside a row's last block, the masked part of a block on the
    diagonal or at the band's edge) is time with no need beside it. None
    where the slice holds fewer than MIN_ADMISSIONS admissions, no op
    carries the name, or the family has no such closed form."""
    import _trace
    fam = obs.family
    lens = _trace.prefilled_in_slice(obs)
    ops = {n: s for n, s in _trace.program_ops(obs, _trace.ADMIT).items()
           if KERNEL_OP.match(n)}
    if len(lens) < MIN_ADMISSIONS or not ops or not obs.peaks \
            or not hasattr(fam, "prefill_attention_flops"):
        return None
    flops = sum(fam.prefill_attention_flops(obs.cfg, n) for n in lens)
    need, took = flops / obs.peaks["bf16_flops"], sum(ops.values())
    print(f"[bench] attn.prefill_roofline.code: {len(lens)} admissions of "
          f"{sum(lens)} prompt tokens need {flops / 1e9:.1f} GFLOP = {need:.4f} s at the "
          f"bf16 peak; {len(ops)} ops took {took:.4f} s: "
          + ", ".join(f"{n[:40]} {s:.4f}" for n, s in sorted(ops.items(), key=lambda kv: -kv[1])),
          flush=True)
    return 100.0 * need / took
