"""What the readers of the sparse block's metrics share (not a metric: no
UNIT).

The unit's `request {json}` access lines (unit.log, see _access.py) carry
the engine's running routing counters as each request ended, for a model
that dispatches tokens to experts: moe_sparse_layer_steps (sparse layers
run over all decode steps), moe_experts_touched (distinct experts those
layers read for live rows, summed) and moe_assignments. A program that
writes no such fields (another model, an older program) leaves every
reader here with nothing to read: None."""

import re

import _access
import _trace

FIELDS = ("moe_sparse_layer_steps", "moe_experts_touched", "moe_assignments")
# megablox's pallas_call, as benchmark/xplane.py cleans an XLA Ops event:
# "%gmm.3 = bf16[256,1536]{...} custom-call(" -> "gmm.3_bf16_256_1536_..."
GROUPED_OP = re.compile(r"^gmm(\.\d+)?_bf16_(\d+)_(\d+)_")


def experts_touched(obs):
    """Mean distinct experts one sparse layer reads per decode step."""
    d = _access.window_delta(obs, FIELDS)
    return d["moe_experts_touched"] / d["moe_sparse_layer_steps"] if d else None


def slice_delta(obs):
    """The routing counters' growth over the TRACED SLICE, the seconds
    the device times of obs.trace come from (_access.slice_delta)."""
    return _access.slice_delta(obs, FIELDS)


def decode_grouped_ops(obs):
    """[(seconds in the traced slice, output columns)] of the grouped
    expert products of the DECODE program: found by name among all the
    device ops that ran inside that program (_trace.program_ops), whatever
    their rank, at the row count of the slab's assignment list (slots x
    experts per token). An admission's products are another program's,
    also where its shortest group has as many rows and XLA numbers the
    instruction alike."""
    k = (obs.cfg or {}).get("num_experts_per_tok")
    if not k or not obs.slots:
        return []
    out = []
    for name, seconds in _trace.program_ops(obs, _trace.DECODE).items():
        m = GROUPED_OP.match(name)
        if m and int(m.group(2)) == obs.slots * k:
            out.append((seconds, int(m.group(3))))
    return out
