"""What the readers of a Mamba-2 stack's metrics share (not a metric: no
UNIT).

A decode step has to step the state of every Mamba-2 layer, [heads, head
width, state size] float32, for the slots that hold a request (what that
needs is the family's ssm_update_cost at the live slots, _need.py). The
device ops that do it are found among `_chunk_impl`'s
(_trace.program_ops). THE KERNEL'S NAME COMES FIRST: where any op is
named `ssm_update...` (ops/ssm_update.py's pallas_call) those ops are the
update, whatever shapes their names carry (a kernel that steps the live
slots alone has no operand of the whole slab's shape). Only where none
is so named are they looked for by shape in the cleaned op name, as an
XLA-fused update over the whole slab looks: a result that is the whole
state ([Mamba-2 layers, slots, heads, head width, state size] float32:
the fusion that updates one layer of it where it lies; no other array
has that shape) or a float32 [slots, heads, head width] (the update's
output y, which XLA computes in a fusion of its own that reads the state
once more, and the step's dt x that goes into it). The held share of a
sparse layer's assignments comes from the unit's access lines
(_access.py): moe_assignments_held beside the moe_* counters _moe.py
reads. A program that has no such op or writes no such field (another
model, an older program) leaves every reader here with nothing to read:
None."""

import re

import _access
import _need
import _trace
import costs

FIELDS = ("moe_sparse_layer_steps", "moe_experts_touched", "moe_assignments",
          "moe_assignments_held")
KERNEL = re.compile(r"^ssm_update")


def state_dims(obs):
    """(Mamba-2 layers, slots, heads, head width, state size) of the cell,
    None where the configuration has no such layers."""
    cfg, fam = obs.cfg or {}, obs.family
    if not hasattr(fam, "layer_counts") or not obs.slots:
        return None
    try:
        layers = fam.layer_counts(cfg).get("mamba")
        dims = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"])
    except (KeyError, TypeError, AttributeError):
        return None
    return (layers, obs.slots) + tuple(int(d) for d in dims) if layers else None


def decode_update_ops(obs):
    """{cleaned op name: seconds in the traced slice} of the decode
    program's state updates: the ops named after the kernel where there
    are any, at whatever shape; else those of the whole slab's state
    shape and the float32 outputs beside them."""
    dims = state_dims(obs)
    if not dims:
        return {}
    ops = _trace.program_ops(obs, _trace.DECODE)
    named = {n: s for n, s in ops.items() if KERNEL.match(n)}
    if named:
        return named
    whole = "_f32_" + "_".join(str(d) for d in dims) + "_"
    out = "_f32_" + "_".join(str(d) for d in dims[1:4]) + "_"
    found = {n: s for n, s in ops.items() if whole in n}
    if found:  # the update is there: its output's fusions belong to it
        wide = re.compile(re.escape(out) + r"\d+_\d+_\d+_[^0-9]")  # layout, not a 4th dim
        found.update({n: s for n, s in ops.items() if wide.search(n) and whole not in n})
    return found


def update_roofline(obs, name, ops, layers):
    """Percent: least time the state updates of the traced slice need over
    the seconds `ops` ({name: seconds}, the decode program's update ops)
    took. Need: the family's ssm_update_cost for one layer over the slots
    that were LIVE in the same seconds (_need.rows: a live row is a live
    slot; the state of a slot that holds no request need not be stepped,
    whatever the program does), x `layers` x the slice's decode steps
    (executions of _chunk_impl x steps per chunk). None where there is
    nothing to read or the family prices no update at these key names."""
    fam = obs.family
    chunk, per = _trace.module(obs, _trace.DECODE), _trace.steps_per_dispatch(obs)
    live, whose = _need.rows(obs)
    if not ops or not layers or not chunk or not per or not live or not obs.peaks \
            or not hasattr(fam, "ssm_update_cost"):
        return None
    try:
        flops, bytes_ = fam.ssm_update_cost(obs.cfg, live)
    except (KeyError, TypeError):  # another family's key names
        return None
    least, side = costs.least_seconds(flops, bytes_, obs.peaks)
    steps = chunk["count"] * per
    need, took = least * layers * steps, sum(ops.values())
    print(f"[bench] {name}: {side}-bound, one layer's update over the {live:.3f} live "
          f"slots of the {whose} (of {obs.slots}) needs {1e6 * least:.1f} us "
          f"({flops / 1e6:.1f} MFLOP, {bytes_ / 1e6:.1f} MB) x {layers} layers x "
          f"{steps:.0f} steps = {need:.4f} s; {len(ops)} ops took {took:.4f} s: "
          + ", ".join(f"{n[:40]} {s:.4f}" for n, s in sorted(ops.items(), key=lambda kv: -kv[1])),
          flush=True)
    return 100.0 * need / took


def held(obs):
    """The window's growth of the sparse block's counters where the
    program tells the assignments held here from all (else None)."""
    return _access.window_delta(obs, FIELDS)
