"""What the readers of a Mamba-2 stack's metrics share (not a metric: no
UNIT).

The decode program steps every Mamba-2 layer's state, [slots, heads, head
width, state size] float32, for every slot of the slab on every step. Its
device ops are found among `_chunk_impl`'s (_trace.program_ops): by the
kernel's name where the update is a Pallas kernel (`ssm_update...`), else
by shape in the cleaned op name: a result that is the whole state
([Mamba-2 layers, slots, heads, head width, state size] float32: the
fusion that updates one layer of it where it lies; no other array has
that shape) or a float32 [slots, heads, head width] (the update's output
y, which XLA computes in a fusion of its own that reads the state once
more, and the step's dt x that goes into it). The held share of a sparse
layer's assignments comes from the unit's access lines (_access.py):
moe_assignments_held beside the moe_* counters _moe.py reads. A program
that has no such op or writes no such field (another model, an older
program) leaves every reader here with nothing to read: None."""

import re

import _access
import _trace

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
    program's state updates."""
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


def held(obs):
    """The window's growth of the sparse block's counters where the
    program tells the assignments held here from all (else None)."""
    return _access.window_delta(obs, FIELDS)
