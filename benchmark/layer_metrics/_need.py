"""What the readers of a roofline's NEED share (not a metric: no UNIT).

A need prices what the traffic required, whatever program serves it: the
fixed-size state of the slots that were live, the experts their rows
chose, the KV they attended; and it is counted in the seconds its device
time comes from, the traced slice. The unit counts all of it on the
device and every `request {json}` access line (_access.py) carries the
running sums: attn_kv_rows_written beside attn_kv_rows_slots (the live
slots' K rows a step wrote beside slots x attention layers: their ratio
is live slots over slots where the decode-attention kernel writes),
sampler_steps and diff_slot_passes (a block-diffusion model's passes and
its (slot, pass) pairs), moe_sparse_layer_steps and moe_experts_touched.
Where the lines do not reach both ends of the slice, or do not resolve it
(a freeze of the machine inside it: the counters read off the lines are
linear in time between two requests' ends, the steps then are not), the
window's growth stands in, and the reader's `[bench]` line says whose
seconds it priced; a program that writes no such fields (an older one)
leaves the window's rows a step from /metrics, and the family's closed
form for the experts."""

import _access
import _diff
import _trace

STEPS = "sampler_steps"  # decode steps run, on every line of every model
LIVE = ("attn_kv_rows_slots", "attn_kv_rows_written")
PASSES = (STEPS, "diff_slot_passes")
ROUTED = ("moe_sparse_layer_steps", "moe_experts_touched")
# how far the slice's steps by the lines may lie off the trace's own count
# (executions of the decode program x steps a chunk, which is exact): the
# two ends' interpolation moves them by a percent or two; a freeze inside
# the slice, whose seconds the lines fill with steps that ran after it, by
# several times
STEP_MISMATCH = 0.1


def _resolved(obs, steps):
    """Whether `steps`, the slice's decode steps read off the access
    lines, are the trace's own to within STEP_MISMATCH."""
    chunk, per = _trace.module(obs, _trace.DECODE), _trace.steps_per_dispatch(obs)
    if not chunk or not per:
        return True  # nothing to hold them against
    traced = chunk["count"] * per
    ok = abs(steps - traced) <= STEP_MISMATCH * traced
    if not ok:
        print(f"[bench] the access lines do not resolve the traced slice: {steps:.0f} decode "
              f"steps between its ends by their counters, {traced:.0f} by the trace", flush=True)
    return ok


def counted(obs, fields):
    """(the counters' growth, whose seconds): over the traced slice where
    the access lines reach both its ends and resolve it, else over the
    window; (None, None) where no line carries them."""
    d = _access.slice_delta(obs, fields if STEPS in fields else fields + (STEPS,))
    if d and _resolved(obs, d[STEPS]):
        return d, "slice"
    d = _access.window_delta(obs, fields)
    return (d, "window") if d else (None, None)


def live_slots(obs):
    """(slots that held a request, mean over the decode steps; whose
    seconds). A pass of a block-diffusion model counts its slots itself;
    elsewhere the K rows written say it, unless the step scatters a row
    of every slot (then they say nothing of the live ones: None)."""
    if _diff.block_length(obs):
        d, where = counted(obs, PASSES)
        return (d["diff_slot_passes"] / d["sampler_steps"], where) if d else (None, None)
    d, where = counted(obs, LIVE)
    if not d or not obs.slots or d["attn_kv_rows_written"] >= d["attn_kv_rows_slots"]:
        return None, None
    return obs.slots * d["attn_kv_rows_written"] / d["attn_kv_rows_slots"], where


def rows(obs):
    """(rows a decode step as the family's decode_step_cost takes them,
    whose seconds): the live slots, which are the live rows of a model
    that emits a token a slot a step and the state a Mamba-2 layer has to
    step; of a model that generates by blocks the tokens a pass emits,
    live slots x block_length / passes a block. Without the counters,
    the window's rows a step by /metrics, which is either."""
    live, where = live_slots(obs)
    if live is None:
        return (obs.rows_per_step, "window, by /metrics") if obs.rows_per_step \
            else (None, None)
    bk = _diff.block_length(obs)
    if bk:
        live *= bk / obs.family.passes_per_block(obs.cfg)
    return live, where


def touched(obs):
    """(distinct experts a sparse layer read a decode step, whose
    seconds); (None, None) where the unit counts none: the family's
    closed form stands."""
    d, where = counted(obs, ROUTED)
    return (d["moe_experts_touched"] / d["moe_sparse_layer_steps"], where) \
        if d else (None, None)
