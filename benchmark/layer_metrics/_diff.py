"""What the readers of a block-diffusion model's metrics share (not a
metric: no UNIT).

A model that generates by diffusion over blocks (a configuration whose
`assumed` gives `block_length`) runs a decode step as a PASS over
block_length positions of every live slot, and its `request {json}`
access lines (_access.py) carry, beside the routing and KV counters,
diff_slot_passes ((slot, pass) pairs run), diff_commit_passes and
diff_tokens_out. A program that writes no such fields (another model, an
older program) leaves every reader here with nothing to read: None."""

import re

import _access
import _moe
import _trace

PASSES = ("diff_slot_passes", "diff_commit_passes", "diff_tokens_out")
ATTN = ("diff_slot_passes", "attn_kv_tokens_read", "attn_kv_rows_written")
# ops/decode_attention's pallas_call, as benchmark/xplane.py cleans an XLA
# Ops event: "%decode_attention.12 = (bf16[64,128,128]..." ->
# "decode_attention.12_bf16_64_128_128_..."
ATTN_OP = re.compile(r"^decode_attention(\.\d+)?_")


def block_length(obs):
    return ((obs.cfg or {}).get("assumed") or {}).get("block_length")


def tokens_per_pass(obs):
    """Tokens a live slot's pass emitted, over the window."""
    d = _access.window_delta(obs, PASSES)
    return d["diff_tokens_out"] / d["diff_slot_passes"] if d else None


def block_grouped_ops(obs):
    """_moe.decode_grouped_ops at a pass's row count: the grouped expert
    products of the decode program are handed slots x block_length x
    experts per token assignment rows."""
    k, bk = (obs.cfg or {}).get("num_experts_per_tok"), block_length(obs)
    if not k or not bk or not obs.slots:
        return []
    out = []
    for name, seconds in _trace.program_ops(obs, _trace.DECODE).items():
        m = _moe.GROUPED_OP.match(name)
        if m and int(m.group(2)) == obs.slots * bk * k:
            out.append((seconds, int(m.group(3))))
    return out


def attention_ops(obs):
    """{name: seconds} of the decode program's decode-attention calls."""
    return {n: s for n, s in _trace.program_ops(obs, _trace.DECODE).items()
            if ATTN_OP.match(n)}
