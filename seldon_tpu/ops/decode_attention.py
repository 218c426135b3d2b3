"""Decode attention over the dense slab's LIVE rows only, where they lie.

The slab holds every slot's window, K and V [La, B, 1, T, Hkv * Dh] one
row a token (models/transformer.cache_spec), and a decode step asks for
one query token of each slot that holds a request against the tokens
that slot has reached: a few thousand rows of 64 x 1024. The einsums of
models/transformer.gqa_attention_decode contract the queries against the
whole layer and mask the rest away, which is as fast as reading a layer
can be and reads ~70 times what the live rows own (PERF.md section 5).

The kernel here leaves the slab in HBM and is told by scalar prefetch
which layer, and a work list (`schedule`, made once a step from `active`
and `pos`): one item a (live slot, block of `block` tokens below its
position), live slots in slot order, each slot's blocks in token order.
ONE grid step walks the list with a traced trip count: it copies an
item's K and V block (and, for an int8 slab, the block's per-(token,
head) scales [Hkv, block]) into one of two VMEM buffers while the
previous item is computed, so a step costs what its live tokens cost
and neither dead slots nor the empty tail of a window are ever
addressed. No slice of the slab is an operand (ops/ssm_update.py's
discipline): the compiled chunk holds the slab once, as the custom
call's operand.

Arithmetic is gqa_attention_decode's with the softmax summed block by
block: scores bf16 x bf16 -> f32 times 1/sqrt(Dh), the int8 scales
applied to the f32 scores and weights (the read stays one byte an
element), the strict mask t < pos, f32 running (max, sum, weighted
values). A token's row holds its heads side by side, so the queries go
block-diagonal over the row (transformer._beside's trick, built in VMEM
from `own`, the 0/1 matrix of which lanes are a query's own head) and
each keeps its own head's lanes of the weighted values; whole rows are
the matrix unit's operands and no head is ever sliced out of a row, so
heads of 64 at 64-lane offsets cost nothing extra. When a slot's last
block is done the fresh token's exact bf16 column is folded in as one
more block (a max/exp combine, as gqa_attention_decode does it) and the
normalised row is written out. Slots the list does not name (dead, or
live at position 0) are never written by the kernel: `attend` gives them
the fresh token's value alone, which is what attention over no past is,
so they are finite and nothing of a dead row reaches a live one.

A layer whose KV is a RING as long as its window (cache_spec's
"kv_window": row s holds the newest position that is s modulo the ring)
is read by the same walk: its schedule counts min(pos, ring) tokens a
slot and the row the step is about to overwrite (`row`) is left out of
the read: once pos has passed the ring's length it holds the position
that has just left the window. The softmax does not care in which order
rows come.

The same walk WRITES the fresh token's K and V, for the live slots and
no other: K and V are aliased to the call's results (ops/ssm_update.py
holds the state so) and `attend` returns them with row `row` of every
live slot holding the fresh token in the slab's storage dtype (an int8
slab: the quantised row, handed in beside the exact column the softmax
folds). The slab lies in HBM in native tiles of rows (32 of int8, 16 of
bf16: a row shares its 32-bit words with its neighbours), so what is
written is the ALIGNED TILE that holds `row`: when the block that holds
it has been copied in and awaited (the last block of a slab's slot
unless pos is a block's first row; whichever block of a full ring), the
tile is cut out of that buffer, the fresh row selected in, and copied
back, so every other row keeps its bytes and the write cannot race the
slot's own reads. The live slots whose row is the first of a block that
the walk does not read (position 0, a block's edge) are `loose`: a
second, nearly always empty loop fetches their tile, sets its first
row and puts it back. A slot at pos == T writes nothing.

A model that generates by diffusion over blocks (ModelConfig.gen_block)
asks for Sq > 1 query positions a slot: Sq x H query rows walk the same
list (the matrix unit's rows are the better filled), the Sq fresh
columns are folded in unmasked among themselves, and the Sq rows
pos .. pos + Sq - 1, which lie in one native tile because pos is a
multiple of Sq and Sq divides the tile, are written for the slots that
commit and for no other (`committing`). With Sq = 1 every line of the
program is what it was: the two cases part on the static Sq in Python.

Like the other kernels of seldon_tpu/ops it never chooses interpret mode
itself: `applies` is False off a TPU, the caller then keeps
gqa_attention_decode, and tests run `attend` through
tests/pallas_interpret.py.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# Bytes of K an item covers (and as many of V): 256 tokens of a bf16 row
# of 1024 lanes, 512 of an int8 one. A narrower row takes as many more
# tokens, so that an item moves the same bytes whatever the row: what an
# item costs beside its bytes (its copies started and awaited, the loop,
# ~0.3 us) is then the same small share of it
# (tools/probe_decode_attention.py: at 256 tokens of 256 bf16 lanes a
# full slab took 1.5 x the einsums' time, at 1024 tokens 0.9 x).
ITEM_BYTES = 512 * 1024
# The kernel's documented tolerance: how far `attend`'s output for a live
# row may lie from gqa_attention_decode's (f32, the tests' geometries).
# The softmax summed block by block and normalised once in f32, against
# einsums that round the normalised weights to bf16 first, sits at ~3e-3
# there (tests/test_decode_attention.py, tests/test_falcon_h1.py).
ATTEND_ATOL = 1e-2


def block_size(k_shape, head_dim: int, itemsize: int = 2) -> int:
    """Tokens a work item covers for a slab K of this shape and element
    size (1: an int8 slab), or 0 where the kernel cannot read it: it
    reads a row a token whose heads tile the 128 lanes (heads of 128, or
    of 64 two a tile: mistral's, nemotron's, lfm2's) in a window that
    whole blocks of whole tiles cover."""
    if len(k_shape) != 5 or k_shape[2] != 1:
        return 0
    T, C = k_shape[3], k_shape[4]
    if head_dim <= 0 or LANES % head_dim or C % LANES or C % head_dim:
        return 0
    most = min(ITEM_BYTES // (C * itemsize), T) // LANES * LANES
    return next((b for b in range(most, 0, -LANES) if T % b == 0), 0)


def reads(k: jnp.ndarray, head_dim: int) -> int:
    """`block_size` of the slab's K array (or its ShapeDtypeStruct)."""
    return block_size(k.shape, head_dim, k.dtype.itemsize)


def applies(k: jnp.ndarray, head_dim: int) -> int:
    """Tokens a work item covers (`block_size`) where the decode step
    takes the kernel: on a TPU, for a slab K it can read; else 0."""
    return reads(k, head_dim) if jax.default_backend() == "tpu" else 0


class Schedule(NamedTuple):
    """A decode step's work list (the same for every layer of it)."""
    n_items: jnp.ndarray  # [1] int32
    slot: jnp.ndarray  # [B * T / block] int32: item -> slot
    blk: jnp.ndarray  # [B * T / block] int32: item -> block of that slot
    pos: jnp.ndarray  # [B] int32
    has_past: jnp.ndarray  # [B] bool: live and past position 0
    block: int
    row: jnp.ndarray  # [B] int32: the row the step writes (left unread)
    n_loose: jnp.ndarray  # [1] int32
    loose: jnp.ndarray  # [B] int32: live slots whose row no item's block holds


def schedule(active: jnp.ndarray, pos: jnp.ndarray, window: int,
             block: int, ring: bool = False) -> Schedule:
    """`ring`: the layer holds `window` rows a slot, position p at row
    p % window; a slot at pos reads min(pos, window) rows and not the
    row pos % window once pos >= window (it holds position pos - window).
    `row` is where the step's token goes: pos, or pos % window."""
    has = active & (pos > 0)
    row = (pos % window if ring else pos).astype(jnp.int32)
    # a block's first row, of a block the walk below does not reach
    is_loose = active & (pos < window) & (pos % block == 0)
    loose = jnp.argsort(~is_loose, stable=True).astype(jnp.int32)
    if ring:
        pos = jnp.minimum(pos, window)
    per_slot = jnp.where(has, (pos + block - 1) // block, 0).astype(jnp.int32)
    ends = jnp.cumsum(per_slot)
    items = jnp.arange(pos.shape[0] * (window // block), dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, items, side="right"),
                       pos.shape[0] - 1).astype(jnp.int32)
    return Schedule(ends[-1:], slot, items - (ends - per_slot)[slot],
                    pos.astype(jnp.int32), has, block, row,
                    jnp.sum(is_loose, dtype=jnp.int32)[None], loose)


def committing(sched: Schedule, writes: jnp.ndarray, window: int) -> Schedule:
    """`sched` for a pass in which only the slots `writes` [B] (live,
    committing, below the window's end) write their rows: every other
    slot's `row` is the window's end, which no block holds, and only a
    writing slot can be loose."""
    is_loose = writes & (sched.pos % sched.block == 0)
    return sched._replace(
        row=jnp.where(writes, sched.row, window).astype(jnp.int32),
        n_loose=jnp.sum(is_loose, dtype=jnp.int32)[None],
        loose=jnp.argsort(~is_loose, stable=True).astype(jnp.int32))


def tokens_read(sched: Schedule) -> jnp.ndarray:
    """KV tokens one attention layer fetches under `sched` (whole
    blocks: what the copies move)."""
    return sched.n_items[0] * sched.block


def tile_rows(dtype) -> int:
    """Rows of the slab's native tile in HBM: a 32-bit word holds 4 int8
    or 2 bf16 rows of a lane, 8 words a tile."""
    return 32 // jnp.dtype(dtype).itemsize


def _kernel(layer_ref, n_ref, slot_ref, blk_ref, pos_ref, row_ref,
            n_loose_ref, loose_ref, q_ref, kf_ref, vf_ref, own_ref, *rest,
            quantized: bool, block: int, scale: float, sq: int = 1):
    new, n_hbm = (kf_ref, vf_ref), 2  # the fresh rows as the slab stores them
    if quantized:  # int8 rows, the scales' spread; K, V and their scales
        spread_ref, *new = rest[:3]
        rest, n_hbm = rest[3:], 4
    hbm, (out_ref, k_out, v_out) = rest[:n_hbm], rest[n_hbm:n_hbm + 3]
    bufs = rest[n_hbm + 3:2 * n_hbm + 3]
    sem, qbd, m_scr, l_scr, acc_scr, wbuf, wsem = rest[2 * n_hbm + 3:]
    kbuf, vbuf = bufs[:2]
    layer, n = layer_ref[0], n_ref[0]
    H, C = qbd.shape
    tiles = C // LANES
    R = wbuf.shape[1]

    def copies(w, buf):
        b = slot_ref[w]
        rows = pl.ds(pl.multiple_of(blk_ref[w] * block, block), block)
        # a token's row of K or V; its scales, [Hkv, T], a column a token
        at = [(layer, b, 0, rows), (layer, b, 0, rows),
              (b, slice(None), rows), (b, slice(None), rows)]
        return [pltpu.make_async_copy(src.at[at[i]], dst.at[buf], sem.at[i, buf])
                for i, (src, dst) in enumerate(zip(hbm, bufs))]

    def tile(slab, b, first):
        return slab.at[layer, b, 0, pl.ds(pl.multiple_of(first, R), R)]

    def puts(b, first):
        """wbuf -> the tile of K and of V that starts at row `first`."""
        return [pltpu.make_async_copy(wbuf.at[i], tile(dst, b, first),
                                      wsem.at[i])
                for i, dst in enumerate((k_out, v_out))]

    def stage(i, b, held, at):
        """wbuf[i] <- `held` [R, C] with the fresh row at row `at` (the
        sq fresh rows from row `at` on)."""
        rows = jax.lax.broadcasted_iota(jnp.int32, held.shape, 0)
        if sq == 1:
            here = rows == at
            wbuf[i] = jnp.where(here, new[i][b].astype(held.dtype), held)
            return
        fresh = new[i][b].astype(held.dtype)  # [sq, C]
        for u in range(sq):
            held = jnp.where(rows == at + u, fresh[u:u + 1], held)
        wbuf[i] = held

    @pl.when(n > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def item(w, writing):
        buf = w % 2
        b, j, p = slot_ref[w], blk_ref[w], pos_ref[slot_ref[w]]
        r = row_ref[b]

        @pl.when(w + 1 < n)
        def _next():
            for c in copies(w + 1, 1 - buf):
                c.start()

        @pl.when(j == 0)
        def _slot_begins():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)
            q = q_ref[b]  # [H, 128]: the head's lanes, tiled to a tile
            for t in range(tiles):
                lanes = slice(t * LANES, (t + 1) * LANES)
                qbd[:, lanes] = (q * own_ref[:, lanes]).astype(qbd.dtype)

        for c in copies(w, buf):
            c.wait()
        s = jax.lax.dot_general(
            qbd[...], kbuf[buf], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, block]
        if quantized:
            s = s * jnp.dot(spread_ref[...], bufs[2][buf],
                            preferred_element_type=jnp.float32)
        cols = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # below the slot's position, and not the row this step writes (a
        # full ring's holds the position that has just left the window)
        mask = (cols < p) & (cols != r)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        m_scr[...] = m_new
        if quantized:
            pr = pr * jnp.dot(spread_ref[...], bufs[3][buf],
                              preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            pr.astype(qbd.dtype), vbuf[buf],
            preferred_element_type=jnp.float32)  # [H, C]

        @pl.when((j + 1) * block >= p)
        def _slot_ends():
            # the fresh token's exact column, folded in as one more block
            f32 = jnp.float32
            if sq > 1:
                # the block's sq fresh columns, each seen by every query
                qf, kf = qbd[...].astype(f32), kf_ref[b].astype(f32)
                s_f = [jnp.sum(qf * kf[u:u + 1], axis=-1, keepdims=True)
                       * scale for u in range(sq)]  # sq x [sq * H, 1]
                m_t = functools.reduce(jnp.maximum, s_f, m_scr[...])
                alpha = jnp.exp(m_scr[...] - m_t)
                p_f = [jnp.exp(s_u - m_t) for s_u in s_f]
                inv = 1.0 / (l_scr[...] * alpha + sum(p_f))
                own = jnp.zeros((H, LANES), f32)
                for t in range(tiles):
                    lanes = slice(t * LANES, (t + 1) * LANES)
                    vf = vf_ref[b][:, lanes].astype(f32)  # [sq, 128]
                    own = own + own_ref[:, lanes] * (
                        acc_scr[:, lanes] * alpha
                        + sum(p_u * vf[u:u + 1]
                              for u, p_u in enumerate(p_f)))
                out_ref[b] = (own * inv).astype(out_ref.dtype)
                return
            s_f = jnp.sum(qbd[...].astype(f32) * kf_ref[b].astype(f32),
                          axis=-1, keepdims=True) * scale  # [H, 1]
            m_t = jnp.maximum(m_scr[...], s_f)
            alpha = jnp.exp(m_scr[...] - m_t)
            p_f = jnp.exp(s_f - m_t)
            inv = 1.0 / (l_scr[...] * alpha + p_f)  # the sum is >= p_f or >= 1
            # each query keeps its own head's lanes; the others' are
            # zeroed, so the row's tiles add up to one tile
            own = jnp.zeros((H, LANES), f32)
            for t in range(tiles):
                lanes = slice(t * LANES, (t + 1) * LANES)
                own = own + own_ref[:, lanes] * (
                    acc_scr[:, lanes] * alpha
                    + p_f * vf_ref[b][:, lanes].astype(f32))
            out_ref[b] = (own * inv).astype(out_ref.dtype)

        # the block in hand holds the row this step writes: its tile goes
        # back with the fresh row in it. The block has been awaited and
        # no later item of the slot reads it; the copy in flight is of
        # another block.
        off = r - j * block
        holds = (off >= 0) & (off < block)

        @pl.when(holds)
        def _write():
            @pl.when(writing > 0)
            def _last_slots():  # wbuf is free once they have landed
                for c in puts(0, 0):
                    c.wait()

            first = pl.multiple_of(off // R * R, R)
            for i in range(2):
                stage(i, b, bufs[i][buf, pl.ds(first, R), :], off - first)
            for c in puts(b, j * block + first):
                c.start()

        return jnp.where(holds, 1, writing)

    writing = jax.lax.fori_loop(0, n, item, jnp.int32(0))

    @pl.when(writing > 0)
    def _landed():
        for c in puts(0, 0):
            c.wait()

    def loose(i, carry):
        b = loose_ref[i]
        first = row_ref[b]  # a block's first row: a tile's too
        gets = [pltpu.make_async_copy(tile(src, b, first), wbuf.at[i_],
                                      wsem.at[i_])
                for i_, src in enumerate(hbm[:2])]
        for c in gets:
            c.start()
        for c in gets:
            c.wait()
        for i_ in range(2):
            stage(i_, b, wbuf[i_], 0)
        back = puts(b, first)
        for c in back:
            c.start()
        for c in back:
            c.wait()
        return carry

    jax.lax.fori_loop(0, n_loose_ref[0], loose, 0)


def attend(
    q: jnp.ndarray,  # [B, Sq, H, Dh]
    k_fresh: jnp.ndarray,  # [B, Sq, Hkv, Dh] (exact, this token)
    v_fresh: jnp.ndarray,  # [B, Sq, Hkv, Dh]
    cache: Dict[str, jnp.ndarray],  # the WHOLE slab, PRE-write
    layer: jnp.ndarray,  # int32 scalar: the attention layer, of La
    sched: Schedule,
    stored: Optional[Dict[str, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """gqa_attention_decode over layer `layer` of the slab for the rows
    `sched` was made of, [B, Sq, H * Dh] in q's dtype, and K and V with
    row `sched.row` of that layer written for every live slot (in place:
    hand them on, the arrays handed in are spent).

    cache {"k", "v"[, "k_scale", "v_scale"]}: [La, B, 1, T, Hkv * Dh]
    (scales [La, B, Hkv, T]). `stored` {"k", "v"}: [B, Hkv * Dh], the
    fresh rows as an int8 slab stores them; any other slab takes the
    fresh column cast to its dtype.

    Sq > 1 (a bf16 slab; transformer.gqa_attention_block is the same
    over the einsums): Sq query positions a slot from `sched.pos` on,
    their fresh columns seen by each of them, rows `sched.row` ..
    + Sq - 1 written where `committing` left a row to write."""
    B, Sq, H, Dh = q.shape
    C = cache["k"].shape[4]
    Hkv, block = C // Dh, sched.block
    G = H // Hkv
    f32 = jnp.float32
    slab = [cache["k"], cache["v"]]
    quantized = "k_scale" in cache
    # query rows are (position, head): row n reads KV head (n % H) // G
    lane_head = (jnp.arange(C) // Dh)[None, :]
    head = jnp.arange(H) if Sq == 1 else jnp.arange(Sq * H) % H
    own = lane_head == (head // G)[:, None]
    q_rows = q[:, 0] if Sq == 1 else q.reshape(B, Sq * H, Dh)
    args = [jnp.tile(q_rows, (1, 1, LANES // Dh)),
            k_fresh.astype(q.dtype).reshape(B, Sq, C),
            v_fresh.astype(q.dtype).reshape(B, Sq, C), own.astype(f32)]
    whole = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    in_specs = [whole((B, Sq * H, LANES)), whole((B, Sq, C)),
                whole((B, Sq, C)), whole((Sq * H, C))]
    if quantized:
        assert Sq == 1, "a block of query positions reads a bf16 slab"
        # [H, Hkv] 0/1: a query head's row of scales is its KV head's
        spread = jnp.arange(H)[:, None] // G == jnp.arange(Hkv)[None, :]
        args += [spread.astype(cache["k_scale"].dtype),
                 stored["k"].reshape(B, 1, C), stored["v"].reshape(B, 1, C)]
        in_specs += [whole((H, Hkv)), whole((B, 1, C)), whole((B, 1, C))]
        # The layer's scales (1 MiB each at 64 x 1024 x 8), not the
        # whole arrays: the compiler takes a call to read its operands
        # whole, and two whole arrays of scales (32 MiB each) fit the
        # chip's fast memory, so it may move one there ahead of the call
        # in EVERY layer: 1.1 GB a step on mistral7b.chat (PERF.md
        # section 6, PR 42). K and V stay whole: a layer of them sliced
        # out is 64 MiB moved a layer.
        slab += [jax.lax.dynamic_index_in_dim(cache[key], layer, 0, False)
                 for key in ("k_scale", "v_scale")]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(slab)
    scratch = [pltpu.VMEM((2, block, C) if a.ndim == 5 else (2, Hkv, block),
                          a.dtype) for a in slab]
    scratch += [pltpu.SemaphoreType.DMA((len(slab), 2)),
                pltpu.VMEM((Sq * H, C), q.dtype),
                pltpu.VMEM((Sq * H, 1), f32), pltpu.VMEM((Sq * H, 1), f32),
                pltpu.VMEM((Sq * H, C), f32),
                # K's and V's tile on its way back
                pltpu.VMEM((2, tile_rows(slab[0].dtype), C), slab[0].dtype),
                pltpu.SemaphoreType.DMA((2,))]
    scalars = (jnp.reshape(layer, (1,)).astype(jnp.int32), sched.n_items,
               sched.slot, sched.blk, sched.pos, sched.row, sched.n_loose,
               sched.loose)
    static = dict(quantized=quantized, block=block, scale=Dh ** -0.5)
    if Sq > 1:
        static["sq"] = Sq
    with jax.named_scope("attn/scores"):
        out, k, v = pl.pallas_call(
            functools.partial(_kernel, **static),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars), grid=(1,),
                in_specs=in_specs,
                out_specs=[whole((B, Sq * H, LANES)),
                           pl.BlockSpec(memory_space=pl.ANY),
                           pl.BlockSpec(memory_space=pl.ANY)],
                scratch_shapes=scratch),
            out_shape=[jax.ShapeDtypeStruct((B, Sq * H, LANES), q.dtype),
                       jax.ShapeDtypeStruct(slab[0].shape, slab[0].dtype),
                       jax.ShapeDtypeStruct(slab[1].shape, slab[1].dtype)],
            # K and V, past the prefetched scalars and the VMEM operands
            input_output_aliases={len(scalars) + len(args): 1,
                                  len(scalars) + len(args) + 1: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=48 * 1024 * 1024),
            name="decode_attention",
        )(*scalars, *args, *slab)
    with jax.named_scope("attn/out"):
        # a row's own head is the one segment of its tile that is not zero
        out = out.reshape(B, Sq * H, LANES // Dh, Dh).sum(axis=2)
        # rows the kernel never wrote hold whatever the buffer held: with
        # no past, attention is the fresh token's value
        if Sq == 1:
            alone = jnp.repeat(v_fresh[:, 0].astype(q.dtype), G, axis=1)
        else:  # ... the block's positions over one another alone
            qr = q.reshape(B, Sq, Hkv, G, Dh)
            w = jax.nn.softmax(jnp.einsum(
                "bskgd,bukd->bkgsu", qr, k_fresh.astype(q.dtype),
                preferred_element_type=f32) * Dh ** -0.5, axis=-1)
            alone = jnp.einsum(
                "bkgsu,bukd->bskgd", w.astype(q.dtype),
                v_fresh.astype(q.dtype)).reshape(B, Sq * H, Dh)
        out = jnp.where(sched.has_past[:, None, None], out, alone)
    return out.reshape(B, Sq, H * Dh), k, v
