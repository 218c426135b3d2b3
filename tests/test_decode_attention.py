"""ops/decode_attention: the kernel that reads the slab's live rows only,
interpreted on the CPU, against the einsums over the whole layer
(transformer.gqa_attention_decode) it stands in for on a TPU; the decode
step and chunk that take it; the two counters of what it read.

None of this is code a benchmark cell runs off a TPU: there the step
keeps the einsums (decode_attention.applies), and the tests that drive
engines stay as they were.
"""

import dataclasses
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_tpu.models import init_params, slot, transformer
from seldon_tpu.models.config import get_config
from seldon_tpu.ops import decode_attention as da
from seldon_tpu.ops.decode_attention import ATTEND_ATOL
from seldon_tpu.servers.engine import (
    CHUNK_COUNTERS, KV_COUNTERS, InferenceEngine)
from tests._engine_fixture import live_config
from tests.pallas_interpret import pallas_interpret

B, T, LAYERS = 8, 512, 2
ITEM_BYTES = 256 * 1024  # half the kernel's own: every shape has 2+ items in T
STEP_ATOL = 5e-2  # logits of magnitude 3-4 through a bf16 residual stream
# (KV heads, head size, queries a KV head): mistral-7b-v0.3 and
# mixtral-8x7b, lfm2-24b-a2b (two heads a tile of 128 lanes),
# nemotron-3-nano-30b-a3b
SHAPES = {"8x128g4": (8, 128, 4), "8x64g4": (8, 64, 4), "2x128g16": (2, 128, 16)}
OCCUPANCY = {
    "none": [()],
    "one": [(b,) for b in range(B)],
    "several": [(1, 4, 5), (0, 2, 3, 6)],
    "all": [tuple(range(B))],
}


def _int8(cache, kf, vf):
    """The bf16 slab and fresh columns as an int8 slab stores them."""
    Dh = kf.shape[-1]
    cache = transformer.kv_writes(cache, {}, dataclasses.replace(
        get_config("tiny"), kv_cache_dtype="int8", head_dim=Dh))
    (kq, _), (vq, _) = transformer._quantize_kv(kf), transformer._quantize_kv(vf)
    rows = lambda x: x.reshape(x.shape[0], -1)
    return cache, {"k": rows(kq), "v": rows(vq)}


@functools.lru_cache(maxsize=None)
def _case(shape: str, kv_dtype: str):
    """The slab, the step's tensors and the two jitted attentions of one
    (shape, KV dtype): compiled once, run at every occupancy."""
    Hkv, Dh, G = SHAPES[shape]
    H, C = Hkv * G, Hkv * Dh
    ks = jax.random.split(jax.random.key(7), 5)
    bf16 = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, 1, H, Dh)).astype(bf16)
    kf = jax.random.normal(ks[1], (B, 1, Hkv, Dh)).astype(bf16)
    vf = (0.25 * jax.random.normal(ks[2], (B, 1, Hkv, Dh))).astype(bf16)
    cache = {"k": jax.random.normal(ks[3], (LAYERS, B, 1, T, C), bf16),
             "v": 0.25 * jax.random.normal(ks[4], (LAYERS, B, 1, T, C), bf16)}
    stored = None
    if kv_dtype == "int8":
        cache, stored = _int8(cache, kf, vf)
    with mock.patch.object(da, "ITEM_BYTES", ITEM_BYTES):
        block = da.block_size(cache["k"].shape, Dh, cache["k"].dtype.itemsize)
    assert block == min(T, ITEM_BYTES // (C * cache["k"].dtype.itemsize))
    # every position the issue names: none, one, a block's edge -1 / 0 /
    # +1 (the window's last where the block is the window), the window's
    # last, and two inside a block
    pos = jnp.minimum(jnp.array(
        [0, 1, block - 1, block, block + 1, T - 1, 300, 77]), T - 1)

    @jax.jit
    def kernel(active, layer):
        return da.attend(q, kf, vf, cache, layer,
                         da.schedule(active, pos, T, block), stored)[0]

    @jax.jit
    def einsums(layer):
        cl = {key: val[layer] for key, val in cache.items()}
        mask_lt = jnp.arange(T)[None, None, :] < pos[:, None, None]
        return transformer.gqa_attention_decode(
            q, cl["k"], cl["v"], kf, vf, mask_lt,
            k_scale=cl.get("k_scale"), v_scale=cl.get("v_scale"))

    fresh_alone = jnp.repeat(vf[:, 0], G, axis=1).reshape(B, 1, H * Dh)
    return kernel, einsums, pos, fresh_alone


@pytest.mark.parametrize("occupancy", list(OCCUPANCY))
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_matches_the_einsums_on_live_rows(shape, kv_dtype, occupancy):
    """Live rows within the tolerance ragged_paged_attention documents
    for its Pallas leg (the softmax is summed block by block and the
    weights are normalised once, in float32); a live row at position 0
    and every dead row give the fresh column's value alone: finite, and
    nothing of what the slab holds for them."""
    kernel, einsums, pos, fresh_alone = _case(shape, kv_dtype)
    f32 = np.float32
    with pallas_interpret():
        for layer, live in enumerate(OCCUPANCY[occupancy]):
            layer = layer % LAYERS
            active = jnp.zeros((B,), bool).at[jnp.array(live, int)].set(True)
            got = np.asarray(kernel(active, layer), f32)
            want = np.asarray(einsums(layer), f32)
            assert np.isfinite(got).all()
            past = np.asarray(active & (pos > 0))
            np.testing.assert_allclose(
                got[past], want[past], atol=ATTEND_ATOL, rtol=0)
            np.testing.assert_array_equal(
                got[~past], np.asarray(fresh_alone, f32)[~past])


WRITE_T, WRITE_BLOCK = 512, 256
# slot -> (live, position): position 0 (no past, no item), a block's first
# row (the block is not read), a native tile's last row and the next
# tile's first (32 rows of int8, 16 of bf16), a dead slot between live
# ones, the window's last row, the slab's end (nothing to write; a ring:
# many wraps), a block's first row again, dead
WRITE_SLOTS = [(True, 0), (True, WRITE_BLOCK), (True, WRITE_BLOCK + 31),
               (True, WRITE_BLOCK + 32), (False, 300), (True, WRITE_T - 1),
               (True, WRITE_T), (False, 0)]
RING_SLOTS = [(True, 0), (True, WRITE_BLOCK), (True, WRITE_T + 15),
              (True, WRITE_T + 16), (False, WRITE_T + 300),
              (True, 2 * WRITE_T - 1), (True, 5 * WRITE_T + WRITE_BLOCK + 77),
              (True, 3 * WRITE_T)]


@pytest.mark.parametrize("kv_dtype,ring", [
    ("bf16", False), ("int8", False), ("bf16", True), ("int8", True)],
    ids=["bf16-slab", "int8-slab", "bf16-ring", "int8-ring"])
def test_the_kernel_writes_the_live_slots_fresh_rows_and_nothing_else(
        kv_dtype, ring):
    """attend returns K and V with the fresh token's row written for the
    live slots, as the step's scatter writes it (`.at[layer, slot, :,
    row].set`, bit for bit; an int8 slab: the quantised row), at row pos
    of a slab and pos % W of a ring; every other byte (the other layer,
    a dead slot, every other row of a live one, a slot at the slab's
    end) is the slab's as it was handed in; and the attention it returns
    is what it read BEFORE the write, the einsums' over the same rows."""
    Hkv, Dh, G = 8, 128, 4
    H, C, W = Hkv * G, Hkv * Dh, WRITE_T
    ks = jax.random.split(jax.random.key(3), 5)
    bf16, f32 = jnp.bfloat16, np.float32
    q = jax.random.normal(ks[0], (B, 1, H, Dh)).astype(bf16)
    kf = jax.random.normal(ks[1], (B, 1, Hkv, Dh)).astype(bf16)
    vf = (0.25 * jax.random.normal(ks[2], (B, 1, Hkv, Dh))).astype(bf16)
    cache = {"k": jax.random.normal(ks[3], (LAYERS, B, 1, W, C), bf16),
             "v": 0.25 * jax.random.normal(ks[4], (LAYERS, B, 1, W, C), bf16)}
    rows = {"k": kf.reshape(B, C), "v": vf.reshape(B, C)}
    stored = None
    if kv_dtype == "int8":
        cache, stored = _int8(cache, kf, vf)
        rows = stored
    assert WRITE_BLOCK % da.tile_rows(cache["k"].dtype) == 0
    active, pos = (jnp.array(x) for x in zip(*(RING_SLOTS if ring else WRITE_SLOTS)))
    sched = da.schedule(active, pos, W, WRITE_BLOCK, ring)
    assert sched.loose[:int(sched.n_loose[0])].tolist() == [0, 1]
    with pallas_interpret():
        out, k, v = jax.jit(lambda c: da.attend(
            q, kf, vf, c, jnp.asarray(1), sched, stored))(cache)
    row = np.asarray(pos % W if ring else pos)
    writes = np.asarray(active) & (row < W)
    assert writes.sum() == (7 if ring else 5)
    for name, got in (("k", k), ("v", v)):
        want = np.array(cache[name].astype(f32))
        for b in np.flatnonzero(writes):
            want[1, b, 0, row[b]] = np.asarray(rows[name].astype(f32))[b]
        assert got.dtype == cache[name].dtype
        np.testing.assert_array_equal(np.asarray(got.astype(f32)), want)
    s_ = jnp.arange(W)[None, None, :]
    mask = (s_ < pos[:, None, None]) & (s_ != jnp.asarray(row)[:, None, None])
    want = transformer.gqa_attention_decode(
        q, cache["k"][1], cache["v"][1], kf, vf, mask,
        k_scale=cache.get("k_scale", [None] * 2)[1],
        v_scale=cache.get("v_scale", [None] * 2)[1])
    past = np.asarray(active & (pos > 0))
    np.testing.assert_allclose(
        np.asarray(out, f32)[past], np.asarray(want, f32)[past],
        atol=ATTEND_ATOL, rtol=0)


def test_the_kernels_tolerance_is_its_own_modules_constant():
    """The bound the comparisons above hold the kernel to is documented
    where the kernel is, at the value they have used since the kernel
    came."""
    assert da.ATTEND_ATOL == ATTEND_ATOL == 1e-2


def test_schedule_lists_live_blocks_in_order():
    active = jnp.array([0, 1, 1, 0, 1, 1], bool)
    pos = jnp.array([9, 0, 128, 300, 129, 511])
    s = da.schedule(active, pos, 512, 128)
    n = int(s.n_items[0])
    assert n == 1 + 2 + 4 and int(da.tokens_read(s)) == n * 128
    assert s.slot[:n].tolist() == [2, 4, 4, 5, 5, 5, 5]
    assert s.blk[:n].tolist() == [0, 0, 1, 0, 1, 2, 3]
    assert s.has_past.tolist() == [False, False, True, False, True, True]
    assert s.slot.shape == (6 * 4,) and int(s.slot.max()) <= 5
    # the row a step writes, and the live slots whose row is the first of
    # a block the walk does not read (slot 3 is dead)
    assert s.row.tolist() == pos.tolist()
    assert s.loose[:int(s.n_loose[0])].tolist() == [1, 2]
    ring = da.schedule(active, pos + 512, 512, 128, ring=True)
    assert ring.row.tolist() == pos.tolist() and int(ring.n_loose[0]) == 0
    assert int(da.tokens_read(ring)) == 4 * 512  # a full ring: every block


@pytest.mark.parametrize("k_shape,head_dim,itemsize,block", [
    ((32, 64, 1, 1024, 1024), 128, 1, 512),  # mistral-7b-v0.3 (int8)
    ((5, 64, 1, 1024, 1024), 128, 2, 256),  # mixtral-8x7b: as many bytes an item
    ((2, 64, 1, 1024, 512), 64, 2, 512),  # lfm2-24b-a2b
    ((2, 64, 1, 1024, 256), 128, 2, 1024),  # nemotron-3-nano-30b-a3b
    ((32, 64, 1, 640, 1024), 128, 2, 128),  # a window only blocks of 128 cover
    ((2, 4, 1, 384, 128), 64, 2, 384),  # a narrow row: the window is one item
    ((2, 4, 1, 64, 32), 16, 2, 0),  # tiny: a row is not whole tiles
    ((2, 4, 1, 200, 128), 64, 2, 0),  # no block of whole tiles covers the window
    ((2, 4, 8, 256, 128), 128, 2, 0),  # by head (the paged pool's view)
    ((2, 4, 1, 256, 512), 256, 2, 0),  # a head wider than a tile
])
def test_block_size_tells_the_slabs_the_kernel_reads(
        k_shape, head_dim, itemsize, block):
    assert da.block_size(k_shape, head_dim, itemsize) == block
    k = jax.ShapeDtypeStruct(k_shape, jnp.int8 if itemsize == 1 else jnp.bfloat16)
    assert da.applies(k, head_dim) == 0  # never off a TPU


def _wide(preset, window=128, **more):
    """`preset` with heads of 64, two to a row of 128 lanes, and a
    window of whole tiles: the smallest slab the kernel reads."""
    return live_config(preset, d_model=256, head_dim=64, max_seq_len=window,
                       **more).validate()


def _armed_state(cfg, slots, active, pos, key):
    """A slot state whose slab holds noise where requests would have
    written KV (dead slots' too: nothing of it may be read)."""
    cache = transformer.init_cache(cfg, slots, cfg.max_seq_len)
    for i, (name, a) in enumerate(sorted(cache.items())):
        k = jax.random.fold_in(key, i)
        if a.dtype == jnp.int8:
            cache[name] = jax.random.randint(
                k, a.shape, -127, 128, jnp.int32).astype(a.dtype)
        elif name in ("k", "v"):
            cache[name] = (0.3 * jax.random.normal(k, a.shape)).astype(a.dtype)
        elif name.endswith("_scale"):
            cache[name] = jnp.abs(
                0.01 * jax.random.normal(k, a.shape)).astype(a.dtype)
    state = slot.fresh(cache, slots)
    return {**state, "active": active, "pos": pos,
            "last_tok": jnp.arange(slots, dtype=jnp.int32) + 5,
            "remaining": jnp.full((slots,), 50, jnp.int32)}


@pytest.mark.parametrize("preset,kv_dtype", [
    ("tiny", "bf16"), ("tiny", "int8"), ("tiny-lfm2", "bf16"),
    ("tiny-nemotron", "bf16")])
def test_decode_step_with_the_kernel_gives_the_einsums_logits(
        monkeypatch, preset, kv_dtype):
    """The three decode stacks take the kernel where the slab allows:
    live rows' logits agree with the einsums' (to STEP_ATOL: a last
    bit of a layer's bf16 output is carried through the layers after
    it, where attention alone is held to ATTEND_ATOL above; a
    wrong layer, slot or mask moves these logits by 0.3 and more) and
    the cache they write is the same where a slot is live (the first
    layer's rows bit for bit; later layers' follow the activations);
    dead rows stay finite, and a dead slot's K and V are what they were
    before the step, where the scatter after the einsums writes them
    too."""
    cfg = _wide(preset, window=256, kv_cache_dtype=kv_dtype)
    monkeypatch.setattr(da, "ITEM_BYTES", 128 * 128 * 2)  # two items a window
    params = init_params(cfg, jax.random.key(0))
    active = jnp.array([True, False, True, True])
    pos = jnp.array([17, 190, 0, 255])
    state = _armed_state(cfg, 4, active, pos, jax.random.key(1))
    step = jax.jit(functools.partial(transformer.decode_step, cfg=cfg))
    want, cache_want = step(params, state["last_tok"], pos, state["cache"],
                            live=active)
    monkeypatch.setattr(da, "applies", da.reads)
    step = jax.jit(functools.partial(transformer.decode_step, cfg=cfg))
    with pallas_interpret():
        got, cache_got = step(params, state["last_tok"], pos, state["cache"],
                              live=active)
    assert np.isfinite(np.asarray(got)).all()
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=STEP_ATOL, rtol=0)
    f32 = lambda a: np.asarray(a, np.float32)
    for name in ("k", "v"):
        # the first layer's rows do not pass through attention
        np.testing.assert_array_equal(
            f32(cache_got[name][0])[live], f32(cache_want[name][0])[live])
        np.testing.assert_allclose(
            f32(cache_got[name])[:, live], f32(cache_want[name])[:, live],
            atol=2 if kv_dtype == "int8" else STEP_ATOL, rtol=0)
        np.testing.assert_array_equal(
            f32(cache_got[name])[:, ~live], f32(state["cache"][name])[:, ~live])
        assert (f32(cache_want[name])[:, ~live]
                != f32(state["cache"][name])[:, ~live]).any()
    for name in set(cache_got) - {"k", "v"}:  # scales, conv and SSM state
        np.testing.assert_allclose(
            f32(cache_got[name])[:, live], f32(cache_want[name])[:, live],
            atol=STEP_ATOL, rtol=2e-2)  # a bf16 last bit of a value over 4


@pytest.mark.parametrize("preset,kv_dtype", [
    ("tiny", "bf16"), ("tiny", "int8"), ("tiny-lfm2", "bf16")])
def test_a_chunk_with_the_kernel_gives_the_scatter_paths_tokens(
        monkeypatch, preset, kv_dtype):
    """Four greedy steps of _chunk_impl with the kernel reading and
    writing the slab give the tokens of the chunk that scores every
    window by einsums and scatters every slot's row: a step reads what
    the steps before it wrote (positions across a block's edge and at
    one), live slots end with the same rows of K and V, and a dead
    slot's slab is untouched by the kernel's chunk."""
    cfg = _wide(preset, window=256, kv_cache_dtype=kv_dtype)
    monkeypatch.setattr(da, "ITEM_BYTES", 128 * 128 * 2)  # two items a window
    params = init_params(cfg, jax.random.key(0))
    active = jnp.array([True, False, True, True])
    pos = jnp.array([126, 190, 1, 200])
    state = _armed_state(cfg, 4, active, pos, jax.random.key(3))
    chunk = lambda: jax.jit(functools.partial(
        InferenceEngine._chunk_impl, cfg=cfg, n_steps=4))(params, state)
    want_state, want, valid, *_ = chunk()
    monkeypatch.setattr(da, "applies", da.reads)
    with pallas_interpret():
        got_state, got, got_valid, *_ = chunk()
    live = np.asarray(active)
    assert np.asarray(valid)[:, live].all()
    np.testing.assert_array_equal(np.asarray(got_valid), np.asarray(valid))
    np.testing.assert_array_equal(np.asarray(got)[:, live],
                                  np.asarray(want)[:, live])
    f32 = lambda a: np.asarray(a, np.float32)
    for name in ("k", "v"):
        np.testing.assert_allclose(
            f32(got_state["cache"][name])[:, live],
            f32(want_state["cache"][name])[:, live],
            atol=2 if kv_dtype == "int8" else STEP_ATOL, rtol=0)
        np.testing.assert_array_equal(
            f32(got_state["cache"][name])[:, ~live],
            f32(state["cache"][name])[:, ~live])


@pytest.mark.parametrize("kernel", [False, True])
def test_chunk_counts_the_kv_tokens_read_and_held(monkeypatch, kernel):
    """A chunk over a slab with known `active` and `pos` returns the
    sums by hand: with the einsums every step reads all the slab holds
    and writes a row of every slot, with the kernel whole blocks of the
    live slots up to where each has got, position 0 reading nothing, and
    the live slots' rows alone."""
    cfg = _wide("tiny", window=384)
    monkeypatch.setattr(da, "ITEM_BYTES", 128 * 128 * 2)  # items of 128 tokens
    params = init_params(cfg, jax.random.key(0))
    active = jnp.array([True, True, False, True, True])
    pos = jnp.array([0, 127, 200, 128, 129])
    state = _armed_state(cfg, 5, active, pos, jax.random.key(2))
    if kernel:
        monkeypatch.setattr(da, "applies", da.reads)
    chunk = jax.jit(functools.partial(
        InferenceEngine._chunk_impl, cfg=cfg, n_steps=2))
    with pallas_interpret():
        *_, counts = chunk(params, state)
    counts = dict(zip(CHUNK_COUNTERS, np.asarray(counts).tolist()))
    assert len(counts) == 3 + len(KV_COUNTERS)
    held = 2 * cfg.n_layers * 5 * 384
    assert counts["attn_kv_tokens_held"] == held
    # blocks of 128: step one reads 0, 1, -, 1, 2 of them, step two (every
    # live row one token on) 1, 1, -, 2, 2
    blocks = (0 + 1 + 1 + 2) + (1 + 1 + 2 + 2)
    assert counts["attn_kv_tokens_read"] == (
        cfg.n_layers * 128 * blocks if kernel else held)
    # two steps, a K row a layer: of all 5 slots, or of the 4 that are live
    assert counts["attn_kv_rows_slots"] == 2 * cfg.n_layers * 5
    assert counts["attn_kv_rows_written"] == 2 * cfg.n_layers * (
        4 if kernel else 5)


def test_a_slab_spread_over_devices_keeps_the_einsums(monkeypatch):
    """Tensor parallelism and a mesh the compiler partitions over say
    `spread`: no kernel reads a slab that lies over several devices,
    whatever the backend, and the step reads all the slab holds."""
    monkeypatch.setattr(da, "applies", da.reads)
    cfg = _wide("tiny")
    cache = transformer.init_cache(cfg, 4, cfg.max_seq_len)
    live, pos = jnp.array([True, False, True, True]), jnp.array([5, 9, 0, 77])
    assert transformer._sparse_decode(cfg, cache, live, pos, False) is not None
    assert transformer._sparse_decode(cfg, cache, live, pos, True) is None
    held = cfg.n_layers * 4 * cfg.max_seq_len
    rows = cfg.n_layers * 4
    assert transformer.decode_kv_counts(
        cfg, cache, live, pos, spread=True).tolist() == [held, held, rows, rows]
    assert transformer.decode_kv_counts(cfg, cache, live, pos).tolist() == [
        cfg.n_layers * 2 * 128, held, cfg.n_layers * 3, rows]
    # a live slot whose position has reached the slab's end writes no row
    assert transformer.decode_kv_counts(
        cfg, cache, live, pos.at[0].set(cfg.max_seq_len)).tolist()[2] \
        == cfg.n_layers * 2


# -- a block of query positions a slot (ModelConfig.gen_block) ----------------

SQ = 4
# slot -> (live, commits, position): no past (attention among the fresh
# columns alone; a loose write), a pass that only denoises, a block's
# last tile, a block's first row (loose), a dead slot that would commit,
# the window's last block, a denoising slot inside a tile, a tile's
# second group of four rows
BLOCK_SLOTS = [(True, True, 0), (True, False, 4), (True, True, 252),
               (True, True, 256), (False, True, 260), (True, True, T - SQ),
               (True, False, 300), (True, True, 68)]


@functools.lru_cache(maxsize=None)
def _block_case():
    Hkv, Dh, G = 4, 128, 8  # sdar-30b-a3b-chat's heads
    H, C = Hkv * G, Hkv * Dh
    ks = jax.random.split(jax.random.key(11), 5)
    bf16 = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, SQ, H, Dh)).astype(bf16)
    kf = jax.random.normal(ks[1], (B, SQ, Hkv, Dh)).astype(bf16)
    vf = (0.25 * jax.random.normal(ks[2], (B, SQ, Hkv, Dh))).astype(bf16)
    cache = {"k": jax.random.normal(ks[3], (LAYERS, B, 1, T, C), bf16),
             "v": 0.25 * jax.random.normal(ks[4], (LAYERS, B, 1, T, C), bf16)}
    live, commit, pos = (jnp.asarray(x) for x in zip(*BLOCK_SLOTS))
    with mock.patch.object(da, "ITEM_BYTES", ITEM_BYTES):
        block = da.block_size(cache["k"].shape, Dh, 2)
    assert block == 256
    with pallas_interpret():
        sched = da.committing(da.schedule(live, pos, T, block),
                              live & commit, T)
        out, k, v = jax.jit(lambda: da.attend(
            q, kf, vf, dict(cache), jnp.int32(1), sched))()
    mask_lt = jnp.arange(T)[None, None, :] < pos[:, None, None]
    want = transformer.gqa_attention_block(
        q, cache["k"][1], cache["v"][1], kf, vf, mask_lt)
    return (np.asarray(out, np.float32), np.asarray(want, np.float32),
            {"k": (cache["k"], k, kf), "v": (cache["v"], v, vf)})


def test_a_block_of_queries_matches_the_einsums():
    """Four query positions a slot: the fresh columns see each other (the
    einsums' leg masks nothing among them), a slot with no past attends
    among them alone."""
    got, want, _ = _block_case()
    live = np.array([s[0] for s in BLOCK_SLOTS])
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live], atol=ATTEND_ATOL, rtol=0)


def test_a_block_of_queries_sees_its_own_fresh_columns_unmasked():
    """The first position of a block with no past: under a causal mask
    among the fresh columns it would be its own value alone."""
    got, _, slabs = _block_case()
    _, _, vf = slabs["v"]
    alone = np.asarray(jnp.repeat(vf[0, 0], 8, axis=0), np.float32).reshape(-1)
    assert np.abs(got[0, 0] - alone).max() > 10 * ATTEND_ATOL


@pytest.mark.parametrize("key", ["k", "v"])
def test_only_the_committing_live_slots_rows_are_written(key):
    """The four rows pos .. pos + 3 of the slots that are live and
    commit, in the layer asked for; every other byte of the slab, the
    dead slot's and the denoising slots' among them, is what it was."""
    _, _, slabs = _block_case()
    before, after, fresh = slabs[key]
    want = np.array(before.astype(jnp.float32))
    for b, (live, commit, p) in enumerate(BLOCK_SLOTS):
        if live and commit:
            want[1, b, 0, p:p + SQ] = np.asarray(
                fresh[b].astype(jnp.float32)).reshape(SQ, -1)
    np.testing.assert_array_equal(np.asarray(after.astype(jnp.float32)), want)
