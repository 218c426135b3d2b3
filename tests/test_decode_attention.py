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
from seldon_tpu.ops.ragged_paged_attention import RAGGED_LOGITS_ATOL
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
    if kv_dtype == "int8":
        cache = transformer.kv_writes(cache, {}, dataclasses.replace(
            get_config("tiny"), kv_cache_dtype="int8", head_dim=Dh))
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
                         da.schedule(active, pos, T, block))

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
                got[past], want[past], atol=RAGGED_LOGITS_ATOL, rtol=0)
            np.testing.assert_array_equal(
                got[~past], np.asarray(fresh_alone, f32)[~past])


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
    it, where attention alone is held to RAGGED_LOGITS_ATOL above; a
    wrong layer, slot or mask moves these logits by 0.3 and more) and
    the cache they write is the same; dead rows stay finite."""
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
    for name in ("k", "v"):  # the first layer's rows do not pass through attention
        np.testing.assert_array_equal(
            np.asarray(cache_got[name][0], np.float32),
            np.asarray(cache_want[name][0], np.float32))


@pytest.mark.parametrize("kernel", [False, True])
def test_chunk_counts_the_kv_tokens_read_and_held(monkeypatch, kernel):
    """A chunk over a slab with known `active` and `pos` returns the two
    sums by hand: with the einsums every step reads all the slab holds,
    with the kernel whole blocks of the live slots up to where each has
    got, position 0 reading nothing."""
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
    assert transformer.decode_kv_counts(
        cfg, cache, live, pos, spread=True).tolist() == [held, held]
    assert transformer.decode_kv_counts(
        cfg, cache, live, pos).tolist() == [cfg.n_layers * 2 * 128, held]
