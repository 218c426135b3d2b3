"""What the TPU v5e's compiler makes of the engine's decode chunk, with
no chip: libtpu compiles for a chip that is described and not attached
(jax.experimental.topologies, "v5e:2x2"), and `.compile().as_text()` is
the optimized HLO the chip would run. A cache-sized `copy` in it is a
relayout the chip would execute on every chunk or step (PERF.md section
6, PR 28 and PR 32: the head-major slab cost the dense cells 31 % of
their device time that way).

The topology is described inside a fixture, never while a module is
imported, and every test here skips where it cannot be had. Keep such
compiles in this one file: the worker that runs it holds libtpu.
"""

import dataclasses
import functools
import re

import jax
import pytest

from seldon_tpu.models import init_params, slot, transformer
from seldon_tpu.models.config import get_config
from seldon_tpu.servers.engine import InferenceEngine
from tools.inspect_hlo import big_instructions

SLOTS, WINDOW, STEPS = 32, 256, 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out of there.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def dense_config(kv_dtype):
    """A small homogeneous stack with the dense cells' heads of 128
    (4 KV heads: a row of 512 lanes), sized so that one layer's K over
    the slab (SLOTS x WINDOW x 512 = 4 Mi elements) is larger than any
    weight matrix stacked over the 3 layers (3 Mi; the compiled chunk
    copies three such stacks on entry, PERF.md section 7): an op that
    large can only be cache."""
    return dataclasses.replace(
        get_config("tiny"), d_model=1024, n_heads=8, head_dim=0, n_kv_heads=4,
        d_ff=1024,
        n_layers=3, vocab_size=512, max_seq_len=WINDOW,
        kv_cache_dtype=kv_dtype).validate()


def relayouts(hlo: str, at_least: int):
    """(op, result type) of every stand-alone copy or transpose of the
    compiled program with at least `at_least` result elements."""
    return [(op, typ) for _, op, typ, _ in big_instructions(hlo, at_least)
            if op in ("copy", "transpose")]


def _compiled_chunk(cfg, one_chip, slots=SLOTS, window=WINDOW,
                    init=init_params):
    """The optimized HLO of the 4-step decode chunk of `cfg` over slots x
    window, compiled for the described v5e, and the state's shapes."""
    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shapes(jax.eval_shape(lambda: init(cfg, jax.random.key(0))))
    state = shapes(jax.eval_shape(
        lambda: slot.fresh(transformer.init_cache(cfg, slots, window),
                           slots)))
    chunk = jax.jit(
        functools.partial(InferenceEngine._chunk_impl, cfg=cfg,
                          n_steps=STEPS),
        donate_argnums=(1,))
    return chunk.lower(params, state).compile().as_text(), state


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_decode_chunk_copies_no_layer_of_the_slab(one_chip, kv_dtype):
    """The compiled 4-step chunk reads and writes the slab as stored:
    no copy or transpose as large as one layer's K is left in it (the
    head-major slab had the whole cache copied three times a chunk and a
    layer's K and V slice on every step)."""
    cfg = dense_config(kv_dtype)
    assert cfg.head_dim == 128

    hlo, state = _compiled_chunk(cfg, one_chip)
    layer_k = SLOTS * WINDOW * cfg.n_kv_heads * cfg.head_dim
    assert state["cache"]["k"].shape == (
        cfg.n_layers, SLOTS, 1, WINDOW, cfg.n_kv_heads * cfg.head_dim)
    assert relayouts(hlo, layer_k) == []
    # the reader finds what it looks for: the weights' copies are there
    assert relayouts(hlo, 1)


# The cells' 64 slots and twice their window: K and V of a stack below
# are then 134 MB and more each, as a deployment's are too large for the
# chip's 128 MiB of fast memory (a slab that fits, the compiler moves
# there whole before the kernel's call: a copy as large as the slab that
# no deployment runs).
KERNEL_SLOTS, KERNEL_WINDOW = 64, 2048


@pytest.mark.parametrize("stack", ["bf16", "int8", "patterned"])
def test_decode_chunk_reads_the_slab_through_the_kernel(
        one_chip, monkeypatch, stack):
    """On a TPU the decode step's attention is ops/decode_attention: the
    compiled chunk of the dense bf16 and int8 stacks and of a patterned
    one holds its custom call (one a layer position of a scan body),
    handed the slab whole, and nothing else as large as a layer of the
    slab: no copy, transpose or slice of it, and no float32 score array
    [slots, heads, window] (what the einsums of gqa_attention_decode
    materialise a layer)."""
    from seldon_tpu.ops import decode_attention, moe_dispatch, ssm_update

    # the chip's branches (the program asks jax.default_backend())
    monkeypatch.setattr(moe_dispatch, "grouped_matmul", moe_dispatch._megablox)
    monkeypatch.setattr(ssm_update, "update", ssm_update._pallas)
    monkeypatch.setattr(decode_attention, "applies", decode_attention.reads)
    if stack == "patterned":
        # heads of 128, two to a row: a layer of K is 2 Mi elements, as
        # large as a layer of the SSM state and larger than any weight
        cfg = dataclasses.replace(
            mamba_config(), n_heads=4, n_kv_heads=2, head_dim=128).validate()
    else:
        cfg = dense_config(stack)
    SLOTS, WINDOW = KERNEL_SLOTS, KERNEL_WINDOW
    hlo, _ = _compiled_chunk(cfg, one_chip, SLOTS, WINDOW)
    row = cfg.n_kv_heads * cfg.head_dim
    layer_k = SLOTS * WINDOW * row
    calls = re.findall(r"%(decode_attention[.\w]*) = bf16\[", hlo)
    assert len(calls) == 1, calls
    slab = "%s[%d,%d,1,%d,%d]" % (
        "s8" if stack == "int8" else "bf16",
        cfg.n_attn_layers, SLOTS, WINDOW, row)
    assert slab in hlo  # carried whole, in the loops' tuples
    big = big_instructions(hlo, layer_k)
    # what is left at that size is the step's scatter of the fresh rows
    # into the whole slab, in place (a fusion whose result IS the slab)
    assert {op for _, op, _, _ in big} <= {"fusion"}, big
    assert {_elements(typ) for _, _, typ, _ in big} <= {
        cfg.n_attn_layers * layer_k}, big
    scores = SLOTS * cfg.n_heads * WINDOW
    assert [typ for _, _, typ, _ in big_instructions(hlo, scores)
            if typ.startswith("f32[")
            and typ.split("]")[0].endswith(",%d" % WINDOW)] == []


def _elements(typ: str) -> int:
    n = 1
    for d in typ[typ.index("[") + 1:typ.index("]")].split(","):
        n *= int(d)
    return n


def mamba_config():
    """Single-block layers sized so that ONE Mamba-2 layer's SSM state
    over the slab (SLOTS x 8 heads x 64 x 128 float32 = 2 Mi elements)
    is larger than any weight matrix stacked over the period's repeats
    and than the KV and conv state: a float32 result that large can only
    be the SSM state."""
    return dataclasses.replace(
        get_config("tiny-nemotron"), d_model=256, n_layers=14,
        layer_types=("mamba", "moe", "mamba", "moe", "mamba", "attention",
                     "moe") * 2,
        ssm_heads=8, ssm_head_dim=64, ssm_groups=2, ssm_state=128,
        ssm_chunk=128, vocab_size=512, max_seq_len=WINDOW).validate()


def test_decode_chunk_updates_the_ssm_state_in_place(one_chip, monkeypatch):
    """Every decode step reads and writes every Mamba-2 layer's state for
    every slot. The compiled chunk does so where the state lies: the
    whole state appears only as the aliased result of the update kernel
    (ops/ssm_update.py, one call a Mamba-2 layer of the period) or
    carried through the loops, and nothing at the top level (a copy, a
    transpose, a stand-alone slice or its fusion) yields an array of one
    layer's state."""
    from seldon_tpu.ops import moe_dispatch, ssm_update

    # the chip's branches (the program asks jax.default_backend())
    monkeypatch.setattr(moe_dispatch, "grouped_matmul", moe_dispatch._megablox)
    monkeypatch.setattr(ssm_update, "update", ssm_update._pallas)
    cfg = mamba_config()

    hlo, state = _compiled_chunk(cfg, one_chip)
    ssm = state["cache"]["ssm"]
    assert ssm.shape == (6, SLOTS, 8, 64, 128) and ssm.dtype == "float32"
    layer = SLOTS * 8 * 64 * 128
    # no instruction at all yields an array as large as one layer's state
    # (the loops and the kernel carry it inside tuples)
    assert big_instructions(hlo, layer) == []
    whole = re.escape("f32[6,%d,8,64,128]" % SLOTS)
    calls = re.findall(r"%(ssm_update[.\w]*) = \(" + whole + r"\S* f32\[", hlo)
    assert len(calls) == 3, calls


def test_attention_and_mixer_in_one_layer_at_the_published_widths(
        one_chip, monkeypatch):
    """The benchmark's falcon-h1-34b-instruct as its file states it (20
    query heads over 4 KV heads of 128, a state of 32 x 128 x 256 float32
    a slot, hidden 5120, the 261120-row head) over the cell's 64 slots x
    1024: Mosaic takes both kernels at these shapes (a head count off the
    sublane tile, a 4 MB block a slot), each layer position of the scan
    body calls each once, and the compiled chunk holds neither a copy,
    transpose or slice as large as a layer of the slab or of the state,
    nor the einsums' score array: KV and the SSM state of the SAME layer
    are read and written where they lie."""
    import json
    import os

    from seldon_tpu.models.config import ModelConfig
    from seldon_tpu.ops import decode_attention, ssm_update
    from tests.test_falcon_h1 import ROOT, _family

    monkeypatch.setattr(ssm_update, "update", ssm_update._pallas)
    monkeypatch.setattr(decode_attention, "applies", decode_attention.reads)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "falcon-h1-34b-instruct.json")) as f:
        raw = json.load(f)
    cfg = ModelConfig(**_family().model_config_kwargs(raw)).validate()
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size) == (5120, 20, 4, 128, 21504, 261120)
    slots, window, L = 64, 1024, cfg.n_layers
    hlo, state = _compiled_chunk(cfg, one_chip, slots, window)
    cache = state["cache"]
    assert cache["k"].shape == (L, slots, 1, window, 512)
    assert cache["ssm"].shape == (L, slots, 32, 128, 256)
    assert len(re.findall(r"%(decode_attention[.\w]*) = bf16\[", hlo)) == 1
    whole = re.escape("f32[%d,%d,32,128,256]" % (L, slots))
    assert len(re.findall(
        r"%(ssm_update[.\w]*) = \(" + whole + r"\S* f32\[", hlo)) == 1
    layer_k = slots * window * 512
    # what is as large as a layer of K and shaped like the cache (the
    # slots beside the window, or beside the state's block; the weights'
    # relayouts on the chunk's entry, PERF.md section 7 k, are larger and
    # are not the cache's)
    cachelike = [
        (op, typ) for _, op, typ, _ in big_instructions(hlo, layer_k)
        if re.search(r"\b%d,(1,)?%d,512\]|\b%d,32,128,256\]"
                     % (slots, window, slots), typ)]
    # the step's scatter of the fresh rows into the whole slab, in place
    assert {op for op, _ in cachelike} <= {"fusion"}, cachelike
    assert {_elements(typ) for _, typ in cachelike} <= {L * layer_k}, cachelike
    assert not [typ for _, typ in cachelike if typ.startswith("f32[")]
    scores = slots * cfg.n_heads * window
    assert [typ for _, _, typ, _ in big_instructions(hlo, scores)
            if typ.startswith("f32[")
            and typ.split("]")[0].endswith(",%d" % window)] == []


def test_mixtral_chunk_hands_the_grouped_kernel_the_int8_stack_whole(
        one_chip, monkeypatch):
    """The benchmark's mixtral-8x7b as its file states it (8 experts of
    4096 x 14336, top-2, int8 weights, 5 layers) over the cell's 64 slots
    x 1024: Mosaic takes ops/gmm_int8 at these widths, the scan body
    calls it three times (gate, up, down) on the expert stack of ALL
    layers as the tree stores it, int8 and whole (the layer is picked by
    the group sizes), and the compiled chunk holds no copy, slice or
    widened twin as large as one layer's expert matrix stack: a step
    reads the experts its live rows chose and nothing else of them."""
    import json
    import os

    from seldon_tpu.models.config import ModelConfig
    from seldon_tpu.models.quantize import init_params_int8
    from seldon_tpu.ops import decode_attention, moe_dispatch
    from tests.test_falcon_h1 import ROOT

    monkeypatch.setattr(moe_dispatch, "grouped_matmul", moe_dispatch._megablox)
    monkeypatch.setattr(decode_attention, "applies", decode_attention.reads)
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    import family

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mixtral-8x7b.json")) as f:
        raw = json.load(f)
    assert raw["serving"]["weight_dtype"] == "int8"
    cfg = ModelConfig(**family.load(os.path.join(ROOT, "benchmark"), raw)
                      .model_config_kwargs(raw)).validate()
    L, E, D, F = cfg.n_layers, cfg.n_experts, cfg.d_model, cfg.d_ff
    assert (L, E, cfg.n_experts_per_token, D, F) == (5, 8, 2, 4096, 14336)
    hlo, _ = _compiled_chunk(cfg, one_chip, 64, 1024, init=init_params_int8)
    calls = re.findall(r"%gmm_int8[.\d]* = bf16\[128,(\d+)\]\S* "
                       r"custom-call\(([^)]*)\)", hlo)
    assert sorted(n for n, _ in calls) == ["14336", "14336", "4096"], calls
    # the kernel's weight operand (after the three scalar-prefetch arrays
    # and the rows) is a parameter or loop-carried value of the merged
    # stack's shape, not the result of a fusion, copy or slice
    merged = {"14336": "s8[%d,%d,%d]" % (L * E, D, F),
              "4096": "s8[%d,%d,%d]" % (L * E, F, D)}
    for n, operands in calls:
        weights = operands.split(", ")[5].split("*/")[-1].lstrip("%")
        made = re.search(r"%" + re.escape(weights) + r" = (\S+) (\S+?)\(", hlo)
        assert made and made.group(1).startswith(merged[n]), (n, made)
        assert made.group(2) in ("get-tuple-element", "parameter", "bitcast"), made
    # nothing the size of one layer's expert matrix, in any dtype
    one_matrix_stack = E * D * F
    assert [(op, typ) for _, op, typ, _ in
            big_instructions(hlo, one_matrix_stack)] == []
