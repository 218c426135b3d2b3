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
import math
import re

import jax
import pytest

from seldon_tpu.models import init_params, slot, transformer
from seldon_tpu.models.config import get_config
from seldon_tpu.servers.engine import InferenceEngine
from tools.inspect_hlo import (
    big_instructions,
    computations,
    configuration,
    reachable,
)

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
    weight matrix stacked over the 3 layers (3 Mi): an op that large can
    only be cache."""
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
                           slots, cfg.gen_block)))
    chunk = jax.jit(
        functools.partial(InferenceEngine._chunk_impl, cfg=cfg,
                          n_steps=STEPS),
        donate_argnums=(1,))
    return chunk.lower(params, state).compile().as_text(), state


def _chip_branches(monkeypatch):
    """The grouped product, state update and decode attention a TPU
    takes: the program asks jax.default_backend(), the CPU here."""
    from seldon_tpu.ops import decode_attention, moe_dispatch, ssm_update

    monkeypatch.setattr(moe_dispatch, "grouped_matmul", moe_dispatch._megablox)
    monkeypatch.setattr(ssm_update, "update", ssm_update._pallas)
    monkeypatch.setattr(decode_attention, "applies", decode_attention.reads)


@pytest.fixture(scope="module")
def published_chunk(one_chip):
    """name -> (cfg, optimized HLO, state shapes) of the 4-step chunk of
    a file of benchmark/configs as it states it, with the chip's
    branches, over the cells' 64 slots x 1024; a configuration is
    compiled once for the tests that read it (25 s to a minute each)."""
    made = {}

    def chunk(name):
        if name not in made:
            cfg, init = configuration(name)
            with pytest.MonkeyPatch.context() as mp:
                _chip_branches(mp)
                made[name] = (cfg,) + _compiled_chunk(
                    cfg, one_chip, 64, 1024, init=init)
        return made[name]

    return chunk


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


def kernel_calls(hlo: str):
    """[(name, the computation's text)] of ops/decode_attention's custom
    calls: their result is a tuple, the attention and K and V written."""
    found = []
    for comp in re.split(r"\n(?=(?:ENTRY )?%\S+ \()", hlo):
        found += [(name, comp) for name in re.findall(
            r"%(decode_attention[.\w]*) = \(bf16\[", comp)]
    return found


def cache_copies(comp: str, shapes):
    """The lines of a computation's text that copy an array of one of
    `shapes` (dims as the HLO prints them: "3,64,4,2048"), started, done
    or whole: a cache array moved inside the layer loop is a pass over
    it in every layer of every step (PERF.md section 6, PR 32 and 42)."""
    found = []
    for line in comp.split("\n"):
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) copy(-start|-done)?\(", line)
        if m and any("[%s]" % dims in m.group(1) for dims in shapes):
            found.append(line.strip()[:160])
    return found


def ring_config():
    """tiny-laguna's pattern (window layers beside full ones, 6 and 8
    queries over KV heads of 128 lanes) with 8 KV heads: a layer of the
    ring over KERNEL_SLOTS x 512 rows is 32 Mi elements, the three window
    layers' rings 192 MB."""
    return get_config("tiny-laguna", d_model=256, head_dim=128, n_heads=16,
                      n_heads_window=16, n_kv_heads=8, sliding_window=512,
                      max_seq_len=KERNEL_WINDOW, vocab_size=512).validate()


@pytest.mark.parametrize("stack", ["bf16", "int8", "patterned", "ring"])
def test_decode_chunk_reads_the_slab_through_the_kernel(
        one_chip, monkeypatch, stack):
    """On a TPU the decode step's attention is ops/decode_attention: the
    compiled chunk of the dense bf16 and int8 stacks, of a patterned one
    and of one with window layers (slab and rings) holds its custom call
    (one a layer position of a scan body), handed K and V whole and
    aliased to its results, and NOTHING else as large as a layer of the
    slab: no copy, transpose or slice of it, no scatter of the step's
    fresh rows over every slot (the kernel writes the live slots'), no
    float32 score array [slots, heads, window] (what the einsums of
    gqa_attention_decode materialise a layer); and the computation that
    holds the call, the layer loop's body, copies no cache array."""
    _chip_branches(monkeypatch)
    if stack == "patterned":
        # heads of 128, two to a row: a layer of K is 2 Mi elements, as
        # large as a layer of the SSM state and larger than any weight
        cfg = dataclasses.replace(
            mamba_config(), n_heads=4, n_kv_heads=2, head_dim=128).validate()
    elif stack == "ring":
        cfg = ring_config()
    else:
        cfg = dense_config(stack)
    SLOTS, WINDOW = KERNEL_SLOTS, KERNEL_WINDOW
    hlo, state = _compiled_chunk(cfg, one_chip, SLOTS, WINDOW)
    row = cfg.n_kv_heads * cfg.head_dim
    layer_k = SLOTS * min(WINDOW, cfg.sliding_window or WINDOW) * row
    calls = kernel_calls(hlo)
    assert stack == "ring" or len(calls) == 1, [n for n, _ in calls]
    cache = state["cache"]
    dims = {key: ",".join(map(str, a.shape)) for key, a in cache.items()}
    written = set()  # K as each call returns it: the slab's, a ring's
    for name, comp in calls:
        call = re.search(
            r"%" + re.escape(name) + r" = \(bf16\[[\d,]*\]\S* (\w+\[[\d,]*\]).*"
            r"output_to_operand_aliasing=\{\{1\}: \(\d+, \{\}\), "
            r"\{2\}: \(\d+, \{\}\)\}", comp)
        assert call, name
        written.add(call.group(1))
        assert cache_copies(comp, dims.values()) == []
    kv = "s8[%s]" if stack == "int8" else "bf16[%s]"
    assert written == {kv % dims[key] for key in ("k", "kw") if key in cache}
    assert big_instructions(hlo, layer_k) == []
    scores = SLOTS * cfg.n_heads * WINDOW
    assert [typ for _, _, typ, _ in big_instructions(hlo, scores)
            if typ.startswith("f32[")
            and typ.split("]")[0].endswith(",%d" % WINDOW)] == []


def test_a_pass_over_blocks_reads_and_commits_through_the_kernel(
        published_chunk):
    """sdar-30b-a3b-chat as its file states it, 64 slots x 1024: a pass's
    attention is the decode-attention kernel at four query positions a
    slot (128 query rows), handed K and V whole and aliased to its
    results, so the commit's rows are written where they lie; nothing as
    large as a layer of the slab is copied, and no logits [slots, 4,
    vocabulary] with the block in the tile's sublanes are made beside
    the flat ones the sampler reads. The head, the sampler and the
    confidence sit in one conditional of three branches by the count of
    slots that hold an undecided position (slot.block_step): the empty
    branch makes no array with the vocabulary in it, the rung's scores
    slot.SCORED_SLOTS x 4 rows, and arrays of all 256 rows x the
    vocabulary are made in the full branch alone."""
    cfg, hlo, state = published_chunk("sdar-30b-a3b-chat")
    assert cfg.gen_block == 4 and state["blk_tok"].shape == (64, 4)
    calls = kernel_calls(hlo)
    assert len(calls) == 1, [n for n, _ in calls]
    name, comp = calls[0]
    dims = ",".join(map(str, state["cache"]["k"].shape))
    call = re.search(
        r"%" + re.escape(name) + r" = \(bf16\[64,128,128\]\S* (\w+\[[\d,]*\]).*"
        r"output_to_operand_aliasing=\{\{1\}: \(\d+, \{\}\), "
        r"\{2\}: \(\d+, \{\}\)\}", comp)
    assert call and call.group(1) == "bf16[%s]" % dims
    assert cache_copies(comp, [dims]) == []
    layer_k = 64 * 1024 * cfg.n_kv_heads * cfg.head_dim
    # (the sampler's drawn tier sorts float32 logits: a branch of its own)
    assert [typ for _, typ in relayouts(hlo, layer_k)
            if not typ.startswith("f32[256,")] == []
    assert not re.search(r"f32\[64,4,151936\]", hlo)
    comps = computations(hlo)
    (switch,) = [m for m in re.finditer(
        r"branch_computations=\{([^}]*)\}", hlo) if m.group(1).count(",") == 2]
    empty, rung, full = (reachable(comps, name.strip(" %"))
                         for name in switch.group(1).split(","))
    rows = 4 * slot.SCORED_SLOTS
    assert "151936" not in "".join(comps[c] for c in empty)
    rung_text = "".join(comps[c] for c in rung)
    assert "f32[%d,151936]" % rows in rung_text
    # 256 rows x the vocabulary (151936 = 1187 x 128), of any dtype
    wide = re.compile(r"\[256,(151936|1187,128)\]")
    assert not wide.search(rung_text)
    assert "f32[256,151936]" in "".join(comps[c] for c in full)
    assert [c for c in comps if c not in full and wide.search(comps[c])] == []
    # the grouped products see slots x 4 positions x 8 experts a token
    assert re.search(r"%gmm[.\d]* = bf16\[2048,768\]", hlo)


def _dims(typ: str):
    return [int(d) for d in
            typ[typ.index("[") + 1:typ.index("]")].split(",") if d]


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
        published_chunk):
    """The benchmark's falcon-h1-34b-instruct as its file states it (20
    query heads over 4 KV heads of 128, a state of 32 x 128 x 256 float32
    a slot, hidden 5120, the 261120-row head) over the cell's 64 slots x
    1024: Mosaic takes both kernels at these shapes (a head count off the
    sublane tile, a 4 MB block a slot), each layer position of the scan
    body calls each once, and the compiled chunk holds neither a copy,
    transpose or slice as large as a layer of the slab or of the state,
    nor the einsums' score array: KV and the SSM state of the SAME layer
    are read and written where they lie."""
    cfg, hlo, state = published_chunk("falcon-h1-34b-instruct")
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size) == (5120, 20, 4, 128, 21504, 261120)
    slots, window, L = 64, 1024, cfg.n_layers
    cache = state["cache"]
    assert cache["k"].shape == (L, slots, 1, window, 512)
    assert cache["ssm"].shape == (L, slots, 32, 128, 256)
    (_, layer_loop), = kernel_calls(hlo)
    assert cache_copies(layer_loop, [
        ",".join(map(str, a.shape)) for a in cache.values()]) == []
    whole = re.escape("f32[%d,%d,32,128,256]" % (L, slots))
    assert len(re.findall(
        r"%(ssm_update[.\w]*) = \(" + whole + r"\S* f32\[", hlo)) == 1
    layer_k = slots * window * 512
    # what is as large as a layer of K and shaped like the cache (the
    # slots beside the window, or beside the state's block)
    cachelike = [
        (op, typ) for _, op, typ, _ in big_instructions(hlo, layer_k)
        if re.search(r"\b%d,(1,)?%d,512\]|\b%d,32,128,256\]"
                     % (slots, window, slots), typ)]
    # not even the step's scatter of the fresh rows: the kernel writes them
    assert cachelike == []
    scores = slots * cfg.n_heads * window
    assert [typ for _, _, typ, _ in big_instructions(hlo, scores)
            if typ.startswith("f32[")
            and typ.split("]")[0].endswith(",%d" % window)] == []


def test_mixtral_chunk_hands_the_grouped_kernel_the_int8_stack_whole(
        published_chunk):
    """The benchmark's mixtral-8x7b as its file states it (8 experts of
    4096 x 14336, top-2, int8 weights, 5 layers) over the cell's 64 slots
    x 1024: Mosaic takes ops/gmm_int8 at these widths, the scan body
    calls it three times (gate, up, down) on the expert stack of ALL
    layers as the tree stores it, int8 and whole (the layer is picked by
    the group sizes), and the compiled chunk holds no copy, slice or
    widened twin as large as one layer's expert matrix stack: a step
    reads the experts its live rows chose and nothing else of them."""
    cfg, hlo, _ = published_chunk("mixtral-8x7b")
    L, E, D, F = cfg.n_layers, cfg.n_experts, cfg.d_model, cfg.d_ff
    assert (L, E, cfg.n_experts_per_token, D, F) == (5, 8, 2, 4096, 14336)
    assert "s8[%d,%d,%d]" % (L, D, D) in hlo  # int8 weights, as stored
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


def _as_stored(typ: str) -> bool:
    """The result type's layout is the row-major one the tree is stored
    in (whatever its tiling and memory space)."""
    layout = re.search(r"\{([\d,]+)", typ)
    return layout is None or layout.group(1) == ",".join(
        str(i) for i in reversed(range(len(_dims(typ)))))


def weight_copies(hlo: str):
    """(result type, parameter) of every `copy` in the entry computation
    that relays out a parameter of the layer stack: a pass over a whole
    stored weight that the chip runs on every chunk's entry. (A small
    stack copied into the chip's fast memory as it is stored is not
    one.)"""
    entry = hlo[hlo.index("\nENTRY "):]
    return [(typ, name) for typ, name in re.findall(
        r"= (\S+) copy\(%(params__(?:blocks|segments)\w*)",
        entry[:entry.index("\n}")]) if not _as_stored(typ)]


# A weight moved into the chip's fast memory ahead of its product, as it
# is stored: one pass over it, which small matrices of any kind get.
_PREFETCH = ("copy-start", "copy-done", "slice-start", "slice-done")


def projection_matrices(hlo: str, cfg):
    """(computation, op, result type) of every instruction outside a
    fusion's inside that MAKES one layer's whole wq or wk, [d_model,
    H*Dh] or [d_model, Hkv*Dh] in any order or split of the heads: a
    dequantised, sliced-out or relaid-out matrix that is written and
    read back in every layer of every step. A prefetch in the stored
    layout is not one."""
    outs = {cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim}
    found = []
    for comp, op, typ, _ in big_instructions(hlo, cfg.d_model * min(outs)):
        dims = [d for d in _dims(typ) if d != 1]
        if cfg.d_model in dims and not (op in _PREFETCH and _as_stored(typ)):
            dims.remove(cfg.d_model)
            if math.prod(dims) in outs:
                found.append((comp, op, typ))
    return found


def unrotated_config():
    """nemotron-3-nano-30b-a3b's kind: single-block layers whose
    attention layers have no rotation between the projections and the
    heads, 6 query heads over 2 KV heads of 128; no other matrix of the
    stack has wq's [384, 768] or wk's [384, 256] elements beside
    d_model."""
    cfg = dataclasses.replace(
        mamba_config(), d_model=384, n_heads=6, n_kv_heads=2, head_dim=128,
        d_ff_expert=64).validate()
    assert not cfg.rotary
    return cfg


@pytest.mark.parametrize("stack", [
    "dense-int8", "dense-bf16", "unrotated", "mixtral-8x7b",
    "falcon-h1-34b-instruct"])
def test_decode_step_reads_the_query_and_key_weights_once(
        one_chip, published_chunk, monkeypatch, stack):
    """transformer._qkv keeps a decode step's wq and wk products flat, so
    the compiled chunk takes the stored stack as the product's own
    operand, dequantise or layer slice fused in, as wv, wo and the MLP
    have it: (a) no copy of a parameter of the layer stack on the
    chunk's entry and (b) no stand-alone instruction that yields a
    layer's whole projection matrix. Unfenced, the reshape to heads
    folded into the product cost both and three passes over wq a layer
    (PERF.md section 6, PR 42)."""
    from seldon_tpu.models.quantize import init_params_int8

    if stack.startswith("dense-") or stack == "unrotated":
        _chip_branches(monkeypatch)
        # (the dense stack with no other matrix of wq's or wk's elements
        # beside d_model: wq [1024, 1024], wk [1024, 512])
        cfg = unrotated_config() if stack == "unrotated" \
            else dataclasses.replace(dense_config("bf16"), d_ff=1536,
                                     vocab_size=768).validate()
        init = init_params_int8 if stack == "dense-int8" else init_params
        hlo, _ = _compiled_chunk(cfg, one_chip, init=init)
    else:
        cfg, hlo, _ = published_chunk(stack)
    # the reader's names are there
    assert re.search(r"%params__(blocks|segments)\w*__wq__", hlo)
    assert weight_copies(hlo) == []
    assert projection_matrices(hlo, cfg) == []


@pytest.mark.parametrize("heads,window,G,S", [
    (64, 512, 2, 4096), (48, 0, 2, 4096), (64, 512, 8, 512), (48, 0, 1, 32)],
    ids=["band-64-heads-4096", "causal-48-heads-4096", "band-group-of-8", "bucket-32"])
def test_prefill_attention_kernel_compiles_at_the_published_heads(
        one_chip, heads, window, G, S):
    """Mosaic takes ops/prefill_attention's kernel at laguna-xs.2's two
    kinds (64 query heads inside a window of 512, 48 causal, over 8 KV
    heads of 128) at the buckets an admission reaches, and the call holds
    no array beside its operands: nothing S x S exists (PR 43)."""
    import jax.numpy as jnp

    from seldon_tpu.ops import prefill_attention as pa

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(functools.partial(
        pa.kernel, head_dim=128, window=window)).lower(
        shaped((G, S, heads * 128)), shaped((G, S, 1024)), shaped((G, S, 1024)),
        shaped((G,), jnp.int32)).compile()
    assert pa.KERNEL_NAME in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < G * S * S  # a byte a pair, at most


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_decode_kernel_compiles_over_a_ring_with_its_skipped_row(
        one_chip, dtype):
    """ops/decode_attention over a window layer's ring (32 slots x 512
    rows of 1024 lanes, 64 query heads) goes through Mosaic with the row
    a slot leaves unread and writes: the native tile of 16 bf16 rows (32
    of int8) cut out of the block in hand at a traced offset, the fresh
    row selected in, copied back into the aliased ring."""
    import jax.numpy as jnp

    from seldon_tpu.ops import decode_attention as da

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, kf, vf, k, v, scale, rows, active, pos):
        sched = da.schedule(active, pos, 512, da.reads(k, 128), ring=True)
        cache, stored = {"k": k, "v": v}, None
        if k.dtype == jnp.int8:
            cache.update(k_scale=scale, v_scale=scale)
            stored = {"k": rows, "v": rows}
        return da.attend(q, kf, vf, cache, jnp.asarray(1), sched, stored)

    compiled = jax.jit(step, donate_argnums=(3, 4)).lower(
        shaped((32, 1, 64, 128)), shaped((32, 1, 8, 128)), shaped((32, 1, 8, 128)),
        shaped((3, 32, 1, 512, 1024), dtype), shaped((3, 32, 1, 512, 1024), dtype),
        shaped((3, 32, 8, 512)), shaped((32, 1024), jnp.int8),
        shaped((32,), jnp.bool_), shaped((32,), jnp.int32)).compile()
    assert kernel_calls(compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 32 * 512 * 1024
