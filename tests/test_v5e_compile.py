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
        get_config("tiny"), d_model=1024, n_heads=8, n_kv_heads=4, d_ff=1024,
        n_layers=3, vocab_size=512, max_seq_len=WINDOW,
        kv_cache_dtype=kv_dtype).validate()


def relayouts(hlo: str, at_least: int):
    """(op, result type) of every stand-alone copy or transpose of the
    compiled program with at least `at_least` result elements."""
    return [(op, typ) for _, op, typ, _ in big_instructions(hlo, at_least)
            if op in ("copy", "transpose")]


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_decode_chunk_copies_no_layer_of_the_slab(one_chip, kv_dtype):
    """The compiled 4-step chunk reads and writes the slab as stored:
    no copy or transpose as large as one layer's K is left in it (the
    head-major slab had the whole cache copied three times a chunk and a
    layer's K and V slice on every step)."""
    cfg = dense_config(kv_dtype)
    assert cfg.head_dim == 128

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    state = shapes(jax.eval_shape(
        lambda: slot.fresh(transformer.init_cache(cfg, SLOTS, WINDOW),
                           SLOTS)))
    chunk = jax.jit(
        functools.partial(InferenceEngine._chunk_impl, cfg=cfg,
                          n_steps=STEPS),
        donate_argnums=(1,))
    hlo = chunk.lower(params, state).compile().as_text()
    layer_k = SLOTS * WINDOW * cfg.n_kv_heads * cfg.head_dim
    assert state["cache"]["k"].shape == (
        cfg.n_layers, SLOTS, 1, WINDOW, cfg.n_kv_heads * cfg.head_dim)
    assert relayouts(hlo, layer_k) == []
    # the reader finds what it looks for: the weights' copies are there
    assert relayouts(hlo, 1)
