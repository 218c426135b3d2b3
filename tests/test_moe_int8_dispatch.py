"""A homogeneous stack's sparse block by token -> expert dispatch, with
int8 expert weights through the grouped product (ops/gmm_int8.py): the
kernel against the ragged product of the dequantised stack, the two
default-engine runners against moe_block on an int8 `tiny-moe`, and the
routing counters on the engine's access line. The kernel runs
interpreted here (tests/pallas_interpret.py); what Mosaic makes of it at
Mixtral's widths is tests/test_v5e_compile.py's."""

import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_tpu.models import transformer as T
from seldon_tpu.models.config import get_config
from seldon_tpu.models.quantize import _quantize_leaf, quantize_params
from seldon_tpu.ops import moe_dispatch
from seldon_tpu.servers.engine import (
    EngineConfig,
    InferenceEngine,
    SamplingParams,
)
from tests._engine_fixture import PROMPT
from tests.pallas_interpret import pallas_interpret


def _int8_stack(key, G, K, N):
    return _quantize_leaf(jax.random.normal(key, (G, K, N), jnp.float32) * 0.05)


# (groups E, k, n, rows m, the layer's group sizes, transpose_rhs, rows'
# dtype, layers merged into the stack, the layer the sizes belong to)
GROUPED_CASES = {
    "plain": (4, 256, 384, 128, [3, 1, 5, 2], False, "bfloat16", 1, 0),
    "transpose_rhs": (4, 256, 384, 128, [3, 1, 5, 2], True, "bfloat16", 1, 0),
    "an_empty_group": (4, 256, 384, 128, [3, 0, 5, 2], False, "bfloat16", 1, 0),
    "an_empty_group_transposed":
        (4, 256, 384, 128, [0, 7, 0, 2], True, "bfloat16", 1, 0),
    "rows_past_the_groups_and_a_padded_tile":
        (4, 256, 384, 100, [30, 0, 50, 2], False, "float32", 1, 0),
    "groups_over_several_row_tiles":
        (4, 256, 256, 384, [130, 0, 150, 2], False, "bfloat16", 1, 0),
    "several_k_steps": (4, 4096, 256, 128, [9, 0, 5, 2], False, "bfloat16", 1, 0),
    "layer_merged_stack_layer_2":
        (4, 256, 384, 128, [3, 0, 5, 2], False, "bfloat16", 3, 2),
    "layer_merged_stack_transposed_layer_1":
        (4, 256, 384, 128, [3, 0, 5, 2], True, "bfloat16", 3, 1),
}


@pytest.mark.parametrize("case", sorted(GROUPED_CASES))
def test_int8_grouped_matmul_is_the_ragged_dot_of_the_dequantised_stack(case):
    """ops/gmm_int8 (what grouped_matmul calls for an int8 rhs on a TPU)
    gives x @ (w_q * scale) for the rows a group owns: the scale applied
    after the float32 sum, an empty group skipped, rows past
    sum(group_sizes) left alone, and only the chosen layer's groups of a
    layer-merged stack used."""
    E, K, N, M, sizes, transpose, dtype, layers, layer = GROUPED_CASES[case]
    k = jax.random.split(jax.random.key(0), 2)
    wq, scale = _int8_stack(k[0], E * layers, K, N)
    x = jax.random.normal(k[1], (M, K), jnp.float32).astype(dtype)
    gs = jnp.zeros((E * layers,), jnp.int32).at[
        layer * E:(layer + 1) * E].set(jnp.asarray(sizes, jnp.int32))
    stored = jnp.swapaxes(wq, 1, 2) if transpose else wq
    with pallas_interpret():
        got = moe_dispatch._megablox(x, stored, gs, transpose, scale)
    assert got.shape == (M, N) and got.dtype == x.dtype
    want = jax.lax.ragged_dot(
        x.astype(jnp.float32), wq.astype(jnp.float32) * scale, gs)
    rows = sum(sizes)
    tol = 1e-4 if dtype == "float32" else 2e-2 * float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got[:rows], np.float32),
                               np.asarray(want[:rows]), atol=tol, rtol=0)
    # off a TPU the same call is the ragged product of the dequantised
    # stack (grouped_matmul asks the backend)
    cpu = moe_dispatch.grouped_matmul(x, stored, gs, transpose, scale)
    np.testing.assert_allclose(np.asarray(cpu[:rows], np.float32),
                               np.asarray(want[:rows]), atol=tol, rtol=0)


def test_int8_grouped_matmul_refuses_scales_of_another_shape():
    wq, scale = _int8_stack(jax.random.key(0), 4, 256, 384)
    x = jnp.zeros((128, 256), jnp.bfloat16)
    gs = jnp.asarray([1, 1, 1, 1], jnp.int32)
    with pallas_interpret(), pytest.raises(ValueError, match="scales"):
        moe_dispatch._megablox(x, wq, gs, False, scale[:, :, :128])


@pytest.fixture(scope="module")
def int8_moe():
    cfg = get_config("tiny-moe")
    return cfg, quantize_params(T.init_params(cfg, jax.random.key(0)))


def test_int8_stack_leaves_the_scan_whole_and_undequantised(int8_moe):
    cfg, params = int8_moe
    sliced, experts = T._dispatched_experts(params["blocks"], cfg, True)
    L, E = cfg.n_layers, cfg.n_experts
    stacks = ("w_gate", "w_up", "w_down")
    assert sorted(experts) == sorted(stacks + ("scales",))
    assert sorted(experts["scales"]) == sorted(stacks)
    assert "router" in sliced and not [n for n in sliced if n.startswith("w_")]
    assert experts["w_up"].dtype == jnp.int8
    assert experts["w_up"].shape == (L * E, cfg.d_model, cfg.d_ff)
    assert experts["scales"]["w_down"].shape == (L * E, 1, cfg.d_model)
    bf16 = T.init_params(cfg, jax.random.key(0))["blocks"]
    assert T._dispatched_experts(bf16, cfg, True)[1]["scales"] is None
    # a stack that is not whole on one device keeps moe_block
    assert T._dispatched_experts(params["blocks"], cfg, False) == \
        (params["blocks"], None)
    dense = get_config("tiny")
    blocks = T.init_params(dense, jax.random.key(0))["blocks"]
    assert T._dispatched_experts(blocks, dense, True) == (blocks, None)


def test_int8_prefill_and_decode_by_dispatch_agree_with_moe_block(int8_moe):
    """An int8 tiny-moe prefill and 8 decode steps through the dispatch
    (the default runners) against the same through moe_block (`spread`
    keeps it), within bf16's tolerance; and routing counts live rows
    alone: a dead slot and a prompt's right-padding route nowhere."""
    cfg, params = int8_moe
    L, K = cfg.n_layers, cfg.n_experts_per_token
    toks = jnp.asarray(np.arange(2, 2 + 36).reshape(3, 12), jnp.int32)
    plens = jnp.asarray([12, 7, 3], jnp.int32)
    cache = T.init_cache(cfg, 3, 32)
    lg, c = T.prefill(params, toks, plens, cache, cfg)
    lg0, c0 = T.prefill(params, toks, plens, cache, cfg, spread=True)
    scale = float(jnp.abs(lg0).max())
    assert float(jnp.abs(lg - lg0).max()) < 0.02 * scale

    # the prompts' own tokens were routed, the right-padding was not
    x = T._embed_rows(params, toks, jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(12), (3, 12))
    mask = jnp.tril(jnp.ones((12, 12), dtype=bool))[None].repeat(3, 0)
    _, _, counts = T._run_blocks_prefill(
        params, x, cfg, pos, T.rope_frequencies(cfg), mask, plens=plens)
    assert counts.tolist()[0::2] == [L, int(plens.sum()) * K * L]
    _, _, padded = T._run_blocks_prefill(
        params, x, cfg, pos, T.rope_frequencies(cfg), mask)
    assert padded.tolist()[2] == 36 * K * L

    live = jnp.asarray([True, False, True])
    tok = tok0 = jnp.argmax(lg0, -1).astype(jnp.int32)
    p = plens
    for _ in range(8):
        out, c, routing = T.decode_step(params, tok, p, c, cfg, live=live,
                                        return_routing=True)
        out0, c0, none = T.decode_step(params, tok0, p, c0, cfg, live=live,
                                       return_routing=True, spread=True)
        assert float(jnp.abs(out[live] - out0[live]).max()) < 0.03 * scale
        # two live rows: 2 x K assignments a layer, at most that many
        # experts and at least K; moe_block counts nothing
        steps, touched, assigned = routing.tolist()
        assert (steps, assigned) == (L, 2 * K * L)
        assert K * L <= touched <= 2 * K * L
        assert none.tolist() == [0, 0, 0]
        tok = tok0 = jnp.argmax(out0, -1).astype(jnp.int32)
        p = p + 1
    # one live row reads its own K experts a layer, whatever the dead
    # slots hold
    _, _, routing = T.decode_step(
        params, tok, p, c, cfg, live=jnp.asarray([False, True, False]),
        return_routing=True)
    assert routing.tolist() == [L, K * L, K * L]


def _access_lines(caplog, cfg, params):
    eng = InferenceEngine(
        params, cfg, EngineConfig(max_slots=2, max_seq_len=64,
                                  prompt_buckets=(32,)))
    eng.start()
    try:
        with caplog.at_level(logging.INFO, logger="seldon_tpu.access"):
            eng.generate_blocking(
                PROMPT, SamplingParams(temperature=0.0, max_new_tokens=6))
    finally:
        eng.stop()
    return [json.loads(r.getMessage().split(" ", 1)[1])
            for r in caplog.records
            if r.name == "seldon_tpu.access"
            and r.getMessage().startswith("request ")]


MOE_FIELDS = ("moe_sparse_layer_steps", "moe_experts_touched",
              "moe_assignments")


def test_access_line_of_a_homogeneous_moe_carries_the_routing_counters(
        caplog, int8_moe):
    cfg, params = int8_moe
    (line,) = _access_lines(caplog, cfg, params)
    steps, touched, assigned = (line[f] for f in MOE_FIELDS)
    L, K = cfg.n_layers, cfg.n_experts_per_token
    assert steps > 0 and steps % L == 0
    # one request: while it is live, one row a step reads its K experts
    # a layer; the steps of a chunk after it ended route nothing
    assert 0 < touched == assigned <= steps * K and assigned % (K * L) == 0
    assert "moe_assignments_held" not in line


def test_access_line_of_a_dense_stack_carries_no_routing_counters(caplog):
    cfg = get_config("tiny")
    (line,) = _access_lines(caplog, cfg, T.init_params(cfg, jax.random.key(0)))
    assert not [f for f in line if f.startswith("moe_")]
    assert "sampler_steps" in line and "attn_kv_tokens_read" in line
