"""Kernel tests: flash attention (pallas vs reference) and ring attention
(shard_map vs single-device reference) on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from seldon_tpu.ops.flash_attention import attention_reference, flash_attention
from seldon_tpu.parallel import MeshPlan, make_mesh
from seldon_tpu.parallel.ring_attention import ring_attention

from pallas_interpret import pallas_interpret


def _qkv(key, BH=4, Sq=64, Skv=64, Dh=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (BH, Sq, Dh), dtype),
        jax.random.normal(kk, (BH, Skv, Dh), dtype),
        jax.random.normal(kv, (BH, Skv, Dh), dtype),
    )


def test_reference_attention_causality():
    q, k, v = _qkv(jax.random.key(0))
    out = attention_reference(q, k, v, causal=True)
    # Changing a future key must not affect past outputs.
    k2 = k.at[:, -1].add(10.0)
    out2 = attention_reference(q, k2, v, causal=True)
    np.testing.assert_allclose(out[:, :-1], out2[:, :-1], rtol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_pallas_interpret_matches_reference(causal):
    """The kernel, interpreted on CPU, vs the reference."""
    q, k, v = _qkv(jax.random.key(1), BH=2, Sq=32, Skv=32, Dh=8)
    ref = attention_reference(q, k, v, causal=causal)
    with pallas_interpret():
        out = flash_attention(q, k, v, causal=causal, block_q=16,
                              block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


def test_flash_raises_instead_of_falling_back():
    """No second implementation stands behind the kernel: a shape the
    grid cannot cover raises, and so does a platform that cannot
    compile it (CPU without the test-only interpret mode)."""
    q, k, v = _qkv(jax.random.key(3), BH=2, Sq=24, Skv=24, Dh=8)
    with pytest.raises(ValueError, match="divisible blocks"):
        flash_attention(q, k, v, block_q=16, block_k=16)
    q, k, v = _qkv(jax.random.key(3), BH=2, Sq=32, Skv=32, Dh=8)
    with pytest.raises(ValueError, match="interpret mode"):
        flash_attention(q, k, v, block_q=16, block_k=16)


def test_flash_q_offset_decode_window():
    """q_offset masks correctly for a decode-style query suffix."""
    q, k, v = _qkv(jax.random.key(2), BH=2, Sq=8, Skv=32, Dh=8)
    # Queries are positions 24..31 of a 32-token sequence.
    out = attention_reference(q, k, v, causal=True, q_offset=24)
    full_q = jnp.concatenate(
        [jnp.zeros((2, 24, 8), q.dtype), q], axis=1
    )
    full = attention_reference(full_q, k, v, causal=True)
    np.testing.assert_allclose(out, full[:, 24:], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = make_mesh(MeshPlan(sp=4, dp=2))
    B, S, H, Dh = 2, 32, 4, 16
    key = jax.random.key(3)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, Dh))
    k = jax.random.normal(kk, (B, S, H, Dh))
    v = jax.random.normal(kv, (B, S, H, Dh))

    # Reference: fold heads, run full attention.
    def ref_fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)

    ref = attention_reference(ref_fold(q), ref_fold(k), ref_fold(v),
                              causal=causal)
    ref = ref.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)

    spec = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    out = jax.jit(
        lambda a, b, c: ring_attention(a, b, c, mesh, causal=causal)
    )(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_gqa_matches_expanded(causal):
    """GQA ring (Hkv-head k/v rotate) == pre-expanded full-head ring."""
    mesh = make_mesh(MeshPlan(sp=4, dp=2))
    B, S, H, Hkv, Dh = 2, 32, 8, 2, 16
    G = H // Hkv
    key = jax.random.key(9)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, Dh))
    k = jax.random.normal(kk, (B, S, Hkv, Dh))
    v = jax.random.normal(kv, (B, S, Hkv, Dh))

    spec = NamedSharding(mesh, P(None, "sp", None, None))
    qs = jax.device_put(q, spec)
    ks, vs = (jax.device_put(x, spec) for x in (k, v))
    out = jax.jit(
        lambda a, b, c: ring_attention(a, b, c, mesh, causal=causal)
    )(qs, ks, vs)

    # Head h must attend kv head h // G — same convention as
    # gqa_attention's reshape(B, S, Hkv, G, Dh).
    k_exp = jax.device_put(jnp.repeat(k, G, axis=2), spec)
    v_exp = jax.device_put(jnp.repeat(v, G, axis=2), spec)
    ref = jax.jit(
        lambda a, b, c: ring_attention(a, b, c, mesh, causal=causal)
    )(qs, k_exp, v_exp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


def test_ring_attention_grad_flows():
    mesh = make_mesh(MeshPlan(sp=2))
    B, S, H, Dh = 1, 16, 2, 8
    key = jax.random.key(4)
    q = jax.random.normal(key, (B, S, H, Dh))

    def loss(q):
        out = ring_attention(q, q, q, mesh, causal=True)
        return jnp.sum(out**2)

    g = jax.grad(loss)(q)
    assert np.isfinite(np.asarray(g)).all()


def test_forward_flash_flag_matches_xla():
    """cfg.attn_impl='flash' (the kernel, interpreted) == default path."""
    from seldon_tpu.models import forward, get_config, init_params

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    base = forward(params, tokens, cfg)
    flash_cfg = get_config("tiny", attn_impl="flash")
    with pallas_interpret():
        out = forward(params, tokens, flash_cfg)
    np.testing.assert_allclose(np.asarray(base), np.asarray(out), rtol=2e-2,
                               atol=2e-2)


def test_flash_gqa_native_interpret():
    """GQA via kv index_map == expanded-kv reference (interpret mode)."""
    B, H, Hkv, S, Dh = 2, 4, 2, 32, 8
    G = H // Hkv
    key = jax.random.key(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B * H, S, Dh))
    k = jax.random.normal(kk, (B * Hkv, S, Dh))
    v = jax.random.normal(kv, (B * Hkv, S, Dh))
    ref = attention_reference(
        q, jnp.repeat(k, G, axis=0), jnp.repeat(v, G, axis=0), causal=True
    )
    with pallas_interpret():
        out = flash_attention(q, k, v, causal=True, q_per_kv=G, block_q=16,
                              block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


def test_config_rejects_bad_attn_impl():
    from seldon_tpu.models import get_config

    with pytest.raises(AssertionError):
        get_config("tiny", attn_impl="Flash")


def test_ring_attn_impl_forward_matches_xla():
    """cfg.attn_impl='ring' + a sequence-sharded mesh: full forward equals
    the plain xla-attention forward (long-context scoring path)."""
    import dataclasses

    import jax
    import numpy as np

    from seldon_tpu.models import get_config, init_params, forward
    from seldon_tpu.parallel import MeshPlan, make_mesh

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    ref = forward(params, tokens, cfg)

    ring_cfg = dataclasses.replace(cfg, attn_impl="ring")
    mesh = make_mesh(MeshPlan(sp=4, tp=2))
    out = jax.jit(
        lambda p, t: forward(p, t, ring_cfg, ring_mesh=mesh)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(out), rtol=2e-2, atol=2e-2
    )


def test_ring_attn_train_step():
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_tpu.models import get_config
    from seldon_tpu.models.train import make_optimizer, make_sharded_train_step
    from seldon_tpu.parallel import MeshPlan, make_mesh

    cfg = dataclasses.replace(get_config("tiny"), attn_impl="ring")
    mesh = make_mesh(MeshPlan(dp=2, sp=2, tp=2))
    init_fn, step_fn = make_sharded_train_step(
        mesh, cfg, make_optimizer(total_steps=10), seq_sharded=True
    )
    state = init_fn(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size)
    state, metrics = step_fn(state, toks, jnp.ones((4, 32), jnp.float32))
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_decode_step_writes_each_layers_token_row_and_no_other(kv_dtype):
    """decode_step writes the slab [L, B, 1, T, Hkv * Dh] (scales
    [L, B, Hkv, T]) at each row's position in one batched scatter: in
    EVERY layer the token's row (and, int8, its heads' scales) changes,
    and no other position of any array does."""
    import dataclasses

    import numpy as np

    from seldon_tpu.models import get_config, init_params, transformer

    cfg = dataclasses.replace(get_config("tiny"), kv_cache_dtype=kv_dtype)
    params = init_params(cfg, jax.random.key(0))
    cache = transformer.init_cache(cfg, 2, 16)
    assert cache["k"].shape == (cfg.n_layers, 2, 1, 16,
                                cfg.n_kv_heads * cfg.head_dim)
    before = {k: np.asarray(v, np.float32) for k, v in cache.items()}
    tok = jnp.array([3, 4], jnp.int32)
    pos = jnp.array([2, 5], jnp.int32)
    _, cache = transformer.decode_step(params, tok, pos, cache, cfg)
    assert set(cache) == set(before)
    for key, old in before.items():
        new = np.asarray(cache[key], np.float32)
        assert new.shape == old.shape
        diff = new != old
        if diff.ndim == 5:  # k / v: any lane of the row
            diff = diff.any(axis=4)
        changed = diff.any(axis=2)  # [L, B, T]
        for b, p in enumerate([2, 5]):
            assert changed[:, b, p].all(), \
                f"{key}: every layer's fresh row lands at the position"
            changed[:, b, p] = False
        assert not changed.any(), f"{key}: no other slot may be touched"
