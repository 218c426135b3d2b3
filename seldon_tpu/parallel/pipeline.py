"""Pipeline parallelism ('pp' axis): GPipe-style microbatch pipeline.

No reference equivalent (SURVEY.md §2.9: reference has no model
parallelism at all) — this is TPU-native capability. The layer-stacked
param layout (models/transformer.py: every block leaf is [L, ...]) makes
pipelining a *sharding* of the leading layer axis: stage i holds layers
[i*L/P, (i+1)*L/P). Activations flow stage-to-stage over ICI via
`ppermute` inside a partial-manual `jax.shard_map` — only 'pp' is manual;
dp/sp/tp/ep stay automatic, so tensor-parallel all-reduces and
data-parallel batch sharding compose with the pipeline untouched.

Schedule: GPipe with M microbatches over P stages — T = M + P - 1 ticks,
bubble fraction (P-1)/T. Each tick every stage runs its local layer scan
on its current microbatch and ppermutes the result to the next stage.
The whole schedule is one `lax.scan`, so it is reverse-differentiable
(training) and compiles to a single fused program.

Use `pp_param_pspecs(cfg)` for the weight shardings and
`make_pipeline_forward(mesh, cfg, n_microbatches)` for the forward fn.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from seldon_tpu.models import transformer
from seldon_tpu.models.config import ModelConfig
from seldon_tpu.models.transformer import _dtype
from seldon_tpu.parallel import sharding as shd


def pp_param_pspecs(cfg) -> Dict[str, Any]:
    """param_pspecs with the stacked layer axis sharded over 'pp'.

    Block leaves are [L, ...]: prepending 'pp' to their spec gives each
    stage a contiguous slab of layers. Non-block params (embed, final
    norm, lm_head) stay pp-replicated — they are consumed outside the
    manual region.
    """
    specs = shd.param_pspecs(cfg)
    blocks = {}
    for name, spec in specs["blocks"].items():
        blocks[name] = P("pp", *spec[1:])
    specs["blocks"] = blocks
    return specs


def _stage_body(x, blocks_local, cfg: ModelConfig, positions, inv_freq, mask,
                remat: bool):
    """Run this stage's local layers (a scan over the local slab)."""

    def body(carry, bp):
        out, aux = transformer._block(
            carry, bp, cfg, positions, inv_freq, mask
        )
        return out, aux

    if remat:
        body = jax.checkpoint(body)
    x, aux = jax.lax.scan(body, x, blocks_local)
    return x, jnp.sum(aux)


def make_pipeline_forward(
    mesh: Mesh,
    cfg: ModelConfig,
    n_microbatches: int = 4,
    remat: bool = False,
):
    """Returns fwd(params, tokens) -> (logits [B,S,V], aux dict).

    `params` must be sharded with `pp_param_pspecs`. Batch must divide
    n_microbatches. Embedding and the vocab projection run OUTSIDE the
    manual region (auto GSPMD: vocab stays tp-sharded); only the block
    stack is pipelined.
    """
    cfg = cfg.validate()
    n_stages = mesh.shape["pp"]
    if cfg.n_layers % n_stages != 0:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp={n_stages}"
        )
    M = n_microbatches

    block_specs = pp_param_pspecs(cfg)["blocks"]
    # Manual specs mention ONLY the manual axis: stage-local layer slab.
    block_manual_specs = jax.tree.map(
        lambda s: P("pp", *([None] * (len(s) - 1))),
        block_specs,
        is_leaf=lambda x: isinstance(x, P),
    )

    def staged(blocks, x_embedded, positions, inv_freq, mask):
        """x_embedded [B,S,D] -> hidden [B,S,D]; manual over 'pp' only.

        x stays f32 until it merges into the (pp-varying) pipeline state:
        every pp-invariant value consumed by varying compute gets an
        implicit pcast whose transpose is a psum over 'pp', and XLA's
        all-reduce type promotion aborts on bf16 all-reduce on the CPU
        backend (test mesh) — so all such boundaries are kept f32."""
        stage = jax.lax.axis_index("pp")
        B = x_embedded.shape[0]
        mb = B // M
        x_mb = x_embedded.reshape(M, mb, *x_embedded.shape[1:])
        pos_mb = positions.reshape(M, mb, *positions.shape[1:])
        mask_mb = mask.reshape(M, mb, *mask.shape[1:])

        T = M + n_stages - 1
        # Initial carries must be marked pp-varying: each stage's state
        # diverges after the first ppermute (scan requires carry types to
        # be loop-invariant, including the varying-manual-axes set).
        # pcast-to-varying transposes to a psum over 'pp'; keep that psum
        # in f32 (same CPU-backend bf16 all-reduce workaround as below) by
        # casting AFTER the pcast.
        def pvary(shape, dtype):
            z = jax.lax.pcast(jnp.zeros(shape, jnp.float32), ("pp",),
                              to="varying")
            return z.astype(dtype)

        dt = _dtype(cfg)
        state = pvary(x_mb[0].shape, dt)
        outputs = pvary(x_mb.shape, dt)
        aux_total = pvary((), jnp.float32)

        def tick(carry, t):
            state, outputs, aux_total = carry
            in_idx = jnp.clip(t, 0, M - 1)
            inp = jax.lax.dynamic_index_in_dim(x_mb, in_idx, 0, False)
            pos_t = jax.lax.dynamic_index_in_dim(pos_mb, in_idx, 0, False)
            mask_t = jax.lax.dynamic_index_in_dim(mask_mb, in_idx, 0, False)
            # Stage 0 consumes fresh microbatches; later stages consume
            # what the previous stage ppermuted over last tick. (pos/mask
            # are causal and identical across microbatches, so indexing
            # them by in_idx rather than the in-flight microbatch id is
            # exact for this full-sequence forward.)
            x_in = jnp.where(
                stage == 0, inp, state.astype(jnp.float32)
            ).astype(dt)
            y, aux = _stage_body(
                x_in, blocks, cfg, pos_t, inv_freq, mask_t, remat
            )
            out_idx = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (out_idx >= 0)
            oi = jnp.clip(out_idx, 0, M - 1)
            prev = jax.lax.dynamic_index_in_dim(outputs, oi, 0, False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, y, prev), oi, 0
            )
            # Only count aux for ticks carrying a real microbatch through
            # this stage: stage s is busy for t in [s, s+M).
            busy = (t >= stage) & (t < stage + M)
            aux_total = aux_total + jnp.where(busy, aux, 0.0)
            state = jax.lax.ppermute(
                y, "pp",
                perm=[(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            return (state, outputs, aux_total), None

        (state, outputs, aux_total), _ = jax.lax.scan(
            tick, (state, outputs, aux_total), jnp.arange(T)
        )
        # Results live on the last stage; psum broadcasts them (all other
        # stages contribute zeros) so the return value is pp-replicated.
        # f32 for the collective: XLA's all-reduce type promotion chokes
        # on bf16 all-reduce on the CPU backend (test mesh).
        hidden = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, 0.0).astype(jnp.float32),
            "pp",
        ).astype(outputs.dtype)
        # aux_total sums per-layer aux over every (stage, microbatch);
        # psum over stages then normalize to the mean over L*M terms.
        aux_mean = jax.lax.psum(aux_total, "pp") / (cfg.n_layers * M)
        return hidden.reshape(-1, *hidden.shape[2:]), aux_mean

    # Partial-manual ('pp' manual, dp/tp/... auto) lets GSPMD shard the
    # stage bodies internally.
    staged_sm = jax.shard_map(
        staged,
        mesh=mesh,
        in_specs=(block_manual_specs, P(), P(), P(), P()),
        out_specs=(P(), P()),
        axis_names=frozenset({"pp"}),
        check_vma=False,
    )

    def fwd(params, tokens):
        B, S = tokens.shape
        if B % M != 0:
            raise ValueError(f"batch {B} not divisible by microbatches {M}")
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        inv_freq = transformer.rope_frequencies(cfg)
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))[None].repeat(B, 0)
        hidden, aux = staged_sm(params["blocks"], x, positions, inv_freq, mask)
        logits = transformer._logits(params, hidden, cfg)
        return logits, {"moe_lb_loss": aux}

    return fwd
