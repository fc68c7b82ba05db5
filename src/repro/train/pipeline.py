"""Pipeline parallelism (GPipe fill–drain) over a "pipe" mesh axis.

Why a third parallelism kind: at 1000+ nodes the (data × model) plane hits
diminishing returns — TP beyond one pod's ICI reach is collective-bound and
DP multiplies optimizer memory. Splitting the *layer stack* into S stages
multiplies reachable model size by S with only point-to-point
(collective-permute) traffic between neighbours, which maps exactly onto
TPU ICI links.

Implementation (pure JAX, shard_map-friendly):

  * stage-stacked params: every leaf is [S, n_layers/S, ...], sharded
    P("pipe", ...) — each pipe group holds one stage's layers;
  * the schedule runs T = M + S − 1 ticks (M = microbatches). At tick t,
    stage s processes microbatch (t − s); activations hop s → s+1 via
    ``jax.lax.ppermute``. The classic rotating-buffer formulation keeps
    the loop body identical per tick (scan-able, SPMD-uniform);
  * loss is computed on the LAST stage's slots and psum'd; ``jax.grad``
    differentiates straight through the ppermute schedule — the reverse
    schedule (activations flow backward) emerges from AD, no hand-written
    backward pass.

This module is self-contained on top of models/lm._dense_block_fwd — the
PP mesh (pipe, data, model) is an additional deployment mode, exercised by
its own dry-run entry (launch/dryrun_pp.py) and subprocess tests; the
assigned 40-cell sweep stays on the spec meshes.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import LMConfig
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.nn import layers as L

Params = dict


def make_pp_mesh(pipe: int = 4, data: int = 8, model: int = 8) -> Mesh:
    """(pipe, data, model) mesh — pipe stages map to ICI-neighbour groups."""
    return make_mesh((pipe, data, model), ("pipe", "data", "model"))


def stage_params(key: jax.Array, cfg: LMConfig, n_stages: int) -> Params:
    """Init dense-family params with blocks reshaped [S, L/S, ...]."""
    assert cfg.n_layers % n_stages == 0, (cfg.n_layers, n_stages)
    params = lm.init_params(key, cfg)
    per = cfg.n_layers // n_stages
    params["blocks"] = jax.tree.map(
        lambda x: x.reshape((n_stages, per) + x.shape[1:]), params["blocks"])
    return params


def stage_pspecs(params: Params, cfg: LMConfig, mesh: Mesh) -> Params:
    """blocks shard over "pipe" (stage-major); embed/final replicate over
    pipe and follow the usual TP rules on their own axes."""
    from repro.sharding import rules

    def drop_stage_dim(x):
        # works for arrays and ShapeDtypeStructs alike
        return jax.ShapeDtypeStruct(x.shape[1:], x.dtype)

    base = rules.param_pspecs({**params, "blocks": jax.tree.map(
        drop_stage_dim, params["blocks"])}, cfg, mesh)

    def prepend_pipe(spec: P) -> P:
        return P("pipe", *tuple(spec))

    return {**base,
            "blocks": jax.tree.map(
                lambda s: prepend_pipe(s), base["blocks"],
                is_leaf=lambda x: isinstance(x, P))}


def _block_stack_fwd(h: jax.Array, stage_blocks: Params, cfg: LMConfig
                     ) -> jax.Array:
    """Run one stage's [L/S, ...] blocks over h (dense family)."""
    def body(hh, bp):
        hh, _ = lm._dense_block_fwd(hh, bp, cfg, None)
        return hh, None
    h, _ = lax.scan(body, h, stage_blocks)
    return h


def pipeline_apply(params: Params, tokens: jax.Array, labels: jax.Array,
                   cfg: LMConfig, mesh: Mesh, n_microbatches: int
                   ) -> jax.Array:
    """Mean CE loss of the pipelined forward. tokens/labels [B, T].

    Embedding and the LM head run on every stage (cheap, replicated over
    pipe) but only the first/last stage's results are *used*; the interior
    transformer stack — the expensive part — is stage-parallel.
    """
    S = dict(zip(mesh.axis_names, mesh.devices.shape))["pipe"]
    M = n_microbatches
    B = tokens.shape[0]
    assert B % M == 0, (B, M)

    def staged(blocks_stage, embed, final_norm, tok_mb, lab_mb):
        """shard_map body: runs on ONE pipe group. blocks_stage is this
        stage's [L/S, ...] params; embed/final_norm replicate; tok/lab are
        [M, B/M(/data), T]."""
        sid = lax.axis_index("pipe")
        T = M + S - 1
        # drop the size-1 pipe-shard dim: local view is [1, L/S, ...]
        blocks_stage = jax.tree.map(lambda x: x[0], blocks_stage)

        # rotating slot: each stage keeps one in-flight activation
        h0 = jnp.zeros(tok_mb.shape[1:] + (cfg.d_model,), L.cdt(cfg))

        def tick(carry, t):
            slot, acc_loss, acc_cnt = carry
            mb = t - sid                       # microbatch this stage sees
            active = (mb >= 0) & (mb < M)

            # stage 0 ingests a fresh microbatch (embedding)
            tok_t = tok_mb[jnp.clip(t, 0, M - 1)]
            fresh = L.embed_apply(embed, tok_t, cfg)
            h_in = jnp.where((sid == 0) & active, fresh, slot)

            # the stage's block stack
            h_out = _block_stack_fwd(h_in, blocks_stage, cfg)
            h_out = jnp.where(active, h_out, slot)

            # last stage computes loss for its finished microbatch
            lab_t = lab_mb[jnp.clip(t - (S - 1), 0, M - 1)]
            hn = L.rmsnorm(h_out, final_norm, cfg.norm_eps)
            ce = L.chunked_cross_entropy(embed, hn, lab_t, cfg)
            take = (sid == S - 1) & active
            acc_loss = acc_loss + jnp.where(take, ce, 0.0)
            acc_cnt = acc_cnt + jnp.where(take, 1.0, 0.0)

            # hop activations to the next stage (ring; last→0 is ignored)
            slot = lax.ppermute(h_out, "pipe",
                                [(i, (i + 1) % S) for i in range(S)])
            return (slot, acc_loss, acc_cnt), None

        (slot, loss_sum, cnt), _ = lax.scan(
            tick, (h0, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            jnp.arange(T))
        # combine over BOTH the pipe stages (only the last contributes) and
        # the data shards (each computed its local microbatch mean); every
        # member then holds the same global mean loss
        loss = lax.psum(loss_sum, ("pipe", "data")) / jnp.maximum(
            lax.psum(cnt, ("pipe", "data")), 1.0)
        return loss[None]

    tok_mb = tokens.reshape(M, B // M, tokens.shape[1])
    lab_mb = labels.reshape(M, B // M, labels.shape[1])

    embed_specs = jax.tree.map(lambda _: P(), params["embed"])
    fn = jax.shard_map(
        staged, mesh=mesh,
        in_specs=(P("pipe"), embed_specs, P(),
                  P(None, "data", None), P(None, "data", None)),
        out_specs=P("pipe"),
        check_vma=False)
    losses = fn(params["blocks"], params["embed"], params["final_norm"],
                tok_mb, lab_mb)
    return jnp.mean(losses)


def build_pp_train_step(cfg: LMConfig, mesh: Mesh, *, n_microbatches: int,
                        lr: float = 3e-4):
    """pjit'd PP train step (loss + SGD update on the stage params)."""

    def step(params, tokens, labels):
        def loss_fn(p):
            return pipeline_apply(p, tokens, labels, cfg, mesh,
                                  n_microbatches)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                              params, grads)
        return params, loss

    return jax.jit(step)
