"""The co-design harness: the paper's full model (P²M layer-1 + spiking-CNN
backbone) and the T_INTG trade-off sweep (Table 1 + Fig 2).

Training protocol (paper §3):
  phase 1  pretrain the whole spiking CNN at a *long* integration time
           (coarse grid, no P²M circuit constraints) — cheap, few timesteps;
  phase 2  impose the P²M constraints on layer 1 at the target (short)
           T_INTG, freeze layer 1, and finetune layers ≥ 2 on the coarse
           grid fed by layer-1 spike counts.

The batched engine in ``repro.core.sweep`` additionally offers an
*unfrozen* phase 2 (``protocol="unfrozen"``) where layer 1 trains jointly
with the backbone through the differentiable curvefit forward — see
``run_sweep``'s ``protocol`` argument.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import backbone, p2m_layer, snn
from repro.core.backbone import BackboneConfig
from repro.core.leakage import CircuitConfig
from repro.core.p2m_layer import P2MConfig
from repro.core.snn import SpikingCNNConfig
from repro.optim import clip_by_global_norm
from repro.optim.optimizers import apply_updates

Params = dict


@dataclass(frozen=True)
class P2MModelConfig:
    """Full paper model: P²M first layer + digital spiking backbone (the
    paper's CNN, or Spikformer — repro.core.backbone)."""
    p2m: P2MConfig = field(default_factory=P2MConfig)
    backbone: BackboneConfig = field(default_factory=lambda: SpikingCNNConfig(
        first_layer_external=True))
    coarse_window_ms: float = 1000.0     # backbone integration time (paper: ~s)

    def __post_init__(self):
        if self.backbone.kind == "spikformer":
            self.backbone.validate(self.p2m.out_channels, self.p2m.stride)

    def coarsen_group(self) -> int:
        g = self.coarse_window_ms / self.p2m.t_intg_ms
        assert abs(g - round(g)) < 1e-6, (self.coarse_window_ms, self.p2m.t_intg_ms)
        return int(round(g))


def model_init(key: jax.Array, cfg: P2MModelConfig) -> tuple[Params, dict]:
    k1, k2 = jax.random.split(key)
    p2m_params = p2m_layer.p2m_init(k1, cfg.p2m)
    bb_params, bb_state = backbone.init(k2, cfg.backbone)
    return {"p2m": p2m_params, "backbone": bb_params}, bb_state


def model_apply(params: Params, state: dict, events: jax.Array,
                cfg: P2MModelConfig, *, train: bool
                ) -> tuple[jax.Array, dict, dict]:
    """events: [B, T_fine, n_sub, H, W, 2] at the P²M fine grid."""
    spikes1, v_pre = p2m_layer.p2m_apply(params["p2m"], events, cfg.p2m)
    # first layer's own 2x pool (keeps pixel pitch parity with the backbone)
    B, T = spikes1.shape[:2]
    tb = spikes1.reshape((B * T,) + spikes1.shape[2:])
    tb = snn.max_pool(tb)
    spikes1 = tb.reshape((B, T) + tb.shape[1:])
    coarse = p2m_layer.coarsen_spikes(spikes1, cfg.coarsen_group())
    logits, new_state, aux = backbone.apply(
        params["backbone"], state, coarse, cfg.backbone, train=train)
    aux["spikes/p2m"] = jax.lax.stop_gradient(jnp.sum(spikes1))
    aux["events/in"] = jax.lax.stop_gradient(jnp.sum(events))
    k = cfg.p2m.kernel_size
    out_elems = jnp.prod(jnp.asarray(spikes1.shape[:2] + spikes1.shape[2:]))
    aux["macs/p2m"] = jax.lax.stop_gradient(
        out_elems.astype(jnp.float32) * k * k * cfg.p2m.in_channels)
    return logits, new_state, aux


# ---------------------------------------------------------------------------
# training steps
# ---------------------------------------------------------------------------

def make_train_step(cfg: P2MModelConfig, opt, *, freeze_p2m: bool):
    """Returns jit-able train_step(params, opt_state, state, batch)."""

    def loss_fn(params, state, ev, labels):
        logits, new_state, aux = model_apply(params, state, ev, cfg, train=True)
        loss = snn.cross_entropy(logits, labels)
        return loss, (new_state, aux, logits)

    @jax.jit
    def step(params, opt_state, state, ev, labels):
        (loss, (new_state, aux, logits)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, state, ev, labels)
        if freeze_p2m:
            grads = {**grads, "p2m": jax.tree.map(jnp.zeros_like, grads["p2m"])}
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        updates, opt_state = opt.update(grads, opt_state, params)
        if freeze_p2m:
            # zero the *updates* too: AdamW weight decay would otherwise
            # shrink the frozen in-pixel weights every step
            updates = {**updates,
                       "p2m": jax.tree.map(jnp.zeros_like, updates["p2m"])}
        params = apply_updates(params, updates)
        metrics = {"loss": loss, "gnorm": gnorm,
                   "acc": snn.accuracy(logits, labels)}
        return params, opt_state, new_state, metrics, aux

    return step


def make_eval_fn(cfg: P2MModelConfig):
    @jax.jit
    def ev_fn(params, state, ev, labels):
        logits, _, aux = model_apply(params, state, ev, cfg, train=False)
        return {"acc": snn.accuracy(logits, labels),
                "loss": snn.cross_entropy(logits, labels)}, aux
    return ev_fn


# ---------------------------------------------------------------------------
# the sweep (Table 1 / Fig 2)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SweepConfig:
    t_intg_grid_ms: tuple[float, ...] = (1.0, 10.0, 100.0, 1000.0)
    batch_size: int = 8
    pretrain_steps: int = 40
    finetune_steps: int = 15
    eval_batches: int = 4
    lr: float = 2e-3
    # layer-1 LR for the unfrozen joint update (sweep.joint_optimizer):
    # the in-pixel kernel usually wants a gentler step than the backbone.
    # None → use ``lr`` (exactly the single-optimizer joint update).
    lr_p2m: float | None = None
    seed: int = 0
    # dataset selection (repro.data.sources.resolve_dataset): a name from
    # sources.DATASETS; file-backed names need data_root. Used when the
    # caller passes no explicit data_cfg/EventSource (run_sweep below).
    dataset: str = "synthetic-gesture"
    data_root: str | None = None


def run_sweep(data_cfg: Any = None,
              model_cfg: P2MModelConfig | None = None,
              sweep: SweepConfig = SweepConfig(),
              circuit: CircuitConfig = CircuitConfig.NULLIFIED,
              log: Any = print,
              protocol: str = "frozen",
              devices: int | None = None,
              eval_data: Any = None) -> list[dict]:
    """Run the co-design T_INTG sweep for ONE circuit config. Returns one
    record per grid point with accuracy, wall-clock train time, bandwidth
    ratio, and backend energies.

    ``data_cfg`` is any ``repro.data.sources.EventSource`` or a synthetic
    ``EventStreamConfig``; pass ``None`` to resolve it from
    ``sweep.dataset`` / ``sweep.data_root`` (the resolution follows the
    model's backbone input grid).

    ``protocol`` picks the phase-2 variant: ``"frozen"`` (paper §3, layer 1
    fixed after phase 1) or ``"unfrozen"`` (layer 1 trains jointly with the
    backbone through the differentiable curvefit forward).

    This is a single-circuit wrapper over the batched engine in
    ``repro.core.sweep`` — the same vectorized path that sweeps all circuit
    configs at once; here the stacked config axis just has length 1. The
    normalization semantics are the engine's: bandwidth and per-step train
    time are normalized to the longest-T point, and the energy improvement
    is computed against a SINGLE conventional reference (the digital
    backend always integrates at the accuracy-optimal long T — paper Fig 2
    right: the P²M advantage grows with T_INTG).

    ``devices`` shards the stacked config axis over a 1-D device mesh
    (core/sweep_exec.py) — with a single circuit the axis has length 1, so
    this only matters when the caller expands mismatch/threshold/sigma
    variants through the model config.

    ``eval_data`` optionally draws the accuracy-eval batches from a
    held-out source (``resolve_eval_dataset``) so record accuracies are
    out-of-sample — same semantics as ``sweep.run_grid(eval_data=...)``.
    """
    from repro.core import sweep as sweep_engine
    from repro.core.sweep_exec import make_executor
    from repro.data import sources as sources_mod

    if model_cfg is None:
        model_cfg = P2MModelConfig()
    if data_cfg is None:
        data_cfg = sources_mod.resolve_dataset(
            sweep.dataset, hw=model_cfg.backbone.input_hw[0],
            data_root=sweep.data_root)
    mcfg = replace(model_cfg,
                   p2m=replace(model_cfg.p2m,
                               leak=replace(model_cfg.p2m.leak,
                                            circuit=circuit)))
    grid = sweep_engine.SweepGrid(
        circuits=(circuit,),
        t_intg_grid_ms=tuple(sweep.t_intg_grid_ms),
        null_mismatch=(mcfg.p2m.leak.null_mismatch,))
    result = sweep_engine.run_grid(data_cfg, mcfg, sweep, grid, log=log,
                                   protocol=protocol,
                                   executor=make_executor(devices),
                                   eval_data=eval_data)
    return result.records
