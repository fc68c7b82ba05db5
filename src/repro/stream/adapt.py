"""Per-lane online adaptation: local plasticity through the serving path.

The sweep enumerates the paper's retention/accuracy trade-off *offline*,
per variant cell. A deployed sensor experiences it *per device*: its
leak drifts with temperature and fab corner (the ``sigma`` axis), and
the weights it was deployed with slowly stop matching the capacitors
they drive. This module is the neuromorphic answer the ROADMAP calls
for — a local, per-lane plasticity rule that nudges each lane's layer-1
quantized weights and comparator threshold *during* serving, the online
analogue of the unfrozen training protocol.

Mechanics
---------
Each serving lane (= one physical sensor) carries a row of
``state["adapt"]``, the adaptation tensors that ride in the serving
state on the ``[capacity, ...]`` lane axis:

- ``dw``/``dtheta`` — the lane's persistent weight/threshold deltas,
  applied as ``quantize(w_base + dw)`` (straight-through, the same
  quantizer the unfrozen protocol trains through) and
  ``theta_base + dtheta``. They survive stream turnover on the lane and
  reset only when the lane rebinds to a different registry entry.
- ``ev`` — a per-filter decay-weighted event accumulator
  ``E_f ← E_f · a_f + ev_k`` folded alongside the charge, so the readout
  can *recompute* the window's linear charge from the raw events under
  the current weights (``diag(conv(E, w_q))``, bit-equal to the fold's
  telescoped sum up to fp ordering) and differentiate through it. It
  precharges (``E ← 0``) with the capacitor at every readout.
- ``elig_w``/``elig_theta`` — eligibility traces for the three-factor
  rule; ``n_updates`` counts applied updates.

At each coarse-window readout the rule takes a truncated (depth-1)
surrogate gradient through the exact serving numerics — re-quantize,
re-linearize the leak, re-derive drift, transfer curve, ATan surrogate
spike, pool, backbone step (``accumulator.relinearized_numerics``, the
unfrozen protocol's differentiable curvefit seam) — and applies one of
two local rules:

- ``surrogate`` — plain surrogate-gradient descent on the window's
  cross-entropy against the replayed stream's label (when it carries
  one; unlabeled lanes never update).
- ``reward`` — reward-modulated three-factor fallback: the gradient
  toward the lane's OWN prediction accumulates into an eligibility
  trace, and a scalar reward (+1 correct / −1 wrong, 0 unlabeled)
  gates the trace into the weights — the RSTDP analogue.

Everything is lane-diagonal: no cross-lane reduction anywhere, so the
state shards with the lane axis (``P_LANE``) under the lane mesh exactly
like the serving state, per-lane updates provably never perturb other
lanes, and registry serving gathers each lane's base numerics from the
stacked entry bundle before applying that lane's deltas.

:func:`make_adapt_fns` returns the frozen builder's surface
(repro.stream.accumulator.StreamFns) with the same call shape and the
same state helpers: ``fold(state, frames, active, *extra)`` and
``readout(state, active, coarse_mask, *extra)``, whose ``extra`` ends
with each lane's stream label (−1: unlabeled). It adds only the
per-lane re-linearisation, the event accumulator, the local update and
``reset_lane_full``. It is opt-in: with ``StreamEngine(adapt=None)``
none of this module runs and serving stays IEEE-bit-identical to the
frozen path. The fused Pallas fold (``kernels/stream_fold``) has no VJP
and shares one weight tensor across lanes, so ``use_kernel=True`` +
adaptation raises (pinned by tests/test_stream_adapt.py). Adapted lanes are harvested through
``StreamEngine.harvest`` and round-trip as validated checkpoint deltas
(repro.stream.deploy.save_adapt_delta) into new registry entries.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import analog, snn
# same conv as the serving fold/offline curvefit — gradient parity
# depends on identical padding/dimension numbers
from repro.core.p2m_layer import _conv
from repro.stream.accumulator import (StreamFns, _mask, entry_numerics,
                                      init_state, lane_executor, read_out,
                                      relinearized_numerics, reset_lane,
                                      zero_lane)
from repro.stream.deploy import Deployment
from repro.stream.shard import P_LANE, P_REP, LaneExecutor

RULES = ("surrogate", "reward")


@dataclass(frozen=True)
class AdaptConfig:
    """Local-rule hyperparameters (one config for the whole fleet; the
    *state* is per lane)."""
    rule: str = "surrogate"          # "surrogate" | "reward"
    lr_w: float = 5e-3               # weight-delta learning rate
    lr_theta: float = 0.0            # threshold-delta learning rate
    trace_decay: float = 0.9         # eligibility-trace decay (reward rule)
    clip_w: float = 0.5              # |dw| bound (keeps quantizer in range)
    clip_theta: float = 0.05         # |dtheta| bound (volts)

    def __post_init__(self) -> None:
        if self.rule not in RULES:
            raise ValueError(f"adapt rule must be one of {RULES}, "
                             f"got {self.rule!r}")
        if self.lr_w < 0 or self.lr_theta < 0:
            raise ValueError("learning rates must be >= 0")
        if self.clip_w <= 0 or self.clip_theta <= 0:
            raise ValueError("delta clips must be > 0")


@dataclass(frozen=True)
class AdaptFns(StreamFns):
    """The serving surface of an adapting engine: :class:`StreamFns`
    whose state carries ``state["adapt"]``. ``reset_lane`` keeps a
    lane's learned deltas over stream turnover; ``reset_lane_full``
    zeroes them too, for a lane rebound to another entry uid."""
    reset_lane_full: Callable[[dict, int], dict]


def adapt_entry_numerics(dep: Deployment) -> dict:
    """:func:`~repro.stream.accumulator.entry_numerics` extended with the
    entry's leak-circuit constants. Adaptation re-linearizes the leak
    from the CURRENT per-lane weights at every readout, so the stacked
    bundle must carry each entry's ``LeakCoeffs`` (a pytree of scalars —
    it stacks on the entry axis and gathers per lane like every other
    leaf), not just the pre-derived ``a``/``drift``."""
    return {**entry_numerics(dep), "coeffs": dep.coeffs}


@jax.jit
def reset_lane_full(state: dict, lane: jax.Array) -> dict:
    """Lane rebinds to a different entry uid: deltas learned against
    the old base are meaningless — zero everything."""
    return jax.tree.map(lambda v: zero_lane(v, lane), state)


def make_adapt_fns(dep: Deployment, *, capacity: int,
                   adapt: AdaptConfig, use_kernel: bool = False,
                   executor: LaneExecutor | None = None,
                   registry: bool = False) -> AdaptFns:
    """Build the jitted per-lane-adapting fold/readout for ``dep``.

    The serving forward matches the frozen engine's semantics exactly
    (same masking, same state update) but is vmapped per lane so each
    lane serves under its OWN ``quantize(w_base + dw)`` /
    ``theta_base + dtheta`` numerics, re-linearized through
    ``relinearized_numerics`` each fold. ``extra`` is the frozen mode's
    (``()``, or ``(entry, bundle)`` with ``registry=True``) followed by
    the per-lane ``labels`` ``[capacity] int32``; registry lanes gather
    their base numerics from the bundle before applying their deltas.
    """
    if dep.model_cfg.backbone.kind != "cnn":
        raise ValueError(
            f"online adaptation steps the paper's spiking CNN in its "
            f"per-lane readout (lane_head); a "
            f"{dep.model_cfg.backbone.kind!r} backbone cannot adapt — "
            f"serve it without adapt")
    if use_kernel:
        raise ValueError(
            "online adaptation requires the differentiable XLA scan "
            "fold: kernels/stream_fold has no VJP and shares one weight "
            "tensor across lanes — serve with use_kernel=False, or drop "
            "adapt")
    ex = lane_executor(executor, capacity)
    cfg = dep.model_cfg
    p2m_cfg, bb_cfg = cfg.p2m, cfg.backbone
    analog_cfg = p2m_cfg.analog
    stride, dv_unit = p2m_cfg.stride, analog_cfg.dv_unit
    H, W = bb_cfg.input_hw
    k, cin, F = p2m_cfg.kernel_size, p2m_cfg.in_channels, p2m_cfg.out_channels
    # per-lane base numerics: gathered from the bundle per call
    # (registry) or closed over (single-deployment); nb_ax is the vmap
    # axis for the nb argument of every per-lane closure.
    nb0 = adapt_entry_numerics(dep)
    nb_ax = 0 if registry else None

    def init_adapt_state() -> dict:
        return {**init_state(cfg, capacity), "adapt": {
            "dw": jnp.zeros((capacity, k, k, cin, F)),
            "dtheta": jnp.zeros((capacity,)),
            "elig_w": jnp.zeros((capacity, k, k, cin, F)),
            "elig_theta": jnp.zeros((capacity,)),
            "ev": jnp.zeros((capacity, F, H, W, cin)),
            "n_updates": jnp.zeros((capacity,), jnp.int32),
        }}

    def lane_relin(nb: dict, dw: jax.Array, dtheta: jax.Array) -> dict:
        """One lane's adapted numerics through the differentiable seam."""
        return relinearized_numerics(
            nb["w_q"] + dw, nb["theta"] + dtheta, analog_cfg=analog_cfg,
            coeffs=nb["coeffs"], n_sub=p2m_cfg.n_sub, dt_ms=p2m_cfg.dt_ms)

    vrelin = jax.vmap(lane_relin, in_axes=(nb_ax, 0, 0))
    vconv = jax.vmap(lambda ev, w: _conv(ev[None], w, stride)[0])

    def _lanes(extra: tuple) -> tuple[dict, jax.Array]:
        """Each lane's base numerics and stream label, from the step's
        trailing ``(*registry, labels)``."""
        *reg, labels = extra
        if registry:
            entry, bundle = reg
            return jax.tree.map(lambda leaf: leaf[entry], bundle), labels
        return nb0, labels

    def fold_body(state: dict, frames: jax.Array, active: jax.Array,
                  *extra) -> dict:
        """The scan fold under per-lane adapted numerics, plus the
        per-filter event accumulator ``E`` riding the same decay."""
        nb, _ = _lanes(extra)
        astate = state["adapt"]
        ln = vrelin(nb, astate["dw"], astate["dtheta"])
        w_q, a = ln["w_q"], ln["a"]          # [cap,k,k,2,F], [cap,F]

        def sub_step(carry, ev_k):           # ev_k [cap, H, W, 2]
            x, E = carry
            x = x * a[:, None, None, :] + vconv(ev_k, w_q) * dv_unit
            E = E * a[:, :, None, None, None] + ev_k[:, None]
            return (x, E), None

        (x, E), _ = lax.scan(sub_step, (state["x"], astate["ev"]),
                             jnp.moveaxis(frames, 1, 0))
        return {**state, "x": _mask(active, x, state["x"]),
                "adapt": {**astate, "ev": _mask(active, E, astate["ev"])}}

    def lane_head(x_lin: jax.Array, ln: dict, nb: dict,
                  coarse: jax.Array, mem) -> dict:
        """One lane's readout forward from a linear charge map: transfer
        curve + PV, surrogate comparator, pool, coarse accumulate,
        backbone step. Shared by the serving pass (x from the fold) and
        the gradient pass (x recomputed from ``ev``)."""
        v_pre = analog.transfer_curve(x_lin + ln["drift"], analog_cfg,
                                      nb["pv"])
        spikes = snn.spike_fn(v_pre - ln["theta"])
        pooled = snn.max_pool(spikes[None])[0]
        coarse2 = coarse + pooled
        logits_t, mem2 = snn.spiking_cnn_stream_step(
            nb["backbone"], nb["bn_state"],
            jax.tree.map(lambda v: v[None], mem), coarse2[None], bb_cfg)
        return {"spikes": spikes, "pooled": pooled, "coarse": coarse2,
                "logits_t": logits_t[0],
                "mem2": jax.tree.map(lambda v: v[0], mem2)}

    def lane_serve(dw, dtheta, nb, x_fold, coarse, mem) -> dict:
        ln = lane_relin(nb, dw, dtheta)
        return lane_head(x_fold, ln, nb, coarse, mem)

    vserve = jax.vmap(lane_serve, in_axes=(0, 0, nb_ax, 0, 0, 0))

    def lane_loss(dw, dtheta, target, nb, E, coarse, mem):
        """Window cross-entropy vs ``target`` with the linear charge
        recomputed from the event accumulator under the CURRENT deltas —
        the truncated depth-1 window through the curvefit seam (the
        decay weighting inside ``E`` and earlier windows' coarse counts
        are constants)."""
        ln = lane_relin(nb, dw, dtheta)
        y = _conv(E, ln["w_q"], stride)              # [F, Hs, Ws, F]
        x_lin = jnp.diagonal(y, axis1=0, axis2=3) * dv_unit
        ro = lane_head(x_lin, ln, nb, coarse, mem)
        return -jax.nn.log_softmax(ro["logits_t"])[target], ro["logits_t"]

    vgrad = jax.vmap(jax.grad(lane_loss, argnums=(0, 1), has_aux=True),
                     in_axes=(0, 0, 0, nb_ax, 0, 0, 0))

    def readout_body(state: dict, active: jax.Array,
                     coarse_mask: jax.Array, *extra) -> tuple[dict, dict]:
        """Frozen-engine readout semantics under per-lane numerics, then
        one local update on lanes crossing a labeled coarse boundary."""
        nb, labels = _lanes(extra)
        astate = state["adapt"]
        ro = vserve(astate["dw"], astate["dtheta"], nb, state["x"],
                    state["coarse"], state["mem"])
        new_state, out = read_out(state, ro, active, coarse_mask)
        logits_t = ro["logits_t"]

        # ---- local update (per lane, lane-diagonal) ----
        has_label = labels >= 0
        boundary = active & coarse_mask
        upd = boundary & has_label
        if adapt.rule == "surrogate":
            tgt = jnp.maximum(labels, 0)
        else:
            # three-factor: eligibility accumulates the gradient toward
            # the lane's own prediction; reward gates it in.
            tgt = jnp.argmax(logits_t, axis=-1).astype(jnp.int32)
        (g_w, g_th), _ = vgrad(astate["dw"], astate["dtheta"], tgt, nb,
                               astate["ev"], state["coarse"],
                               state["mem"])
        if adapt.rule == "surrogate":
            dw_step, th_step = adapt.lr_w * g_w, adapt.lr_theta * g_th
            elig_w, elig_th = astate["elig_w"], astate["elig_theta"]
        else:
            elig_w = _mask(boundary,
                           adapt.trace_decay * astate["elig_w"] + g_w,
                           astate["elig_w"])
            elig_th = jnp.where(boundary,
                                adapt.trace_decay * astate["elig_theta"]
                                + g_th,
                                astate["elig_theta"])
            r = jnp.where(has_label,
                          jnp.where(tgt == labels, 1.0, -1.0), 0.0)
            dw_step = adapt.lr_w * r[:, None, None, None, None] * elig_w
            th_step = adapt.lr_theta * r * elig_th
        dw = jnp.clip(astate["dw"] - dw_step, -adapt.clip_w, adapt.clip_w)
        dth = jnp.clip(astate["dtheta"] - th_step,
                       -adapt.clip_theta, adapt.clip_theta)
        new_astate = {
            "dw": _mask(upd, dw, astate["dw"]),
            "dtheta": jnp.where(upd, dth, astate["dtheta"]),
            "elig_w": elig_w,
            "elig_theta": elig_th,
            # the event accumulator precharges with the capacitor
            "ev": _mask(active, jnp.zeros_like(astate["ev"]),
                        astate["ev"]),
            "n_updates": astate["n_updates"] + upd.astype(jnp.int32),
        }
        return {**new_state, "adapt": new_astate}, out

    specs = ((P_LANE, P_LANE, P_LANE) + ((P_LANE, P_REP) if registry else ())
             + (P_LANE,))
    fold = jax.jit(ex.shard(fold_body, in_specs=specs, out_specs=P_LANE))
    readout = jax.jit(ex.shard(readout_body, in_specs=specs,
                               out_specs=(P_LANE, P_LANE)))

    return AdaptFns(init_state=init_adapt_state, reset_lane=reset_lane,
                    reset_lane_full=reset_lane_full, fold=fold,
                    readout=readout, entry_numerics=adapt_entry_numerics,
                    in_hw=(H, W), n_classes=bb_cfg.n_classes)


def lane_stats(astate: dict) -> list[dict]:
    """Host-side per-lane rows for the v5 stats artifact: lanes that
    applied at least one update, with their delta norms."""
    dw = np.asarray(astate["dw"])
    dth = np.asarray(astate["dtheta"])
    n_upd = np.asarray(astate["n_updates"])
    rows = []
    for lane in range(n_upd.shape[0]):
        if int(n_upd[lane]) == 0:
            continue
        rows.append({
            "lane": lane,
            "n_updates": int(n_upd[lane]),
            "dw_norm": float(np.linalg.norm(dw[lane])),
            "dtheta": float(dth[lane]),
        })
    return rows
