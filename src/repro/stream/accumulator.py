"""Leak-aware online charge accumulation: the jitted lane-batched steps
behind the streaming engine.

The paper's central constraint — the passive kernel capacitor loses
charge between event arrival and readout — is an *online* phenomenon.
This module integrates it online: each serving lane carries the linear
charge state ``x`` of one stream's pixel array, and every arriving
sub-slot of events advances the exact leak ODE before depositing its
conv contribution:

    x ← x · a + conv(events_k) · dv_unit,     a = e^(−dt/τ)  per filter

Folding sub-slots ``k = 0..n_sub−1`` this way telescopes to the offline
curve-fit forward's decay weighting ``Σ_k conv(ev_k)·a^(n_sub−1−k)``
(core/p2m_layer.curvefit_reduce) — an EMPTY sub-slot is one multiply by
``a`` (the capacitor keeps leaking while nothing arrives), and a chunk
gap of Δt sub-slots decays by ``a^Δt`` without touching the event path.
At each T_INTG boundary the readout adds the window's asymptotic
drift, applies the fitted transfer curve + process variation, compares
against the variant's threshold, 2x-pools the binary spikes onto the
sensor output, accumulates them toward the backbone's coarse grid, and
— on lanes crossing a coarse boundary — steps the stateful spiking
backbone through the backbone seam (core/backbone.stream_step: the
paper's CNN or Spikformer) and the rate-decoding logit average. The
capacitor precharges (x ← 0) after every readout.

Everything is masked per lane (``active`` / ``coarse_mask``), so one
fixed-shape jitted step serves a continuously-batched lane table whose
streams start, progress, and finish independently. Numerical parity with
the offline batched forward (repro.stream.deploy.offline_forward) is
pinned by tests/test_streaming.py.

Every serving mode gets its steps from one builder and calls them with
one shape (:class:`StreamFns`): ``fold(state, frames, active, *extra)``
and ``readout(state, active, coarse_mask, *extra)``, where ``extra`` is
the mode's per-window arguments. :func:`make_stream_fns` builds the
frozen modes: one deployment (``extra = ()``, its numerics closed over)
or a registry (``extra = (entry, bundle)``, per-lane numerics from a
stacked bundle). Per-lane adaptation (repro.stream.adapt) builds on the
same state helpers and appends each lane's label to ``extra``.

The lane axis is also *mesh-shardable* (repro.stream.shard): pass a
sharded :class:`~repro.stream.shard.LaneExecutor` and the fold/readout
bodies run under ``shard_map`` over a 1-D ``"lane"`` mesh — one
contiguous lane block per device, the deployed weights replicated. Every
lane's numerics are independent of its neighbours (no cross-lane
reduction anywhere in the serving forward), which is what gives sharded
serving the predictions and spike counts of single-device serving, with
logits to a few ulp (tests/test_stream_shard.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import analog, backbone, leakage, p2m_layer, snn
# the SAME conv the offline curvefit forward runs — parity depends on
# identical padding/dimension numbers, so it is imported, not copied
from repro.core.p2m_layer import _conv
from repro.kernels.stream_fold import ops as stream_fold_ops
from repro.stream.deploy import Deployment
from repro.stream.shard import P_LANE, P_REP, LaneExecutor

# keys of ``state["adapt"]`` (repro.stream.adapt) that outlive a stream on
# its lane: what the lane has learned, void only when it rebinds to
# another registry entry
LEARNED = ("dw", "dtheta", "n_updates")


def _mask(m: jax.Array, new: jax.Array, old: jax.Array) -> jax.Array:
    """Per-lane select: lanes where ``m`` take ``new``, others keep
    ``old`` (broadcast over trailing axes)."""
    return jnp.where(m.reshape(m.shape + (1,) * (new.ndim - 1)), new, old)


@dataclass(frozen=True)
class StreamFns:
    """The compiled serving surface for one deployment (or registry) ×
    lane capacity: ``state`` is a pytree batched on the leading lane
    axis; ``fold(state, frames, active, *extra) -> state`` and
    ``readout(state, active, coarse_mask, *extra) -> (state, out)`` take
    the serving mode's trailing per-window arguments ``extra``."""
    init_state: Callable[[], dict]
    reset_lane: Callable[[dict, int], dict]
    fold: Callable[..., dict]
    readout: Callable[..., tuple[dict, dict]]
    # one registry entry's row of the stacked bundle (:func:`stack_entries`)
    entry_numerics: Callable[[Deployment], dict]
    in_hw: tuple[int, int]       # event-frame resolution the lanes consume
    n_classes: int


def relinearized_numerics(w_raw: jax.Array, theta: jax.Array, *,
                          analog_cfg, coeffs: leakage.LeakCoeffs,
                          n_sub: int, dt_ms: float) -> dict:
    """The unfrozen protocol's differentiable curvefit seam, factored out
    for online use: quantize the raw layer-1 weights (straight-through),
    re-linearize the leak from the CURRENT quantized kernel, and derive
    the per-filter sub-slot decay ``a`` and window ``drift``.

    Every op is differentiable w.r.t. ``w_raw`` (STE through the
    quantizer, branch-free ``leak_params_from_coeffs``) and ``theta`` —
    which is what lets the per-lane adaptation rule (repro.stream.adapt)
    take surrogate gradients through the exact serving numerics at each
    coarse-window readout, the online analogue of the unfrozen phase-2
    training path."""
    w_q = analog.quantize_weights(w_raw, analog_cfg)
    lk = leakage.leak_params_from_coeffs(w_q, coeffs)
    a = leakage.decay_factor(lk.tau_ms, dt_ms)                        # [C]
    _, drift = p2m_layer.window_decay(lk, n_sub, dt_ms)
    return {"w_q": w_q, "a": a, "drift": drift, "theta": theta}


def entry_numerics(dep: Deployment) -> dict:
    """The deployed variant's serving numerics, as one pytree.

    Exactly the values :func:`make_stream_fns` closes over — quantized
    layer-1 weights, the per-filter sub-slot decay ``a`` and window drift
    from the leak linearization of the DEPLOYED kernel, the transfer
    curve's process-variation params, the comparator threshold, and the
    backbone params/BN state. Two compat-equal deployments (same
    geometry; see repro.stream.registry.compat_key) yield identically
    shaped pytrees, which is what lets a registry stack them on an entry
    axis (:func:`stack_entries`) and co-serve them from one engine."""
    cfg = dep.model_cfg
    p2m_cfg = cfg.p2m
    coeffs = dep.coeffs
    nb = relinearized_numerics(
        dep.params["p2m"]["w"], coeffs.v_threshold,
        analog_cfg=p2m_cfg.analog, coeffs=coeffs,
        n_sub=p2m_cfg.n_sub, dt_ms=p2m_cfg.dt_ms)
    return {
        **nb,
        "pv": {"gain": dep.params["p2m"]["pv_gain"],
               "offset": dep.params["p2m"]["pv_offset"]},
        "backbone": dep.params["backbone"],
        "bn_state": dep.bn_state,
    }


def stack_entries(numerics: list[dict]) -> dict:
    """Stack per-entry numerics pytrees on a leading ``[E]`` entry axis —
    the ``bundle`` argument of registry serving. All entries must be
    compat-equal (identical leaf shapes)."""
    if not numerics:
        raise ValueError("cannot stack an empty entry list")
    return jax.tree.map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *numerics)


def lane_executor(executor: LaneExecutor | None,
                  capacity: int) -> LaneExecutor:
    """The steps' executor (default: one device), checked against the
    lane ``capacity`` it splits."""
    ex = executor or LaneExecutor()
    if capacity % ex.devices:
        raise ValueError(
            f"capacity={capacity} must be a multiple of "
            f"executor.devices={ex.devices} — pad the lane axis first "
            f"(LaneExecutor.padded_size)")
    return ex


def init_state(model_cfg, capacity: int) -> dict:
    """The serving state of ``capacity`` idle lanes."""
    p2m_cfg, bb_cfg = model_cfg.p2m, model_cfg.backbone
    H, W = bb_cfg.input_hw
    C = p2m_cfg.out_channels
    hp, wp = H // p2m_cfg.stride // 2, W // p2m_cfg.stride // 2  # post-pool
    return {
        # linear charge accumulator (pre-transfer-curve swing volts),
        # at the conv OUTPUT resolution (stride applied)
        "x": jnp.zeros((capacity, H // p2m_cfg.stride,
                        W // p2m_cfg.stride, C)),
        # pooled layer-1 spikes accumulating toward the next coarse
        # backbone frame
        "coarse": jnp.zeros((capacity, hp, wp, C)),
        # backbone LIF membranes (per layer) + rate-decoding average
        "mem": backbone.stream_init(bb_cfg, capacity),
        "logits": jnp.zeros((capacity, bb_cfg.n_classes)),
        "n_coarse": jnp.zeros((capacity,), jnp.int32),
    }


def zero_lane(v: jax.Array, lane: jax.Array) -> jax.Array:
    """``v`` with one lane zeroed."""
    return v.at[lane].set(jnp.zeros_like(v[lane]))


@jax.jit
def reset_lane(state: dict, lane: jax.Array) -> dict:
    """Zero one lane's state (a newly admitted stream's precharge). The
    lane's learned adaptation deltas (``state["adapt"]`` leaves named in
    :data:`LEARNED`) outlive the stream: the lane models one sensor."""
    def reset(path, v):
        learned = (len(path) == 2 and path[0].key == "adapt"
                   and path[1].key in LEARNED)
        return v if learned else zero_lane(v, lane)
    return jax.tree_util.tree_map_with_path(reset, state)


def read_out(state: dict, ro: dict, active: jax.Array,
             coarse_mask: jax.Array) -> tuple[dict, dict]:
    """The serving state after a T_INTG readout ``ro`` (the outputs of
    :func:`_readout_core` under each lane's numerics), and the window's
    outputs (binary spike map, pooled spike count) for stats and parity
    checks.

    ``active`` gates which lanes read out (and precharge);
    ``coarse_mask ⊆ active`` marks lanes whose coarse window just
    completed — only those keep the backbone step and the logit average.
    """
    spikes, pooled, coarse = ro["spikes"], ro["pooled"], ro["coarse"]
    logits_t, mem2 = ro["logits_t"], ro["mem2"]
    new_state = {
        **state,
        "x": _mask(active, jnp.zeros_like(state["x"]), state["x"]),
        "coarse": _mask(active,
                        _mask(coarse_mask, jnp.zeros_like(coarse), coarse),
                        state["coarse"]),
        "mem": jax.tree.map(lambda n, o: _mask(coarse_mask, n, o),
                            mem2, state["mem"]),
        "logits": state["logits"] + _mask(coarse_mask, logits_t,
                                          jnp.zeros_like(logits_t)),
        "n_coarse": state["n_coarse"] + coarse_mask.astype(jnp.int32),
    }
    out = {"spikes": spikes,
           "n_spikes": jnp.sum(pooled, axis=(1, 2, 3))
           * active.astype(pooled.dtype)}
    return new_state, out


def _fold_core(x: jax.Array, frames: jax.Array, nb: dict, *,
               stride: int, dv_unit: float, use_kernel: bool) -> jax.Array:
    """One variant's fold: advance the charge ODE of every lane
    through ``frames`` [capacity, S, H, W, 2] under numerics
    ``nb`` (:func:`entry_numerics`). Each sub-slot decays the standing
    charge by ``a`` and deposits its (dv_unit-scaled) conv — empty slots
    decay without deposit."""
    if use_kernel:
        return stream_fold_ops.fold_chunk(
            x, frames, nb["w_q"], nb["a"], stride=stride, dv_unit=dv_unit)

    def sub_step(x, ev_k):
        ideal = _conv(ev_k, nb["w_q"], stride) * dv_unit
        return x * nb["a"] + ideal, None

    x, _ = lax.scan(sub_step, x, jnp.moveaxis(frames, 1, 0))
    return x


def _readout_core(state: dict, nb: dict, *, analog_cfg, bb_cfg) -> dict:
    """One variant's T_INTG readout over every lane at once: window
    drift, transfer curve + PV, comparator, 2x pool, coarse accumulate,
    backbone step. Pure — masking/selection is :func:`read_out`'s job."""
    v_pre = analog.transfer_curve(state["x"] + nb["drift"], analog_cfg,
                                  nb["pv"])
    spikes = snn.spike_fn(v_pre - nb["theta"])                # [B, H, W, C]
    pooled = snn.max_pool(spikes)
    coarse = state["coarse"] + pooled
    logits_t, mem2 = backbone.stream_step(
        nb["backbone"], nb["bn_state"], state["mem"], coarse, bb_cfg)
    return {"spikes": spikes, "pooled": pooled, "coarse": coarse,
            "logits_t": logits_t, "mem2": mem2}


def make_stream_fns(dep: Deployment, *, capacity: int,
                    use_kernel: bool = False,
                    executor: LaneExecutor | None = None,
                    registry: bool = False) -> StreamFns:
    """Build the jitted lane-batched fold/readout steps for ``dep``.

    ``fold`` consumes frames ``[capacity, S, H, W, 2]`` for any sub-slot
    count ``S``: the engine folds a whole window at once (``S = n_sub``).
    ``use_kernel=True`` routes the sub-slot fold through the fused Pallas
    stream_fold kernel (one launch per fold call, charge tile
    VMEM-resident — see docs/kernels.md); the XLA ``lax.scan`` fold below
    is its parity reference and stays the default.

    ``registry=False`` serves ``dep`` alone: its numerics are closed
    over and ``extra = ()``. ``registry=True`` serves a deployment
    registry: ``dep`` is the engine's ANCHOR entry and only pins the
    shared serving geometry (the compat key); the per-lane numerics
    arrive per call as ``extra = (entry, bundle)``, a stacked ``bundle``
    (:func:`stack_entries` over :func:`entry_numerics`) and a per-lane
    ``entry`` index ``[capacity] int32`` into its ``[E]`` axis. The
    bundle is an ARGUMENT, not a closure, so hot-swapping a registry
    entry re-stacks it without recompiling (shapes are unchanged).

    Bit-exactness contract (the registry's headline invariant): for each
    entry ``e``, the registry body runs the IDENTICAL full-lane-batch
    program a single-variant engine would run with ``e``'s numerics —
    ``lax.map`` over the entry axis, the same idiom the sweep engine uses
    for the variant axis — and then gathers, per lane, the row of the
    entry that lane is bound to. Because every lane's numerics are
    independent of its neighbours, lane ``i`` of entry ``e``'s sweep is
    bit-identical to lane ``i`` of a single-variant serve
    (tests/test_registry.py pins it, on 1 device and on a lane mesh).

    A sharded ``executor`` (repro.stream.shard.LaneExecutor) partitions
    the lane axis over the 1-D ``"lane"`` mesh: ``capacity`` must then be
    a multiple of ``executor.devices`` (the engine pads it —
    ``LaneExecutor.padded_size``), fold/readout bodies run under
    ``shard_map`` with the state/frames/masks and the entry index split
    into contiguous per-device lane blocks and the deployed weights (or
    the bundle of all E variants) replicated, and
    ``init_state``/``reset_lane`` stay global (admission is host-side and
    touches one lane at a time). ``executor=None`` (or ``devices=1``) is
    the exact unsharded path.
    """
    ex = lane_executor(executor, capacity)
    cfg = dep.model_cfg
    p2m_cfg = cfg.p2m
    bb_cfg = cfg.backbone
    # variant numerics, identical to the offline curvefit path: quantized
    # weights, leak linearization from the DEPLOYED kernel, per-filter
    # sub-slot decay a, window drift toward V_inf, transfer curve + PV.
    nb0 = None if registry else entry_numerics(dep)

    def per_lane(core: Callable[[dict], dict], extra: tuple,
                 keys: tuple[str, ...] | None = None):
        """``core(nb)`` under each lane's numerics: the closed-over
        deployment's, or every registry entry's (``keys`` of the bundle
        only) mapped, then gathered per lane — lane i takes row
        ``[entry[i], i]``."""
        if not registry:
            return core(nb0)
        entry, bundle = extra
        if keys is not None:
            bundle = {k: bundle[k] for k in keys}
        per_entry = lax.map(core, bundle)            # [E, capacity, ...]
        lanes = jnp.arange(entry.shape[0])
        return jax.tree.map(lambda leaf: leaf[entry, lanes], per_entry)

    def fold_body(state: dict, frames: jax.Array, active: jax.Array,
                  *extra) -> dict:
        """Advance the charge ODE through ``S`` sub-slots.

        ``frames`` [capacity, S, H, W, 2] — events binned on the fine
        sub-slot grid (the engine passes one T_INTG window, S = n_sub);
        ``active`` [capacity] bool.
        Under a sharded executor this body sees one device's contiguous
        lane block (capacity / devices lanes).
        """
        x = per_lane(lambda nb: _fold_core(state["x"], frames, nb,
                                           stride=p2m_cfg.stride,
                                           dv_unit=p2m_cfg.analog.dv_unit,
                                           use_kernel=use_kernel),
                     extra, keys=("w_q", "a"))
        return {**state, "x": _mask(active, x, state["x"])}

    def readout_body(state: dict, active: jax.Array,
                     coarse_mask: jax.Array, *extra) -> tuple[dict, dict]:
        """T_INTG-boundary readout for every lane at once
        (:func:`read_out`)."""
        ro = per_lane(lambda nb: _readout_core(state, nb,
                                               analog_cfg=p2m_cfg.analog,
                                               bb_cfg=bb_cfg),
                      extra)
        return read_out(state, ro, active, coarse_mask)

    # shard the lane axis over the mesh (identity when devices=1): every
    # lane-leading leaf shards, the entry index rides the lane axis with
    # them, the closed-over deployed weights or the bundle replicate. jit
    # wraps the shard_map, as in the sweep engine.
    specs = (P_LANE, P_LANE, P_LANE) + ((P_LANE, P_REP) if registry else ())
    fold = jax.jit(ex.shard(fold_body, in_specs=specs, out_specs=P_LANE))
    readout = jax.jit(ex.shard(readout_body, in_specs=specs,
                               out_specs=(P_LANE, P_LANE)))

    return StreamFns(init_state=partial(init_state, cfg, capacity),
                     reset_lane=reset_lane, fold=fold, readout=readout,
                     entry_numerics=entry_numerics,
                     in_hw=tuple(bb_cfg.input_hw), n_classes=bb_cfg.n_classes)
