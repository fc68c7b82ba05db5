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
At each T_INTG boundary :func:`readout` adds the window's asymptotic
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
from repro.stream.shard import P_LANE, P_REP, LaneExecutor  # noqa: F401


def _mask(m: jax.Array, new: jax.Array, old: jax.Array) -> jax.Array:
    """Per-lane select: lanes where ``m`` take ``new``, others keep
    ``old`` (broadcast over trailing axes)."""
    return jnp.where(m.reshape(m.shape + (1,) * (new.ndim - 1)), new, old)


@dataclass(frozen=True)
class StreamFns:
    """The compiled serving surface for one deployment × lane capacity:
    ``state`` is a pytree batched on the leading lane axis."""
    init_state: Callable[[], dict]
    reset_lane: Callable[[dict, int], dict]
    fold: Callable[[dict, jax.Array, jax.Array], dict]
    readout: Callable[[dict, jax.Array, jax.Array], tuple[dict, dict]]
    in_hw: tuple[int, int]       # event-frame resolution the lanes consume
    n_classes: int


@dataclass(frozen=True)
class MultiStreamFns:
    """The compiled MULTI-VARIANT serving surface (deployment registry,
    repro.stream.registry): fold/readout additionally take a per-lane
    ``entry`` index ``[capacity] int32`` and a stacked numerics ``bundle``
    (every :func:`entry_numerics` leaf stacked on a leading ``[E]`` entry
    axis — :func:`stack_entries`). The bundle is an ARGUMENT, not a
    closure, so hot-swapping a registry entry re-stacks the bundle
    without recompiling (shapes are unchanged)."""
    init_state: Callable[[], dict]
    reset_lane: Callable[[dict, int], dict]
    fold: Callable[[dict, jax.Array, jax.Array, jax.Array, dict], dict]
    readout: Callable[[dict, jax.Array, jax.Array, jax.Array, dict],
                      tuple[dict, dict]]
    in_hw: tuple[int, int]
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
    the ``bundle`` argument of :class:`MultiStreamFns`. All entries must
    be compat-equal (identical leaf shapes)."""
    if not numerics:
        raise ValueError("cannot stack an empty entry list")
    return jax.tree.map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *numerics)


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
    backbone step. Pure — masking/selection is the caller's job."""
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
                    chunk_slots: int, use_kernel: bool = False,
                    executor: LaneExecutor | None = None) -> StreamFns:
    """Build the jitted lane-batched fold/readout steps for ``dep``.

    ``chunk_slots`` is the number of fine sub-slots one replay chunk
    spans; it must divide ``n_sub`` so T_INTG boundaries land on chunk
    boundaries. ``fold`` consumes frames ``[capacity, S, H, W, 2]`` for
    any sub-slot count ``S``: the engine folds a whole window at once
    (``S = n_sub``). ``use_kernel=True`` routes the sub-slot fold
    through the fused Pallas stream_fold kernel (one launch per fold
    call, charge tile VMEM-resident — see docs/kernels.md); the XLA
    ``lax.scan`` fold below is its parity reference and stays the
    default.

    A sharded ``executor`` (repro.stream.shard.LaneExecutor) partitions
    the lane axis over the 1-D ``"lane"`` mesh: ``capacity`` must then be
    a multiple of ``executor.devices`` (the engine pads it —
    ``LaneExecutor.padded_size``), fold/readout bodies run under
    ``shard_map`` with the state/frames/masks split into contiguous
    per-device lane blocks and the deployed weights replicated, and
    ``init_state``/``reset_lane`` stay global (admission is host-side and
    touches one lane at a time). ``executor=None`` (or ``devices=1``) is
    the exact unsharded path.
    """
    ex = executor or LaneExecutor()
    if capacity % ex.devices:
        raise ValueError(
            f"capacity={capacity} must be a multiple of "
            f"executor.devices={ex.devices} — pad the lane axis first "
            f"(LaneExecutor.padded_size)")
    cfg = dep.model_cfg
    p2m_cfg = cfg.p2m
    bb_cfg = cfg.backbone
    n_sub = p2m_cfg.n_sub
    if n_sub % chunk_slots:
        raise ValueError(f"chunk_slots={chunk_slots} must divide "
                         f"n_sub={n_sub}")
    H, W = bb_cfg.input_hw
    C = p2m_cfg.out_channels
    hp, wp = H // p2m_cfg.stride // 2, W // p2m_cfg.stride // 2  # post-pool

    # variant numerics, identical to the offline curvefit path: quantized
    # weights, leak linearization from the DEPLOYED kernel, per-filter
    # sub-slot decay a, window drift toward V_inf, transfer curve + PV.
    nb = entry_numerics(dep)

    def init_state() -> dict:
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

    @jax.jit
    def reset_lane(state: dict, lane: jax.Array) -> dict:
        """Zero one lane's state (a newly admitted stream's precharge)."""
        return jax.tree.map(
            lambda v: v.at[lane].set(jnp.zeros_like(v[lane])), state)

    def fold_body(state: dict, frames: jax.Array, active: jax.Array
                  ) -> dict:
        """Advance the charge ODE through ``S`` sub-slots.

        ``frames`` [capacity, S, H, W, 2] — events binned on the fine
        sub-slot grid (the engine passes one T_INTG window, S = n_sub);
        ``active`` [capacity] bool.
        Each sub-slot decays the standing charge by ``a`` and deposits
        its (dv_unit-scaled) conv — empty slots decay without deposit.
        Under a sharded executor this body sees one device's contiguous
        lane block (capacity / devices lanes).
        """
        x = _fold_core(state["x"], frames, nb, stride=p2m_cfg.stride,
                       dv_unit=p2m_cfg.analog.dv_unit,
                       use_kernel=use_kernel)
        return {**state, "x": _mask(active, x, state["x"])}

    def readout_body(state: dict, active: jax.Array,
                     coarse_mask: jax.Array) -> tuple[dict, dict]:
        """T_INTG-boundary readout for every lane at once.

        ``active`` gates which lanes read out (and precharge);
        ``coarse_mask ⊆ active`` marks lanes whose coarse window just
        completed — only those step the backbone and the logit average.
        Returns the new state and per-lane outputs (binary spike map,
        pooled spike count) for stats and parity checks.
        """
        ro = _readout_core(state, nb, analog_cfg=p2m_cfg.analog,
                           bb_cfg=bb_cfg)
        spikes, pooled, coarse = ro["spikes"], ro["pooled"], ro["coarse"]
        logits_t, mem2 = ro["logits_t"], ro["mem2"]
        new_state = {
            "x": _mask(active, jnp.zeros_like(state["x"]), state["x"]),
            "coarse": _mask(active,
                            _mask(coarse_mask, jnp.zeros_like(coarse),
                                  coarse),
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

    # shard the lane axis over the mesh (identity when devices=1): every
    # input/output leaf is lane-leading, the closed-over deployed weights
    # replicate. jit wraps the shard_map, as in the sweep engine.
    fold = jax.jit(ex.shard(fold_body,
                            in_specs=(P_LANE, P_LANE, P_LANE),
                            out_specs=P_LANE))
    readout = jax.jit(ex.shard(readout_body,
                               in_specs=(P_LANE, P_LANE, P_LANE),
                               out_specs=(P_LANE, P_LANE)))

    return StreamFns(init_state=init_state, reset_lane=reset_lane,
                     fold=fold, readout=readout, in_hw=(H, W),
                     n_classes=bb_cfg.n_classes)


def make_multi_stream_fns(dep: Deployment, *, capacity: int,
                          chunk_slots: int, use_kernel: bool = False,
                          executor: LaneExecutor | None = None
                          ) -> MultiStreamFns:
    """Build the jitted MULTI-VARIANT fold/readout steps (deployment
    registry serving). ``dep`` is the engine's ANCHOR entry — it only
    pins the shared serving geometry (resolution, stride, channels,
    n_sub, backbone architecture; the compat key); the actual per-lane
    numerics arrive per call as a stacked ``bundle``
    (:func:`stack_entries` over :func:`entry_numerics`) plus a per-lane
    ``entry`` index ``[capacity] int32`` into its ``[E]`` axis.

    Bit-exactness contract (the registry's headline invariant): for each
    entry ``e``, the body runs the IDENTICAL full-lane-batch program a
    single-variant engine would run with ``e``'s numerics — ``lax.map``
    over the entry axis, the same idiom the sweep engine uses for the
    variant axis — and then gathers, per lane, the row of the entry that
    lane is bound to. Because every lane's numerics are independent of
    its neighbours (no cross-lane reduction anywhere in the serving
    forward — the same property that makes sharding bit-exact), lane
    ``i`` of entry ``e``'s sweep is bit-identical to lane ``i`` of a
    single-variant serve, so the gathered mixed-variant state is
    bit-identical per lane too (tests/test_registry.py pins it, on 1
    device and on a lane mesh).

    Under a sharded ``executor`` the state/frames/masks and the entry
    index split into per-device lane blocks (``P_LANE``) while the
    bundle replicates (``P_REP``) — every device carries all E variants,
    exactly as the single-variant engine replicates its one deployment.
    """
    ex = executor or LaneExecutor()
    if capacity % ex.devices:
        raise ValueError(
            f"capacity={capacity} must be a multiple of "
            f"executor.devices={ex.devices} — pad the lane axis first "
            f"(LaneExecutor.padded_size)")
    cfg = dep.model_cfg
    p2m_cfg = cfg.p2m
    bb_cfg = cfg.backbone
    if p2m_cfg.n_sub % chunk_slots:
        raise ValueError(f"chunk_slots={chunk_slots} must divide "
                         f"n_sub={p2m_cfg.n_sub}")
    H, W = bb_cfg.input_hw
    C = p2m_cfg.out_channels
    hp, wp = H // p2m_cfg.stride // 2, W // p2m_cfg.stride // 2

    def init_state() -> dict:
        return {
            "x": jnp.zeros((capacity, H // p2m_cfg.stride,
                            W // p2m_cfg.stride, C)),
            "coarse": jnp.zeros((capacity, hp, wp, C)),
            "mem": backbone.stream_init(bb_cfg, capacity),
            "logits": jnp.zeros((capacity, bb_cfg.n_classes)),
            "n_coarse": jnp.zeros((capacity,), jnp.int32),
        }

    @jax.jit
    def reset_lane(state: dict, lane: jax.Array) -> dict:
        return jax.tree.map(
            lambda v: v.at[lane].set(jnp.zeros_like(v[lane])), state)

    def _gather(tree, entry: jax.Array):
        """Per-lane entry selection: leaf [E, capacity, ...] → lane i
        takes row ``[entry[i], i]`` — the exact gather that makes mixed
        serving bit-identical to the per-entry full-batch programs."""
        lanes = jnp.arange(entry.shape[0])
        return jax.tree.map(lambda leaf: leaf[entry, lanes], tree)

    def fold_body(state: dict, frames: jax.Array, active: jax.Array,
                  entry: jax.Array, bundle: dict) -> dict:
        xs = lax.map(
            lambda nb: _fold_core(state["x"], frames, nb,
                                  stride=p2m_cfg.stride,
                                  dv_unit=p2m_cfg.analog.dv_unit,
                                  use_kernel=use_kernel),
            {"w_q": bundle["w_q"], "a": bundle["a"]})   # [E, cap, ...]
        x = _gather(xs, entry)
        return {**state, "x": _mask(active, x, state["x"])}

    def readout_body(state: dict, active: jax.Array,
                     coarse_mask: jax.Array, entry: jax.Array,
                     bundle: dict) -> tuple[dict, dict]:
        ro = _gather(
            lax.map(lambda nb: _readout_core(state, nb,
                                             analog_cfg=p2m_cfg.analog,
                                             bb_cfg=bb_cfg),
                    bundle),
            entry)
        spikes, pooled, coarse = ro["spikes"], ro["pooled"], ro["coarse"]
        logits_t, mem2 = ro["logits_t"], ro["mem2"]
        new_state = {
            "x": _mask(active, jnp.zeros_like(state["x"]), state["x"]),
            "coarse": _mask(active,
                            _mask(coarse_mask, jnp.zeros_like(coarse),
                                  coarse),
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

    # lane-leading leaves shard over the mesh; the entry index rides the
    # lane axis with them; the bundle (all E variants) replicates.
    fold = jax.jit(ex.shard(
        fold_body,
        in_specs=(P_LANE, P_LANE, P_LANE, P_LANE, P_REP),
        out_specs=P_LANE))
    readout = jax.jit(ex.shard(
        readout_body,
        in_specs=(P_LANE, P_LANE, P_LANE, P_LANE, P_REP),
        out_specs=(P_LANE, P_LANE)))

    return MultiStreamFns(init_state=init_state, reset_lane=reset_lane,
                          fold=fold, readout=readout, in_hw=(H, W),
                          n_classes=bb_cfg.n_classes)
