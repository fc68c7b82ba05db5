"""Pallas TPU kernel for the P²M in-pixel analog convolution (paper §2/§4).

TPU-native mapping of the in-pixel dataflow (docs/kernels.md): the per-filter
capacitor state lives in **VMEM** for the whole integration window — exactly
like charge stays on C_K in the pixel — while event patches stream
HBM→VMEM one sub-slot at a time. One fused pass computes

    conv step (MXU)  →  leak decay  →  step non-linearity  →  rail clamp

per sub-slot, then the threshold comparator; only binary spikes leave the
"array". Avoids materializing per-sub-slot conv outputs in HBM
([T·n_sub, P, F] tensors), which is what the pure-XLA path does.

Layout: im2col patches [T_out, n_sub, P, K] (P = B·H'·W' sites, K = receptive
field), weights [K, F]. The grid carries a **circuit-config axis** in front:
grid = (n_cfg, T_out, P tiles), with the per-config leak linearization
``(v_inf, decay)`` AND the per-config comparator threshold ``theta`` (the
variant grid's v_threshold axis) passed as [n_cfg, 1, F] tensors indexed
by the config grid dimension (the singleton axis keeps each [1, F] tile
full-extent in its last two dims, which the TPU tiling rule requires).
Patches and weights are config-independent, so the same event tile is
revisited once per config with only new [1, F] leak/threshold tiles
loaded —
this is what lets the co-design sweep engine (core/sweep.py) evaluate all
three MAC circuit configs (and nullifier-mismatch variants) in ONE
pallas_call instead of one compile per circuit. The n_sub loop runs inside
the kernel with the voltage tile VMEM-resident per config.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.backend import lane_pad, resolve_interpret


def _p2m_kernel(patches_ref, w_ref, vinf_ref, decay_ref, theta_ref,
                pvg_ref, pvo_ref, spikes_ref, vpre_ref, *,
                dv_unit: float, half_swing: float, v_lo: float, v_hi: float,
                nonlinear: bool):
    n_sub = patches_ref.shape[1]
    bp = patches_ref.shape[2]
    F = w_ref.shape[1]
    vinf = vinf_ref[0, 0, :]                   # [F] — this grid step's config
    decay = decay_ref[0, 0, :]
    theta = theta_ref[0, 0, :]                 # per-config comparator level
    pvg = pvg_ref[0, :]
    pvo = pvo_ref[0, :]

    def sub_step(i, v):
        # leak between events: V ← V_inf + (V - V_inf)·e^{-dt/τ}
        v = vinf + (v - vinf) * decay
        patch = patches_ref[0, i, :, :]        # [bp, K]
        ideal = jnp.dot(patch, w_ref[...],
                        preferred_element_type=jnp.float32) * dv_unit
        if nonlinear:
            g = jnp.clip(1.0 - (v / half_swing) ** 2, 0.05, 1.0)
        else:
            g = 1.0
        v = jnp.clip(v + ideal * g * pvg, v_lo, v_hi)
        return v

    v0 = jnp.zeros((bp, F), jnp.float32)
    v = lax.fori_loop(0, n_sub, sub_step, v0)
    v = v + pvo
    vpre_ref[0, 0, :, :] = v
    spikes_ref[0, 0, :, :] = (v > theta).astype(spikes_ref.dtype)


def p2m_conv_multi_pallas(patches: jax.Array, w: jax.Array, v_inf: jax.Array,
                          decay: jax.Array, theta: jax.Array,
                          pv_gain: jax.Array, pv_offset: jax.Array, *,
                          dv_unit: float, half_swing: float, v_lo: float,
                          v_hi: float, nonlinear: bool = True,
                          block_p: int = 256, interpret: bool | None = None
                          ) -> tuple[jax.Array, jax.Array]:
    """Multi-circuit-config P²M conv.

    patches: [T_out, n_sub, P, K] f32; w: [K, F];
    v_inf/decay/theta: [n_cfg, F] per-config leak linearizations and
    comparator thresholds (the circuit grid axis — theta rides the same
    [1, F] per-config tile stream as the leak legs, so threshold variants
    cost no extra patch traffic). Returns (spikes, v_pre), both
    [n_cfg, T_out, P, F] f32.

    ``interpret=None`` autodetects the backend (compiled on TPU,
    interpreted elsewhere). Compiled mode pads the K and F lane axes to
    the TPU lane width with zero weights / inert leak legs and crops the
    outputs — zero-filled filters integrate nothing and never spike.
    """
    T, n_sub, P, K = patches.shape
    F = w.shape[1]
    n_cfg = v_inf.shape[0]
    assert decay.shape == (n_cfg, F), (decay.shape, (n_cfg, F))
    assert theta.shape == (n_cfg, F), (theta.shape, (n_cfg, F))
    interpret = resolve_interpret(interpret)
    Fp, Kp = lane_pad(F, interpret), lane_pad(K, interpret)
    if Kp != K:
        patches = jnp.pad(patches, ((0, 0), (0, 0), (0, 0), (0, Kp - K)))
        w = jnp.pad(w, ((0, Kp - K), (0, 0)))
    if Fp != F:
        w = jnp.pad(w, ((0, 0), (0, Fp - F)))
        cfgpad = ((0, 0), (0, Fp - F))
        v_inf = jnp.pad(v_inf, cfgpad)
        decay = jnp.pad(decay, cfgpad)
        theta = jnp.pad(theta, cfgpad)
        pv_gain = jnp.pad(pv_gain, (0, Fp - F))
        pv_offset = jnp.pad(pv_offset, (0, Fp - F))
    block_p = min(block_p, P)
    if P % block_p != 0:
        pad = block_p - P % block_p
        patches = jnp.pad(patches, ((0, 0), (0, 0), (0, pad), (0, 0)))
        P = patches.shape[2]
    grid = (n_cfg, T, P // block_p)

    kernel = functools.partial(
        _p2m_kernel, dv_unit=dv_unit, half_swing=half_swing, v_lo=v_lo,
        v_hi=v_hi, nonlinear=nonlinear)

    spikes, vpre = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n_sub, block_p, Kp),
                         lambda c, t, p: (t, 0, p, 0)),
            pl.BlockSpec((Kp, Fp), lambda c, t, p: (0, 0)),
            # per-config rows ride as [n_cfg, 1, Fp]: the tile's last two
            # dims (1, Fp) are full-extent, as the (8, 128) rule demands
            pl.BlockSpec((1, 1, Fp), lambda c, t, p: (c, 0, 0)),
            pl.BlockSpec((1, 1, Fp), lambda c, t, p: (c, 0, 0)),
            pl.BlockSpec((1, 1, Fp), lambda c, t, p: (c, 0, 0)),
            pl.BlockSpec((1, Fp), lambda c, t, p: (0, 0)),
            pl.BlockSpec((1, Fp), lambda c, t, p: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_p, Fp), lambda c, t, p: (c, t, p, 0)),
            pl.BlockSpec((1, 1, block_p, Fp), lambda c, t, p: (c, t, p, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_cfg, T, P, Fp), jnp.float32),
            jax.ShapeDtypeStruct((n_cfg, T, P, Fp), jnp.float32),
        ],
        interpret=interpret,
    )(patches, w, v_inf[:, None, :], decay[:, None, :], theta[:, None, :],
      pv_gain[None, :], pv_offset[None, :])
    return spikes[..., :F], vpre[..., :F]


def p2m_conv_pallas(patches: jax.Array, w: jax.Array, v_inf: jax.Array,
                    decay: jax.Array, theta: jax.Array,
                    pv_gain: jax.Array, pv_offset: jax.Array,
                    *, dv_unit: float, half_swing: float, v_lo: float,
                    v_hi: float, nonlinear: bool = True,
                    block_p: int = 256, interpret: bool | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Single-config wrapper over the multi-config kernel.

    patches: [T_out, n_sub, P, K] f32; w: [K, F]; v_inf/decay/theta: [F].
    Returns (spikes, v_pre) both [T_out, P, F] f32.
    """
    spikes, vpre = p2m_conv_multi_pallas(
        patches, w, v_inf[None, :], decay[None, :], theta[None, :],
        pv_gain, pv_offset,
        dv_unit=dv_unit, half_swing=half_swing, v_lo=v_lo, v_hi=v_hi,
        nonlinear=nonlinear, block_p=block_p, interpret=interpret)
    return spikes[0], vpre[0]
