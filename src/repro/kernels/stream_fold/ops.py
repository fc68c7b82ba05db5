"""Serving-shaped wrapper: replay-chunk frames → fused Pallas sub-slot fold.

``fold_chunk(x, frames, w_q, a, ...)`` is a drop-in for the XLA
``lax.scan`` fold inside ``repro.stream.accumulator.make_stream_fns``
(the ``use_kernel=True`` switch): it advances every lane's standing
charge through one replay chunk's S fine sub-slots in ONE kernel launch.

``mode="deposit"`` (default) computes the per-sub-slot conv deposits
with the SAME ``repro.core.p2m_layer._conv`` the XLA fold runs — one
conv per sub-slot, identical shapes — then fuses the fold in-kernel.
The result is bit-exact with the scan over those deposits (``use_ref``)
and within a few ulp of the conv-fused XLA scan (the compiler's
multiply-add contraction is the only difference); layer-1 spike maps
and predictions are identical, which is the contract serving relies
on. ``mode="mac"`` pushes the conv itself
into the kernel as an im2col matmul (full fusion, no deposit tensor in
HBM) at the cost of matmul-vs-conv summation-order drift (≤1e-5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# the SAME conv the XLA fold and the offline curvefit forward run —
# mode="deposit" parity depends on it being imported, not copied
from repro.core.p2m_layer import _conv
from repro.kernels.p2m_conv.ops import _extract_patches
from repro.kernels.stream_fold.ref import stream_fold_mac_ref, stream_fold_ref
from repro.kernels.stream_fold.stream_fold import (
    stream_fold_mac_pallas, stream_fold_pallas,
)


def fold_chunk(x: jax.Array, frames: jax.Array, w_q: jax.Array,
               a: jax.Array, *, stride: int, dv_unit: float,
               mode: str = "deposit", block_n: int = 256,
               interpret: bool | None = None,
               use_ref: bool = False) -> jax.Array:
    """One fused launch of ``x ← x·a + conv(ev_s)·dv_unit`` over S sub-slots.

    x [B, Ho, Wo, F] per-lane charge carry (conv OUTPUT resolution);
    frames [B, S, H, W, Cin] the chunk's events on the fine sub-slot
    grid; w_q [k, k, Cin, F] quantized weights; a [F] per-filter decay.
    Returns the advanced charge, same shape as ``x``.
    """
    B, S, H, W, Cin = frames.shape
    F = w_q.shape[-1]
    N = x.shape[0] * x.shape[1] * x.shape[2]
    x_flat = x.reshape(N, F)

    if mode == "deposit":
        # one conv per sub-slot at the lane-batched shape [B, H, W, Cin] —
        # exactly the op sequence of the XLA scan fold, minus the fold
        dep = lax.map(lambda ev: _conv(ev, w_q, stride) * dv_unit,
                      jnp.moveaxis(frames, 1, 0))       # [S, B, Ho, Wo, F]
        dep = dep.reshape(S, N, F)
        fn = stream_fold_ref if use_ref else stream_fold_pallas
        kw = {} if use_ref else {"block_n": block_n, "interpret": interpret}
        out = fn(x_flat, dep, a, **kw)
    elif mode == "mac":
        k = w_q.shape[0]
        patches, _ = _extract_patches(
            frames.reshape(B * S, H, W, Cin), k, stride)  # [B·S, P, K]
        P = patches.shape[1]
        patches = patches.reshape(B, S, P, k * k * Cin)
        patches = jnp.moveaxis(patches, 1, 0).reshape(S, B * P, k * k * Cin)
        w2 = w_q.reshape(k * k * Cin, F)
        fn = stream_fold_mac_ref if use_ref else stream_fold_mac_pallas
        kw = {} if use_ref else {"block_n": block_n, "interpret": interpret}
        out = fn(x_flat, patches, w2, a, dv_unit=dv_unit, **kw)
    else:
        raise ValueError(f"unknown stream_fold mode {mode!r} "
                         f"(expected 'deposit' or 'mac')")
    return out.reshape(x.shape)
