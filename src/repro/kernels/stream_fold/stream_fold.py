"""Pallas TPU kernel: fused streaming leak-fold over the fine sub-slots.

The online serving hot path (repro.stream.accumulator) advances each
lane's standing charge through one replay chunk as

    x ← x·a + c_k,      c_k = conv(events_k) · dv_unit,   k = 0..S−1

— the in-pixel analogue of Neuromorphic-P2M's fused MAC+leak
accumulation. The XLA path runs this as ``lax.scan`` over the S fine
sub-slots, round-tripping the [N, F] charge state through HBM every
step. This kernel fuses the whole sub-slot scan into ONE launch per
coarse slot: the charge tile stays VMEM-resident across all S steps
(exactly like charge staying on the pixel capacitor C_K for the whole
integration window) and only the final state leaves the array.

Two fusion levels, same grid layout (tiles over the flattened
lane·site axis N; the filter axis F is the TPU lane axis, padded to
lane width in compiled mode):

* :func:`stream_fold_pallas` — the serving default. Consumes
  PRE-COMPUTED per-sub-slot deposits ``c_k`` [S, N, F] and fuses the
  fold. It is **bit-exact** with ``ref.stream_fold_ref`` (the
  ``lax.scan`` over the same deposits). The deposit stream comes from
  the very conv the XLA serving fold runs, so the two folds differ only
  where a compiler contracts a multiply and an add into one rounding
  (fused multiply-add): a few ulp of the charge, with layer-1 spike maps
  and predictions unchanged (tests/test_stream_fold.py,
  tests/test_streaming.py).
* :func:`stream_fold_mac_pallas` — full fusion: the conv itself runs
  in-kernel as an im2col matmul on the MXU (``patches[s] @ w``), so the
  [S, N, F] deposit tensor is never materialized in HBM. Float-exact
  up to matmul summation order vs the conv path (parity-tested to
  1e-5), which is why serving keeps the deposit variant.

HBM traffic per chunk drops from the scan's ~3·S·N·F (read x, read c,
write x per step) to (S+1)·N·F reads + N·F writes (deposit variant) or
S·N·K + N·(K·F + 2F) (MAC variant).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.backend import lane_pad, resolve_interpret


def _fold_kernel(x0_ref, dep_ref, a_ref, out_ref):
    S = dep_ref.shape[0]
    a = a_ref[0, :]                     # [F] per-filter sub-slot decay

    def step(s, x):
        return x * a + dep_ref[s, :, :]

    out_ref[:, :] = lax.fori_loop(0, S, step, x0_ref[:, :])


def _fold_mac_kernel(x0_ref, patches_ref, w_ref, a_ref, out_ref, *,
                     dv_unit: float):
    S = patches_ref.shape[0]
    a = a_ref[0, :]

    def step(s, x):
        dep = jnp.dot(patches_ref[s, :, :], w_ref[...],
                      preferred_element_type=jnp.float32) * dv_unit
        return x * a + dep

    out_ref[:, :] = lax.fori_loop(0, S, step, x0_ref[:, :])


def _pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def stream_fold_pallas(x0: jax.Array, deposits: jax.Array, a: jax.Array, *,
                       block_n: int = 256,
                       interpret: bool | None = None) -> jax.Array:
    """Fold ``x ← x·a + deposits[s]`` over all S sub-slots in one launch.

    x0 [N, F] f32 charge carry; deposits [S, N, F]; a [F] per-filter
    decay. Returns the folded state [N, F], bit-exact with
    ``ref.stream_fold_ref`` (the ``lax.scan`` fold of the same deposits).
    """
    S, N, F = deposits.shape
    assert x0.shape == (N, F), (x0.shape, (N, F))
    interpret = resolve_interpret(interpret)
    Fp = lane_pad(F, interpret)
    block_n = min(block_n, N)
    Np = -(-N // block_n) * block_n
    x0 = _pad_axis(_pad_axis(x0, 1, Fp), 0, Np)
    deposits = _pad_axis(_pad_axis(deposits, 2, Fp), 1, Np)
    a = _pad_axis(a, 0, Fp)

    out = pl.pallas_call(
        _fold_kernel,
        grid=(Np // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, Fp), lambda i: (i, 0)),
            pl.BlockSpec((S, block_n, Fp), lambda i: (0, i, 0)),
            pl.BlockSpec((1, Fp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, Fp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, Fp), jnp.float32),
        interpret=interpret,
    )(x0, deposits, a[None, :])
    return out[:N, :F]


def stream_fold_mac_pallas(x0: jax.Array, patches: jax.Array, w: jax.Array,
                           a: jax.Array, *, dv_unit: float,
                           block_n: int = 256,
                           interpret: bool | None = None) -> jax.Array:
    """Fully-fused variant: deposits computed in-kernel on the MXU.

    x0 [N, F]; patches [S, N, K] (im2col event patches per sub-slot);
    w [K, F]; a [F]. Returns the folded state [N, F]. Matches the
    deposit path to matmul-vs-conv summation order (≤1e-5), not bitwise.
    """
    S, N, K = patches.shape
    F = w.shape[1]
    assert x0.shape == (N, F), (x0.shape, (N, F))
    assert w.shape[0] == K, (w.shape, K)
    interpret = resolve_interpret(interpret)
    Fp = lane_pad(F, interpret)
    Kp = lane_pad(K, interpret)
    block_n = min(block_n, N)
    Np = -(-N // block_n) * block_n
    x0 = _pad_axis(_pad_axis(x0, 1, Fp), 0, Np)
    patches = _pad_axis(_pad_axis(patches, 2, Kp), 1, Np)
    w = _pad_axis(_pad_axis(w, 0, Kp), 1, Fp)
    a = _pad_axis(a, 0, Fp)

    kernel = functools.partial(_fold_mac_kernel, dv_unit=dv_unit)
    out = pl.pallas_call(
        kernel,
        grid=(Np // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, Fp), lambda i: (i, 0)),
            pl.BlockSpec((S, block_n, Kp), lambda i: (0, i, 0)),
            pl.BlockSpec((Kp, Fp), lambda i: (0, 0)),
            pl.BlockSpec((1, Fp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, Fp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, Fp), jnp.float32),
        interpret=interpret,
    )(x0, patches, w, a[None, :])
    return out[:N, :F]
