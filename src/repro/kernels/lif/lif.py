"""Pallas TPU kernel: fused LIF neuron scan over time.

The LIF update is memory-bound (3 elementwise ops per element per step); the
XLA scan materializes membrane state to HBM every timestep. This kernel keeps
the membrane tile resident in VMEM across the whole time loop: traffic drops
from ~4·T·N (x, v in, v out, s) to (T+1)·N reads + T·N writes.

Layout: x [T, N] (N = flattened batch·features). Grid over N tiles; the time
loop runs inside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.backend import lane_pad, resolve_interpret


def _lif_kernel(x_ref, out_ref, *, tau: float, v_th: float, soft_reset: bool):
    T = x_ref.shape[0]

    def step(t, v):
        x_t = x_ref[pl.ds(t, 1), :][0]
        v = v + (x_t - v) / tau
        s = (v > v_th).astype(x_ref.dtype)
        if soft_reset:
            v = v - s * v_th
        else:
            v = v * (1.0 - s)
        out_ref[pl.ds(t, 1), :] = s[None]
        return v

    v0 = jnp.zeros((x_ref.shape[1],), x_ref.dtype)
    lax.fori_loop(0, T, step, v0)


def lif_pallas(x: jax.Array, *, tau: float = 2.0, v_th: float = 1.0,
               soft_reset: bool = True, block_n: int = 512,
               interpret: bool | None = None) -> jax.Array:
    """x: [T, N] input currents → spikes [T, N] (forward only).

    ``interpret=None`` autodetects the backend (compiled on TPU,
    interpreted elsewhere); compiled mode rounds the N-tile up to the
    TPU lane width so the membrane tile is hardware-aligned.
    """
    T, N = x.shape
    interpret = resolve_interpret(interpret)
    block_n = lane_pad(min(block_n, N), interpret)
    pad = (-N) % block_n
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    Np = x.shape[1]
    kernel = functools.partial(_lif_kernel, tau=tau, v_th=v_th,
                               soft_reset=soft_reset)
    out = pl.pallas_call(
        kernel,
        grid=(Np // block_n,),
        in_specs=[pl.BlockSpec((T, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((T, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((T, Np), x.dtype),
        interpret=interpret,
    )(x)
    return out[:, :N]
