"""Kernel µbenchmarks: Pallas (interpret) vs the pure-XLA paths.

On this CPU container interpret-mode timings measure Python emulation, NOT
TPU performance — the meaningful outputs are (i) allclose vs oracle at
benchmark scale and (ii) the XLA-path timing (the production fallback).
How to read the numbers, the BENCH_kernels.json trajectory record this
module emits, and the regression gate are documented in docs/benchmarks.md.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_entry, bench_record, emit, save_json, timed


def bench_p2m(fast: bool = False) -> dict:
    from repro.core.p2m_layer import P2MConfig, p2m_forward_scan, p2m_init
    from repro.kernels.p2m_conv import ops

    hw = 24 if fast else 32
    cfg = P2MConfig(out_channels=8, n_sub=4)
    params = p2m_init(jax.random.PRNGKey(0), cfg)
    ev = jax.random.poisson(jax.random.PRNGKey(1), 0.3,
                            (2, 4, 4, hw, hw, 2)).astype(jnp.float32)
    t_xla, (s_ref, v_ref) = timed(
        jax.jit(lambda p, e: p2m_forward_scan(p, e, cfg)), params, ev)
    t_pal, (s_k, v_k) = timed(
        lambda p, e: ops.p2m_conv(p, e, cfg), params, ev)
    err = float(jnp.max(jnp.abs(v_k - v_ref)))
    emit("kernel/p2m_conv/xla_scan", t_xla * 1e6, f"hw={hw}")
    emit("kernel/p2m_conv/pallas_interpret", t_pal * 1e6,
         f"max_err_vs_oracle={err:.2e}")
    assert err < 1e-4
    return {"xla_s": t_xla, "pallas_interpret_s": t_pal, "max_err": err}


def bench_p2m_multi(fast: bool = False) -> dict:
    """Fused multi-config launch vs n_cfg separate single-config launches.

    The fusion claim of the circuit-grid axis (p2m_conv.py): all configs
    revisit the same patch tiles in ONE pallas_call, so the fused path
    should not cost n_cfg× the single-config path.
    """
    import dataclasses

    from repro.core.leakage import CircuitConfig, LeakageConfig
    from repro.core.p2m_layer import P2MConfig, p2m_init
    from repro.kernels.p2m_conv import ops

    hw = 16 if fast else 24
    circuits = (CircuitConfig.BASIC, CircuitConfig.SWITCH,
                CircuitConfig.NULLIFIED)
    leak_cfgs = tuple(LeakageConfig(circuit=c) for c in circuits)
    cfg = P2MConfig(out_channels=8, n_sub=4)
    params = p2m_init(jax.random.PRNGKey(0), cfg)
    ev = jax.random.poisson(jax.random.PRNGKey(1), 0.3,
                            (2, 4, 4, hw, hw, 2)).astype(jnp.float32)

    t_multi, (s_multi, v_multi) = timed(
        lambda p, e: ops.p2m_conv_multi(p, e, cfg, leak_cfgs), params, ev)

    def separate(p, e):
        outs = [ops.p2m_conv(p, e, dataclasses.replace(cfg, leak=lc))
                for lc in leak_cfgs]
        return (jnp.stack([o[0] for o in outs]),
                jnp.stack([o[1] for o in outs]))

    t_sep, (s_sep, v_sep) = timed(separate, params, ev)
    err = float(jnp.max(jnp.abs(v_multi - v_sep)))
    emit("kernel/p2m_conv_multi/fused", t_multi * 1e6,
         f"n_cfg={len(leak_cfgs)},hw={hw}")
    emit("kernel/p2m_conv_multi/separate_launches", t_sep * 1e6,
         f"max_err_vs_fused={err:.2e}")
    assert err < 1e-5
    assert bool(jnp.all(s_multi == s_sep))
    return {"fused_s": t_multi, "separate_s": t_sep, "max_err": err,
            "n_cfg": len(leak_cfgs)}


def bench_stream_fold(fast: bool = False) -> dict:
    """Serving fold: XLA scan (oracle) vs the fused stream_fold kernel.

    ``deposit`` mode must be bit-exact with the scan over the same
    deposits and within a few ulp of the conv-fused scan — the contract
    the streaming engine's ``use_kernel`` switch relies on
    (tests/test_stream_fold.py). ``mac`` mode is the fully-fused variant,
    parity-checked with tolerance.
    """
    from jax import lax

    from repro.core.p2m_layer import _conv
    from repro.kernels.stream_fold import ops as sf_ops

    hw = 16 if fast else 24
    B, S, F, k = (4, 4, 8, 3) if fast else (8, 8, 8, 3)
    key = jax.random.PRNGKey(0)
    frames = jax.random.poisson(key, 0.3, (B, S, hw, hw, 2)
                                ).astype(jnp.float32)
    w_q = jax.random.normal(jax.random.fold_in(key, 1), (k, k, 2, F)) * 0.1
    a = jnp.exp(-jax.random.uniform(jax.random.fold_in(key, 2), (F,)))
    x0 = jax.random.normal(jax.random.fold_in(key, 3), (B, hw, hw, F)) * 0.01
    dv_unit = 0.01

    def scan_fold(x, fr):
        def sub(x, ev):
            return x * a + _conv(ev, w_q, 1) * dv_unit, None
        x, _ = lax.scan(sub, x, jnp.moveaxis(fr, 1, 0))
        return x

    t_xla, ref = timed(jax.jit(scan_fold), x0, frames)
    t_dep, out_dep = timed(
        jax.jit(lambda x, fr: sf_ops.fold_chunk(
            x, fr, w_q, a, stride=1, dv_unit=dv_unit)), x0, frames)
    t_mac, out_mac = timed(
        jax.jit(lambda x, fr: sf_ops.fold_chunk(
            x, fr, w_q, a, stride=1, dv_unit=dv_unit, mode="mac")),
        x0, frames)
    oracle = sf_ops.fold_chunk(x0, frames, w_q, a, stride=1,
                               dv_unit=dv_unit, use_ref=True)
    exact_err = float(jnp.max(jnp.abs(out_dep - oracle)))
    err = float(jnp.max(jnp.abs(out_dep - ref)))
    mac_err = float(jnp.max(jnp.abs(out_mac - ref)))
    emit("kernel/stream_fold/xla_scan", t_xla * 1e6, f"B={B},S={S},hw={hw}")
    emit("kernel/stream_fold/pallas_deposit", t_dep * 1e6,
         f"max_err_vs_oracle={err:.2e}")
    emit("kernel/stream_fold/pallas_mac", t_mac * 1e6,
         f"max_err_vs_oracle={mac_err:.2e}")
    assert exact_err == 0.0, f"deposit fold must be bit-exact, got " \
        f"{exact_err}"
    assert err < 1e-7, f"deposit fold vs conv-fused scan: {err}"
    assert mac_err < 1e-4
    return {"xla_s": t_xla, "pallas_interpret_s": t_dep, "mac_s": t_mac,
            "max_err": err, "mac_err": mac_err}


def bench_lif(fast: bool = False) -> dict:
    from repro.kernels.lif.lif import lif_pallas
    from repro.kernels.lif.ref import lif_ref

    T, N = (32, 4096) if fast else (64, 16384)
    x = jax.random.normal(jax.random.PRNGKey(0), (T, N))
    t_xla, ref = timed(jax.jit(lif_ref), x)
    t_pal, out = timed(lambda x: lif_pallas(x), x)
    err = float(jnp.max(jnp.abs(out - ref)))
    emit("kernel/lif/xla_scan", t_xla * 1e6, f"T={T},N={N}")
    emit("kernel/lif/pallas_interpret", t_pal * 1e6,
         f"max_err_vs_oracle={err:.2e}")
    assert err == 0.0
    return {"xla_s": t_xla, "pallas_interpret_s": t_pal, "max_err": err}


def bench_ssd(fast: bool = False) -> dict:
    from repro.kernels.ssd.ref import ssd_ref
    from repro.kernels.ssd.ssd import ssd_pallas
    from repro.nn.ssm import ssd_chunked

    b, s, h, p, g, n = (1, 256, 4, 32, 1, 16) if fast else (2, 512, 8, 64, 1, 32)
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, g, n))
    C = jax.random.normal(ks[4], (b, s, g, n))

    t_chunk, (y_c, _) = timed(
        jax.jit(lambda *a: ssd_chunked(*a, chunk=128)), x, dt, A, B, C)
    t_pal, (y_k, _) = timed(
        lambda *a: ssd_pallas(*a, chunk=128), x, dt, A, B, C)
    y_r, _ = ssd_ref(x, dt, A, B, C)
    err = float(jnp.max(jnp.abs(y_k - y_r)))
    rel = err / float(jnp.max(jnp.abs(y_r)))
    emit("kernel/ssd/xla_chunked", t_chunk * 1e6, f"s={s},h={h},p={p}")
    emit("kernel/ssd/pallas_interpret", t_pal * 1e6,
         f"rel_err_vs_oracle={rel:.2e}")
    assert rel < 1e-3
    return {"xla_s": t_chunk, "pallas_interpret_s": t_pal, "rel_err": rel}


def bench_flash(fast: bool = False) -> dict:
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_pallas)
    from repro.kernels.flash_attention.ref import attention_ref

    BH, S, d = (4, 256, 64) if fast else (8, 512, 64)
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (BH, S, d))
    kk = jax.random.normal(jax.random.fold_in(k, 1), (BH, S, d))
    v = jax.random.normal(jax.random.fold_in(k, 2), (BH, S, d))
    t_xla, ref = timed(jax.jit(lambda *a: attention_ref(*a, causal=True)),
                       q, kk, v)
    t_pal, out = timed(
        lambda *a: flash_attention_pallas(*a, causal=True), q, kk, v)
    err = float(jnp.max(jnp.abs(out - ref)))
    emit("kernel/flash/xla_full", t_xla * 1e6, f"S={S},d={d}")
    emit("kernel/flash/pallas_interpret", t_pal * 1e6,
         f"max_err_vs_oracle={err:.2e}")
    assert err < 5e-3
    return {"xla_s": t_xla, "pallas_interpret_s": t_pal, "max_err": err}


def run(fast: bool = False) -> dict:
    out = {"p2m": bench_p2m(fast), "p2m_multi": bench_p2m_multi(fast),
           "lif": bench_lif(fast), "stream_fold": bench_stream_fold(fast),
           "ssd": bench_ssd(fast), "flash": bench_flash(fast)}
    save_json("kernels", out)

    def us(s):
        return None if s is None else s * 1e6

    bench_record("kernels", [
        bench_entry("p2m_conv", xla_us=us(out["p2m"]["xla_s"]),
                    kernel_us=us(out["p2m"]["pallas_interpret_s"]),
                    max_err=out["p2m"]["max_err"]),
        bench_entry("p2m_conv_multi", xla_us=us(out["p2m_multi"]["separate_s"]),
                    kernel_us=us(out["p2m_multi"]["fused_s"]),
                    max_err=out["p2m_multi"]["max_err"],
                    meta={"n_cfg": out["p2m_multi"]["n_cfg"]}),
        bench_entry("lif", xla_us=us(out["lif"]["xla_s"]),
                    kernel_us=us(out["lif"]["pallas_interpret_s"]),
                    max_err=out["lif"]["max_err"]),
        bench_entry("stream_fold", xla_us=us(out["stream_fold"]["xla_s"]),
                    kernel_us=us(out["stream_fold"]["pallas_interpret_s"]),
                    max_err=out["stream_fold"]["max_err"],
                    meta={"mac_us": us(out["stream_fold"]["mac_s"]),
                          "mac_err": out["stream_fold"]["mac_err"]}),
        bench_entry("ssd", xla_us=us(out["ssd"]["xla_s"]),
                    kernel_us=us(out["ssd"]["pallas_interpret_s"]),
                    max_err=out["ssd"]["rel_err"],
                    meta={"err_kind": "rel"}),
        bench_entry("flash_attention", xla_us=us(out["flash"]["xla_s"]),
                    kernel_us=us(out["flash"]["pallas_interpret_s"]),
                    max_err=out["flash"]["max_err"]),
    ], extra={"fast": fast})
    return out


if __name__ == "__main__":
    run()
