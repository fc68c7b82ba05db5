"""Plain reference of the P²M network: the in-pixel analog first layer
and the spiking CNN backbone, written from the configuration alone.

It imports nothing of the program under test. It also makes the weights
the benchmark serves (:func:`make_init`), so neither side takes anything
the other made.

What one stream computes, window by window (``T_INTG`` each, ``n_sub``
sub-slots):

1. Layer-1 charge, per output site and filter, starting from zero at
   every window (the capacitor precharges after each readout):
   ``x ← x·a + conv(events_k, w_q)·dv_unit`` for ``k = 0..n_sub-1``,
   with ``w_q`` the weights quantised to ``weight_levels`` steps on
   ``[-w_clip, w_clip]``, and ``a = exp(-dt/τ)`` the per-filter decay of
   the leak linearisation of the configured MAC circuit.
2. Readout: the window's drift toward the leak asymptote,
   ``Σ_k (1 - a^(n_sub-1-k))·v_inf / n_sub``, is added; the fitted
   transfer curve ``(c1·u + c3·u³)·VDD/2`` (``u = x / (VDD/2)``), the
   per-filter process variation ``gain, offset`` and the rail clamp give
   the realised swing, and the comparator fires where it exceeds the
   threshold. Spikes are 2×2 max-pooled.
3. Pooled spikes are summed over ``coarse_window_ms / t_intg_ms``
   windows into one coarse frame. Each coarse frame steps the backbone:
   per conv block, conv + bias → batch norm (running statistics) → LIF
   (``v ← v + (x - v)/τ``, spike where ``v > θ``, soft reset) → 2×2 max
   pool; then FC → LIF → FC gives the step's logits.
4. The stream's logits are the mean of its coarse steps' logits (rate
   decoding); its layer-1 count is the sum of its pooled spikes.

Computed in ``dtype`` throughout: float32 at the precision the
configuration states for the reference, bfloat16 for the control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


# He-normal weights scaled up where a layer's input is a binary spike map
# (every backbone block after the first, and the first FC layer): with a
# LIF threshold of 1 and time constant 2, plain He weights let activity
# die out after one block. At this gain every LIF layer of both
# configurations fires on 10-30% of its sites, as in a trained network,
# so the logits depend on every layer below them.
SPIKE_GAIN = 4.0


def _shapes(model: dict) -> dict:
    """Layer shapes of the backbone, walked from the configuration."""
    p2m, bb = model["p2m"], model["backbone"]
    h, w = bb["input_hw"]
    s1 = p2m["stride"]
    conv = []
    h, w, c_in = h // s1 // 2, w // s1 // 2, bb["channels"][0]
    for c_out in bb["channels"][1:]:
        conv.append({"c_in": c_in, "c_out": c_out, "hw": (h, w)})
        h, w, c_in = h // 2, w // 2, c_out
    return {"conv": conv, "flat": h * w * c_in}


def make_init(model: dict):
    """One jitted call: key → (params, bn_state), float32, in the layout
    the served deployment takes (``p2m``: ``w``, ``pv_gain``,
    ``pv_offset``; ``backbone``: ``conv{i}``, ``bn{i}``, ``fc0``,
    ``fc1``). Conv and dense weights are He-normal (times
    :data:`SPIKE_GAIN` behind a spike map); biases, batch-norm affine
    terms and running statistics are drawn around their neutral values,
    as a trained deployment's would lie, so every term of the forward is
    exercised."""
    p2m, bb = model["p2m"], model["backbone"]
    analog = p2m["analog"]
    k = p2m["kernel_size"]
    kb = bb["kernel_size"]
    shapes = _shapes(model)
    n_conv = len(shapes["conv"])

    def he(key, shape, fan_in):
        return jax.random.normal(key, shape) * math.sqrt(2.0 / fan_in)

    def init(key):
        ks = iter(jax.random.split(key, 8 + 8 * n_conv))
        f = p2m["out_channels"]
        if analog["enable_process_variation"]:
            gain = 1.0 + analog["pv_gain_sigma"] * jax.random.normal(
                next(ks), (f,))
            offset = analog["pv_offset_sigma_mv"] * 1e-3 * \
                jax.random.normal(next(ks), (f,))
        else:
            gain, offset = jnp.ones((f,)), jnp.zeros((f,))
        params = {"p2m": {"w": he(next(ks), (k, k, p2m["in_channels"], f),
                                  k * k * p2m["in_channels"]),
                          "pv_gain": gain, "pv_offset": offset},
                  "backbone": {}}
        state = {}
        for i, layer in enumerate(shapes["conv"], start=1):
            ci, co = layer["c_in"], layer["c_out"]
            gain = 1.0 if i == 1 else SPIKE_GAIN
            params["backbone"][f"conv{i}"] = {
                "w": gain * he(next(ks), (kb, kb, ci, co), kb * kb * ci),
                "b": 0.05 * jax.random.normal(next(ks), (co,))}
            params["backbone"][f"bn{i}"] = {
                "scale": 1.0 + 0.1 * jax.random.normal(next(ks), (co,)),
                "bias": 0.1 * jax.random.normal(next(ks), (co,))}
            state[f"bn{i}"] = {
                "mean": 0.1 * jax.random.normal(next(ks), (co,)),
                "var": jax.random.uniform(next(ks), (co,), minval=0.5,
                                          maxval=2.0)}
        flat, hid, n_cls = shapes["flat"], bb["fc_hidden"], bb["n_classes"]
        params["backbone"]["fc0"] = {
            "w": SPIKE_GAIN * he(next(ks), (flat, hid), flat),
            "b": 0.05 * jax.random.normal(next(ks), (hid,))}
        params["backbone"]["fc1"] = {
            "w": he(next(ks), (hid, n_cls), hid),
            "b": 0.05 * jax.random.normal(next(ks), (n_cls,))}
        return params, state

    return jax.jit(init)


def _leak(w_q: jax.Array, leak: dict, dt_ms: float):
    """Per-filter sub-slot decay ``a`` and asymptote ``v_inf`` (swing
    volts) of the configured MAC circuit: (a) leaks through the weight
    transistors toward a kernel-dependent level, (b) through the
    isolation switch toward ground, (c) as (b) scaled by the nullifier's
    mismatch; "ideal" does not leak."""
    if leak["sigma"]:
        raise NotImplementedError("the reference covers sigma = 0 only")
    circuit = leak["circuit"]
    f = w_q.shape[-1]
    if circuit == "a":
        axes = tuple(range(w_q.ndim - 1))
        pos = jnp.sum(jnp.maximum(w_q, 0.0), axis=axes)
        neg = jnp.sum(jnp.maximum(-w_q, 0.0), axis=axes)
        v_inf = leak["vdd"] * pos / (pos + neg + leak["w_eps"]) \
            - leak["v_precharge"]
        tau = leak["tau0_a_ms"] / jnp.maximum(
            jnp.mean(jnp.abs(w_q), axis=axes), leak["w_eps"])
        return jnp.exp(-dt_ms / tau), v_inf
    if circuit == "b":
        tau = leak["tau_b_ms"]
    elif circuit == "c":
        tau = leak["tau_b_ms"] / max(leak["null_mismatch"], 1e-6)
    elif circuit == "ideal":
        return jnp.ones((f,)), jnp.zeros((f,))
    else:
        raise ValueError(f"unknown circuit {circuit!r}")
    tau = jnp.full((f,), tau, jnp.float32)
    return jnp.exp(-dt_ms / tau), jnp.full((f,), -leak["v_precharge"],
                                          jnp.float32)


def _pool2(x):
    return lax.reduce_window(x, jnp.array(-jnp.inf, x.dtype), lax.max,
                             (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def make_forward(model: dict, *, bn_eps: float, near_v: float,
                 precision: str = "default", dtype=jnp.float32):
    """``(params, bn_state, counts) → outputs`` for ``counts`` ``[B,
    windows·n_sub, H, W, 2]`` (ON, OFF event counts on the sub-slot grid):

    * ``layer1_spikes`` [B]: pooled layer-1 spikes over all windows;
    * ``layer1_near`` [B]: pooled layer-1 outputs whose 2×2 pool held a
      site within ``near_v`` volts of the threshold, where float32
      rounding may decide the comparator either way;
    * ``backbone_spikes`` [B, layers]: spikes of each backbone LIF layer;
    * ``logits`` [B, n_classes]: the rate-decoded logits.

    The leak coefficients (``a``, drift) are computed on the device
    outside the jitted forward, as a deployment computes them once, so
    the device's own ``exp`` and ``pow`` give them. Matmuls and
    convolutions run at ``precision`` ("default" or "highest") in
    float32, and at the backend's default in bfloat16."""
    p2m, bb = model["p2m"], model["backbone"]
    analog, leak, lif = p2m["analog"], p2m["leak"], bb["lif"]
    n_sub = p2m["n_sub"]
    group = round(model["coarse_window_ms"] / p2m["t_intg_ms"])
    dt_ms = p2m["t_intg_ms"] / n_sub
    theta = (p2m["v_threshold"] if leak["v_threshold"] is None
             else leak["v_threshold"])
    s1 = p2m["stride"]
    n_conv = len(_shapes(model)["conv"])
    prec = (getattr(lax.Precision, precision.upper())
            if dtype == jnp.float32 else lax.Precision.DEFAULT)
    c = lambda v: jnp.asarray(v, dtype)                       # noqa: E731

    def conv(x, w, stride):
        return lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
            preferred_element_type=dtype)

    def lif_step(v, x):
        v = v + (x - v) / c(lif["tau"])
        s = (v - c(lif["v_threshold"]) > 0).astype(dtype)
        v = v - s * c(lif["v_threshold"]) if lif["soft_reset"] \
            else v * (c(1.0) - s)
        return v, s

    def coefficients(w):
        w = jnp.clip(w, -analog["w_clip"], analog["w_clip"])
        step = analog["w_clip"] / (analog["weight_levels"] // 2)
        w_q = jnp.round(w / step) * step
        a, v_inf = _leak(w_q, leak, dt_ms)
        k = jnp.arange(n_sub)
        decay = a[None, :] ** (n_sub - 1 - k)[:, None]
        drift = jnp.sum(1.0 - decay, axis=0) * v_inf / n_sub
        return {"w_q": w_q, "a": a, "drift": drift}

    @jax.jit
    def run(params, bn_state, co, counts):
        cast = lambda t: jax.tree.map(lambda v: v.astype(dtype), t)  # noqa
        params, bn_state, co = cast(params), cast(bn_state), cast(co)
        w_q, a, drift = co["w_q"], co["a"], co["drift"]
        half = c(analog["vdd"] / 2.0)
        gain, offset = params["p2m"]["pv_gain"], params["p2m"]["pv_offset"]
        bbp = params["backbone"]

        B, n_total, H, W = counts.shape[:4]
        windows = n_total // n_sub
        ev = counts.reshape((B, windows // group, group, n_sub)
                            + counts.shape[2:])
        ev = jnp.moveaxis(ev, 0, 3)          # [coarse, group, n_sub, B, ...]

        def window(carry, ev_w):
            coarse, n_spk, n_near = carry
            x = None
            for j in range(n_sub):
                dep = conv(ev_w[j].astype(dtype), w_q, s1) * c(
                    analog["dv_unit"])
                x = dep if x is None else x * a + dep
            u = x + drift
            if analog["enable_nonlinearity"]:
                un = u / half
                u = (c(analog["c1"]) * un + c(analog["c3"]) * un ** 3) * half
            u = jnp.clip(u * gain + offset, c(-analog["v_precharge"]),
                         c(analog["vdd"] - analog["v_precharge"]))
            pooled = _pool2((u - c(theta) > 0).astype(dtype))
            near = _pool2((jnp.abs(u.astype(jnp.float32) - theta)
                           < near_v).astype(jnp.float32))
            return (coarse + pooled,
                    n_spk + jnp.sum(pooled.astype(jnp.float32),
                                    axis=(1, 2, 3)),
                    n_near + jnp.sum(near, axis=(1, 2, 3))), None

        def coarse_step(carry, ev_c):
            mem, n_spk, n_near, bb_spk, logit_sum = carry
            h0 = jnp.zeros((B, H // s1 // 2, W // s1 // 2,
                            p2m["out_channels"]), dtype)
            (h, n_spk, n_near), _ = lax.scan(window, (h0, n_spk, n_near),
                                             ev_c)
            new_mem, fired = [], []
            for i in range(1, n_conv + 1):
                y = conv(h, bbp[f"conv{i}"]["w"], 1) + bbp[f"conv{i}"]["b"]
                st, bp = bn_state[f"bn{i}"], bbp[f"bn{i}"]
                y = (y - st["mean"]) * lax.rsqrt(st["var"] + c(bn_eps)) \
                    * bp["scale"] + bp["bias"]
                v, s = lif_step(mem[i - 1], y)
                new_mem.append(v)
                fired.append(jnp.sum(s.astype(jnp.float32), axis=(1, 2, 3)))
                h = _pool2(s)
            z = jnp.dot(h.reshape((B, -1)), bbp["fc0"]["w"], precision=prec,
                        preferred_element_type=dtype) + bbp["fc0"]["b"]
            v, s = lif_step(mem[n_conv], z)
            new_mem.append(v)
            fired.append(jnp.sum(s.astype(jnp.float32), axis=1))
            logits_t = jnp.dot(s, bbp["fc1"]["w"], precision=prec,
                               preferred_element_type=dtype) \
                + bbp["fc1"]["b"]
            return (new_mem, n_spk, n_near, bb_spk + jnp.stack(fired, 1),
                    logit_sum + logits_t.astype(jnp.float32)), None

        mem0 = []
        for layer in _shapes(model)["conv"]:
            mem0.append(jnp.zeros((B,) + layer["hw"] + (layer["c_out"],),
                                  dtype))
        mem0.append(jnp.zeros((B, bb["fc_hidden"]), dtype))
        zeros = jnp.zeros((B,), jnp.float32)
        init = (mem0, zeros, zeros, jnp.zeros((B, n_conv + 1), jnp.float32),
                jnp.zeros((B, bb["n_classes"]), jnp.float32))
        (_, n_spk, n_near, bb_spk, logit_sum), _ = lax.scan(
            coarse_step, init, ev)
        return {"layer1_spikes": n_spk, "layer1_near": n_near,
                "backbone_spikes": bb_spk,
                "logits": logit_sum / (windows // group)}

    def forward(params, bn_state, counts):
        return run(params, bn_state, coefficients(params["p2m"]["w"]),
                   counts)

    return forward
