"""Plain reference of Spikformer-2-256 behind the P²M in-pixel layer,
written from the configuration alone.

It imports nothing of the program under test; the in-pixel layer's leak
coefficients and its 2×2 pool are those of the CNN's reference
(``bench/references/p2m_snn.py``). It also makes the weights the
benchmark serves (:func:`make_init`).

What one stream computes (Zhou et al., ICLR 2023, arXiv:2209.15425, the
DVS128-Gesture model; D = ``embed_dim``):

1. Layer 1, window by window, as the CNN's reference computes it: the
   leaky charge of SPS stage 0's 3×3 conv (2 → D/8) over ``n_sub``
   sub-slots, drift, transfer curve, process variation, comparator, 2×2
   pool.
2. Pooled spikes are summed over ``coarse_window_ms / t_intg_ms`` windows
   into one coarse frame; each coarse frame is one backbone step.
3. Per step, with ``LIF`` as ``v ← v + (x − v)/τ``, spike where ``v > θ``,
   hard reset to 0 (θ = 1, or ``attn_v_threshold`` in the attention), and
   ``BN`` batch norm with running statistics:
   * SPS stages 1–3: ``MaxPool3s2(LIF(BN(conv3×3(x))))``, widths D/4, D/2,
     D (pool: window 3, stride 2, padding 1);
   * ``X = x + LIF(BN(conv3×3(x)))`` (relative-position conv), flattened
     to tokens;
   * per encoder block: ``Q, K, V = LIF(BN(X·W))``; per head of
     ``D / heads`` channels ``A = LIF(Q·(Kᵀ·V)·attn_scale)``;
     ``X ← X + LIF(BN(A·W_o))``; ``X ← X + LIF(BN(LIF(BN(X·W₁))·W₂))``;
   * logits: ``mean_tokens(X)·W_head + b``.
4. The stream's logits are the mean of its steps' logits; its layer-1
   count is the sum of its pooled spikes.

Departures from the published model, each stated in the configuration's
``assumed``: stage 0's BN + LIF are the analog MAC and comparator and its
pool is the 2×2 comparator pool; a step's input is a coarse window of
spike counts, not a binary frame; a step is a 100 ms window (the
published frames are ~370 ms); projections carry no bias, since the BN
shift that follows takes its place.

Computed in ``dtype`` throughout: float32 at the precision the
configuration states, bfloat16 for the control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench import catalog

_l1 = catalog.reference("p2m_snn")

KERNEL = 3
# He-normal weights times these gains. Behind the layer-1 counts (SPS
# stage 1) plain He weights suffice; behind a spike map the LIF (τ 2,
# θ 1, hard reset) needs the input a few times larger to fire at all;
# the attention's LIF sees Q·(Kᵀ·V)·0.125, which grows with the cube of
# the Q, K, V rates, so those are held near a seventh, and scaled down
# with depth as the residual sums grow. At these gains the LIF layers fire
# on 12–28% of their sites at the published widths, as in a trained
# network, so the logits depend on every layer below them; the first SPS
# stage on about 9%, bound by how few sites the scene's layer-1 counts
# light (about 2%).
GAIN = {"sps1": 8.0, "sps": 5.0, "rpe": 3.0, "qkv": 1.7, "proj": 4.0,
        "mlp1": 3.0, "mlp2": 3.0}


def lif_names(bb: dict) -> list[str]:
    """The backbone's LIF layers, in order (``backbone_spikes`` columns)."""
    names = ["sps1", "sps2", "sps3", "rpe"]
    for b in range(bb["depth"]):
        names += [f"b{b}_{m}" for m in ("q", "k", "v", "attn", "proj",
                                        "mlp1", "mlp2")]
    return names


def make_init(model: dict):
    """One jitted call: key → (params, bn_state), float32, in the layout
    the served deployment takes (``p2m``: ``w``, ``pv_gain``,
    ``pv_offset``; ``backbone``: ``sps{1,2,3}``, ``rpe``,
    ``b{block}_{q,k,v,proj,mlp1,mlp2}`` each ``{"w"}`` with a
    ``<name>_bn`` beside it, and ``head``). Weights are He-normal times
    :data:`GAIN`; batch-norm affine terms and running statistics are drawn
    around their neutral values, as a trained deployment's would lie."""
    p2m, bb = model["p2m"], model["backbone"]
    analog = p2m["analog"]
    k = p2m["kernel_size"]
    d = bb["embed_dim"]
    hid = bb["mlp_ratio"] * d
    widths = [d // 8, d // 4, d // 2, d]

    def he(key, shape, fan_in, gain):
        return gain * jax.random.normal(key, shape) * math.sqrt(2.0 / fan_in)

    def init(key):
        ks = iter(jax.random.split(key, 8 + 5 * (4 + 6 * bb["depth"])))
        f = p2m["out_channels"]
        if analog["enable_process_variation"]:
            gain = 1.0 + analog["pv_gain_sigma"] * jax.random.normal(
                next(ks), (f,))
            offset = analog["pv_offset_sigma_mv"] * 1e-3 * \
                jax.random.normal(next(ks), (f,))
        else:
            gain, offset = jnp.ones((f,)), jnp.zeros((f,))
        params = {"p2m": {"w": he(next(ks), (k, k, p2m["in_channels"], f),
                                  k * k * p2m["in_channels"], 1.0),
                          "pv_gain": gain, "pv_offset": offset},
                  "backbone": {}}
        bbp, state = params["backbone"], {}

        def layer(name, shape, fan_in, gain):
            c = shape[-1]
            bbp[name] = {"w": he(next(ks), shape, fan_in, gain)}
            bbp[f"{name}_bn"] = {
                "scale": 1.0 + 0.1 * jax.random.normal(next(ks), (c,)),
                "bias": 0.1 * jax.random.normal(next(ks), (c,))}
            state[f"{name}_bn"] = {
                "mean": 0.1 * jax.random.normal(next(ks), (c,)),
                "var": jax.random.uniform(next(ks), (c,), minval=0.5,
                                          maxval=2.0)}

        for i in (1, 2, 3):
            ci, co = widths[i - 1], widths[i]
            layer(f"sps{i}", (KERNEL, KERNEL, ci, co), KERNEL * KERNEL * ci,
                  GAIN["sps1"] if i == 1 else GAIN["sps"])
        layer("rpe", (KERNEL, KERNEL, d, d), KERNEL * KERNEL * d,
              GAIN["rpe"])
        for b in range(bb["depth"]):
            qkv = GAIN["qkv"] / math.sqrt(1 + b)
            for m, shape, g in (("q", (d, d), qkv), ("k", (d, d), qkv),
                                ("v", (d, d), qkv),
                                ("proj", (d, d), GAIN["proj"]),
                                ("mlp1", (d, hid), GAIN["mlp1"]),
                                ("mlp2", (hid, d), GAIN["mlp2"])):
                layer(f"b{b}_{m}", shape, shape[0], g)
        bbp["head"] = {
            "w": he(next(ks), (d, bb["n_classes"]), d, 1.0),
            "b": 0.05 * jax.random.normal(next(ks), (bb["n_classes"],))}
        return params, state

    return jax.jit(init)


def _pool3s2(x):
    return lax.reduce_window(x, jnp.array(-jnp.inf, x.dtype), lax.max,
                             (1, 3, 3, 1), (1, 2, 2, 1),
                             ((0, 0), (1, 1), (1, 1), (0, 0)))


def make_forward(model: dict, *, bn_eps: float, near_v: float,
                 precision: str = "default", dtype=jnp.float32):
    """``(params, bn_state, counts) → outputs`` for ``counts`` ``[B,
    windows·n_sub, H, W, 2]`` (ON, OFF event counts on the sub-slot grid):

    * ``layer1_spikes`` [B]: pooled layer-1 spikes over all windows;
    * ``layer1_near`` [B]: pooled layer-1 outputs whose 2×2 pool held a
      site within ``near_v`` volts of the threshold;
    * ``backbone_spikes`` [B, layers]: spikes of each backbone LIF layer
      (:func:`lif_names`);
    * ``logits`` [B, n_classes]: the rate-decoded logits.

    The leak coefficients are computed on the device outside the jitted
    forward, as a deployment computes them once. Matmuls and convolutions
    run at ``precision`` in float32, and at the backend's default in
    bfloat16."""
    p2m, bb = model["p2m"], model["backbone"]
    analog, leak, lif = p2m["analog"], p2m["leak"], bb["lif"]
    if lif["soft_reset"]:
        raise ValueError("Spikformer's LIF resets to 0 (soft_reset false)")
    n_sub = p2m["n_sub"]
    group = round(model["coarse_window_ms"] / p2m["t_intg_ms"])
    dt_ms = p2m["t_intg_ms"] / n_sub
    theta = (p2m["v_threshold"] if leak["v_threshold"] is None
             else leak["v_threshold"])
    s1 = p2m["stride"]
    d, heads = bb["embed_dim"], bb["heads"]
    names = lif_names(bb)
    prec = (getattr(lax.Precision, precision.upper())
            if dtype == jnp.float32 else lax.Precision.DEFAULT)
    c = lambda v: jnp.asarray(v, dtype)                       # noqa: E731

    def conv(x, w, stride=1):
        return lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
            preferred_element_type=dtype)

    def matmul(x, w):
        return jnp.dot(x, w, precision=prec, preferred_element_type=dtype)

    def lif_step(v, x, v_th):
        v = v + (x - v) / c(lif["tau"])
        s = (v - c(v_th) > 0).astype(dtype)
        return v * (c(1.0) - s), s

    def coefficients(w):
        w = jnp.clip(w, -analog["w_clip"], analog["w_clip"])
        step = analog["w_clip"] / (analog["weight_levels"] // 2)
        w_q = jnp.round(w / step) * step
        a, v_inf = _l1._leak(w_q, leak, dt_ms)
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
            pooled = _l1._pool2((u - c(theta) > 0).astype(dtype))
            near = _l1._pool2((jnp.abs(u.astype(jnp.float32) - theta)
                               < near_v).astype(jnp.float32))
            return (coarse + pooled,
                    n_spk + jnp.sum(pooled.astype(jnp.float32),
                                    axis=(1, 2, 3)),
                    n_near + jnp.sum(near, axis=(1, 2, 3))), None

        def bn(name, y):
            st, bp = bn_state[name], bbp[name]
            return (y - st["mean"]) * lax.rsqrt(st["var"] + c(bn_eps)) \
                * bp["scale"] + bp["bias"]

        def coarse_step(carry, ev_c):
            mem, n_spk, n_near, bb_spk, logit_sum = carry
            h0 = jnp.zeros((B, H // s1 // 2, W // s1 // 2,
                            p2m["out_channels"]), dtype)
            (h, n_spk, n_near), _ = lax.scan(window, (h0, n_spk, n_near),
                                             ev_c)
            new_mem, fired = {}, []

            def fire(name, y, v_th=lif["v_threshold"]):
                new_mem[name], s = lif_step(mem[name], y, v_th)
                fired.append(jnp.sum(s.astype(jnp.float32),
                                     axis=tuple(range(1, s.ndim))))
                return s

            for i in (1, 2, 3):
                name = f"sps{i}"
                h = _pool3s2(fire(name, bn(f"{name}_bn",
                                           conv(h, bbp[name]["w"]))))
            h = h + fire("rpe", bn("rpe_bn", conv(h, bbp["rpe"]["w"])))
            x = h.reshape((B, -1, d))                          # tokens
            n_tok = x.shape[1]

            def proj(name, y):
                return fire(name, bn(f"{name}_bn",
                                     matmul(y, bbp[name]["w"])))

            for b in range(bb["depth"]):
                q, k, v = (proj(f"b{b}_{m}", x).reshape(
                    (B, n_tok, heads, d // heads)) for m in ("q", "k", "v"))
                kv = jnp.einsum("bnhd,bnhe->bhde", k, v, precision=prec,
                                preferred_element_type=dtype)
                att = jnp.einsum("bnhd,bhde->bnhe", q, kv, precision=prec,
                                 preferred_element_type=dtype)
                att = fire(f"b{b}_attn",
                           att.reshape((B, n_tok, d)) * c(bb["attn_scale"]),
                           bb["attn_v_threshold"])
                x = x + proj(f"b{b}_proj", att)
                x = x + proj(f"b{b}_mlp2", proj(f"b{b}_mlp1", x))
            logits_t = matmul(jnp.mean(x, axis=1), bbp["head"]["w"]) \
                + bbp["head"]["b"]
            return (new_mem, n_spk, n_near, bb_spk + jnp.stack(fired, 1),
                    logit_sum + logits_t.astype(jnp.float32)), None

        mem0 = {n: jnp.zeros((B,) + shape, dtype)
                for n, shape in _membranes(model).items()}
        zeros = jnp.zeros((B,), jnp.float32)
        init = (mem0, zeros, zeros, jnp.zeros((B, len(names)), jnp.float32),
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


def _membranes(model: dict) -> dict:
    """Membrane shape of every LIF layer, walked from the configuration."""
    p2m, bb = model["p2m"], model["backbone"]
    d = bb["embed_dim"]
    h, w = (n // p2m["stride"] // 2 for n in bb["input_hw"])
    out = {}
    for i, c in zip((1, 2, 3), (d // 4, d // 2, d)):
        out[f"sps{i}"] = (h, w, c)
        h, w = (h + 1) // 2, (w + 1) // 2
    out["rpe"] = (h, w, d)
    for b in range(bb["depth"]):
        for m in ("q", "k", "v", "attn", "proj", "mlp2"):
            out[f"b{b}_{m}"] = (h * w, d)
        out[f"b{b}_mlp1"] = (h * w, bb["mlp_ratio"] * d)
    return out
