"""Spiking-CNN substrate: LIF neurons with surrogate gradients, conv/BN/pool
layers, and the paper's backbone network (4× [conv→BN→LIF→maxpool] → FC512 →
LIF → FC10, rate decoding). Pure functional JAX: params/state are dict
pytrees, time handled with lax.scan.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, ClassVar

import jax
import jax.numpy as jnp
from jax import lax

Params = dict
State = dict

# ---------------------------------------------------------------------------
# Surrogate-gradient spike function (ATan surrogate, SpikingJelly's default)
# ---------------------------------------------------------------------------

_SG_ALPHA = 2.0


@jax.custom_vjp
def spike_fn(x: jax.Array) -> jax.Array:
    """Heaviside spike with ATan surrogate gradient."""
    return (x > 0.0).astype(x.dtype)


def _spike_fwd(x):
    return spike_fn(x), x


def _spike_bwd(x, g):
    # d/dx [ (1/pi) * atan(pi/2 * alpha * x) + 1/2 ]
    sg = _SG_ALPHA / (2.0 * (1.0 + (0.5 * math.pi * _SG_ALPHA * x) ** 2))
    return (g * sg,)


spike_fn.defvjp(_spike_fwd, _spike_bwd)


# ---------------------------------------------------------------------------
# LIF dynamics
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LIFConfig:
    tau: float = 2.0          # membrane time constant (in timesteps)
    v_threshold: float = 1.0
    soft_reset: bool = True   # subtract threshold on spike (vs reset to 0)


def lif_step(v: jax.Array, x: jax.Array, cfg: LIFConfig) -> tuple[jax.Array, jax.Array]:
    """One LIF update. Returns (new membrane, spikes)."""
    v = v + (x - v) / cfg.tau
    s = spike_fn(v - cfg.v_threshold)
    if cfg.soft_reset:
        v = v - s * cfg.v_threshold
    else:
        v = v * (1.0 - s)
    return v, s


def lif_over_time(x: jax.Array, cfg: LIFConfig) -> jax.Array:
    """Run LIF over the time axis. x: [T, B, ...] → spikes [T, B, ...]."""
    v0 = jnp.zeros_like(x[0])

    def step(v, xt):
        v, s = lif_step(v, xt, cfg)
        return v, s

    _, spikes = lax.scan(step, v0, x)
    return spikes


# ---------------------------------------------------------------------------
# Stateless layer helpers (params as dicts)
# ---------------------------------------------------------------------------

def conv_init(key, kh, kw, c_in, c_out, dtype=jnp.float32) -> Params:
    fan_in = kh * kw * c_in
    w = jax.random.normal(key, (kh, kw, c_in, c_out), dtype) * math.sqrt(2.0 / fan_in)
    return {"w": w, "b": jnp.zeros((c_out,), dtype)}


def conv_apply(p: Params, x: jax.Array, stride: int = 1, padding: str = "SAME") -> jax.Array:
    """x: [N, H, W, C] NHWC."""
    y = lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def dense_init(key, d_in, d_out, dtype=jnp.float32) -> Params:
    w = jax.random.normal(key, (d_in, d_out), dtype) * math.sqrt(2.0 / d_in)
    return {"w": w, "b": jnp.zeros((d_out,), dtype)}


def dense_apply(p: Params, x: jax.Array) -> jax.Array:
    return x @ p["w"] + p["b"]


def bn_init(c: int, dtype=jnp.float32) -> tuple[Params, State]:
    params = {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}
    state = {"mean": jnp.zeros((c,), dtype), "var": jnp.ones((c,), dtype)}
    return params, state


def bn_apply(p: Params, s: State, x: jax.Array, *, train: bool,
             momentum: float = 0.9, eps: float = 1e-5) -> tuple[jax.Array, State]:
    """BatchNorm over all axes but the last (channels)."""
    if train:
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(x, axis=axes)
        var = jnp.var(x, axis=axes)
        new_s = {
            "mean": momentum * s["mean"] + (1 - momentum) * mean,
            "var": momentum * s["var"] + (1 - momentum) * var,
        }
    else:
        mean, var = s["mean"], s["var"]
        new_s = s
    inv = lax.rsqrt(var + eps)
    y = (x - mean) * inv * p["scale"] + p["bias"]
    return y, new_s


def max_pool(x: jax.Array, window: int = 2) -> jax.Array:
    """x: [N, H, W, C] → 2x2 max pool, stride=window."""
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, window, window, 1),
        padding="VALID")


# ---------------------------------------------------------------------------
# The paper's backbone spiking CNN
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SpikingCNNConfig:
    """4 conv blocks (conv→BN→LIF→pool) + FC(512)→LIF→FC(n_classes).

    The first block can be replaced by the P²M hardware layer (see
    p2m_layer.py); in that case `first_layer_external=True` and the model
    consumes the P²M layer's (already-spiking, possibly multi-bit counts)
    output directly.
    """
    in_channels: int = 2                        # DVS ON/OFF
    channels: tuple[int, ...] = (16, 32, 64, 64)
    kernel_size: int = 3
    first_stride: int = 1
    fc_hidden: int = 512
    n_classes: int = 11
    input_hw: tuple[int, int] = (128, 128)
    lif: LIFConfig = field(default_factory=LIFConfig)
    first_layer_external: bool = False          # True when P²M supplies layer 1
    # the backbone seam's key (repro.core.backbone); a class constant, so
    # the config's dict and every checkpoint written before it stay as
    # they were
    kind: ClassVar[str] = "cnn"

    @property
    def n_conv(self) -> int:
        return len(self.channels)


def spiking_cnn_init(key: jax.Array, cfg: SpikingCNNConfig) -> tuple[Params, State]:
    keys = jax.random.split(key, cfg.n_conv + 2)
    params: Params = {}
    state: State = {}
    h, w = cfg.input_hw
    c_in = cfg.in_channels
    start = 0
    if cfg.first_layer_external:
        # layer 1 lives in the pixel array (P²M); the backbone starts at conv2.
        c_in = cfg.channels[0]
        h //= (2 * cfg.first_stride)   # P²M stride + its pool
        w //= (2 * cfg.first_stride)
        start = 1
    for i in range(start, cfg.n_conv):
        stride = cfg.first_stride if i == 0 else 1
        params[f"conv{i}"] = conv_init(keys[i], cfg.kernel_size, cfg.kernel_size,
                                       c_in, cfg.channels[i])
        bnp, bns = bn_init(cfg.channels[i])
        params[f"bn{i}"] = bnp
        state[f"bn{i}"] = bns
        c_in = cfg.channels[i]
        h = h // (2 * stride)
        w = w // (2 * stride)
    flat = h * w * c_in
    params["fc0"] = dense_init(keys[-2], flat, cfg.fc_hidden)
    params["fc1"] = dense_init(keys[-1], cfg.fc_hidden, cfg.n_classes)
    return params, state


def spiking_cnn_apply(params: Params, state: State, x: jax.Array,
                      cfg: SpikingCNNConfig, *, train: bool
                      ) -> tuple[jax.Array, State, dict[str, jax.Array]]:
    """Forward over time.

    x: [B, T, H, W, C]  (C = in_channels, or channels[0] counts if
    first_layer_external). Returns (logits [B, n_classes], new_state,
    aux) where aux["spikes/<layer>"] holds total spike counts (for the
    energy/bandwidth model) and aux["synops/<layer>"] synaptic-operation
    counts.
    """
    B, T = x.shape[0], x.shape[1]
    aux: dict[str, jax.Array] = {}
    new_state: State = {}
    # [B,T,...] → [T,B,...] so scans run over axis 0
    h = jnp.moveaxis(x, 1, 0)
    start = 1 if cfg.first_layer_external else 0
    for i in range(start, cfg.n_conv):
        stride = cfg.first_stride if i == 0 else 1
        tb = h.reshape((T * B,) + h.shape[2:])
        y = conv_apply(params[f"conv{i}"], tb, stride=stride)
        # synops: each output element consumed k*k*c_in inputs; count sparsity
        fan_in = cfg.kernel_size * cfg.kernel_size * h.shape[-1]
        aux[f"synops/conv{i}"] = jax.lax.stop_gradient(
            jnp.sum(h != 0) * fan_in * (cfg.channels[i] / h.shape[-1]))
        y, bns = bn_apply(params[f"bn{i}"], state[f"bn{i}"], y, train=train)
        new_state[f"bn{i}"] = bns
        y = y.reshape((T, B) + y.shape[1:])
        s = lif_over_time(y, cfg.lif)
        tb = s.reshape((T * B,) + s.shape[2:])
        tb = max_pool(tb)
        h = tb.reshape((T, B) + tb.shape[1:])
        aux[f"spikes/conv{i}"] = jax.lax.stop_gradient(jnp.sum(s))
    # FC head
    flat = h.reshape((T, B, -1))
    z = dense_apply(params["fc0"], flat)
    aux["synops/fc0"] = jax.lax.stop_gradient(
        jnp.sum(flat != 0).astype(jnp.float32) * params["fc0"]["w"].shape[1])
    s = lif_over_time(z, cfg.lif)
    aux["spikes/fc0"] = jax.lax.stop_gradient(jnp.sum(s))
    logits_t = dense_apply(params["fc1"], s)
    aux["synops/fc1"] = jax.lax.stop_gradient(
        jnp.sum(s != 0).astype(jnp.float32) * params["fc1"]["w"].shape[1])
    logits = jnp.mean(logits_t, axis=0)   # rate decoding
    return logits, new_state, aux


# ---------------------------------------------------------------------------
# streaming (one-coarse-frame-at-a-time) evaluation
# ---------------------------------------------------------------------------

def _stream_shapes(cfg: SpikingCNNConfig) -> tuple[dict, int]:
    """Per-layer LIF membrane shapes (pre-pool conv outputs + FC hidden)
    and the layer the stream starts at — mirrors spiking_cnn_init's shape
    walk so streaming state lines up with the trained params."""
    h, w = cfg.input_hw
    c_in = cfg.in_channels
    start = 0
    if cfg.first_layer_external:
        c_in = cfg.channels[0]
        h //= (2 * cfg.first_stride)
        w //= (2 * cfg.first_stride)
        start = 1
    shapes = {}
    for i in range(start, cfg.n_conv):
        stride = cfg.first_stride if i == 0 else 1
        h_c, w_c = h // stride, w // stride       # conv output (SAME pad)
        shapes[f"lif{i}"] = (h_c, w_c, cfg.channels[i])
        h, w = h_c // 2, w_c // 2                 # 2x pool
        c_in = cfg.channels[i]
    shapes["lif_fc0"] = (cfg.fc_hidden,)
    return shapes, start


def spiking_cnn_stream_init(cfg: SpikingCNNConfig, batch: int) -> State:
    """Zero LIF membranes for step-wise (online) evaluation — one state
    tree per serving lane batch. ``lif_over_time`` starts every scan from
    v=0, so a fresh stream state reproduces the batched forward exactly."""
    shapes, _ = _stream_shapes(cfg)
    return {k: jnp.zeros((batch,) + s) for k, s in shapes.items()}


def spiking_cnn_stream_step(params: Params, state: State, mem: State,
                            x_t: jax.Array, cfg: SpikingCNNConfig
                            ) -> tuple[jax.Array, State]:
    """One coarse timestep of the backbone with explicit LIF state.

    ``x_t`` is a single coarse frame [B, H, W, C] (what
    ``spiking_cnn_apply`` sees at one index of its time axis); ``mem``
    carries every layer's membrane between calls. Stepping T frames
    through this function and averaging the returned per-step logits is
    IDENTICAL to ``spiking_cnn_apply(..., train=False)`` on the stacked
    [B, T, ...] tensor (conv/BN are stateless at eval, LIF scans are
    causal) — the parity the online serving engine (repro.stream) relies
    on and tests/test_streaming.py pins.
    """
    _, start = _stream_shapes(cfg)
    new_mem: State = {}
    h = x_t
    for i in range(start, cfg.n_conv):
        stride = cfg.first_stride if i == 0 else 1
        y = conv_apply(params[f"conv{i}"], h, stride=stride)
        y, _ = bn_apply(params[f"bn{i}"], state[f"bn{i}"], y, train=False)
        v, s = lif_step(mem[f"lif{i}"], y, cfg.lif)
        new_mem[f"lif{i}"] = v
        h = max_pool(s)
    z = dense_apply(params["fc0"], h.reshape((h.shape[0], -1)))
    v, s = lif_step(mem["lif_fc0"], z, cfg.lif)
    new_mem["lif_fc0"] = v
    logits_t = dense_apply(params["fc1"], s)
    return logits_t, new_mem


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
