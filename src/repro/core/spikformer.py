"""Spikformer backbone (Zhou et al., ICLR 2023, arXiv:2209.15425) behind the
P²M in-pixel layer: the published DVS128-Gesture model, Spikformer-2-256.

The in-pixel layer is the Spiking Patch Splitting (SPS) stem's stage 0
(conv3×3, 2 → D/8, its BN + LIF the analog MAC + comparator, its pool the
engine's 2×2 comparator pool), so this module starts at SPS stage 1 and
consumes the pooled layer-1 spike counts of one coarse window per step:

* SPS stages 1–3: conv3×3 → BN → LIF → MaxPool(3, stride 2, pad 1), widths
  D/4, D/2, D; a ``(H/2)×(W/2)`` input leaves ``(H/16)×(W/16)`` tokens;
* relative-position conv: ``X₀ = x + LIF(BN(conv3×3(x)))``;
* ``depth`` encoder blocks, ``X′ = X + SSA(X)``, ``X″ = X′ + MLP(X′)`` (sums
  of spike counts), with spiking self-attention
  ``Q, K, V = LIF(BN(X·W))``, per head
  ``SSA′ = LIF_θ=attn_v_threshold(Q·(Kᵀ·V)·attn_scale)`` (no softmax, so
  ``Kᵀ·V`` is taken first, as the published code does) and output
  ``LIF(BN(SSA′·W_o))``; ``MLP = LIF(BN(LIF(BN(X·W₁))·W₂))``, W₁ of width
  D → mlp_ratio·D;
* head: the mean over tokens, then a linear layer to ``n_classes`` every
  step; the logits are averaged over steps (rate decoding).

LIF is :func:`repro.core.snn.lif_step` (τ, θ, hard reset to 0 with
``soft_reset=False``). Projections carry no bias: the BN shift that
follows takes its place, the same function at inference.

:func:`apply` runs a ``[B, T, ...]`` stack layer by layer (every conv and
projection over all ``T·B`` frames at once, each LIF scanned over time);
:func:`stream_step` runs one coarse frame with the membranes carried in
``mem``. Both run the same layer sequence (:func:`_network`), which is
handed the LIF as a callback, so stepping ``T`` frames and averaging the
logits equals :func:`apply` (tests/test_spikformer.py pins it).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import snn
from repro.core.snn import LIFConfig

Params = dict
State = dict

KERNEL = 3


@dataclass(frozen=True)
class SpikformerConfig:
    """Spikformer-2-256 at its published widths. The in-pixel layer's
    width (``P2MConfig.out_channels``) is SPS stage 0's, ``embed_dim // 8``."""
    embed_dim: int = 256
    depth: int = 2
    heads: int = 16
    mlp_ratio: int = 4
    attn_scale: float = 0.125
    attn_v_threshold: float = 0.5
    lif: LIFConfig = field(default_factory=lambda: LIFConfig(
        tau=2.0, v_threshold=1.0, soft_reset=False))
    input_hw: tuple[int, int] = (128, 128)
    n_classes: int = 11
    kind: str = "spikformer"

    @property
    def in_channels(self) -> int:
        """Channels of the backbone's input: SPS stage 0's output."""
        return self.embed_dim // 8

    @property
    def sps_widths(self) -> tuple[int, int, int]:
        d = self.embed_dim
        return (d // 4, d // 2, d)

    @property
    def grid_hw(self) -> tuple[int, int]:
        """The backbone input grid: the sensor after the 2×2 pool."""
        return (self.input_hw[0] // 2, self.input_hw[1] // 2)

    def validate(self, p2m_out_channels: int, p2m_stride: int) -> None:
        """Refuse a P²M layer or sensor this stem cannot take."""
        if self.embed_dim % 8 or self.embed_dim % self.heads:
            raise ValueError(f"embed_dim={self.embed_dim} must divide by 8 "
                             f"and by heads={self.heads}")
        if p2m_out_channels != self.in_channels:
            raise ValueError(
                f"the in-pixel layer is SPS stage 0: out_channels must be "
                f"embed_dim // 8 = {self.in_channels}, got "
                f"{p2m_out_channels}")
        if p2m_stride != 1:
            raise ValueError(f"SPS stage 0 is a stride-1 conv, got "
                             f"stride={p2m_stride}")
        if any(n % 16 for n in self.input_hw):
            raise ValueError(f"input_hw={self.input_hw} must divide by 16 "
                             f"(the 2×2 pool and three stride-2 pools)")


def _membranes(cfg: SpikformerConfig) -> dict[str, tuple[int, ...]]:
    """Membrane shape of every LIF layer, in order."""
    h, w = cfg.grid_hw
    out = {}
    for i, c in enumerate(cfg.sps_widths, start=1):
        out[f"sps{i}"] = (h, w, c)
        h, w = h // 2, w // 2
    d, n = cfg.embed_dim, h * w
    out["rpe"] = (h, w, d)
    for b in range(cfg.depth):
        for name in ("q", "k", "v", "attn", "proj"):
            out[f"b{b}_{name}"] = (n, d)
        out[f"b{b}_mlp1"] = (n, cfg.mlp_ratio * d)
        out[f"b{b}_mlp2"] = (n, d)
    return out


def _pool(x: jax.Array) -> jax.Array:
    """MaxPool(3, stride 2, pad 1) over NHWC, padded with −inf."""
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))


def _conv(p: Params, x: jax.Array) -> jax.Array:
    return lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def init(key: jax.Array, cfg: SpikformerConfig) -> tuple[Params, State]:
    """He-normal conv and projection weights (no bias), BN at identity,
    a linear head with bias."""
    d, hid = cfg.embed_dim, cfg.mlp_ratio * cfg.embed_dim
    keys = iter(jax.random.split(key, 5 + 6 * cfg.depth))

    def he(shape, fan_in):
        return {"w": jax.random.normal(next(keys), shape)
                * math.sqrt(2.0 / fan_in)}

    params: Params = {}
    state: State = {}

    def bn(name, c):
        params[name], state[name] = snn.bn_init(c)

    c_in = cfg.in_channels
    for i, c in enumerate(cfg.sps_widths, start=1):
        params[f"sps{i}"] = he((KERNEL, KERNEL, c_in, c),
                               KERNEL * KERNEL * c_in)
        bn(f"sps{i}_bn", c)
        c_in = c
    params["rpe"] = he((KERNEL, KERNEL, d, d), KERNEL * KERNEL * d)
    bn("rpe_bn", d)
    for b in range(cfg.depth):
        for name, shape in (("q", (d, d)), ("k", (d, d)), ("v", (d, d)),
                            ("proj", (d, d)), ("mlp1", (d, hid)),
                            ("mlp2", (hid, d))):
            params[f"b{b}_{name}"] = he(shape, shape[0])
            bn(f"b{b}_{name}_bn", shape[1])
    params["head"] = snn.dense_init(next(keys), d, cfg.n_classes)
    return params, state


Lif = Callable[[str, jax.Array, LIFConfig], jax.Array]


def _network(params: Params, state: State, x: jax.Array,
             cfg: SpikformerConfig, lif: Lif, *, train: bool
             ) -> tuple[jax.Array, State]:
    """The layer sequence over a leading batch of frames ``x`` [N, h, w,
    C]: ``lif(name, y, lif_cfg)`` turns each LIF layer's input into its
    spikes. Returns per-frame logits [N, n_classes] and the BN state."""
    new_state: State = {}

    def bn(name, y):
        y, new_state[name] = snn.bn_apply(params[name], state[name], y,
                                          train=train)
        return y

    def linear_lif(name, y):
        return lif(name, bn(f"{name}_bn", y @ params[name]["w"]), cfg.lif)

    with jax.named_scope("p2m.sps"):
        h = x
        for i in range(1, len(cfg.sps_widths) + 1):
            s = lif(f"sps{i}", bn(f"sps{i}_bn", _conv(params[f"sps{i}"], h)),
                    cfg.lif)
            h = _pool(s)
        h = h + lif("rpe", bn("rpe_bn", _conv(params["rpe"], h)), cfg.lif)
    n, th, tw, d = h.shape
    heads, hd = cfg.heads, d // cfg.heads
    with jax.named_scope("p2m.encoder"):
        tok = h.reshape((n, th * tw, d))
        for b in range(cfg.depth):
            q, k, v = (linear_lif(f"b{b}_{m}", tok).reshape(
                (n, th * tw, heads, hd)) for m in ("q", "k", "v"))
            kv = jnp.einsum("bnhd,bnhe->bhde", k, v)
            a = jnp.einsum("bnhd,bhde->bnhe", q, kv).reshape(
                (n, th * tw, d)) * cfg.attn_scale
            a = lif(f"b{b}_attn", a,
                    replace(cfg.lif, v_threshold=cfg.attn_v_threshold))
            tok = tok + linear_lif(f"b{b}_proj", a)
            tok = tok + linear_lif(f"b{b}_mlp2",
                                   linear_lif(f"b{b}_mlp1", tok))
    with jax.named_scope("p2m.head"):
        logits = snn.dense_apply(params["head"], jnp.mean(tok, axis=1))
    return logits, new_state


def apply(params: Params, state: State, x: jax.Array, cfg: SpikformerConfig,
          *, train: bool) -> tuple[jax.Array, State, dict[str, jax.Array]]:
    """Forward over time. ``x``: [B, T, H/2, W/2, embed_dim // 8] coarse
    frames of pooled layer-1 spike counts. Returns (rate-decoded logits
    [B, n_classes], new BN state, aux) with ``aux["spikes/<layer>"]`` the
    spike total of every LIF layer."""
    B, T = x.shape[:2]
    aux: dict[str, jax.Array] = {}

    def lif(name, y, lif_cfg):
        s = snn.lif_over_time(y.reshape((T, B) + y.shape[1:]), lif_cfg)
        aux[f"spikes/{name}"] = lax.stop_gradient(jnp.sum(s))
        return s.reshape(y.shape)

    tb = jnp.moveaxis(x, 1, 0).reshape((T * B,) + x.shape[2:])
    logits_t, new_state = _network(params, state, tb, cfg, lif, train=train)
    logits = jnp.mean(logits_t.reshape((T, B, -1)), axis=0)
    return logits, new_state, aux


def stream_init(cfg: SpikformerConfig, batch: int) -> State:
    """Zero LIF membranes of every layer for ``batch`` lanes: SPS and RPE
    maps, and per encoder block one membrane per token and channel of Q,
    K, V, attention, projection and both MLP layers."""
    return {name: jnp.zeros((batch,) + shape)
            for name, shape in _membranes(cfg).items()}


def stream_step(params: Params, state: State, mem: State, x_t: jax.Array,
                cfg: SpikformerConfig) -> tuple[jax.Array, State]:
    """One coarse step: ``x_t`` [B, H/2, W/2, embed_dim // 8], ``mem`` the
    membranes :func:`stream_init` makes. Returns the step's logits and the
    new membranes. Stepping T frames and averaging the logits equals
    :func:`apply` with ``train=False`` (the contract of
    :func:`repro.core.snn.spiking_cnn_stream_step`)."""
    new_mem: State = {}

    def lif(name, y, lif_cfg):
        new_mem[name], s = snn.lif_step(mem[name], y, lif_cfg)
        return s

    logits_t, _ = _network(params, state, x_t, cfg, lif, train=False)
    return logits_t, new_mem
