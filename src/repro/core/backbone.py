"""The one seam between the P²M in-pixel layer and the digital backbone.

A model's ``backbone`` config is one of two kinds, told apart by its
``kind``: the paper's spiking CNN (:class:`repro.core.snn.SpikingCNNConfig`,
``"cnn"``) or Spikformer (:class:`repro.core.spikformer.SpikformerConfig`,
``"spikformer"``). Everything above the backbone — the co-design model
(``codesign.model_init``/``model_apply``), the serving oracle
(``deploy.offline_forward``) and both serving step builders
(``stream/accumulator.py``) — calls these four functions and never a
kind's own, so a kind is added here and nowhere else.

Each kind keeps one contract: :func:`stream_step` over ``T`` coarse frames,
its logits averaged, equals :func:`apply` with ``train=False``.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

from repro.core import snn, spikformer
from repro.core.snn import LIFConfig, SpikingCNNConfig
from repro.core.spikformer import SpikformerConfig

BackboneConfig = SpikingCNNConfig | SpikformerConfig


class _Kind(NamedTuple):
    config: type
    init: Callable
    apply: Callable
    stream_init: Callable
    stream_step: Callable


KINDS = {
    "cnn": _Kind(SpikingCNNConfig, snn.spiking_cnn_init,
                 snn.spiking_cnn_apply, snn.spiking_cnn_stream_init,
                 snn.spiking_cnn_stream_step),
    "spikformer": _Kind(SpikformerConfig, spikformer.init, spikformer.apply,
                        spikformer.stream_init, spikformer.stream_step),
}


def _kind(cfg: BackboneConfig) -> _Kind:
    return KINDS[cfg.kind]


def init(key, cfg: BackboneConfig):
    """``(params, bn_state)`` of a fresh backbone."""
    return _kind(cfg).init(key, cfg)


def apply(params, state, x, cfg: BackboneConfig, *, train: bool):
    """Forward over ``x`` [B, T, ...] coarse frames → ``(logits, new
    state, aux)``."""
    return _kind(cfg).apply(params, state, x, cfg, train=train)


def stream_init(cfg: BackboneConfig, batch: int):
    """Zero membranes for ``batch`` serving lanes."""
    return _kind(cfg).stream_init(cfg, batch)


def stream_step(params, state, mem, x_t, cfg: BackboneConfig):
    """One coarse frame ``x_t`` [B, ...] → ``(logits_t, new membranes)``."""
    return _kind(cfg).stream_step(params, state, mem, x_t, cfg)


def config_from_dict(d: dict) -> BackboneConfig:
    """A backbone config from its JSON dict (``dataclasses.asdict`` of
    either kind). A dict without ``kind`` is the spiking CNN, as every
    config and checkpoint written before the key existed."""
    d = dict(d)
    kind = d.pop("kind", "cnn")
    if kind not in KINDS:
        raise ValueError(f"unknown backbone kind {kind!r} "
                         f"(have {sorted(KINDS)})")
    lif = LIFConfig(**d.pop("lif"))
    d["input_hw"] = tuple(d["input_hw"])
    if kind == "cnn":
        d["channels"] = tuple(d["channels"])
    return KINDS[kind].config(**d, lif=lif)
