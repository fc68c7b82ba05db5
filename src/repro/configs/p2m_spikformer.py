"""Spikformer-2-256 (Zhou et al., ICLR 2023, arXiv:2209.15425), the
published DVS128-Gesture spiking transformer, behind the P²M in-pixel
layer: the in-pixel layer is SPS stage 0 (3×3, 2 → 32), the backbone the
rest (SPS stages 1–3 at 64/128/256, the relative-position conv, two
encoder blocks of D = 256 with 16 heads and an MLP of 1024, and an
11-class head).

One backbone step is one 100 ms coarse window of pooled layer-1 spike
counts, so a 1.6 s stream takes the published T = 16 steps. The analog
MAC, the leak circuit and T_INTG are those of ``p2m_dvs.CONFIG``.
"""
from dataclasses import replace

from repro.configs.p2m_dvs import CONFIG as CNN_CONFIG
from repro.core.codesign import P2MModelConfig
from repro.core.spikformer import SpikformerConfig
from repro.data.events import EventStreamConfig

CONFIG = P2MModelConfig(
    p2m=replace(CNN_CONFIG.p2m, out_channels=32),
    backbone=SpikformerConfig(embed_dim=256, depth=2, heads=16, mlp_ratio=4,
                              input_hw=(128, 128), n_classes=11),
    coarse_window_ms=100.0,
)

DATA = EventStreamConfig(name="gesture", height=128, width=128, n_classes=11,
                         duration_ms=1600.0)


def reduced(hw: int = 64, embed_dim: int = 32, heads: int = 4
            ) -> tuple[P2MModelConfig, EventStreamConfig]:
    """CPU-scale variant for tests: ``hw``×``hw`` sensor, ``embed_dim``
    wide (``(hw/16)²`` tokens)."""
    cfg = replace(
        CONFIG,
        p2m=replace(CONFIG.p2m, out_channels=embed_dim // 8),
        backbone=replace(CONFIG.backbone, embed_dim=embed_dim, heads=heads,
                         input_hw=(hw, hw)))
    return cfg, replace(DATA, height=hw, width=hw)
