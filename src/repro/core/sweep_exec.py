"""Mesh-sharded execution of stacked embarrassingly-parallel axes.

Two batched axes in this repo are embarrassingly parallel — every element
runs the same program with different numerics — and both shard the same
way, so one executor abstraction serves both:

  * the sweep engine's stacked ``[n_cfg]`` circuit-variant axis
    (:class:`SweepExecutor`, 1-D ``"cfg"`` mesh) — each device
    finetunes/evaluates ``n_cfg / n_devices`` variants, events and the
    shared layer-1 params are replicated;
  * the serving engine's ``[capacity]`` lane axis
    (``repro.stream.shard.LaneExecutor``, 1-D ``"lane"`` mesh) — each
    device folds/reads out ``capacity / n_devices`` serving lanes.

:class:`MeshExecutor` holds the shared machinery: the 1-D mesh over the
first ``devices`` local devices, ``shard_map`` wrapping with pytree-prefix
in/out specs, and leading-axis padding up to a device multiple (padded
lanes compute real-but-discarded work; callers read back only the first
``n`` lanes, so no element's result reads another element's values).

On CPU CI the mesh comes from forced host devices::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.launch.sweep --grid fast --devices 8

``devices=1`` (the default) is the exact pre-sharding path: no mesh, no
padding, plain ``jax.jit``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import cached_property
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

CFG_AXIS = "cfg"
# PartitionSpec shorthands for in/out spec trees: one stacked-variant spec,
# one replicated spec (pytree prefixes — a single spec covers a whole
# params/opt-state subtree).
P_CFG = PartitionSpec(CFG_AXIS)
P_REP = PartitionSpec()


@dataclass(frozen=True)
class MeshExecutor:
    """Execution policy for one stacked embarrassingly-parallel axis.

    ``devices=1`` → single-device (no shard_map, no padding). ``devices=n``
    → 1-D ``axis`` mesh over the first n local devices.
    """
    devices: int = 1
    axis: str = CFG_AXIS

    def __post_init__(self):
        if self.devices < 1:
            raise ValueError(f"devices must be >= 1, got {self.devices}")

    @property
    def is_sharded(self) -> bool:
        return self.devices > 1

    @property
    def p_axis(self) -> PartitionSpec:
        """Spec for leaves stacked on this executor's axis."""
        return PartitionSpec(self.axis)

    @property
    def p_rep(self) -> PartitionSpec:
        """Spec for replicated leaves."""
        return P_REP

    @cached_property
    def mesh(self) -> Mesh:
        avail = jax.devices()
        if self.devices > len(avail):
            raise ValueError(
                f"executor wants {self.devices} devices but only "
                f"{len(avail)} are visible; on CPU force host devices with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{self.devices}")
        return Mesh(np.asarray(avail[: self.devices]), (self.axis,))

    def padded_size(self, n_cfg: int) -> int:
        """Smallest multiple of the device count >= n_cfg."""
        return math.ceil(n_cfg / self.devices) * self.devices

    def pad_stacked(self, tree: Any, n_cfg: int) -> Any:
        """Pad every leaf's leading [n_cfg] axis to ``padded_size(n_cfg)``
        by repeating the last variant (real work, discarded on read-back)."""
        pad = self.padded_size(n_cfg) - n_cfg
        if pad == 0:
            return tree
        return jax.tree.map(
            lambda x: jnp.concatenate(
                [x, jnp.repeat(x[-1:], pad, axis=0)], axis=0), tree)

    def shard(self, fn, in_specs: Sequence, out_specs):
        """shard_map ``fn`` over the 1-D mesh (identity when devices=1).

        ``in_specs``/``out_specs`` are pytree prefixes of
        :attr:`p_axis` / :attr:`p_rep`. The body is already differentiated
        (the sweep engine's steps take grads inside), so no shard_map
        transpose is ever needed and replication checking is disabled.
        """
        if not self.is_sharded:
            return fn
        return jax.shard_map(fn, mesh=self.mesh, in_specs=tuple(in_specs),
                             out_specs=out_specs, check_vma=False)


@dataclass(frozen=True)
class SweepExecutor(MeshExecutor):
    """The sweep engine's executor: the stacked circuit-variant axis on
    the 1-D ``"cfg"`` mesh (the :class:`MeshExecutor` defaults)."""


def make_executor(devices: int | None) -> SweepExecutor:
    """CLI entry: ``devices=None`` → single-device executor.

    Validates the device count EAGERLY (builds the mesh up front) so a bad
    ``--devices`` fails before any compute — not after a paper-scale
    phase-1 pretrain has already run.
    """
    ex = SweepExecutor(devices=devices or 1)
    if ex.is_sharded:
        _ = ex.mesh
    return ex
