"""Mesh-sharding of the serving engine's lane axis.

The ``[capacity, ...]`` lane axis of the streaming fold/readout programs
(repro.stream.accumulator) is embarrassingly parallel — every lane
integrates its own stream's leak ODE with the same deployed weights — so
it shards exactly the way the sweep engine's stacked variant axis does
(core/sweep_exec.py): a 1-D device mesh, ``shard_map`` over the leading
axis, and leading-axis padding up to a device multiple.

:class:`LaneExecutor` is the :class:`~repro.core.sweep_exec.MeshExecutor`
instantiation for the 1-D ``"lane"`` mesh. ``devices=1`` is the exact
unsharded path (no mesh, no padding, plain ``jax.jit``); ``devices=n``
pads the lane capacity to a multiple of n and runs each device's
``capacity / n`` lanes under ``shard_map``. Padded lanes are never
admitted (their ``active`` mask stays False, and the per-shard
:class:`~repro.serve.slots.ShardedSlots` bookkeeping never places a
stream on them), so sharded serving gives the predictions, ledgers and
spike counts of ``devices=1`` exactly and its logits to a few ulp — a
device's smaller lane batch may get another reduction order
(tests/test_stream_shard.py pins it).

On CPU CI the mesh comes from forced host devices, mirroring the sweep::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.launch.stream --smoke --devices 8
"""
from __future__ import annotations

from dataclasses import dataclass

from jax.sharding import PartitionSpec

from repro.core.sweep_exec import MeshExecutor, P_REP
from repro.serve.slots import ShardedSlots

LANE_AXIS = "lane"
# pytree-prefix specs for the serving steps: state/frames/masks are all
# stacked on the leading lane axis; closed-over weights are replicated.
P_LANE = PartitionSpec(LANE_AXIS)

__all__ = ["LANE_AXIS", "P_LANE", "P_REP", "LaneExecutor",
           "make_lane_executor", "ShardedSlots"]


@dataclass(frozen=True)
class LaneExecutor(MeshExecutor):
    """The serving engine's executor: the lane axis on a 1-D ``"lane"``
    mesh. All mesh/padding/spec machinery is inherited from
    :class:`~repro.core.sweep_exec.MeshExecutor`."""
    axis: str = LANE_AXIS


def make_lane_executor(devices: int | None) -> LaneExecutor:
    """CLI entry: ``devices=None`` → single-device executor.

    Validates the device count EAGERLY (builds the mesh up front) so a
    bad ``--devices`` fails before any stream is opened — the same
    contract as ``sweep_exec.make_executor``.
    """
    ex = LaneExecutor(devices=devices or 1)
    if ex.is_sharded:
        _ = ex.mesh
    return ex
