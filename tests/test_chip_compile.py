"""Real-size compiles of the serving path's programs for a described TPU
v5e, with no chip attached: the Pallas kernels at paper width (128×128
sensor, F=16 in-pixel filters), the jitted serving fold step with the
fused kernel, and the readout step with Spikformer-2-256's backbone at its
published widths. The TPU compiler refuses here what it would refuse on the
chip — tiles off the (8, 128) grid, VMEM overruns, programs that do not
fit — at no chip time.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
from __future__ import annotations

import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

LANES = 8                 # serving lanes in one kernel launch
HW = 128                  # paper sensor
F = 16                    # in-pixel filters
K = 3 * 3 * 2             # layer-1 receptive field (3×3, ON/OFF)
S = 4                     # fine sub-slots per T_INTG window


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    cache_was = jax.config.jax_enable_compilation_cache
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


def test_stream_fold_deposit(one_chip):
    from repro.kernels.stream_fold.stream_fold import stream_fold_pallas

    n = LANES * HW * HW
    _compile(lambda x0, dep, a: stream_fold_pallas(x0, dep, a,
                                                   interpret=False),
             _sds((n, F), one_chip), _sds((S, n, F), one_chip),
             _sds((F,), one_chip))


def test_stream_fold_mac(one_chip):
    from repro.kernels.stream_fold.stream_fold import stream_fold_mac_pallas

    n = LANES * HW * HW
    _compile(lambda x0, p, w, a: stream_fold_mac_pallas(
        x0, p, w, a, dv_unit=0.01, interpret=False),
        _sds((n, F), one_chip), _sds((S, n, K), one_chip),
        _sds((K, F), one_chip), _sds((F,), one_chip))


def test_p2m_conv_multi_three_configs(one_chip):
    from repro.kernels.p2m_conv.p2m_conv import p2m_conv_multi_pallas

    n_cfg, t_out = 3, 2
    row = _sds((n_cfg, F), one_chip)
    vec = _sds((F,), one_chip)
    _compile(lambda p, w, vi, de, th, pg, po: p2m_conv_multi_pallas(
        p, w, vi, de, th, pg, po, dv_unit=0.01, half_swing=0.4,
        v_lo=-0.4, v_hi=0.4, interpret=False),
        _sds((t_out, S, HW * HW, K), one_chip), _sds((K, F), one_chip),
        row, row, row, vec, vec)


def test_lif(one_chip):
    from repro.kernels.lif.lif import lif_pallas

    # backbone layer-2 membranes of 8 lanes: 64×64 sites × 16 channels
    _compile(lambda x: lif_pallas(x, interpret=False),
             _sds((8, LANES * 64 * 64 * F), one_chip))


@pytest.mark.parametrize("slots", [1, S], ids=["chunk", "window"])
def test_serving_fold_step_with_kernel(one_chip, monkeypatch, slots):
    """The engine's jitted ``use_kernel=True`` fold at the paper config
    holds the Mosaic kernel, on one sub-slot and on the whole window of
    ``n_sub`` sub-slots that serving dispatches. This process's backend
    is the CPU, where the kernel would interpret, so the test pins
    compiled mode."""
    from repro.configs import p2m_dvs
    from repro.kernels.stream_fold import stream_fold
    from repro.stream import accumulator, deploy

    assert p2m_dvs.CONFIG.p2m.n_sub == S
    monkeypatch.setattr(stream_fold, "resolve_interpret", lambda _: False)
    dep = deploy.fresh_deployment(p2m_dvs.CONFIG, seed=0)
    capacity = 16
    fns = accumulator.make_stream_fns(dep, capacity=capacity,
                                      use_kernel=True)
    state = jax.eval_shape(fns.init_state)
    state = jax.tree.map(lambda s: _sds(s.shape, one_chip, s.dtype), state)
    compiled = fns.fold.lower(
        state, _sds((capacity, slots, HW, HW, 2), one_chip),
        _sds((capacity,), one_chip, jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_spikformer_readout_step(one_chip):
    """The engine's jitted readout at Spikformer-2-256's published widths
    (8 lanes of 5.6 MiB of state, the backbone step inside) compiles for
    the chip, and its ops carry the backbone's named scopes, which the
    profiler's op metadata shows."""
    from repro.configs import p2m_spikformer
    from repro.stream import accumulator, deploy

    dep = deploy.fresh_deployment(p2m_spikformer.CONFIG, seed=0)
    capacity = 8
    fns = accumulator.make_stream_fns(dep, capacity=capacity)
    state = jax.eval_shape(fns.init_state)
    state = jax.tree.map(lambda s: _sds(s.shape, one_chip, s.dtype), state)
    mask = _sds((capacity,), one_chip, jnp.bool_)
    compiled = fns.readout.lower(state, mask, mask).compile()
    text = compiled.as_text()
    for scope in ("p2m.sps", "p2m.encoder", "p2m.head"):
        assert scope in text
    assert compiled.memory_analysis().argument_size_in_bytes < 2 ** 30
