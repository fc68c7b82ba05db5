"""CPU rehearsal of ``chip_smoke.py``: its phase functions at a tiny width
(kernels interpreted), its refusal to run without a TPU, and the
compile-cache placement it shares with the CLIs."""
from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro import utils  # noqa: E402
from repro.core.codesign import P2MModelConfig  # noqa: E402
from repro.core.leakage import CircuitConfig, LeakageConfig  # noqa: E402
from repro.core.p2m_layer import P2MConfig  # noqa: E402
from repro.core.snn import SpikingCNNConfig  # noqa: E402
from repro.data import sources  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
HW = 16


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()


def _tiny_model() -> P2MModelConfig:
    """The paper network's structure at CPU scale: T_INTG 100 ms × 2
    sub-slots, two 200 ms coarse windows per 400 ms stream."""
    return P2MModelConfig(
        p2m=P2MConfig(out_channels=8, n_sub=2, t_intg_ms=100.0,
                      leak=LeakageConfig(circuit=CircuitConfig.NULLIFIED)),
        backbone=SpikingCNNConfig(channels=(8, 16), input_hw=(HW, HW),
                                  fc_hidden=32, n_classes=11,
                                  first_layer_external=True),
        coarse_window_ms=200.0)


def _source():
    return sources.resolve_dataset("synthetic-gesture", hw=HW,
                                   duration_ms=400.0)


@pytest.fixture(scope="module")
def dep(tmp_path_factory):
    return smoke.phase_deploy(_tiny_model(), seed=0,
                              directory=tmp_path_factory.mktemp("ckpt"))


def test_deploy_phase_roundtrips_checkpoint(dep, tmp_path):
    from repro.stream import deploy

    again = smoke.phase_deploy(_tiny_model(), seed=0, directory=tmp_path)
    assert again.model_cfg == dep.model_cfg
    for a, b in zip(jax.tree.leaves(again.params),
                    jax.tree.leaves(deploy.fresh_deployment(
                        _tiny_model(), seed=0).params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serve_phase_matches_offline(dep):
    lines: list[str] = []
    out = smoke.phase_serve(dep, _source(), n_streams=4, capacity=2, seed=0,
                            require_kernel=False, log=lines.append)
    for tag in ("xla", "kernel"):
        res = out[tag]
        assert res["preds_agree"] == res["n_streams"] == 4
        assert res["max_dlogit"] <= smoke.LOGIT_ATOL
        assert res["layer1_spikes"] == res["ref_layer1_spikes"] > 0
        assert res["spike_flips"] == 0
        assert res["report"].total_readouts == 4 * 4
    assert out["fold_max_dx"] < 1e-6
    assert any(line.startswith("[serve:kernel]") for line in lines)


def test_compare_rejects_a_wrong_answer(dep):
    from repro.stream.engine import StreamEngine

    engine = StreamEngine(dep, capacity=2)
    src = _source()
    ref = smoke.offline_reference(dep, src, engine, n_streams=2, seed=0)
    report = engine.serve(src, 2, seed=0)
    ok = smoke.compare(report, ref, atol=smoke.LOGIT_ATOL)
    assert ok["spike_flips"] == 0 and ok["streams_flipped"] == 0
    np.testing.assert_array_equal(
        smoke.as_reference(report)["layer1_spikes"], ref["layer1_spikes"])
    with pytest.raises(smoke.SmokeFailure, match="Δlogit"):
        smoke.compare(report, {**ref, "logits": ref["logits"] + 1e-3},
                      atol=smoke.LOGIT_ATOL)
    with pytest.raises(smoke.SmokeFailure, match="spikes differ"):
        smoke.compare(report, {**ref, "layer1_spikes":
                               ref["layer1_spikes"] + [1.0, 0.0]},
                      atol=smoke.LOGIT_ATOL)
    with pytest.raises(smoke.SmokeFailure, match="labels"):
        smoke.compare(report, {**ref, "labels": ref["labels"][::-1]},
                      atol=smoke.LOGIT_ATOL)


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, {tests!r})
    import test_chip_smoke as t
    t.run_mesh_phase({ckpt!r})
    print("MESH_PASS")
""")


def run_mesh_phase(ckpt: str) -> None:
    dep = smoke.phase_deploy(_tiny_model(), seed=0, directory=Path(ckpt))
    lines: list[str] = []
    out = smoke.phase_mesh(dep, _source(), n_streams=6, capacity=4,
                           devices=4, seed=0, log=lines.append)
    assert out["preds_agree"] == 6
    placed = [line for line in lines if line.startswith("[mesh:4] lanes")]
    assert len(placed) == 4 and len({line.split(" on ")[1]
                                     for line in placed}) == 4


def test_mesh_phase_on_four_devices(tmp_path):
    """The ``--chips 4`` phase on 4 host devices: in-process where this
    process has them, else in a child with forced host devices."""
    if jax.device_count() >= 4:
        run_mesh_phase(str(tmp_path))
        return
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    script = _MESH_SCRIPT.format(tests=str(REPO / "tests"),
                                 ckpt=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "MESH_PASS" in proc.stdout


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "needs a TPU" in err


def test_script_alone_refuses(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore_config(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_fixed_in_checkout_path_when_unset(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = utils.init_compile_cache()
        assert first == utils.init_compile_cache()
        assert Path(first) == REPO / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == first
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()

    def test_env_variable_left_alone(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert utils.init_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
