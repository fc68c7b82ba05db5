"""Runtime substrate: checkpointing, fault-tolerance monitors, elastic
planning, gradient compression, token pipeline, training loop restart."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest


# ---------------------------------------------------------------------------
# checkpoint store
# ---------------------------------------------------------------------------


class TestCheckpoint:
    def _tree(self, scale=1.0):
        return {"a": {"w": jnp.full((4, 4), scale), "b": jnp.arange(3.0)},
                "step_arr": jnp.ones((2,)) * scale}

    def test_roundtrip(self, tmp_path):
        from repro.checkpoint import load_checkpoint, save_checkpoint
        t = self._tree(2.0)
        save_checkpoint(tmp_path, 7, t, extra={"step": 7})
        got, extra = load_checkpoint(tmp_path)
        assert extra["step"] == 7
        np.testing.assert_array_equal(np.asarray(got["a"]["w"]),
                                      np.asarray(t["a"]["w"]))

    def test_atomic_commit_ignores_uncommitted(self, tmp_path):
        from repro.checkpoint import latest_step, save_checkpoint
        save_checkpoint(tmp_path, 5, self._tree())
        # simulate a crashed save: directory without COMMIT
        bad = tmp_path / "step_000000009"
        bad.mkdir()
        (bad / "index.json").write_text("{}")
        assert latest_step(tmp_path) == 5

    def test_retention_gc(self, tmp_path):
        from repro.checkpoint import CheckpointManager, latest_step
        mgr = CheckpointManager(tmp_path, every_steps=1, keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, self._tree(s))
        kept = sorted(p.name for p in tmp_path.iterdir())
        assert kept == ["step_000000003", "step_000000004"]
        assert latest_step(tmp_path) == 4

    def test_async_save(self, tmp_path):
        from repro.checkpoint import CheckpointManager
        mgr = CheckpointManager(tmp_path, every_steps=1, keep=5)
        mgr.save(1, self._tree(1.0), blocking=False)
        mgr.wait()
        got, _ = mgr.restore()
        np.testing.assert_array_equal(np.asarray(got["step_arr"]), [1.0, 1.0])

    def test_restore_with_shardings(self, tmp_path):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh()
        mgr = CheckpointManager(tmp_path)
        mgr.save(1, self._tree(3.0))
        sh = NamedSharding(mesh, P())
        shardings = jax.tree.map(lambda _: sh, self._tree())
        got, _ = mgr.restore(shardings=shardings)
        assert got["a"]["w"].sharding == sh

    def test_missing_returns_none(self, tmp_path):
        from repro.checkpoint import CheckpointManager
        assert CheckpointManager(tmp_path / "nope").restore() is None


# ---------------------------------------------------------------------------
# fault-tolerance monitors
# ---------------------------------------------------------------------------


class TestStragglerMonitor:
    def test_flags_outlier_after_warmup(self):
        from repro.ft import StragglerMonitor
        m = StragglerMonitor(warmup_steps=4, k_sigma=4.0)
        flagged = []
        for i in range(30):
            dt = 1.0 + 0.01 * ((i * 2654435761) % 7 - 3) / 3.0
            flagged.append(m.observe(i, dt))
        assert not any(flagged)
        assert m.observe(30, 3.0)         # 3x the mean → straggler
        # baseline not poisoned by the outlier
        assert abs(m.mean_s - 1.0) < 0.05

    def test_consecutive_flags(self):
        from repro.ft import StragglerMonitor
        m = StragglerMonitor(warmup_steps=2, k_sigma=3.0)
        for i in range(10):
            m.observe(i, 1.0)
        for i in range(10, 13):
            m.observe(i, 5.0)
        assert m.consecutive_flags(3)


class TestHeartbeat:
    def test_dead_detection_simulated_clock(self):
        from repro.ft import HeartbeatTracker
        now = [0.0]
        hb = HeartbeatTracker(n_workers=4, timeout_s=10.0, clock=lambda: now[0])
        now[0] = 5.0
        hb.beat(0); hb.beat(1); hb.beat(2)
        now[0] = 12.0
        assert hb.dead() == [3]
        assert hb.alive() == [0, 1, 2]


class TestPreemptionGuard:
    def test_trigger_and_poll(self):
        from repro.ft import PreemptionGuard
        with PreemptionGuard() as g:
            assert not g.preempted
            g.trigger()
            assert g.preempted


class TestElasticPlan:
    def test_preserves_model_axis(self):
        from repro.ft import plan_remesh
        plan = plan_remesh(480, tp=16, global_batch=256)
        assert plan.mesh_shape == (30, 16)
        # 256 % 30 != 0 → grad accumulation restores the global batch
        assert plan.grad_accum > 1

    def test_no_accum_when_batch_divides(self):
        from repro.ft import plan_remesh
        plan = plan_remesh(256, tp=16, global_batch=256)
        assert plan.mesh_shape == (16, 16)
        assert plan.grad_accum == 1

    def test_degrades_model_axis_when_needed(self):
        from repro.ft import plan_remesh
        plan = plan_remesh(8, tp=16, global_batch=64)
        assert plan.mesh_shape[1] <= 8
        assert plan.chips <= 8

    def test_full_pod(self):
        from repro.ft import plan_remesh
        plan = plan_remesh(512, tp=16, global_batch=256)
        assert plan.mesh_shape == (32, 16)
        assert plan.grad_accum == 1
        assert plan.dropped_chips == 0


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------


class TestCompression:
    def test_int8_roundtrip_error_bound(self):
        from repro.distributed import compress_int8, decompress_int8
        g = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3.0
        q, scale, pad = compress_int8(g, block=128)
        back = decompress_int8(q, scale, pad, g.shape)
        # max error ≤ scale/2 per block
        err = jnp.abs(back - g)
        bound = jnp.repeat(scale / 2, 128)[:1000] + 1e-9
        assert bool(jnp.all(err <= bound))

    def test_compression_ratio(self):
        from repro.distributed import compress_int8
        g = jnp.ones((4096,))
        q, scale, pad = compress_int8(g, block=256)
        raw = 4096 * 4
        comp = q.size * 1 + scale.size * 4
        assert raw / comp > 3.5

    def test_error_feedback_unbiased_over_steps(self):
        """With EF, the *cumulative* applied update converges to the
        cumulative true gradient (residual stays bounded)."""
        from repro.distributed import compress_int8, decompress_int8
        key = jax.random.PRNGKey(1)
        ef = jnp.zeros((512,))
        total_true = jnp.zeros((512,))
        total_applied = jnp.zeros((512,))
        for i in range(20):
            g = jax.random.normal(jax.random.fold_in(key, i), (512,))
            total_true += g
            gq, scale, pad = compress_int8(g + ef, block=128)
            applied = decompress_int8(gq, scale, pad, g.shape)
            ef = (g + ef) - applied
            total_applied += applied
        # residual is one quantization step, not 20 accumulated ones
        drift = float(jnp.max(jnp.abs(total_true - total_applied)))
        assert drift < 0.05

    def test_tree_allreduce_single_device(self):
        """pmean over a 1-member axis is identity → compressed allreduce
        reduces to quantize/dequantize + EF bookkeeping."""
        from repro.distributed import (CompressionState,
                                       init_error_feedback)
        from repro.distributed.compression import tree_compressed_allreduce
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (64,))}
        state = init_error_feedback(grads)

        def f(g, res):
            out, new_state = tree_compressed_allreduce(
                g, CompressionState(residual=res), "data")
            return out, new_state.residual

        fm = jax.shard_map(
            f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False)   # all_gather-based reduce defeats rep inference
        out, res = fm(grads, state.residual)
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   np.asarray(grads["w"]), atol=0.05)
        # residual + applied == original
        np.testing.assert_allclose(
            np.asarray(out["w"] + res["w"]), np.asarray(grads["w"]),
            rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# token pipeline
# ---------------------------------------------------------------------------


class TestTokens:
    def _cfg(self):
        from repro.data.tokens import TokenStreamConfig
        return TokenStreamConfig(vocab_size=128, seq_len=32, global_batch=4)

    def test_deterministic_in_step(self):
        from repro.data.tokens import sample_batch
        cfg = self._cfg()
        b1 = sample_batch(cfg, jnp.asarray(5))
        b2 = sample_batch(cfg, jnp.asarray(5))
        np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                      np.asarray(b2["tokens"]))
        b3 = sample_batch(cfg, jnp.asarray(6))
        assert not np.array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b3["tokens"]))

    def test_labels_are_shifted_tokens(self):
        from repro.data.tokens import sample_batch
        b = sample_batch(self._cfg(), jnp.asarray(0))
        np.testing.assert_array_equal(np.asarray(b["labels"][:, :-1]),
                                      np.asarray(b["tokens"][:, 1:]))

    def test_seek_skip_ahead(self):
        from repro.data.tokens import TokenLoader
        cfg = self._cfg()
        l1 = TokenLoader(cfg)
        for _ in range(3):
            next(l1)
        s1, b1 = next(l1)
        l2 = TokenLoader(cfg)
        l2.seek(3)
        s2, b2 = next(l2)
        assert s1 == s2 == 3
        np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                      np.asarray(b2["tokens"]))

    def test_structure_learnable(self):
        """Markov stream has bigram structure: H(next|prev) < H(next) —
        a next-token predictor can beat the unigram baseline."""
        from repro.data.tokens import TokenStreamConfig, sample_batch
        cfg = TokenStreamConfig(vocab_size=16, seq_len=512, global_batch=8,
                                markov_temp=0.4, n_states=8)
        b = sample_batch(cfg, jnp.asarray(0))
        toks = np.asarray(b["tokens"])
        uni = np.bincount(toks.reshape(-1), minlength=16).astype(float) + 1e-9
        p_uni = uni / uni.sum()
        h_uni = -(p_uni * np.log2(p_uni)).sum()
        big = np.zeros((16, 16)) + 1e-9
        for row in toks:
            np.add.at(big, (row[:-1], row[1:]), 1.0)
        p_j = big / big.sum()
        p_prev = p_j.sum(1, keepdims=True)
        h_cond = -(p_j * np.log2(p_j / p_prev)).sum()
        assert h_cond < h_uni - 0.05   # ≥0.05 bits of usable structure

    def test_host_slice(self):
        from repro.data.tokens import host_slice, sample_batch
        b = sample_batch(self._cfg(), jnp.asarray(0))
        s0 = host_slice(b, 0, 2)
        s1 = host_slice(b, 1, 2)
        assert s0["tokens"].shape[0] == 2
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(s0["tokens"]), np.asarray(s1["tokens"])]),
            np.asarray(b["tokens"]))


# ---------------------------------------------------------------------------
# training loop restart (integration)
# ---------------------------------------------------------------------------


class TestLoopRestart:
    def test_restart_replays_identically(self, tmp_path):
        from repro.configs import get_config, smoke_variant
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_host_mesh
        from repro.train.loop import LoopConfig, run

        cfg = smoke_variant(get_config("internlm2-1.8b"))
        shape = ShapeConfig("t", "train", 32, 2)
        mesh = make_host_mesh()
        lp = LoopConfig(total_steps=5, ckpt_every=3, log_every=100,
                        ckpt_dir=str(tmp_path), ckpt_async=False)
        logs = []
        r1 = run(cfg, shape, mesh, lp, log=logs.append)
        assert r1.final_step == 5
        # a "crashed" rerun resumes at 3 and reproduces steps 3..4 exactly
        r2 = run(cfg, shape, mesh, lp, log=logs.append)
        assert r2.restored_from == 3
        np.testing.assert_allclose(r2.losses, r1.losses[3:], rtol=1e-5)
