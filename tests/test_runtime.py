"""Checkpointing, fault tolerance, elastic resharding, compression, data."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, plan_remesh
from repro.data import DataConfig, SyntheticPipeline
from repro.launch.mesh import auto_mesh
from repro.runtime import (HeartbeatMonitor, StragglerPolicy, WorkerFailure,
                           compressed_psum, dequantize_int8, fake_quant_grads,
                           quantize_int8, run_with_restarts)


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        ckpt = CheckpointManager(str(tmp_path))
        state = {"w": jnp.arange(12.0).reshape(3, 4),
                 "nested": {"b": jnp.ones((2,), jnp.bfloat16)}}
        ckpt.save(5, state)
        step, restored = ckpt.restore(state)
        assert step == 5
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(state["w"]))
        assert restored["nested"]["b"].dtype == jnp.bfloat16

    def test_async_and_gc(self, tmp_path):
        ckpt = CheckpointManager(str(tmp_path), keep=2)
        state = {"w": jnp.zeros((4,))}
        for s in (1, 2, 3, 4):
            ckpt.save_async(s, state)
        ckpt.wait()
        assert ckpt.steps() == [3, 4]

    def test_atomicity_no_partial_dirs(self, tmp_path):
        ckpt = CheckpointManager(str(tmp_path))
        ckpt.save(1, {"w": jnp.zeros((2,))})
        names = os.listdir(tmp_path)
        assert all(not n.startswith(".tmp") for n in names)


class TestFault:
    def test_heartbeat_detection(self):
        mon = HeartbeatMonitor(n_workers=3, timeout_s=10)
        mon.beat(0, now=100.0)
        mon.beat(1, now=100.0)
        mon.beat(2, now=95.0)
        assert mon.check(now=106.0) == [2]

    def test_restart_from_checkpoint(self, tmp_path):
        """Injected failure at step 7 -> driver resumes from step 5 ckpt."""
        ckpt = CheckpointManager(str(tmp_path))
        calls = {"fails": 0}

        def train_some(start, state):
            step = start
            while step < 10:
                state = {"w": state["w"] + 1}
                step += 1
                if step == 5:
                    ckpt.save(5, state)
                if step == 7 and calls["fails"] == 0:
                    calls["fails"] = 1
                    raise WorkerFailure(3, "injected ICI timeout")
            return step, state

        step, state = run_with_restarts(
            train_some, {"w": jnp.zeros(())}, ckpt, total_steps=10)
        assert step == 10
        # 5 increments to ckpt, restart at 5, +5 more
        assert float(state["w"]) == 10.0

    def test_too_many_failures_raises(self, tmp_path):
        ckpt = CheckpointManager(str(tmp_path))

        def always_fail(start, state):
            raise WorkerFailure(0, "dead")

        with pytest.raises(RuntimeError, match="restarts"):
            run_with_restarts(always_fail, {"w": jnp.zeros(())}, ckpt,
                              total_steps=1, max_restarts=2)

    def test_straggler_backup_plan(self):
        pol = StragglerPolicy(factor=2.0)
        for t in (1.0, 1.1, 0.9, 1.0, 1.05):
            pol.observe(t)
        plan = pol.plan_backup({0: 1.0, 1: 0.9, 2: 5.0, 3: 1.1})
        assert 2 in plan and plan[2] != 2


class TestElastic:
    def test_plan_remesh_smaller_mesh(self):
        from repro.configs import ARCHS, reduced_config
        from repro.launch.specs import params_sds
        cfg = reduced_config(ARCHS["llama3.2-3b"])
        p = params_sds(cfg)
        mesh = auto_mesh((1, 1), ("data", "model"))
        rep = plan_remesh(p, (2, 2), mesh)
        assert rep["n_devices"] == 1 and rep["leaves"] > 10

    def test_restore_onto_new_mesh(self, tmp_path):
        """Save (simulating mesh A), restore placed on mesh B shardings."""
        from repro.dist import sharding as shd
        ckpt = CheckpointManager(str(tmp_path))
        state = {"w": jnp.arange(32.0).reshape(4, 8)}
        ckpt.save(1, state)
        mesh = auto_mesh((1, 1), ("data", "model"))
        sh = shd.to_named(shd.param_specs(state, mesh), mesh)
        _, restored = ckpt.restore(state, shardings=sh)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(state["w"]))


class TestCompression:
    def test_quant_roundtrip_error(self, rng):
        x = jnp.asarray(rng.standard_normal(1000), jnp.float32)
        q, s = quantize_int8(x)
        err = np.abs(np.asarray(dequantize_int8(q, s) - x))
        assert err.max() <= float(s) * 0.51 + 1e-6

    def test_fake_quant_grads_small_effect(self, rng):
        g = {"a": jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)}
        fq = fake_quant_grads(g)
        rel = np.linalg.norm(np.asarray(fq["a"] - g["a"])) / \
            np.linalg.norm(np.asarray(g["a"]))
        assert rel < 0.02

    def test_compressed_psum_shard_map(self):
        from jax.sharding import PartitionSpec as P
        mesh = auto_mesh((1,), ("x",))
        x = jnp.asarray(np.linspace(-1, 1, 64), jnp.float32)
        f = jax.shard_map(functools.partial(compressed_psum, axis_name="x"),
                      mesh=mesh, in_specs=P(), out_specs=P())
        np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x),
                                   rtol=2e-2, atol=2e-2)


class TestData:
    def test_restart_reproducible(self):
        cfg = DataConfig(vocab=100, seq_len=16, global_batch=4, seed=1)
        a = SyntheticPipeline(cfg).batch_np(17)
        b = SyntheticPipeline(cfg).batch_np(17)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_labels_shifted(self):
        cfg = DataConfig(vocab=100, seq_len=16, global_batch=2)
        b = SyntheticPipeline(cfg).batch_np(0)
        np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
        assert (b["labels"][:, -1] == -1).all()

    def test_mithril_readahead_learns_shard_pattern(self):
        from repro.core import MithrilConfig
        mcfg = MithrilConfig(min_support=2, max_support=8, lookahead=16,
                             rec_buckets=128, rec_ways=4, mine_rows=16,
                             pf_buckets=128, pf_ways=4)
        cfg = DataConfig(vocab=100, seq_len=8, global_batch=2, n_shards=16,
                         shard_group=4)
        plain = SyntheticPipeline(cfg)
        smart = SyntheticPipeline(cfg, mithril_cfg=mcfg)
        for step in range(200):
            plain.fetch_shard(step)
            smart.fetch_shard(step)
        assert smart.readahead_hits >= plain.readahead_hits
