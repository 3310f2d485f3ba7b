"""Substrate tests: optimizer, data determinism, checkpoint atomicity +
elastic restore, gradient compression, failure/straggler machinery, and the
fault-tolerant trainer end-to-end (kill mid-run, verify recovery)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore_checkpoint, save_checkpoint
from repro.checkpoint.manager import latest_step
from repro.configs import get_reduced
from repro.data import SyntheticLMStream
from repro.models import Model
from repro.optim import AdamWConfig, adamw_update, init_opt_state, lr_schedule
from repro.runtime import (FailureInjector, StragglerDetector, Trainer,
                           TrainerConfig)
from repro.runtime.compression import (compress_gradients, decompress,
                                       init_compression_state, wire_bytes)


# ---------------------------------------------------------------------------
class TestOptimizer:
    def test_adamw_reduces_quadratic(self):
        params = {"w": jnp.array([5.0, -3.0])}
        state = init_opt_state(params)
        cfg = AdamWConfig(peak_lr=0.1, warmup_steps=5, total_steps=200,
                          weight_decay=0.0, clip_norm=100.0)
        for _ in range(150):
            grads = {"w": 2 * params["w"]}
            params, state, m = adamw_update(params, grads, state, cfg)
        assert float(jnp.abs(params["w"]).max()) < 0.3

    def test_schedule_shape(self):
        cfg = AdamWConfig(peak_lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
        assert float(lr_schedule(jnp.array(0), cfg)) == 0.0
        assert abs(float(lr_schedule(jnp.array(10), cfg)) - 1.0) < 1e-6
        end = float(lr_schedule(jnp.array(100), cfg))
        assert abs(end - 0.1) < 1e-6

    def test_clip_engages(self):
        params = {"w": jnp.ones((4, 4))}
        state = init_opt_state(params)
        cfg = AdamWConfig(clip_norm=1.0)
        _, _, m = adamw_update(params, {"w": jnp.full((4, 4), 100.0)}, state,
                               cfg)
        assert float(m["grad_norm"]) > 1.0  # reported pre-clip


# ---------------------------------------------------------------------------
class TestData:
    def test_deterministic_resume(self):
        s = SyntheticLMStream(vocab_size=64, batch_size=4, seq_len=16, seed=1)
        b1 = s.batch_at(7)
        b2 = s.batch_at(7)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_host_sharding_partitions_global_batch(self):
        full = SyntheticLMStream(vocab_size=64, batch_size=8, seq_len=8,
                                 seed=2)
        parts = [SyntheticLMStream(vocab_size=64, batch_size=8, seq_len=8,
                                   seed=2, host_id=h, num_hosts=4)
                 for h in range(4)]
        got = np.concatenate([p.batch_at(3)["tokens"] for p in parts])
        np.testing.assert_array_equal(got, full.batch_at(3)["tokens"])

    def test_learnable_structure(self):
        s = SyntheticLMStream(vocab_size=64, batch_size=2, seq_len=64, seed=0,
                              noise=0.0)
        b = s.batch_at(0)
        # noiseless: next = (a·t + b) mod V exactly
        t, y = b["tokens"][0], b["targets"][0]
        assert ((s.a * t + s.b) % 64 == y).all()


# ---------------------------------------------------------------------------
class TestCheckpoint:
    def test_roundtrip_and_integrity(self, tmp_path):
        tree = {"a": jnp.arange(10, dtype=jnp.float32),
                "b": {"c": jnp.ones((3, 3), jnp.bfloat16)}}
        path = save_checkpoint(str(tmp_path), 5, tree)
        got, manifest = restore_checkpoint(path, tree)
        assert manifest["step"] == 5
        np.testing.assert_array_equal(np.asarray(got["a"]),
                                      np.asarray(tree["a"]))
        assert got["b"]["c"].dtype == jnp.bfloat16

    def test_corruption_detected(self, tmp_path):
        tree = {"a": jnp.arange(4.0)}
        path = save_checkpoint(str(tmp_path), 1, tree)
        npz = os.path.join(path, "arrays.npz")
        with open(npz, "r+b") as f:
            f.seek(30)
            f.write(b"\xde\xad")
        with pytest.raises(IOError):
            restore_checkpoint(path, tree)

    def test_torn_write_invisible(self, tmp_path):
        os.makedirs(tmp_path / "step_00000009.tmp")
        assert latest_step(str(tmp_path)) is None
        save_checkpoint(str(tmp_path), 3, {"a": jnp.zeros(1)})
        assert latest_step(str(tmp_path)) == 3

    def test_async_manager_and_gc(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for s in [10, 20, 30]:
            mgr.save_async(s, {"a": jnp.full(4, float(s))})
        mgr.wait()
        assert mgr.latest() == 30
        kept = sorted(os.listdir(tmp_path))
        assert len([k for k in kept if k.startswith("step_")]) == 2

    def test_elastic_reshard_restore(self, tmp_path):
        """A checkpoint written unsharded restores onto a 4-device mesh with
        explicit shardings (elastic rescale path)."""
        import subprocess, sys, textwrap

        code = textwrap.dedent(f"""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax, jax.numpy as jnp, numpy as np
            import sys
            sys.path.insert(0, "src")
            from jax.sharding import PartitionSpec as P, NamedSharding
            from repro.checkpoint import save_checkpoint, restore_checkpoint
            from repro.launch.mesh import make_mesh
            tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
            path = save_checkpoint({str(tmp_path)!r}, 1, tree)
            mesh = make_mesh((4,), ("data",))
            sh = {{"w": NamedSharding(mesh, P("data", None))}}
            got, _ = restore_checkpoint(path, tree, shardings=sh)
            assert len(got["w"].sharding.device_set) == 4
            np.testing.assert_array_equal(np.asarray(got["w"]),
                                          np.asarray(tree["w"]))
            print("OK")
        """)
        out = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                             capture_output=True, text=True)
        assert "OK" in out.stdout, out.stderr


# ---------------------------------------------------------------------------
class TestCompression:
    def test_quant_roundtrip_accuracy(self):
        rng = np.random.default_rng(0)
        grads = {"w": jnp.asarray(rng.normal(size=(1000,)), jnp.float32)}
        st = init_compression_state(grads)
        payload, st = compress_gradients(grads, st)
        approx = decompress(payload, grads)
        err = float(jnp.abs(approx["w"] - grads["w"]).max())
        assert err < 0.05  # int8 block quant: ~scale/127

    def test_error_feedback_unbiased_over_time(self):
        """Constant gradient: EF makes the *cumulative* quantized sum track
        the true cumulative sum (residual stays bounded)."""
        g = {"w": jnp.asarray(np.linspace(-1, 1, 512), jnp.float32)}
        st = init_compression_state(g)
        acc = jnp.zeros(512)
        for _ in range(50):
            payload, st = compress_gradients(g, st)
            acc = acc + decompress(payload, g)["w"]
        np.testing.assert_allclose(np.asarray(acc) / 50, np.asarray(g["w"]),
                                   atol=1e-3)

    def test_wire_volume_4x_smaller(self):
        g = {"w": jnp.zeros((4096,), jnp.float32)}
        st = init_compression_state(g)
        payload, _ = compress_gradients(g, st)
        assert wire_bytes(payload) < 0.3 * 4096 * 4


# ---------------------------------------------------------------------------
class TestFailureMachinery:
    def test_straggler_detection(self):
        det = StragglerDetector(threshold=1.5, min_samples=4)
        for _ in range(8):
            for n in range(4):
                det.record(n, 1.0 if n != 2 else 2.5)
        assert det.stragglers() == [2]

    def test_injector_fires_once(self):
        inj = FailureInjector(schedule={5: [1, 2]})
        assert inj.tick(4) == []
        assert inj.tick(5) == [1, 2]
        assert inj.tick(5) == []


# ---------------------------------------------------------------------------
class TestTrainerEndToEnd:
    def test_loss_decreases_and_recovers_from_failure(self, tmp_path):
        cfg = get_reduced("tinyllama-1.1b")
        model = Model(cfg, scan_layers=True)
        stream = SyntheticLMStream(vocab_size=cfg.vocab_size, batch_size=8,
                                   seq_len=32, seed=0, noise=0.05)
        tcfg = TrainerConfig(total_steps=60, checkpoint_every=20,
                             checkpoint_dir=str(tmp_path), log_every=5)
        inj = FailureInjector(schedule={30: [0]})
        tr = Trainer(model, AdamWConfig(peak_lr=3e-3, warmup_steps=10,
                                        total_steps=60),
                     tcfg, stream, failure_injector=inj)
        out = tr.run()
        assert out["recoveries"] == 1
        losses = [h["loss"] for h in out["history"]]
        assert losses[-1] < losses[0] * 0.8, losses
        assert latest_step(str(tmp_path)) == 60

    def test_resume_identical_to_uninterrupted(self, tmp_path):
        """Determinism: run 20 steps straight vs 10 + restart + 10."""
        cfg = get_reduced("tinyllama-1.1b")

        def make(dirname):
            model = Model(cfg, scan_layers=True)
            stream = SyntheticLMStream(vocab_size=cfg.vocab_size,
                                       batch_size=4, seq_len=16, seed=3)
            return Trainer(
                model, AdamWConfig(peak_lr=1e-3, warmup_steps=5,
                                   total_steps=20),
                TrainerConfig(total_steps=20, checkpoint_every=10,
                              checkpoint_dir=dirname, log_every=100),
                stream)

        a = make(str(tmp_path / "a")).run(seed=7)
        t2 = make(str(tmp_path / "b"))
        t2.cfg = TrainerConfig(total_steps=10, checkpoint_every=10,
                               checkpoint_dir=str(tmp_path / "b"),
                               log_every=100)
        t2.run(seed=7)  # first 10 steps
        t3 = make(str(tmp_path / "b"))  # resumes at 10 from checkpoint
        b = t3.run(seed=7)
        wa = jax.tree.leaves(a["state"]["params"])[0]
        wb = jax.tree.leaves(b["state"]["params"])[0]
        np.testing.assert_allclose(np.asarray(wa, np.float32),
                                   np.asarray(wb, np.float32), atol=1e-6)
