"""Substrate tests: optimizer, checkpointing (incl. elastic + atomicity +
resume), data pipeline, fault tolerance, continuous batching."""

import os
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import CheckpointManager, io as ckpt_io
from repro.configs import get_reduced
from repro.data import PrefetchLoader, SyntheticLM
from repro.ft import Coordinator, HangDetector, StepWatchdog, plan_mesh_after_failure
from repro.models.lm import LM
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.optim.schedule import warmup_cosine
from repro.runtime.batching import ContinuousBatcher, Request
from repro.runtime.train import make_train_step


# --------------------------------------------------------------------------- #
class TestAdamW:
    def test_quadratic_convergence(self):
        """AdamW minimises a quadratic (the from-scratch optimizer works)."""
        target = jnp.asarray([1.0, -2.0, 3.0])
        params = {"w": jnp.zeros(3)}
        cfg = AdamWConfig(lr=0.1, weight_decay=0.0, grad_clip=0.0)
        state = adamw.init(params, cfg)
        loss = lambda p: jnp.sum((p["w"] - target) ** 2)
        for _ in range(300):
            g = jax.grad(loss)(params)
            params, state, _ = adamw.update(g, state, params, cfg)
        assert float(loss(params)) < 1e-3

    def test_grad_clip_bounds_update(self):
        params = {"w": jnp.zeros(4)}
        cfg = AdamWConfig(lr=1.0, grad_clip=1.0, weight_decay=0.0)
        state = adamw.init(params, cfg)
        g = {"w": jnp.full((4,), 1e6)}
        _, _, m = adamw.update(g, state, params, cfg)
        assert float(m["grad_norm"]) > 1e5  # reported pre-clip

    def test_master_fp32_with_bf16_params(self):
        params = {"w": jnp.ones(8, jnp.bfloat16)}
        cfg = AdamWConfig(lr=1e-4, master_fp32=True)
        state = adamw.init(params, cfg)
        assert state["master"]["w"].dtype == jnp.float32
        g = {"w": jnp.full((8,), 1e-3, jnp.bfloat16)}
        p2, s2, _ = adamw.update(g, state, params, cfg)
        assert p2["w"].dtype == jnp.bfloat16
        # tiny updates accumulate in the master even when bf16 can't see them
        for _ in range(3):
            p2, s2, _ = adamw.update(g, s2, p2, cfg)
        assert not np.array_equal(np.asarray(s2["master"]["w"]),
                                  np.asarray(state["master"]["w"]))

    def test_schedule(self):
        f = warmup_cosine(1.0, 10, 100)
        assert float(f(jnp.asarray(5))) == pytest.approx(0.5)
        assert float(f(jnp.asarray(10))) == pytest.approx(1.0, abs=0.01)
        assert float(f(jnp.asarray(100))) == pytest.approx(0.1, abs=0.01)


# --------------------------------------------------------------------------- #
class TestTrainLoop:
    def test_loss_decreases_on_synthetic(self):
        cfg = get_reduced("phi3-mini-3.8b")
        model = LM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        opt_cfg = AdamWConfig(lr=3e-3, weight_decay=0.0)
        state = adamw.init(params, opt_cfg)
        step = make_train_step(model, cfg, opt_cfg, donate=False)
        ds = SyntheticLM(vocab=cfg.vocab, seq_len=32, batch=8, seed=0)
        losses = []
        for i in range(30):
            batch = {k: jnp.asarray(v) for k, v in ds.batch_at(i % 4).items()}
            params, state, metrics = step(params, state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] - 0.5, losses[::10]
        assert np.isfinite(losses).all()


# --------------------------------------------------------------------------- #
class TestCheckpoint:
    def _state(self, key=0):
        k = jax.random.PRNGKey(key)
        return {"params": {"w": jax.random.normal(k, (4, 8)),
                           "stack": [jnp.ones((2, 3)), jnp.zeros((5,))]},
                "step": jnp.asarray(7)}

    def test_roundtrip(self, tmp_path):
        state = self._state()
        ckpt_io.save(str(tmp_path), 7, state)
        target = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                              state)
        restored = ckpt_io.restore(str(tmp_path), target)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), state, restored)

    def test_atomicity_tmp_dir_ignored(self, tmp_path):
        state = self._state()
        ckpt_io.save(str(tmp_path), 1, state)
        # simulate a crash mid-save of step 2: stray .tmp dir
        os.makedirs(tmp_path / "step_00000002.tmp")
        assert ckpt_io.list_steps(str(tmp_path)) == [1]

    def test_manager_rotation_and_resume(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), interval=2, keep=2,
                                async_save=False)
        state = self._state()
        for step in range(1, 9):
            mgr.maybe_save(step, state, {"loss": 1.0 / step})
        assert mgr.latest_step() == 8
        assert len(ckpt_io.list_steps(str(tmp_path))) == 2  # rotated
        meta = ckpt_io.restore_metadata(str(tmp_path))
        assert meta["step"] == 8

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), interval=1, async_save=True)
        mgr.save(3, self._state())
        mgr.wait()
        assert mgr.latest_step() == 3

    def test_elastic_restore_different_sharding(self, tmp_path):
        """Checkpoint written 'on one mesh', restored with explicit new
        shardings (single-device here; the reshard path is device_put)."""
        state = self._state()
        ckpt_io.save(str(tmp_path), 1, state)
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
        target = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                              state)
        restored = ckpt_io.restore(str(tmp_path), target, shardings=sh)
        assert restored["params"]["w"].sharding == NamedSharding(mesh, P())

    def test_shape_mismatch_rejected(self, tmp_path):
        ckpt_io.save(str(tmp_path), 1, {"w": jnp.ones((4,))})
        with pytest.raises(ValueError):
            ckpt_io.restore(str(tmp_path),
                            {"w": jax.ShapeDtypeStruct((5,), jnp.float32)})

    def test_train_resume_bitexact(self, tmp_path):
        """Crash/restart: resumed run reproduces the uninterrupted run."""
        cfg = get_reduced("minitron-4b")
        model = LM(cfg)
        opt_cfg = AdamWConfig(lr=1e-3)
        ds = SyntheticLM(vocab=cfg.vocab, seq_len=16, batch=4, seed=1)
        step_fn = make_train_step(model, cfg, opt_cfg, donate=False)

        def run(n_steps, params, state, start=0):
            for i in range(start, n_steps):
                batch = {k: jnp.asarray(v) for k, v in ds.batch_at(i).items()}
                params, state, _ = step_fn(params, state, batch)
            return params, state

        p0 = model.init_params(jax.random.PRNGKey(0))
        s0 = adamw.init(p0, opt_cfg)
        p_full, _ = run(6, p0, s0)

        p_half, s_half = run(3, p0, s0)
        ckpt_io.save(str(tmp_path), 3, {"params": p_half, "opt": s_half})
        target = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            {"params": p_half, "opt": s_half})
        rest = ckpt_io.restore(str(tmp_path), target)
        p_res, _ = run(6, rest["params"], rest["opt"], start=3)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-6),
            p_full, p_res)


# --------------------------------------------------------------------------- #
class TestData:
    def test_prefetch_loader_orders_steps(self):
        ds = SyntheticLM(vocab=101, seq_len=8, batch=2, seed=5)
        loader = PrefetchLoader(ds.batch_at, prefetch=2)
        steps = []
        for _ in range(5):
            step, batch = next(loader)
            steps.append(step)
            np.testing.assert_array_equal(batch["tokens"],
                                          ds.batch_at(step)["tokens"])
        loader.close()
        assert steps == [0, 1, 2, 3, 4]


# --------------------------------------------------------------------------- #
class TestFaultTolerance:
    def test_watchdog_flags_straggler(self):
        wd = StepWatchdog(window=20, threshold=2.0)
        for i in range(10):
            wd.start()
            time.sleep(0.004)
            assert not wd.stop()
        wd.start()
        time.sleep(0.05)
        assert wd.stop() is True
        assert wd.stragglers == [11]

    def test_hang_detector_fires(self):
        fired = []
        with HangDetector(0.02, lambda: fired.append(1)):
            time.sleep(0.08)
        assert fired
        with HangDetector(1.0, lambda: fired.append(2)):
            pass
        assert fired == [1]

    def test_coordinator_membership(self):
        c = Coordinator(deadline=0.05)
        c.register("host0")
        c.register("host1")
        gen0 = c.generation
        for _ in range(3):
            c.heartbeat("host0")
            time.sleep(0.02)
        dead = c.sweep()
        assert dead == ["host1"]
        assert c.alive() == ["host0"]
        assert c.generation > gen0

    def test_elastic_mesh_plan(self):
        assert plan_mesh_after_failure(512) == ((32, 16), ("data", "model"))
        assert plan_mesh_after_failure(496) == ((31, 16), ("data", "model"))
        assert plan_mesh_after_failure(8) is None


# --------------------------------------------------------------------------- #
class TestContinuousBatching:
    def test_outputs_match_unbatched_and_slots_reused(self):
        cfg = get_reduced("phi3-mini-3.8b")
        model = LM(cfg)
        params = model.init_params(jax.random.PRNGKey(3))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(2, cfg.vocab, size=rng.integers(3, 7))
                   .astype(np.int32) for _ in range(5)]

        # unbatched greedy reference
        def greedy(prompt, n):
            toks = jnp.asarray(prompt)[None]
            lg, caches, lengths = model.prefill(params, {"tokens": toks},
                                                cache_cap=32)
            out = [int(jnp.argmax(lg[0]))]
            for _ in range(n - 1):
                lg, caches = model.decode_step(
                    params, jnp.asarray([out[-1]]), caches, lengths)
                lengths = lengths + 1
                out.append(int(jnp.argmax(lg[0])))
            return out

        batcher = ContinuousBatcher(model, params, n_slots=2, cache_cap=32,
                                    eos_id=-1)  # no eos: run to max tokens
        reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            batcher.submit(r)
        batcher.run(max_steps=100)
        assert all(r.done for r in reqs)
        # 5 requests through 2 slots => slots were reused
        assert batcher.utilisation > 0.5
        for r in reqs:
            assert r.out_tokens[:4] == greedy(r.prompt, 4), f"req {r.uid}"
