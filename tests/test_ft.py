"""Fault tolerance: checkpoint atomicity, exact resume, failure injection,
work-stealing scheduler, gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.ft import DriverConfig, FailureInjector, TrainDriver
from repro.ft.scheduler import WorkStealingScheduler


def _toy_state(seed=0):
    k = jax.random.PRNGKey(seed)
    params = {"w": jax.random.normal(k, (8, 8)), "b": jnp.zeros((8,))}
    return params


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6.0).reshape(2, 3),
                "nested": {"b": jnp.ones((4,), jnp.int32)}}
        save_checkpoint(tmp_path, 5, tree, extra={"note": "x"})
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        got, step, extra = restore_checkpoint(tmp_path, abstract)
        assert step == 5 and extra == {"note": "x"}
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_atomic_no_partial(self, tmp_path):
        tree = {"a": jnp.ones((3,))}
        save_checkpoint(tmp_path, 1, tree)
        # simulate a crashed partial write
        bad = tmp_path / "step_2.tmp"
        bad.mkdir()
        (bad / "garbage.npy").write_bytes(b"xx")
        assert latest_step(tmp_path) == 1  # tmp dirs never count

    def test_gc_keeps_last(self, tmp_path):
        mgr = CheckpointManager(tmp_path, keep=2)
        t = {"a": jnp.ones((2,))}
        for s in [1, 2, 3, 4]:
            mgr.save(s, t)
        steps = sorted(int(d.name.split("_")[1]) for d in tmp_path.iterdir())
        assert steps == [3, 4]

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(tmp_path, async_save=True)
        mgr.save(7, {"a": jnp.arange(4.0)})
        mgr.wait()
        assert latest_step(tmp_path) == 7

    def test_shape_mismatch_rejected(self, tmp_path):
        save_checkpoint(tmp_path, 0, {"a": jnp.ones((3,))})
        with pytest.raises(ValueError):
            restore_checkpoint(tmp_path,
                               {"a": jax.ShapeDtypeStruct((4,), jnp.float32)})


def _make_driver(tmp_path, total=12, fail_at=None, ckpt_every=4):
    """Toy quadratic optimization driver with deterministic data."""
    from repro.optim import adamw_init, adamw_update

    def init_state():
        params = _toy_state()
        return params, adamw_init(params)

    @jax.jit
    def step_fn(params, opt_state, x, y):
        def loss_fn(p):
            pred = x @ p["w"] + p["b"]
            return jnp.mean((pred - y) ** 2)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state, m = adamw_update(grads, opt_state, params,
                                            lr=5e-2, weight_decay=0.0)
        return params, opt_state, {"loss": loss, **m}

    def batch_fn(step):
        r = np.random.default_rng(step)
        x = jnp.asarray(r.standard_normal((16, 8)).astype(np.float32))
        return x, jnp.asarray((np.asarray(x) @ np.eye(8)).astype(np.float32))

    cfg = DriverConfig(total_steps=total, ckpt_dir=str(tmp_path),
                       ckpt_every=ckpt_every, async_save=False)
    return TrainDriver(cfg, step_fn, init_state, batch_fn,
                       injector=FailureInjector(fail_at))


class TestDriver:
    def test_loss_decreases(self, tmp_path):
        out = _make_driver(tmp_path / "a", total=30).run()
        losses = [h["loss"] for h in out["history"]]
        assert np.mean(losses[-5:]) < np.mean(losses[:5])

    def test_crash_resume_is_exact(self, tmp_path):
        # uninterrupted reference
        ref = _make_driver(tmp_path / "ref").run()
        # crashed run: fails at step 9, restart resumes from step 7 ckpt
        d1 = _make_driver(tmp_path / "crash", fail_at=9)
        with pytest.raises(RuntimeError, match="injected failure"):
            d1.run()
        d2 = _make_driver(tmp_path / "crash")
        out = d2.run()
        ref_by_step = {h["step"]: h["loss"] for h in ref["history"]}
        for h in out["history"]:
            assert h["loss"] == pytest.approx(ref_by_step[h["step"]],
                                              rel=1e-6), h
        # final params identical
        for a, b in zip(jax.tree.leaves(ref["params"]),
                        jax.tree.leaves(out["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)

    def test_straggler_detection(self, tmp_path):
        d = _make_driver(tmp_path / "s", total=8)
        import time as _t
        orig = d.step_fn

        calls = {"n": 0}

        def slow_step(*a):
            calls["n"] += 1
            if calls["n"] == 7:
                _t.sleep(0.5)
            return orig(*a)

        d.step_fn = slow_step
        out = d.run()
        assert 6 in out["stragglers"]


class TestScheduler:
    def test_balanced_assignment_and_steal(self):
        sched = WorkStealingScheduler(n_groups=2)
        sched.submit([[1] * c for c in [8, 1, 1, 1, 1]])
        # group with the big cluster gets it alone; other gets the rest
        g0 = sum(i.cost for i in sched.queues[0])
        g1 = sum(i.cost for i in sched.queues[1])
        assert {g0, g1} == {8.0, 4.0}
        # drain group that has small items, then steal from the loaded one
        light = 0 if g0 < g1 else 1
        for _ in range(4):
            it = sched.next_for(light)
            sched.complete(it.cluster_id, "ok")
        it = sched.next_for(light)
        assert it is not None
        assert sched.steals == 1

    def test_failure_requeues_in_flight(self):
        sched = WorkStealingScheduler(n_groups=2)
        sched.submit([[1, 2], [3], [4]])
        it = sched.next_for(0)
        sched.fail_group(0, [it.cluster_id])
        assert sched.pending() == 3
        # the lost cluster is completable again
        seen = set()
        for g in [0, 1, 0, 1, 0, 1]:
            nxt = sched.next_for(g)
            if nxt:
                seen.add(nxt.cluster_id)
                sched.complete(nxt.cluster_id, "ok")
        assert it.cluster_id in seen

    def test_snapshot_restore(self, tmp_path):
        sched = WorkStealingScheduler(n_groups=2)
        sched.submit([[1], [2], [3]])
        it = sched.next_for(0)
        sched.complete(it.cluster_id, "done")
        it2 = sched.next_for(0)       # in flight at crash time
        sched.snapshot(tmp_path / "q.json")
        restored = WorkStealingScheduler.restore(tmp_path / "q.json", 2)
        assert it.cluster_id in restored.done
        assert restored.pending() == 2  # 1 queued + 1 requeued in-flight


class TestCompression:
    def test_int8_roundtrip_error_bound(self):
        from repro.optim.compress import compress_int8, decompress_int8
        r = np.random.default_rng(0)
        x = jnp.asarray(r.standard_normal((256,)).astype(np.float32)) * 3
        codes, scale = compress_int8(x)
        err = np.abs(np.asarray(decompress_int8(codes, scale) - x)).max()
        assert err <= float(scale) / 2 + 1e-6

    def test_error_feedback_unbiased_over_time(self):
        """EF compression: accumulated transmitted sum converges to the true
        gradient sum (the EF invariant: sum(sent) = sum(g) - final_error)."""
        from repro.optim.compress import ef_compressed_psum
        import jax
        from jax.sharding import Mesh
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
        r = np.random.default_rng(1)
        gs = [jnp.asarray(r.standard_normal(64).astype(np.float32)) * 10 ** (i % 3)
              for i in range(20)]
        err = jnp.zeros(64)
        sent_total = jnp.zeros(64)

        fn = shard_map(lambda g, e: ef_compressed_psum(g, e, "pod"),
                       mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
        for g in gs:
            sent, err = fn(g, err)
            sent_total = sent_total + sent
        true_total = sum(np.asarray(g) for g in gs)
        np.testing.assert_allclose(np.asarray(sent_total + err), true_total,
                                   rtol=1e-4, atol=1e-3)
