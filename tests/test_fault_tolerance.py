"""Fault tolerance: checkpoint round-trip, elastic reshard, restart-exact
training, straggler/heartbeat detection."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore_state, save_state
from repro.launch.mesh import make_mesh
from repro.data import make_dataset
from repro.runtime import (FaultInjector, FaultTolerantLoop,
                           HeartbeatMonitor, StragglerDetector)


def test_checkpoint_roundtrip(tmp_path):
    state = {"a": jnp.arange(12.0).reshape(3, 4),
             "b": {"c": jnp.ones((5,), jnp.int32)},
             "step": jnp.asarray(7)}
    save_state(str(tmp_path), 7, state)
    spec = jax.eval_shape(lambda: state)
    restored = restore_state(str(tmp_path), 7, spec)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_manager_keep_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=True)
    state = {"x": jnp.zeros((4,))}
    for step in (10, 20, 30):
        mgr.save(step, state)
    mgr.wait()
    assert mgr.latest() == 30
    dirs = sorted(os.listdir(tmp_path))
    assert "step_10" not in dirs and "step_30" in dirs


def test_elastic_reshard_restore(tmp_path):
    """Save under one sharding, restore under a different mesh layout."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh1 = make_mesh((1,), ("data",))
    x = jax.device_put(jnp.arange(16.0).reshape(4, 4),
                       NamedSharding(mesh1, P("data")))
    save_state(str(tmp_path), 0, {"w": x})
    # "new cluster": different (trivial on 1 CPU, same code path) sharding
    mesh2 = make_mesh((1,), ("model",))
    sh = {"w": NamedSharding(mesh2, P(None, "model"))}
    restored = restore_state(str(tmp_path), 0,
                             jax.eval_shape(lambda: {"w": x}), sh)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(x))
    assert restored["w"].sharding == sh["w"]


def test_fault_tolerant_loop_restarts_exactly(tmp_path):
    """Injected faults at steps 7 and 13; the loop must finish all 20 steps
    and produce the SAME final state as a fault-free run (determinism)."""

    def train_step(state, batch):
        new = {"w": state["w"] + jnp.sum(batch["x"]),
               "step": state["step"] + 1}
        return new, {"loss": float(jnp.sum(batch["x"]))}

    def make_state():
        return {"w": jnp.zeros(()), "step": jnp.asarray(0)}

    def batch_at(step):
        rng = np.random.default_rng(step)
        return {"x": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}

    def run(fail_at, path):
        mgr = CheckpointManager(path, keep=2, async_write=False)
        loop = FaultTolerantLoop(
            train_step, make_state, batch_at, mgr, ckpt_every=5,
            abstract_state=jax.eval_shape(make_state),
            fault_injector=FaultInjector(fail_at))
        res = loop.run(20)
        final, _ = mgr.restore(jax.eval_shape(make_state))
        return res, final

    res_f, final_f = run((7, 13), str(tmp_path / "a"))
    res_c, final_c = run((), str(tmp_path / "b"))
    assert res_f.final_step == res_c.final_step == 20
    assert res_f.restarts == 2 and res_c.restarts == 0
    np.testing.assert_allclose(np.asarray(final_f["w"]),
                               np.asarray(final_c["w"]), rtol=1e-6)


def test_fault_tolerant_loop_history_no_duplicate_steps(tmp_path):
    """Regression: `run` used to keep appending to metrics_history across
    restarts, so the steps between the last checkpoint and the fault
    appeared once per restart (duplicate step keys).  The history must now
    hold each step exactly once and match a fault-free run's metrics."""

    def train_step(state, batch):
        new = {"w": state["w"] + jnp.sum(batch["x"]),
               "step": state["step"] + 1}
        return new, {"loss": float(jnp.sum(batch["x"]))}

    def make_state():
        return {"w": jnp.zeros(()), "step": jnp.asarray(0)}

    def batch_at(step):
        rng = np.random.default_rng(step)
        return {"x": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}

    def run(fail_at, path):
        mgr = CheckpointManager(path, keep=2, async_write=False)
        loop = FaultTolerantLoop(
            train_step, make_state, batch_at, mgr, ckpt_every=5,
            abstract_state=jax.eval_shape(make_state),
            fault_injector=FaultInjector(fail_at))
        return loop.run(20)

    # faults at 7 and 13 re-run steps 6-7 and 11-13 after restoring the
    # step-5 / step-10 checkpoints — exactly the duplicate-prone window
    res_f = run((7, 13), str(tmp_path / "a"))
    res_c = run((), str(tmp_path / "b"))
    steps_f = [m["step"] for m in res_f.metrics_history]
    assert steps_f == list(range(1, 21)), "history has duplicate/missing steps"
    assert res_f.metrics_history == res_c.metrics_history


def test_data_pipeline_deterministic_and_restart_exact():
    from repro.configs import get_config
    cfg = get_config("gemma-2b", smoke=True)
    ds1 = make_dataset(cfg, seq_len=32, global_batch=4, seed=5)
    ds2 = make_dataset(cfg, seq_len=32, global_batch=4, seed=5)
    for step in (0, 3, 17):
        a, b = ds1.batch_at(step), ds2.batch_at(step)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # host sharding partitions the global batch
    h0 = make_dataset(cfg, 32, 4, seed=5, n_hosts=2, host_id=0)
    h1 = make_dataset(cfg, 32, 4, seed=5, n_hosts=2, host_id=1)
    assert h0.batch_at(0)["tokens"].shape[0] == 2
    assert not np.array_equal(h0.batch_at(0)["tokens"],
                              h1.batch_at(0)["tokens"])


def test_straggler_detector():
    det = StragglerDetector(n_workers=4, factor=2.0)
    for _ in range(8):
        for w in range(4):
            det.record(w, 1.0 if w != 2 else 3.5)
    assert det.stragglers() == [2]


def test_heartbeat_monitor():
    clock = [0.0]
    mon = HeartbeatMonitor(3, timeout_s=10.0, clock=lambda: clock[0])
    clock[0] = 5.0
    mon.beat(0)
    mon.beat(1)
    clock[0] = 12.0
    assert mon.dead() == [2]
    mon.beat(2)
    assert mon.healthy()
