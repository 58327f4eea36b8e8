"""Distribution-layer tests: sharding rules, divisibility validation,
small-mesh train-step lowering, a2a MoE parity.  Multi-device cases run in a
subprocess (device count is locked at first jax init)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.api import logical_to_spec, validate_spec
from repro.launch.mesh import make_mesh
from repro.dist.sharding import DEFAULT_RULES, make_rules

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str, devices: int = 8, timeout=600) -> str:
    prog = (f"import os\n"
            f"os.environ['XLA_FLAGS']="
            f"'--xla_force_host_platform_device_count={devices}'\n"
            + textwrap.dedent(code))
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_validate_spec_dedupe_and_identity():
    mesh = make_mesh((1,), ("data",))
    # the same mesh axis may not shard two dims: the second use drops
    spec = validate_spec(P("data", "data"), (4, 4), mesh)
    assert spec in (P("data"), P("data", None))
    # size-1 axes always divide (no-op sharding is kept)
    assert validate_spec(P("data"), (7,), mesh) == P("data")


def test_validate_spec_divisibility_multidevice():
    """Non-dividing dims must drop the axis (needs a >1-sized axis)."""
    code = """
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.dist.api import validate_spec
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("model",))
    assert validate_spec(P("model"), (7,), mesh) in (P(), P(None))
    assert validate_spec(P("model"), (8,), mesh) == P("model")
    # tuple axes keep the longest dividing prefix
    mesh2 = make_mesh((2, 2), ("pod", "data"))
    assert validate_spec(P(("pod", "data")), (2,), mesh2) == P(("pod",))
    print("OK")
    """
    out = run_subprocess(code, devices=4)
    assert "OK" in out


def test_logical_to_spec_and_rules():
    rules = dict(DEFAULT_RULES)
    spec = logical_to_spec(("batch", None, "heads"), rules)
    assert spec == P(("pod", "data"), None, "model")
    mesh = make_mesh((1,), ("data",))
    r = make_rules(mesh)
    assert r["heads"] is None  # no 'model' axis on this mesh
    assert r["batch"] == ("data",)


def test_small_mesh_train_step_compiles_and_runs():
    """End-to-end: jit train step on a (1,1)-mesh with real data."""
    from repro.configs import get_config
    from repro.data import make_dataset
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import TrainState, jit_train_step
    from repro.models import init_params
    from repro.optim import adamw

    cfg = get_config("gemma-2b", smoke=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = adamw(3e-3)
    ds = make_dataset(cfg, seq_len=32, global_batch=4)
    b0 = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}
    bspec = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in b0.items()}
    fn, state_sh, _ = jit_train_step(cfg, opt, mesh, bspec)
    params = init_params(cfg, jax.random.PRNGKey(0))
    state = TrainState(params=params, opt=opt.init(params),
                       step=jnp.zeros((), jnp.int32))
    losses = []
    for step in range(24):
        batch = {k: jnp.asarray(v) for k, v in ds.batch_at(step).items()}
        state, metrics = fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-6:]) < np.mean(losses[:6]), \
        f"loss should drop: {losses}"


@pytest.mark.slow
def test_multi_device_sharded_train_equals_single_device():
    """The same train step on a (2,2) mesh must produce the same loss
    trajectory as single-device (SPMD correctness)."""
    code = """
    import jax, jax.numpy as jnp, json
    from repro.configs import get_config
    from repro.data import make_dataset
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import TrainState, jit_train_step
    from repro.models import init_params
    from repro.optim import adamw

    def losses_for(mesh_shape):
        cfg = get_config('olmoe-1b-7b', smoke=True)
        mesh = make_mesh(mesh_shape, ('data', 'model'))
        opt = adamw(1e-3)
        ds = make_dataset(cfg, seq_len=16, global_batch=4)
        b0 = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}
        bspec = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in b0.items()}
        fn, _, _ = jit_train_step(cfg, opt, mesh, bspec)
        params = init_params(cfg, jax.random.PRNGKey(0))
        state = TrainState(params=params, opt=opt.init(params),
                           step=jnp.zeros((), jnp.int32))
        out = []
        for step in range(4):
            batch = {k: jnp.asarray(v) for k, v in ds.batch_at(step).items()}
            state, m = fn(state, batch)
            out.append(float(m['loss']))
        return out

    a = losses_for((1, 1))
    b = losses_for((2, 2))
    print(json.dumps({'single': a, 'sharded': b}))
    """
    out = run_subprocess(code, devices=4)
    data = json.loads(out.strip().splitlines()[-1])
    np.testing.assert_allclose(data["single"], data["sharded"],
                               rtol=5e-2, atol=5e-2)


@pytest.mark.slow
def test_dryrun_cell_small_smoke():
    """The dry-run driver itself works end-to-end (tiny cell, 512 devices)."""
    code = """
    from repro.launch.dryrun import run_cell
    rec = run_cell('whisper-base', 'train_4k', multi_pod=False,
                   verbose=False, skip_cost=True)
    assert rec['status'] == 'ok', rec
    print('MEM', rec['memory']['argument_bytes'])
    """
    out = run_subprocess(code, devices=512, timeout=1500)
    assert "MEM" in out


def test_param_shardings_cover_tree():
    from repro.configs import get_config
    from repro.dist.sharding import param_shardings
    from repro.models import init_params
    cfg = get_config("olmoe-1b-7b", smoke=True)
    mesh = make_mesh((1,), ("data",))
    spec = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    sh = param_shardings(cfg, spec, mesh)
    assert (len(jax.tree.leaves(sh)) == len(jax.tree.leaves(spec)))
