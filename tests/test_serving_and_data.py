"""Serving engine + optimizer + misc substrate tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.optim import adafactor, adamw, opt_shardings, schedule_cosine, sgd
from repro.serve import Request, ServeEngine


def test_serve_engine_waves_and_greedy_determinism():
    cfg = get_config("gemma-2b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_batch=3, max_len=96)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, rng.integers(3, 10))
               .astype(np.int32) for _ in range(5)]
    for i, p in enumerate(prompts):
        engine.submit(Request(uid=i, prompt=p, max_new_tokens=6))
    results = engine.run_all()
    assert len(results) == 5
    assert all(len(r.tokens) == 6 for r in results)

    # same prompt twice (greedy) -> identical generations
    e2 = ServeEngine(cfg, params, max_batch=2, max_len=96)
    e2.submit(Request(uid=0, prompt=prompts[0], max_new_tokens=6))
    e2.submit(Request(uid=1, prompt=prompts[0], max_new_tokens=6))
    r = e2.run_all()
    np.testing.assert_array_equal(r[0].tokens, r[1].tokens)


def test_serve_engine_length_aware_wave_packing():
    """Regression: the old packer popped `max_batch` requests BEFORE the
    `total <= max_len` assert, so one oversized request crashed `run_all`
    with an AssertionError and took every other request in its wave down
    with it.  Now an unfittable request gets a per-request error Result and
    requests that fit alone but not together split across waves."""
    cfg = get_config("gemma-2b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)

    # (a) single unfittable request -> error Result, neighbors unharmed
    engine = ServeEngine(cfg, params, max_batch=4, max_len=32)
    ok_prompt = rng.integers(1, cfg.vocab, 4).astype(np.int32)
    big_prompt = rng.integers(1, cfg.vocab, 30).astype(np.int32)
    engine.submit(Request(uid=0, prompt=ok_prompt, max_new_tokens=4))
    engine.submit(Request(uid=1, prompt=big_prompt, max_new_tokens=8))
    engine.submit(Request(uid=2, prompt=ok_prompt, max_new_tokens=4))
    results = {r.uid: r for r in engine.run_all()}
    assert results[1].error is not None and "max_len" in results[1].error
    assert len(results[1].tokens) == 0
    for uid in (0, 2):
        assert results[uid].error is None
        assert len(results[uid].tokens) == 4

    # (b) requests that fit alone but not together split into two waves
    e2 = ServeEngine(cfg, params, max_batch=4, max_len=32)
    e2.submit(Request(uid=0, prompt=rng.integers(1, cfg.vocab, 24)
                      .astype(np.int32), max_new_tokens=8))
    e2.submit(Request(uid=1, prompt=rng.integers(1, cfg.vocab, 4)
                      .astype(np.int32), max_new_tokens=20))
    first = e2.run_wave()
    assert [r.uid for r in first] == [0] and e2.queue  # uid 1 deferred
    second = e2.run_wave()
    assert [r.uid for r in second] == [1]
    assert all(r.error is None for r in first + second)


def test_serve_engine_eos_early_stop():
    cfg = get_config("gemma-2b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_batch=1, max_len=64)
    engine.submit(Request(uid=0, prompt=np.asarray([5, 6], np.int32),
                          max_new_tokens=8))
    greedy_first = engine.run_all()[0].tokens[0]
    engine.submit(Request(uid=1, prompt=np.asarray([5, 6], np.int32),
                          max_new_tokens=8, eos_id=int(greedy_first)))
    r = engine.run_all()[0]
    assert len(r.tokens) == 1 and r.tokens[0] == greedy_first


def _quad_loss_params():
    return {"w": jnp.asarray([1.0, -2.0, 3.0]),
            "deep": {"v": jnp.full((4, 4), 0.5)}}


@pytest.mark.parametrize("make_opt", [lambda: sgd(0.1),
                                      lambda: adamw(0.05),
                                      lambda: adafactor(0.05)])
def test_optimizers_minimize_quadratic(make_opt):
    opt = make_opt()
    params = _quad_loss_params()
    state = opt.init(params)

    def loss(p):
        return sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(p))

    l0 = float(loss(params))
    for step in range(60):
        grads = jax.grad(loss)(params)
        params, state = opt.update(grads, state, params,
                                   jnp.asarray(step))
    assert float(loss(params)) < 0.2 * l0


def test_adafactor_state_is_factored():
    opt = adafactor(0.05, min_dim_factored=4)
    params = {"big": jnp.zeros((8, 16)), "small": jnp.zeros((3,))}
    state = opt.init(params)
    assert set(state["big"].keys()) == {"vr", "vc"}
    assert state["big"]["vr"].shape == (8,)
    assert state["big"]["vc"].shape == (16,)
    assert state["small"]["v"].shape == (3,)


def test_opt_shardings_mirror_params():
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((1,), ("data",))
    params = {"w": jnp.zeros((256, 512))}
    psh = {"w": NamedSharding(mesh, P("data", None))}
    opt = adamw(1e-3)
    osh = opt_shardings(opt, psh, params, mesh)
    assert osh["m"]["w"] == psh["w"] and osh["v"]["w"] == psh["w"]
    fopt = adafactor(1e-2, min_dim_factored=4)
    osh2 = opt_shardings(fopt, psh, params, mesh)
    assert osh2["w"]["vr"].spec == P("data")
    assert osh2["w"]["vc"].spec in (P(None), P())


def test_schedule_cosine_shape():
    lr = schedule_cosine(1.0, warmup=10, total=100)
    assert float(lr(jnp.asarray(0))) < 0.2
    assert float(lr(jnp.asarray(10))) == pytest.approx(1.0, rel=0.05)
    assert float(lr(jnp.asarray(100))) <= 0.2


def test_end_to_end_tiny_training_run(tmp_path):
    """The (b) deliverable driver: loss decreases over a short run with a
    checkpoint/restart in the middle."""
    from repro.launch.train import run_training
    res = run_training("stablelm-3b", smoke=True, steps=30, batch=4, seq=32,
                       ckpt_dir=str(tmp_path), ckpt_every=10,
                       optimizer="adamw", lr=3e-3, fail_at=(17,),
                       log_every=100, print_fn=lambda *a, **k: None)
    assert res.final_step == 30
    assert res.restarts == 1
    losses = [m["loss"] for m in res.metrics_history]
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, monkeypatch, from_env):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and the code sets
    no directory of its own; otherwise the cache is the checkout's
    gitignored .jax_cache/."""
    from pathlib import Path

    from repro.launch import compile_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert compile_cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == \
                saved["jax_compilation_cache_dir"]
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = compile_cache.enable_compile_cache()
            assert path == jax.config.jax_compilation_cache_dir
            root = Path(compile_cache.__file__).resolve().parents[3]
            assert Path(path) == root / ".jax_cache"
            assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
