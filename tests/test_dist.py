"""Unit tests for the repro.dist subsystem beyond the seed suite:
spec builders on compressed pytrees, constraint identities with no mesh,
optimizer-state spec inheritance, error feedback under repeated steps,
deterministic fault injection, and kernel block-geometry validation."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.compression import CompressedTensor, compress
from repro.core.formats import get_spec
from repro.dist import sharding as sh
from repro.dist.fault import FaultInjector, InjectedFault, StragglerWatchdog
from repro.dist.grad_compression import make_compressed_allreduce
from repro.launch.mesh import make_mesh


class Ctx:
    """Rule-level context: spec builders only read axis_sizes/fsdp/mode."""
    axis_sizes = {"pod": 2, "data": 4, "model": 8}
    fsdp = True
    mode = "train"


def test_constrain_is_identity_without_mesh():
    x = jnp.ones((2, 3, 4))
    assert sh.constrain(x, "bsd") is x
    q = jnp.ones((2, 3, 4, 5))
    out = sh.constrain_qkv(q, q, q)
    assert all(o is q for o in out)


def test_spec_for_never_reuses_an_axis():
    # both dims are model-shardable; only the first gets the axis
    assert sh.spec_for((8, 8), ("model", "model"), Ctx) == P("model", None)
    # fsdp role is inert when the ctx disables it
    class NoFsdp(Ctx):
        fsdp = False
    assert sh.spec_for((16, 16), ("fsdp", "model"), NoFsdp) == P(None, "model")
    assert sh.spec_for((16, 16), ("fsdp", "model"), Ctx) == P("data", "model")


def test_param_spec_tree_compressed_leaves():
    """codes/mask/scales shard along the dense (K, N) axes — with the K-axis
    divisibility re-checked against the group dim ng."""
    spec = get_spec("int8_50")  # sparse + scaled: all three components
    w_big = np.random.default_rng(0).standard_normal((256, 128)).astype(np.float32)
    w_small = np.random.default_rng(1).standard_normal((64, 128)).astype(np.float32)
    tree = {"mlp": {"w_up": compress(w_big, spec), "w_gate": compress(w_small, spec)}}
    specs = sh.param_spec_tree(tree, Ctx)

    big = specs["mlp"]["w_up"]
    assert isinstance(big, CompressedTensor)
    # K=256 -> ng=8, divisible by data=4; N=128 divisible by model=8
    assert big.codes == P("data", None, "model")
    assert big.mask == P("data", "model")
    assert big.scales == P("data", "model")

    small = specs["mlp"]["w_gate"]
    # K=64 % 4 == 0 but ng=2 % 4 != 0: K-axis must fall back to replication
    assert small.codes == P(None, None, "model")
    assert small.mask == P(None, "model")


def test_data_spec_tree_compressed_and_batches():
    spec = get_spec("bf8_100")  # dense-quantized: codes only
    ct = compress(
        np.random.default_rng(2).standard_normal((256, 64)).astype(np.float32),
        spec,
    )
    tree = {
        "w": ct,
        "tokens": jax.ShapeDtypeStruct((16, 32), jnp.int32),
        "positions": jax.ShapeDtypeStruct((3, 16, 32), jnp.int32),
    }
    specs = sh.data_spec_tree(tree, Ctx)
    # CompressedTensor leaf: consistent with its dense (K, N) = (256, 64) shape
    assert specs["w"].codes == P("data", None, "model")
    assert specs["w"].mask is None and specs["w"].scales is None
    # batch dim over ('pod','data')=8; M-RoPE stream dim replicated
    assert specs["tokens"] == P(("pod", "data"), None)
    assert specs["positions"] == P(None, ("pod", "data"), None)


def test_opt_spec_tree_adafactor_factored():
    from repro.optim.optimizers import Adafactor

    aparams = {"w": jax.ShapeDtypeStruct((64, 128), jnp.float32),
               "norm": jax.ShapeDtypeStruct((64,), jnp.float32)}
    aopt = jax.eval_shape(Adafactor().init, aparams)
    specs = sh.opt_spec_tree(aopt, aparams, Ctx)
    # param w -> P('data','model'); vr drops the last dim, vc the row dim
    assert specs["v"]["w"]["vr"] == P("data")
    assert specs["v"]["w"]["vc"] == P("model")
    assert specs["v"]["norm"]["v"] == P(None)


def test_compressed_allreduce_error_feedback_reduces_bias():
    """Over repeated steps, error feedback keeps the accumulated average
    near the true gradient sum; naive quantization accumulates bias."""
    mesh = make_mesh((1,), ("data",))
    # one outlier per group forces a coarse scale -> visible per-step bias
    g_np = np.full((128,), 0.03, np.float32)
    g_np[0] = 1.0
    g = {"w": jnp.asarray(g_np)}
    allreduce, init_err = make_compressed_allreduce(mesh, g, method="int8")

    n_steps = 16
    err = init_err(g)
    total_ef = np.zeros_like(g_np)
    total_naive = np.zeros_like(g_np)
    for _ in range(n_steps):
        avg, err = allreduce(g, err)
        total_ef += np.asarray(avg["w"])
        naive, _ = allreduce(g, init_err(g))
        total_naive += np.asarray(naive["w"])
    target = n_steps * g_np
    ef_bias = np.abs(total_ef - target).max()
    naive_bias = np.abs(total_naive - target).max()
    assert ef_bias < 0.01, ef_bias
    assert ef_bias < naive_bias


def test_compressed_allreduce_bf8_method():
    mesh = make_mesh((1,), ("data",))
    g = {"w": jnp.asarray(
        np.random.default_rng(3).standard_normal((64,)).astype(np.float32)
    )}
    allreduce, init_err = make_compressed_allreduce(mesh, g, method="bf8")
    avg, err = allreduce(g, init_err(g))
    np.testing.assert_allclose(np.asarray(avg["w"]), np.asarray(g["w"]), atol=0.2)
    np.testing.assert_allclose(
        np.asarray(err["w"]),
        np.asarray(g["w"]) - np.asarray(avg["w"]),
        atol=1e-6,
    )
    with pytest.raises(ValueError):
        make_compressed_allreduce(mesh, g, method="fp3")


def test_grad_compression_threads_through_train_loop():
    """Regression: `grad_compression='int8'` must work end-to-end through
    make_train_step/train_loop — the error-feedback state has to make it
    around the loop (it used to be built and then dropped), and training
    with the quantized all-reduce must still fit the synthetic task."""
    from repro.configs.base import ShapeConfig, get_smoke_config
    from repro.data.pipeline import SyntheticPipeline
    from repro.models.model import Model
    from repro.train.trainer import make_train_step, train_loop

    cfg = get_smoke_config("llama3.2-1b")
    model = Model(cfg)
    mesh = make_mesh((1,), ("data",))
    from repro.optim.optimizers import AdamW

    opt = AdamW(lr=3e-3, weight_decay=0.0)
    pipe = SyntheticPipeline(cfg, ShapeConfig("t", "train", 16, 8), seed=9)

    # the compressed step has the 5-arg error-feedback signature
    with pytest.raises(ValueError, match="mesh"):
        make_train_step(model, opt, grad_compression="int8")
    step = make_train_step(model, opt, remat=False,
                           grad_compression="int8", mesh=mesh)
    params = model.init(jax.random.PRNGKey(0))
    err0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch(0).items()}
    p1, _, m1, err1 = step(params, opt.init(params), batch, 0, err0)
    assert np.isfinite(float(m1["loss"]))
    # error feedback is live: the int8 residual of a real gradient is nonzero
    err_norm = sum(float(jnp.sum(jnp.abs(e))) for e in jax.tree.leaves(err1))
    assert err_norm > 0.0

    # end-to-end: train_loop owns the state and the model still fits
    params = model.init(jax.random.PRNGKey(0))
    _, _, history = train_loop(
        model, params, opt.init(params), pipe, n_steps=12,
        train_step=jax.jit(make_train_step(
            model, opt, remat=False, grad_compression="int8", mesh=mesh)),
        grad_compression="int8", mesh=mesh,
    )
    losses = [m["loss"] for _, m, _ in history]
    assert losses[-1] < losses[0] - 0.3, losses


def test_fault_injector_seeded_determinism():
    a = FaultInjector(seed=7, p_fail=0.2)
    b = FaultInjector(seed=7, p_fail=0.2)
    actions = [a.action_for(s) for s in range(100)]
    assert actions == [b.action_for(s) for s in range(100)]
    assert any(x == "crash" for x in actions)
    # each scheduled step fires exactly once across restarts
    step = next(s for s, x in enumerate(actions) if x == "crash")
    with pytest.raises(InjectedFault):
        a.poll(step)
    a.poll(step)  # second poll: transient fault already fired


def test_fault_injector_slow_action_hits_watchdog():
    """A planned 'slow' step sleeps instead of crashing, so the straggler
    watchdog (not the restart machinery) is what catches it."""
    import time

    inj = FaultInjector(plan={5: "slow"}, slow_s=0.05)
    w = StragglerWatchdog(factor=3.0)
    for step in range(8):
        t0 = time.monotonic()
        inj.poll(step)  # never raises for 'slow'
        time.sleep(0.003)
        flagged = w.observe(step, time.monotonic() - t0)
        assert flagged == (step == 5)
    assert w.events == [5]


def test_straggler_watchdog_report():
    w = StragglerWatchdog(factor=2.0)
    for i in range(6):
        w.observe(i, 0.01)
    assert w.observe(6, 0.05) is True
    r = w.report()
    assert r["n_stragglers"] == 1 and r["events"] == [6]
    assert r["n_steps"] == 7
    assert r["mean_step_s"] == pytest.approx(0.01)


def test_decompress_pallas_rejects_partial_groups():
    """K not a multiple of the group must fail loudly, not underflow the
    block-shrink loop to zero."""
    from repro.kernels.deca_decompress import decompress_pallas
    from repro.kernels.deca_gemm import decompress_gemm_pallas

    spec = get_spec("bf8_100")
    bad = CompressedTensor(
        codes=jnp.zeros((2, spec.group, 8), jnp.uint8),
        mask=None,
        scales=None,
        spec=spec,
        shape=(65, 8),  # 65 % 32 != 0
    )
    with pytest.raises(ValueError, match="not a multiple"):
        decompress_pallas(bad)
    with pytest.raises(ValueError, match="not a multiple"):
        decompress_gemm_pallas(jnp.zeros((4, 65), jnp.bfloat16), bad)
