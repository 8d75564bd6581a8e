"""Device-resident decode-loop battery (DESIGN.md §12).

Chunked multi-step decode (up to `decode_chunk` steps inside one jitted
`lax.scan`, tokens fed back on device) must reproduce the single-step
scheduler token-for-token: all codecs, EOS mid-chunk, admission mid-drain,
temperature sampling, and under a 2x1 mesh. Plus the decode-GeMV regime
checks: the decode step's jaxpr must never materialize a dense f32 (K, N)
weight for compressed params, the oracle's GeMV path must be bit-identical
to the full-matrix reference, the fused Pallas GEMV must match it within
the f32 summation-order bound, and the route must follow backend and M."""
import numpy as np
import pytest
import jax
import jax.extend
import jax.numpy as jnp

from repro.configs.base import get_smoke_config
from repro.core.compression import CompressedTensor, compress
from repro.core.decompress import compress_tree
from repro.core.formats import get_spec
from repro.kernels import ops, ref
from repro.models.model import Model
from repro.serve.engine import GenerationEngine

MIXED_LENGTHS = (4, 19, 11, 26, 7)


def _prompts(vocab, lengths=MIXED_LENGTHS, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


@pytest.fixture(scope="module")
def llama():
    cfg = get_smoke_config("llama3-8b")
    m = Model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _run(m, params, prompts, n_steps, *, chunk, eos_ids=None, **kw):
    eng = GenerationEngine(
        m, params, max_len=64, block_size=8, max_slots=2,
        decode_chunk=chunk, **kw,
    )
    eos_ids = eos_ids or {}
    rids = [
        eng.submit(p, max_new_tokens=n_steps, eos_id=eos_ids.get(i))
        for i, p in enumerate(prompts)
    ]
    done = eng.run_until_drained()
    return [done[r] for r in rids], eng


# ---------------------------------------------------------------------------
# chunked == single-step golden equivalence
# ---------------------------------------------------------------------------

def test_chunked_matches_single_step_mixed_lengths(llama):
    """Admission mid-drain: 5 mixed-length requests through 2 slots, so the
    queue refills slots across several chunk boundaries."""
    m, params = llama
    prompts = _prompts(m.cfg.vocab_size)
    want, _ = _run(m, params, prompts, 6, chunk=1)
    for chunk in (2, 4, 8):
        got, _ = _run(m, params, prompts, 6, chunk=chunk)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("fmt", ["bf8_100", "bf8_20", "mxfp4_100", "int8_50",
                                 "nf4_50"])
def test_chunked_matches_single_step_all_codecs(llama, fmt):
    """The device-resident loop with DECA-compressed weights on the decode
    critical path, for every compression format."""
    m, params = llama
    c = compress_tree(params, get_spec(fmt))
    prompts = _prompts(m.cfg.vocab_size, lengths=(5, 18, 9))
    want, _ = _run(m, c, prompts, 4, chunk=1)
    got, _ = _run(m, c, prompts, 4, chunk=4)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_chunked_eos_mid_chunk(llama):
    """A request whose EOS lands mid-chunk stops exactly there: the device
    done-flag masks the remaining writes, the host discards the junk tail,
    and the pages go back to the pool."""
    m, params = llama
    prompts = _prompts(m.cfg.vocab_size, lengths=(4, 9))
    n_steps = 10  # chunk=8 covers token indices 1..8: EOS below 8 is mid-chunk
    ref_out, _ = _run(m, params, prompts, n_steps, chunk=1)
    seq = ref_out[0]
    stop = next(
        (i for i in range(1, len(seq)) if seq[i] not in seq[:i].tolist()), 0
    )
    assert 0 < stop < 8, "need an EOS strictly inside the first chunk"
    eos = int(seq[stop])
    want, _ = _run(m, params, prompts, n_steps, chunk=1, eos_ids={0: eos})
    got, eng = _run(m, params, prompts, n_steps, chunk=8, eos_ids={0: eos})
    assert got[0][-1] == eos and len(got[0]) == stop + 1
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert eng.kv.free_blocks == eng.kv.num_blocks


def test_chunked_matches_single_step_temperature(llama):
    """Keyed sampling inside the scan folds the same (rid, token-index)
    stream as the host sampler — temperature traffic is chunk-invariant."""
    m, params = llama
    prompts = _prompts(m.cfg.vocab_size, lengths=(6, 14, 9))
    want, _ = _run(m, params, prompts, 5, chunk=1, temperature=0.8)
    got, _ = _run(m, params, prompts, 5, chunk=4, temperature=0.8)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_chunked_matches_dense_golden(llama):
    """Transitively: chunked paged decode == the dense per-request ring
    cache (the PR 2/3 golden battery), with compressed weights."""
    m, params = llama
    c = compress_tree(params, get_spec("mxfp4_100"))
    prompts = _prompts(m.cfg.vocab_size, lengths=(5, 18))
    want = [
        GenerationEngine(m, c, max_len=64, paged=False)
        .generate(p[None], 4)[0]
        for p in prompts
    ]
    got, _ = _run(m, c, prompts, 4, chunk=4)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


@pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices (set XLA_FLAGS=--xla_force_host_platform_device_count)",
)
def test_chunked_matches_single_step_under_mesh(llama):
    """The device-resident loop over a (data=2, model=1) mesh."""
    from repro.launch.mesh import make_test_mesh

    m, params = llama
    c = compress_tree(params, get_spec("mxfp4_100"))
    prompts = _prompts(m.cfg.vocab_size, lengths=(4, 19, 11))
    want, _ = _run(m, c, prompts, 4, chunk=1)
    got, _ = _run(m, c, prompts, 4, chunk=4, mesh=make_test_mesh(2, 1))
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# scheduler accounting and sampling-key hygiene
# ---------------------------------------------------------------------------

def test_prefill_stats_recorded(llama):
    """Prefill work is accounted: calls, padded token-steps, real tokens —
    so occupancy stats no longer overstate efficiency for prompt-heavy
    traffic (the padded waste is visible)."""
    m, params = llama
    prompts = _prompts(m.cfg.vocab_size, lengths=(4, 19, 11))
    _, eng = _run(m, params, prompts, 3, chunk=4)
    st = eng.scheduler.stats()
    assert st["prefill_calls"] >= 2  # 2 slots, 3 requests -> >= 2 rounds
    assert st["prefill_real_tokens"] == sum(len(p) for p in prompts)
    assert st["prefill_token_steps"] >= st["prefill_real_tokens"]
    assert 0.0 <= st["prefill_padding_waste"] < 1.0
    assert st["decode_chunks"] <= st["decode_steps"]


def test_inactive_slots_sample_with_sentinel_rid(llama):
    """Regression: inactive decode slots used to sample with rid 0 / step 0,
    colliding with real request 0's key stream. They must carry rid -1."""
    m, params = llama
    eng = GenerationEngine(
        m, params, max_len=64, block_size=8, max_slots=3, decode_chunk=1,
        temperature=0.8,
    )
    seen = []
    orig = eng.scheduler._sample

    def spy(logits, rids, steps):
        seen.append(np.asarray(rids).copy())
        return orig(logits, rids, steps)

    eng.scheduler._sample = spy
    eng.submit(_prompts(m.cfg.vocab_size, lengths=(6,))[0], max_new_tokens=3)
    eng.run_until_drained()
    decode_rids = [r for r in seen if len(r) == 3]
    assert decode_rids, "expected decode-step sampling over all slots"
    for rids in decode_rids:
        assert (rids[1:] == -1).all(), "inactive slots must use the sentinel"
        assert rids[0] == 0


# ---------------------------------------------------------------------------
# decode-GeMV regime: no dense (K, N) materialization, bit-identity
# ---------------------------------------------------------------------------

def _eqn_avals(jaxpr):
    """All output avals of all equations, recursing into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for p in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                p, is_leaf=lambda x: isinstance(
                    x, (jax.extend.core.Jaxpr, jax.extend.core.ClosedJaxpr)
                )
            ):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqn_avals(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqn_avals(sub)


def test_decode_step_never_materializes_dense_weight():
    """Acceptance: no dense (K, N) intermediate — f32 *or* bf16 — appears in
    the jaxpr of the device-resident decode chunk for any compressed
    weight. The GeMV tiles keep the peak intermediate at (K, block_n).

    Uses widths where no weight's full (K, N) can coincide with another
    weight's legitimate (K, block_n) GeMV tile (on the default smoke config
    wq's (64, 32) tile aliases wk's full (64, 32) shape)."""
    import dataclasses

    cfg = dataclasses.replace(
        get_smoke_config("llama3-8b"), n_kv_heads=4, d_ff=192
    )
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    c = compress_tree(params, get_spec("bf8_50"))
    eng = GenerationEngine(
        m, c, max_len=64, block_size=8, max_slots=2, decode_chunk=4
    )
    w_shapes = {
        ct.shape
        for ct in jax.tree_util.tree_leaves(
            eng.params, is_leaf=lambda x: isinstance(x, CompressedTensor)
        )
        if isinstance(ct, CompressedTensor)
    }
    assert w_shapes, "smoke model must have compressed FC weights"

    C, M, MB = 4, 2, eng.max_blocks
    F = M * ((C + 7) // 8 + 1)
    i32 = np.int32
    jaxpr = jax.make_jaxpr(
        lambda *a: eng._paged_decode_chunk(*a, greedy=True)
    )(
        eng.params, eng.kv.pools,
        np.zeros((M, 1), i32), np.zeros((M, MB), i32),
        np.zeros((C, M, 1), i32), np.zeros((C, M, 1), i32),
        np.zeros((C, M, 1), i32), np.zeros((C, F), i32),
        np.ones((C, M), i32),
        np.zeros(M, np.uint32), np.zeros(M, np.uint32),
        np.full(M, C, i32), np.full(M, -1, i32), np.ones(M, bool),
        np.float32(1.0), jax.random.PRNGKey(0),
    )
    bad = [
        a for a in _eqn_avals(jaxpr.jaxpr)
        if getattr(a, "shape", None) in w_shapes
        and a.dtype in (jnp.float32, jnp.bfloat16)
    ]
    assert not bad, f"dense weight materialized in decode step: {bad}"


@pytest.mark.parametrize("m_rows", [1, 4, 17])
@pytest.mark.parametrize("fmt", ["bf8_50", "mxfp4_100", "int4_25", "nf4_100"])
def test_gemv_bit_identical_to_reference(fmt, m_rows):
    """The decode-shaped GeMV (N-tiled, group-local dequant-and-contract)
    is bit-identical to the full-matrix decompress_gemm — tiling over N
    keeps every output element a single full-K dot."""
    rng = np.random.default_rng(3)
    K, N = 128, 96
    w = rng.standard_normal((K, N)).astype(np.float32)
    ct = compress(w, get_spec(fmt))
    x = jnp.asarray(rng.standard_normal((m_rows, K)), jnp.float32)
    want = np.asarray(ref.decompress_gemm(x, ct))
    got = np.asarray(ref.decompress_gemv(x, ct))
    np.testing.assert_array_equal(got, want)
    # the public entry point routes small M to the GeMV path
    via_ops = np.asarray(ops.decompress_gemm(x, ct, impl="ref"))
    np.testing.assert_array_equal(via_ops, want)


# (K, N, geometry): the first is the shape this check began with, at the
# default geometry (one block, one sub-block); the second spans two K
# blocks of two sub-blocks each and a ragged last N block (200 = 128 + 72)
GEMV_GRIDS = {
    "K256-N96": (256, 96, {}),
    "K1024-N200": (1024, 200, dict(block_n=128, block_k=512)),
}


@pytest.mark.parametrize("grid", list(GEMV_GRIDS))
@pytest.mark.parametrize("m_rows", [1, 4, 8, 32])
@pytest.mark.parametrize("fmt", ["mxfp4_100", "bf8_50", "int4_25", "nf4_100"])
def test_gemv_pallas_grid_variant_matches_oracle(fmt, m_rows, grid):
    """The fused GEMV (interpreted here) against the full-matrix oracle.

    Both compute sum_k x[m, k] * w[k, n] with x and w rounded to bf16, so
    every product is exact in f32 (8-bit by 8-bit significands), and both
    add the same K products in f32, in different orders: the oracle in
    one dot, the kernel per value plane, sub-block and K block. Any order
    of adding K terms errs by at most (K - 1) * u * sum_k |x_k w_k| with
    u = 2**-24 (Higham, Accuracy and Stability, §4.2), so the two differ
    by at most twice that. The weights enter as the oracle decodes them
    (`ref.decompress`), so a decode that differed in a single weight
    would exceed the bound only where its product does; the values are
    checked exactly in tests/test_codecs.py. That bound is a worst case,
    about 1000x the gaps these cases show (under 4e-5), so every case is
    also held to the 1e-4 this check set the GEMV before."""
    from repro.kernels.deca_gemm import decompress_gemv_pallas

    K, N, geometry = GEMV_GRIDS[grid]
    rng = np.random.default_rng(4)
    w = rng.standard_normal((K, N)).astype(np.float32)
    ct = compress(w, get_spec(fmt))
    x = jnp.asarray(rng.standard_normal((m_rows, K)), jnp.float32)
    want = np.asarray(ref.decompress_gemm(x, ct))
    got = np.asarray(
        decompress_gemv_pallas(x, ct, interpret=True, **geometry)
    )
    assert got.shape == want.shape == (m_rows, N)
    gap = np.abs(got - want)
    assert (gap <= _f32_order_bound(x, ct)).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)


def _f32_order_bound(x, ct):
    """2 (K - 1) u sum_k |x_k w_k|: how far two f32 sums of the same K
    exact products can lie apart (see the test above)."""
    xb = np.asarray(x.astype(jnp.bfloat16), np.float64)
    wb = np.asarray(ref.decompress(ct, out_dtype=jnp.bfloat16), np.float64)
    return 2 * (x.shape[1] - 1) * 2.0 ** -24 * (np.abs(xb) @ np.abs(wb))


def test_gemv_route_follows_backend_and_m(monkeypatch):
    """`ops.decompress_gemm` with no implementation asked for: on a TPU
    (the backend test patched, the kernel interpreted) M <= GEMV_MAX_M
    runs the fused kernel and M above it the GEMM route; explicit
    `impl="ref"` runs the oracle anywhere. The trace-time counter counts
    each GEMV call site under the implementation it lowered to."""
    from repro.obs.metrics import TRACE_COUNTS

    def sites():
        return {
            r: TRACE_COUNTS.counter(f"kernels.gemv_sites.{r}", "sites").value
            for r in ("fused", "ref")
        }

    def pallas_calls(f, *args):
        return str(jax.make_jaxpr(f)(*args)).count("pallas_call")

    rng = np.random.default_rng(5)
    K, N = 256, 128
    ct = compress(rng.standard_normal((K, N)).astype(np.float32), get_spec("mxfp4_100"))
    x = jnp.asarray(rng.standard_normal((4, K)), jnp.float32)
    big = jnp.asarray(rng.standard_normal((ops.GEMV_MAX_M + 1, K)), jnp.float32)
    oracle = np.asarray(ref.decompress_gemv(x, ct))

    # on this backend, nothing changes: the oracle
    before = sites()
    assert pallas_calls(lambda x: ops.decompress_gemm(x, ct), x) == 0
    assert sites() == {"fused": before["fused"], "ref": before["ref"] + 1}

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    before = sites()
    assert pallas_calls(lambda x: ops.decompress_gemm(x, ct), x) == 1
    assert sites() == {"fused": before["fused"] + 1, "ref": before["ref"]}
    got = np.asarray(ops.decompress_gemm(x, ct))
    assert (np.abs(got - oracle) <= _f32_order_bound(x, ct)).all()

    before = sites()
    assert pallas_calls(lambda x: ops.decompress_gemm(x, ct), big) == 0
    assert sites() == before  # the GEMM route is no GEMV site

    before = sites()
    via_ref = ops.decompress_gemm(x, ct, impl="ref")
    np.testing.assert_array_equal(np.asarray(via_ref), oracle)
    assert pallas_calls(lambda x: ops.decompress_gemm(x, ct, impl="ref"), x) == 0
    assert sites() == {"fused": before["fused"], "ref": before["ref"] + 2}


def test_mesh_engine_traces_the_jnp_gemv(llama, monkeypatch):
    """A mesh engine's weights are GSPMD-sharded, and GSPMD cannot
    partition a Pallas call: with the backend test patched to a TPU, the
    engine under a mesh traces every GEMV site on the jnp oracle, while
    the same engine without a mesh takes the fused kernel."""
    from repro.launch.mesh import make_test_mesh
    from repro.obs.metrics import TRACE_COUNTS

    def sites():
        return {
            r: TRACE_COUNTS.counter(f"kernels.gemv_sites.{r}", "sites").value
            for r in ("fused", "ref")
        }

    m, params = llama
    c = compress_tree(params, get_spec("mxfp4_100"))
    prompts = _prompts(m.cfg.vocab_size, lengths=(5, 9))
    monkeypatch.setattr(ops, "on_tpu", lambda: True)

    before = sites()
    meshed, _ = _run(m, c, prompts, 3, chunk=2, mesh=make_test_mesh(1, 1))
    after = sites()
    assert after["fused"] == before["fused"]
    assert after["ref"] > before["ref"]

    plain, _ = _run(m, c, prompts, 3, chunk=2)
    assert sites()["fused"] > after["fused"]
    assert all(len(t) == 3 for t in meshed + plain)
