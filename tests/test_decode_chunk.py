"""Device-resident decode-loop battery (DESIGN.md §12).

Chunked multi-step decode (up to `decode_chunk` steps inside one jitted
`lax.scan`, tokens fed back on device) must reproduce the single-step
scheduler token-for-token: all codecs, EOS mid-chunk, admission mid-drain,
temperature sampling, and under a 2x1 mesh. Plus the decode-GeMV regime
checks: the decode step's jaxpr must never materialize a dense f32 (K, N)
weight for compressed params, and the GeMV path must be bit-identical to
the full-matrix reference."""
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


def test_gemv_pallas_grid_variant_matches_oracle():
    from repro.kernels.deca_gemm import decompress_gemv_pallas

    rng = np.random.default_rng(4)
    K, N = 256, 96
    w = rng.standard_normal((K, N)).astype(np.float32)
    ct = compress(w, get_spec("bf8_50"))
    for m_rows in (1, 4, 8):
        x = jnp.asarray(rng.standard_normal((m_rows, K)), jnp.float32)
        want = np.asarray(ref.decompress_gemm(x, ct))
        got = np.asarray(decompress_gemv_pallas(x, ct, interpret=True))
        np.testing.assert_allclose(got, want, atol=1e-4)
