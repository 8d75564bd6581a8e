"""Serving-path tests: compressed-weight generation (the paper's technique
end-to-end), engine behaviour, impl equivalence (ref vs pallas), and the
paged-KV golden battery — mixed-length prompts through the continuous-
batching scheduler must reproduce dense per-request generation
token-for-token (DESIGN.md §10)."""
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import get_smoke_config
from repro.core.compression import CompressedTensor
from repro.core.decompress import (
    compress_tree, compressed_bytes, mm, use_impl,
)
from repro.core.formats import get_spec
from repro.models.model import Model
from repro.serve.engine import GenerationEngine

MIXED_LENGTHS = (4, 19, 11, 26, 7)


def _prompts(vocab, lengths=MIXED_LENGTHS, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


def _dense_per_request(m, params, prompts, n_steps, **kw):
    """Golden reference: each request alone through the legacy ring cache."""
    return [
        GenerationEngine(m, params, max_len=64, paged=False, **kw)
        .generate(p[None], n_steps)[0]
        for p in prompts
    ]


@pytest.fixture(scope="module")
def llama():
    cfg = get_smoke_config("llama3-8b")
    m = Model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def test_compress_tree_targets_fc_weights(llama):
    m, params = llama
    c = compress_tree(params, get_spec("bf8_100"))
    leaves = jax.tree_util.tree_leaves(
        c, is_leaf=lambda x: isinstance(x, CompressedTensor)
    )
    n_ct = sum(isinstance(l, CompressedTensor) for l in leaves)
    assert n_ct > 0
    # embeddings are never compressed (gather, not GeMM)
    assert not isinstance(c["embed"], CompressedTensor)
    assert compressed_bytes(c) < compressed_bytes(params)


def test_compressed_forward_close_to_dense(llama):
    """bf16 'compression' at 100% density is numerically lossless (modulo
    bf16 roundtrip), so logits must match the dense model closely."""
    m, params = llama
    c = compress_tree(params, get_spec("bf16_100"))
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    dense, _, _ = m.forward(params, tokens=tokens)
    comp, _, _ = m.forward(c, tokens=tokens)
    np.testing.assert_allclose(
        np.asarray(dense, np.float32), np.asarray(comp, np.float32), atol=2e-2
    )


def test_ref_and_pallas_serving_agree(llama):
    m, params = llama
    c = compress_tree(params, get_spec("bf8_50"))
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    with use_impl("ref"):
        a, _, _ = m.forward(c, tokens=tokens)
    with use_impl("pallas"):
        b, _, _ = m.forward(c, tokens=tokens)
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-2
    )


def test_generation_engine_shapes(llama):
    m, params = llama
    prompts = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    out = GenerationEngine(m, params, max_len=32).generate(prompts, 6)
    assert out.shape == (2, 6)
    assert (out >= 0).all() and (out < m.cfg.vocab_size).all()


def test_engine_holds_weights_on_device(llama):
    """mesh=None: the engine uploads every CompressedTensor plane, draft
    tree included, once at construction — no numpy plane rides along to
    be re-copied on each jitted call."""
    from repro.serve.engine import SpecConfig

    m, params = llama
    c = compress_tree(params, get_spec("int4_25"))
    eng = GenerationEngine(
        m, c, max_len=32, spec_decode=SpecConfig(k=2, draft_codec="bf8_50")
    )
    for tree in (eng.params, eng.draft_params):
        cts = [
            l for l in jax.tree_util.tree_leaves(
                tree, is_leaf=lambda x: isinstance(x, CompressedTensor)
            ) if isinstance(l, CompressedTensor)
        ]
        assert cts
        for ct in cts:
            assert ct.mask is not None  # both codecs here are sparse
            for plane in (ct.codes, ct.mask, ct.scales):
                assert plane is None or isinstance(plane, jax.Array), type(plane)


def test_generation_deterministic_greedy(llama):
    m, params = llama
    prompts = np.array([[3, 1, 4, 1, 5, 9]], np.int32)
    a = GenerationEngine(m, params, max_len=32).generate(prompts, 5)
    b = GenerationEngine(m, params, max_len=32).generate(prompts, 5)
    np.testing.assert_array_equal(a, b)


def test_compressed_generation_all_formats(llama):
    m, params = llama
    prompts = np.array([[1, 2, 3, 4, 5, 6, 7, 8]], np.int32)
    for fmt in ("bf8_100", "bf8_20", "mxfp4_100", "int8_50"):
        c = compress_tree(params, get_spec(fmt))
        out = GenerationEngine(m, c, max_len=32).generate(prompts, 4)
        assert out.shape == (1, 4), fmt


# ---------------------------------------------------------------------------
# paged KV + continuous batching: golden equivalence vs dense per-request
# ---------------------------------------------------------------------------

def test_paged_matches_dense_mixed_lengths(llama):
    """Mixed-length prompts through the paged scheduler (2 slots, so the
    queue drains through admission/eviction/page-reuse) give token-for-token
    the dense per-request greedy output — and no request is ever padded to
    max_len: each holds exactly ceil(len/block_size) pages."""
    m, params = llama
    prompts = _prompts(m.cfg.vocab_size)
    n_steps = 5
    want = _dense_per_request(m, params, prompts, n_steps)

    eng = GenerationEngine(
        m, params, max_len=64, block_size=8, max_slots=2, num_blocks=10
    )
    rids = [eng.submit(p, max_new_tokens=n_steps) for p in prompts]
    done = eng.run_until_drained()
    for rid, ref, p in zip(rids, want, prompts):
        np.testing.assert_array_equal(done[rid], ref)
        kv_len = len(p) + n_steps - 1
        assert eng.scheduler.request_peaks[rid] == math.ceil(kv_len / 8)

    st = eng.scheduler.stats()
    assert st["peak_blocks"] <= 10
    assert st["padding_waste_saved"] > 0.5  # short requests ≪ max_len pages
    assert eng.kv.free_blocks == 10  # every page returned


@pytest.mark.parametrize("fmt", ["bf8_100", "bf8_20", "mxfp4_100", "int8_50"])
def test_paged_matches_dense_all_formats(llama, fmt):
    """The golden equivalence holds with DECA-compressed weights on the
    decode critical path, for every compression format."""
    m, params = llama
    c = compress_tree(params, get_spec(fmt))
    prompts = _prompts(m.cfg.vocab_size, lengths=(5, 18))
    want = _dense_per_request(m, c, prompts, 3)
    eng = GenerationEngine(m, c, max_len=64, block_size=8, max_slots=2)
    rids = [eng.submit(p, max_new_tokens=3) for p in prompts]
    done = eng.run_until_drained()
    for rid, ref in zip(rids, want):
        np.testing.assert_array_equal(done[rid], ref)


@pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices (set XLA_FLAGS=--xla_force_host_platform_device_count)",
)
def test_paged_matches_dense_under_mesh(llama):
    """Paged serving over a (data=2, model=1) mesh — pool pages replicated
    on 'data', heads on 'model' — still matches unsharded dense greedy."""
    from repro.launch.mesh import make_test_mesh

    m, params = llama
    c = compress_tree(params, get_spec("mxfp4_100"))
    prompts = _prompts(m.cfg.vocab_size, lengths=(4, 19, 11))
    want = _dense_per_request(m, c, prompts, 4)
    eng = GenerationEngine(
        m, c, max_len=64, block_size=8, max_slots=2, mesh=make_test_mesh(2, 1)
    )
    rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    done = eng.run_until_drained()
    for rid, ref in zip(rids, want):
        np.testing.assert_array_equal(done[rid], ref)


def test_paged_eos_frees_slot_early(llama):
    """EOS eviction: a request that emits its eos token stops there, returns
    its pages, and the engine still drains the rest of the queue."""
    m, params = llama
    prompts = _prompts(m.cfg.vocab_size, lengths=(4, 9))
    ref = _dense_per_request(m, params, prompts, 6)
    seq = ref[0]
    # eos = the first value whose first occurrence is mid-stream (greedy
    # output repeats, so an early value may recur)
    stop = next(
        (i for i in range(1, len(seq)) if seq[i] not in seq[:i].tolist()), 0
    )
    eos = int(seq[stop])
    eng = GenerationEngine(m, params, max_len=64, block_size=8, max_slots=2)
    r0 = eng.submit(prompts[0], max_new_tokens=6, eos_id=eos)
    r1 = eng.submit(prompts[1], max_new_tokens=6)
    done = eng.run_until_drained()
    assert done[r0][-1] == eos and len(done[r0]) == stop + 1
    np.testing.assert_array_equal(done[r0], seq[: stop + 1])
    np.testing.assert_array_equal(done[r1], ref[1])
    assert eng.kv.free_blocks == eng.kv.num_blocks


def test_paged_submit_rejects_invalid_requests(llama):
    """Bad requests fail loudly at submit(), not by hanging the drain loop
    (a request larger than the whole pool can never be admitted) or by an
    opaque shape error mid-prefill (empty prompt)."""
    m, params = llama
    eng = GenerationEngine(m, params, max_len=32, block_size=8)
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.submit(np.arange(30, dtype=np.int32), max_new_tokens=8)
    with pytest.raises(ValueError, match="at least one token"):
        eng.submit(np.array([], dtype=np.int32), max_new_tokens=2)
    tiny = GenerationEngine(m, params, max_len=32, block_size=8, num_blocks=2)
    with pytest.raises(ValueError, match="pages"):
        tiny.submit(np.arange(20, dtype=np.int32), max_new_tokens=4)


# ---------------------------------------------------------------------------
# sampling keys: per-(request, step), independent of batch composition
# ---------------------------------------------------------------------------

def test_sampled_tokens_independent_of_admission_order(llama):
    """Regression for the host-side split bug: keys are now a pure function
    of (seed, request id, token index), so changing max_slots — which
    changes admission timing and batch composition — cannot change any
    request's sampled tokens."""
    m, params = llama
    prompts = _prompts(m.cfg.vocab_size, lengths=(6, 14, 9))
    outs = []
    for slots in (1, 3):
        eng = GenerationEngine(
            m, params, max_len=64, temperature=0.8, block_size=8,
            max_slots=slots,
        )
        rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
        done = eng.run_until_drained()
        outs.append([done[r] for r in rids])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def test_dense_sampling_independent_of_batch(llama):
    """Same regression on the legacy batch path: row 0 sampled alone equals
    row 0 sampled alongside another request (the old engine drew one key for
    the whole batch, so batch shape changed every row's tokens)."""
    m, params = llama
    prompts = _prompts(m.cfg.vocab_size, lengths=(6, 6))
    a = GenerationEngine(
        m, params, max_len=32, temperature=0.8, paged=False
    ).generate(prompts[0][None], 5)
    b = GenerationEngine(
        m, params, max_len=32, temperature=0.8, paged=False
    ).generate(np.stack(prompts), 5)
    np.testing.assert_array_equal(a[0], b[0])


def test_greedy_prelude_does_not_shift_sampled_tokens(llama):
    """Regression for the skipped-split bug: greedy sampling must not
    advance any PRNG state. A probe request gets the same tokens whether
    the request before it was served greedy or with temperature — under
    the old engine, temperature traffic advanced a shared key that greedy
    traffic left untouched, entangling every later request."""
    m, params = llama
    prompts = _prompts(m.cfg.vocab_size, lengths=(6, 9))

    def probe_after_prelude(prelude_temp):
        eng = GenerationEngine(
            m, params, max_len=64, block_size=8, temperature=prelude_temp
        )
        eng.submit(prompts[1], max_new_tokens=4)  # rid 0: the prelude
        eng.run_until_drained()
        eng.temperature = 0.8
        rid = eng.submit(prompts[0], max_new_tokens=4)  # rid 1: the probe
        return eng.run_until_drained()[rid]

    np.testing.assert_array_equal(
        probe_after_prelude(0.0), probe_after_prelude(0.8)
    )


def test_moe_compressed_serving():
    """Expert FFNs are compressible too (stacked per-expert compression)."""
    cfg = get_smoke_config("grok-1-314b")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    c = compress_tree(params, get_spec("bf8_100"))
    tokens = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    dense, _, _ = m.forward(params, tokens=tokens)
    comp, _, _ = m.forward(c, tokens=tokens)
    assert np.isfinite(np.asarray(comp, np.float32)).all()
    # bf8 is lossy; just require correlation, not equality
    d, cc = np.asarray(dense, np.float32).ravel(), np.asarray(comp, np.float32).ravel()
    assert np.corrcoef(d, cc)[0, 1] > 0.98
