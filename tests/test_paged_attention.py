"""Fused paged-attention decode battery (DESIGN.md §13).

The fused path (dequantize-on-read inside a length-bounded page walk with
an online-softmax accumulator — `ref.paged_decode_attention` and the
Pallas kernel) must match the `paged_gather_kv` + `attention_core` golden
reference to fp32-accumulator tolerance for every KV codec, mixed lengths,
windowed/softcapped attention, and end-to-end through the serving engine;
the decode-chunk jaxpr must never materialize the gathered
(B, MB*bsize, Hkv, Dh) KV view; and the Roof-Surface KV-decode term must
price the formats consistently with their byte/vop footprints.
"""
import dataclasses
import types

import numpy as np
import pytest
import jax
import jax.extend
import jax.numpy as jnp

from repro.configs.base import get_smoke_config
from repro.core import roofsurface as rs
from repro.core.codecs import kv_codec_names
from repro.kernels import mode, ops
from repro.kernels.paged_attention import paged_attention_pallas
from repro.models.layers import (
    CACHE_EMPTY_POS,
    attention_core,
    init_paged_kv_cache,
    paged_gather_kv,
    paged_update_cache,
)
from repro.models.model import Model
from repro.serve.engine import GenerationEngine
from repro.serve.paged_cache import PagedKVCache

KV_FORMATS = ("none",) + tuple(sorted(kv_codec_names()))
MIXED_LENGTHS = (5, 13, 1, 29)


class _Stub:
    cfg = types.SimpleNamespace(kv_quant="none")

    def init_paged_cache(self, *a, **k):
        return {}


def _build_pool(quant, lengths, *, bs=4, hkv=2, dh=8, mb=8, seed=0):
    """Stream per-request KV into a shared paged pool exactly as serving
    does (lazy page allocation through PagedKVCache bookkeeping)."""
    rng = np.random.default_rng(seed)
    b = len(lengths)
    num_blocks = b * mb
    pool = init_paged_kv_cache(
        num_blocks + 1, bs, hkv, dh, jnp.float32, quant=quant
    )
    cache = PagedKVCache(_Stub(), num_blocks=num_blocks, block_size=bs)
    tables = np.zeros((b, mb), np.int32)
    for i, n in enumerate(lengths):
        cache.admit(i, n)
        k = rng.standard_normal((1, n, hkv, dh)).astype(np.float32)
        v = rng.standard_normal((1, n, hkv, dh)).astype(np.float32)
        pos = np.arange(n, dtype=np.int32)[None]
        slots = cache.write_slots(i, 0, n)[None]
        fresh = jnp.asarray(cache.drain_fresh(mb))
        pool = paged_update_cache(
            pool, jnp.asarray(k), jnp.asarray(v), jnp.asarray(pos),
            jnp.asarray(slots), fresh, quant=quant,
        )
        tables[i] = cache.block_table_row(i, mb)
    return pool, jnp.asarray(tables)


def _case(quant, lengths=MIXED_LENGTHS, g=3, **geom):
    pool, tables = _build_pool(quant, lengths, **geom)
    hkv = pool["kp"].shape[2]
    dh = geom.get("dh", 8)
    hq = hkv * g
    rng = np.random.default_rng(42)
    q = jnp.asarray(rng.standard_normal((len(lengths), 1, hq, dh)), jnp.bfloat16)
    q_pos = jnp.asarray([n - 1 for n in lengths], jnp.int32)
    kv_lens = jnp.asarray(lengths, jnp.int32)
    return pool, tables, q, q_pos, kv_lens


def _gather_reference(pool, tables, q, q_pos, quant, window, softcap):
    k_all, v_all, k_pos = paged_gather_kv(pool, tables, quant=quant)
    out = attention_core(
        q, k_all, v_all, q_pos=q_pos[:, None], k_pos=k_pos,
        causal=True, window=window, softcap=softcap,
    )
    return np.asarray(out, np.float32)[:, 0]


# ---------------------------------------------------------------------------
# fused == gather golden reference, all codecs / masks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", KV_FORMATS)
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (7, 0.0), (0, 30.0)])
def test_fused_ref_matches_gather(quant, window, softcap):
    pool, tables, q, q_pos, kv_lens = _case(quant)
    want = _gather_reference(pool, tables, q, q_pos, quant, window, softcap)
    got = np.asarray(
        ops.paged_attention(
            q[:, 0], pool, tables, kv_lens, q_pos,
            quant=quant, causal=True, window=window, softcap=softcap,
            impl="ref",
        ),
        np.float32,
    )
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("quant", KV_FORMATS)
def test_pallas_kernel_matches_ref(quant):
    """The Pallas kernel (scalar-prefetched block tables, pl.when length
    skip) against the jnp while-loop oracle — same page-block math, so the
    agreement is essentially exact."""
    pool, tables, q, q_pos, kv_lens = _case(quant)
    args = (q[:, 0], pool, tables, kv_lens, q_pos)
    kw = dict(quant=quant, causal=True, window=0, softcap=0.0)
    want = np.asarray(ops.paged_attention(*args, impl="ref", **kw), np.float32)
    got = np.asarray(ops.paged_attention(*args, impl="pallas", **kw), np.float32)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("ppb", [1, 2, 4, 8])
def test_page_block_grid_invariance(ppb):
    """Any page-block size (autotune's knob) gives the same attention: the
    online-softmax fold is associative over page blocks up to f32 rounding.
    ppb=8 covers the whole-table block; ppb=1 the single-page walk."""
    quant = "int8"
    pool, tables, q, q_pos, kv_lens = _case(quant)
    outs = [
        np.asarray(
            f(
                q[:, 0], pool, tables, kv_lens, q_pos,
                quant=quant, pages_per_block=ppb,
            ),
            np.float32,
        )
        for f in (
            lambda *a, **k: ops.paged_attention(*a, impl="ref", **k),
            lambda *a, **k: paged_attention_pallas(*a, interpret=True, **k),
        )
    ]
    want = np.asarray(
        ops.paged_attention(q[:, 0], pool, tables, kv_lens, q_pos, quant=quant),
        np.float32,
    )
    for got in outs:
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_length_bound_is_exact_not_approximate():
    """Truncating the walk at the per-slot length bound changes nothing:
    pages past the bound are scrubbed-empty / null and carry the position
    sentinel, so walking all max_blocks pages gives the identical result."""
    pool, tables, q, q_pos, kv_lens = _case("bf8")
    mb, bs = tables.shape[1], pool["kp"].shape[1]
    full = jnp.full_like(kv_lens, mb * bs)
    kw = dict(quant="bf8", causal=True, window=0, softcap=0.0, impl="ref")
    bounded = np.asarray(
        ops.paged_attention(q[:, 0], pool, tables, kv_lens, q_pos, **kw)
    )
    unbounded = np.asarray(
        ops.paged_attention(q[:, 0], pool, tables, full, q_pos, **kw)
    )
    np.testing.assert_array_equal(bounded, unbounded)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_windowed_walk_skips_dead_prefix_exactly(impl):
    """With a window, the walk is bounded from below too: pages wholly
    behind the window hold only masked keys, so starting at the first
    visible page (what window freeing leaves live) changes nothing — for
    any page-block size, including one that misaligns with the bound."""
    lengths = (29, 27)
    window = 7
    pool, tables, q, q_pos, kv_lens = _case("int8", lengths=lengths)
    want = _gather_reference(pool, tables, q, q_pos, "int8", window, 0.0)
    for ppb in (1, 2, 4):
        got = np.asarray(
            ops.paged_attention(
                q[:, 0], pool, tables, kv_lens, q_pos,
                quant="int8", causal=True, window=window, softcap=0.0,
                impl=impl, pages_per_block=ppb,
            ),
            np.float32,
        )
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_empty_slot_yields_zeros_not_nan():
    """A slot with kv_len 0 and an all-null table (inactive decode slot)
    must produce finite zeros — its logits are discarded, but NaNs would
    poison the whole batch through the shared lm_head matmul."""
    pool, tables, q, q_pos, kv_lens = _case("none")
    empty_tables = jnp.zeros_like(tables)
    out = np.asarray(
        ops.paged_attention(
            q[:, 0], pool, empty_tables, jnp.zeros_like(kv_lens), q_pos,
            quant="none", impl="ref",
        ),
        np.float32,
    )
    assert np.isfinite(out).all() and (out == 0).all()


# ---------------------------------------------------------------------------
# end-to-end: the serving engine routed through the fused path
# ---------------------------------------------------------------------------

def _serve(model, params, prompts, n_steps, *, fused, **kw):
    prev = ops.PAGED_ATTENTION_FUSED
    ops.PAGED_ATTENTION_FUSED = fused
    try:
        eng = GenerationEngine(
            model, params, max_len=64, block_size=8, max_slots=2, **kw
        )
        rids = [eng.submit(p, max_new_tokens=n_steps) for p in prompts]
        done = eng.run_until_drained()
        return [done[r] for r in rids], eng
    finally:
        ops.PAGED_ATTENTION_FUSED = prev


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_engine_fused_matches_gather_path(kv_quant):
    """Greedy serving traffic through the fused decode path reproduces the
    gather-read path token-for-token (and transitively the dense golden,
    which the gather path is tested against)."""
    cfg = dataclasses.replace(get_smoke_config("llama3-8b"), kv_quant=kv_quant)
    m = Model(cfg)
    params = Model(get_smoke_config("llama3-8b")).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 19, 11)]
    want, _ = _serve(m, params, prompts, 5, fused=False)
    got, eng = _serve(m, params, prompts, 5, fused=True)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    st = eng.scheduler.stats()
    # the §13 observable: the length-bounded walk read fewer bytes/token
    # than the max_blocks worst case the gather path always paid
    assert 0 < st["kv_read_bytes_per_token"] < st["kv_read_bytes_per_token_worst"]


def test_engine_fused_matches_gather_with_temperature():
    """Keyed sampling is numerics-sensitive only through logits; the fused
    path's fp32-accumulator agreement keeps sampled traffic identical."""
    cfg = get_smoke_config("llama3-8b")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 14)]
    want, _ = _serve(m, params, prompts, 5, fused=False, temperature=0.8)
    got, _ = _serve(m, params, prompts, 5, fused=True, temperature=0.8)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


@pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices (set XLA_FLAGS=--xla_force_host_platform_device_count)",
)
def test_engine_fused_under_mesh_matches_unsharded(llama_mesh=None):
    """The fused page walk under a (data=2, model=1) mesh — pools
    replicated over 'data', heads on 'model' — matches unsharded decode."""
    from repro.launch.mesh import make_test_mesh

    cfg = dataclasses.replace(get_smoke_config("llama3-8b"), kv_quant="int8")
    m = Model(cfg)
    params = Model(get_smoke_config("llama3-8b")).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 19, 11)]
    want, _ = _serve(m, params, prompts, 4, fused=True)
    got, _ = _serve(m, params, prompts, 4, fused=True, mesh=make_test_mesh(2, 1))
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# window-aware page freeing (all-local stacks)
# ---------------------------------------------------------------------------

def test_local_window_freeing_matches_dense_and_frees_pages():
    """An all-local-attention stack slides its window past early pages;
    the scheduler returns them to the free list mid-request without
    changing a single sampled token vs the dense ring reference."""
    cfg = dataclasses.replace(
        get_smoke_config("llama3-8b"),
        block_pattern=("attn_local",), window=16,
    )
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, 24).astype(np.int32),
               rng.integers(0, cfg.vocab_size, 9).astype(np.int32)]
    n_steps = 12
    want = [
        GenerationEngine(m, params, max_len=64, paged=False)
        .generate(p[None], n_steps)[0]
        for p in prompts
    ]
    got, eng = _serve(m, params, prompts, n_steps, fused=True)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    st = eng.scheduler.stats()
    assert eng.scheduler.local_window == 16
    assert st["window_freed_pages"] > 0  # pages actually slid out and freed
    assert eng.kv.free_blocks == eng.kv.num_blocks  # and none leaked


def test_global_attention_never_window_frees():
    """A stack with any global layer must keep the full history: the engine
    does not arm window freeing for mixed or global stacks."""
    cfg = get_smoke_config("gemma2-2b")  # local_global alternating
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = GenerationEngine(m, params, max_len=64, block_size=8)
    assert eng.scheduler.local_window is None


# ---------------------------------------------------------------------------
# no materialized KV: the acceptance jaxpr check
# ---------------------------------------------------------------------------

def _eqn_avals(jaxpr):
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


def test_decode_chunk_never_materializes_gathered_kv():
    """Acceptance: the device-resident decode chunk's jaxpr contains no
    (B, MB*bsize, Hkv, Dh) bf16/f32 KV intermediate — neither the flat
    gathered view nor its (B, MB, bsize, Hkv, Dh) pre-reshape form. The
    fused walk keeps the peak KV intermediate at one page block."""
    cfg = get_smoke_config("llama3-8b")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = GenerationEngine(
        m, params, max_len=64, block_size=8, max_slots=2, decode_chunk=4
    )
    hkv, dh = cfg.n_kv_heads, cfg.d_head
    C, M, MB, bs = 4, 2, eng.max_blocks, eng.block_size
    forbidden = {(M, MB * bs, hkv, dh), (M, MB, bs, hkv, dh)}
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
        if getattr(a, "shape", None) in forbidden
        and a.dtype in (jnp.float32, jnp.bfloat16)
    ]
    assert not bad, f"gathered KV view materialized in decode chunk: {bad}"


# ---------------------------------------------------------------------------
# one-switch Pallas compile mode (REPRO_PALLAS_INTERPRET)
# ---------------------------------------------------------------------------

def test_interpret_env_switch(monkeypatch):
    for val in ("1", "true", "YES", "on"):
        monkeypatch.setenv(mode.INTERPRET_ENV, val)
        assert mode.resolve_interpret() is True
        assert mode.resolve_interpret(False) is False  # explicit flag wins
    for val in ("0", "false", "No", "off"):
        monkeypatch.setenv(mode.INTERPRET_ENV, val)
        assert mode.resolve_interpret() is False
    monkeypatch.setenv(mode.INTERPRET_ENV, "definitely")
    with pytest.raises(ValueError, match="REPRO_PALLAS_INTERPRET"):
        mode.resolve_interpret()
    monkeypatch.delenv(mode.INTERPRET_ENV)
    assert mode.resolve_interpret() is (jax.default_backend() != "tpu")


def test_interpret_env_honored_by_paged_attention(monkeypatch):
    """The forced-interpret override flows through the paged-attention
    entry point (the other three kernel entries share `resolve_interpret`)."""
    monkeypatch.setenv(mode.INTERPRET_ENV, "1")
    pool, tables, q, q_pos, kv_lens = _case("none", lengths=(3, 9))
    out = ops.paged_attention(
        q[:, 0], pool, tables, kv_lens, q_pos, quant="none", impl="pallas"
    )
    assert np.isfinite(np.asarray(out, np.float32)).all()


# ---------------------------------------------------------------------------
# the Roof-Surface KV-decode term (attention on the 3D roofline)
# ---------------------------------------------------------------------------

PROD = dict(hq=32, hkv=8, dh=128, kv_len=4096, profile=rs.TPU_V5E)


def test_kv_decode_term_is_mem_bound_at_production_shapes():
    """Decode attention is the bandwidth problem the paper's thesis names:
    every KV format at llama3-8b-like shapes and long context lands in the
    MEM region of the surface."""
    for quant in KV_FORMATS:
        pt = rs.paged_attention_point(f"kv_{quant}", kv_quant=quant, **PROD)
        assert pt.bound == "MEM", (quant, pt.rates)


def test_kv_decode_term_prices_byte_shrink():
    """The point of dequantize-on-read: a MEM-bound kernel speeds up in
    proportion to the byte shrink. bf8 halves the bf16 stream, int4
    quarters the code plane (minus scale overhead)."""
    none = rs.paged_attention_point("none", kv_quant="none", **PROD)
    bf8 = rs.paged_attention_point("bf8", kv_quant="bf8", **PROD)
    int4 = rs.paged_attention_point("int4", kv_quant="int4", **PROD)
    assert 1.9 <= bf8.tps / none.tps <= 2.1
    assert 3.4 <= int4.tps / none.tps <= 4.0
    assert rs.kv_bytes_per_token("int4", 8, 128) < rs.kv_bytes_per_token(
        "bf8", 8, 128
    ) < rs.kv_bytes_per_token("none", 8, 128)


def test_kv_decode_vec_term():
    """Unquantized pools spend no decode vops (never VEC-bound); nibble
    formats cost more decode vops than byte formats; starving the VPU
    exposes the VEC bound for quantized formats."""
    assert rs.kv_decode_vops_per_token("none", 8, 128) == 0.0
    assert rs.kv_decode_vops_per_token("int4", 8, 128) > (
        rs.kv_decode_vops_per_token("int8", 8, 128)
    )
    starved = rs.TPU_V5E.scaled(vos_mult=1e-4)
    pt = rs.paged_attention_point(
        "int4_starved", kv_quant="int4",
        hq=32, hkv=8, dh=128, kv_len=4096, profile=starved,
    )
    assert pt.bound == "VEC"
    none_pt = rs.paged_attention_point(
        "none_starved", kv_quant="none",
        hq=32, hkv=8, dh=128, kv_len=4096, profile=starved,
    )
    assert none_pt.bound != "VEC"
