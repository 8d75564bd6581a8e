"""Pure-jnp oracles for DECA decompression, compressed GeMM, and the fused
paged-attention decode.

The decompression oracles mirror the DECA PE pipeline (paper Fig. 11)
stage by stage:
  1. Dequantization  — code -> BF16 value (LUT array in hardware; the
                       registered codec's jnp decode here),
  2. Expansion       — de-sparsification: prefix-sum over the bitmask
                       (POPCNT + parallel-prefix + crossbar in hardware;
                       popcount + a select per packed slot here),
  3. Scaling         — per-group scale multiply (group quantization).

`paged_decode_attention` is the same idea applied to the KV stream
(DESIGN.md §13): quantized pages are dequantized-on-read one page block at
a time and folded into a flash-style online-softmax accumulator, so the
dense (B, MB*bsize, Hkv, Dh) KV view of `paged_gather_kv` is never
materialized and the page walk is bounded by the slots' used page count
instead of max_blocks.

Everything is jittable jnp; used as the correctness reference for the
Pallas kernels and as the portable fallback path. Stage 1, the scale
decode, and the KV decode route through `repro.core.codecs`, and stages
2-3 are the kernels' own block pipeline
(`deca_decompress.decompress_block`), so this module and the Pallas kernel
bodies share exactly one decode implementation per format.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codecs import get_codec
from repro.core.compression import CompressedTensor
from repro.core.formats import CompressionSpec
from repro.kernels.deca_decompress import decompress_block

# Empty KV-cache slots are masked via a huge position: with causal masking
# the sentinel exceeds every query position, and the fused path also drops
# it explicitly (it is the canonical constant; models/layers re-exports it).
CACHE_EMPTY_POS = 1 << 30


# ---------------------------------------------------------------------------
# stage 1: dequantization (delegates to the codec registry)
# ---------------------------------------------------------------------------

def dequant_codes(codes: jax.Array, spec: CompressionSpec) -> jax.Array:
    """(ng, packed_k, N) uint8 -> (ng, k_cap, N) f32 unquantized values."""
    return get_codec(spec.quant).decode_values(codes)


def dequant_scales(scales: jax.Array, spec: CompressionSpec) -> jax.Array:
    """(ng, N) stored scales -> (ng, N) f32 multipliers."""
    return get_codec(spec.quant).decode_scales(scales)


# ---------------------------------------------------------------------------
# stage 2 + 3: expansion (de-sparsification) and scaling
# ---------------------------------------------------------------------------

def decompress(ct: CompressedTensor, out_dtype=jnp.bfloat16) -> jax.Array:
    """Full DECA pipeline: CompressedTensor -> dense (K, N) weights."""
    return decompress_block(ct.codes, ct.mask, ct.scales, ct.spec).astype(
        out_dtype
    )


def decompress_gemm(
    x: jax.Array, ct: CompressedTensor, out_dtype=jnp.float32
) -> jax.Array:
    """x (M, K) @ decompress(ct) (K, N) -> (M, N). Unfused reference."""
    w = decompress(ct, out_dtype=jnp.bfloat16)
    return jnp.dot(
        x.astype(jnp.bfloat16), w, preferred_element_type=jnp.float32
    ).astype(out_dtype)


GEMV_UNROLL_MAX = 8  # column tiles computed unrolled before falling to scan


def decompress_gemv(
    x: jax.Array,
    ct: CompressedTensor,
    *,
    block_n: Optional[int] = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Decode-shaped compressed GeMV: x (M, K) @ W (K, N) without ever
    materializing the dense (K, N) weight.

    The serving decode step is the GeMV regime (M = a handful of
    continuous-batching slots): the full-matrix `decompress_gemm` pays a
    dense f32 (K, N) intermediate per layer per token, pure bandwidth waste
    when the matmul itself is bandwidth-bound (DESIGN.md §12). Here the
    contraction walks column tiles: each dequantizes one (K, block_n)
    group-local tile to bf16 and contracts it immediately, so no (K, N)
    dense intermediate ever exists. Few tiles (the common decode shapes)
    are unrolled — a `lax.scan` step costs ~100us of loop machinery on
    CPU, swamping the tile work itself; many tiles fall back to the scan,
    which keeps exactly one tile live regardless of N.

    Tiling over N (not K) keeps each output element a single full-K dot —
    the result is *bit-identical* to `decompress_gemm`, which K-split
    accumulation would not be (f32 addition is not associative)."""
    spec = ct.spec
    K, N = ct.shape
    if x.shape[1] != K:
        raise ValueError(f"x K dim {x.shape[1]} != weight K {K}")
    if block_n is None:
        from repro.kernels.autotune import select_block

        # force >= 2 tiles whenever N splits at all: with one tile the full
        # dense matrix would appear after all
        block_n = select_block(N, max(1, min(128, N // 2)))
        if block_n < 8 and N // block_n > GEMV_UNROLL_MAX:
            # awkward N (prime-ish): every divisor <= N//2 is tiny, and a
            # long scan of 1..7-wide tiles pays ~100us of loop machinery
            # per step — far worse than the dense materialization a single
            # whole-matrix tile costs. Real model dims are lane multiples,
            # so the serving path never lands here.
            block_n = N
    if N % block_n:
        raise ValueError(f"block_n={block_n} does not divide N={N}")
    nb = N // block_n
    xb = x.astype(jnp.bfloat16)

    def tile(codes, mask, scales):
        w = decompress_block(codes, mask, scales, spec).astype(jnp.bfloat16)
        return jnp.dot(xb, w, preferred_element_type=jnp.float32)

    if nb == 1:
        return tile(ct.codes, ct.mask, ct.scales).astype(out_dtype)

    def col(a, i):
        return None if a is None else a[..., i * block_n:(i + 1) * block_n]

    if nb <= GEMV_UNROLL_MAX:
        outs = [
            tile(col(ct.codes, i), col(ct.mask, i), col(ct.scales, i))
            for i in range(nb)
        ]
        return jnp.concatenate(outs, axis=1).astype(out_dtype)

    def split(a):
        # (..., N) -> (nb, ..., block_n) scan stack over column tiles
        if a is None:
            return None
        return jnp.moveaxis(a.reshape(a.shape[:-1] + (nb, block_n)), -2, 0)

    xs = (split(ct.codes), split(ct.mask), split(ct.scales))

    def body(_, cms):
        codes, mask, scales = cms
        return None, tile(codes, mask, scales)

    _, tiles = jax.lax.scan(body, None, xs)  # (nb, M, block_n)
    out = jnp.moveaxis(tiles, 0, 1).reshape(x.shape[0], N)
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# fused paged-attention decode (DESIGN.md §13)
# ---------------------------------------------------------------------------

def kv_decode_page(
    codes: jax.Array, scales: Optional[jax.Array], quant: str
) -> jax.Array:
    """Dequantize one KV page block via the codec registry (identity for
    unquantized pools). Shared by this oracle and the Pallas kernel body,
    so each format has exactly one KV decoder on the attention path too."""
    if quant in ("none", "", None):
        return codes
    return get_codec(quant).kv_decode(codes, scales).astype(jnp.bfloat16)


def resolve_page_walk(
    block_tables: jax.Array,  # (B, MB)
    bs: int,
    hkv: int,
    dh: int,
    quant: str,
    hq: int,
    pages_per_block: Optional[int],
):
    """One resolution of the page-walk grid for both impls: autotuned (or
    clamped explicit) pages-per-block, the number of walk steps, and the
    block tables padded to a whole number of blocks (pad entries are the
    null page, whose sentinel positions mask to zero — the jnp oracle and
    the Pallas kernel must walk the *same* grid)."""
    mb = block_tables.shape[1]
    if pages_per_block is None:
        from repro.kernels.autotune import pick_page_block

        pages_per_block = pick_page_block(mb, bs, hkv, dh, quant, hq=hq)
    ppb = max(1, min(pages_per_block, mb))
    nblocks = -(-mb // ppb)
    pad = nblocks * ppb - mb
    tables = (
        jnp.pad(block_tables, ((0, 0), (0, pad))) if pad else block_tables
    )
    return ppb, nblocks, tables


def paged_softmax_update(
    q: jax.Array,      # (Hkv, G, Dh)
    k: jax.Array,      # (T, Hkv, Dh)
    v: jax.Array,      # (T, Hkv, Dh)
    k_pos: jax.Array,  # (T,) int32; CACHE_EMPTY_POS marks empty slots
    q_pos: jax.Array,  # () int32
    m: jax.Array,      # (Hkv, G) f32 running max
    l: jax.Array,      # (Hkv, G) f32 running exp-sum
    acc: jax.Array,    # (Hkv, G, Dh) f32 running weighted-V sum
    *,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
) -> tuple:
    """Fold one page block of one slot's KV into its online-softmax state.

    One slot, so each contraction has the single batch dim (the KV head)
    that Mosaic's matmul takes; the jnp oracle vmaps it over slots.
    Per-element math matches `attention_core` exactly (bf16 q·k with f32
    accumulation, softcap before the additive mask), so the renormalized
    result agrees with the gather-read reference to fp32-accumulator
    tolerance. Shared by the jnp oracle and the Pallas kernel body."""
    s = jnp.einsum(
        "hgd,thd->hgt",
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ) * scale
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    ok = k_pos != CACHE_EMPTY_POS
    if causal:
        ok = ok & (k_pos <= q_pos)
    if window > 0:
        ok = ok & (k_pos > q_pos - window)
    s = s + jnp.where(ok, 0.0, -1e30)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # an all-masked block leaves m at the -1e30 init, where exp(s - m) is 1
    # for masked entries — their mass is therefore zeroed explicitly
    p = jnp.exp(s - m_new[..., None]) * ok
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum(
        "hgt,thd->hgd", p, v, preferred_element_type=jnp.float32
    )
    return m_new, l_new, acc * alpha[..., None] + pv


def paged_decode_attention(
    q: jax.Array,             # (B, Hq, Dh) one query token per slot
    pools: Dict[str, jax.Array],  # kp/vp/ppos (+ks/vs for scaled codecs)
    block_tables: jax.Array,  # (B, MB) int32 device page ids (0 = null page)
    kv_lens: jax.Array,       # (B,) int32 valid KV tokens per slot
    q_pos: jax.Array,         # (B,) int32 query positions
    *,
    quant: str = "none",
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    pages_per_block: Optional[int] = None,
) -> jax.Array:
    """Fused paged-attention decode: dequantize-on-read inside the walk.

    Walks each slot's block table `pages_per_block` pages at a time inside
    a `lax.while_loop` bounded by the batch's max used page count — O(used
    context) work per token instead of O(max_context) — decoding the
    quantized K/V pool codes via the codec registry one block at a time.
    The dense (B, MB*bsize, Hkv, Dh) KV copy of `paged_gather_kv` (kept as
    the golden reference path) never exists. Pages past a slot's length,
    scrubbed-fresh pages, and null-page reads all carry the position
    sentinel and fold in with exactly-zero weight, so truncating the walk
    at the length bound is exact, not approximate. Windowed attention also
    bounds the walk from *below*: pages wholly behind every slot's window
    (which window-aware freeing has typically already returned to the
    allocator) are masked anyway, so the walk starts at the batch-min
    first visible page — O(window) work per token for all-local stacks."""
    kp = pools["kp"]
    bs, hkv = kp.shape[1], kp.shape[2]
    b, hq, dh = q.shape
    g = hq // hkv
    mb = block_tables.shape[1]
    ppb, _, tables = resolve_page_walk(
        block_tables, bs, hkv, dh, quant, hq, pages_per_block
    )
    has_scale = "ks" in pools
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(b, hkv, g, dh)
    pages_needed = jnp.clip(-(-kv_lens // bs), 0, mb)
    bound = -(-jnp.max(pages_needed) // ppb)  # traced: the length bound
    if window > 0:
        # first page any slot's window can still see: keys are visible iff
        # k_pos >= q_pos - window + 1
        first_page = jnp.clip((q_pos - window + 1) // bs, 0, mb)
        start = jnp.min(first_page).astype(jnp.int32) // ppb
    else:
        start = jnp.zeros((), jnp.int32)

    update = functools.partial(
        paged_softmax_update,
        scale=scale, causal=causal, window=window, softcap=softcap,
    )

    def grab(name, tbl):
        x = jnp.take(pools[name], tbl, axis=0)  # (B, ppb, bs, ...)
        return x.reshape((b, ppb * bs) + x.shape[3:])

    def body(carry):
        i, m, l, acc = carry
        tbl = jax.lax.dynamic_slice(tables, (0, i * ppb), (b, ppb))
        ks = grab("ks", tbl) if has_scale else None
        vs = grab("vs", tbl) if has_scale else None
        k = kv_decode_page(grab("kp", tbl), ks, quant)
        v = kv_decode_page(grab("vp", tbl), vs, quant)
        m, l, acc = jax.vmap(update)(
            qg, k, v, grab("ppos", tbl), q_pos, m, l, acc
        )
        return i + 1, m, l, acc

    init = (
        start,
        jnp.full((b, hkv, g), -1e30, jnp.float32),
        jnp.zeros((b, hkv, g), jnp.float32),
        jnp.zeros((b, hkv, g, dh), jnp.float32),
    )
    _, _, l, acc = jax.lax.while_loop(lambda c: c[0] < bound, body, init)
    out = jnp.where(
        l[..., None] > 0, acc / jnp.maximum(l, 1e-30)[..., None], 0.0
    )
    return out.reshape(b, hq, dh).astype(q.dtype)


def dense_roundtrip(w: np.ndarray, spec: CompressionSpec) -> np.ndarray:
    """Numpy helper: what the dense weight looks like after compress->decompress
    (i.e. the quantization+pruning error the *model* sees). Used by tests."""
    from repro.core.compression import compress

    ct = compress(w, spec)
    return np.asarray(decompress(ct, out_dtype=jnp.float32))
