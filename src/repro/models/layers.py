"""Layer library: norms, rotary embeddings (incl. M-RoPE), GQA attention
(global / local-window / softcapped, chunked for long context), dense and
MoE FFNs, the RG-LRU recurrent block (Griffin), and the Mamba-1 block.

Functional style: every layer is `f(params, x, ...) -> y` with `init_*`
companions returning param pytrees. Activation sharding constraints are
injected via `repro.dist.sharding.constrain` (identity outside a mesh).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
# quantize_bf8_jnp / dequantize_bf8_jnp are re-exported for back-compat:
# their canonical home is the codec registry
from repro.core.codecs import (  # noqa: F401
    dequantize_bf8_jnp,
    get_codec,
    quantize_bf8_jnp,
)
from repro.core.decompress import current_impl, mm
from repro.dist.sharding import constrain, constrain_qkv
from repro.kernels import ops as kernel_ops

Params = Dict[str, Any]

# Device-trace names (`jax.named_scope`) of the paged attention layer's
# work outside the DECA kernels (DESIGN.md §14).
SCOPE_KV_WRITE = "kv.write"
SCOPE_ATTENTION = "model.attention"


def _dense_init(key, shape, scale_axis=0, dtype=jnp.float32):
    fan_in = shape[scale_axis]
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(w: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Mixed-precision RMS norm: two big passes instead of ~six.

    The sum of squares is f32-accumulated directly from the bf16 input
    (einsum with preferred_element_type) so no f32 copy of x is ever
    materialized; the per-row scale (f32, tiny) is applied in the input
    dtype. §Perf hillclimb 1, iteration 2."""
    d = x.shape[-1]
    ssq = jnp.einsum(
        "...d,...d->...", x, x, preferred_element_type=jnp.float32
    )
    scale = jax.lax.rsqrt(ssq / d + eps)[..., None]
    return (x * scale.astype(x.dtype)) * (1.0 + w).astype(x.dtype)


def init_rms_norm(d: int) -> jax.Array:
    return jnp.zeros((d,), jnp.float32)  # gemma-style (1 + w) parameterization


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE and qwen2-vl M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head // 2, dtype=jnp.float32) * 2 / d_head))


def apply_rope(
    x: jax.Array,  # (B, S, H, Dh)
    positions: jax.Array,  # (B, S) int32
    theta: float,
) -> jax.Array:
    """RoPE with a shared trig table: positions are batch-shared (synthetic
    pipeline), so cos/sin are computed once at (S, Dh/2) f32 and applied in
    the input dtype — no (B, S, H, Dh) f32 materialization (§Perf
    hillclimb 1, iteration 3)."""
    freqs = rope_freqs(x.shape[-1], theta)
    angles = positions[0][:, None].astype(jnp.float32) * freqs  # (S, Dh/2)
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope_batched(
    x: jax.Array,  # (B, S, H, Dh)
    positions: jax.Array,  # (B, S) int32 — per-request positions
    theta: float,
) -> jax.Array:
    """RoPE with per-request positions (paged decode: every active slot sits
    at its own depth). Same per-element math as `apply_rope`, so a request's
    rotated q/k are identical whichever path served it."""
    freqs = rope_freqs(x.shape[-1], theta)
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # (B, S, Dh/2)
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_mrope(
    x: jax.Array,  # (B, S, H, Dh)
    positions: jax.Array,  # (3, B, S) — temporal / height / width ids
    theta: float,
    sections: Tuple[int, ...],
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: the rotary half-dim is split into sections,
    each rotated by a different positional stream."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (Dh/2,)
    # section id per frequency index
    sec_id = jnp.repeat(
        jnp.arange(len(sections)), jnp.asarray(sections), total_repeat_length=dh // 2
    )
    pos = positions.astype(jnp.float32)  # (3, B, S)
    angles = jnp.take(pos, sec_id, axis=0)  # (Dh/2, B, S) via axis-0 gather
    angles = jnp.moveaxis(angles, 0, -1) * freqs  # (B, S, Dh/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], (d, hq * dh), dtype=dtype),
        "wk": _dense_init(ks[1], (d, hkv * dh), dtype=dtype),
        "wv": _dense_init(ks[2], (d, hkv * dh), dtype=dtype),
        "wo": _dense_init(ks[3], (hq * dh, d), dtype=dtype),
    }


def _attn_scores_mask(
    q_pos: jax.Array,  # (Sq,) or (B, Sq) absolute positions of queries
    k_pos: jax.Array,  # (Sk,) or (B, Sk)
    causal: bool,
    window: int,
) -> jax.Array:
    """Additive mask in f32: (Sq, Sk) for shared positions, (B, Sq, Sk) when
    either side carries per-request positions (paged decode)."""
    dq = q_pos[..., :, None]
    dk = k_pos[..., None, :]
    ok = jnp.broadcast_to(
        jnp.ones((), jnp.bool_), jnp.broadcast_shapes(dq.shape, dk.shape)
    )
    if causal:
        ok &= dk <= dq
    if window > 0:
        ok &= dk > dq - window
    return jnp.where(ok, 0.0, -1e30)


def _softcap(x: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap > 0 else x


def attention_core(
    q: jax.Array,  # (B, Sq, Hq, Dh)
    k: jax.Array,  # (B, Sk, Hkv, Dh)
    v: jax.Array,  # (B, Sk, Hkv, Dh)
    *,
    q_pos: jax.Array,
    k_pos: jax.Array,
    causal: bool,
    window: int = 0,
    softcap: float = 0.0,
    q_chunk: int = 1024,
) -> jax.Array:
    """Grouped-query attention, chunked over queries so peak memory is
    O(q_chunk * Sk) rather than O(Sq * Sk). Mixed-precision: scores in f32,
    and the PV contraction f32-accumulates *f32 probabilities* — the same
    discipline as the fused paged-attention accumulator (kernels/ref.py),
    which keeps the gather-read and fused decode paths within
    fp32-accumulator tolerance of each other (greedy decode is
    path-independent in practice; a bf16 probs cast here put ~1e-2 noise
    between the paths, enough to flip near-tie argmaxes).

    `q_pos`/`k_pos` may be shared `(Sq,)`/`(Sk,)` or per-request
    `(B, Sq)`/`(B, Sk)` (paged KV: each request gathers its own blocks)."""
    b, sq, hq, dh = q.shape
    _, sk, hkv, _ = k.shape
    group = hq // hkv
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(b, sq, hkv, group, dh)

    def chunk_attn(qc, qp):  # qc: (B, Cq, Hkv, G, Dh)
        scores = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qc.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        ) * scale
        scores = _softcap(scores, softcap)
        mask = _attn_scores_mask(qp, k_pos, causal, window)
        if mask.ndim == 3:  # (B, Cq, Sk) -> broadcast over (Hkv, G)
            mask = mask[:, None, None]
        scores = scores + mask
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum(
            "bhgqk,bkhd->bqhgd", probs, v, preferred_element_type=jnp.float32
        )

    if sq <= q_chunk:
        out = chunk_attn(qg, q_pos)
    else:
        n_chunks = math.ceil(sq / q_chunk)
        pad = n_chunks * q_chunk - sq
        qg_p = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        qg_c = qg_p.reshape(b, n_chunks, q_chunk, hkv, group, dh)
        if q_pos.ndim == 1:
            qp_c = jnp.pad(q_pos, (0, pad)).reshape(n_chunks, q_chunk)
        else:  # (B, Sq) per-request positions
            qp_p = jnp.pad(q_pos, ((0, 0), (0, pad)))
            qp_c = jnp.moveaxis(
                qp_p.reshape(b, n_chunks, q_chunk), 1, 0
            )  # (n_chunks, B, Cq)
        out = jax.lax.map(
            lambda args: chunk_attn(args[0], args[1]),
            (jnp.moveaxis(qg_c, 1, 0), qp_c),
        )  # (n_chunks, B, Cq, Hkv, G, Dh)
        out = jnp.moveaxis(out, 0, 1).reshape(b, n_chunks * q_chunk, hkv, group, dh)
        out = out[:, :sq]
    return out.reshape(b, sq, hq, dh).astype(q.dtype)


# sentinel: empty cache slots masked via huge position (canonical home is
# kernels/ref.py, where the fused paged-attention page walk also needs it)
from repro.kernels.ref import CACHE_EMPTY_POS  # noqa: F401, E402


def _kv_codec(quant: str):
    """KV-cache codec for a `kv_quant` format name ('none' -> unquantized)."""
    if quant in ("none", "", None):
        return None
    codec = get_codec(quant)  # raises ValueError for unregistered formats
    if not codec.kv_capable:
        raise ValueError(f"codec {quant!r} does not support KV-cache quantization")
    return codec


def _check_cache_quant(stored_dtype, codec, quant: str) -> None:
    """Fail fast (at trace time) when `quant` disagrees with how the cache
    was built: an unquantized write into a code pool — or vice versa —
    would otherwise silently `.astype()` raw floats into garbage codes."""
    is_float = jnp.issubdtype(stored_dtype, jnp.floating)
    if (codec is None) != is_float:
        raise ValueError(
            f"cache stores {stored_dtype} but quant={quant!r}; the cache "
            "must be initialized with the same kv_quant it is accessed with"
        )


def init_kv_cache(
    b: int, size: int, hkv: int, dh: int, dtype=jnp.bfloat16, quant: str = "none"
) -> Dict[str, jax.Array]:
    """Ring KV cache; `quant` names any kv-capable registered codec.
    Quantized caches store codes (packed for 4-bit formats) plus, for scaled
    codecs, one bf16 scale per (slot, head) in `k_scale`/`v_scale`."""
    size = (size + 31) // 32 * 32  # seq shardable over any mesh axis
    codec = _kv_codec(quant)
    if codec is None:
        kv_dtype, width = dtype, dh
    else:
        kv_dtype, width = codec.kv_dtype, codec.kv_code_width(dh)
    cache = {
        "k": jnp.zeros((b, size, hkv, width), kv_dtype),
        "v": jnp.zeros((b, size, hkv, width), kv_dtype),
        "pos": jnp.full((size,), CACHE_EMPTY_POS, jnp.int32),
        "length": jnp.zeros((), jnp.int32),
    }
    if codec is not None and codec.has_scale:
        cache["k_scale"] = jnp.zeros((b, size, hkv), jnp.bfloat16)
        cache["v_scale"] = jnp.zeros((b, size, hkv), jnp.bfloat16)
    return cache


def update_cache(
    cache: Dict[str, jax.Array],
    k: jax.Array,
    v: jax.Array,
    pos: jax.Array,
    quant: str = "none",
) -> Dict[str, jax.Array]:
    """Append s tokens. Ring semantics: masking is position-based, so slot
    order in the buffer is irrelevant (local-window caches wrap). Quantized
    caches encode on write via the codec registry."""
    codec = _kv_codec(quant)
    _check_cache_quant(cache["k"].dtype, codec, quant)
    ks = vs = None
    if codec is not None:
        k, ks = codec.kv_encode(k)
        v, vs = codec.kv_encode(v)
    size = cache["k"].shape[1]
    s = k.shape[1]
    length = cache["length"]
    dus = jax.lax.dynamic_update_slice_in_dim
    if s >= size:  # static: prefill longer than the (windowed) cache
        ck, cv, cp = k[:, -size:], v[:, -size:], pos[-size:].astype(jnp.int32)
        cks = ks[:, -size:] if ks is not None else None
        cvs = vs[:, -size:] if vs is not None else None
    else:
        idx = length % size
        ck = dus(cache["k"], k, idx, axis=1)
        cv = dus(cache["v"], v, idx, axis=1)
        cp = dus(cache["pos"], pos.astype(jnp.int32), idx, axis=0)
        cks = dus(cache["k_scale"], ks, idx, axis=1) if ks is not None else None
        cvs = dus(cache["v_scale"], vs, idx, axis=1) if vs is not None else None
    out = {"k": ck, "v": cv, "pos": cp, "length": length + s}
    if cks is not None:
        out["k_scale"], out["v_scale"] = cks, cvs
    return out


def read_cache_kv(
    cache: Dict[str, jax.Array], quant: str = "none"
) -> Tuple[jax.Array, jax.Array]:
    """Dequantize-on-read for the ring cache (identity when unquantized)."""
    codec = _kv_codec(quant)
    _check_cache_quant(cache["k"].dtype, codec, quant)
    if codec is None:
        return cache["k"], cache["v"]
    return (
        codec.kv_decode(cache["k"], cache.get("k_scale")).astype(jnp.bfloat16),
        codec.kv_decode(cache["v"], cache.get("v_scale")).astype(jnp.bfloat16),
    )


def init_paged_kv_cache(
    num_blocks: int,
    block_size: int,
    hkv: int,
    dh: int,
    dtype=jnp.bfloat16,
    quant: str = "none",
) -> Dict[str, jax.Array]:
    """Block-paged KV pool: `num_blocks` pages of `block_size` tokens shared
    by all requests (device row 0 is the null page — pad/inactive writes land
    there and stay masked via the position sentinel). Quantized pools encode
    on write like the ring cache; scaled codecs add `ks`/`vs` planes holding
    one bf16 scale per (page, slot, head)."""
    codec = _kv_codec(quant)
    if codec is None:
        kv_dtype, width = dtype, dh
    else:
        kv_dtype, width = codec.kv_dtype, codec.kv_code_width(dh)
    pools = {
        "kp": jnp.zeros((num_blocks, block_size, hkv, width), kv_dtype),
        "vp": jnp.zeros((num_blocks, block_size, hkv, width), kv_dtype),
        "ppos": jnp.full((num_blocks, block_size), CACHE_EMPTY_POS, jnp.int32),
    }
    if codec is not None and codec.has_scale:
        pools["ks"] = jnp.zeros((num_blocks, block_size, hkv), jnp.bfloat16)
        pools["vs"] = jnp.zeros((num_blocks, block_size, hkv), jnp.bfloat16)
    return pools


def paged_update_cache(
    cache: Dict[str, jax.Array],
    k: jax.Array,          # (B, S, Hkv, Dh)
    v: jax.Array,          # (B, S, Hkv, Dh)
    write_pos: jax.Array,  # (B, S) int32; CACHE_EMPTY_POS for pad tokens
    write_slots: jax.Array,  # (B, S) int32 flat slot ids (block * bsize + off)
    fresh_pages: Optional[jax.Array] = None,  # (F,) page ids, 0 = none
    copy_pages: Optional[jax.Array] = None,   # (C, 2) (src, dst) page ids
    quant: str = "none",
) -> Dict[str, jax.Array]:
    """Scatter S tokens per request into the shared pool. Slot ids are
    host-computed from each request's block table; pad tokens target the
    null page (their position stays the empty sentinel, so reads mask them).

    `fresh_pages` lists pages newly taken from the free list this step:
    their position plane is scrubbed to the empty sentinel *before* the
    scatter, so a page recycled from an evicted request can never leak the
    old tenant's KV entries into a gather-read. Entry 0 (the null page,
    always empty) pads the fixed shape.

    `copy_pages` lists copy-on-write clones queued by the host allocator:
    each (src, dst) row copies every pool plane of page `src` into page
    `dst` *before* the scrub and the scatter, so a write diverging from a
    prefix-shared page lands in a private clone while sibling requests keep
    reading the untouched original. Padding rows are (0, 0) — a null-page
    self-copy, the identity."""
    codec = _kv_codec(quant)
    _check_cache_quant(cache["kp"].dtype, codec, quant)
    ks = vs = None
    if codec is not None:
        k, ks = codec.kv_encode(k)
        v, vs = codec.kv_encode(v)
    if copy_pages is not None:
        src, dst = copy_pages[:, 0], copy_pages[:, 1]
        cache = {
            name: pool.at[dst].set(pool[src]) for name, pool in cache.items()
        }
    nb, bs, hkv, width = cache["kp"].shape
    flat = write_slots.reshape(-1)

    def scatter(pool, updates):
        return (
            pool.reshape((nb * bs,) + pool.shape[2:])
            .at[flat].set(updates.reshape((-1,) + pool.shape[2:]).astype(pool.dtype))
            .reshape(pool.shape)
        )

    out = {
        "kp": constrain(scatter(cache["kp"], k), "pkv"),
        "vp": constrain(scatter(cache["vp"], v), "pkv"),
    }
    ppos = cache["ppos"]
    if fresh_pages is not None:
        ppos = ppos.at[fresh_pages].set(CACHE_EMPTY_POS)
    out["ppos"] = (
        ppos.reshape(nb * bs)
        .at[flat].set(write_pos.reshape(-1).astype(jnp.int32))
        .reshape(nb, bs)
    )
    if ks is not None:
        out["ks"] = constrain(scatter(cache["ks"], ks), "pkvs")
        out["vs"] = constrain(scatter(cache["vs"], vs), "pkvs")
    return out


def paged_gather_kv(
    cache: Dict[str, jax.Array],
    block_tables: jax.Array,  # (B, MB) int32 device page ids (0 = null page)
    quant: str = "none",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Gather each request's pages into a contiguous (B, MB*bsize, Hkv, Dh)
    KV view plus per-request key positions (empty slots carry the sentinel
    and mask to exactly-zero attention weight). Quantized pools decode on
    read — the DECA dequantize-on-read path, via the codec registry."""
    codec = _kv_codec(quant)
    _check_cache_quant(cache["kp"].dtype, codec, quant)
    k = jnp.take(cache["kp"], block_tables, axis=0)  # (B, MB, bs, Hkv, W)
    v = jnp.take(cache["vp"], block_tables, axis=0)
    pos = jnp.take(cache["ppos"], block_tables, axis=0)  # (B, MB, bs)
    b, mb, bs = pos.shape
    k = k.reshape(b, mb * bs, *k.shape[3:])
    v = v.reshape(b, mb * bs, *v.shape[3:])
    if codec is not None:
        ks = vs = None
        if codec.has_scale:
            ks = jnp.take(cache["ks"], block_tables, axis=0).reshape(b, mb * bs, -1)
            vs = jnp.take(cache["vs"], block_tables, axis=0).reshape(b, mb * bs, -1)
        k = codec.kv_decode(k, ks).astype(jnp.bfloat16)
        v = codec.kv_decode(v, vs).astype(jnp.bfloat16)
    return k, v, pos.reshape(b, mb * bs)


def paged_attention_block(
    params: Params,
    x: jax.Array,  # (B, S, D)
    cfg: ModelConfig,
    *,
    positions: jax.Array,      # (B, S) or (3, B, S) — per-request positions
    local: bool,
    cache: Dict[str, jax.Array],
    block_tables: jax.Array,   # (B, MB)
    write_slots: jax.Array,    # (B, S)
    write_pos: jax.Array,      # (B, S)
    fresh_pages: Optional[jax.Array] = None,  # (F,)
    kv_lens: Optional[jax.Array] = None,      # (B,) valid KV tokens per slot
    copy_pages: Optional[jax.Array] = None,   # (C, 2) CoW (src, dst) pages
    window_override: Optional[int] = None,    # cap attn window (spec draft)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Attention layer against the paged pool: proj -> per-request rope ->
    scatter into pool -> read -> attn -> out.

    Decode shapes (S == 1 with a `kv_lens` length vector threaded from the
    scheduler) route through the fused paged-attention path (DESIGN.md
    §13): quantized pages are dequantized-on-read inside a length-bounded
    page walk with an online-softmax accumulator, so the dense gathered KV
    view never exists. Prefill — and `kernel_ops.PAGED_ATTENTION_FUSED =
    False` — keep the gather-read path, which doubles as the golden
    reference: the gathered key order is position order (table slot
    p//bsize, offset p%bsize), so real-token accumulation matches the
    dense ring cache."""
    b, s, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = mm(x, params["wq"]).reshape(b, s, hq, dh)
    k = mm(x, params["wk"]).reshape(b, s, hkv, dh)
    v = mm(x, params["wv"]).reshape(b, s, hkv, dh)
    q, k, v = constrain_qkv(q, k, v)

    if cfg.mrope_sections:
        mpos = positions if positions.ndim == 3 else jnp.broadcast_to(
            positions, (3,) + positions.shape
        )
        tok_pos = mpos[0]
        q = apply_mrope(q, mpos, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, mpos, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.pos_emb == "rope":
        tok_pos = positions if positions.ndim == 2 else positions[0]
        q = apply_rope_batched(q, tok_pos, cfg.rope_theta)
        k = apply_rope_batched(k, tok_pos, cfg.rope_theta)
    else:
        tok_pos = positions if positions.ndim == 2 else positions[0]

    with jax.named_scope(SCOPE_KV_WRITE):
        new_cache = paged_update_cache(
            cache, k, v, write_pos, write_slots, fresh_pages, copy_pages,
            quant=cfg.kv_quant,
        )
    window = cfg.window if local else 0
    if window_override:
        # spec-decode draft passes: a sliding-window cap trades a little
        # draft accuracy for an O(window) fused page walk (DESIGN.md §16);
        # verify passes never set it, so acceptance stays exact
        window = min(window, window_override) if window else window_override
    if kv_lens is not None and s == 1 and kernel_ops.PAGED_ATTENTION_FUSED:
        att = kernel_ops.paged_attention(
            q[:, 0], new_cache, block_tables, kv_lens, tok_pos[:, 0],
            quant=cfg.kv_quant, causal=cfg.causal, window=window,
            softcap=cfg.attn_softcap, impl=current_impl(),
        )
        out = att[:, None]  # (B, 1, Hq, Dh)
    else:
        with jax.named_scope(SCOPE_ATTENTION):
            k_all, v_all, k_pos = paged_gather_kv(
                new_cache, block_tables, quant=cfg.kv_quant
            )
            k_all, v_all = constrain(k_all, "bshd"), constrain(v_all, "bshd")
            out = attention_core(
                q, k_all, v_all,
                q_pos=tok_pos, k_pos=k_pos,
                causal=cfg.causal, window=window,
                softcap=cfg.attn_softcap,
            )
    out = constrain(out, "bshd")
    return mm(out.reshape(b, s, hq * dh), params["wo"]), new_cache


def attention_block(
    params: Params,
    x: jax.Array,  # (B, S, D)
    cfg: ModelConfig,
    *,
    positions: jax.Array,          # (B, S) or (3, B, S) for M-RoPE
    local: bool,
    cache: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Full attention layer: proj -> rope -> (cache update) -> attn -> out."""
    b, s, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = mm(x, params["wq"]).reshape(b, s, hq, dh)
    k = mm(x, params["wk"]).reshape(b, s, hkv, dh)
    v = mm(x, params["wv"]).reshape(b, s, hkv, dh)
    q, k, v = constrain_qkv(q, k, v)

    if cfg.mrope_sections:
        mpos = positions if positions.ndim == 3 else jnp.broadcast_to(
            positions, (3,) + positions.shape
        )
        tok_pos = mpos[0]  # temporal stream carries token order
        q = apply_mrope(q, mpos, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, mpos, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.pos_emb == "rope":
        tok_pos = positions
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    else:
        tok_pos = positions if positions.ndim == 2 else positions[0]

    window = cfg.window if local else 0
    q_pos = tok_pos[0]  # positions shared across the batch (synthetic pipeline)
    if cache is not None:
        new_cache = update_cache(cache, k, v, q_pos, quant=cfg.kv_quant)
        # DECA-style dequantize-on-read (identity for unquantized caches)
        k_all, v_all = read_cache_kv(new_cache, quant=cfg.kv_quant)
        out = attention_core(
            q, k_all, v_all,
            q_pos=q_pos, k_pos=new_cache["pos"],
            causal=cfg.causal, window=window, softcap=cfg.attn_softcap,
        )
    else:
        new_cache = None
        out = attention_core(
            q, k, v,
            q_pos=q_pos, k_pos=q_pos,
            causal=cfg.causal, window=window, softcap=cfg.attn_softcap,
        )
    out = constrain(out, "bshd")
    return mm(out.reshape(b, s, hq * dh), params["wo"]), new_cache


# ---------------------------------------------------------------------------
# FFNs: dense and MoE
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_act in ("swiglu", "geglu"):
        return {
            "w_gate": _dense_init(ks[0], (d, f), dtype=dtype),
            "w_up": _dense_init(ks[1], (d, f), dtype=dtype),
            "w_down": _dense_init(ks[2], (f, d), dtype=dtype),
        }
    return {
        "w_up": _dense_init(ks[0], (d, f), dtype=dtype),
        "w_down": _dense_init(ks[1], (f, d), dtype=dtype),
    }


def _act(name: str, x: jax.Array) -> jax.Array:
    if name in ("swiglu",):
        return jax.nn.silu(x)
    if name in ("geglu", "gelu"):
        return jax.nn.gelu(x)
    return jax.nn.relu(x)


def mlp_block(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.mlp_act in ("swiglu", "geglu"):
        h = _act(cfg.mlp_act, mm(x, params["w_gate"])) * mm(x, params["w_up"])
    else:
        h = _act(cfg.mlp_act, mm(x, params["w_up"]))
    h = constrain(h, "bsf")
    return mm(h, params["w_down"])


def init_moe(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": _dense_init(ks[0], (d, e), dtype=jnp.float32),
        "w_up": _dense_init(ks[1], (e, d, f), scale_axis=1, dtype=dtype),
        "w_down": _dense_init(ks[2], (e, f, d), scale_axis=1, dtype=dtype),
    }
    if cfg.mlp_act in ("swiglu", "geglu"):
        p["w_gate"] = _dense_init(ks[3], (e, d, f), scale_axis=1, dtype=dtype)
    return p


def _dispatch_groups(t: int) -> int:
    """Number of group-local dispatch shards = the active mesh's batch
    sharding (pod*data), so sorts/capacity stay shard-local (no cross-shard
    communication for routing; the expert transpose is the one EP all-to-all).
    Falls back to 1 outside a mesh or when t is too small."""
    from repro.dist.sharding import active_ctx

    ctx = active_ctx()
    if ctx is None:
        return 1
    sizes = ctx.axis_sizes
    g = sizes.get("pod", 1) * sizes.get("data", 1)
    while g > 1 and t % g:
        g //= 2
    return max(1, g)


def moe_block(
    params: Params, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """Top-k token-choice MoE with group-local capacity dispatch.

    Tokens are split into G dispatch groups matching the data sharding; each
    group top-k routes, sorts, and packs into (E, cap_local) capacity bins
    *locally* (vmapped sort => no inter-shard communication). The grouped
    buffer (G, E, cap, D) is then transposed to (E, G*cap, D) — with E
    expert-sharded this transpose is the canonical EP all-to-all. Routing
    slots are processed sequentially (scan over k) to bound peak memory at
    kimi-k2 scale. Returns (output, aux_loss)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    t = b * s
    g = _dispatch_groups(t)
    tl = t // g  # tokens per dispatch group
    xf = x.reshape(g, tl, d)

    logits = jnp.einsum(
        "gtd,de->gte", xf.astype(jnp.float32), params["router"]
    )  # (G, Tl, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (G, Tl, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch-style), computed globally
    me = probs.mean(axis=(0, 1))  # (E,)
    ce = jnp.zeros((e,)).at[expert_idx.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    # capacity floor of 4 keeps tiny decode batches drop-free
    cap = max(4, int(math.ceil(tl / e * cfg.capacity_factor)))
    gi = jnp.arange(g)[:, None]  # group index for batched scatters

    def one_slot(carry, slot):
        out = carry
        eid = expert_idx[:, :, slot]            # (G, Tl)
        gates = gate_vals[:, :, slot]           # (G, Tl)
        order = jnp.argsort(eid, axis=1)        # local per-group sort
        sorted_eid = jnp.take_along_axis(eid, order, axis=1)
        seg_start = jax.vmap(
            lambda se: jnp.searchsorted(se, se, side="left")
        )(sorted_eid)
        pos = jnp.arange(tl)[None, :] - seg_start  # rank within expert bin
        keep = pos < cap
        dest = jnp.where(keep, sorted_eid * cap + pos, e * cap)  # drop->pad row
        # vmapped per-group gather/scatter: keeps index operands at (Tl,)
        # per group (take_along_axis would broadcast u32 indices to
        # (G, Tl, D) — tens of GB — and GSPMD replicates them)
        x_sorted = jax.vmap(lambda xrow, o: xrow[o])(xf, order)
        xg = jax.vmap(
            lambda dst, xs: jnp.zeros((e * cap + 1, d), x.dtype).at[dst].set(xs)
        )(dest, x_sorted)
        xg = xg[:, :-1].reshape(g, e, cap, d)
        # (G, E, cap, D) -> (E, G, cap, D): the EP all-to-all when E is
        # sharded. Kept 4D through the expert einsums — flattening (G, cap)
        # would merge a sharded with an unsharded dim and force GSPMD into
        # full rematerialization.
        xe = jnp.swapaxes(xg, 0, 1)
        xe = constrain(xe, "egcd")
        from repro.core.compression import CompressedTensor

        if isinstance(params["w_up"], CompressedTensor):
            # compressed serving: per-expert DECA decompress-GeMM
            def expert_ffn(xi, eidx):
                pick = lambda ct: jax.tree.map(lambda a: a[eidx], ct)
                up = mm(xi, pick(params["w_up"]))
                if "w_gate" in params:
                    hi = _act(cfg.mlp_act, mm(xi, pick(params["w_gate"]))) * up
                else:
                    hi = _act(cfg.mlp_act, up)
                return mm(hi, pick(params["w_down"]))

            ye = jnp.stack([expert_ffn(xe[i], i) for i in range(e)])
        else:
            # explicit ZeRO: all-gather the FSDP ('data') shard of each expert
            # weight at point of use (no data-axis conflict inside the einsum).
            # Train-only: at decode the weights stay contraction-sharded and
            # the (tiny) outputs are all-reduced instead (§Perf hillclimb 2).
            from repro.dist.sharding import active_ctx

            ctx = active_ctx()
            gather = ctx is not None and ctx.mode == "train"
            wuse = lambda w, kind: constrain(w, kind) if gather else w
            w_up = wuse(params["w_up"], "edf_use")
            w_down = wuse(params["w_down"], "efd_use")
            if "w_gate" in params:
                w_gate = wuse(params["w_gate"], "edf_use")
                h = _act(
                    cfg.mlp_act, jnp.einsum("egcd,edf->egcf", xe, w_gate)
                ) * jnp.einsum("egcd,edf->egcf", xe, w_up)
            else:
                h = _act(cfg.mlp_act, jnp.einsum("egcd,edf->egcf", xe, w_up))
            h = constrain(h, "egcf")
            ye = jnp.einsum("egcf,efd->egcd", h, w_down)
        yg = jnp.swapaxes(ye, 0, 1)  # A2A back: (G, E, cap, D)
        yflat = yg.reshape(g, e * cap, d)
        yflat = jnp.concatenate(
            [yflat, jnp.zeros((g, 1, d), yflat.dtype)], axis=1
        )
        y_tok = jax.vmap(lambda yrow, dst: yrow[dst])(yflat, dest)
        gathered_gates = jnp.take_along_axis(gates, order, axis=1)
        weighted = (y_tok * (gathered_gates * keep)[:, :, None]).astype(x.dtype)
        contrib = jax.vmap(
            lambda o, w: jnp.zeros((tl, d), x.dtype).at[o].set(w)
        )(order, weighted)
        return out + contrib, None

    out0 = jnp.zeros((g, tl, d), x.dtype)
    out, _ = jax.lax.scan(one_slot, out0, jnp.arange(k))
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# RG-LRU recurrent block (Griffin / recurrentgemma)
# ---------------------------------------------------------------------------

def init_rglru(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, r = cfg.d_model, cfg.lru_width or cfg.d_model
    ks = jax.random.split(key, 6)
    return {
        "w_in": _dense_init(ks[0], (d, r), dtype=dtype),
        "w_gate_branch": _dense_init(ks[1], (d, r), dtype=dtype),
        "conv_w": _dense_init(ks[2], (cfg.ssm_conv, r), dtype=jnp.float32),
        "w_a": _dense_init(ks[3], (r, r), dtype=dtype),
        "w_x": _dense_init(ks[4], (r, r), dtype=dtype),
        "b_a": jnp.zeros((r,), jnp.float32),
        "b_x": jnp.zeros((r,), jnp.float32),
        # c=8 in Griffin; a = sigmoid(lambda) stable init around 0.9-0.999
        "a_param": jnp.log(jnp.expm1(jnp.linspace(0.9, 0.999, r) ** (1 / 8.0))),
        "w_out": _dense_init(ks[5], (r, d), dtype=dtype),
    }


def rglru_scan(
    params: Params,
    u: jax.Array,  # (B, S, R) conv output
    h0: jax.Array,  # (B, R)
) -> Tuple[jax.Array, jax.Array]:
    """h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * u_t)."""
    c = 8.0
    log_a_base = -c * jax.nn.softplus(params["a_param"])  # (R,) negative
    r_gate = jax.nn.sigmoid(
        u.astype(jnp.float32) @ params["w_a"].astype(jnp.float32) + params["b_a"]
    )
    i_gate = jax.nn.sigmoid(
        u.astype(jnp.float32) @ params["w_x"].astype(jnp.float32) + params["b_x"]
    )
    log_a = r_gate * log_a_base  # (B, S, R)
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.clip(1.0 - a * a, 1e-12)) * (i_gate * u.astype(jnp.float32))

    def step(h, inp):
        a_t, g_t = inp
        h = a_t * h + g_t
        return h, h

    hT, hs = jax.lax.scan(
        step, h0.astype(jnp.float32),
        (jnp.moveaxis(a, 1, 0), jnp.moveaxis(gated, 1, 0)),
    )
    return jnp.moveaxis(hs, 0, 1).astype(u.dtype), hT


def rglru_block(
    params: Params,
    x: jax.Array,  # (B, S, D)
    cfg: ModelConfig,
    state: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    b, s, _ = x.shape
    r = cfg.lru_width or cfg.d_model
    gate = jax.nn.gelu(mm(x, params["w_gate_branch"]))  # (B, S, R)
    u = mm(x, params["w_in"])  # (B, S, R)

    # short conv1d along time (depthwise)
    ck = cfg.ssm_conv
    if state is not None:
        conv_buf = state["conv"]  # (B, ck-1, R)
        u_ext = jnp.concatenate([conv_buf.astype(u.dtype), u], axis=1)
        new_conv = u_ext[:, -(ck - 1):, :]
    else:
        u_ext = jnp.pad(u, ((0, 0), (ck - 1, 0), (0, 0)))
        new_conv = u_ext[:, -(ck - 1):, :]
    # depthwise causal conv as ck shifted adds (no (B,S,ck,R) blow-up)
    u = sum(
        u_ext[:, i : i + s, :].astype(jnp.float32) * params["conv_w"][i]
        for i in range(ck)
    ).astype(x.dtype)

    h0 = state["h"] if state is not None else jnp.zeros((b, r), jnp.float32)
    hs, hT = rglru_scan(params, u, h0)
    out = mm(hs * gate, params["w_out"])
    new_state = {"conv": new_conv, "h": hT} if state is not None else None
    return out, new_state


# ---------------------------------------------------------------------------
# Mamba-1 block (falcon-mamba)
# ---------------------------------------------------------------------------

def init_mamba(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, di, st, dr = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    ks = jax.random.split(key, 6)
    a_init = jnp.tile(jnp.arange(1, st + 1, dtype=jnp.float32)[None, :], (di, 1))
    return {
        "in_proj": _dense_init(ks[0], (d, 2 * di), dtype=dtype),
        "conv_w": _dense_init(ks[1], (cfg.ssm_conv, di), dtype=jnp.float32),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "x_proj": _dense_init(ks[2], (di, dr + 2 * st), dtype=dtype),
        "dt_proj": _dense_init(ks[3], (dr, di), dtype=dtype),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((di,), 0.01))),  # softplus^-1(0.01)
        "a_log": jnp.log(a_init),
        "d_skip": jnp.ones((di,), jnp.float32),
        "out_proj": _dense_init(ks[4], (di, d), dtype=dtype),
    }


def mamba_block(
    params: Params,
    x: jax.Array,  # (B, S, D)
    cfg: ModelConfig,
    state: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Selective SSM: h_t = exp(dt*A) h_{t-1} + dt*B_t x_t ; y = C_t h + D x."""
    b, s, _ = x.shape
    di, st, dr = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    xz = mm(x, params["in_proj"])  # (B, S, 2*di)
    u, z = jnp.split(xz, 2, axis=-1)

    # depthwise causal conv
    ck = cfg.ssm_conv
    if state is not None:
        conv_buf = state["conv"]
        u_ext = jnp.concatenate([conv_buf.astype(u.dtype), u], axis=1)
        new_conv = u_ext[:, -(ck - 1):, :]
    else:
        u_ext = jnp.pad(u, ((0, 0), (ck - 1, 0), (0, 0)))
        new_conv = u_ext[:, -(ck - 1):, :]
    u = jax.nn.silu(
        sum(
            u_ext[:, i : i + s, :].astype(jnp.float32) * params["conv_w"][i]
            for i in range(ck)
        )
        + params["conv_b"]
    ).astype(x.dtype)

    # input-dependent SSM parameters
    proj = mm(u, params["x_proj"])  # (B, S, dr + 2*st)
    dt_r, b_mat, c_mat = jnp.split(proj, [dr, dr + st], axis=-1)
    dt = jax.nn.softplus(
        (dt_r @ params["dt_proj"]).astype(jnp.float32) + params["dt_bias"]
    )  # (B, S, di)
    a = -jnp.exp(params["a_log"])  # (di, st)
    da = jnp.exp(dt[..., None] * a)  # (B, S, di, st)
    db = dt[..., None] * b_mat[:, :, None, :].astype(jnp.float32)  # (B, S, di, st)
    dbu = db * u[..., None].astype(jnp.float32)

    h0 = (
        state["h"]
        if state is not None
        else jnp.zeros((b, di, st), jnp.float32)
    )

    def step(h, inp):
        da_t, dbu_t = inp
        h = da_t * h + dbu_t
        return h, h

    hT, hs = jax.lax.scan(
        step, h0, (jnp.moveaxis(da, 1, 0), jnp.moveaxis(dbu, 1, 0))
    )  # hs: (S, B, di, st)
    y = jnp.einsum("sbin,bsn->bsi", hs, c_mat.astype(jnp.float32))
    y = (y + params["d_skip"] * u.astype(jnp.float32)).astype(x.dtype)
    out = mm(y * jax.nn.silu(z), params["out_proj"])
    new_state = {"conv": new_conv, "h": hT} if state is not None else None
    return out, new_state
