"""Config-driven model assembly.

One `Model` class covers all assigned families:
  dense/vlm/audio : [attn + FFN] x L          (global / local_global)
  moe             : [attn + MoE-FFN] x L
  ssm             : [mamba] x L               (no separate FFN)
  hybrid          : pattern of [rec|attn + FFN] blocks (Griffin 2:1)

Homogeneous stacks are scanned (`lax.scan` over stacked layer params) so the
HLO stays compact at 64+ layers; heterogeneous patterns (gemma2,
recurrentgemma) are unrolled. Both paths share the same block function.

The paper's technique plugs in at serving time: `compress_params` converts
every FC weight into a `CompressedTensor` and `forward` routes matmuls
through `repro.kernels.ops.decompress_gemm` (see serve/engine.py).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.codecs import get_codec
from repro.dist.sharding import constrain
from repro.models import layers as L

Params = Dict[str, Any]

# Device-trace name (`jax.named_scope`) of the LM head matmul (DESIGN.md §14).
SCOPE_HEAD = "model.head"


def _kind_layout(cfg: ModelConfig) -> Tuple[str, ...]:
    return cfg.layer_kinds()


class Model:
    def __init__(self, cfg: ModelConfig):
        if cfg.kv_quant != "none":
            # fail fast on unregistered / non-KV formats instead of deep in
            # a jitted cache init
            codec = get_codec(cfg.kv_quant)
            if not codec.kv_capable:
                raise ValueError(
                    f"kv_quant={cfg.kv_quant!r} is not a KV-capable codec"
                )
        self.cfg = cfg
        self.kinds = _kind_layout(cfg)
        self.uniform = len(set(self.kinds)) == 1 and cfg.scan_layers

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def _init_block(self, key, kind: str, dtype) -> Params:
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        p: Params = {"pre_norm": L.init_rms_norm(cfg.d_model)}
        if kind in ("attn", "attn_local"):
            p["attn"] = L.init_attention(ks[0], cfg, dtype)
            if cfg.post_norms:
                p["post_attn_norm"] = L.init_rms_norm(cfg.d_model)
        elif kind == "ssm":
            p["mamba"] = L.init_mamba(ks[0], cfg, dtype)
        elif kind == "rec":
            p["rec"] = L.init_rglru(ks[0], cfg, dtype)
        if cfg.d_ff and kind != "ssm":
            p["pre_mlp_norm"] = L.init_rms_norm(cfg.d_model)
            if cfg.n_experts:
                p["moe"] = L.init_moe(ks[1], cfg, dtype)
            else:
                p["mlp"] = L.init_mlp(ks[1], cfg, dtype)
            if cfg.post_norms:
                p["post_mlp_norm"] = L.init_rms_norm(cfg.d_model)
        return p

    def init(self, key: jax.Array, dtype=jnp.bfloat16) -> Params:
        cfg = self.cfg
        k_emb, k_blocks, k_head, k_pos = jax.random.split(key, 4)
        params: Params = {
            "embed": (
                jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model)) * 0.02
            ).astype(dtype),
            "final_norm": L.init_rms_norm(cfg.d_model),
        }
        if cfg.pos_emb == "learned":
            params["pos_embed"] = (
                jax.random.normal(k_pos, (cfg.pos_table, cfg.d_model)) * 0.02
            ).astype(dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = (
                jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size))
                / math.sqrt(cfg.d_model)
            ).astype(dtype)
        bkeys = jax.random.split(k_blocks, cfg.n_layers)
        blocks = [
            self._init_block(bkeys[i], self.kinds[i], dtype)
            for i in range(cfg.n_layers)
        ]
        if self.uniform:
            params["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
        else:
            params["layers"] = {str(i): b for i, b in enumerate(blocks)}
        return params

    # ------------------------------------------------------------------
    # cache
    # ------------------------------------------------------------------
    def _block_cache(self, kind: str, b: int, max_len: int, dtype) -> Params:
        cfg = self.cfg
        if kind == "attn":
            return L.init_kv_cache(
                b, max_len, cfg.n_kv_heads, cfg.d_head, dtype, quant=cfg.kv_quant
            )
        if kind == "attn_local":
            return L.init_kv_cache(
                b, min(max_len, cfg.window), cfg.n_kv_heads, cfg.d_head, dtype,
                quant=cfg.kv_quant,
            )
        if kind == "ssm":
            return {
                "conv": jnp.zeros((b, cfg.ssm_conv - 1, cfg.d_inner), dtype),
                "h": jnp.zeros((b, cfg.d_inner, cfg.ssm_state), jnp.float32),
            }
        if kind == "rec":
            r = cfg.lru_width or cfg.d_model
            return {
                "conv": jnp.zeros((b, cfg.ssm_conv - 1, r), dtype),
                "h": jnp.zeros((b, r), jnp.float32),
            }
        raise ValueError(kind)

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> Any:
        caches = [
            self._block_cache(k, batch, max_len, dtype) for k in self.kinds
        ]
        if self.uniform:
            return jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
        return {str(i): c for i, c in enumerate(caches)}

    def init_paged_cache(
        self,
        num_blocks: int,
        block_size: int,
        dtype=jnp.bfloat16,
        kv_quant: Optional[str] = None,
    ) -> Any:
        """Block-paged KV pools (serve/paged_cache.py owns the block tables).

        `num_blocks` counts allocatable pages; one extra null page (device
        row 0) absorbs pad/inactive writes. Only attention stacks page —
        ssm/rec state is O(1) per request and needs no paging.

        `kv_quant` (default `cfg.kv_quant`) names the pool's codec; the
        decode path quantizes/dequantizes with `cfg.kv_quant`, so an
        explicit value must match — build the Model with the desired
        `kv_quant` (GenerationEngine's `kv_quant=` arg does this)."""
        cfg = self.cfg
        if kv_quant is not None and kv_quant != cfg.kv_quant:
            raise ValueError(
                f"pool kv_quant={kv_quant!r} != cfg.kv_quant={cfg.kv_quant!r}; "
                "the decode path reads cfg.kv_quant — rebuild the Model with "
                "the desired format"
            )
        bad = [k for k in self.kinds if k not in ("attn", "attn_local")]
        if bad:
            raise NotImplementedError(
                f"paged KV serving needs an attention stack, got {set(bad)}"
            )
        caches = [
            L.init_paged_kv_cache(
                num_blocks + 1, block_size, cfg.n_kv_heads, cfg.d_head, dtype,
                quant=cfg.kv_quant,
            )
            for _ in self.kinds
        ]
        if self.uniform:
            return jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
        return {str(i): c for i, c in enumerate(caches)}

    def paged_scrub(self, pools: Any, pages: jax.Array) -> Any:
        """Scrub the position plane of `pages` (device page ids, 0 = null
        no-op) to the empty sentinel across every layer — the out-of-step
        form of the fresh-page scrub `paged_update_cache` does inline.

        The scheduler uses it when one admission round recycles more pages
        than the jitted step's fixed `fresh_pages` width can carry (long-
        prompt bursts, unaligned chunked-prefill boundaries): overflow rows
        are scrubbed with dedicated calls *before* the step that writes
        into them, so a recycled page still never leaks its previous
        tenant's entries into a gather-read."""

        def one(cache):
            cache = dict(cache)
            # ppos is (nb, bs) per layer or (L, nb, bs) stacked; the
            # ellipsis lands `pages` on the page axis either way
            cache["ppos"] = cache["ppos"].at[..., pages, :].set(L.CACHE_EMPTY_POS)
            return cache

        if isinstance(pools, dict) and "ppos" in pools:
            return one(pools)
        return {k: one(c) for k, c in pools.items()}

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def _block_apply(
        self, p: Params, x: jax.Array, kind: str, positions, cache, paged=None
    ) -> Tuple[jax.Array, Any, jax.Array]:
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        h = L.rms_norm(p["pre_norm"], x, cfg.norm_eps)
        if kind in ("attn", "attn_local"):
            if paged is not None:
                out, new_cache = L.paged_attention_block(
                    p["attn"], h, cfg, positions=positions,
                    local=(kind == "attn_local"), cache=cache,
                    block_tables=paged["block_tables"],
                    write_slots=paged["write_slots"],
                    write_pos=paged["write_pos"],
                    fresh_pages=paged.get("fresh_pages"),
                    kv_lens=paged.get("kv_lens"),
                    copy_pages=paged.get("copy_pages"),
                    window_override=paged.get("window_override"),
                )
            else:
                out, new_cache = L.attention_block(
                    p["attn"], h, cfg, positions=positions,
                    local=(kind == "attn_local"), cache=cache,
                )
            if cfg.post_norms:
                out = L.rms_norm(p["post_attn_norm"], out, cfg.norm_eps)
        elif kind == "ssm":
            out, new_cache = L.mamba_block(p["mamba"], h, cfg, state=cache)
        elif kind == "rec":
            out, new_cache = L.rglru_block(p["rec"], h, cfg, state=cache)
        else:  # pragma: no cover
            raise ValueError(kind)
        x = x + out
        if cfg.d_ff and kind != "ssm":
            h = L.rms_norm(p["pre_mlp_norm"], x, cfg.norm_eps)
            if cfg.n_experts:
                out, aux = L.moe_block(p["moe"], h, cfg)
            else:
                out = L.mlp_block(p["mlp"], h, cfg)
            if cfg.post_norms:
                out = L.rms_norm(p["post_mlp_norm"], out, cfg.norm_eps)
            x = x + out
        x = constrain(x, "bsd")
        return x, new_cache, aux

    def forward(
        self,
        params: Params,
        *,
        tokens: Optional[jax.Array] = None,     # (B, S) int32
        embeds: Optional[jax.Array] = None,     # (B, S, D) frontend stub
        positions: Optional[jax.Array] = None,  # (B, S) or (3, B, S)
        cache: Optional[Any] = None,
        remat: bool = False,
        paged: Optional[Dict[str, jax.Array]] = None,
    ) -> Tuple[jax.Array, Any, jax.Array]:
        """Returns (logits (B, S, V), new_cache, moe_aux_loss).

        `paged` routes attention through the block-paged KV pool instead of
        the dense ring cache: {block_tables (B, MB), write_slots (B, S),
        write_pos (B, S)} — host-computed by serve/paged_cache.py. With
        paged, `cache` must be an `init_paged_cache` pool tree and
        `positions` carries true per-request positions. An optional
        `kv_lens` (B,) length vector (threaded from the scheduler's block
        allocator) routes decode shapes through the fused paged-attention
        page walk (DESIGN.md §13)."""
        cfg = self.cfg
        if embeds is None:
            x = jnp.take(params["embed"], tokens, axis=0)
        else:
            x = embeds
        b, s, _ = x.shape
        if cfg.embed_scale:
            x = x * math.sqrt(cfg.d_model)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        if cfg.pos_emb == "learned":
            tok_pos = positions if positions.ndim == 2 else positions[0]
            idx = jnp.clip(tok_pos, 0, cfg.pos_table - 1)
            x = x + jnp.take(params["pos_embed"], idx, axis=0)
        x = constrain(x.astype(jnp.bfloat16), "bsd")

        if paged is not None and cache is None:
            raise ValueError("paged forward requires an init_paged_cache pool")

        block = self._block_apply
        if remat:
            block = jax.checkpoint(
                block, static_argnums=(2,), prevent_cse=False
            )

        if self.uniform:
            kind = self.kinds[0]

            def body(carry, per_layer):
                xc, aux_acc = carry
                if cache is None:
                    p_l, cache_l = per_layer, None
                else:
                    p_l, cache_l = per_layer
                xc, new_cache_l, aux_l = block(
                    p_l, xc, kind, positions, cache_l, paged
                )
                return (xc, aux_acc + aux_l), new_cache_l

            xs = params["blocks"] if cache is None else (params["blocks"], cache)
            (x, aux), new_cache = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
            if cache is None:
                new_cache = None
        else:
            aux = jnp.zeros((), jnp.float32)
            new_cache = {} if cache is not None else None
            for i, kind in enumerate(self.kinds):
                cache_l = cache[str(i)] if cache is not None else None
                x, nc, aux_l = block(
                    params["layers"][str(i)], x, kind, positions, cache_l, paged
                )
                aux = aux + aux_l
                if cache is not None:
                    new_cache[str(i)] = nc

        x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
        with jax.named_scope(SCOPE_HEAD):
            if cfg.tie_embeddings:
                logits = jnp.einsum(
                    "bsd,vd->bsv", x, params["embed"],
                    preferred_element_type=jnp.float32,
                )
            else:
                from repro.core.decompress import mm

                logits = mm(x.astype(jnp.float32), params["lm_head"])
        if cfg.final_softcap:
            logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
        logits = constrain(logits, "btv")
        return logits, new_cache, aux

    # ------------------------------------------------------------------
    # losses and steps
    # ------------------------------------------------------------------
    def loss(
        self,
        params: Params,
        batch: Dict[str, jax.Array],
        *,
        remat: bool = True,
        aux_weight: float = 0.01,
        z_weight: float = 1e-4,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        logits, _, aux = self.forward(
            params,
            tokens=batch.get("tokens"),
            embeds=batch.get("embeds"),
            positions=batch.get("positions"),
            remat=remat,
        )
        labels = batch["labels"]
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0] - logz
        mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = -(ll * mask).sum() / denom
        zl = ((logz * mask) ** 2).sum() / denom
        total = ce + aux_weight * aux + z_weight * zl
        return total, {"ce": ce, "aux": aux, "z_loss": zl}

    def decode_step(
        self,
        params: Params,
        tokens: jax.Array,      # (B, 1)
        positions: jax.Array,   # (B, 1) or (3, B, 1)
        cache: Any,
    ) -> Tuple[jax.Array, Any]:
        """One next-token step against a filled cache. Returns (logits(B,V), cache)."""
        logits, new_cache, _ = self.forward(
            params, tokens=tokens, positions=positions, cache=cache
        )
        return logits[:, -1, :], new_cache

    def decode_step_paged(
        self,
        params: Params,
        tokens: jax.Array,        # (B, 1)
        positions: jax.Array,     # (B, 1) true per-request positions
        cache: Any,               # init_paged_cache pool tree
        block_tables: jax.Array,  # (B, MB)
        write_slots: jax.Array,   # (B, 1)
        write_pos: jax.Array,     # (B, 1)
        fresh_pages: jax.Array,   # (B,) pages newly allocated this step
        kv_lens: Optional[jax.Array] = None,  # (B,) valid KV tokens per slot
    ) -> Tuple[jax.Array, Any]:
        """One next-token step over the active continuous-batching slots,
        reading/writing the block-paged pool. Fixed-shape: B is the slot
        count and MB the max pages per request, so it jits once. `kv_lens`
        (threaded from the scheduler) bounds the fused attention page walk;
        without it the step falls back to the gather-read reference."""
        logits, new_cache, _ = self.forward(
            params, tokens=tokens, positions=positions, cache=cache,
            paged={
                "block_tables": block_tables,
                "write_slots": write_slots,
                "write_pos": write_pos,
                "fresh_pages": fresh_pages,
                "kv_lens": kv_lens,
            },
        )
        return logits[:, -1, :], new_cache

    def decode_chunk_paged(
        self,
        params: Params,
        tokens0: jax.Array,       # (B, 1) last sampled token per slot
        cache: Any,               # init_paged_cache pool tree
        block_tables: jax.Array,  # (B, MB) — static for the whole chunk
        positions: jax.Array,     # (C, B, 1) per-step per-slot positions
        write_slots: jax.Array,   # (C, B, 1) precomputed flat slot ids
        write_pos: jax.Array,     # (C, B, 1) write positions
        fresh_pages: jax.Array,   # (C, F) pages to scrub (row 0 real)
        kv_lens: jax.Array,       # (C, B) valid KV tokens per step per slot
        *,
        sample_fn: Callable[[jax.Array, jax.Array], jax.Array],
        max_steps: jax.Array,     # (B,) steps this slot may still take
        eos_ids: jax.Array,       # (B,) int32 eos token, -1 = none
        active: jax.Array,        # (B,) bool — slot holds a live request
    ) -> Tuple[jax.Array, Any]:
        """Device-resident multi-step decode: C steps in one `lax.scan`.

        The paper's TEPL extension removes per-invocation synchronization
        between the core and DECA (§5); this is the serving-loop analog —
        the host round-trip (token sync + numpy batch assembly) moves off
        the per-token path onto the per-chunk path. Sampled tokens feed
        back on device; per-slot done flags (EOS / length cap) are computed
        on device and route the writes of finished slots to the null page,
        so the KV pool is bitwise what C single steps would have produced.

        `sample_fn(logits (B, V), step j) -> tokens (B,)` is supplied by
        the engine (it owns keys/temperature). Returns (tokens (C, B),
        new cache). Tokens past a slot's done point are junk the host
        discards when it replays the chunk against request state.
        """
        def body(carry, xs):
            pools, tok, done, j = carry
            pos, wslot, wpos, fresh, klen = xs
            # finished (or inactive) slots write to the null page with the
            # empty sentinel — identical to the single-step inactive path
            wslot = jnp.where(done[:, None], 0, wslot)
            wpos = jnp.where(done[:, None], L.CACHE_EMPTY_POS, wpos)
            if self.cfg.mrope_sections:
                pos = jnp.broadcast_to(pos, (3,) + pos.shape)
            logits, pools = self.decode_step_paged(
                params, tok, pos, pools, block_tables, wslot, wpos, fresh,
                klen,
            )
            t = sample_fn(logits, j).astype(jnp.int32)
            done = done | (j + 1 >= max_steps) | (t == eos_ids)
            return (pools, t[:, None], done, j + 1), t

        done0 = ~active
        carry0 = (cache, tokens0, done0, jnp.zeros((), jnp.int32))
        (new_cache, _, _, _), toks = jax.lax.scan(
            body, carry0,
            (positions, write_slots, write_pos, fresh_pages, kv_lens),
        )
        return toks, new_cache

    def spec_decode_chunk(
        self,
        params: Params,
        draft_params: Params,
        tokens0: jax.Array,       # (M, 1) pending token per slot (KV unwritten)
        cache: Any,               # init_paged_cache pool tree
        block_tables: jax.Array,  # (M, TW) device page ids, bounded width
        p0: jax.Array,            # (M,) position of the pending token
        fresh: jax.Array,         # (F,) device page ids to pre-scrub (0 = noop)
        *,
        sample_fn: Callable[[jax.Array, jax.Array], jax.Array],
        max_steps: jax.Array,     # (M,) emissions this slot may still take
        eos_ids: jax.Array,       # (M,) int32 eos token, -1 = none
        active: jax.Array,        # (M,) bool — slot holds a live request
        k: int,
        rounds: int,
        block_size: int,
        draft_window: int = 0,
        out_cap: Optional[int] = None,
    ) -> Tuple[jax.Array, jax.Array, Any]:
        """Device-resident speculative decode: `rounds` draft-k/verify-once
        rounds in one `lax.scan` (DESIGN.md §16).

        Per-slot state between rounds is (committed positions < pos,
        pending token at `pos` with KV unwritten). A round drafts k tokens
        through `draft_params` — k fused S=1 steps, optionally window-capped
        via `draft_window` — writing draft-weight KV as it goes, then runs
        ONE target forward over the k+1 positions [pending, d_1..d_k],
        overwriting every draft entry with target KV before the bounded
        gather-read. Greedy/keyed sampling of the k+1 verify rows uses
        `sample_fn(logits (M,S,V), chunk_idx (M,S))` on the SAME
        per-(rid, output-index) key stream the sequential path uses — the
        draft proposes with it too — so the accepted prefix plus bonus
        token is bit-identical to sequential decode.

        Rejected drafts are never rolled back on device: their entries sit
        at positions > every committed query position (causally masked,
        DESIGN.md §13) until the next round's writes — which start at the
        new pending position ≤ stale-min — overwrite them. Writes that
        would run past `p0 + max_steps` (drafts overhanging a slot's
        emission budget) route to the null page with the empty sentinel,
        so the host's page reservation is never exceeded; whole-page
        overhang left at chunk end is trimmed by
        `PagedKVCache.rollback`.

        Returns (out (out_cap, M) emitted tokens packed from row 0,
        e_rounds (rounds, M) per-round emission counts for host replay,
        new cache)."""
        cfg = self.cfg
        m = tokens0.shape[0]
        bs = block_size
        tw = block_tables.shape[1]
        if out_cap is None:
            out_cap = rounds * (k + 1)
        limit = p0 + max_steps  # first write position past the slot's budget

        cache = self.paged_scrub(cache, fresh)
        offs = jnp.arange(k + 1, dtype=jnp.int32)

        def slots_for(wpos, ok):
            # flat slot ids from the bounded table; invalid writes land on
            # the null page with the empty sentinel (inactive-slot idiom)
            idx = jnp.clip(wpos // bs, 0, tw - 1)
            page = jnp.take_along_axis(block_tables, idx, axis=1)
            page = jnp.where(ok, page, 0)
            return page * bs + wpos % bs, jnp.where(ok, wpos, L.CACHE_EMPTY_POS)

        def fwd(pp, pools, toks, wpos, ok, klen, wov):
            wslot, eff = slots_for(wpos, ok)
            pos = wpos
            if cfg.mrope_sections:
                pos = jnp.broadcast_to(pos, (3,) + pos.shape)
            logits, pools, _ = self.forward(
                pp, tokens=toks, positions=pos, cache=pools,
                paged={
                    "block_tables": block_tables,
                    "write_slots": wslot,
                    "write_pos": eff,
                    "kv_lens": klen,
                    "window_override": wov,
                },
            )
            return logits, pools

        def round_body(carry, _):
            pools, tok, pos, emitted, done, out = carry
            live = ~done

            # draft phase: k proposals, draft weights, fused S=1 walks
            def draft_body(dc, j):
                dpools, dtok = dc
                wpos = (pos + j)[:, None]
                ok = live[:, None] & (wpos < limit[:, None])
                logits, dpools = fwd(
                    draft_params, dpools, dtok, wpos, ok,
                    jnp.minimum(pos + j + 1, limit), draft_window or None,
                )
                d = sample_fn(logits, (emitted + j)[:, None]).astype(jnp.int32)
                return (dpools, d), d[:, 0]

            (pools, _), drafts = jax.lax.scan(
                draft_body, (pools, tok), jnp.arange(k)
            )
            drafts = drafts.T  # (M, k)

            # verify phase: one target forward over the k+1 positions
            toks_v = jnp.concatenate([tok, drafts], axis=1)
            wpos_v = pos[:, None] + offs[None, :]
            ok_v = live[:, None] & (wpos_v < limit[:, None])
            logits_v, pools = fwd(params, pools, toks_v, wpos_v, ok_v, None, None)
            s = sample_fn(logits_v, emitted[:, None] + offs[None, :])
            s = s.astype(jnp.int32)  # (M, k+1)

            # acceptance: longest matched draft prefix, plus the bonus row
            rem = max_steps - emitted
            span = jnp.clip(jnp.minimum(k, rem - 1), 0, k)
            match = (s[:, :k] == drafts) & (offs[None, :k] < span[:, None])
            a = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
            e = a + 1
            is_eos = (s == eos_ids[:, None]) & (offs[None, :] < e[:, None])
            has_eos = jnp.any(is_eos, axis=1)
            e = jnp.where(has_eos, jnp.argmax(is_eos, axis=1) + 1, e)
            e = jnp.where(live, e, 0)

            # emit + advance: accepted rows pack into `out` at the slot's
            # running emission index; the last accepted sample becomes the
            # next round's pending token
            rows = jnp.where(
                offs[None, :] < e[:, None],
                emitted[:, None] + offs[None, :], out_cap,
            )
            out = out.at[rows, jnp.arange(m)[:, None]].set(s, mode="drop")
            last = jnp.take_along_axis(s, jnp.clip(e - 1, 0, k)[:, None], axis=1)
            tok = jnp.where(live[:, None], last, tok)
            pos = pos + e
            emitted = emitted + e
            done = done | has_eos | (emitted >= max_steps)
            return (pools, tok, pos, emitted, done, out), e

        carry0 = (
            cache, tokens0.astype(jnp.int32), p0,
            jnp.zeros((m,), jnp.int32), ~active,
            jnp.zeros((out_cap, m), jnp.int32),
        )
        (new_cache, _, _, _, _, out), e_rounds = jax.lax.scan(
            round_body, carry0, None, length=rounds
        )
        return out, e_rounds, new_cache
