"""Serving: prefill / decode step builders and the generation engine.

`make_prefill_step` / `make_decode_step` are the units the multi-pod dry-run
lowers (`decode_*` / `long_*` cells lower serve_step — one new token against
a seq_len KV cache — per the assignment).

The engine supports compressed-weight serving: pass params through
`compress_params` and the FC matmuls route through the DECA decompress-GeMM
(kernels/ops.py) — the paper's technique on the serving critical path.

Two cache regimes (DESIGN.md §6/§10):

  paged (default for attention stacks)
      block-paged KV pool + continuous-batching scheduler. Request-level
      API: `submit()` / `run_until_drained()`; `generate()` is a thin
      wrapper that submits one request per prompt row. A request at length
      `len` holds ceil(len / block_size) pages — nothing is padded to
      max_len.
  dense (`paged=False`, and the fallback for ssm/rec stacks)
      the legacy fixed-slot ring cache: one (B, max_len) batch runs to
      completion. Kept as the golden reference the paged path is tested
      against, and for recurrent models whose state is O(1) per request.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.decompress import current_impl, use_impl
from repro.dist import sharding as sh
from repro.models.model import Model
from repro.obs.trace import Span
from repro.serve.host_tier import HostTier, apply_page_planes
from repro.serve.paged_cache import PagedKVCache
from repro.serve.scheduler import Scheduler


def make_prefill_step(model: Model, cache_len: Optional[int] = None) -> Callable:
    """prefill(params, batch) -> (last_logits (B, V), cache)."""

    def prefill(params, batch):
        tokens = batch.get("tokens")
        embeds = batch.get("embeds")
        positions = batch.get("positions")
        b = (tokens if tokens is not None else embeds).shape[0]
        s = (tokens if tokens is not None else embeds).shape[1]
        cache = model.init_cache(b, cache_len or s)
        logits, cache, _ = model.forward(
            params, tokens=tokens, embeds=embeds, positions=positions, cache=cache
        )
        return logits[:, -1, :], cache

    return prefill


def make_decode_step(model: Model) -> Callable:
    """serve_step(params, tokens (B,1), positions, cache) -> (logits, cache)."""

    def serve_step(params, tokens, positions, cache):
        return model.decode_step(params, tokens, positions, cache)

    return serve_step


def make_paged_prefill_step(model: Model) -> Callable:
    """paged_prefill(params, tokens (B,Sp), positions, cache, block_tables,
    write_slots, write_pos, fresh_pages, copies (C,2), last_idx (B,)) ->
    (last-token logits (B,V), cache).

    Batched: every request admitted in a scheduling round prefills in one
    call (the scheduler buckets B to a power of two and Sp to the round's
    max page-rounded length, bounding the jit-shape count). Each row's last
    real token is gathered on device — only the (B, V) logits rows the
    sampler needs ever leave the forward pass.

    `copies` carries the round's queued copy-on-write page clones (null-page
    self-copies pad the fixed shape); the cache update applies them before
    any scrub or scatter, so a prefix-hit row recomputing its last prompt
    token writes into its private clone, never into the shared page.

    The same step serves chunked prefill (DESIGN.md §15): the scheduler
    passes a *length-bounded* block-table width covering only pages the
    chunk can attend to — the gather-read cost then scales with the prompt
    prefix written so far instead of the engine-wide max table width."""

    def paged_prefill(params, tokens, positions, cache, tables, slots, wpos,
                      fresh, copies, last_idx):
        logits, new_cache, _ = model.forward(
            params, tokens=tokens, positions=positions, cache=cache,
            paged={
                "block_tables": tables,
                "write_slots": slots,
                "write_pos": wpos,
                "fresh_pages": fresh,
                "copy_pages": copies,
            },
        )
        last = jnp.take_along_axis(logits, last_idx[:, None, None], axis=1)
        return last[:, 0], new_cache

    return paged_prefill


def make_paged_decode_step(model: Model) -> Callable:
    """paged_step(params, tokens (M,1), positions, cache, block_tables,
    write_slots, write_pos, fresh_pages, kv_lens (M,)) -> (logits (M,V),
    cache). Fixed shape over the M continuous-batching slots — jits exactly
    once. `kv_lens` bounds the fused attention page walk (DESIGN.md §13)."""

    def paged_step(params, tokens, positions, cache, tables, slots, wpos,
                   fresh, kv_lens):
        return model.decode_step_paged(
            params, tokens, positions, cache, tables, slots, wpos, fresh,
            kv_lens,
        )

    return paged_step


def sample_rows_keyed(key, rids, steps, logits, temp):
    """Per-row sampling keyed on (request id, token index) — THE key
    derivation, shared by the host-side sampler and the device-resident
    chunk sampler. The chunked == single-step token-reproducibility
    guarantee rests on both paths calling this one function."""
    def one(rid, step, row):
        k = jax.random.fold_in(jax.random.fold_in(key, rid), step)
        return jax.random.categorical(k, row / temp)

    return jax.vmap(one)(rids, steps, logits)


def make_paged_decode_chunk_step(model: Model) -> Callable:
    """Device-resident multi-step decode (DESIGN.md §12): C steps of
    `decode_step_paged` inside one `lax.scan`, with sampling, token
    feedback, and EOS/length-cap done flags all on device. One jit
    specialization per (C, F) bucket; `greedy` is static because it
    changes the sampler's structure, `temp`/`key` stay traced."""

    @functools.partial(jax.jit, static_argnames=("greedy",))
    def chunk_step(params, cache, tokens0, tables, positions, wslots, wpos,
                   fresh, kv_lens, rids, start_steps, max_steps, eos, active,
                   temp, key, *, greedy):
        def sample(logits, j):
            logits = logits.astype(jnp.float32)
            if greedy:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            steps = start_steps + j.astype(jnp.uint32)
            out = sample_rows_keyed(key, rids, steps, logits, temp)
            return out.astype(jnp.int32)

        return model.decode_chunk_paged(
            params, tokens0, cache, tables, positions, wslots, wpos, fresh,
            kv_lens,
            sample_fn=sample, max_steps=max_steps, eos_ids=eos, active=active,
        )

    return chunk_step


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Self-speculative decoding knobs (DESIGN.md §16).

    `k` draft tokens per verify; `draft_codec` names the codec-registry
    format the engine re-encodes the weight tree at for the draft pass (no
    second checkpoint — `make_draft_tree` requantizes the served weights);
    `draft_window` > 0 caps the draft's attention window so its fused page
    walk is O(window) instead of O(context) — verify always keeps the full
    window, so acceptance (and therefore output) stays exact; `rounds`
    draft/verify rounds run per device-resident chunk (default: enough to
    cover the engine's `decode_chunk` at full acceptance)."""

    k: int = 3
    draft_codec: str = "nf4"
    draft_window: int = 0
    rounds: Optional[int] = None

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"spec_decode needs k >= 1, got {self.k}")
        if self.draft_window < 0:
            raise ValueError("draft_window must be >= 0 (0 = full window)")
        if self.rounds is not None and self.rounds < 1:
            raise ValueError("rounds must be >= 1")


def make_paged_spec_decode_step(
    model: Model, *, k: int, rounds: int, draft_window: int, block_size: int
) -> Callable:
    """Device-resident speculative decode (DESIGN.md §16): `rounds`
    draft-k/verify-once rounds of `Model.spec_decode_chunk` per call. The
    sampler closure keys every row on (request id, global output index)
    through `sample_rows_keyed` — the same derivation as sequential decode,
    which is what makes accepted tokens bit-identical — and hands the draft
    the same stream so proposals agree with verify wherever the draft's
    logits do."""

    @functools.partial(jax.jit, static_argnames=("greedy",))
    def spec_step(params, draft_params, cache, tokens0, tables, p0, fresh,
                  rids, start_steps, max_steps, eos, active, temp, key, *,
                  greedy):
        def sample(logits, idx):
            # logits (M, S, V); idx (M, S) chunk-local output indices
            logits = logits.astype(jnp.float32)
            if greedy:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            m, s_dim, v = logits.shape
            steps = (start_steps[:, None] + idx.astype(jnp.uint32)).reshape(-1)
            flat = sample_rows_keyed(
                key, jnp.repeat(rids, s_dim), steps,
                logits.reshape(m * s_dim, v), temp,
            )
            return flat.reshape(m, s_dim).astype(jnp.int32)

        return model.spec_decode_chunk(
            params, draft_params, tokens0, cache, tables, p0, fresh,
            sample_fn=sample, max_steps=max_steps, eos_ids=eos,
            active=active, k=k, rounds=rounds, block_size=block_size,
            draft_window=draft_window,
        )

    return spec_step


class GenerationEngine:
    """Continuous-batching generation over a block-paged KV cache.

    Request model: `submit()` enqueues a prompt; `run_until_drained()` steps
    the scheduler — per-step admission into `max_slots` decode slots while
    free pages suffice, page-granular KV allocation, eviction on EOS or
    length cap — until every request completes. Admission happens on host;
    prefill (page-rounded prompt lengths) and the slot-batched decode step
    are fixed-shape jitted functions.

    Sampling is keyed per request on (seed, request id, token index), so a
    request's tokens are independent of admission order and of whatever
    else shares the batch.

    Sharded serving: pass a `mesh` and the engine places params — including
    DECA CompressedTensor weights, whose codes/mask/scales shard along the
    dense (K, N) axes — with `dist.sharding.param_spec_tree`, lays the KV
    pool out with the §10 rule (pages replicated over 'data', KV heads over
    'model'), and traces prefill/decode under `use_mesh(mode="serve")`,
    on the jnp kernels unless `use_impl` asked for others (GSPMD cannot
    partition the fused Pallas GEMV). With `mesh=None` nothing changes.

    `paged="auto"` (default) uses the paged path for attention stacks and
    falls back to the dense ring cache for ssm/rec stacks; `paged=False`
    forces the legacy fixed-batch path (the golden reference in tests).

    `kv_quant` names any KV-capable codec from `repro.core.codecs`
    (bf8/int8/int4/mxfp4/nf4/...) and quantizes the KV pools end-to-end:
    encode-on-write, dequantize-on-read, per-(slot, head) bf16 scales for
    scaled codecs, in both the paged pool and the dense ring cache. Default
    is the model config's `kv_quant`.

    `decode_chunk` (DESIGN.md §12) runs up to that many decode steps inside
    one jitted `lax.scan` — sampling, token feedback, and EOS/length-cap
    flags stay on device, and the host syncs once per chunk instead of once
    per token. `decode_chunk=1` restores the single-step loop (the golden
    reference in tests). `prefill_batch=False` likewise restores one jit
    call per admitted request (the pre-PR4 baseline in benchmarks).

    `prefix_cache=True` (DESIGN.md §15) turns on multi-tenant prefix
    sharing: a radix index over `block_size`-token prompt chunks maps
    cached prefixes to refcounted pages, admission pins the longest cached
    prefix and computes only the tail, and the first divergent write
    copy-on-writes the shared page. Greedy outputs are bit-identical to the
    unshared path; the default stays off so the pool drains to empty when
    idle (the prefix index deliberately retains pages).

    `prefill_chunk` caps how many prompt tokens one prefill call processes
    per request: longer (non-cached) prompt tails run as fixed-size chunks
    interleaved with decode rounds, each chunk reading through a
    length-bounded block table, so a long prompt neither stalls the running
    batch nor pays the engine-wide max gather width. `None` (default) keeps
    monolithic prefill.

    `spec_decode` (DESIGN.md §16) turns on self-speculative decoding: the
    engine re-encodes the served weight tree at `SpecConfig.draft_codec`
    (no second checkpoint), drafts `k` tokens per round through the fused
    paged walk at the draft codec's byte width, verifies all k+1 positions
    in one target-codec forward, and rolls rejected KV back in the paged
    pool. Greedy and keyed-temperature outputs are bit-identical to the
    non-speculative engine; only throughput changes. Requires the paged
    path.

    `obs` installs a `repro.obs.Observability` bundle (DESIGN.md §14):
    request-lifecycle tracing (TTFT/ITL, Chrome trace export), the metrics
    registry, and the RoofLens predicted-vs-measured loop — the engine
    binds the lens to this model's geometry (weight-stream bytes, codec,
    decode batch rows, chip count). Observability is host-side only: it
    never enters a jitted function, and with `obs=None` (the default) the
    serving loop takes the exact pre-PR6 path.

    `sla` installs a `repro.serve.slo.SLAPolicy` (DESIGN.md §17): bounded
    queue, TTFT shedding, roofline-driven ITL admission deferral, and the
    graceful-degradation ladder down to parking residents. `injector` /
    `watchdog` hook a `repro.dist.fault.FaultInjector` /
    `StragglerWatchdog` into the scheduler round loop (the serving chaos
    harness). All three require the paged engine; terminal per-request
    statuses surface through `GenerationEngine.statuses`.

    `host_tier` (DESIGN.md §18) installs a host-memory KV tier under the
    prefix cache: pool pressure *spills* cold cached pages (quantized
    payloads + CRC32C checksums) instead of dropping them, admission
    restores tier-resident prefix hits with a verified device upload
    before the first prefill round, and the degradation ladder gains a
    `spill` rung before `park`. Pass `True` for an unbounded tier or a
    configured `HostTier`; requires `prefix_cache=True` and the paged
    engine. `snapshot()` / `restore()` ride on the tier to persist the
    prefix index, tier payloads, and parked-session state across process
    death — a restarted engine keeps tenants warm and resumes parked
    sessions bit-identically.
    """

    def __init__(
        self,
        model: Model,
        params: Any,
        *,
        max_len: int = 2048,
        temperature: float = 0.0,
        seed: int = 0,
        mesh: Optional[jax.sharding.Mesh] = None,
        fsdp: bool = False,
        paged: Union[bool, str] = "auto",
        block_size: int = 32,
        max_slots: int = 4,
        num_blocks: Optional[int] = None,
        kv_quant: Optional[str] = None,
        decode_chunk: int = 8,
        prefill_batch: bool = True,
        prefix_cache: bool = False,
        prefill_chunk: Optional[int] = None,
        obs=None,
        spec_decode: Optional[SpecConfig] = None,
        prefill_sla_s: Optional[float] = None,
        sla=None,
        injector=None,
        watchdog=None,
        host_tier: Union[bool, HostTier, None] = None,
    ):
        if kv_quant is not None and kv_quant != model.cfg.kv_quant:
            # end-to-end kv_quant plumbing: the format name is a codec-
            # registry key; rebuilding the Model keeps cache init, the
            # quantize-on-write/dequantize-on-read sites, and the pool
            # layout on one consistent value (params are unaffected)
            model = type(model)(dataclasses.replace(model.cfg, kv_quant=kv_quant))
        self.kv_quant = model.cfg.kv_quant
        self.model = model
        self.cfg = model.cfg
        self.mesh = mesh
        self.fsdp = fsdp
        self.spec = spec_decode
        draft_params = None
        if spec_decode is not None:
            # self-speculation: the draft is the SAME weight tree re-encoded
            # at a cheaper codec, built from the raw params so the sharder
            # below places both trees with one rule
            from repro.core.decompress import make_draft_tree
            from repro.core.formats import get_spec

            draft_params = make_draft_tree(
                params, get_spec(spec_decode.draft_codec)
            )
        if mesh is not None:
            ctx = sh.ShardingCtx(mesh, fsdp=fsdp, mode="serve")
            params = sh.shard_params(params, ctx, scan_stacked=model.uniform)
            if draft_params is not None:
                draft_params = sh.shard_params(
                    draft_params, ctx, scan_stacked=model.uniform
                )
        else:
            # one upload: host (numpy) planes passed to every jitted call
            # would be copied to the device on each of them
            params = jax.device_put(params)
            draft_params = jax.device_put(draft_params)
        self.params = params
        self.draft_params = draft_params
        self.max_len = max_len
        self.temperature = temperature
        self._seed = seed
        self._base_key = jax.random.PRNGKey(seed)
        self._prefill = jax.jit(make_prefill_step(model, cache_len=max_len))
        self._decode = jax.jit(make_decode_step(model))

        self.obs = obs
        if obs is not None and obs.rooflens is not None:
            self._bind_rooflens(obs.rooflens, max_slots)

        attn_only = all(k in ("attn", "attn_local") for k in model.kinds)
        if paged == "auto":
            paged = attn_only
        self.paged = bool(paged)
        if spec_decode is not None and not self.paged:
            raise ValueError("spec_decode requires the paged engine")
        if not self.paged and (
            sla is not None or injector is not None or watchdog is not None
        ):
            raise ValueError(
                "sla / injector / watchdog require the paged engine "
                "(the dense ring cache has no admission loop to gate)"
            )
        self.tier: Optional[HostTier] = None
        if host_tier:
            if not self.paged:
                raise ValueError("host_tier requires the paged engine")
            self.tier = (
                host_tier if isinstance(host_tier, HostTier) else HostTier()
            )
        self.scheduler: Optional[Scheduler] = None
        if self.paged:
            self.block_size = block_size
            self.max_blocks = math.ceil(max_len / block_size)
            if num_blocks is None:
                num_blocks = max_slots * self.max_blocks
            self.kv = PagedKVCache(
                model, num_blocks=num_blocks, block_size=block_size,
                kv_quant=self.kv_quant, prefix_cache=prefix_cache,
                tier=self.tier,
            )
            if mesh is not None:
                ctx = sh.ShardingCtx(mesh, fsdp=fsdp, mode="serve")
                specs = sh.data_spec_tree(
                    self.kv.pools, ctx, scan_stacked=model.uniform
                )
                self.kv.pools = jax.tree.map(
                    lambda a, s: jax.device_put(
                        a, jax.sharding.NamedSharding(mesh, s)
                    ),
                    self.kv.pools, specs,
                )
            self._paged_prefill = jax.jit(make_paged_prefill_step(model))
            self._paged_decode = jax.jit(make_paged_decode_step(model))
            self._paged_decode_chunk = make_paged_decode_chunk_step(model)
            self._paged_scrub = jax.jit(model.paged_scrub)
            self.spec_rounds = 0
            self._paged_spec_chunk = None
            if spec_decode is not None:
                self.spec_rounds = spec_decode.rounds or max(
                    1, -(-max(1, decode_chunk) // (spec_decode.k + 1))
                )
                self._paged_spec_chunk = make_paged_spec_decode_step(
                    model, k=spec_decode.k, rounds=self.spec_rounds,
                    draft_window=spec_decode.draft_window,
                    block_size=block_size,
                )
            # window-aware page freeing is sound only when *every* layer's
            # attention is local: one global layer keeps the full history
            # live (the pool is shared across layers)
            all_local = all(k == "attn_local" for k in model.kinds)
            self.scheduler = Scheduler(
                self.kv,
                max_slots=max_slots,
                max_len=max_len,
                prefill_fn=self._run_paged_prefill,
                decode_fn=self._run_paged_decode,
                sample_fn=self._sample_rows,
                decode_chunk_fn=self._run_paged_decode_chunk,
                chunk=max(1, decode_chunk),
                prefill_batch=prefill_batch,
                prefill_chunk=prefill_chunk,
                scrub_fn=self._run_paged_scrub,
                local_window=(
                    self.cfg.window if all_local and self.cfg.window > 0 else None
                ),
                obs=obs,
                spec_fn=(
                    self._run_paged_spec_chunk if spec_decode is not None else None
                ),
                spec_k=spec_decode.k if spec_decode is not None else 0,
                spec_rounds=self.spec_rounds,
                spec_window=(
                    spec_decode.draft_window if spec_decode is not None else 0
                ),
                prefill_sla_s=prefill_sla_s,
                sla=sla,
                injector=injector,
                watchdog=watchdog,
                tier_restore_fn=(
                    self._run_tier_restore if self.tier is not None else None
                ),
            )

    @contextlib.contextmanager
    def _mesh_scope(self):
        """Trace and run under the engine's mesh. The placed weights are
        GSPMD-sharded and GSPMD cannot partition a Pallas call, so where
        no kernel implementation was asked for the steps trace the jnp
        kernels (`ops.decompress_gemm`'s 'auto' would pick the fused GEMV
        on a TPU)."""
        if self.mesh is None:
            yield
            return
        impl = current_impl()
        with sh.use_mesh(self.mesh, fsdp=self.fsdp, mode="serve"), use_impl(
            "ref" if impl == "auto" else impl
        ):
            yield

    def _bind_rooflens(self, lens, max_slots: int) -> None:
        """Bind the RoofLens predicted-vs-measured model (DESIGN.md §14) to
        this engine's traffic shape: stored weight-stream bytes (compressed
        leaves count their packed planes via `.nbytes` — no device
        transfer), the dense element count behind them (sizes the
        decompression vector-op term), weight/KV codecs, decode batch rows,
        and the chip count the streams are sharded over."""
        from repro.core.compression import CompressedTensor

        leaves = jax.tree_util.tree_leaves(
            self.params, is_leaf=lambda x: isinstance(x, CompressedTensor)
        )
        compressed = [l for l in leaves if isinstance(l, CompressedTensor)]
        draft_bytes = None
        if self.draft_params is not None:
            draft_bytes = sum(
                int(l.nbytes)
                for l in jax.tree_util.tree_leaves(
                    self.draft_params,
                    is_leaf=lambda x: isinstance(x, CompressedTensor),
                )
            )
        lens.bind(
            cfg=self.cfg,
            weight_bytes=sum(int(l.nbytes) for l in leaves),
            weight_elems=sum(
                int(np.prod(ct.shape)) for ct in compressed
            ),
            weight_spec=compressed[0].spec.name if compressed else None,
            kv_quant=self.kv_quant,
            m_slots=max_slots,
            n_chips=self.mesh.size if self.mesh is not None else 1,
            draft_weight_bytes=draft_bytes,
            spec_k=self.spec.k if self.spec is not None else 0,
            draft_window=self.spec.draft_window if self.spec is not None else 0,
        )

    # ------------------------------------------------------------------
    # sampling: keyed per (request, token index) — admission order and
    # batch composition can never change a request's sampled tokens
    # ------------------------------------------------------------------
    @functools.cached_property
    def _sampler(self):
        return jax.jit(sample_rows_keyed)

    def _sample_rows(
        self, logits: jax.Array, rids: np.ndarray, steps: np.ndarray
    ) -> np.ndarray:
        """logits (N, V) -> tokens (N,); greedy at temperature <= 0.
        Sampling runs on device — only the (N,) token ids cross to host."""
        if self.temperature <= 0.0:
            return np.asarray(jnp.argmax(jnp.asarray(logits), axis=-1))
        out = self._sampler(
            self._base_key,
            jnp.asarray(rids, jnp.uint32),
            jnp.asarray(steps, jnp.uint32),
            jnp.asarray(logits, jnp.float32),
            jnp.float32(self.temperature),
        )
        return np.asarray(out)

    # ------------------------------------------------------------------
    # paged request API
    # ------------------------------------------------------------------
    def _positions(self, pos2d: jax.Array) -> jax.Array:
        if self.cfg.mrope_sections:
            return jnp.broadcast_to(pos2d, (3,) + pos2d.shape)
        return pos2d

    def _run_paged_prefill(
        self, tokens, positions, tables, slots, wpos, fresh, copies, last_idx
    ):
        with self._mesh_scope():
            logits, self.kv.pools = self._paged_prefill(
                self.params,
                jnp.asarray(tokens),
                self._positions(jnp.asarray(positions)),
                self.kv.pools,
                jnp.asarray(tables),
                jnp.asarray(slots),
                jnp.asarray(wpos),
                jnp.asarray(fresh),
                jnp.asarray(copies),
                jnp.asarray(last_idx),
            )
        return logits

    def _run_paged_scrub(self, pages):
        """Out-of-step scrub for fresh-page overflow rows (see
        `Model.paged_scrub`): one fixed-shape jitted call per extra row."""
        with self._mesh_scope():
            self.kv.pools = self._paged_scrub(
                self.kv.pools, jnp.asarray(pages, jnp.int32)
            )

    def _run_tier_restore(self, dev_pages, planes_list):
        """Upload verified tier payloads into their reserved HBM pages
        (DESIGN.md §18). Eager `.at[].set` scatter, mirroring the
        out-of-step scrub: it runs *before* the jitted launch that reads
        the pages, and under a mesh the updated pools are re-placed with
        their original shardings (the eager op would otherwise decide its
        own layout)."""
        with self._mesh_scope():
            old = self.kv.pools
            new = apply_page_planes(old, dev_pages, planes_list)
            if self.mesh is not None:
                new = jax.tree.map(
                    lambda n, o: jax.device_put(n, o.sharding), new, old
                )
            self.kv.pools = new

    def _run_paged_decode(
        self, tokens, positions, tables, slots, wpos, fresh, kv_lens
    ):
        with self._mesh_scope():
            logits, self.kv.pools = self._paged_decode(
                self.params,
                jnp.asarray(tokens),
                self._positions(jnp.asarray(positions)),
                self.kv.pools,
                jnp.asarray(tables),
                jnp.asarray(slots),
                jnp.asarray(wpos),
                jnp.asarray(fresh),
                jnp.asarray(kv_lens, jnp.int32),
            )
        return logits

    def _run_paged_decode_chunk(
        self, tokens0, tables, positions, wslots, wpos, fresh, kv_lens,
        rids, start_steps, max_steps, eos, active,
    ):
        """One device-resident chunk: only the sampled (C, M) token ids
        cross back to host — a single synchronization per `chunk` tokens,
        which the scheduler makes (returned on device)."""
        with self._mesh_scope():
            toks, self.kv.pools = self._paged_decode_chunk(
                self.params,
                self.kv.pools,
                jnp.asarray(tokens0),
                jnp.asarray(tables),
                jnp.asarray(positions),
                jnp.asarray(wslots),
                jnp.asarray(wpos),
                jnp.asarray(fresh),
                jnp.asarray(kv_lens, jnp.int32),
                jnp.asarray(rids, jnp.uint32),
                jnp.asarray(start_steps, jnp.uint32),
                jnp.asarray(max_steps, jnp.int32),
                jnp.asarray(eos, jnp.int32),
                jnp.asarray(active),
                jnp.float32(self.temperature),
                self._base_key,
                greedy=self.temperature <= 0.0,
            )
        return toks

    def _run_paged_spec_chunk(
        self, tokens0, tables, p0, fresh, rids, start_steps, max_steps, eos,
        active,
    ):
        """One device-resident spec chunk: `spec_rounds` draft/verify rounds;
        only the packed emitted tokens and per-round emission counts cross
        back to host (returned on device; the scheduler syncs)."""
        with self._mesh_scope():
            out, e_rounds, self.kv.pools = self._paged_spec_chunk(
                self.params,
                self.draft_params,
                self.kv.pools,
                jnp.asarray(tokens0),
                jnp.asarray(tables),
                jnp.asarray(p0, jnp.int32),
                jnp.asarray(fresh, jnp.int32),
                jnp.asarray(rids, jnp.uint32),
                jnp.asarray(start_steps, jnp.uint32),
                jnp.asarray(max_steps, jnp.int32),
                jnp.asarray(eos, jnp.int32),
                jnp.asarray(active),
                jnp.float32(self.temperature),
                self._base_key,
                greedy=self.temperature <= 0.0,
            )
        return out, e_rounds

    def submit(
        self,
        prompt: np.ndarray,
        *,
        max_new_tokens: int,
        eos_id: Optional[int] = None,
        deadline_s: Optional[float] = None,
        priority: int = 0,
    ) -> int:
        """Enqueue one request; returns its id (key into run_until_drained).
        `deadline_s` / `priority` feed the §17 resilience layer: a deadline
        drops the request (EXPIRED / PREEMPTED) once it can no longer be
        served; priority orders park-victim selection under pool pressure."""
        if not self.paged:
            raise RuntimeError("request-level API requires the paged engine")
        with Span("serve.submit"):
            return self.scheduler.submit(
                prompt, max_new_tokens=max_new_tokens, eos_id=eos_id,
                deadline_s=deadline_s, priority=priority,
            )

    @property
    def statuses(self) -> Dict[int, Any]:
        """rid -> terminal `RequestStatus` for every finished request (§17).
        Unlike results, statuses are not drained — the mapping accumulates
        for the engine's lifetime."""
        if not self.paged:
            raise RuntimeError("request-level API requires the paged engine")
        return dict(self.scheduler.statuses)

    def run_until_drained(self) -> Dict[int, np.ndarray]:
        """Step the scheduler until every submitted request completes."""
        if not self.paged:
            raise RuntimeError("request-level API requires the paged engine")
        return self.scheduler.run_until_drained()

    # ------------------------------------------------------------------
    # crash-safe persistence (DESIGN.md §18)
    # ------------------------------------------------------------------
    def _require_tiered(self, what: str) -> None:
        if not self.paged or self.kv.prefix is None or self.tier is None:
            raise RuntimeError(
                f"{what} requires the paged engine with prefix_cache=True "
                "and a host_tier (the snapshot format is the tier's "
                "content-addressed payloads)"
            )

    def snapshot(self, directory: str) -> Dict[str, int]:
        """Persist the engine's warm state to `directory`, atomically
        (manifest-written-last): every resident is parked (emitted tokens
        fold into its prompt, exactly the overload-preemption path), every
        index page spills into the host tier as a checksummed payload, and
        the radix index structure + tier payloads + queued/parked request
        metadata + the sampling-stream configuration go to disk through
        `checkpoint.ckpt.save_snapshot`. A fresh engine constructed with
        the same model/codec/seed/temperature restores all of it with
        `restore()` and resumes parked sessions bit-identically — the
        `fold_in(rid, global_output_index)` key stream extends across
        process death because rids, banked token counts, and the base seed
        all survive. The live engine stays usable afterwards (its cached
        pages are now tier-resident; the next hit restores them).

        Returns {"nodes": ..., "requests": ...} counts."""
        self._require_tiered("snapshot")
        from repro.checkpoint.ckpt import save_snapshot

        sched = self.scheduler
        for slot in range(sched.max_slots):
            if sched.slots[slot] is not None:
                sched._park(slot)
        self.kv.spill_all()
        # DFS parent-first: a child's record index is always greater than
        # its parent's, so restore can rebuild top-down in one pass
        prefix, tier = self.kv.prefix, self.tier
        payloads = tier.state()
        arrays: Dict[str, np.ndarray] = {}
        node_meta = []
        order: Dict[int, int] = {id(prefix._root): -1}
        stack = [prefix._root]
        while stack:
            n = stack.pop()
            for c in n.children.values():
                i = len(node_meta)
                order[id(c)] = i
                p = payloads[c.key]
                arrays[f"node/{i}/chunk"] = np.frombuffer(c.chunk, np.uint8)
                arrays[f"node/{i}/blob"] = np.frombuffer(p.blob, np.uint8)
                node_meta.append({
                    "parent": order[id(n)],
                    "tick": c.tick,
                    "codec": p.codec,
                    "wire_id": p.wire_id,
                    "planes": [
                        [path, list(shape), dt] for path, shape, dt in p.planes
                    ],
                    "nbytes": p.nbytes,
                    "crc": p.crc,
                })
                stack.append(c)
        req_meta = []
        for j, r in enumerate(sched.queue):
            arrays[f"req/{j}/prompt"] = np.asarray(r.prompt, np.int32)
            arrays[f"req/{j}/done"] = np.asarray(r.done_tokens, np.int32)
            req_meta.append({
                "rid": r.rid,
                "max_new_tokens": r.max_new_tokens,
                "eos_id": r.eos_id,
                "priority": r.priority,
                "parks": r.parks,
                "was_parked": r.was_parked,
            })
        # finished-but-undrained results survive the crash too: a session
        # whose tokens were computed but never fetched is still a session
        statuses = {}
        for rid, toks in sched.results.items():
            arrays[f"res/{rid}"] = np.asarray(toks, np.int32)
            statuses[str(rid)] = sched.statuses[rid].value
        meta = {
            "version": 1,
            "kv_quant": self.kv_quant or "none",
            "block_size": self.block_size,
            "seed": self._seed,
            "temperature": self.temperature,
            "max_len": self.max_len,
            "next_rid": sched._next_rid,
            "tick": prefix._tick,
            "nodes": node_meta,
            "requests": req_meta,
            "results": statuses,
        }
        save_snapshot(directory, arrays, meta)
        return {"nodes": len(node_meta), "requests": len(req_meta)}

    def restore(self, directory: str) -> Dict[str, int]:
        """Load a `snapshot()` into this freshly constructed engine: the
        radix prefix index is rebuilt with every node *tiered* (zero HBM
        cost — tenants are warm immediately, pages restore lazily on their
        first hit), the tier refills with the saved payloads (corruption
        included verbatim: a damaged payload degrades to recompute at
        admission, exactly as it would have pre-crash), and parked/queued
        sessions re-enter the queue under their original rids so their
        sampling-key streams continue where they stopped. Raises
        ValueError when the engine's codec/block size/seed/temperature
        disagree with the snapshot — resumed outputs could not be
        bit-identical."""
        self._require_tiered("restore")
        from repro.checkpoint.ckpt import load_snapshot
        from repro.serve.host_tier import TierPayload, chain_key
        from repro.serve.paged_cache import _RadixNode
        from repro.serve.scheduler import Request

        arrays, meta = load_snapshot(directory)
        if meta.get("version") != 1:
            raise ValueError(
                f"unsupported snapshot version {meta.get('version')!r}"
            )
        for field, mine in (
            ("kv_quant", self.kv_quant or "none"),
            ("block_size", self.block_size),
            ("seed", self._seed),
            ("temperature", self.temperature),
            ("max_len", self.max_len),
        ):
            if meta[field] != mine:
                raise ValueError(
                    f"snapshot {field} mismatch: saved {meta[field]!r}, "
                    f"this engine has {mine!r} — resumed outputs would "
                    "not be bit-identical"
                )
        prefix, tier, sched = self.kv.prefix, self.tier, self.scheduler
        if (
            prefix.pages or prefix.tiered_count or sched.queue
            or any(r is not None for r in sched.slots)
        ):
            raise RuntimeError(
                "restore requires a fresh engine (empty prefix index, "
                "tier, and queue)"
            )
        if (
            tier.capacity_pages is not None
            and tier.capacity_pages < len(meta["nodes"])
        ):
            raise ValueError(
                f"tier capacity ({tier.capacity_pages} pages) is smaller "
                f"than the snapshot ({len(meta['nodes'])} pages)"
            )
        built: list = []
        for i, nm in enumerate(meta["nodes"]):
            parent = prefix._root if nm["parent"] < 0 else built[nm["parent"]]
            chunk = arrays[f"node/{i}/chunk"].tobytes()
            node = _RadixNode(
                chunk, None, parent, nm["tick"],
                key=chain_key(parent.key, chunk),
            )
            parent.children[chunk] = node
            prefix._tiered += 1
            built.append(node)
            tier.put(node.key, TierPayload(
                codec=nm["codec"],
                wire_id=nm["wire_id"],
                planes=tuple(
                    (path, tuple(shape), dt) for path, shape, dt in nm["planes"]
                ),
                nbytes=nm["nbytes"],
                crc=nm["crc"],
                blob=arrays[f"node/{i}/blob"].tobytes(),
            ))
        prefix._tick = meta["tick"]
        now = sched._clock()
        for j, rm in enumerate(meta["requests"]):
            r = Request(
                rm["rid"],
                np.asarray(arrays[f"req/{j}/prompt"], np.int32),
                rm["max_new_tokens"],
                rm["eos_id"],
                priority=rm["priority"],
                submit_t=now,
            )
            r.done_tokens = [int(t) for t in arrays[f"req/{j}/done"]]
            r.parks = rm["parks"]
            r.was_parked = rm["was_parked"]
            sched.queue.append(r)
        from repro.serve.slo import RequestStatus

        for rid_s, status in meta.get("results", {}).items():
            rid = int(rid_s)
            sched.results[rid] = np.asarray(arrays[f"res/{rid}"], np.int32)
            sched.statuses[rid] = RequestStatus(status)
        sched._next_rid = max(sched._next_rid, meta["next_rid"])
        return {"nodes": len(meta["nodes"]), "requests": len(meta["requests"])}

    # ------------------------------------------------------------------
    # batch API (thin wrapper over the scheduler when paged)
    # ------------------------------------------------------------------
    def generate(self, prompts: np.ndarray, n_steps: int) -> np.ndarray:
        """prompts (B, S) int32 -> generated tokens (B, n_steps)."""
        if self.paged:
            rids = [
                self.submit(np.asarray(p, np.int32), max_new_tokens=n_steps)
                for p in prompts
            ]
            done = self.run_until_drained()
            return np.stack([done[r] for r in rids], axis=0)
        return self._generate_dense(prompts, n_steps)

    def _generate_dense(self, prompts: np.ndarray, n_steps: int) -> np.ndarray:
        b, s = prompts.shape
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if self.cfg.mrope_sections:
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            batch["positions"] = jnp.broadcast_to(pos, (3, b, s))
        rows = np.arange(b)
        with self._mesh_scope():
            logits, cache = self._prefill(self.params, batch)
            out = []
            tok = self._sample_rows(logits, rows, np.zeros(b))[:, None]
            for i in range(n_steps):
                out.append(tok[:, 0])
                pos = jnp.full((b, 1), s + i, jnp.int32)
                if self.cfg.mrope_sections:
                    pos = jnp.full((3, b, 1), s + i, jnp.int32)
                logits, cache = self._decode(
                    self.params, jnp.asarray(tok, jnp.int32), pos, cache
                )
                tok = self._sample_rows(logits, rows, np.full(b, i + 1))[:, None]
        return np.stack(out, axis=1)
