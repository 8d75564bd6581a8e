"""Continuous-batching scheduler over the block-paged KV cache.

Host-side control loop (DESIGN.md §10/§12): a FIFO request queue feeds a
fixed set of `max_slots` decode slots. Each scheduling round the host
admits queued requests into free slots whenever the pool has enough
unreserved pages for the request's worst case, prefills **all requests
admitted in the round in one bucketed-shape call** (batch rounded to a
power of two, prompt span to the round's max page count), and then runs
**up to `chunk` decode steps inside one jitted `lax.scan`** — sampled
tokens feed back on device, per-slot done flags (EOS / length cap) are
computed on device, and block-table / write-slot advancement is
precomputed for the whole chunk. The host only touches admission and
eviction between chunks: one device→host synchronization per `chunk`
tokens instead of one per token (the TEPL analogy, DESIGN.md §12).

The decode step itself stays fixed-shape over all `max_slots` slots:
inactive slots feed token 0 at position 0, write to the null page, and
their logits are ignored — the standard continuous-batching-on-XLA
compromise, now without per-request max_len padding.

Sampling is per-request: `sample_fn(logits, rids, steps)` keys on
(request id, token index) only, so admission order and batch composition
can never change a request's sampled tokens. Inactive / padding rows carry
rid -1 (an unreachable uint32 sentinel), so their junk draws can never
collide with a real request's key stream.

With a prefix-cached pool (DESIGN.md §15) admission first pins the longest
cached prefix of the prompt, so prefill starts at `Request.prefilled`
instead of 0; with `prefill_chunk` set, the remaining prompt tail runs as
fixed-size chunks interleaved with decode rounds — each chunk reads
through a length-bounded block table (the PR 5 idea applied to prefill),
and a slot joins decode only once its final chunk has sampled the first
output token.

Overload resilience (DESIGN.md §17): an `SLAPolicy` bounds the queue,
sheds candidates that can no longer meet the TTFT SLO, defers admission
when the calibrated roofline predicts an ITL breach, and escalates the
graceful-degradation ladder — down to parking the lowest-priority
resident via `PagedKVCache.park` — when the pool blocks the queue head.
Per-request deadlines drop expired queued work at admission time. Every
request ends in exactly one `RequestStatus` (`Scheduler.statuses`); a
`FaultInjector` hooks the round loop for chaos testing (pool exhaustion,
straggler rounds, poisoned prefills, corrupted host-tier payloads), and
the non-finite-logit guard at the prefill host sync fails only the
poisoned request.

Tiered KV durability (DESIGN.md §18): with a `HostTier` installed on the
cache, the degradation ladder gains a `spill` rung (flush reclaimable
index pages to host memory instead of dropping them), admission restores
tier-resident prefix hits through checksum-verified uploads drained just
before the prefill launch (`tier_restore_fn`), and `check_invariants`
audits the tiered pages as a fourth conservation class.

Spans (DESIGN.md §14): `serve.step` covers a round; inside it
`serve.admit`, `serve.prefill` (`.pack`, `.launch`, `.sync`) and
`serve.decode` (`.pack`, `.launch`, `.sync`, `.replay`) name the host's
phases on the profiler's clock, and `serve.prefill` / `serve.decode` carry
the work their launch gives the device, counted from the host arrays.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.kernels import ops as kernel_ops
from repro.models.layers import CACHE_EMPTY_POS
from repro.obs.trace import Span
from repro.serve.paged_cache import PagedKVCache
from repro.serve.slo import LADDER, RequestStatus, SLAPolicy


def _pow2ceil(x: int) -> int:
    return 1 << max(0, x - 1).bit_length()


#: Units for every key `Scheduler.stats()` can return (DESIGN.md §14's
#: naming rule: a number is meaningless without its unit). Raw counters
#: first, derived ratios/bytes after. tests/test_obs.py asserts the
#: returned keys and this table never drift apart.
STAT_UNITS: Dict[str, str] = {
    "decode_steps": "steps (batch decode iterations actually counted)",
    "decode_chunks": "calls (device-resident chunk launches, 1 per round)",
    "host_syncs": "calls (device->host synchronizations: one per prefill "
                  "call and one per decode round)",
    "active_slot_steps": "slot*steps (decoded tokens across all requests)",
    "paged_block_steps": "pages*steps (pool pages held, summed per step)",
    "dense_block_steps": "pages*steps (what a max_len ring cache would hold)",
    "peak_blocks": "pages (max pool pages held at any step)",
    "prefill_calls": "calls (bucketed prefill launches, incl. chunked)",
    "prefill_chunk_calls": "calls (length-bounded chunked-prefill launches)",
    "prefill_token_steps": "tokens (padded token-steps launched in prefill)",
    "prefill_real_tokens": "tokens (real prompt tokens prefilled)",
    "prefix_hit_tokens": "tokens (prompt tokens served from the prefix "
                         "cache instead of recomputed)",
    "cow_copies": "pages (copy-on-write clones of prefix-shared pages)",
    "shared_pages": "pages (pages currently held by >1 holder)",
    "prefix_cached_pages": "pages (pages the prefix index currently pins)",
    "kv_pages_read": "pages (decode-attention pages actually walked)",
    "kv_pages_read_worst": "pages (max_blocks gather worst case)",
    "window_freed_pages": "pages (released behind the attention window)",
    "mean_occupancy": "ratio (active slot-steps / max_slots*steps)",
    "mean_blocks": "pages (mean pool pages held per decode step)",
    "padding_waste_saved": "ratio (ring-cache block-steps never allocated)",
    "prefill_padding_waste": "ratio (padded prefill token-steps wasted)",
    "kv_bytes_per_token": "bytes (pool footprint per token slot, all layers)",
    "kv_read_bytes_per_token": "bytes (KV actually streamed per decoded token)",
    "kv_read_bytes_per_token_worst": "bytes (max_blocks gather per token)",
    "draft_tokens": "tokens (draft proposals computed on the speculative path)",
    "verify_calls": "calls (per-slot verify passes on the speculative path)",
    "shed_requests": "requests (rejected by the SLA policy: bounded queue "
                     "at submit or predicted TTFT breach at admission)",
    "expired_requests": "requests (deadline passed while queued, dropped "
                        "at admission time)",
    "preempted_requests": "requests (parked under pool pressure and "
                          "expired before resume; partial output kept)",
    "parked_requests": "events (residents preempted via PagedKVCache.park; "
                       "a request can park more than once)",
    "resumed_requests": "events (parked requests re-admitted through the "
                        "prefix cache)",
    "failed_requests": "requests (non-finite logits at the host sync; "
                       "pages reclaimed, co-batched survivors unaffected)",
    "degradations": "events (graceful-degradation ladder escalations)",
    "itl_deferrals": "events (admissions deferred by the predicted-ITL gate)",
    "accepted_tokens_per_step": "tokens/call (tokens emitted per verify pass; "
                                ">1 is the speculative-decode win)",
    "tier_spilled_pages": "pages (pages spilled into the host tier, lifetime)",
    "tier_restored_pages": "pages (verified tier payloads uploaded back "
                           "into HBM pages, lifetime)",
    "tier_pages": "pages (payloads resident in the host tier right now)",
    "tier_bytes": "bytes (packed payload bytes resident in the host tier)",
    "tier_corrupt": "pages (payloads that failed checksum verification)",
    "tier_fallback_recompute": "events (admissions that recomputed a prefix "
                               "because a tier payload was corrupt/missing)",
    "tier_hit_tokens": "tokens (prompt tokens served from tier-restored "
                       "pages instead of recomputed)",
}


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (P,) int32
    max_new_tokens: int           # total output budget, park/resume invariant
    eos_id: Optional[int] = None
    out: List[int] = dataclasses.field(default_factory=list)
    peak_blocks: int = 0
    # prompt tokens whose KV is already in the pool: the prefix-cache hit
    # at admission, then each prefill chunk advances it; prefill is done
    # (and the slot decode-ready, signalled by a non-empty `out`) once it
    # reaches len(prompt)
    prefilled: int = 0
    # resilience state (DESIGN.md §17)
    priority: int = 0             # park-victim ordering; queue stays FIFO
    deadline_t: Optional[float] = None  # absolute clock seconds, or None
    submit_t: float = 0.0         # clock stamp for the TTFT admission gate
    # tokens emitted before a park: park folds `out` into `prompt` (the
    # resume re-prefills them) and banks them here — results and the
    # sampling-key stream stay indexed by *global* output position, so a
    # resumed request's tokens are bit-identical to an uninterrupted run
    done_tokens: List[int] = dataclasses.field(default_factory=list)
    parks: int = 0                # times this request has been parked
    was_parked: bool = False      # pending-resume marker (resume counter)

    @property
    def next_pos(self) -> int:
        return len(self.prompt) + len(self.out)

    @property
    def emitted(self) -> int:
        """Tokens emitted over the request's whole life — the sampling-key
        step index and the length-cap meter, both park/resume invariant."""
        return len(self.done_tokens) + len(self.out)

    @property
    def all_out(self) -> List[int]:
        return self.done_tokens + self.out


class Scheduler:
    """Request queue + admission/eviction around jitted prefill/decode fns.

    prefill_fn(tokens (B,Sp), positions (B,Sp), block_tables (B,TW),
               write_slots (B,Sp), write_pos (B,Sp), fresh (F,),
               copies (B,2), last_idx (B,)) -> last-token logits (B, V)
               on device; TW is max_blocks for monolithic prefill and a
               length-bounded pow2 page count for chunked prefill
    decode_fn(tokens (M,1), positions (M,1), block_tables (M,MB),
              write_slots (M,1), write_pos (M,1), fresh (M,),
              kv_lens (M,)) -> logits (M, V)
    decode_chunk_fn(tokens0 (M,1), tables (M,MB), positions (C,M,1),
                    write_slots (C,M,1), write_pos (C,M,1), fresh (C,F),
                    kv_lens (C,M), rids (M,), start_steps (M,),
                    max_steps (M,), eos (M,), active (M,)) -> tokens (C, M),
                    on device or host (the scheduler's sync reads them)
    sample_fn(logits (N,V) on device, rids (N,), steps (N,)) -> np tokens (N,)

    `kv_lens` is the per-slot length vector of DESIGN.md §13 — the block
    allocator's view of how many KV tokens each slot actually holds — and
    bounds the fused paged-attention page walk to each slot's used pages
    instead of max_blocks.

    With `chunk` > 1 and a `decode_chunk_fn`, decode runs device-resident:
    logits, sampling, and EOS/length-cap checks never leave the device
    inside a chunk — only the (C, M) sampled token ids cross to host, once
    per chunk.

    `local_window` (set by the engine when *every* attention layer is
    local) enables window-aware page freeing: after each scheduling round,
    pages that have slid entirely behind every live and future query's
    attention window go back to the free list; their table entries become
    null-page reads, which the position sentinel masks to zero weight.

    `prefill_chunk` switches prefill to chunked mode: each scheduling
    round advances every mid-prefill slot by at most that many prompt
    tokens in one length-bounded launch, then runs a normal decode round
    for the slots that already sampled their first token — a long prompt
    admits immediately and interleaves with decode instead of stalling it.
    `scrub_fn(pages)` is the engine's out-of-step fresh-page scrub, used
    when one round recycles more pages than the launch's fixed
    fresh-vector width (satellite of the same fix: `drain_fresh` used to
    hard-fail mid-admission with pages already allocated).

    `spec_fn` (DESIGN.md §16) replaces the decode chunk with speculative
    rounds: spec_fn(tokens0 (M,1), tables (M,TW), p0 (M,), fresh (F,),
    rids, start_steps, max_steps, eos, active) -> (out (cap, M) packed
    emissions, e_rounds (rounds, M)), on device or host as above;
    `spec_k`/`spec_rounds`/`spec_window` mirror the engine's SpecConfig
    for accounting. `prefill_sla_s` plus an
    installed RoofLens switches the chunked-prefill span from the fixed
    `prefill_chunk` to the largest predicted-to-fit ladder step (see
    `_prefill_span_cap`).

    `sla` installs an `SLAPolicy` (DESIGN.md §17): bounded queue, TTFT
    shedding and predicted-ITL admission deferral, and the graceful-
    degradation ladder under pool pressure. `injector` hooks a
    `dist.fault.FaultInjector` into the round loop (plan steps index
    scheduler rounds); `watchdog` feeds a `StragglerWatchdog` each round's
    wall time. The non-finite-logit guard at the prefill host sync is
    armed whenever `sla` or `injector` is set; with neither, every hot
    path is exactly the pre-PR9 one. Terminal statuses land in
    `self.statuses` (rid -> RequestStatus) next to `self.results`.
    """

    def __init__(
        self,
        cache: PagedKVCache,
        *,
        max_slots: int,
        max_len: int,
        prefill_fn: Callable,
        decode_fn: Callable,
        sample_fn: Callable,
        decode_chunk_fn: Optional[Callable] = None,
        chunk: int = 1,
        prefill_batch: bool = True,
        local_window: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        scrub_fn: Optional[Callable] = None,
        obs=None,
        spec_fn: Optional[Callable] = None,
        spec_k: int = 0,
        spec_rounds: int = 0,
        spec_window: int = 0,
        prefill_sla_s: Optional[float] = None,
        sla: Optional[SLAPolicy] = None,
        injector=None,
        watchdog=None,
        tier_restore_fn: Optional[Callable] = None,
    ):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        if chunk > 1 and decode_chunk_fn is None:
            raise ValueError("chunk > 1 requires a decode_chunk_fn")
        if local_window is not None and local_window < 1:
            raise ValueError(f"local_window must be >= 1, got {local_window}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if spec_fn is not None and (spec_k < 1 or spec_rounds < 1):
            raise ValueError(
                f"spec_fn requires spec_k >= 1 and spec_rounds >= 1, got "
                f"k={spec_k}, rounds={spec_rounds}"
            )
        self.cache = cache
        self.max_slots = max_slots
        self.max_len = max_len
        self.max_blocks = math.ceil(max_len / cache.block_size)
        self._prefill = prefill_fn
        self._decode = decode_fn
        self._decode_chunk = decode_chunk_fn
        self._sample = sample_fn
        self.chunk = chunk
        self.prefill_batch = prefill_batch
        self.local_window = local_window
        self.prefill_chunk = prefill_chunk
        self._scrub = scrub_fn
        # host-tier restore (DESIGN.md §18): uploads verified payloads into
        # reserved HBM pages before the prefill launch that reads them
        self._tier_restore = tier_restore_fn
        self._spec = spec_fn
        self.spec_k = spec_k
        self.spec_rounds = spec_rounds
        self.spec_window = spec_window
        self.prefill_sla_s = prefill_sla_s
        self.queue: collections.deque = collections.deque()
        self.slots: List[Optional[Request]] = [None] * max_slots
        self.results: Dict[int, np.ndarray] = {}
        self.statuses: Dict[int, RequestStatus] = {}  # rid -> terminal status
        self.request_peaks: Dict[int, int] = {}  # rid -> peak pages held
        self._next_rid = 0
        # resilience state (DESIGN.md §17)
        self.sla = sla
        self._injector = injector
        self._watchdog = watchdog
        self._round = 0  # scheduler rounds; the injector plan's step index
        self.degradation_level = 0  # rungs of slo.LADDER currently applied
        self._spec_enabled = True
        self._span_shrunk = False
        self._poison_pending = False
        # the non-finite guard adds one tiny per-row reduction to the
        # prefill launch, so it arms only when resilience is in play
        self._guard_nonfinite = sla is not None or injector is not None
        # occupancy / padding-waste accounting (benchmarks/run.py serving_paged)
        self._stats = {
            "decode_steps": 0, "decode_chunks": 0, "host_syncs": 0,
            "active_slot_steps": 0,
            "paged_block_steps": 0, "dense_block_steps": 0, "peak_blocks": 0,
            "prefill_calls": 0, "prefill_chunk_calls": 0,
            "prefill_token_steps": 0, "prefill_real_tokens": 0,
            "kv_pages_read": 0, "kv_pages_read_worst": 0, "window_freed_pages": 0,
            "draft_tokens": 0, "verify_calls": 0,
            "shed_requests": 0, "expired_requests": 0, "preempted_requests": 0,
            "parked_requests": 0, "resumed_requests": 0, "failed_requests": 0,
            "degradations": 0, "itl_deferrals": 0,
        }
        # observability (DESIGN.md §14): every site below is guarded on the
        # specific collector it feeds — with obs=None the serving loop does
        # no clock reads, no allocation, and (always) no device work
        self._obs_metrics = obs.metrics if obs is not None else None
        self._obs_tracer = obs.tracer if obs is not None else None
        self._obs_rooflens = obs.rooflens if obs is not None else None
        obs_clock = obs.clock if obs is not None else None
        # deadlines / TTFT gating need a clock even without observability;
        # share the obs clock when installed so trace timestamps line up
        self._clock = obs_clock or time.monotonic
        # spans read the clock only for the collectors that time them
        timed = self._obs_tracer is not None or self._obs_rooflens is not None
        self._span_clock = obs_clock if timed else None

    # ------------------------------------------------------------------
    # request API
    # ------------------------------------------------------------------
    def submit(
        self,
        prompt: np.ndarray,
        *,
        max_new_tokens: int,
        eos_id: Optional[int] = None,
        deadline_s: Optional[float] = None,
        priority: int = 0,
    ) -> int:
        """Enqueue one request. `deadline_s` is a relative wall-clock
        budget: a request still queued (or parked) when it runs out is
        dropped at admission time with status EXPIRED / PREEMPTED instead
        of occupying the queue forever. `priority` orders park-victim
        selection under pool pressure (lower parks first); the queue itself
        stays FIFO. A submit past the SLA policy's `max_queue` is SHED
        immediately — the rid still comes back, with an empty result and a
        terminal status, never an exception."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) < 1:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if deadline_s is not None and deadline_s < 0:
            raise ValueError(f"deadline_s must be >= 0, got {deadline_s}")
        # KV footprint: prompt + every fed-back token except the last sample
        kv_len = len(prompt) + max_new_tokens - 1
        if kv_len > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_len={self.max_len}"
            )
        if self.cache.blocks_for(kv_len) > self.cache.num_blocks:
            # would never admit, even against an empty pool — reject here
            # rather than spinning forever in run_until_drained
            raise ValueError(
                f"request needs {self.cache.blocks_for(kv_len)} pages but the "
                f"pool only has {self.cache.num_blocks}"
            )
        rid = self._next_rid
        self._next_rid += 1
        now = self._clock()
        r = Request(
            rid, prompt, max_new_tokens, eos_id, priority=priority,
            deadline_t=None if deadline_s is None else now + deadline_s,
            submit_t=now,
        )
        if self._obs_tracer is not None:
            self._obs_tracer.on_submit(rid, len(prompt), max_new_tokens)
        if self._obs_metrics is not None:
            self._obs_metrics.counter(
                "serve.requests.submitted", unit="requests"
            ).inc()
        if self.sla is not None and self.sla.queue_full(len(self.queue)):
            self._terminate(r, RequestStatus.SHED)
            return rid
        self.queue.append(r)
        if self._obs_metrics is not None:
            self._obs_metrics.gauge(
                "serve.queue_depth", unit="requests"
            ).set(len(self.queue))
        return rid

    def run_until_drained(self) -> Dict[int, np.ndarray]:
        while self.queue or any(r is not None for r in self.slots):
            self.step()
        out, self.results = self.results, {}
        return out

    # ------------------------------------------------------------------
    # one scheduling round: admission -> batched prefill -> chunked decode
    # ------------------------------------------------------------------
    def step(self) -> None:
        with Span("serve.step"):
            self._step()

    def _step(self) -> None:
        t0 = time.monotonic() if self._watchdog is not None else 0.0
        chaos_pages: List[int] = []
        if self._injector is not None:
            inj = self._injector
            if inj.take(self._round, "slow"):
                # straggler round: the sleep sits inside the watchdog's
                # timed window, so the round must be flagged
                time.sleep(inj.slow_s)
            if inj.take(self._round, "poison_prefill"):
                # the next prefill launch NaNs one real row's logits; the
                # host-sync guard must fail exactly that request
                self._poison_pending = True
            if inj.take(self._round, "corrupt_tier_page"):
                # flip bytes in one stored host-tier payload: the next
                # restore of that prefix must detect the damage and fall
                # back to recompute — only the affected request pays
                if self.cache.tier is not None:
                    self.cache.tier.corrupt_one()
            if inj.take(self._round, "exhaust_pool"):
                # transient pool exhaustion for this round: grab only the
                # *unreserved* headroom — residents' reservations stay
                # backed (their lazy allocations must not start failing),
                # but admission sees zero admittable pages
                n = self.cache.free_blocks - self.cache.reserved_blocks
                chaos_pages = [
                    self.cache.allocator.alloc() for _ in range(max(0, n))
                ]
        try:
            self._admit()
            if self.prefill_chunk is not None:
                self._prefill_pending()
            self._decode_active()
        finally:
            if chaos_pages:
                # never written: no scrub needed now; a later tenant scrubs
                # them through the normal fresh-page path
                self.cache.allocator.free(chaos_pages)
            if self._watchdog is not None:
                self._watchdog.observe(self._round, time.monotonic() - t0)
            self._round += 1

    def _kv_len(self, r: Request) -> int:
        # park/resume: `prompt` absorbs emitted tokens, so subtract them
        # from the output budget — the total stays len(P0) + max_new - 1
        return len(r.prompt) + (r.max_new_tokens - len(r.done_tokens)) - 1

    def _admit(self) -> None:
        with Span("serve.admit", self._span_clock) as span:
            self._expire_queued()
            admitted: List[tuple] = []
            blocked = False  # pool pressure (not SLO deferral) stalled the head
            for slot in range(self.max_slots):
                if self.slots[slot] is not None:
                    continue
                r = self._next_candidate()
                if r is None:
                    break
                if self._itl_defer(r):
                    break
                if not self.cache.can_admit(self._kv_len(r), r.prompt):
                    blocked = True
                    break  # FIFO: don't let short requests starve the head
                self.queue.popleft()
                r.prefilled = self.cache.admit(
                    r.rid, self._kv_len(r), prompt=r.prompt
                )
                if r.was_parked:
                    r.was_parked = False
                    self._stats["resumed_requests"] += 1
                    if self._obs_metrics is not None:
                        self._obs_metrics.counter(
                            "serve.requests.resumed", unit="events"
                        ).inc()
                self.slots[slot] = r
                admitted.append((slot, r))
            if self.sla is not None:
                if blocked and self.queue:
                    self._degrade()
                elif not self.queue and self.degradation_level:
                    # backlog drained: restore full capability (DESIGN.md §17)
                    self._relax()
        if self._obs_tracer is not None and admitted:
            for slot, r in admitted:
                self._obs_tracer.on_admit(r.rid, slot)
            self._obs_tracer.on_admit_round(
                span.t0, span.t1, len(admitted), len(self.queue)
            )
        if self._obs_metrics is not None and admitted:
            self._obs_metrics.counter(
                "serve.requests.admitted", unit="requests"
            ).inc(len(admitted))
            self._publish_gauges()
        if admitted and self.prefill_chunk is None:
            # monolithic prefill: the whole (non-cached) prompt tail in one
            # launch; chunked mode defers to _prefill_pending instead
            rows = [
                (slot, r, r.prefilled, len(r.prompt) - r.prefilled)
                for slot, r in admitted
            ]
            if self.prefill_batch:
                self._prefill_rows(rows)
            else:
                # legacy pre-PR4 behavior (kept as the benchmark baseline):
                # one jit call per admitted request, exact page rounding
                for one in rows:
                    self._prefill_rows([one], bucketed=False)
            for slot, r in admitted:
                if self._finished(r):
                    self._evict(slot)
            self._free_window_pages()  # long prompts may already out-span it

    # ------------------------------------------------------------------
    # overload resilience (DESIGN.md §17): deadlines, SLO gates, the
    # degradation ladder, park/resume, and the page-conservation audit
    # ------------------------------------------------------------------
    def _terminate(self, r: Request, status: RequestStatus) -> None:
        """Terminal bookkeeping for a request that ends off-slot (shed,
        expired, preempted-for-good, failed): its tokens so far become the
        result, exactly one status is recorded, and the lifecycle
        collectors see a finish with the status as the reason."""
        self.results[r.rid] = np.asarray(r.all_out, np.int32)
        self.statuses[r.rid] = status
        self.request_peaks[r.rid] = r.peak_blocks
        self._stats[f"{status.value}_requests"] += 1
        if self._obs_tracer is not None:
            self._obs_tracer.on_finish(r.rid, status.value)
        if self._obs_metrics is not None:
            self._obs_metrics.counter(
                f"serve.requests.{status.value}", unit="requests"
            ).inc()

    def _expire_queued(self) -> None:
        """Drop queued requests whose deadline has passed — at admission
        time, before they can consume pool pages. A never-admitted request
        expires empty (EXPIRED); a parked one keeps the tokens it emitted
        before preemption (PREEMPTED)."""
        if not any(r.deadline_t is not None for r in self.queue):
            return
        now = self._clock()
        alive: collections.deque = collections.deque()
        dropped = 0
        for r in self.queue:
            if r.deadline_t is not None and now >= r.deadline_t:
                self._terminate(
                    r,
                    RequestStatus.PREEMPTED if r.parks
                    else RequestStatus.EXPIRED,
                )
                dropped += 1
            else:
                alive.append(r)
        if dropped:
            self.queue = alive
            if self._obs_metrics is not None:
                self._obs_metrics.gauge(
                    "serve.queue_depth", unit="requests"
                ).set(len(self.queue))

    def _next_candidate(self) -> Optional[Request]:
        """The queue head, after shedding heads that can no longer meet
        the TTFT SLO: time already waited plus the predicted prefill wall
        time (when a bound RoofLens is installed) past `ttft_slo_s` means
        admitting would only burn pages on a guaranteed miss — the
        admitted population then meets the SLO by construction. Resumed
        requests already delivered their first token and are exempt."""
        shed_gate = self.sla is not None and self.sla.ttft_slo_s is not None
        while self.queue:
            r = self.queue[0]
            if not shed_gate or r.done_tokens:
                return r
            pred = 0.0
            lens = self._obs_rooflens
            if lens is not None and getattr(lens, "_bound", False):
                bs = self.cache.block_size
                span = math.ceil(max(1, len(r.prompt)) / bs) * bs
                pred = lens.predict_prefill(1, span)
                # a tier-resident prefix hit trades prefill compute for
                # restore traffic (DESIGN.md §18): price the host->HBM
                # upload so the gate accounts for the restore time too
                n_tiered = self.cache.tiered_hit_pages(r.prompt)
                if n_tiered:
                    pred += lens.predict_tier_restore(
                        n_tiered, self.cache.bytes_per_token() * bs
                    )
            if not self.sla.ttft_breached(self._clock() - r.submit_t, pred):
                return r
            self.queue.popleft()
            self._terminate(r, RequestStatus.SHED)
            if self._obs_metrics is not None:
                self._obs_metrics.gauge(
                    "serve.queue_depth", unit="requests"
                ).set(len(self.queue))
        return None

    def _itl_defer(self, cand: Request) -> bool:
        """Roofline-driven admission gate: defer the candidate while the
        predicted per-token time of one decode chunk over (residents +
        candidate) breaches the ITL SLO — the marginal-contention question
        the calibrated 3D roofline can answer *before* the batch slows
        down. Inert without a bound RoofLens (the `prefill_sla_s`
        template), and never defers onto an idle batch: a lone request
        must always make progress."""
        if self.sla is None or self.sla.itl_slo_s is None:
            return False
        lens = self._obs_rooflens
        if lens is None or not getattr(lens, "_bound", False):
            return False
        resident = [
            float(r.next_pos) for r in self.slots
            if r is not None and r.out
        ]
        if not resident:
            return False
        steps = max(1, self.chunk)
        pred = lens.predict_decode_chunk(
            resident + [float(len(cand.prompt) + 1)], steps
        )
        if not self.sla.itl_breached(pred, steps):
            return False
        self._stats["itl_deferrals"] += 1
        if self._obs_metrics is not None:
            self._obs_metrics.counter(
                "serve.admission.itl_deferrals", unit="events"
            ).inc()
        return True

    def _degrade(self) -> None:
        """Escalate one applicable rung of the degradation ladder (slo.
        LADDER, strictly in order) in a round where the pool blocked the
        queue head. Rungs the engine build lacks (no prefix index, no spec
        decode, monolithic prefill) are skipped within the same call; the
        final rung — park the lowest-priority resident — may repeat on
        later blocked rounds, since one eviction may not free enough."""
        head = self.queue[0]
        applied = None
        while self.degradation_level < len(LADDER) and applied is None:
            rung = LADDER[self.degradation_level]
            self.degradation_level += 1
            if rung == "prefix_evict":
                if self.cache.prefix is not None:
                    need = self.cache.blocks_for(self._kv_len(head))
                    # with a host tier installed the reclaim spills (restore
                    # latency later) instead of dropping (recompute later)
                    if self.cache.reclaim_index_pages(need) > 0:
                        applied = rung
            elif rung == "spill":
                # flush every reclaimable index page to the host tier
                # (DESIGN.md §18) — skipped without a tier
                if self.cache.tier is not None and self.cache.spill_all() > 0:
                    applied = rung
            elif rung == "spec_off":
                if self._spec is not None and self._spec_enabled:
                    applied = rung
                self._spec_enabled = False
            elif rung == "prefill_shrink":
                if self.prefill_chunk is not None and not self._span_shrunk:
                    applied = rung
                self._span_shrunk = True
            elif self._park_lowest(head):
                applied = rung
        if applied is None and self.degradation_level >= len(LADDER):
            if self._park_lowest(head):
                applied = LADDER[-1]
        if applied is not None:
            self._stats["degradations"] += 1
            if self._obs_metrics is not None:
                self._obs_metrics.counter(
                    "serve.degradations", unit="events"
                ).inc()
                self._obs_metrics.gauge(
                    "serve.degradation_level", unit="rungs"
                ).set(self.degradation_level)

    def _relax(self) -> None:
        """De-escalate the whole ladder once the queue drains: speculative
        rounds and the full prefill span come back (parked requests have
        already re-queued themselves; index pages are simply gone)."""
        self.degradation_level = 0
        self._spec_enabled = True
        self._span_shrunk = False
        if self._obs_metrics is not None:
            self._obs_metrics.gauge(
                "serve.degradation_level", unit="rungs"
            ).set(0)

    def _park_lowest(self, cand: Request) -> bool:
        """Park the lowest-priority resident strictly below the blocked
        head's priority (ties: youngest first — the oldest keeps its
        progress). False when no resident may be preempted for this head."""
        victims = [
            (i, r) for i, r in enumerate(self.slots)
            if r is not None and r.priority < cand.priority
        ]
        if not victims:
            return False
        slot, _ = min(victims, key=lambda t: (t[1].priority, -t[1].rid))
        self._park(slot)
        return True

    def _park(self, slot: int) -> None:
        """Preempt one resident: index its written history into the prefix
        cache (when installed), release its pages and reservation through
        `PagedKVCache.park`, fold its emitted tokens into the prompt, and
        re-queue it at the tail for a later re-prefill. Sampling keys ride
        the *global* output index (`Request.emitted`), so the resumed
        request's remaining tokens are bit-identical to an uninterrupted
        run — the resume prefill's sample IS its next output token."""
        r = self.slots[slot]
        if r.out:
            # KV in the pool covers positions [0, next_pos - 1): the whole
            # prompt plus every emitted token except the last (whose KV is
            # written by the decode step that feeds it back)
            written = np.concatenate(
                [r.prompt, np.asarray(r.out[:-1], np.int32)]
            )
        else:
            written = r.prompt[:r.prefilled]  # mid-prefill victim
        self.cache.park(r.rid, written)
        if r.out:
            r.done_tokens += r.out
            r.prompt = np.concatenate(
                [r.prompt, np.asarray(r.out, np.int32)]
            )
            r.out = []
        r.prefilled = 0
        r.parks += 1
        r.was_parked = True
        self.slots[slot] = None
        self.queue.append(r)
        self._stats["parked_requests"] += 1
        if self._obs_metrics is not None:
            self._obs_metrics.counter(
                "serve.requests.parked", unit="events"
            ).inc()
            self._publish_gauges()

    def _fail(self, slot: int, r: Request) -> None:
        """Fail exactly one request at the host-sync guard: reclaim its
        pages, clear its slot, record status FAILED. Its poisoned pages
        never enter the prefix index (the guard runs before
        `prefix_insert`), so co-batched survivors stay bit-identical."""
        self.cache.release(r.rid)
        self.slots[slot] = None
        self._terminate(r, RequestStatus.FAILED)
        if self._obs_metrics is not None:
            self._publish_gauges()

    def check_invariants(self) -> Dict[str, int]:
        """Page-conservation audit (DESIGN.md §17): every allocator page is
        either free or held; every held page's refcount equals exactly the
        number of resident block-table references plus prefix-index pins;
        reservations never exceed the free list. Raises RuntimeError on any
        violation (these are the invariants the hypothesis batteries check
        per-op; this is the live-engine spot check the chaos harness and
        the overload benchmark run at drain). Returns the occupancy
        snapshot so callers can assert drain-state on top."""
        alloc = self.cache.allocator
        if alloc.free_count + alloc.used_count != self.cache.num_blocks:
            raise RuntimeError(
                f"page leak: free {alloc.free_count} + used "
                f"{alloc.used_count} != pool {self.cache.num_blocks}"
            )
        holders: Dict[int, int] = {}
        for r in self.slots:
            if r is None:
                continue
            for p in self.cache.held_pages(r.rid):
                holders[p] = holders.get(p, 0) + 1
        if self.cache.prefix is not None:
            for p in self.cache.prefix.page_multiset():
                holders[p] = holders.get(p, 0) + 1
        if alloc.used_count != len(holders):
            raise RuntimeError(
                f"orphaned pages: allocator holds {alloc.used_count} unique "
                f"pages but residents + prefix index account for "
                f"{len(holders)}"
            )
        for p, c in holders.items():
            if alloc.ref_count(p) != c:
                raise RuntimeError(
                    f"refcount drift on page {p}: allocator says "
                    f"{alloc.ref_count(p)}, holders say {c}"
                )
        if self.cache.reserved_blocks > alloc.free_count:
            raise RuntimeError(
                f"reservations ({self.cache.reserved_blocks}) exceed the "
                f"free list ({alloc.free_count})"
            )
        if self.cache.tier is not None:
            # fourth conservation class (DESIGN.md §18): every tiered index
            # node has exactly one tier payload under its content address,
            # and vice versa — a drift either way means a page was lost
            # (unresumable prefix) or leaked (unreachable payload)
            idx_keys = sorted(self.cache.prefix.tier_keys())
            tier_keys = sorted(self.cache.tier.keys())
            if idx_keys != tier_keys:
                raise RuntimeError(
                    f"tiered-page drift: index holds {len(idx_keys)} tiered "
                    f"nodes but the tier stores {len(tier_keys)} payloads"
                )
            if self.cache.prefix.tiered_count != self.cache.tier.pages:
                raise RuntimeError(
                    f"tiered-count drift: index says "
                    f"{self.cache.prefix.tiered_count}, tier says "
                    f"{self.cache.tier.pages}"
                )
        return self.cache.occupancy()

    def _prefill_pending(self) -> None:
        """Chunked prefill (DESIGN.md §15): advance every mid-prefill slot
        by at most `prefill_chunk` prompt tokens in one length-bounded
        launch. A slot whose final chunk completes samples its first output
        token and joins the next decode round; until then the decode loop
        skips it (empty `out`)."""
        pending = [
            (i, r) for i, r in enumerate(self.slots)
            if r is not None and r.prefilled < len(r.prompt)
        ]
        if not pending:
            return
        span_cap = self._prefill_span_cap(pending)
        rows = [
            (i, r, r.prefilled,
             min(span_cap, len(r.prompt) - r.prefilled))
            for i, r in pending
        ]
        self._prefill_rows(rows, bounded=True)
        for i, r in pending:
            if r.out and self._finished(r):
                self._evict(i)
        self._free_window_pages()

    def _prefill_span_cap(self, pending) -> int:
        """Tokens one chunked-prefill launch may process per request. The
        fixed `prefill_chunk` unless an SLA budget *and* a bound RoofLens
        are installed — then the cap is the largest page-aligned pow2
        ladder step whose *predicted* launch time fits `prefill_sla_s`
        (DESIGN.md §14: the calibrated predicted-vs-measured loop put to
        work). A long-context round, whose gather term grows with the
        written prefix, then automatically takes smaller bites than a cold
        one — constant predicted stall on the interleaved decode stream
        instead of constant token count. Never returns less than one page
        (progress must be possible even over budget)."""
        if self._span_shrunk:
            # degradation rung "prefill_shrink" (DESIGN.md §17): one page
            # per chunk — prefill keeps making progress but stops competing
            # with the blocked queue head for pool pages
            return min(self.prefill_chunk, self.cache.block_size)
        if (
            self.prefill_sla_s is None
            or self._obs_rooflens is None
            or not getattr(self._obs_rooflens, "_bound", False)
        ):
            return self.prefill_chunk
        bs = self.cache.block_size
        rows = len(pending)
        best = min(bs, self.prefill_chunk)
        n = bs
        while n <= self.prefill_chunk:
            table = max(
                math.ceil(min(r.prefilled + n, len(r.prompt)) / bs)
                for _, r in pending
            ) * bs
            if self._obs_rooflens.predict_prefill_chunk(
                rows, n, table
            ) > self.prefill_sla_s:
                break
            best = n
            n *= 2
        return best

    def _prefill_rows(
        self, rows: List[tuple], bucketed: bool = True, bounded: bool = False
    ) -> None:
        """One bucketed-shape prefill launch over `rows` of
        (slot, request, start, n): each row writes prompt tokens
        [start, start + n) at their true positions. Monolithic admission
        passes start = the prefix-cache hit and n = the whole remaining
        tail; chunked mode passes fixed-size chunks.

        Batch is padded to a power of two (<= max_slots) and the token
        span to the round's max page-rounded chunk length, so the jit-shape
        count stays O(log(max_slots) * max_blocks) instead of one compile
        per (batch, length) pair. Padding rows write to the null page under
        the empty-position sentinel and sample with rid -1.

        `bounded=True` (chunked prefill) also shrinks the block-table width
        to the pow2-rounded page count the round's furthest row can attend
        to — the gather-read then scales with written prefix, not
        max_blocks (the PR 5 length-bounding, applied to prefill).

        Only rows whose chunk reaches the end of the prompt sample a token
        (the first output); the others' logits rows are discarded. Finished
        prompts are inserted into the prefix index here, while their full
        pages are still position-contiguous."""
        with Span("serve.prefill") as span:
            with Span("serve.prefill.pack"):
                bs = self.cache.block_size
                nrows = len(rows)
                pages = max(math.ceil(n / bs) for _, _, _, n in rows)
                if bucketed:
                    # batch rides power-of-two buckets; the span stays at
                    # the exact page count (<= max_blocks shapes, same as the
                    # per-request path) — padding rows are cheap, padded
                    # columns are not
                    b = min(_pow2ceil(nrows), self.max_slots)
                else:
                    b = nrows
                sp = pages * bs
                if bounded:
                    furthest = max(
                        math.ceil((start + n) / bs) for _, _, start, n in rows
                    )
                    tw = min(_pow2ceil(furthest), self.max_blocks)
                else:
                    tw = self.max_blocks

                tokens = np.zeros((b, sp), np.int32)
                positions = np.broadcast_to(
                    np.arange(sp, dtype=np.int32), (b, sp)
                ).copy()
                write_pos = np.full((b, sp), CACHE_EMPTY_POS, np.int32)
                write_slots = np.broadcast_to(
                    self.cache.null_slots(np.arange(sp)), (b, sp)
                ).copy()
                tables = np.zeros((b, tw), np.int32)
                last_idx = np.zeros(b, np.int32)
                rids = np.full(b, -1, np.int64)
                steps0 = np.zeros(b, np.int64)
                # (row, slot, r) of the rows sampling their first token
                completing: List[tuple] = []
                for row, (slot, r, start, n) in enumerate(rows):
                    tokens[row, :n] = r.prompt[start:start + n]
                    positions[row] = start + positions[row]
                    write_pos[row, :n] = np.arange(
                        start, start + n, dtype=np.int32
                    )
                    write_slots[row, :n] = self.cache.write_slots(
                        r.rid, start, n
                    )
                    tables[row] = self.cache.block_table_row(r.rid, tw)
                    r.prefilled = start + n
                    if r.prefilled >= len(r.prompt):
                        last_idx[row] = n - 1
                        rids[row] = r.rid
                        # a resume prefill's sample is the request's next
                        # *global* output token, so it keys on the banked
                        # count — this is what makes park/resume
                        # bit-identical (DESIGN.md §17)
                        steps0[row] = len(r.done_tokens)
                        completing.append((row, slot, r))
                copies = self.cache.drain_copies(b)
                fresh_rows = self.cache.drain_fresh_rows(b * pages)
                for extra in fresh_rows[1:]:
                    # more recycled pages than the launch's fresh vector
                    # carries (long-prompt burst / unaligned chunk
                    # boundaries): scrub the overflow in dedicated
                    # fixed-shape calls *before* the launch that writes into
                    # those pages
                    if self._scrub is None:
                        n_fresh = sum(int((fr != 0).sum()) for fr in fresh_rows)
                        raise ValueError(
                            f"{n_fresh} fresh pages > pad_to={b * pages} and "
                            "no scrub_fn installed"
                        )
                    self._scrub(extra)
                restores = self.cache.drain_restores()
                if restores is not None:
                    # tier-restored pages (DESIGN.md §18): upload the
                    # verified payloads into their reserved HBM pages *before*
                    # the launch that reads through them — the restore is the
                    # page's full initialization (codes, scales, positions),
                    # so it needs no scrub and must not race the jitted step
                    if self._tier_restore is None:
                        raise ValueError(
                            f"{len(restores[0])} pending tier restores and no "
                            "tier_restore_fn installed"
                        )
                    rt0 = self._clock()
                    self._tier_restore(*restores)
                    rt1 = self._clock()
                    if self._obs_rooflens is not None:
                        self._obs_rooflens.observe_tier_restore(
                            len(restores[0]),
                            self.cache.bytes_per_token() * bs,
                            rt1 - rt0,
                        )
                    if self._obs_metrics is not None:
                        self._obs_metrics.histogram(
                            "serve.tier.restore_wall_s", unit="s"
                        ).record(rt1 - rt0)
            if Span.profiling():
                span.annotate(rows=b, span=sp,
                              real_tokens=sum(n for _, _, _, n in rows))
            with Span("serve.prefill.launch", self._span_clock) as launch:
                logits = self._prefill(
                    tokens, positions, tables, write_slots, write_pos,
                    fresh_rows[0], copies, last_idx,
                )
            with Span("serve.prefill.sync", self._span_clock) as sync:
                if self._poison_pending and completing:
                    # chaos "poison_prefill" (DESIGN.md §17): NaN one real
                    # row's logits before sampling — the guard below must
                    # fail exactly this request and leave its batch-mates
                    # untouched
                    import jax.numpy as jnp
                    self._poison_pending = False
                    logits = jnp.asarray(logits).at[completing[0][0]].set(
                        jnp.nan
                    )
                failed_rows: set = set()
                if self._guard_nonfinite and completing:
                    import jax.numpy as jnp
                    finite = np.asarray(
                        jnp.all(jnp.isfinite(jnp.asarray(logits)), axis=-1)
                    )
                    failed_rows = {
                        row for row, _, _ in completing if not bool(finite[row])
                    }
                toks = self._sample(logits, rids, steps0)
            for row, slot, r in completing:
                if row in failed_rows:
                    # ordered before out/prefix_insert: a poisoned request
                    # never emits a token and never seeds the prefix index
                    self._fail(slot, r)
                    continue
                r.out.append(int(toks[row]))
                self.cache.prefix_insert(r.rid, r.prompt)
            for row, (slot, r, start, n) in enumerate(rows):
                if row in failed_rows:
                    continue  # released: its pages are already reclaimed
                r.peak_blocks = max(
                    r.peak_blocks, self.cache.blocks_held(r.rid)
                )

            st = self._stats
            st["prefill_calls"] += 1
            st["host_syncs"] += 1
            if bounded:
                st["prefill_chunk_calls"] += 1
            st["prefill_token_steps"] += b * sp
            st["prefill_real_tokens"] += sum(n for _, _, _, n in rows)
            t0, t1 = launch.t0, sync.t1  # launch through the host's tokens
            if self._obs_tracer is not None:
                # TTFT attribution: a request's first-token timestamp is the
                # completing chunk's sync — mid-prefill chunks don't emit one
                self._obs_tracer.on_prefill(
                    t0, t1,
                    [r.rid for row, _, r in completing
                     if row not in failed_rows],
                    b, sp,
                )
            if self._obs_rooflens is not None:
                if bounded:
                    self._obs_rooflens.observe_prefill_chunk(
                        b, sp, tw * bs, t1 - t0
                    )
                else:
                    self._obs_rooflens.observe_prefill(b, sp, t1 - t0)

    # ------------------------------------------------------------------
    # decode: single-step (chunk == 1) or device-resident chunk
    # ------------------------------------------------------------------
    def _decode_active(self) -> None:
        # a slot is decode-ready once prefill sampled its first token;
        # mid-prefill slots (chunked mode, empty `out`) sit the round out
        active = [
            (i, r) for i, r in enumerate(self.slots)
            if r is not None and r.out
        ]
        if not active:
            return
        # CoW clones only ever arise from prefix-hit prompt recomputes, and
        # the prefill launch that caused them drains them — decode writing
        # a shared page would mean the plan in PagedKVCache._plan is wrong
        assert self.cache.pending_copies == 0, "unflushed CoW copies at decode"
        # tier restores likewise only arise at admission, and the prefill
        # launch that follows every admission drains them
        assert self.cache.pending_restores == 0, (
            "unflushed tier restores at decode"
        )
        with Span("serve.decode") as span:
            if self._spec is not None and self._spec_enabled:
                self._decode_active_spec(active)
            elif self.chunk > 1:
                self._decode_active_chunked(active, span)
            else:
                self._decode_active_single(active, span)

    def _decode_active_single(self, active, span: Span) -> None:
        m, mb = self.max_slots, self.max_blocks
        with Span("serve.decode.pack"):
            tokens = np.zeros((m, 1), np.int32)
            positions = np.zeros((m, 1), np.int32)
            write_pos = np.full((m, 1), CACHE_EMPTY_POS, np.int32)
            write_slots = np.zeros((m, 1), np.int32)  # null page, offset 0
            tables = np.zeros((m, mb), np.int32)
            kv_lens = np.zeros(m, np.int32)
            rids = np.full(m, -1, np.int64)  # -1: unreachable uint32 sentinel
            steps = np.zeros(m, np.int64)
            for i, r in active:
                pos = r.next_pos - 1  # feed back the last sampled token
                tokens[i, 0] = r.out[-1]
                positions[i, 0] = pos
                write_pos[i, 0] = pos
                write_slots[i, 0] = self.cache.write_slots(r.rid, pos, 1)[0]
                tables[i] = self.cache.block_table_row(r.rid, mb)
                kv_lens[i] = r.next_pos  # incl. the token this step writes
                rids[i] = r.rid
                steps[i] = r.emitted  # global output index: park/resume
            fresh = self.cache.drain_fresh(m)
        if Span.profiling():
            span.annotate(scan_steps=1, slots=len(active),
                          kv_tokens=int(kv_lens.sum(dtype=np.int64)))
        with Span("serve.decode.launch", self._span_clock) as launch:
            logits = self._decode(
                tokens, positions, tables, write_slots, write_pos, fresh,
                kv_lens,
            )
        with Span("serve.decode.sync", self._span_clock) as sync:
            toks = self._sample(logits, rids, steps)
        with Span("serve.decode.replay"):
            for i, r in active:
                r.out.append(int(toks[i]))
                r.peak_blocks = max(
                    r.peak_blocks, self.cache.blocks_held(r.rid)
                )

            self._account_decode(1, len(active))
            self._account_kv_read(int(kv_lens[i]) for i, _ in active)
            self._observe_decode(
                launch.t0, sync.t1, 1, {r.rid: 1 for _, r in active},
                [int(kv_lens[i]) for i, _ in active],
            )

            for i, r in active:
                if self._finished(r):
                    self._evict(i)
            self._free_window_pages()

    def _decode_active_chunked(self, active, span: Span) -> None:
        """Precompute a whole chunk's slot/position advancement, run it as
        one device-resident scan, then replay the sampled tokens against
        host request state (EOS / length caps are also computed on device;
        the replay only decides how many of the C tokens each slot keeps)."""
        m, mb, bs = self.max_slots, self.max_blocks, self.cache.block_size
        with Span("serve.decode.pack"):
            rem = {i: r.max_new_tokens - r.emitted for i, r in active}
            c = min(self.chunk, _pow2ceil(max(rem.values())))
            f = m * ((c + bs - 1) // bs + 1)  # fresh-page bound for the chunk

            # snapshot page state before the chunk pre-allocates, so the
            # accounting below can replay the single-step charging order
            used0 = self.cache.allocator.used_count
            held0 = {i: self.cache.blocks_held(r.rid) for i, r in active}
            p0s: Dict[int, int] = {}

            tokens0 = np.zeros((m, 1), np.int32)
            positions = np.zeros((c, m, 1), np.int32)
            write_slots = np.zeros((c, m, 1), np.int32)
            write_pos = np.full((c, m, 1), CACHE_EMPTY_POS, np.int32)
            tables = np.zeros((m, mb), np.int32)
            kv_lens = np.zeros((c, m), np.int32)
            rids = np.full(m, -1, np.int64)
            start_steps = np.zeros(m, np.int64)
            max_steps = np.zeros(m, np.int32)
            eos = np.full(m, -1, np.int32)
            act = np.zeros(m, bool)
            for i, r in active:
                p0 = p0s[i] = r.next_pos - 1
                si = min(c, rem[i])
                tokens0[i, 0] = r.out[-1]
                rids[i] = r.rid
                start_steps[i] = r.emitted  # sampling keys: global index
                max_steps[i] = si
                act[i] = True
                if r.eos_id is not None:
                    eos[i] = r.eos_id
                # pre-allocate the chunk's pages now; the device table is
                # static for the whole chunk (future slots are
                # scrubbed-empty and mask to zero attention weight until
                # written)
                slots_i = self.cache.write_slots(r.rid, p0, si)
                positions[:, i, 0] = p0 + np.arange(c)
                write_slots[:si, i, 0] = slots_i
                write_pos[:si, i, 0] = p0 + np.arange(si)
                # the §13 length vector: the fused page walk at step j
                # covers the tokens written through position p0 + j (the
                # chunk's pre-allocated future pages sit scrubbed-empty
                # past it)
                kv_lens[:, i] = p0 + 1 + np.arange(c)
            for i, r in active:
                tables[i] = self.cache.block_table_row(r.rid, mb)
            fresh = np.zeros((c, f), np.int32)
            fresh[0] = self.cache.drain_fresh(f)
        if Span.profiling():
            # the scan runs all c steps, dead tail included, and each step's
            # attention walks every active slot's context
            span.annotate(scan_steps=c, slots=len(active),
                          kv_tokens=int(kv_lens.sum(dtype=np.int64)))
        with Span("serve.decode.launch", self._span_clock) as launch:
            toks = self._decode_chunk(
                tokens0, tables, positions, write_slots, write_pos, fresh,
                kv_lens, rids, start_steps, max_steps, eos, act,
            )
        with Span("serve.decode.sync", self._span_clock) as sync:
            toks = np.asarray(toks)  # (c, m): the chunk's one host sync

        with Span("serve.decode.replay"):
            steps_taken: Dict[int, int] = {}
            for i, r in active:
                for j in range(int(max_steps[i])):
                    r.out.append(int(toks[j, i]))
                    if self._finished(r):
                        break
                steps_taken[i] = r.emitted - int(start_steps[i])
                r.peak_blocks = max(
                    r.peak_blocks, self.cache.blocks_held(r.rid)
                )

            self._account_decode_chunk(
                active, steps_taken, used0, held0, p0s, c
            )
            # the fixed-shape scan always runs all c steps, so the roofline
            # prediction is over c; the tracer gets only the kept tokens
            self._observe_decode(
                launch.t0, sync.t1, c,
                {r.rid: steps_taken[i] for i, r in active},
                [p0s[i] + 1 for i, _ in active],
            )

            for i, r in active:
                if self._finished(r):
                    self._evict(i)
            self._free_window_pages()

    def _decode_active_spec(self, active) -> None:
        """Speculative decode round (DESIGN.md §16): `spec_rounds`
        draft-k/verify-once rounds run device-resident in one launch. The
        host pre-allocates each slot's worst-case accepted span (every
        round fully accepted), hands the device a length-bounded block
        table, and afterwards replays the packed emissions against request
        state and rolls the paged pool back to the committed length —
        whole pages the chunk reserved but rejection left unwritten go
        back to the allocator."""
        m, bs = self.max_slots, self.cache.block_size
        k, rounds = self.spec_k, self.spec_rounds
        cap = rounds * (k + 1)
        with Span("serve.decode.pack"):
            rem = {i: r.max_new_tokens - r.emitted for i, r in active}

            used0 = self.cache.allocator.used_count
            held0 = {i: self.cache.blocks_held(r.rid) for i, r in active}
            p0s: Dict[int, int] = {}
            sis: Dict[int, int] = {}

            tokens0 = np.zeros((m, 1), np.int32)
            p0 = np.zeros(m, np.int32)
            rids = np.full(m, -1, np.int64)
            start_steps = np.zeros(m, np.int64)
            max_steps = np.zeros(m, np.int32)
            eos = np.full(m, -1, np.int32)
            act = np.zeros(m, bool)
            for i, r in active:
                pos0 = p0s[i] = r.next_pos - 1
                si = sis[i] = min(cap, rem[i])
                tokens0[i, 0] = r.out[-1]
                p0[i] = pos0
                rids[i] = r.rid
                start_steps[i] = r.emitted  # global index: park/resume
                max_steps[i] = si
                act[i] = True
                if r.eos_id is not None:
                    eos[i] = r.eos_id
                # pre-allocate the full-acceptance span; the device computes
                # write slots from the table, and rollback below returns
                # whatever rejection left unwritten
                self.cache.write_slots(r.rid, pos0, si)
            # the bounded-table trick (PR 5/PR 7): width covers the furthest
            # slot's span, pow2-rounded — it serves both the draft's fused
            # walk and the verify gather, so neither pays max_blocks
            tw = min(
                _pow2ceil(max(
                    math.ceil((p0s[i] + sis[i]) / bs) for i, _ in active
                )),
                self.max_blocks,
            )
            tables = np.zeros((m, tw), np.int32)
            for i, r in active:
                tables[i] = self.cache.block_table_row(r.rid, tw)
            fresh = self.cache.drain_fresh(m * ((cap + bs - 1) // bs + 1))
        with Span("serve.decode.launch", self._span_clock) as launch:
            out, e_rounds = self._spec(
                tokens0, tables, p0, fresh, rids, start_steps, max_steps,
                eos, act,
            )  # out (cap, m) packed emissions, e_rounds (rounds, m)
        with Span("serve.decode.sync", self._span_clock) as sync:
            out, e_rounds = np.asarray(out), np.asarray(e_rounds)

        with Span("serve.decode.replay"):
            steps_taken: Dict[int, int] = {}
            for i, r in active:
                emitted = 0
                for t in range(rounds):
                    for _ in range(int(e_rounds[t, i])):
                        r.out.append(int(out[emitted, i]))
                        emitted += 1
                steps_taken[i] = emitted
                r.peak_blocks = max(
                    r.peak_blocks, self.cache.blocks_held(r.rid)
                )
                # rewind to the committed length: positions >= next_pos - 1
                # hold only rejected-draft junk (the pending token's KV is
                # written next round), so their whole pages are dead weight
                self.cache.rollback(r.rid, r.next_pos - 1)

            self._account_decode_spec(active, e_rounds, p0s, held0, used0, tw)
            kept = {r.rid: steps_taken[i] for i, r in active}
            live_rounds = int(np.sum(np.any(e_rounds > 0, axis=1)))
            t0, t1 = launch.t0, sync.t1
            if self._obs_tracer is not None:
                self._obs_tracer.on_decode_chunk(t0, t1, live_rounds, kept)
            if self._obs_rooflens is not None:
                self._obs_rooflens.observe_spec(
                    [p0s[i] + 1 for i, _ in active], k, max(1, live_rounds),
                    t1 - t0,
                )
            if self._obs_metrics is not None:
                self._publish_gauges()

            for i, r in active:
                if self._finished(r):
                    self._evict(i)
            self._free_window_pages()

    def _account_decode_spec(
        self,
        active,
        e_rounds: np.ndarray,
        p0s: Dict[int, int],
        held0: Dict[int, int],
        used0: int,
        tw: int,
    ) -> None:
        """Replay the spec chunk's per-round charging. One round counts as
        one decode step (it is one draft+verify iteration of the batch), so
        `mean_occupancy` reads as emitted tokens per slot-round — above 1.0
        exactly when speculation is paying off. Page charging mirrors
        `_account_decode_chunk` over *committed* tokens only: pages the
        chunk pre-allocated but rollback reclaimed never existed as far as
        the occupancy stats are concerned.

        KV read traffic per live slot-round: k fused draft walks (window-
        capped when a draft window is set) plus one verify gather over the
        bounded table width `tw`; with the fused path routed off both
        passes gather `tw` pages."""
        st = self._stats
        st["decode_chunks"] += 1
        st["host_syncs"] += 1
        bs = self.cache.block_size
        k = self.spec_k
        fused = kernel_ops.PAGED_ATTENTION_FUSED
        wins = [
            w for w in (self.spec_window or None, self.local_window)
            if w is not None
        ]
        window = min(wins) if wins else None
        used = used0
        grown = dict.fromkeys(held0, 0)
        pos = dict(p0s)
        cum = dict.fromkeys(held0, 0)
        total = {i: int(np.sum(e_rounds[:, i])) for i, _ in active}
        for t in range(e_rounds.shape[0]):
            live = [i for i, _ in active if int(e_rounds[t, i]) > 0]
            if not live:
                break
            st["decode_steps"] += 1
            st["verify_calls"] += len(live)
            st["draft_tokens"] += k * len(live)
            for i in live:
                e = int(e_rounds[t, i])
                for j in range(e):
                    if (pos[i] + j) % bs == 0:
                        used += 1
                        grown[i] += 1
                # draft walks at kv_len = pos+j+1, j in [0, k)
                for j in range(k):
                    kv = pos[i] + j + 1
                    if fused:
                        first = max(0, kv - window) // bs if window else 0
                        st["kv_pages_read"] += min(tw, -(-kv // bs)) - first
                    else:
                        st["kv_pages_read"] += tw
                # one verify gather over the bounded table
                st["kv_pages_read"] += tw
                st["kv_pages_read_worst"] += e * self.max_blocks
                st["active_slot_steps"] += e
                pos[i] += e
                cum[i] += e
            st["paged_block_steps"] += used
            st["dense_block_steps"] += len(live) * self.max_blocks
            st["peak_blocks"] = max(st["peak_blocks"], used)
            for i, r in active:
                if i in live and cum[i] == total[i] and self._finished(r):
                    used -= held0[i] + grown[i]

    def _account_decode_chunk(
        self,
        active,
        steps_taken: Dict[int, int],
        used0: int,
        held0: Dict[int, int],
        p0s: Dict[int, int],
        c: int,
    ) -> None:
        """Replay the single-step charging order over the chunk: a page is
        charged from the step its first token lands and released the step
        its request finishes — even though the chunk pre-allocates pages up
        front and evicts at the boundary. Charging the end-of-chunk
        `used_count` for all c steps would overstate paged_block_steps as a
        function of chunk size, making padding-waste stats non-comparable
        between chunk settings."""
        st = self._stats
        st["decode_chunks"] += 1
        st["host_syncs"] += 1
        bs = self.cache.block_size
        used = used0
        grown = dict.fromkeys(held0, 0)  # pages newly landed per slot
        for j in range(c):
            live = [i for i, _ in active if j < steps_taken[i]]
            if not live:
                # dead tail of the chunk (EOS drained every slot): the scan
                # did run these steps, but counting them would make
                # decode_steps — and every per-step stat derived from it —
                # a function of the chunk setting
                break
            st["decode_steps"] += 1
            for i in live:
                if (p0s[i] + j) % bs == 0:
                    used += 1
                    grown[i] += 1
            st["active_slot_steps"] += len(live)
            st["paged_block_steps"] += used
            st["dense_block_steps"] += len(live) * self.max_blocks
            st["peak_blocks"] = max(st["peak_blocks"], used)
            self._account_kv_read(p0s[i] + j + 1 for i in live)
            for i, r in active:
                if steps_taken[i] == j + 1 and self._finished(r):
                    used -= held0[i] + grown[i]

    def _account_decode(self, steps: int, slot_steps: int) -> None:
        st = self._stats
        st["decode_steps"] += steps
        st["decode_chunks"] += 1
        st["host_syncs"] += 1
        st["active_slot_steps"] += slot_steps
        used = self.cache.allocator.used_count
        st["paged_block_steps"] += used * steps
        # what a max_len ring cache would have held for the same work:
        # max_blocks pages per active slot-step
        st["dense_block_steps"] += slot_steps * self.max_blocks
        st["peak_blocks"] = max(st["peak_blocks"], used)

    def _account_kv_read(self, kv_lens) -> None:
        """Charge one decode token's KV read traffic per live slot. With
        the fused path on, the walk covers [first window-visible page,
        ceil(kv_len / bsize)) — the §13 bounds; with it routed off
        (`ops.PAGED_ATTENTION_FUSED = False`, the benchmark baseline),
        decode really does gather all max_blocks pages and the stat must
        say so. The worst-case column is always the max_blocks gather."""
        st = self._stats
        bs = self.cache.block_size
        fused = kernel_ops.PAGED_ATTENTION_FUSED
        for kv_len in kv_lens:
            if fused:
                first = (
                    max(0, kv_len - self.local_window) // bs
                    if self.local_window
                    else 0
                )
                pages = min(self.max_blocks, -(-kv_len // bs)) - first
            else:
                pages = self.max_blocks
            st["kv_pages_read"] += pages
            st["kv_pages_read_worst"] += self.max_blocks

    def _observe_decode(self, t0: float, t1: float, steps: int,
                        kept: Dict[int, int], kv_lens: List[int]) -> None:
        """Feed one decode round to whichever collectors are installed
        (DESIGN.md §14). `t0`/`t1` are the launch's start and the sync's
        end, read by the spans only when a collector times them; `steps`
        is scan steps launched, `kept` the tokens each request keeps,
        `kv_lens` the active slots' context lengths at round start."""
        if self._obs_tracer is not None:
            self._obs_tracer.on_decode_chunk(t0, t1, steps, kept)
        if self._obs_rooflens is not None:
            self._obs_rooflens.observe_decode(kv_lens, steps, t1 - t0)
        if self._obs_metrics is not None:
            self._publish_gauges()

    def _publish_gauges(self) -> None:
        """Pool / queue occupancy gauges (metrics registry installed)."""
        m = self._obs_metrics
        m.gauge("serve.queue_depth", unit="requests").set(len(self.queue))
        occ = self.cache.occupancy()
        m.gauge("serve.pool.used_pages", unit="pages").set(occ["used"])
        m.gauge("serve.pool.free_pages", unit="pages").set(occ["free"])
        m.gauge("serve.pool.reserved_pages", unit="pages").set(occ["reserved"])
        m.gauge("serve.pool.admittable_pages", unit="pages").set(
            occ["admittable"]
        )
        m.gauge("serve.pool.shared_pages", unit="pages").set(occ["shared"])
        m.gauge("serve.pool.prefix_cached_pages", unit="pages").set(
            occ["cached"]
        )
        m.gauge("serve.pool.tiered_pages", unit="pages").set(occ["tiered"])
        m.gauge("serve.slots.active", unit="slots").set(
            sum(1 for r in self.slots if r is not None)
        )

    def _free_window_pages(self) -> None:
        """Window-aware page freeing (all-local-attention stacks only):
        a key at position p is visible to query q iff p > q - window, and
        live queries only advance, so every page wholly below
        `next_pos - window` is dead for good. Its table entry becomes a
        null-page read (masked by the scrubbed sentinel — never the stale
        physical page, which may be reallocated to another tenant)."""
        if self.local_window is None:
            return
        freed = 0
        for r in self.slots:
            if r is not None:
                # next query position: decode feeds back the last sampled
                # token at next_pos - 1; a mid-prefill slot's next chunk
                # starts at `prefilled`
                nq = r.next_pos - 1 if r.out else r.prefilled
                freed += self.cache.free_behind(
                    r.rid, nq + 1 - self.local_window
                )
        self._stats["window_freed_pages"] += freed

    def _finished(self, r: Request) -> bool:
        # `emitted` counts the whole life incl. banked pre-park tokens, so
        # a resumed request's length cap is unchanged by the interruption
        return r.emitted >= r.max_new_tokens or (
            r.eos_id is not None and r.out and r.out[-1] == r.eos_id
        )

    def _evict(self, slot: int) -> None:
        r = self.slots[slot]
        if r is None:
            # idempotent: EOS-at-prefill and a length cap can both route a
            # request here in one round; the second visit is a no-op (the
            # cache release below is likewise idempotent)
            return
        self.results[r.rid] = np.asarray(r.all_out, np.int32)
        self.statuses[r.rid] = RequestStatus.OK
        self.request_peaks[r.rid] = r.peak_blocks
        self.cache.release(r.rid)
        self.slots[slot] = None
        if self._obs_tracer is not None:
            reason = (
                "eos" if r.eos_id is not None and r.out
                and r.out[-1] == r.eos_id else "length"
            )
            self._obs_tracer.on_finish(r.rid, reason)
        if self._obs_metrics is not None:
            self._obs_metrics.counter(
                "serve.requests.finished", unit="requests"
            ).inc()
            # queue_depth / pool gauges refresh at eviction too, not only
            # at submit and admission — an idle-tail drain stays observable
            self._publish_gauges()

    # ------------------------------------------------------------------
    # occupancy / padding-waste report
    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """Defensive snapshot of the serving counters plus derived ratios.

        The returned dict is freshly built on every call and shares no
        state with the scheduler — callers may mutate or hold it without
        affecting later snapshots (the PR 4 ad-hoc dict aliased nothing
        either, but that was an accident of `dict()`, not a contract; now
        it is the contract, test-enforced). Every key's unit is documented
        in `STAT_UNITS`; when a metrics registry is installed the snapshot
        is also folded into it as `serve.stats.*` gauges."""
        st = dict(self._stats)
        steps = max(1, st["decode_steps"])
        st["mean_occupancy"] = st["active_slot_steps"] / (steps * self.max_slots)
        st["mean_blocks"] = st["paged_block_steps"] / steps
        dense = max(1, st["dense_block_steps"])
        # fraction of block-steps a max_len ring cache would have held that
        # the paged pool never allocated
        st["padding_waste_saved"] = 1.0 - st["paged_block_steps"] / dense
        # prefill accounting: padded token-steps actually launched vs real
        # prompt tokens — occupancy stats no longer overstate efficiency
        # for prompt-heavy traffic
        padded = max(1, st["prefill_token_steps"])
        st["prefill_padding_waste"] = 1.0 - st["prefill_real_tokens"] / padded
        # codec-driven KV footprint: pool bytes per token slot (all layers),
        # so a quantized kv_quant shows its byte saving next to the paging
        # stats
        st["kv_bytes_per_token"] = self.cache.bytes_per_token()
        # decode-attention read traffic (DESIGN.md §13): bytes the fused
        # length-bounded page walk actually streamed per decoded token vs
        # the max_blocks worst case the gather-read always paid — the
        # observable for the paged-attention win (benchmarks serving_decode)
        page_bytes = self.cache.bytes_per_token() * self.cache.block_size
        toks = max(1, st["active_slot_steps"])
        st["kv_read_bytes_per_token"] = st["kv_pages_read"] * page_bytes / toks
        st["kv_read_bytes_per_token_worst"] = (
            st["kv_pages_read_worst"] * page_bytes / toks
        )
        # prefix-sharing observables (DESIGN.md §15): hit tokens and CoW
        # clones are lifetime counters the cache owns; shared/cached pages
        # are point-in-time occupancy (0 on an idle pool without an index)
        # speculative decode (DESIGN.md §16): tokens emitted per verify
        # pass. On a spec engine every decoded token flows through verify,
        # so the ratio is exact; without speculation it reads 0.0
        st["accepted_tokens_per_step"] = (
            st["active_slot_steps"] / st["verify_calls"]
            if st["verify_calls"] else 0.0
        )
        occ = self.cache.occupancy()
        st["prefix_hit_tokens"] = self.cache.prefix_hit_tokens
        st["cow_copies"] = self.cache.cow_copies
        st["shared_pages"] = occ["shared"]
        st["prefix_cached_pages"] = occ["cached"]
        # host-tier observables (DESIGN.md §18): lifetime spill/restore/
        # corruption counters the tier owns, plus point-in-time residency;
        # all read 0 on an engine without a tier
        tier = self.cache.tier
        st["tier_spilled_pages"] = tier.spilled_pages if tier else 0
        st["tier_restored_pages"] = tier.restored_pages if tier else 0
        st["tier_pages"] = tier.pages if tier else 0
        st["tier_bytes"] = tier.payload_bytes if tier else 0
        st["tier_corrupt"] = tier.corrupt_pages if tier else 0
        st["tier_fallback_recompute"] = tier.fallback_recomputes if tier else 0
        st["tier_hit_tokens"] = self.cache.tier_hit_tokens
        assert set(st) <= set(STAT_UNITS), (
            f"undocumented stats keys: {set(st) - set(STAT_UNITS)} — "
            "add units to STAT_UNITS"
        )
        if self._obs_metrics is not None:
            self._obs_metrics.ingest("serve.stats", st, units=STAT_UNITS)
        return st
