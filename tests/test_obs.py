"""Observability-layer battery (DESIGN.md §14).

Covers the three collectors (metrics registry, request tracer, RoofLens)
on a fake monotonic clock — deterministic TTFT/ITL math, histogram
quantile edge cases, Chrome-trace schema — plus the zero-overhead
contract: the serving engine's outputs and the decode chunk's jaxpr must
be bit-identical with and without observers installed, and the roofline
predicted-vs-measured loop must land within a loose factor after
calibration on real engine runs.
"""
import io
import json
import math

import numpy as np
import pytest
import jax

from repro.configs.base import get_smoke_config
from repro.models.model import Model
from repro.obs import MetricsRegistry, Observability, RoofLens, Tracer
from repro.obs.metrics import Histogram, exact_percentiles
from repro.serve.engine import GenerationEngine, SpecConfig
from repro.serve.scheduler import STAT_UNITS


class FakeClock:
    """Injectable monotonic clock: advances only when told."""

    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> float:
        self.t += dt
        return self.t


@pytest.fixture(scope="module")
def llama():
    cfg = get_smoke_config("llama3-8b")
    m = Model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _prompts(vocab, lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


def _drain(m, params, prompts, n_steps, *, chunk=4, obs=None, **kw):
    eng = GenerationEngine(
        m, params, max_len=64, block_size=8, max_slots=2,
        decode_chunk=chunk, obs=obs, **kw,
    )
    rids = [eng.submit(p, max_new_tokens=n_steps) for p in prompts]
    done = eng.run_until_drained()
    return [done[r] for r in rids], eng


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("t.requests", unit="requests")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("t.depth", unit="requests")
    g.set(7)
    g.set(3)
    assert g.value == 3.0
    # get-or-create returns the same instance
    assert reg.counter("t.requests", unit="requests") is c


def test_registry_name_conflicts_raise():
    reg = MetricsRegistry()
    reg.counter("t.x", unit="tokens")
    with pytest.raises(ValueError):
        reg.gauge("t.x", unit="tokens")  # type conflict
    with pytest.raises(ValueError):
        reg.counter("t.x", unit="pages")  # unit conflict


def test_histogram_empty_and_single_sample():
    h = Histogram("t.h")
    assert math.isnan(h.quantile(0.5))
    assert math.isnan(h.mean)
    h.record(0.125)
    # single sample: clamping into [min, max] makes every quantile exact
    for q in (0.0, 0.5, 0.99, 1.0):
        assert h.quantile(q) == 0.125
    assert h.mean == 0.125


def test_histogram_zero_stream_stays_exact():
    h = Histogram("t.h")
    for _ in range(10):
        h.record(0.0)
    assert h.quantile(0.5) == 0.0
    assert h.quantile(0.99) == 0.0
    h.record(8.0)  # one outlier: p99 leaves the zero bucket
    assert h.quantile(0.5) == 0.0
    assert h.quantile(1.0) > 0.0


def test_histogram_bounded_relative_error():
    """Log bucketing: any quantile of positive samples is within one
    bucket ratio of the true order statistic."""
    h = Histogram("t.h", ratio=2 ** 0.25)
    rng = np.random.default_rng(0)
    samples = np.exp(rng.uniform(-8, 8, 500))  # 7 orders of magnitude
    for v in samples:
        h.record(float(v))
    for q in (0.5, 0.9, 0.99):
        true = float(np.quantile(samples, q, method="inverted_cdf"))
        got = h.quantile(q)
        assert true / h.ratio <= got <= true * h.ratio


def test_histogram_rejects_bad_samples():
    h = Histogram("t.h")
    with pytest.raises(ValueError):
        h.record(-1.0)
    with pytest.raises(ValueError):
        h.record(math.nan)
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        Histogram("t.bad", ratio=1.0)


def test_registry_timer_uses_injected_clock():
    clk = FakeClock()
    reg = MetricsRegistry(clock=clk)
    with reg.timer("t.span_s"):
        clk.tick(0.25)
    h = reg.histogram("t.span_s", unit="s")
    assert h.count == 1
    assert h.quantile(0.5) == 0.25


def test_registry_ingest_and_snapshot_defensive():
    reg = MetricsRegistry()
    reg.ingest("pre", {"a": 1.0, "b": 2.0}, units={"a": "pages"})
    snap = reg.snapshot()
    assert snap["pre.a"] == {"type": "gauge", "unit": "pages", "value": 1.0}
    assert snap["pre.b"]["unit"] == "value"
    snap["pre.a"]["value"] = 999  # caller mutation must not leak back
    assert reg.gauge("pre.a", unit="pages").value == 1.0


def test_exact_percentiles_nearest_rank():
    assert all(math.isnan(v) for v in exact_percentiles([]).values())
    vals = [float(x) for x in range(1, 101)]
    p = exact_percentiles(vals)
    assert p == {"p50": 50.0, "p90": 90.0, "p99": 99.0}
    assert exact_percentiles([7.0]) == {"p50": 7.0, "p90": 7.0, "p99": 7.0}


# ---------------------------------------------------------------------------
# tracer: fake-clock lifecycle math and chrome-trace schema
# ---------------------------------------------------------------------------

def _scripted_lifecycle():
    """One request through submit/admit/prefill/2 decode chunks/finish on a
    fake clock; returns (tracer, clock)."""
    clk = FakeClock(t=10.0)
    tr = Tracer(clock=clk)
    tr.on_submit(0, prompt_len=8, max_new_tokens=5)          # t = 10.0
    clk.tick(0.5)
    tr.on_admit(0, slot=1)                                   # t = 10.5
    tr.on_admit_round(10.0, 10.5, 1, 0)
    clk.tick(0.5)
    tr.on_prefill(10.5, 11.0, [0], batch_rows=1, span_tokens=8)
    clk.tick(0.25)
    tr.on_decode_chunk(11.0, 11.25, steps=2, kept={0: 2})
    clk.tick(0.25)
    tr.on_decode_chunk(11.25, 11.5, steps=2, kept={0: 2})
    tr.on_finish(0, "length")                                # t = 11.5
    return tr, clk


def test_tracer_fake_clock_ttft_itl():
    tr, _ = _scripted_lifecycle()
    r = tr.requests[0]
    # first token becomes visible at prefill end; chunk tokens burst at
    # the chunk-end sync
    assert r.token_times == [11.0, 11.25, 11.25, 11.5, 11.5]
    assert r.ttft == pytest.approx(1.0)
    assert r.queue_wait == pytest.approx(0.5)
    assert r.itl == pytest.approx([0.25, 0.0, 0.25, 0.0])
    assert r.finish_reason == "length"

    s = tr.summary()
    assert s["n_requests"] == 1 and s["n_tokens"] == 5
    assert s["ttft_s"]["p50"] == pytest.approx(1.0)
    # pooled ITL nearest-rank over [0, 0, 0.25, 0.25]
    assert s["itl_s"]["p50"] == pytest.approx(0.0)
    assert s["itl_s"]["p99"] == pytest.approx(0.25)
    assert s["itl_s"]["mean"] == pytest.approx(0.125)
    assert s["queue_wait_s"]["p50"] == pytest.approx(0.5)


def test_tracer_unfinished_requests_excluded_from_summary():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    tr.on_submit(0, 4, 4)
    assert math.isnan(tr.requests[0].ttft)
    s = tr.summary()
    assert s["n_requests"] == 0
    assert math.isnan(s["ttft_s"]["p50"])


def test_tracer_reset_keeps_instance_live():
    tr, _ = _scripted_lifecycle()
    tr.reset()
    assert tr.requests == {} and tr.spans == []
    tr.on_submit(1, 4, 4)  # still usable after reset
    assert 1 in tr.requests


def test_chrome_trace_schema():
    tr, _ = _scripted_lifecycle()
    buf = io.StringIO()
    tr.export_chrome_trace(buf)
    doc = json.loads(buf.getvalue())
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    for ev in evs:
        assert ev["ph"] in ("X", "M", "i")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert "name" in ev
        if ev["ph"] != "M":
            assert ev["ts"] >= 0.0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
        if ev["ph"] == "i":
            assert ev["s"] in ("t", "p", "g")
    names = [e["name"] for e in evs]
    # scheduler track spans + per-request track + token instants
    assert names.count("admit") == 2  # scheduler span + request instant
    assert "prefill" in names and names.count("decode_chunk") == 2
    assert "first_token" in names and names.count("token") == 4
    # the request span carries its lifecycle args
    req = next(e for e in evs if e["name"] == "req0")
    assert req["args"]["n_tokens"] == 5
    assert req["args"]["reason"] == "length"
    assert req["args"]["ttft_ms"] == pytest.approx(1000.0)
    # spans are microseconds relative to the earliest event
    pre = next(e for e in evs if e["name"] == "prefill")
    assert pre["ts"] == pytest.approx(0.5e6) and pre["dur"] == pytest.approx(0.5e6)


# ---------------------------------------------------------------------------
# zero-overhead contract: observers change nothing
# ---------------------------------------------------------------------------

def test_engine_outputs_bit_identical_with_and_without_obs(llama):
    m, params = llama
    prompts = _prompts(m.cfg.vocab_size, lengths=(5, 13, 9))
    want, _ = _drain(m, params, prompts, 5, chunk=4, obs=None)
    got, eng = _drain(
        m, params, prompts, 5, chunk=4, obs=Observability.default()
    )
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    # and the collectors actually saw the run
    s = eng.obs.tracer.summary()
    assert s["n_requests"] == 3
    assert s["n_tokens"] == sum(len(t) for t in got)
    assert eng.obs.metrics.counter(
        "serve.requests.finished", unit="requests"
    ).value == 3


def test_decode_chunk_jaxpr_identical_with_and_without_obs(llama):
    """Acceptance: instrumentation adds no device-side work — the decode
    chunk traces to the same jaxpr whether or not observers are installed
    (all hooks live outside the jitted function)."""
    m, params = llama

    def build(obs):
        return GenerationEngine(
            m, params, max_len=64, block_size=8, max_slots=2,
            decode_chunk=4, obs=obs,
        )

    def trace(eng):
        C, M, MB = 4, 2, eng.max_blocks
        F = M * ((C + 7) // 8 + 1)
        i32 = np.int32
        return jax.make_jaxpr(
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

    without = trace(build(None))
    with_obs = trace(build(Observability.default()))
    assert str(without) == str(with_obs)


# ---------------------------------------------------------------------------
# scheduler stats contract
# ---------------------------------------------------------------------------

def test_stats_snapshot_is_defensive_and_units_documented(llama):
    m, params = llama
    prompts = _prompts(m.cfg.vocab_size, lengths=(5, 9))
    _, eng = _drain(m, params, prompts, 4, chunk=4)
    st = eng.scheduler.stats()
    # every returned key carries a documented unit, and vice versa the
    # raw-counter half of the table stays live
    assert set(st) == set(STAT_UNITS)
    # mutating the snapshot must not corrupt the scheduler
    st["decode_steps"] = -999
    st["mean_occupancy"] = math.inf
    st2 = eng.scheduler.stats()
    assert st2["decode_steps"] > 0
    assert st2["mean_occupancy"] == pytest.approx(
        st2["active_slot_steps"] / (st2["decode_steps"] * 2)
    )
    assert st is not st2


@pytest.mark.parametrize("chunk", [1, 4])
def test_host_sync_accounting(llama, chunk):
    """host_syncs = one per prefill call + one per decode round, in both
    the single-step and device-resident chunked modes."""
    m, params = llama
    prompts = _prompts(m.cfg.vocab_size, lengths=(5, 9))
    _, eng = _drain(m, params, prompts, 4, chunk=chunk)
    st = eng.scheduler.stats()
    assert st["host_syncs"] == st["prefill_calls"] + st["decode_chunks"]
    if chunk == 1:
        assert st["decode_chunks"] == st["decode_steps"]
    else:
        assert st["decode_chunks"] < st["decode_steps"]


def test_stats_fold_into_registry_and_pool_gauges(llama):
    m, params = llama
    obs = Observability.default()
    prompts = _prompts(m.cfg.vocab_size, lengths=(5, 9))
    _, eng = _drain(m, params, prompts, 4, chunk=4, obs=obs)
    eng.scheduler.stats()  # folds the snapshot into serve.stats.* gauges
    snap = obs.metrics.snapshot()
    assert snap["serve.stats.mean_occupancy"]["unit"] == (
        STAT_UNITS["mean_occupancy"]
    )
    occ = eng.kv.occupancy()
    assert occ["used"] == 0 and occ["free"] == occ["total"]  # drained
    # pool gauges are the last *published* sample (end of the final decode
    # round, before eviction frees the pages) — hold the allocator
    # invariant at that instant, not the post-drain state
    assert (
        snap["serve.pool.used_pages"]["value"]
        + snap["serve.pool.free_pages"]["value"]
        == occ["total"]
    )
    assert snap["serve.stats.host_syncs"]["value"] == (
        eng.scheduler.stats()["host_syncs"]
    )


# ---------------------------------------------------------------------------
# RoofLens: predicted-vs-measured
# ---------------------------------------------------------------------------

def _bound_lens(registry=None):
    lens = RoofLens(registry=registry)
    cfg = get_smoke_config("llama3-8b")
    lens.bind(cfg=cfg, weight_bytes=10 ** 6, kv_quant=None, m_slots=2)
    return lens


def test_rooflens_perfect_proxy_calibrates_to_unity():
    """If measured time is an exact constant multiple of the raw roofline
    prediction, calibration absorbs the constant and the error report
    shows unit ratios across batch compositions."""
    lens = _bound_lens()
    comps = [([8.0], 4), ([16.0, 24.0], 4), ([40.0], 2), ([4.0, 4.0], 8)]
    for kv_lens, steps in comps:
        lens.observe_decode(kv_lens, steps, 1234.0 * lens._raw_decode(kv_lens, steps))
    lens.observe_prefill(2, 16, 987.0 * lens._raw_prefill(2, 16))
    scale = lens.calibrate()
    assert scale["decode"] == pytest.approx(1234.0)
    assert scale["prefill"] == pytest.approx(987.0)
    rep = lens.error_report()
    assert rep["decode"]["n"] == len(comps)
    assert rep["decode"]["geomean_ratio"] == pytest.approx(1.0)
    assert rep["decode"]["max_abs_log2"] == pytest.approx(0.0, abs=1e-9)
    # per-codec breakdown keys exist
    assert "decode[w=dense,kv=none]" in rep


def test_rooflens_prediction_monotone_in_work():
    """More rows, longer contexts, more steps -> larger predicted time
    (the ranking property the SLA scheduler needs)."""
    lens = _bound_lens()
    assert lens._raw_prefill(4, 32) > lens._raw_prefill(1, 32)
    assert lens._raw_prefill(2, 64) > lens._raw_prefill(2, 16)
    assert lens._raw_decode([64.0], 4) > lens._raw_decode([8.0], 4)
    assert lens._raw_decode([8.0], 8) > lens._raw_decode([8.0], 4)


def test_rooflens_requires_bind():
    lens = RoofLens()
    with pytest.raises(RuntimeError, match="not bound"):
        lens.predict_decode([8.0], 1)


def test_rooflens_engine_loose_factor(llama):
    """Real engine runs: after calibrating on one compiled drain, the
    decode-regime roofline prediction must track measured chunk times
    within a loose factor (8x) — CPU-interpreted timings are noisy, but
    the model's relative structure has to hold."""
    m, params = llama
    obs = Observability.default()
    eng = GenerationEngine(
        m, params, max_len=64, block_size=8, max_slots=2, decode_chunk=4,
        obs=obs,
    )
    prompts = _prompts(m.cfg.vocab_size, lengths=(5, 9, 13))

    def drain():
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        eng.run_until_drained()

    drain()                          # compile pass: timings are compiles
    obs.rooflens.reset_samples()
    drain()                          # clean pass: fit the calibration
    obs.rooflens.calibrate()
    obs.rooflens.reset_samples()
    drain()                          # measured pass
    rep = obs.rooflens.error_report()
    dec = rep["decode"]
    assert dec["n"] >= 2
    assert 1 / 8 < dec["geomean_ratio"] < 8
    assert dec["max_abs_log2"] < 5.0
    # the registry mirrored the loop
    assert obs.metrics.histogram(
        "rooflens.decode.measured_s", unit="s"
    ).count >= dec["n"]


# ---------------------------------------------------------------------------
# spans on the profiler's clock, and kernel scopes in the HLO
# ---------------------------------------------------------------------------

SPAN_PARENTS = {
    "serve.admit": "serve.step", "serve.prefill": "serve.step",
    "serve.decode": "serve.step",
    **{f"serve.prefill.{p}": "serve.prefill"
       for p in ("pack", "launch", "sync")},
    **{f"serve.decode.{p}": "serve.decode"
       for p in ("pack", "launch", "sync", "replay")},
}


def _profiled_drain(m, params, prompts, n_steps, chunk, tmp_path, **kw):
    """Drain `prompts` under the profiler; the program's `serve.*` host
    events (name, start, end, int args) and the stats deltas."""
    import glob

    from jax.profiler import ProfileData

    eng = GenerationEngine(
        m, params, max_len=64, block_size=8, max_slots=len(prompts),
        decode_chunk=chunk, **kw,
    )
    st0 = eng.scheduler.stats()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        for p in prompts:
            eng.submit(p, max_new_tokens=n_steps)
        eng.run_until_drained()
    st1 = eng.scheduler.stats()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    events = [
        (ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:CPU")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("serve.")
    ]
    return events, {k: st1[k] - st0[k] for k in st0}


@pytest.mark.parametrize("chunk", [1, 4])
def test_serving_spans_nest_and_count_the_launched_work(llama, chunk, tmp_path):
    """The scheduler's spans nest as named, and their arguments count the
    device's work: two requests admitted together run 8 decode steps in
    lockstep (no dead chunk tail), each step's attention reading every
    slot's context through the token it writes."""
    m, params = llama
    lengths, n_steps = (5, 13), 9
    prompts = _prompts(m.cfg.vocab_size, lengths)
    events, delta = _profiled_drain(m, params, prompts, n_steps, chunk,
                                    tmp_path)
    names = [e[0] for e in events]
    assert names.count("serve.submit") == len(prompts)
    for name, s, e, _ in events:
        parent = SPAN_PARENTS.get(name)
        if parent is not None:
            assert any(n == parent and ps <= s and e <= pe
                       for n, ps, pe, _ in events), name
    decode = [a for n, _, _, a in events if n == "serve.decode"]
    prefill = [a for n, _, _, a in events if n == "serve.prefill"]
    assert len(decode) == delta["decode_chunks"]
    assert len(prefill) == delta["prefill_calls"] == 1
    steps = n_steps - 1  # the first token comes from the prefill
    assert sum(a["scan_steps"] for a in decode) == steps == delta["decode_steps"]
    assert all(a["slots"] == len(prompts) for a in decode)
    # decoding output j + 1 reads the prompt and j outputs
    assert sum(a["kv_tokens"] for a in decode) == sum(
        p + j for p in lengths for j in range(1, steps + 1)
    )
    (a,) = prefill
    assert a["rows"] == 2 and a["span"] == 16  # pow2 rows, page-rounded span
    assert a["real_tokens"] == sum(lengths) == delta["prefill_real_tokens"]
    assert a["rows"] * a["span"] == delta["prefill_token_steps"]


def test_spec_decode_spans_nest_and_state_no_work(llama, tmp_path):
    """The speculative path opens the same decode spans, and states no
    work: its draft and verify steps read two weight trees, which one
    `scan_steps` count cannot price."""
    m, params = llama
    prompts = _prompts(m.cfg.vocab_size, (5, 13))
    events, delta = _profiled_drain(
        m, params, prompts, 9, 4, tmp_path,
        spec_decode=SpecConfig(k=2, draft_codec="bf8"),
    )
    decode = [(s, e, a) for n, s, e, a in events if n == "serve.decode"]
    assert decode and len(decode) == delta["decode_chunks"]
    assert all(a == {} for _, _, a in decode)
    for child in ("pack", "launch", "sync", "replay"):
        kids = [(s, e) for n, s, e, _ in events
                if n == f"serve.decode.{child}"]
        assert len(kids) == len(decode), child
        assert all(any(ps <= s and e <= pe for ps, pe, _ in decode)
                   for s, e in kids), child


def test_spans_take_no_clock_and_no_args_when_nothing_listens(llama):
    """Without a profile or a collector the spans read no clock and the
    scheduler computes no span arguments."""
    from repro.obs.trace import Span

    assert not Span.profiling()
    m, params = llama
    eng = GenerationEngine(m, params, max_len=64, block_size=8, max_slots=2)
    assert eng.scheduler._span_clock is None
    with Span("serve.test") as s:
        pass
    assert (s.t0, s.t1) == (0.0, 0.0)


def _compressed(llama):
    from repro.core.decompress import compress_tree
    from repro.core.formats import get_spec

    m, params = llama
    return m, compress_tree(params, get_spec("mxfp4_100"))


def test_step_programs_carry_the_kernel_scopes(llama):
    """The decode chunk's and the paged prefill's HLO name every kernel
    scope the device-trace reduction reads."""
    m, params = _compressed(llama)
    eng = GenerationEngine(m, params, max_len=64, block_size=8, max_slots=2,
                           decode_chunk=4)
    C, M, MB = 4, 2, eng.max_blocks
    F = M * ((C + 7) // 8 + 1)
    i32 = np.int32
    chunk = eng._paged_decode_chunk.lower(
        eng.params, eng.kv.pools,
        np.zeros((M, 1), i32), np.zeros((M, MB), i32),
        np.zeros((C, M, 1), i32), np.zeros((C, M, 1), i32),
        np.zeros((C, M, 1), i32), np.zeros((C, F), i32),
        np.ones((C, M), i32),
        np.zeros(M, np.uint32), np.zeros(M, np.uint32),
        np.full(M, C, i32), np.full(M, -1, i32), np.ones(M, bool),
        np.float32(1.0), jax.random.PRNGKey(0), greedy=True,
    ).as_text(debug_info=True)
    for scope in ("deca.gemv", "deca.paged_attention", "model.head",
                  "kv.write"):
        assert scope in chunk, scope
    assert "deca.gemm" not in chunk
    B, S = 2, 32  # 64 rows: the GEMM regime
    prefill = eng._paged_prefill.lower(
        eng.params, np.zeros((B, S), i32), np.zeros((B, S), i32),
        eng.kv.pools, np.zeros((B, MB), i32), np.zeros((B, S), i32),
        np.zeros((B, S), i32), np.zeros(B * 4, i32), np.zeros((B, 2), i32),
        np.zeros(B, i32),
    ).as_text(debug_info=True)
    for scope in ("deca.gemm", "model.attention", "model.head", "kv.write"):
        assert scope in prefill, scope


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("rows,scope", [(4, "deca.gemv"), (64, "deca.gemm")])
def test_decompress_gemm_scope_names_the_kernel_not_the_impl(impl, rows,
                                                             scope):
    import jax.numpy as jnp

    from repro.core.compression import compress
    from repro.core.formats import get_spec
    from repro.kernels import ops

    w = np.random.default_rng(0).standard_normal((256, 256)).astype(np.float32)
    ct = compress(w, get_spec("bf8_50"))
    text = jax.jit(
        lambda x: ops.decompress_gemm(x, ct, impl=impl)
    ).lower(jnp.ones((rows, 256), jnp.bfloat16)).as_text(debug_info=True)
    other = "deca.gemm" if scope == "deca.gemv" else "deca.gemv"
    assert scope in text and other not in text
