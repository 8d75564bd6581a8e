#!/usr/bin/env python3
"""Serving benchmark of compressed-weight LLM serving on one TPU host.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process runs one cell of BENCHMARK.json once:

1. checks the device: a TPU whose `device_kind` is in `peaks.py`, with as
   many chips as the cell asks for; anything else exits 2 with no result;
2. builds the served weights on the device from the seed (`weights.py`);
   a cell of several chips serves on a (data=1, model=chips) mesh, each
   chip building its own share of the weights in place;
3. warms up every program shape the cell's traffic can reach, through the
   serving API itself, with JAX's persistent compilation cache kept at
   `<checkout>/.jax_cache`;
4. runs the timed window: a closed loop of clients driving
   `GenerationEngine.submit` / `Scheduler.step`, each token stamped by the
   host clock when `step()` returns with it visible; with `--trace 1` the
   last seconds of the window are traced by the profiler;
5. frees the program's state and compares a seeded sample of the served
   requests with the plain float32 reference (`reference.py`, or the
   file under `bench/` that the configuration names as "reference");
6. prints one JSON line: `correct`, `attempted`, `failed`, `metrics`,
   `device` (and `breakdown` when traced), then `checks`, the numbers
   compared with their limits, which also end standard error.

Everything a cell needs is found by name: the configuration in
`configs/<config>.json` (and its reference, the module it names),
the traffic mix in `traffic/<traffic>.json`,
each metric's reader in `metrics/<metric>.py` (a metric kept apart per cell,
`<metric>.<cell kind>`, reads with `metrics/<metric>.py` where it has no file
of its own).
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

import traffic  # noqa: E402

TRACE_SECONDS = 5.0      # the traced slice: the last seconds of the window
REF_MIN_TOKENS = 1024    # served tokens the reference checks, at least
REF_SCORE_BYTES = 2e9    # bound on one reference batch's attention scores


def process_age() -> float:
    """Seconds since this process started, from /proc."""
    ticks = os.sysconf("SC_CLK_TCK")
    start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
    uptime = float(Path("/proc/uptime").read_text().split()[0])
    return uptime - start / ticks


def info(msg: str) -> None:
    print(f"# {msg}", flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str) -> dict:
    """The cell, its configuration, its traffic mix and its metrics."""
    manifest = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cell["cfg"] = load_json(ROOT / conf["file"])
    cell["mix"] = traffic.load(cell["traffic"])

    def applies(metric):
        return name in metric.get("workloads", [name])

    cell["end_to_end"] = [m for m in manifest["end_to_end"] if applies(m)]
    cell["per_layer"] = [m for m in manifest["per_layer"] if applies(m)]
    return cell


def check_device(chips: int):
    """(identity, peaks) of the accelerator; raises RuntimeError if there
    is no TPU, too few chips, or no published peaks for it."""
    import jax

    import peaks

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {dev.platform}")
    if len(devices) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX found {len(devices)}")
    try:
        peak = peaks.peaks_for(dev.device_kind)
    except KeyError as e:
        raise RuntimeError(str(e)) from None
    identity = {"platform": dev.platform, "kind": dev.device_kind,
                "count": len(devices)}
    return identity, peak


def use_cache() -> None:
    """JAX's persistent compilation cache at its one fixed place in the
    checkout, every program kept, nothing evicted (an environment that sets
    a size limit would otherwise evict programs this run needs again)."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{metric.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts the traces and the backend compiles (loads from the persistent
    cache included) that JAX reports through `jax.monitoring`."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.traces = self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.TRACE:
            self.traces += 1
        elif event == self.COMPILE:
            self.compiles += 1

    def snapshot(self):
        return self.traces, self.compiles


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def engine_settings(cell: dict) -> dict:
    cfg, mix = cell["cfg"], cell["mix"]
    eng = cfg["engine"]
    bs = eng["block_size"]
    max_len = -(-traffic.max_tokens(mix) // bs) * bs
    slots = eng["max_slots"]
    return {
        "block_size": bs, "max_slots": slots, "max_len": max_len,
        "num_blocks": slots * (max_len // bs),
        "decode_chunk": eng["decode_chunk"],
    }


def cell_mesh(chips: int):
    """The (data=1, model=chips) mesh over the first `chips` devices, the
    layout `chip_smoke.py --chips 4` serves on; None for one chip."""
    if chips == 1:
        return None
    import jax

    from repro.launch.mesh import make_mesh

    return make_mesh((1, chips), ("data", "model"),
                     devices=jax.devices()[:chips])


def build_engine(cell: dict, seed: int):
    import jax

    from repro.configs.base import ModelConfig
    from repro.models.model import Model
    from repro.serve.engine import GenerationEngine

    import weights

    cfg = cell["cfg"]
    model = Model(ModelConfig(name=cfg["name"], **cfg["model"]))
    mesh = cell_mesh(cell["chips"])
    t0 = time.perf_counter()
    params, host_s = weights.build_params(model, cfg["weights"], seed, mesh)
    jax.block_until_ready(params)
    built_s = time.perf_counter() - t0
    es = engine_settings(cell)
    eng = GenerationEngine(
        model, params, max_len=es["max_len"], temperature=0.0,
        block_size=es["block_size"], max_slots=es["max_slots"],
        num_blocks=es["num_blocks"], decode_chunk=es["decode_chunk"],
        mesh=mesh,
    )
    info(f"weights {cfg['weights']} built in {built_s:.2f} s "
         f"({host_s:.2f} s compressing slabs on the host); engine {es}")
    return eng


def pow2ceil(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def warm_plan(cell: dict) -> dict:
    """The shapes the traffic can reach: prefill batches (pow2 rows, exact
    pages) and decode chunk lengths."""
    es, mix = engine_settings(cell), cell["mix"]
    bs = es["block_size"]
    rows = min(traffic.clients(mix, es["max_slots"]), es["max_slots"])
    batches = sorted({min(pow2ceil(n), es["max_slots"]) for n in range(1, rows + 1)})
    p_lo = -(-mix["prompt_tokens"]["min"] // bs)
    p_hi = -(-mix["prompt_tokens"]["max"] // bs)
    chunk = es["decode_chunk"]
    chunks = sorted({min(chunk, pow2ceil(r)) for r in range(1, chunk + 1)})
    return {"batches": batches, "pages": list(range(p_lo, p_hi + 1)),
            "chunks": chunks}


def warm_up(eng, cell: dict) -> int:
    """Serve requests shaped to reach every program of the plan once.
    Returns the number of prefill/decode shapes driven."""
    import numpy as np

    plan = warm_plan(cell)
    es = engine_settings(cell)
    bs, sched = es["block_size"], eng.scheduler
    rng = np.random.default_rng(0)
    vocab = cell["cfg"]["model"]["vocab_size"]
    shapes = 0
    for b in plan["batches"]:
        for p in plan["pages"]:
            plen = min(p * bs, es["max_len"] - 1)
            for _ in range(b):
                eng.submit(rng.integers(0, vocab, plen, dtype=np.int32),
                           max_new_tokens=1)
            sched.step()
            if sched.queue or any(s is not None for s in sched.slots):
                raise RuntimeError(f"warm-up batch {b}x{p} pages did not drain")
            shapes += 1
    plen = plan["pages"][0] * bs
    for c in plan["chunks"]:
        eng.submit(rng.integers(0, vocab, plen, dtype=np.int32),
                   max_new_tokens=c + 1)
        sched.run_until_drained()
        shapes += 1
    sched.results.clear()
    return shapes


# ---------------------------------------------------------------------------
# the timed window
# ---------------------------------------------------------------------------

class Window:
    """Closed-loop clients and the client-side record of every request."""

    def __init__(self, eng, cell: dict, seed: int):
        es = engine_settings(cell)
        self.eng, self.sched = eng, eng.scheduler
        self.requests = traffic.Requests(
            cell["mix"], seed, cell["cfg"]["model"]["vocab_size"]
        )
        self.clients = traffic.clients(cell["mix"], es["max_slots"])
        self.records: list = []
        self.inflight: dict = {}
        self.steps: list = []  # per step(): (t0, t1, traced, [(rid, n0, n1)])

    def send(self, client: int, t: float) -> None:
        import jax

        prompt, out = next(self.requests)
        with jax.profiler.TraceAnnotation("bench.submit"):
            rid = self.eng.submit(prompt, max_new_tokens=out)
        rec = {"rid": rid, "client": client, "prompt": prompt,
               "max_new": out, "send_t": t, "stamps": [], "count": 0,
               "first_t": None, "done_t": None, "out": None, "partial": None}
        self.records.append(rec)
        self.inflight[rid] = rec

    def run(self, seconds: float, trace_dir=None) -> None:
        import jax
        import numpy as np

        sched = self.sched
        self.t0 = time.perf_counter()
        end = self.t0 + seconds
        trace_from = end - min(TRACE_SECONDS, seconds)
        tracing = None
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # TraceAnnotation spans stay
        for c in range(self.clients):
            self.send(c, self.t0)
        while True:
            if trace_dir is not None and tracing is None and \
                    time.perf_counter() >= trace_from:
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing = jax.profiler.TraceAnnotation("bench.traced")
                tracing.__enter__()
            s0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                sched.step()
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.bookkeeping"):
                grown = self._observe(t, end)
            self.steps.append((s0, t, tracing is not None, grown))
            if t >= end:
                break
        self.t1 = t
        for r in sched.slots:  # what in-flight requests had served by the end
            if r is not None and r.rid in self.inflight:
                rec = self.inflight[r.rid]
                rec["partial"] = np.asarray(r.out[:rec["count"]], np.int32)
        if tracing is not None:
            tracing.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def _observe(self, t: float, end: float) -> list:
        sched = self.sched
        visible = {r.rid: r for r in sched.slots if r is not None}
        grown = []
        for rid, rec in list(self.inflight.items()):
            done = rid in sched.results
            if done:
                out = sched.results.pop(rid)
                n = len(out)
            else:
                r = visible.get(rid)
                n = len(r.out) if r is not None else 0
            if n > rec["count"]:
                grown.append((rid, rec["count"], n))
                rec["stamps"].append((t, n))
                if rec["first_t"] is None:
                    rec["first_t"] = t
                rec["count"] = n
            if done:
                rec["done_t"], rec["out"] = t, out
                status = sched.statuses.get(rid)
                rec["status"] = getattr(status, "value", "missing")
                del self.inflight[rid]
                if t < end:
                    self.send(rec["client"], t)
        return grown


# ---------------------------------------------------------------------------
# readings: what the metric readers see
# ---------------------------------------------------------------------------

class Readings:
    """Everything a metric reader may read, from one run. `peak` is the
    deployment's: each rate of one chip's (`peaks.peaks_for`) times the
    cell's chips, while the trace's times are the mean over the chips, so
    a share reads the least time of the whole deployment over the time
    each chip took, and work replicated over the chips counts as waste."""

    def __init__(self, cell, window, setup_s, stats_delta, trace, peak):
        import peaks

        self.cell, self.model = cell, cell["cfg"]["model"]
        self.weights = cell["cfg"]["weights"]
        self.settings = engine_settings(cell)
        self.window = window
        self.setup_s = setup_s
        self.stats = stats_delta
        self.trace = trace       # trace.reduce(...) or None
        self.peak = peaks.deployment(peak, cell["chips"])
        self.elapsed = window.t1 - window.t0
        self._traced = None

    def traced_work(self) -> dict:
        """Decode and prefill work of the traced steps whose device events
        the trace kept in full: live decode steps, decoded tokens and the KV
        they read, and the prompt lengths prefilled (a request's first token
        is visible at the end of the step that prefilled it). Empty if the
        trace's steps cannot be paired with the window's."""
        if self._traced is None:
            prompts = {r["rid"]: len(r["prompt"]) for r in self.window.records}
            work = {"decode_steps": 0, "decode_tokens": 0, "kv_sum": 0,
                    "prefill_prompts": []}
            traced = [grown for _, _, on, grown in self.window.steps if on]
            marks = self.trace["steps"] if self.trace else []
            if len(marks) == len(traced):
                for mark, grown in zip(marks, traced):
                    if mark["covered"]:
                        self._count(work, grown, prompts)
            self._traced = work
        return self._traced

    @staticmethod
    def _count(work, grown, prompts):
        most = 0
        for rid, n0, n1 in grown:
            p = prompts[rid]
            if n0 == 0:
                work["prefill_prompts"].append(p)
            first = max(n0, 1)
            k = n1 - first
            most = max(most, k)
            work["decode_tokens"] += k
            # decoding output token j reads a context of p + j tokens
            work["kv_sum"] += k * p + (first + n1 - 1) * k // 2
        work["decode_steps"] += most


def memory_peak() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def served(rec):
    """The tokens a request was served in the window: all of them if it
    finished, those visible at the window's end if it was still running."""
    return rec["out"] if rec["out"] is not None else rec["partial"]


def reference_sample(window: Window, seed: int) -> list:
    """The requests the reference checks: the one with the most served
    tokens, then others drawn from the seed until REF_MIN_TOKENS. Finished
    requests come first; requests still running at the window's end count
    with the tokens they had been served, where the finished ones fall
    short (a decode-bound window may finish none)."""
    import numpy as np

    done = [r for r in window.records if r["out"] is not None and len(r["out"])]
    running = [r for r in window.records
               if r["out"] is None and r["partial"] is not None and len(r["partial"])]
    pick, tokens = [], 0
    rng = np.random.default_rng([seed, 11])
    for pool in (done, running):
        if not pool or tokens >= REF_MIN_TOKENS:
            continue
        pool = sorted(pool, key=lambda r: (-len(served(r)), r["rid"]))
        order = [0] + [1 + i for i in rng.permutation(len(pool) - 1)]
        for i in order:
            if tokens >= REF_MIN_TOKENS:
                break
            pick.append(pool[i])
            tokens += len(served(pool[i]))
    return pick


def reference_rows(m: dict, pad_to: int, requests: int) -> int:
    """Requests the reference runs together: its attention scores bounded by
    REF_SCORE_BYTES."""
    per_row = m["n_heads"] * pad_to * pad_to * 4
    return max(1, min(requests, int(REF_SCORE_BYTES // per_row)))


def gap_numbers(gaps) -> dict:
    """The numbers compared, from the per-position gaps of every checked
    request: the widest gap, and the mean gap over all served positions."""
    import numpy as np

    g = np.concatenate([np.asarray(x, np.float64) for x in gaps])
    return {"logit_gap": float(g.max()), "logit_gap_mean": float(g.mean())}


_REFERENCES: dict = {}


def reference_module(name: str):
    """The plain reference `bench/<name>.py` that a configuration names as
    its "reference" (default `reference`). Only a file beside this one can
    be named, so no module of the program can stand in for it."""
    path = BENCH / f"{name}.py"
    if not (name.isidentifier() and path.is_file()):
        raise ValueError(f"no reference module {name!r} under {BENCH}")
    if name not in _REFERENCES:
        spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _REFERENCES[name] = mod
    return _REFERENCES[name]


def check(cell: dict, window: Window, seed: int, control: bool = False) -> dict:
    """The gaps of served tokens' reference logits below the reference's
    best, over a seeded sample of served requests, as `gap_numbers` (with
    `control`, also the fp8 control's numbers on the same positions), from
    the reference the configuration names (`reference_module`)."""
    import numpy as np

    reference = reference_module(cell["cfg"].get("reference", "reference"))
    m, fmt = cell["cfg"]["model"], cell["cfg"]["weights"]
    pad_to = engine_settings(cell)["max_len"]
    sample = reference_sample(window, seed)
    if not sample:
        none = {"logit_gap": math.inf, "logit_gap_mean": math.inf}
        return {"requests": 0, "tokens": 0, "numbers": none, "control": None}
    got = reference.served_gaps(
        m, fmt, seed, [(r["prompt"], np.asarray(served(r))) for r in sample],
        pad_to, reference_rows(m, pad_to, len(sample)), control,
    )
    return {
        "requests": len(sample),
        "tokens": int(sum(len(g) for g in got["served"])),
        "numbers": gap_numbers(got["served"]),
        "control": gap_numbers(got["control"]) if control else None,
    }


def judge(numbers: dict, failed: int, limits: dict):
    """(correct, checks): every number the configuration sets a limit for
    is at or under it, and no request failed."""
    checks = {name: {"value": numbers[name], "limit": limit}
              for name, limit in limits.items()}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             identity: dict, peak: dict) -> dict:
    import jax

    counter = CompileCounter()
    eng = build_engine(cell, seed)
    c0 = counter.snapshot()
    t_warm = time.perf_counter()
    shapes = warm_up(eng, cell)
    c1 = counter.snapshot()
    info(f"warm-up: {shapes} request shapes, {c1[1] - c0[1]} programs "
         f"compiled or loaded, {c1[0] - c0[0]} traces, "
         f"{time.perf_counter() - t_warm:.2f} s; plan {warm_plan(cell)}")
    stats0 = eng.scheduler.stats()
    window = Window(eng, cell, seed)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    setup_s = process_age()
    window.run(seconds, trace_dir)
    c2 = counter.snapshot()
    stats1 = eng.scheduler.stats()
    delta = {k: stats1[k] - stats0[k] for k in stats1
             if isinstance(stats1[k], (int, float))}
    peak_bytes = memory_peak()
    compiles = (c2[0] - c1[0], c2[1] - c1[1])
    mem_limit = (jax.local_devices()[0].memory_stats() or {}).get("bytes_limit")
    done = sum(1 for r in window.records if r["out"] is not None)
    took = sorted((t1 - t0, i) for i, (t0, t1, _, _) in enumerate(window.steps))
    info(f"window: {window.t1 - window.t0:.3f} s, {len(window.steps)} steps "
         f"(median {took[len(took) // 2][0]:.3f} s, slowest {took[-1][0]:.3f} s "
         f"at step {took[-1][1]}), {len(window.records)} requests sent, "
         f"{done} finished, "
         f"traces in window {compiles[0]}, "
         f"compiles in window {compiles[1]}, device memory peak {peak_bytes} "
         f"of {mem_limit} bytes")
    reduced = None
    if trace:
        import devtrace
        import kernels
        from jax.profiler import ProfileData

        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        reduced = devtrace.reduce(ProfileData.from_file(str(files[-1])))
        reduced["kernels"] = kernels.reduce(files[-1], reduced)
        shutil.rmtree(trace_dir, ignore_errors=True)
    # free the program's state before the reference runs
    window.eng = window.sched = None
    del eng
    gc.collect()

    readings = Readings(cell, window, setup_s, delta, reduced, peak)
    metrics_cfg = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for m in metrics_cfg:
        got = reader(m["name"])(readings)
        if isinstance(got, dict):
            info(f"{m['name']}: " + ", ".join(f"{k}={v}" for k, v in got.items()))
            got = got["value"]
        if got is not None:
            metrics[m["name"]] = {"value": got, "unit": m["unit"]}

    t_ref = time.perf_counter()
    result = check(cell, window, seed)
    info(f"reference: {result['requests']} requests, {result['tokens']} served "
         f"tokens, {time.perf_counter() - t_ref:.2f} s; "
         + json.dumps(result["numbers"]))
    sent = len(window.records)
    failed = sum(1 for r in window.records
                 if r["out"] is not None and r["status"] != "ok")
    correct, checks = judge(result["numbers"], failed, cell["cfg"]["limits"])
    device = dict(identity, memory_peak_bytes=peak_bytes)
    line = {"correct": bool(correct), "attempted": sent, "failed": failed,
            "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_ns"] / 1e9
        device["window_s"] = reduced["window_ns"] / 1e9
        import devtrace

        line["breakdown"] = {
            "device_ops": devtrace.top(reduced["op_ns"]),
            "idle_gaps": devtrace.top(reduced["idle_by_host_ns"]),
        }
        info("device time by program: " + json.dumps(
            devtrace.top(reduced["module_ns"])))
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        import jax
    except ImportError as e:
        print(f"bench: JAX is not importable ({e})", file=sys.stderr)
        return 2
    use_cache()
    try:
        identity, peak = check_device(cell["chips"])
    except RuntimeError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    info(f"device {json.dumps(identity)} jax {jax.__version__} "
         f"workload {args.workload} seed {args.seed}")
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    identity, peak)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
