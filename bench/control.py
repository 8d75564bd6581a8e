#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds <s> \
        [--control 3]

One process, one engine: for each seed the served weights are rebuilt from
that seed and swapped into the engine, a window of the cell's own traffic
runs for `--seconds`, and the reference checks a seeded sample of the
served requests exactly as `run.py` does. That gives the program's
readings (`run.gap_numbers`: the widest and the mean gap of a served token
below the reference's best). For the first `--control` seeds the fp8
control is read too, at the same positions: the gaps of the tokens the
control puts first, put in the program's place. Both go through
`run.judge` under the configuration's limits, so each line says whether
the program and the control come out correct. The benchmark's own runs
never run the control. Prints one line per seed and a JSON summary last.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run


def drop_running(sched) -> None:
    """End every request still queued or running, releasing its pages, so
    the next seed starts from an empty engine."""
    sched.queue.clear()
    for i, r in enumerate(sched.slots):
        if r is not None:
            sched.cache.release(r.rid)
            sched.slots[i] = None
    sched.results.clear()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.use_cache()
    try:
        identity, _ = run.check_device(cell["chips"])
    except RuntimeError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    import weights

    seeds = [int(s) for s in args.seeds.split(",")]
    eng = run.build_engine(cell, seeds[0])
    run.warm_up(eng, cell)
    rows = []
    for i, seed in enumerate(seeds):
        if i:
            eng.params = None
            gc.collect()
            eng.params, _ = weights.build_params(
                eng.model, cell["cfg"]["weights"], seed, eng.mesh)
        window = run.Window(eng, cell, seed)
        window.run(args.seconds)
        drop_running(eng.scheduler)
        params, eng.params = eng.params, None
        del params
        gc.collect()
        t0 = time.perf_counter()
        got = run.check(cell, window, seed, control=i < args.control)
        limits = cell["cfg"]["limits"]
        got.update(seed=seed, ref_s=time.perf_counter() - t0,
                   correct=run.judge(got["numbers"], 0, limits)[0])
        if got["control"] is not None:
            got["control_correct"] = run.judge(got["control"], 0, limits)[0]
        rows.append(got)
        print(json.dumps(got), flush=True)
    names = rows[0]["numbers"]
    ctrl = [r["control"] for r in rows if r["control"] is not None]
    print(json.dumps({
        "workload": args.workload, "device": identity,
        "program_max": {k: max(r["numbers"][k] for r in rows) for k in names},
        "control_min": {k: min(c[k] for c in ctrl) for k in names} if ctrl else None,
        "program": [r["numbers"] for r in rows], "control": ctrl,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
