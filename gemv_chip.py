#!/usr/bin/env python3
"""Time one decode GEMV per weight shape of the benchmark's cells, on a TPU.

    python3 gemv_chip.py               # every shape: ref and fused
    python3 gemv_chip.py --tiny        # small shapes on any backend (rehearsal)

For each shape, at M = 4 rows (the cells' decode slots), two
implementations of `x @ W` with W compressed:

- `ref`: `ops.decompress_gemm(impl="ref")`, the jnp oracle's N-tiled GEMV
  (`kernels/ref.decompress_gemv`) that XLA compiles;
- `fused`: `decompress_gemv_pallas` at `autotune.pick_gemv_blocks`'s
  geometry, the kernel `ops.decompress_gemm` runs on a TPU at M <= 32.

Weights are the program's own `compress` of a seeded dense (K, 512) slab,
tiled across N on the device, so the planes are real codes. Each time is
the mean of `--reps` calls chained inside one jitted loop (each call's
input depends on the last call's output), after one warm-up. `roof_pct`
is the compressed planes' bytes at the v5e's 819 GB/s over that time.
`err` is the widest gap to `ref` over the widest `ref` output. One JSON
line per measurement; the last line is a summary. Exits 2 without a TPU
unless `--tiny`.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.compression import CompressedTensor, compress  # noqa: E402
from repro.core.formats import get_spec  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.autotune import pick_gemv_blocks  # noqa: E402
from repro.kernels.deca_gemm import decompress_gemv_pallas  # noqa: E402

HBM_BYTES_S = 819e9  # TPU v5e HBM bandwidth (Google Cloud, "TPU v5e")
M = 4
SLAB = 512
# (format, K, N): OPT-66B's 9216 x 9216 (q, k, v, o), 9216 x 36864 (fc1),
# 36864 x 9216 (fc2); Granite-3.0-8B's 4096 x 4096 (q, o), 4096 x 1024
# (k, v), 4096 x 12800 (gate, up), 12800 x 4096 (down)
SHAPES = (
    ("mxfp4_100", 9216, 9216), ("mxfp4_100", 9216, 36864),
    ("mxfp4_100", 36864, 9216), ("bf8_50", 4096, 4096),
    ("bf8_50", 4096, 1024), ("bf8_50", 4096, 12800), ("bf8_50", 12800, 4096),
)
TINY = (("mxfp4_100", 512, 1024), ("bf8_50", 512, 1024))


def weight(fmt: str, k: int, n: int, seed: int) -> CompressedTensor:
    """`compress` of one seeded (k, SLAB) slab, tiled n / SLAB times."""
    w = np.random.default_rng(seed).standard_normal((k, SLAB)) / np.sqrt(k)
    ct = compress(w.astype(np.float32), get_spec(fmt))
    reps = -(-n // SLAB)

    def tile(a):
        if a is None:
            return None
        return jnp.tile(jnp.asarray(a), (1,) * (a.ndim - 1) + (reps,))[..., :n]

    return CompressedTensor(
        codes=tile(ct.codes), mask=tile(ct.mask), scales=tile(ct.scales),
        spec=ct.spec, shape=(k, n),
    )


def timed(fn, x, ct, reps: int):
    """(seconds a call, output): `reps` calls chained in one jitted loop."""

    @jax.jit
    def loop(x, ct):
        def body(_, carry):
            x, _ = carry
            y = fn(x, ct)
            # the next input depends on this output, so no call is skipped
            return x + y[:, :1].astype(x.dtype) * 0, y

        y0 = jnp.zeros((x.shape[0], ct.shape[1]), jnp.float32)
        return jax.lax.fori_loop(0, reps, body, (x, y0))[1]

    out = loop(x, ct).block_until_ready()
    t0 = time.perf_counter()
    loop(x, ct).block_until_ready()
    return (time.perf_counter() - t0) / reps, np.asarray(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind,
              "count": jax.device_count()}
    print(json.dumps({"device": device}), flush=True)
    if dev.platform != "tpu" and not args.tiny:
        print("no TPU: nothing measured", file=sys.stderr)
        return 2
    shapes = TINY if args.tiny else SHAPES
    summary = {}
    for i, (fmt, k, n) in enumerate(shapes):
        spec = get_spec(fmt)
        ct = weight(fmt, k, n, args.seed + i)
        x = jax.random.normal(jax.random.PRNGKey(args.seed + i), (M, k), jnp.bfloat16)
        bn, bk, sg, sub_n = pick_gemv_blocks(M, n, k, spec)
        impls = {
            "ref": (lambda x, ct: ops.decompress_gemm(x, ct, impl="ref"), {}),
            "fused": (
                decompress_gemv_pallas,
                dict(block_n=bn, block_k=bk, sub_groups=sg, sub_n=sub_n),
            ),
        }
        want = None
        for name, (fn, geom) in impls.items():
            t, out = timed(fn, x, ct, args.reps)
            if want is None:
                want = out
            err = float(np.abs(out - want).max() / np.abs(want).max())
            rec = {
                "shape": [fmt, k, n], "impl": name, "geometry": geom,
                "ms": round(t * 1e3, 4),
                "roof_pct": round(100 * spec.bytes_for(k, n) / HBM_BYTES_S / t, 3),
                "err": err,
            }
            print(json.dumps(rec), flush=True)
            summary[f"{fmt}.{k}x{n}.{name}_ms"] = rec["ms"]
    print(json.dumps({"ok": True, "device": device, "ms": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
