#!/usr/bin/env python3
"""Smoke run of compressed serving on a TPU, at OPT-66B widths.

    python3 chip_smoke.py              # one chip: ref vs Pallas serving,
                                       # then the kernel phase
    python3 chip_smoke.py --chips 4    # four chips: (data=1, model=4) mesh
                                       # serving vs the same on one chip

One chip. `configs/opt_66b.py` at its published widths, cut to 2 layers,
random weights from `--seed`, every FC weight compressed to `mxfp4_100`.
Four prompts of 128-512 tokens each get 32 new tokens through the normal
serving path (`GenerationEngine.submit` / `run_until_drained`, paged KV):
prefill runs the decompress-GEMM, decode the decompress-GEMV. It serves
once under `use_impl("ref")` and once under `use_impl("pallas")` and
compares the prefill logits and the logits of the first decode chunk's
first step. The kernel phase then runs `ops.decompress_gemm` with
`impl="pallas"` against `impl="ref"` for every codec on one 9216 x 9216
weight at M=4 (GEMV) and M=512 (GEMM).

Four chips (`--chips 4`). The same serving, with the default kernels, over
a (data=1, model=4) mesh, compared with the same run on the first chip
alone; it also checks that every compressed plane is split across the
chips.

Everything runs in this one process, which holds the chip. There is no
CPU fallback: the script exits non-zero, printing no result, when JAX
finds no TPU, when any phase raises, or when a comparison is out of its
tolerance. Device identity, timings and errors go on earlier lines; the
last line is `{"ok": true, "device": {...}}`. This is a smoke run, not a
measurement: its wall times include compilation.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_LAYERS = 2
WEIGHT_FORMAT = "mxfp4_100"
PROMPT_LENS = (128, 512)  # inclusive range prompt lengths are drawn from
N_PROMPTS = 4
NEW_TOKENS = 32
BLOCK_SIZE = 32
KERNEL_FORMATS = (
    "mxfp4_100", "bf8_100", "bf8_50", "int4_25", "nf4_100", "bf16_100",
    "int8_100",
)
KERNEL_KN = 9216  # opt-66b d_model: one square attention weight
KERNEL_MS = (4, 512)  # decode slots (GEMV); a prefill batch (GEMM)
# bf16 roundings per layer an activation crosses on its way to the logits:
# q/k/v and o projections, the attention output, MLP up and down
ROUNDINGS_PER_LAYER = 7


def log(msg: str) -> None:
    print(msg, flush=True)


def serving_tolerance(ref_logits: np.ndarray, n_layers: int) -> float:
    """Both runs contract the same decoded bf16 weights with f32
    accumulation; only the summation order differs between XLA's dot, the
    Pallas kernels and a sharded contraction. A different order can move a
    bf16-rounded activation by one ulp (at most 2^-8 relative), once per
    rounding it crosses, so the logits may drift by that many ulps of
    their own scale. A wrong decode or a misplaced block moves them by
    their whole scale."""
    return ROUNDINGS_PER_LAYER * n_layers * 2.0 ** -8 * float(
        np.abs(ref_logits).max()
    )


@dataclasses.dataclass
class Served:
    engine: object
    tokens: dict            # rid -> generated tokens
    prefill_logits: dict    # rid -> (V,) logits its first token came from
    first_chunk: tuple      # (scheduler args, KV pools before the launch)
    seconds: float


def serve(model, params, prompts, impl: str, **engine_kw) -> Served:
    """Serve `prompts` through `GenerationEngine` under kernel `impl`,
    recording the logits each request's first token is sampled from and
    the inputs of the first decode chunk."""
    import jax
    from repro.core.decompress import use_impl
    from repro.serve.engine import GenerationEngine

    with use_impl(impl):
        t0 = time.perf_counter()
        eng = GenerationEngine(
            model, params, max_len=PROMPT_LENS[1] + NEW_TOKENS,
            max_slots=len(prompts), block_size=BLOCK_SIZE, **engine_kw,
        )
        sched = eng.scheduler
        prefill_logits, first_chunk = {}, []
        sample, decode_chunk = sched._sample, sched._decode_chunk

        def record_sample(logits, rids, steps):
            host = np.asarray(jax.device_get(logits), np.float32)
            for row, rid in enumerate(np.asarray(rids)):
                if rid >= 0:
                    prefill_logits.setdefault(int(rid), host[row])
            return sample(logits, rids, steps)

        def record_chunk(*args):
            if not first_chunk:
                first_chunk.append((args, eng.kv.pools))
            return decode_chunk(*args)

        sched._sample, sched._decode_chunk = record_sample, record_chunk
        rids = [eng.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
        done = eng.run_until_drained()
        seconds = time.perf_counter() - t0
    if not first_chunk:
        raise RuntimeError(f"{impl}: no decode chunk ran")
    return Served(
        engine=eng,
        tokens={r: np.asarray(done[r]) for r in rids},
        prefill_logits=prefill_logits,
        first_chunk=first_chunk[0],
        seconds=seconds,
    )


def first_step_logits(served: Served, impl: str, chunk: tuple) -> np.ndarray:
    """Logits of step 0 of a recorded decode chunk, run through `served`'s
    engine decode step (the step the chunk scans) from the recorded KV
    pools, for the chunk's active slots."""
    import jax
    from repro.core.decompress import use_impl

    (tokens0, tables, positions, wslots, wpos, fresh, kv_lens,
     *_, active), pools = chunk
    eng = served.engine
    eng.kv.pools = jax.tree.map(
        lambda a, like: jax.device_put(a, like.sharding), pools, eng.kv.pools
    )
    with use_impl(impl):
        logits = eng._run_paged_decode(
            tokens0, positions[0], tables, wslots[0], wpos[0], fresh[0],
            kv_lens[0],
        )
    return np.asarray(jax.device_get(logits), np.float32)[np.asarray(active)]


def compare_served(name: str, ref: Served, ref_impl: str, other: Served,
                   other_impl: str, n_layers: int) -> bool:
    """Prefill logits of every request, and the first decode step's logits
    from the same recorded state, each within `serving_tolerance`."""
    rids = sorted(ref.prefill_logits)
    a = np.stack([ref.prefill_logits[r] for r in rids])
    b = np.stack([other.prefill_logits[r] for r in rids])
    da = first_step_logits(ref, ref_impl, ref.first_chunk)
    db = first_step_logits(other, other_impl, ref.first_chunk)
    ok = True
    for what, x, y in (("prefill", a, b), ("decode_step", da, db)):
        err = float(np.abs(x - y).max())
        tol = serving_tolerance(x, n_layers)
        good = bool(np.isfinite(y).all()) and err <= tol
        ok &= good
        log(f"compare {name} {what}_logits shape={tuple(x.shape)} "
            f"max_abs_err={err!r} tol={tol!r} "
            f"logit_scale={float(np.abs(x).max())!r} "
            f"{'ok' if good else 'FAIL'}")
    same = sum(
        int(np.array_equal(ref.tokens[r], other.tokens[r])) for r in rids
    )
    first = sum(int(ref.tokens[r][0] == other.tokens[r][0]) for r in rids)
    log(f"compare {name} tokens: {same}/{len(rids)} streams identical, "
        f"{first}/{len(rids)} first tokens identical (greedy; a near-tie "
        "may flip a token within tolerance, so this is not a check)")
    return ok


def build_model(seed: int):
    """opt-66b at published widths, 2 layers, `WEIGHT_FORMAT` weights. The
    dense tree is made on the device and freed once compressed."""
    import jax
    from repro.configs.opt_66b import CONFIG
    from repro.core.decompress import compress_tree, compressed_bytes
    from repro.core.formats import get_spec
    from repro.models.model import Model

    cfg = dataclasses.replace(CONFIG, n_layers=N_LAYERS)
    model = Model(cfg)
    t0 = time.perf_counter()
    dense = model.init(jax.random.PRNGKey(seed))
    jax.block_until_ready(dense)
    t1 = time.perf_counter()
    params = compress_tree(dense, get_spec(WEIGHT_FORMAT))
    del dense
    t2 = time.perf_counter()
    log(f"model {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}x{cfg.d_head} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} weights={WEIGHT_FORMAT} "
        f"stored_bytes={compressed_bytes(params)} init_s={t1 - t0:.1f} "
        f"compress_s={t2 - t1:.1f}")
    return model, params


def make_prompts(seed: int, vocab: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_PROMPTS)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


def phase_serving(seed: int) -> bool:
    import jax

    model, params = build_model(seed)
    params = jax.device_put(params)  # one upload, shared by both engines
    prompts = make_prompts(seed, model.cfg.vocab_size)
    log(f"prompts lens={[len(p) for p in prompts]} new_tokens={NEW_TOKENS}")
    runs = {}
    for impl in ("ref", "pallas"):
        runs[impl] = serve(model, params, prompts, impl)
        st = runs[impl].engine.scheduler.stats()
        log(f"served impl={impl} requests={len(prompts)} "
            f"tokens={sum(len(t) for t in runs[impl].tokens.values())} "
            f"wall_s_incl_compile={runs[impl].seconds:.1f} "
            f"peak_blocks={st['peak_blocks']}")
    return compare_served(
        "ref_vs_pallas", runs["ref"], "ref", runs["pallas"], "pallas",
        model.cfg.n_layers,
    )


def kernel_check(fmt: str, k: int, n: int, ms, seed: int) -> bool:
    """`ops.decompress_gemm` Pallas vs ref on one (k, n) weight. Both
    contract the same decoded bf16 operands with f32 accumulation, so
    they differ only by summation order: each is within k * 2^-24 *
    (|x| @ |W|) of the exact sum (recursive-summation bound), so they are
    within twice that of each other, element by element."""
    import jax
    import jax.numpy as jnp
    from repro.core.compression import compress
    from repro.core.formats import get_spec
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    w = rng.standard_normal((k, n), dtype=np.float32) * 0.02
    t0 = time.perf_counter()
    ct = jax.device_put(compress(w, get_spec(fmt)))
    compress_s = time.perf_counter() - t0
    abs_w = jax.jit(
        lambda c: jnp.abs(ref.decompress(c, out_dtype=jnp.float32))
    )(ct)
    pallas = jax.jit(functools.partial(ops.decompress_gemm, impl="pallas"))
    reference = jax.jit(functools.partial(ops.decompress_gemm, impl="ref"))
    ok = True
    for m in ms:
        x = jax.random.normal(jax.random.PRNGKey(seed + m), (m, k), jnp.bfloat16)
        t0 = time.perf_counter()
        got = jax.block_until_ready(pallas(x, ct))
        pallas_s = time.perf_counter() - t0
        want = reference(x, ct)
        bound = 2 * k * 2.0 ** -24 * jnp.dot(
            jnp.abs(x).astype(jnp.float32), abs_w,
            precision=jax.lax.Precision.HIGHEST,
        )
        err = jnp.abs(got - want)
        finite = bool(jnp.isfinite(got).all())
        worst = float(jnp.max(err / jnp.maximum(bound, 1e-30)))
        good = finite and worst <= 1.0
        ok &= good
        log(f"kernel {fmt} K={k} N={n} M={m} path={'gemv' if m <= ops.GEMV_MAX_M else 'gemm'} "
            f"max_abs_err={float(err.max())!r} err_over_bound={worst!r} "
            f"finite={finite} compress_s={compress_s:.1f} "
            f"pallas_s_incl_compile={pallas_s:.2f} {'ok' if good else 'FAIL'}")
    return ok


def phase_kernels(seed: int) -> bool:
    ok = True
    for fmt in KERNEL_FORMATS:
        ok &= kernel_check(fmt, KERNEL_KN, KERNEL_KN, KERNEL_MS, seed)
    return ok


def phase_mesh(seed: int) -> bool:
    """(data=1, model=4) mesh serving vs the same on the first chip."""
    import jax
    from repro.core.compression import CompressedTensor
    from repro.launch.mesh import make_test_mesh

    model, params = build_model(seed)
    prompts = make_prompts(seed, model.cfg.vocab_size)
    log(f"prompts lens={[len(p) for p in prompts]} new_tokens={NEW_TOKENS}")
    one = serve(model, params, prompts, "ref")
    log(f"served one_chip device={jax.devices()[0]} "
        f"wall_s_incl_compile={one.seconds:.1f}")
    mesh = make_test_mesh(data=1, model=4)
    four = serve(model, params, prompts, "ref", mesh=mesh)
    log(f"served mesh={dict(mesh.shape)} wall_s_incl_compile={four.seconds:.1f}")

    planes = [
        p for ct in jax.tree_util.tree_leaves(
            four.engine.params, is_leaf=lambda x: isinstance(x, CompressedTensor)
        ) if isinstance(ct, CompressedTensor)
        for p in (ct.codes, ct.mask, ct.scales) if p is not None
    ]
    total = sum(p.nbytes for p in planes)
    on_dev0 = sum(p.addressable_shards[0].data.nbytes for p in planes)
    split = sum(
        p.addressable_shards[0].data.size * mesh.size == p.size for p in planes
    )
    sharded = split == len(planes)
    log(f"mesh planes={len(planes)} split_4_ways={split} bytes_total={total} "
        f"bytes_on_device0={on_dev0} {'ok' if sharded else 'FAIL'}")
    return sharded & compare_served(
        "one_chip_vs_mesh", one, "ref", four, "ref", model.cfg.n_layers
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.kernels.mode import resolve_interpret
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: this repo's src/ is not beside the script ({e})",
              file=sys.stderr)
        return 2
    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    if resolve_interpret():
        print("chip_smoke: Pallas would run interpreted on the chip "
              "(REPRO_PALLAS_INTERPRET is set)", file=sys.stderr)
        return 2
    identity = {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }
    log(f"device {json.dumps(identity)} jax={jax.__version__} "
        f"compile_cache={cache}")

    phases = (
        [("serving", phase_serving), ("kernels", phase_kernels)]
        if args.chips == 1 else [("mesh_serving", phase_mesh)]
    )
    ok = True
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            good = phase(args.seed)
        except Exception:
            traceback.print_exc()
            good = False
        gc.collect()  # engines hold reference cycles; free their HBM now
        ok &= good
        log(f"phase {name} {'ok' if good else 'FAILED'} "
            f"seconds={time.perf_counter() - t0:.1f}")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": identity}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
