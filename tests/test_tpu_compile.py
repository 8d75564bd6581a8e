"""Compile the DECA Pallas kernels for a described TPU v5e at OPT-66B widths.

Interpret mode accepts programs that Mosaic refuses (8-bit shifts, 3-D
gathers, prefix scans, unaligned blocks), so every interpret-mode test can
pass while no kernel builds for the chip. These tests lower and compile
each kernel for one chip of a described `v5e:2x2` topology, which the TPU
compiler does without an attached device: a refusal fails here, not on
the chip. Nothing runs, so results are checked elsewhere (test_kernels.py,
test_paged_attention.py).

Shapes are `ShapeDtypeStruct`s whose code-plane geometry comes from a
narrow `compress`, so nothing is compressed at full width.
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.codecs import kv_codec_names
from repro.core.compression import CompressedTensor, compress
from repro.core.formats import get_spec
from repro.kernels.deca_gemm import decompress_gemm_pallas, decompress_gemv_pallas
from repro.kernels.paged_attention import paged_attention_pallas

# opt-66b (configs/opt_66b.py): d_model 9216, d_ff 36864, 72 MHA heads of
# 128, vocab 50272 (not a lane multiple: the lm_head grids N raggedly)
D_MODEL, D_FF, HEADS, D_HEAD, VOCAB = 9216, 36864, 72, 128, 50272
FORMATS = (
    "mxfp4_100", "bf8_100", "bf8_50", "int4_25", "nf4_100", "bf16_100",
    "int8_100",
)
GEMV_M, GEMM_M = 4, 512  # decode slots; a prefill batch of prompt tokens
KN = {
    "up": (D_MODEL, D_FF), "down": (D_FF, D_MODEL), "sq": (D_MODEL, D_MODEL),
    "vocab": (D_MODEL, VOCAB),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the described topology, with the persistent compilation
    cache off: an entry written for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _ct_struct(fmt, k, n, sharding, lead=()):
    """A (k, n) CompressedTensor of ShapeDtypeStructs, stacked under `lead`
    dims; the per-group plane widths and dtypes come from compressing one
    narrow (group, 128) slab."""
    spec = get_spec(fmt)
    probe = compress(
        np.random.default_rng(0).standard_normal((spec.group, 128)), spec
    )
    ng = k // spec.group

    def plane(a, *mid):
        if a is None:
            return None
        return _struct((*lead, ng, *mid, n), a.dtype, sharding)

    return CompressedTensor(
        codes=plane(probe.codes, probe.codes.shape[1]),
        mask=plane(probe.mask),
        scales=plane(probe.scales),
        spec=spec,
        shape=(k, n),
    )


def _assert_mosaic(lowered):
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("which", ["up", "down"])
def test_gemv_compiles_for_v5e(one_chip, fmt, which):
    k, n = KN[which]
    x = _struct((GEMV_M, k), jnp.bfloat16, one_chip)
    ct = _ct_struct(fmt, k, n, one_chip)
    _assert_mosaic(
        jax.jit(
            lambda x, ct: decompress_gemv_pallas(x, ct, interpret=False)
        ).lower(x, ct)
    )


@pytest.mark.parametrize("fmt", FORMATS)
def test_gemm_compiles_for_v5e(one_chip, fmt):
    k, n = KN["sq"]
    x = _struct((GEMM_M, k), jnp.bfloat16, one_chip)
    ct = _ct_struct(fmt, k, n, one_chip)
    _assert_mosaic(
        jax.jit(
            lambda x, ct: decompress_gemm_pallas(x, ct, interpret=False)
        ).lower(x, ct)
    )


@pytest.mark.parametrize("quant", ("none",) + kv_codec_names())
def test_paged_attention_compiles_for_v5e(one_chip, quant):
    from repro.core.codecs import get_codec

    slots, bs, max_blocks, num_blocks = 4, 32, 20, 81
    pools = {
        "ppos": _struct((num_blocks, bs), jnp.int32, one_chip),
    }
    if quant == "none":
        kv = _struct((num_blocks, bs, HEADS, D_HEAD), jnp.bfloat16, one_chip)
    else:
        codec = get_codec(quant)
        kv = _struct(
            (num_blocks, bs, HEADS, codec.kv_code_width(D_HEAD)),
            codec.kv_dtype, one_chip,
        )
        if codec.has_scale:
            pools["ks"] = pools["vs"] = _struct(
                (num_blocks, bs, HEADS), jnp.bfloat16, one_chip
            )
    pools["kp"] = pools["vp"] = kv
    q = _struct((slots, HEADS, D_HEAD), jnp.bfloat16, one_chip)
    tables = _struct((slots, max_blocks), jnp.int32, one_chip)
    lens = _struct((slots,), jnp.int32, one_chip)
    _assert_mosaic(
        jax.jit(
            lambda q, pools, t, ln, qp: paged_attention_pallas(
                q, pools, t, ln, qp, quant=quant, interpret=False
            )
        ).lower(q, pools, tables, lens, lens)
    )


@pytest.mark.parametrize("m", [GEMV_M, GEMM_M])
def test_ragged_vocab_compiles_for_v5e(one_chip, m):
    """The lm_head's N=50272 has no lane-aligned divisor: the kernels take
    lane-aligned blocks with a ragged last one."""
    k, n = KN["vocab"]
    kernel = decompress_gemv_pallas if m == GEMV_M else decompress_gemm_pallas
    x = _struct((m, k), jnp.float32, one_chip)
    ct = _ct_struct("mxfp4_100", k, n, one_chip)
    _assert_mosaic(
        jax.jit(lambda x, ct: kernel(x, ct, interpret=False)).lower(x, ct)
    )


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_serving_steps_compile_for_v5e(one_chip, monkeypatch, impl):
    """The engine's paged prefill and decode-chunk steps for opt-66b at
    published widths (2 layers, mxfp4_100 weights, 4 slots of 512-token
    prompts), compiled whole for one chip with either kernel set."""
    import dataclasses

    from repro.configs.opt_66b import CONFIG
    from repro.core.decompress import _eligible, use_impl
    from repro.models.model import Model
    from repro.serve.engine import (
        make_paged_decode_chunk_step,
        make_paged_prefill_step,
    )

    # the kernels' compile mode follows the CPU backend here; steer it
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    model = Model(dataclasses.replace(CONFIG, n_layers=2))
    fmt = "mxfp4_100"

    def leaf(path, a):
        name = "/".join(getattr(p, "key", str(p)) for p in path)
        if not _eligible(name, a, get_spec(fmt)):
            return _struct(a.shape, a.dtype, one_chip)
        *lead, k, n = a.shape
        return _ct_struct(fmt, k, n, one_chip, tuple(lead))

    params = jax.tree_util.tree_map_with_path(
        leaf, jax.eval_shape(model.init, jax.random.PRNGKey(0))
    )
    slots, bs, sp, chunk = 4, 32, 512, 8
    mb = (sp + 32) // bs + 1
    cache = jax.tree.map(
        lambda a: _struct(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: model.init_paged_cache(slots * mb, bs)),
    )
    i32 = lambda *shape: _struct(shape, jnp.int32, one_chip)
    u32 = lambda *shape: _struct(shape, jnp.uint32, one_chip)
    fresh = slots * ((chunk + bs - 1) // bs + 1)
    with use_impl(impl):
        prefill = jax.jit(make_paged_prefill_step(model)).lower(
            params, i32(slots, sp), i32(slots, sp), cache, i32(slots, mb),
            i32(slots, sp), i32(slots, sp), i32(slots * sp // bs),
            i32(slots, 2), i32(slots),
        )
        decode = make_paged_decode_chunk_step(model).lower(
            params, cache, i32(slots, 1), i32(slots, mb),
            i32(chunk, slots, 1), i32(chunk, slots, 1), i32(chunk, slots, 1),
            i32(chunk, fresh), i32(chunk, slots), u32(slots), u32(slots),
            i32(slots), i32(slots), _struct((slots,), jnp.bool_, one_chip),
            _struct((), jnp.float32, one_chip), u32(2), greedy=True,
        )
        hlos = [prefill.compile().as_text(), decode.compile().as_text()]
    for hlo in hlos:
        assert ("tpu_custom_call" in hlo) == (impl == "pallas")
