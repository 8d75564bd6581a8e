"""Per-kernel validation: Pallas (interpret=True) vs the ref.py jnp oracle,
swept over shapes, dtypes/formats, and block sizes."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.compression import compress
from repro.core.formats import get_spec
from repro.kernels import ref
from repro.kernels.deca_decompress import decompress_pallas
from repro.kernels.deca_gemm import decompress_gemm_pallas

FORMATS = [
    "bf16_100", "bf16_50", "bf16_10",
    "bf8_100", "bf8_50", "bf8_20", "bf8_5",
    "mxfp4_100", "mxfp4_50", "int8_50", "int4_25",
    "nf4_100", "nf4_50",  # registry-only codec: zero kernel changes
]
SHAPES = [(32, 8), (64, 128), (128, 96), (256, 256), (512, 64)]


def _compress(k, n, name, seed=0):
    w = np.random.default_rng(seed).standard_normal((k, n)).astype(np.float32)
    return w, compress(w, get_spec(name))


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("kn", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_decompress_kernel_matches_oracle(fmt, kn):
    k, n = kn
    _, ct = _compress(k, n, fmt)
    want = ref.decompress(ct, out_dtype=jnp.float32)
    got = decompress_pallas(ct, out_dtype=jnp.float32, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("m", [1, 4, 16, 40])
def test_fused_gemm_matches_oracle(fmt, m):
    k, n = 128, 96
    _, ct = _compress(k, n, fmt, seed=7)
    x = np.random.default_rng(8).standard_normal((m, k)).astype(np.float32)
    want = ref.decompress_gemm(jnp.asarray(x), ct)
    got = decompress_gemm_pallas(jnp.asarray(x), ct, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize(
    "blocks", [(32, 32, 32), (64, 64, 64), (128, 96, 256), (16, 48, 64)]
)
def test_gemm_block_shape_sweep(blocks):
    """Any block tiling must give identical results (accumulation order may
    differ -> small f32 tolerance)."""
    bm, bn, bk = blocks
    k, n, m = 256, 96, 32
    _, ct = _compress(k, n, "bf8_50", seed=11)
    x = np.random.default_rng(12).standard_normal((m, k)).astype(np.float32)
    want = ref.decompress_gemm(jnp.asarray(x), ct)
    got = decompress_gemm_pallas(
        jnp.asarray(x), ct, block_m=bm, block_n=bn, block_k=bk, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("fmt", ["mxfp4_100", "bf8_50", "int4_25"])
@pytest.mark.parametrize("m", [4, 40])
def test_ragged_n_matches_oracle(fmt, m):
    """N with no lane-aligned divisor (like a 50272 vocab) runs lane-aligned
    blocks with a ragged last one; its out-of-range columns are dropped."""
    from repro.kernels import ops

    k, n = 64, 300
    _, ct = _compress(k, n, fmt, seed=5)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((m, k)), jnp.float32)
    want = ref.decompress_gemm(x, ct)
    got = ops.decompress_gemm(x, ct, impl="pallas")
    assert got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_decompress_output_dtype():
    _, ct = _compress(64, 32, "bf8_100")
    out = decompress_pallas(ct, out_dtype=jnp.bfloat16, interpret=True)
    assert out.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# block geometry: divisor selection + roofline autotune (DESIGN.md §12)
# ---------------------------------------------------------------------------

def test_select_block_picks_largest_divisor():
    from repro.kernels.autotune import select_block

    assert select_block(256, 200, multiple=128) == 128
    assert select_block(96, 256) == 96          # clamped to the dim
    assert select_block(224, 112) == 112
    assert select_block(32, 16, multiple=32) == 16  # falls back: no aligned div
    # odd dims: the old decrement loop silently produced tiny blocks; the
    # divisor selection is exact and O(sqrt n)
    assert select_block(97, 64) == 1
    # a minimum (block_k's group clamp) lifts an undersized target
    assert select_block(256, 16, multiple=32, minimum=32) == 32


def test_select_block_warns_on_non_lane_aligned():
    import warnings as w

    from repro.kernels.autotune import select_block

    with pytest.warns(UserWarning, match="128-lane"):
        select_block(131, 64, warn_lanes=True)  # prime >= 128: only 1 fits
    with w.catch_warnings():
        w.simplefilter("error")
        # lane-aligned choices must stay silent...
        assert select_block(1024, 512, multiple=128, warn_lanes=True) == 512
        # ...and so must dims below 128, which have no aligned option at all
        assert select_block(96, 64, warn_lanes=True) == 48


def test_odd_n_kernel_still_matches_oracle():
    """Prime N used to shrink block_n to a non-lane-aligned sliver silently;
    now it warns but stays correct (the whole dim becomes one block)."""
    k, n = 64, 131
    _, ct = _compress(k, n, "bf8_50", seed=3)
    want = ref.decompress(ct, out_dtype=jnp.float32)
    with pytest.warns(UserWarning, match="128-lane"):
        got = decompress_pallas(ct, out_dtype=jnp.float32, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_undersized_block_k_clamps_to_group():
    """Regression: an explicit block_k below the compression group used to
    clamp to G via max(G, ...); divisor selection must keep that floor
    instead of producing a zero-group BlockSpec."""
    k, n = 256, 96
    _, ct = _compress(k, n, "bf8_50", seed=5)
    want = ref.decompress(ct, out_dtype=jnp.float32)
    got = decompress_pallas(ct, block_k=16, out_dtype=jnp.float32, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    x = jnp.asarray(
        np.random.default_rng(6).standard_normal((8, k)), jnp.float32
    )
    want_g = ref.decompress_gemm(x, ct)
    got_g = decompress_gemm_pallas(x, ct, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g), atol=1e-4)


def test_pick_blocks_regimes():
    from repro.kernels.autotune import pick_blocks

    spec = get_spec("bf8_50")
    # decode GeMV regime: M below sublane granularity stays whole
    bm, bn, bk = pick_blocks(4, 1024, 4096, spec)
    assert bm == 4 and bn % 128 == 0 and bk % spec.group == 0
    # prefill GeMM regime: MXU-aligned tiles
    bm, bn, bk = pick_blocks(256, 1024, 4096, spec)
    assert bm % 8 == 0 and bn % 128 == 0 and bk % spec.group == 0
    assert 1024 % bn == 0 and 4096 % bk == 0 and 256 % bm == 0


def test_bf8_alu_decode_equals_lut_decode():
    """The registry's ALU bit-twiddle decode (the one implementation both
    ref.py and the Pallas kernels use) must agree with the numpy
    high-byte-of-fp16 dequantization for every code (DESIGN.md §2)."""
    from repro.core.codecs import dequantize_bf8, get_codec

    codes = np.arange(256, dtype=np.uint8).reshape(1, 16, 16)
    want = dequantize_bf8(codes)
    got = np.asarray(get_codec("bf8").decode_values(jnp.asarray(codes)))
    np.testing.assert_array_equal(
        got[np.isfinite(want)], want[np.isfinite(want)]
    )
    assert np.isinf(got[np.isinf(want)]).all()


def test_kernel_decode_routes_through_registry():
    """ref.py and deca_decompress.py must share exactly one jnp decoder per
    format: both module-level hooks are the codec's decode_values."""
    from repro.core.codecs import get_codec
    from repro.kernels import ref
    from repro.kernels import deca_decompress as dd

    codes = np.arange(256, dtype=np.uint8).reshape(1, 16, 16)
    for fmt in ("bf8", "mxfp4", "int8", "int4", "nf4", "bf16"):
        spec = get_spec(fmt)
        want = np.asarray(get_codec(fmt).decode_values(jnp.asarray(codes)))
        np.testing.assert_array_equal(
            np.asarray(ref.dequant_codes(jnp.asarray(codes), spec)), want
        )
        np.testing.assert_array_equal(
            np.asarray(dd.decode_values(jnp.asarray(codes), spec)), want
        )
