"""Unit + property tests for the offline compression substrate."""
import math

import numpy as np
import pytest
import jax.numpy as jnp

pytest.importorskip("hypothesis", reason="install the [test] extra")
from hypothesis import given, settings, strategies as st

from repro.core.compression import (
    compress, dequantize_bf8, dequantize_fp4, quantize_bf8, quantize_fp4,
)
from repro.core.formats import CompressionSpec, get_spec, PAPER_SCHEMES
from repro.kernels import ref

ALL_SPECS = [
    "bf16_100", "bf16_50", "bf16_30", "bf16_10",
    "bf8_100", "bf8_50", "bf8_20", "bf8_5",
    "mxfp4_100", "mxfp4_50", "int8_50", "int4_25",
]


# ---------------------------------------------------------------------------
# number formats
# ---------------------------------------------------------------------------

def test_bf8_roundtrip_exact_on_representables():
    # every E5M2 code must roundtrip exactly (bit-level identity)
    codes = np.arange(256, dtype=np.uint8)
    vals = dequantize_bf8(codes)
    finite = np.isfinite(vals)
    again = quantize_bf8(vals[finite])
    np.testing.assert_array_equal(again, codes[finite])


@given(st.floats(min_value=-50000, max_value=50000, allow_nan=False))
@settings(max_examples=200, deadline=None)
def test_bf8_quantization_error_bound(x):
    code = quantize_bf8(np.array([x], np.float32))
    back = dequantize_bf8(code)[0]
    if not np.isfinite(back):
        return  # overflowed to inf: |x| beyond E5M2 max
    # E5M2 has 2 mantissa bits: relative error <= 2^-3 (RNE: half ULP = 1/8)
    assert abs(back - x) <= max(abs(x) * 0.125, 6.2e-5)


def test_fp4_grid_roundtrip():
    grid = np.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0], np.float32)
    for sign in (1.0, -1.0):
        codes = quantize_fp4(sign * grid)
        np.testing.assert_allclose(dequantize_fp4(codes), sign * grid)


# ---------------------------------------------------------------------------
# compression properties (hypothesis)
# ---------------------------------------------------------------------------

@st.composite
def weight_and_spec(draw):
    k = draw(st.sampled_from([32, 64, 128, 256]))
    n = draw(st.integers(min_value=1, max_value=33))
    spec = get_spec(draw(st.sampled_from(ALL_SPECS)))
    seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
    w = np.random.default_rng(seed).standard_normal((k, n)).astype(np.float32)
    return w, spec


@given(weight_and_spec())
@settings(max_examples=60, deadline=None)
def test_density_invariant(ws):
    """Decompressed nonzero fraction never exceeds k_cap/group per group."""
    w, spec = ws
    dense = ref.dense_roundtrip(w, spec)
    ng = w.shape[0] // spec.group
    per_group = (dense.reshape(ng, spec.group, -1) != 0).sum(axis=1)
    assert per_group.max() <= spec.k_cap


@given(weight_and_spec())
@settings(max_examples=60, deadline=None)
def test_sparsity_keeps_topk(ws):
    """Kept positions are exactly the per-group top-|w| (no value corruption
    of position choice)."""
    w, spec = ws
    if not spec.is_sparse:
        return
    dense = ref.dense_roundtrip(w, spec)
    ng = w.shape[0] // spec.group
    wg = w.reshape(ng, spec.group, -1)
    dg = dense.reshape(ng, spec.group, -1)
    kept = dg != 0
    # every kept |w| must be >= every dropped |w| within its group/column
    for g in range(ng):
        for c in range(w.shape[1]):
            kept_vals = np.abs(wg[g, kept[g, :, c], c])
            drop_vals = np.abs(wg[g, ~kept[g, :, c], c])
            if kept_vals.size and drop_vals.size:
                # mxfp4/int can quantize small kept values to 0 — allow ties
                assert kept_vals.min() >= drop_vals.max() - 1e-6


@given(weight_and_spec())
@settings(max_examples=40, deadline=None)
def test_quantization_error_bounded(ws):
    """Error on kept values bounded by the format's precision: floating
    formats give a *relative* per-value bound; group-scaled formats give an
    *absolute* per-group bound proportional to the group max."""
    w, spec = ws
    dense = ref.dense_roundtrip(w, spec)
    keepmask = dense != 0
    if not keepmask.any():
        return
    if spec.quant in ("bf16", "bf8"):
        err = np.abs(dense - w)[keepmask]
        mag = np.abs(w)[keepmask]
        bound = {"bf16": 2 ** -8, "bf8": 0.13}[spec.quant]
        assert (err <= mag * bound + 1e-6).all()
    else:
        # per (group, column): |err| <= half max grid spacing * scale, and
        # scale <= group_amax / qmax_effective
        frac = {"mxfp4": 0.27, "int8": 0.005, "int4": 0.08}[spec.quant]
        ng = w.shape[0] // spec.group
        errs = np.abs(dense - w).reshape(ng, spec.group, -1)
        errs = np.where(keepmask.reshape(ng, spec.group, -1), errs, 0.0)
        # bound against the max |kept value| per (group, col)
        kept_w = np.where(keepmask, np.abs(w), 0.0).reshape(ng, spec.group, -1)
        amax = kept_w.max(axis=1) + 1e-9
        assert (errs.max(axis=1) <= amax * frac + 1e-6).all()


def test_compression_factor_matches_paper_formula():
    # CF = 16/(Q*d+1) for sparse schemes without scales (paper §2.2)
    spec = get_spec("bf8_50")
    k_cap_density = spec.k_cap / spec.group
    expected = 16.0 / (8 * k_cap_density + 1)
    assert math.isclose(spec.compression_factor(), expected, rel_tol=1e-9)


def test_exact_byte_accounting():
    rng = np.random.default_rng(3)
    w = rng.standard_normal((256, 48)).astype(np.float32)
    for name in ALL_SPECS:
        spec = get_spec(name)
        ct = compress(w, spec)
        assert ct.nbytes == spec.bytes_for(256, 48), name


@pytest.mark.parametrize("name", ["mxfp4_100", "bf8_50", "int4_25"])
def test_parallel_slabs_match_one_pass(name):
    """A matrix large enough to be compressed as column slabs on threads
    gives planes bit-identical to one pass over the whole matrix."""
    from repro.core import compression

    spec = get_spec(name)
    w = np.random.default_rng(4).standard_normal((256, 16384)).astype(np.float32)
    assert w.size >= compression._PARALLEL_MIN
    got, want = compress(w, spec), compression._compress_cols(w, spec)
    for plane in ("codes", "mask", "scales"):
        a, b = getattr(got, plane), getattr(want, plane)
        assert (a is None) == (b is None), plane
        if a is not None:
            np.testing.assert_array_equal(a, b, err_msg=plane)
