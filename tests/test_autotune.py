"""Block-geometry selection tests (kernels/autotune.py): largest-divisor
`select_block` behavior on awkward dimensions (primes, non-lane-aligned N,
with the §2 warning), roofline-mapped `pick_blocks` on production shapes,
and the paged-attention `pick_page_block` page-block grid (DESIGN.md §13)."""
import warnings

import pytest

from repro.core.formats import get_spec
from repro.kernels.autotune import (
    LANES,
    kv_page_bytes,
    pick_blocks,
    pick_page_block,
    select_block,
)


# ---------------------------------------------------------------------------
# select_block: divisors, alignment preference, warnings
# ---------------------------------------------------------------------------

def test_select_block_largest_divisor():
    assert select_block(1024, 256) == 256
    assert select_block(14336, 256, multiple=LANES) == 256  # 2^11 * 7
    assert select_block(96, 64) == 48
    assert select_block(12, 8) == 6


def test_select_block_prefers_aligned_divisor():
    # 384 = 2^7 * 3: largest divisor <= 300 is 192, but 128 is lane-aligned
    assert select_block(384, 300, multiple=LANES) == 128
    # no aligned divisor exists -> falls back to the largest plain one
    assert select_block(96, 64, multiple=LANES) == 48


def test_select_block_prime_dimension_warns():
    """A prime dim >= 128 has no divisor but 1 and itself: the old
    decrement-by-1 loop silently shrank to 1; select_block warns."""
    with pytest.warns(UserWarning, match="128-lane"):
        assert select_block(251, 128, warn_lanes=True, name="block_n") == 1


def test_select_block_non_lane_aligned_warns():
    # 192 = 2^6 * 3: nothing <= 128 is a multiple of 128 -> best is 96
    with pytest.warns(UserWarning, match="128-lane"):
        assert (
            select_block(192, 128, multiple=LANES, warn_lanes=True) == 96
        )


def test_select_block_aligned_choice_is_silent():
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert select_block(1024, 256, multiple=LANES, warn_lanes=True) == 256
        # dims below the lane width have no aligned option: stay silent
        assert select_block(96, 32, warn_lanes=True) == 32


def test_select_block_minimum_clamps_target():
    # block_k callers pass the compression group as the minimum so an
    # undersized explicit target still holds whole groups
    assert select_block(256, 8, multiple=32, minimum=32) == 32


def test_select_block_rejects_bad_dimension():
    with pytest.raises(ValueError, match="positive"):
        select_block(0, 128)


# ---------------------------------------------------------------------------
# pick_blocks: §2 roofline-mapped shapes
# ---------------------------------------------------------------------------

def test_pick_blocks_prefill_regime_llama_shapes():
    """llama3-8b d_model x d_ff with bf8_50: classic MXU tiling — 128-row
    blocks, lane-aligned 256 columns, 512-deep whole-group k blocks."""
    bm, bn, bk = pick_blocks(1024, 14336, 4096, get_spec("bf8_50"))
    assert (bm, bn, bk) == (128, 256, 512)
    assert bn % LANES == 0 and bk % get_spec("bf8_50").group == 0


def test_pick_blocks_decode_regime_keeps_m_whole():
    """Below the sublane granularity M is kept whole and block_n gets the
    wider lane target (the MEM-bound GeMV regime of DESIGN.md §12)."""
    bm, bn, bk = pick_blocks(4, 14336, 4096, get_spec("mxfp4_100"))
    assert bm == 4
    assert bn >= 2 * LANES and bn % LANES == 0


@pytest.mark.parametrize("m", [4, 1024])
def test_pick_blocks_unaligned_vocab_keeps_lanes_full(m):
    """opt-66b's vocab (50272 = 2^5 x 1571) has no lane-aligned divisor:
    block_n stays a lane multiple and the kernels grid N raggedly, instead
    of the 32-wide divisor Mosaic refuses."""
    bm, bn, bk = pick_blocks(m, 50272, 9216, get_spec("mxfp4_100"))
    assert bn % LANES == 0 and bn >= LANES
    assert 9216 % bk == 0 and m % bm == 0


def test_pick_blocks_shrinks_k_first_under_vmem_pressure():
    spec = get_spec("bf8_50")
    full = pick_blocks(128, 4096, 4096, spec)
    tight = pick_blocks(128, 4096, 4096, spec, vmem_budget=1 << 20)
    assert tight[2] < full[2]  # k gave way first
    assert tight[1] % LANES == 0  # lanes stay filled as long as possible


# ---------------------------------------------------------------------------
# pick_page_block: the paged-attention page-block grid
# ---------------------------------------------------------------------------

def test_pick_page_block_divides_and_caps():
    # divisor of mb, never more than the target, capped at mb // 2 so the
    # walk can never degenerate into one whole-table block
    assert pick_page_block(8, 16, 8, 128) == 4
    assert pick_page_block(12, 16, 8, 128) == 6
    assert pick_page_block(7, 16, 8, 128) == 1  # prime: only 1 divides
    assert pick_page_block(2, 16, 8, 128) == 1
    assert pick_page_block(1, 16, 8, 128) == 1
    assert pick_page_block(128, 16, 8, 128) == 8
    assert pick_page_block(128, 16, 8, 128, target=16) == 16


def test_pick_page_block_respects_vmem_budget():
    # one 512-token bf16 page at Hkv=8, Dh=128 is ~2.1 MB: a 2 MB budget
    # can only double-buffer a single page
    assert (
        pick_page_block(64, 512, 8, 128, "none", vmem_budget=2 << 20) == 1
    )
    # quantized pages are smaller, so the same budget fits more of them
    assert pick_page_block(
        64, 512, 8, 128, "int4", vmem_budget=8 << 20
    ) > pick_page_block(64, 512, 8, 128, "none", vmem_budget=8 << 20)


def test_kv_page_bytes_is_codec_driven():
    none = kv_page_bytes(16, 8, 128, "none")
    bf8 = kv_page_bytes(16, 8, 128, "bf8")
    int4 = kv_page_bytes(16, 8, 128, "int4")
    assert bf8 < none and int4 < bf8
    # bf8 halves the bf16 payload (position plane aside)
    assert abs(bf8 / none - 0.5) < 0.05
