"""The weight formats as the reference sees them: a dense float32 matrix in,
the dense float32 matrix that its compressed form stands for out.

Written from the formats' definitions, independently of the program's
codecs, so that the reference never decodes a plane the program made:

- sparsity: in each group of `group` consecutive rows of a column, the
  `k_cap` largest magnitudes are kept (ties: the lower row first), the
  rest are zero;
- `bf8` is E5M2 (the high byte of IEEE binary16): the value is rounded to
  binary16, then to E5M2, each to nearest-even;
- `mxfp4` is OCP MX FP4: E2M1 elements with one power-of-two (E8M0) scale
  per group, 2^(floor(log2(amax)) - 2) so that the largest magnitude lands
  at or below 6; elements round to the nearest E2M1 level, a tie to the
  lower magnitude, and saturate at 6.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

GROUP = 32
E2M1_LEVELS = np.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0], np.float32)
_E2M1_CUTS = (E2M1_LEVELS[1:] + E2M1_LEVELS[:-1]) / 2

# bits per stored element, and bits of one per-group scale
FORMATS = {"mxfp4": (4, 8), "bf8": (8, 0)}


def parse(name: str) -> tuple[str, float]:
    """'mxfp4_100' -> ('mxfp4', 1.0)."""
    quant, pct = name.rsplit("_", 1)
    if quant not in FORMATS:
        raise ValueError(f"no reference for weight format {name!r}")
    return quant, int(pct) / 100.0


def k_cap(name: str, group: int = GROUP) -> int:
    """Values kept per group."""
    quant, density = parse(name)
    k = max(1, round(group * density))
    if FORMATS[quant][0] == 4:
        k += k % 2  # two 4-bit values to a byte
    return min(k, group)


def stored_bytes(name: str, k: int, n: int, group: int = GROUP) -> int:
    """Bytes a (k, n) weight occupies in this format: the packed values,
    one 32-bit mask per group and column when sparse, one scale per group
    and column when scaled."""
    quant, density = parse(name)
    bits, scale_bits = FORMATS[quant]
    groups = k // group * n
    total = groups * k_cap(name, group) * bits // 8
    if density < 1.0:
        total += groups * 4
    return total + groups * scale_bits // 8


def _keep_mask(wg: np.ndarray, keep: int) -> np.ndarray:
    """(ng, G, N) -> bool mask of the `keep` largest |w| per (group, column)."""
    order = np.argsort(-np.abs(wg), axis=1, kind="stable")
    rank = np.empty_like(order)
    np.put_along_axis(rank, order, np.arange(wg.shape[1])[None, :, None], axis=1)
    return rank < keep


def _bf8(x: np.ndarray) -> np.ndarray:
    half = x.astype(np.float16)
    return half.astype(ml_dtypes.float8_e5m2).astype(np.float32)


def _mxfp4(wg: np.ndarray) -> np.ndarray:
    amax = np.abs(wg).max(axis=1, keepdims=True)
    exp = np.floor(np.log2(np.maximum(amax, np.float32(2.0 ** -126))))
    scale = np.float32(2.0) ** np.clip(exp - 2, -127, 127)
    q = wg / scale
    level = E2M1_LEVELS[np.searchsorted(_E2M1_CUTS, np.abs(q), side="left")]
    return np.where(q < 0, -level, level).astype(np.float32) * scale


def dequantized(w: np.ndarray, name: str, group: int = GROUP) -> np.ndarray:
    """The dense float32 matrix that `w` (K, N), stored as `name`, stands
    for. Groups run along K."""
    w = np.asarray(w, np.float32)
    kdim, n = w.shape
    wg = w.reshape(kdim // group, group, n)
    quant, density = parse(name)
    keep = _keep_mask(wg, k_cap(name, group)) if density < 1.0 else None
    if quant == "bf8":
        out = _bf8(wg)
    else:
        out = _mxfp4(wg)
    if keep is not None:
        out = np.where(keep, out, np.float32(0.0))
    return out.reshape(kdim, n)
