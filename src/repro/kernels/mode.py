"""Pallas compile mode: one switch for every kernel entry point.

`interpret=None` (every entry point's default) resolves here, in order:
an explicit `interpret=True/False` wins; otherwise `REPRO_PALLAS_INTERPRET`
(`1` interprets even on a TPU, to debug a Mosaic miscompile against the
interpreter; `0` compiles for Mosaic anywhere); otherwise the backend
decides: compiled on a TPU, interpreted everywhere else. So nothing
interprets on a TPU unless it is told to.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get(INTERPRET_ENV, "").strip().lower()
    if env in ("1", "true", "yes", "on"):
        return True
    if env in ("0", "false", "no", "off"):
        return False
    if env:
        raise ValueError(
            f"{INTERPRET_ENV}={env!r}: expected 1/true/yes/on or 0/false/no/off"
        )
    return jax.default_backend() != "tpu"
