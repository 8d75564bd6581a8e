"""Shared fixtures of the benchmark's own tests (run by path:
`python -m pytest bench/tests`). They run on the CPU at smoke sizes."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

SMOKE_MODELS = {
    "opt": {
        "family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 4, "d_head": 16, "d_ff": 256, "vocab_size": 256,
        "pos_emb": "learned", "pos_table": 128, "mlp_act": "relu",
        "tie_embeddings": True, "norm_eps": 1e-5,
    },
    "granite": {
        "family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "d_head": 16, "d_ff": 160, "vocab_size": 259,
        "pos_emb": "rope", "mlp_act": "swiglu", "tie_embeddings": True,
        "norm_eps": 1e-5,
    },
}
SMOKE_WEIGHTS = {"opt": "mxfp4_100", "granite": "bf8_50"}


def smoke_cell(arch: str, limit: float = 0.5) -> dict:
    """A cell as `run.load_cell` makes it, at smoke sizes."""
    import json

    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return {
        "name": f"{arch}.smoke", "chips": 1,
        "cfg": {
            "name": f"{arch}-smoke", "model": dict(SMOKE_MODELS[arch]),
            "weights": SMOKE_WEIGHTS[arch], "kv_quant": "none",
            "engine": {"block_size": 16, "max_slots": 4, "decode_chunk": 4},
            "limits": {"logit_gap": limit},
        },
        "mix": {
            "loop": "closed", "clients": "max_slots",
            "prompt_tokens": {"dist": "loguniform", "min": 8, "max": 40},
            "output_tokens": {"dist": "loguniform", "min": 4, "max": 24},
            "block": 4, "sampling": "greedy",
        },
        "end_to_end": manifest["end_to_end"],
        "per_layer": manifest["per_layer"],
    }


@pytest.fixture
def cpu_device(monkeypatch):
    """Stands in for the TPU check: the CPU with the v5e's peaks."""
    import peaks
    import run

    identity = {"platform": "cpu", "kind": "cpu", "count": 1}
    monkeypatch.setattr(run, "check_device",
                        lambda chips: (identity, peaks.PEAKS["TPU v5 lite"]))
    return identity, peaks.PEAKS["TPU v5 lite"]
