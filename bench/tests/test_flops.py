"""FLOP and byte counts against hand counts at the smoke configurations,
and the stored bytes against the planes the builder makes."""
import jax
import pytest

import flops
import weights
from conftest import SMOKE_MODELS, SMOKE_WEIGHTS


def test_linear_params_by_hand():
    opt, gra = SMOKE_MODELS["opt"], SMOKE_MODELS["granite"]
    # opt: q,k,v,o 64x64 each, up 64x256, down 256x64; 2 layers
    assert flops.linear_params(opt) == 2 * (4 * 64 * 64 + 2 * 64 * 256)
    # granite: q 64x64, k,v 64x32, o 64x64, gate/up 64x160, down 160x64
    assert flops.linear_params(gra) == 2 * (2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 160)


def test_decode_and_prefill_flops_by_hand():
    m = SMOKE_MODELS["opt"]
    lin = flops.linear_params(m)
    head = 2 * 64 * 256
    # 3 tokens reading 10 KV tokens in all: 4 * layers * heads * d_head per KV token
    assert flops.decode_flops(m, 3, 10) == 3 * (2 * lin + head) + 4 * 2 * 4 * 16 * 10
    # a 5-token prompt: causal attention over 1+2+3+4+5 keys, QK and PV
    attn = 2 * 2 * (2 * 4 * 16 * 15)  # layers, QK and PV
    assert flops.prefill_flops(m, 5) == 2 * lin * 5 + attn + head


def test_decode_bytes_by_hand():
    m = SMOKE_MODELS["opt"]
    # mxfp4: 32 values at 4 bits + one 8-bit scale per group of 32 rows
    per_group = 16 + 1
    planes = 2 * sum(k // 32 * n * per_group for k, n in flops.linear_shapes(m))
    assert flops.plane_bytes(m, "mxfp4_100") == planes
    kv = 2 * 2 * 4 * 16 * 2  # layers, K and V, heads, d_head, bf16
    assert flops.kv_bytes_per_token(m) == kv
    assert flops.decode_bytes(m, "mxfp4_100", 2, 7) == 2 * (planes + 256 * 64 * 2) + 7 * kv


@pytest.mark.parametrize("arch", ["opt", "granite"])
def test_stored_bytes_match_the_built_planes(arch):
    from repro.configs.base import ModelConfig
    from repro.core.compression import CompressedTensor
    from repro.models.model import Model

    m = SMOKE_MODELS[arch]
    model = Model(ModelConfig(name="smoke", **m))
    params, _ = weights.build_params(model, SMOKE_WEIGHTS[arch], 3)
    cts = [l for l in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, CompressedTensor))
        if isinstance(l, CompressedTensor)]
    assert sum(ct.nbytes for ct in cts) == flops.plane_bytes(m, SMOKE_WEIGHTS[arch])
