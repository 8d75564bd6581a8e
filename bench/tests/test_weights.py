"""The slab-and-permute builder: every built matrix decodes to exactly what
`compress` makes of the same permutation of the dense slab, and the
reference's own formats agree with the program's codecs bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import formats
import weights
from conftest import SMOKE_MODELS


@pytest.mark.parametrize("fmt", ["mxfp4_100", "bf8_50"])
def test_formats_match_the_program_codecs(fmt):
    from repro.core.compression import compress
    from repro.core.formats import get_spec
    from repro.kernels import ref

    rng = np.random.default_rng(0)
    w = (rng.standard_normal((256, 96)) / 16).astype(np.float32)
    w[0, :4] = [0.75 * 2 ** -6, 0.25 * 2 ** -6, -1.25 * 2 ** -6, 0.0]  # ties
    got = np.asarray(ref.decompress(compress(w, get_spec(fmt)),
                                    out_dtype=jnp.float32))
    assert np.array_equal(got, formats.dequantized(w, fmt))


@pytest.mark.parametrize("fmt", ["mxfp4_100", "bf8_50"])
def test_built_matrix_is_compress_of_the_permuted_slab(fmt):
    from repro.core.compression import compress
    from repro.core.formats import get_spec
    from repro.kernels import ref

    layers, k, n, width = 2, 128, 192, 64
    slab = weights.dense_slab(5, k, width)
    ct = compress(slab, get_spec(fmt))
    key = jax.random.PRNGKey(9)
    gp = weights.permutations(key, layers, n // width, k // 32)
    for layer in range(layers):
        codes = weights._tile(jnp.asarray(ct.codes), gp[layer])
        planes = {"codes": codes}
        for name in ("mask", "scales"):
            if getattr(ct, name) is not None:
                planes[name] = weights._tile(jnp.asarray(getattr(ct, name)),
                                             gp[layer])
        built = type(ct)(codes=planes["codes"], mask=planes.get("mask"),
                         scales=planes.get("scales"), spec=ct.spec, shape=(k, n))
        # the same permutation of the dense slab, compressed by the program
        grouped = slab.reshape(k // 32, 32, width)
        dense = np.concatenate(
            [grouped[np.asarray(gp[layer, t])].reshape(k, width)
             for t in range(n // width)], axis=1)
        want = np.asarray(ref.decompress(compress(dense, get_spec(fmt)),
                                         out_dtype=jnp.float32))
        got = np.asarray(ref.decompress(built, out_dtype=jnp.float32))
        assert np.array_equal(got, want)


@pytest.mark.parametrize("arch", ["opt", "granite"])
def test_served_weights_decode_to_the_reference_weights(arch):
    from repro.configs.base import ModelConfig
    from repro.kernels import ref
    from repro.models.model import Model

    import reference

    # wide enough (16 groups of 32 rows) that no two group orders coincide
    m = dict(SMOKE_MODELS[arch], d_model=512, n_heads=4, d_head=128,
             d_ff=1024)
    fmt = "mxfp4_100"
    model = Model(ModelConfig(name="smoke", **m))
    params, _ = weights.build_params(model, fmt, 2 ** 31 + 5)
    fc = weights.dense_fc(reference.fc_shapes(m), fmt, 2 ** 31 + 5)
    blocks = params["blocks"]
    seen = set()
    for role, (layers, _, _) in reference.fc_shapes(m):
        ct = (blocks["attn"] if role in ("wq", "wk", "wv", "wo") else blocks["mlp"])[role]
        for layer in range(layers):
            got = np.asarray(ref.decompress(jax.tree.map(lambda a: a[layer], ct),
                                            out_dtype=jnp.float32))
            assert np.array_equal(got, np.asarray(fc(role, layer)))
            seen.add(got.tobytes())
    assert len(seen) == len(reference.fc_shapes(m)) * m["n_layers"]  # all differ
    dense = weights.dense_leaves(reference.dense_shapes(m), 2 ** 31 + 5)
    assert np.array_equal(np.asarray(params["embed"]), np.asarray(dense["embed"]))
    assert np.array_equal(np.asarray(blocks["pre_norm"]), np.asarray(dense["pre_norm"]))


def test_same_seed_same_weights_other_seed_other_weights():
    from repro.configs.base import ModelConfig
    from repro.models.model import Model

    model = Model(ModelConfig(name="smoke", **SMOKE_MODELS["opt"]))
    a, _ = weights.build_params(model, "mxfp4_100", 1)
    b, _ = weights.build_params(model, "mxfp4_100", 1)
    c, _ = weights.build_params(model, "mxfp4_100", 2)
    wa, wb, wc = (np.asarray(p["blocks"]["mlp"]["w_up"].codes) for p in (a, b, c))
    assert np.array_equal(wa, wb) and not np.array_equal(wa, wc)
