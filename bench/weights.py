"""Seeded weights without dense weights and without compressing every matrix.

Every plane of a compressed weight is local to its column and to its group
of rows, so permuting whole groups of a valid compressed weight gives the
compressed form of the dense weight permuted the same way. So for each
contraction width K one seeded dense slab (K, W) is compressed once on the
host with the program's own `compress`, and every (K, N) matrix of every
layer is built on the device: N / W copies of the slab side by side, each
with its own seeded order of the groups (a gather of whole groups, cheap on
the device, where a gather of single byte columns is not). No two tiles
and no two matrices share a layout, and each is exactly what `compress`
makes of its dense twin.

The dense twin is what the reference uses: `dense_fc` builds it from the
slab through `formats.dequantized`, never from a plane the program made.
Embeddings, positions and norm gains are drawn on the device from the seed
by `dense_leaves`, once for the program and again for the reference.
"""
from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import formats

MAX_SLAB = 512


def seed_words(seed: int, purpose: int) -> int:
    """A 32-bit word for one purpose, from a seed of any size."""
    return int(np.random.SeedSequence([seed, purpose]).generate_state(1)[0])


def role_id(role: str) -> int:
    """A weight's key is drawn from its role ("wq", "w_down", "embed"...),
    so the program's tree and the reference's list of shapes agree on it
    whatever order either keeps its leaves in."""
    return zlib.crc32(role.encode())


def role_of(path) -> str:
    return str(path[-1].key)


def fc_layout(abstract) -> list:
    """[(role, (L, K, N))] of the stacked FC weights in a `Model.init` tree."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(abstract)[0]:
        name = jax.tree_util.keystr(path)
        if "blocks" in name and "norm" not in name and leaf.ndim == 3:
            out.append((role_of(path), tuple(leaf.shape)))
    return out


def slab_width(layout) -> int:
    """The widest slab, at most MAX_SLAB columns, whose width divides every N."""
    g = 0
    for _, (_, _, n) in layout:
        g = math.gcd(g, n)
    return max(d for d in range(1, min(g, MAX_SLAB) + 1) if g % d == 0)


def dense_slab(seed: int, k: int, width: int) -> np.ndarray:
    """N(0, 1/K), each column centred over K. Every tile of every layer
    repeats the slab's columns, so an uncentred column sum would be the same
    in every layer: a ReLU's positive mean times those sums would add one
    direction to every position once per layer, and the logits would share
    one top token at nearly every position, where no rounding moves it."""
    rng = np.random.default_rng([seed, k, width])
    slab = rng.standard_normal((k, width), np.float32) / np.sqrt(k)
    return (slab - slab.mean(axis=0, keepdims=True)).astype(np.float32)


def permutations(key, layers: int, tiles: int, groups: int):
    """Group orders (layers, tiles, groups): one per tile of each layer."""
    keys = jax.random.split(key, layers * tiles)
    gp = jax.vmap(lambda k: jax.random.permutation(k, groups))(keys)
    return gp.reshape(layers, tiles, groups)


def _tile(plane, gp_l):
    """plane (groups, ..., W) -> (groups, ..., tiles * W), tile t holding
    the groups in the order gp_l[t]."""
    tiles = jnp.take(plane, gp_l, axis=0)  # (T, groups, ..., W)
    tiles = jnp.moveaxis(tiles, 0, -2)     # (groups, ..., T, W)
    return tiles.reshape(tiles.shape[:-2] + (-1,))


def fc_key(seed: int, role: str):
    return jax.random.fold_in(
        jax.random.PRNGKey(seed_words(seed, 1)), role_id(role)
    )


def _std(role: str, shape) -> float:
    if "norm" in role:
        return 0.1
    if role in ("embed", "pos_embed"):
        return 0.02
    if role == "lm_head":
        return 1.0 / math.sqrt(shape[0])
    raise ValueError(f"no rule for a weight of role {role!r}")


def dense_leaves(leaves, seed: int, shardings=None) -> dict:
    """Every weight that is not an FC weight, made on the device in one call
    from the seed: `leaves` is [(role, shape, dtype)], the result
    {role: array}, placed by `shardings` ({role: sharding}) where given.
    Embeddings N(0, 0.02^2), norm gains N(0, 0.1^2)."""
    leaves = [(r, tuple(s), jnp.dtype(d)) for r, s, d in leaves]

    def make(key):
        return {
            role: (
                jax.random.normal(jax.random.fold_in(key, role_id(role)), shape)
                * _std(role, shape)
            ).astype(dtype)
            for role, shape, dtype in leaves
        }

    return jax.jit(make, out_shardings=shardings)(
        jax.random.PRNGKey(seed_words(seed, 2)))


def placements(abstract, mesh, scan_stacked: bool):
    """The program's own serving placement of a parameter tree (arrays or
    shapes, compressed weights as `CompressedTensor`s) on `mesh`, as
    ({role: {plane: sharding}} of the FC weights, {role: sharding} of the
    rest): `dist.sharding.param_spec_tree`, the rule the engine's
    `shard_params` applies, so it finds every leaf already in place."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.compression import CompressedTensor
    from repro.dist.sharding import ShardingCtx, param_spec_tree

    specs = param_spec_tree(abstract, ShardingCtx(mesh, mode="serve"),
                            scan_stacked=scan_stacked)
    fc, dense = {}, {}
    for path, spec in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, (CompressedTensor, PartitionSpec))
    )[0]:
        role = role_of(path)
        if isinstance(spec, CompressedTensor):
            fc[role] = {name: NamedSharding(mesh, getattr(spec, name))
                        for name in ("codes", "mask", "scales")
                        if getattr(spec, name) is not None}
        else:
            dense[role] = NamedSharding(mesh, spec)
    return fc, dense


def build_params(model, spec_name: str, seed: int, mesh=None):
    """The served parameter tree: FC weights as stacked `CompressedTensor`s
    built on the device, the rest from `dense_leaves`. With a `mesh`, each
    build is compiled with the program's placement (`placements`) as its
    output sharding, so no chip ever holds more than its share; the values
    are those of the build without one. Returns (params, seconds spent
    compressing slabs on the host)."""
    import time

    from repro.core.compression import CompressedTensor, compress
    from repro.core.formats import get_spec

    spec = get_spec(spec_name)
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layout = fc_layout(abstract)
    width = slab_width(layout)
    t0 = time.perf_counter()
    slabs = {}
    for _, (_, k, _) in layout:
        if k not in slabs:
            ct = compress(dense_slab(seed, k, width), spec)
            slabs[k] = {
                name: jnp.asarray(getattr(ct, name))
                for name in ("codes", "mask", "scales")
                if getattr(ct, name) is not None
            }
    host_s = time.perf_counter() - t0

    def build(slab_planes, keys):
        out = {}
        for role, (layers, k, n) in layout:
            gp = permutations(keys[role], layers, n // width, k // spec.group)
            out[role] = {
                name: jax.vmap(partial(_tile, plane))(gp)
                for name, plane in slab_planes[k].items()
            }
        return out

    shapes = dict(layout)
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    others = [(role_of(p), leaf.shape, leaf.dtype) for p, leaf in flat
              if role_of(p) not in shapes]

    def assemble(built, dense):
        def leaf(path):
            role = role_of(path)
            if role not in shapes:
                return dense[role]
            planes = built[role]
            return CompressedTensor(
                codes=planes["codes"], mask=planes.get("mask"),
                scales=planes.get("scales"), spec=spec, shape=shapes[role][1:],
            )

        return jax.tree_util.tree_unflatten(treedef, [leaf(p) for p, _ in flat])

    # the keys are arguments, not constants: one program serves every seed
    keys = {role: fc_key(seed, role) for role, _ in layout}
    fc_out = dense_out = None
    if mesh is not None:
        fc_out, dense_out = placements(
            assemble(jax.eval_shape(build, slabs, keys),
                     {r: jax.ShapeDtypeStruct(s, d) for r, s, d in others}),
            mesh, model.uniform)
    built = jax.jit(build, out_shardings=fc_out)(slabs, keys)
    return assemble(built, dense_leaves(others, seed, dense_out)), host_s


def dense_fc(layout, spec_name: str, seed: int):
    """For the reference: fc(role, layer) -> the dense float32 (K, N) weight
    that the program's compressed weight of that role and layer stands for.
    `layout` is [(role, (L, K, N))], as `fc_layout` gives it."""
    width = slab_width(layout)
    shapes = dict(layout)
    slabs = {}

    @partial(jax.jit, static_argnums=(2, 3))
    def one(slab, key, layers, n, layer):
        k = slab.shape[0]
        groups = k // formats.GROUP
        gp = permutations(key, layers, n // width, groups)
        grouped = slab.reshape(groups, formats.GROUP, width)
        full = _tile(grouped, gp[layer])  # (groups, G, N)
        return full.reshape(k, n)

    def fc(role: str, layer: int):
        layers, k, n = shapes[role]
        if k not in slabs:
            slabs[k] = jnp.asarray(
                formats.dequantized(dense_slab(seed, k, width), spec_name)
            )
        return one(slabs[k], fc_key(seed, role), layers, n, layer)

    return fc
