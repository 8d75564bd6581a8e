"""A cell of four chips through the harness's own functions, on four CPU
devices: the weights are built in place on the (data=1, model=4) mesh,
every FC plane split four ways, with the values of the build without a
mesh, and the run comes out correct with its result line whole. With the
exchange between the chips left out, the same run comes out not correct.

The CPU backend's device count is fixed when JAX starts, so the checks run
in a child process (this file run as a script) with four host devices."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CHIPS = 4


def _planes(params):
    from repro.core.compression import CompressedTensor
    import jax

    return [p for ct in jax.tree_util.tree_leaves(
                params, is_leaf=lambda x: isinstance(x, CompressedTensor))
            if isinstance(ct, CompressedTensor)
            for p in (ct.codes, ct.mask, ct.scales) if p is not None]


def own_part_only(build):
    """`weights.build_params` whose row-parallel weights (`wo`, `w_down`,
    their rows split over the chips) keep the first chip's rows alone: the
    served sums lack what the other chips' exchange would add to them."""
    import jax

    def built(model, spec, seed, mesh=None):
        params, host_s = build(model, spec, seed, mesh)
        for group, role in (("attn", "wo"), ("mlp", "w_down")):
            ct = params["blocks"][group][role]
            groups = ct.codes.shape[1]
            codes = jax.jit(lambda c: c.at[:, groups // CHIPS:].set(0),
                            out_shardings=ct.codes.sharding)(ct.codes)
            params["blocks"][group][role] = dataclasses.replace(ct, codes=codes)
        return params, host_s

    return built


def four_chips() -> dict:
    import jax
    import numpy as np

    sys.path[:0] = [str(HERE.parent), str(HERE.parents[1] / "src"), str(HERE)]
    import peaks
    import run
    import weights
    from conftest import smoke_cell
    from repro.dist import sharding as sh

    cell = smoke_cell("opt")
    cell["chips"] = CHIPS
    # heads of 32, so that every contraction holds a multiple of 4 groups
    # of 32 rows and the row-parallel weights split as well
    cell["cfg"]["model"].update(d_model=128, d_head=32)
    seed = 2 ** 31 + 41
    eng = run.build_engine(cell, seed)
    planes = _planes(eng.params)
    split = [p.addressable_shards[0].data.size * CHIPS == p.size for p in planes]
    model, fmt = eng.model, cell["cfg"]["weights"]
    built, _ = weights.build_params(model, fmt, seed, eng.mesh)
    # the engine's own placement finds every leaf of the build in place
    placed = sh.shard_params(built, sh.ShardingCtx(eng.mesh, mode="serve"),
                             scan_stacked=model.uniform)
    in_place = all(a is b for a, b in zip(jax.tree_util.tree_leaves(built),
                                          jax.tree_util.tree_leaves(placed)))
    plain, _ = weights.build_params(model, fmt, seed)
    same = all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(
        jax.tree_util.tree_leaves(built), jax.tree_util.tree_leaves(plain)))
    del eng, built, placed
    identity = {"platform": "cpu", "kind": "cpu", "count": jax.device_count()}
    line = run.run_cell(cell, seed, 2.0, False, identity,
                        peaks.PEAKS["TPU v5 lite"])
    weights.build_params = own_part_only(weights.build_params)
    cut = run.run_cell(cell, seed + 1, 2.0, False, identity,
                       peaks.PEAKS["TPU v5 lite"])
    return {"devices": jax.device_count(), "planes": len(planes),
            "split": sum(split), "in_place": in_place, "same_values": same,
            "line": line,
            "no_exchange": cut}


@pytest.fixture(scope="module")
def got():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          f" --xla_force_host_platform_device_count={CHIPS}"))
    done = subprocess.run([sys.executable, __file__], env=env, cwd=HERE,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_a_four_chip_cell_runs_on_a_mesh(got):
    assert got["devices"] == CHIPS
    assert got["planes"] > 0 and got["split"] == got["planes"], got
    assert got["in_place"] and got["same_values"]
    line = got["line"]
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"out_tok_s", "out_tok_s.code", "setup_s"} <= set(line["metrics"])
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_left_out_exchange_is_not_correct(got):
    cut = got["no_exchange"]
    assert not cut["correct"]
    gap = cut["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


if __name__ == "__main__":
    print(json.dumps(four_chips()), flush=True)
