"""A cell whose layers are shared over a four-chip tensor-parallel mesh,
driven through a whole run on four virtual CPU devices (kernels in
interpret mode), past the harness's look for a chip.

One subprocess (the device count is fixed when JAX starts) makes the
weights with and without the mesh's shardings, runs the sound cell, and
runs it once under each fault a cell on four chips can have: the
one-chip faults of ``test_correct.py``, and those only a sharded cell can
have (a shard's K/V pages zeroed after the window; a row-parallel GEMM's
partial products left unsummed)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from chipbench import faults, run, spec

CHIPS = 4
ROOT = spec.ROOT
SCRIPT = textwrap.dedent(f"""
    import json, sys
    sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
    import jax
    import numpy as np
    from chipbench import faults, run, spec, weights
    from chipbench.tests.smoke import smoke_cell
    from repro.kernels import shmap

    def say(**kw):
        print("RESULT " + json.dumps(kw), flush=True)

    cell = smoke_cell(interpret=True, chips={CHIPS})
    devices = jax.devices()
    cfg = spec.model_config(cell["config"])
    mesh = run.make_mesh(cell["config"], devices)
    seed = 2 ** 32 + 17
    plain = weights.make_params(cfg, seed)
    laid = weights.make_params(cfg, seed,
                               shardings=weights.shardings(cfg, mesh))
    same = jax.tree.map(lambda a, b: bool(np.array_equal(
        np.asarray(a), np.asarray(b))), plain, laid)
    sharded = jax.tree.map(lambda b: not b.sharding.is_fully_replicated,
                           laid)
    view = weights.hf_view(laid, cfg)
    say(what="weights", devices=len(devices),
        equal=all(jax.tree.leaves(same)),
        sharded_leaves=sum(jax.tree.leaves(sharded)),
        view_replicated=sorted(
            k for k, v in view.items()
            if v.ndim > 2 and v.sharding.is_fully_replicated))
    del plain, laid, view

    shmap.reset_counters()
    res = run.run_cell(cell, seed, 2.0, False, devices)
    say(what="sound", correct=res["correct"], check=res["check"],
        attempted=res["attempted"], failed=res["failed"],
        shard_map=shmap.counters())
    for name, fault in sorted(faults.for_chips({CHIPS}).items()):
        with fault():
            res = run.run_cell(cell, seed, 2.0, False, devices)
        say(what=name, correct=res["correct"], check=res["check"])
""")


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={CHIPS}").strip()
    p = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=str(ROOT), timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = {}
    for line in p.stdout.splitlines():
        if line.startswith("RESULT "):
            r = json.loads(line[len("RESULT "):])
            out[r.pop("what")] = r
    return out


def test_sharded_weights_are_the_same_numbers(results):
    """The weights drawn over the mesh are bitwise those drawn on one
    device; the projections, MLP and vocabulary are sharded, and the
    reference's view of them is not gathered onto every chip."""
    w = results["weights"]
    assert w["devices"] == CHIPS and w["equal"]
    assert w["sharded_leaves"] >= 7
    assert w["view_replicated"] == []


def test_sound_run_over_the_mesh_is_correct(results):
    r = results["sound"]
    assert r["correct"], r["check"]
    assert r["check"]["kv_rel_err"]["value"] is not None
    assert r["attempted"] > 0 and r["failed"] == 0
    # the fused kernels ran per shard, through shard_map
    assert all(r["shard_map"][k] > 0
               for k in ("matmul", "attention", "paged", "kv_write"))


@pytest.mark.parametrize("fault", sorted(faults.for_chips(CHIPS)))
def test_fault_over_the_mesh_is_caught(results, fault):
    r = results[fault]
    assert not r["correct"], r["check"]


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      spec.load_benchmark()["workloads"]
                                      if w["chips"] == 1])
def test_one_chip_cell_builds_no_mesh(workload):
    assert run.make_mesh(spec.cell(workload)["config"], jax.devices()) \
        is None
