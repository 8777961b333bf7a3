"""Production mesh construction (assignment-mandated shape).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state, so tests/benches keep their 1-CPU-device world while the
dry-run builds 512 placeholder devices."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes.  The framework shards through
    GSPMD propagation plus explicit ``shard_map`` regions, so its meshes
    must not default to Explicit axes (jax >= 0.7), under which an
    unannotated gather such as the embedding lookup is a type error."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """``(devices / model, model)`` ("data", "model") mesh over every
    device of this process."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


# Compiler flags a real TPU launch would set for collective/compute overlap
# (recorded here so launch scripts and docs share one source of truth; they
# are no-ops on the CPU dry-run backend).
TPU_XLA_FLAGS = " ".join([
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
])
