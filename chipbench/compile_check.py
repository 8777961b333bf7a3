"""Compile a cell's serving programs at full size for a described TPU.

    JAX_PLATFORMS=cpu python chipbench/compile_check.py --workload <name> \
        [--slots <n>]

No chip is needed: the TPU compiler is handed shapes on a described
``v5e:2x2`` topology, and refuses what the chip would refuse (a kernel
Mosaic cannot lower, a program that does not fit).  For the decode step
and each prefill chunk program it prints ``memory_analysis()``: the bytes
of arguments, outputs and temporaries on one device.  A cell whose
configuration shares every layer over ``N`` chips is compiled over the
first ``N`` devices of the topology, its weights, page pools and inputs
laid out as ``run.py`` lays them out, and the bytes are per chip; a
chunk program that hands its scratch cache back in another layout than
it took is compiled again for the layout the later chunks take.
"""
from __future__ import annotations

import argparse
import functools
import os
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--slots", type=int, default=None,
                    help="compile for this many slots, not the traffic's")
    args = ap.parse_args(argv)
    import contextlib

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding
    from repro import numerics
    from repro.models import get_model
    from repro.parallel import ctx as pctx
    from repro.serving import engine as eng_mod
    from repro.serving.kv_cache import write_span_pages

    from chipbench import weights

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.cell(args.workload)
    config, mix = cell["config"], cell["traffic"]
    if args.slots:
        mix["engine"]["max_slots"] = args.slots
    cfg = spec.model_config(config)
    sizes = run.engine_sizes(config, mix)
    run.validate(mix, sizes)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    mesh = run.make_mesh(config, topo.devices)
    rep = (SingleDeviceSharding(topo.devices[0]) if mesh is None
           else NamedSharding(mesh, PartitionSpec()))
    model = get_model(cfg)
    nc = run.numerics_for(config, mix).replace(force=True, interpret=False)

    def on(tree, shardings=None):
        """Shapes of ``tree``, laid out by ``shardings`` (a matching
        tree), or replicated."""
        if shardings is None:
            shardings = jax.tree.map(lambda _: rep, tree)
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), tree, shardings)

    params = on(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                weights.shardings(cfg, mesh))
    pool_shapes = jax.eval_shape(functools.partial(
        model.init_paged_cache, sizes["num_pages"], sizes["page_size"],
        dtype=jnp.dtype(sizes["cache_dtype"])))
    pools = on(pool_shapes, None if mesh is None else jax.tree.map(
        lambda s: NamedSharding(mesh, eng_mod._pool_spec(s.shape, mesh)),
        pool_shapes))
    B, maxp = sizes["max_slots"], sizes["max_pages_per_slot"]
    where = "" if mesh is None else f" per chip of {spec.mesh_size(config)}"

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=rep)

    def report(name, fn, donate, *a):
        t = time.perf_counter()
        scope = (contextlib.nullcontext() if mesh is None
                 else pctx.use_mesh(mesh))
        with numerics.use(nc), scope:
            c = jax.jit(fn, donate_argnums=donate).lower(*a).compile()
        m = c.memory_analysis()
        print(f"{name}: compiled in {time.perf_counter() - t:.1f} s; "
              f"arguments {m.argument_size_in_bytes / 1e9:.3f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{m.alias_size_in_bytes / 1e9:.3f} GB{where}; fused kernels: "
              f"{c.as_text().count('tpu_custom_call')}", flush=True)
        return c

    i32, f32 = jnp.int32, jnp.float32
    report(f"decode step (slots {B}, block table {maxp})",
           functools.partial(eng_mod._decode_and_sample, model=model,
                             cfg=cfg), (1,),
           params, pools, sds((B, maxp), i32), sds((B,), i32),
           sds((B,), i32), sds((B,), f32), sds((B,), i32), sds((B,), f32),
           sds((B, 2), jnp.uint32), sds((B,), jnp.bool_))
    C = int(mix["engine"]["chunked_prefill"])
    for L in mix["prompt_lengths"]:
        scratch = on(jax.eval_shape(functools.partial(
            model.init_cache, 1, int(L), dtype=f32)))
        c = report(f"prefill chunk {C} of {L}",
                   lambda p, c, t, s: model.prefill_chunk(p, c, t, s), (),
                   params, scratch, sds((1, C), i32), sds((), i32))
        later = on(scratch, c.output_shardings[1])
        if later != scratch:
            scratch = later
            report(f"later prefill chunk {C} of {L}",
                   lambda p, c, t, s: model.prefill_chunk(p, c, t, s), (),
                   params, scratch, sds((1, C), i32), sds((), i32))
        report(f"chunk page write of {L}", write_span_pages.__wrapped__,
               (0,), pools, scratch,
               sds((), i32), sds((C // sizes["page_size"],), i32))
    return 0


if __name__ == "__main__":
    sys.exit(main())
