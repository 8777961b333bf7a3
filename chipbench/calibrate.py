"""Readings from which a cell's limits are set, on the chip.

    python chipbench/calibrate.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 [--fault-seeds 4,5,6] --seconds 15

In one process, runs the cell once per seed (a short window at the
cell's own load, the same code path as ``run.py``) and prints the
program's compared numbers.  On the control seeds it also runs the
program with its own matmul path one step below the configuration's
(``CONTROL_POLICY``: three bf16 passes in place of the six of
``tcec_bf16x6``), and puts the reference in the program's place over the
program's sample in the steps below what the configuration states:
``high`` (three bf16 passes in place of float32 at the highest
precision) and a bfloat16 K/V cache (in place of the float32 pool).  On
the fault seeds it runs the cell under each planted fault.  One JSON
line per reading.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import check, faults, run, spec  # noqa: E402

CONTROL_POLICY = "tcec_bf16x3"
CONTROLS = {"high": {"precision": "high", "kv_dtype": "float32"},
            "bf16_cache": {"precision": "highest", "kv_dtype": "bfloat16"}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="",
                    help="also run these seeds under planted faults")
    ap.add_argument("--faults", default="",
                    help="comma-separated; default: every fault the cell "
                    "can have")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    try:
        devices = run.require_chips(int(cell["workload"]["chips"]))
    except run.NoChip as exc:
        run.log(f"calibrate: {exc}")
        return 3
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    ctl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    chips = int(cell["workload"]["chips"])
    devices = devices[:chips]
    planted = faults.for_chips(chips)
    mesh = run.make_mesh(cell["config"], devices)
    for seed in [int(s) for s in args.seeds.split(",")]:
        kept = []
        res = run.run_cell(cell, seed, args.seconds, False, devices,
                           keep_sample=kept)
        row = {"seed": seed, "program": {k: v["value"] for k, v in
                                         res["check"].items()},
               **{k: res["compared"][k] for k in ("v_rel_err", "layer0_k_v")},
               "correct": res["correct"], "metrics": res["metrics"]}
        if seed in ctl_seeds:
            low = copy.deepcopy(cell)
            low["config"]["numerics"]["policy"] = CONTROL_POLICY
            ctl = run.run_cell(low, seed, args.seconds, False, devices,
                               keep_sample=[])
            row[CONTROL_POLICY] = {
                **{k: v["value"] for k, v in ctl["check"].items()},
                **{k: ctl["compared"][k] for k in ("v_rel_err",
                                                   "layer0_k_v")},
                "correct": ctl["correct"]}
            sample, cfg = kept
            config = cell["config"]
            ref = spec.load_module(spec.reference_path(config["reference"]),
                                   "chipbench_reference")
            w = run.reference_weights(cfg, seed, mesh)
            for name, ctl in CONTROLS.items():
                got = check.compare(ref, w, config["hf_config"], sample,
                                    control=ctl)
                row[name] = {k: got[k] for k in ("token_gap", "kv_rel_err",
                                                 "v_rel_err", "layer0_k_v")}
            del w
        del kept
        print(json.dumps(row), flush=True)
    for seed in [int(s) for s in args.fault_seeds.split(",") if s]:
        for name in args.faults.split(",") if args.faults else planted:
            with planted[name]():
                res = run.run_cell(cell, seed, args.seconds, False, devices)
            print(json.dumps({"seed": seed, "fault": name,
                              "correct": res["correct"],
                              "numbers": {k: v["value"] for k, v in
                                          res["check"].items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
