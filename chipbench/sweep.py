"""Find the highest arrival rate a cell's engine sustains, on the chip.

    python chipbench/sweep.py --workload <name> --rates 1,2,3 \
        --seconds 20 [--seed 1]

Builds and warms the cell once, then offers its traffic at each rate in
turn, for ``--seconds`` after a pre-roll, draining the engine between
rates.  For each rate it prints the tokens completed per second, the
time-to-first-token and inter-token percentiles, and the waiting queue
at the middle and at the end of the window: a queue that grows from the
middle to the end means the rate is above what the engine sustains.  The
cell's traffic file then fixes one rate; this tool is only for choosing
it.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import run, spec, traffic  # noqa: E402


def one_rate(engine, mix: dict, rate: float, seconds: float, seed: int,
             vocab: int) -> dict:
    mix = dict(mix, rate_per_s=rate)
    rec = run.Recorder(engine)
    ws = float(mix["preroll_s"])
    we = ws + seconds
    stream = traffic.Stream(mix, seed, vocab, (ws, seconds))
    clock0 = time.perf_counter()
    lateness = []
    run.drive(rec, stream, clock0, ws + seconds / 2, lateness=lateness)
    q_mid = len(engine.sched.waiting)
    run.drive(rec, stream, clock0, we, lateness=lateness)
    q_end = len(engine.sched.waiting)
    e2e, extra = run.e2e_metrics(rec, ws, we, time.perf_counter() - clock0)
    # drain what is left before the next rate
    while engine.sched.has_work:
        engine.step()
    return {"rate_per_s": rate, **e2e, **extra, "waiting_mid": q_mid,
            "waiting_end": q_end,
            "generator_late_p95_ms": 1e3 * run.pct(lateness, 95)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    try:
        run.require_chips(int(cell["workload"]["chips"]))
    except run.NoChip as exc:
        run.log(f"sweep: {exc}")
        return 3
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    cfg, engine, _ = run.build(cell, args.seed)
    run.warm(engine, cell["traffic"], cfg.vocab_size)
    for rate in [float(r) for r in args.rates.split(",")]:
        row = one_rate(engine, cell["traffic"], rate, args.seconds,
                       args.seed, cfg.vocab_size)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
