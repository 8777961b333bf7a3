"""Share (%) of the traced span in which a chip's ``XLA Ops`` line runs a
collective: an all-reduce, all-gather, reduce-scatter, collective-permute
or all-to-all, whole, as its ``-start`` and ``-done`` halves, or as an
async fusion named after it.  The union of those events' intervals on
each chip, over the span, averaged over the cell's chips.  Time in which
a collective is in flight between its halves while other ops run on the
chip is not counted: only time the op line spends in collective ops.
A cell on one chip has no collectives and reads 0."""
import re

from chipbench import trace_reduce

COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|ragged-all-to-all|"
                        r"async-collective)")


def read(ctx):
    lo, hi = ctx.win
    shares = [trace_reduce.busy([e for e in evs
                                 if COLLECTIVE.match(e.name)], lo, hi)
              / (hi - lo) for evs in ctx.trace.ops.values()]
    return 100.0 * sum(shares) / len(shares) if shares else None
