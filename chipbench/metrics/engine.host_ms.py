"""Mean host self time of an engine step in the traced span, in ms: each
``engine.step`` span of the program lying wholly inside the span, less
the ``engine.sync`` spans nested in it, where the host waits on the
device.  None where the program records no ``engine.step`` span.

Also logs the device's idle time by the host phase it fell in
(:func:`spans.idle_by_phase`)."""
import json
import sys

from chipbench import spans


def read(ctx):
    evs = spans.program_spans(ctx)
    self_ms = spans.step_self_ms(evs, *ctx.win)
    if not self_ms:
        return None
    print("chipbench: idle_by_phase_s "
          + json.dumps(spans.idle_by_phase(ctx, evs)), file=sys.stderr,
          flush=True)
    return sum(self_ms) / len(self_ms)
