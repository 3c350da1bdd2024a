"""Model step (decode): the operations the decode ticks inside the
traced window needed (``flops.decode_token_flops`` at each live slot's
context), over the traced window's length times the chip's bf16 peak."""
from benchmarks.chip import flops


def read(run):
    c, m = run["counters"], run["model"]
    ticks = [s.tick_ctx for s in c["steps_in_trace"] if s.tick_ctx]
    if not ticks or not c.get("trace_host_s"):
        return None
    work = sum(flops.decode_token_flops(m, x) for t in ticks for x in t)
    return 100.0 * work / (c["trace_host_s"]
                           * run["peaks"]["bf16_flops_per_s"])
