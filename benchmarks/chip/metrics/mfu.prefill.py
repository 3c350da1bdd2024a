"""Model step (admission prefill): the forward operations the prompt
suffixes admitted inside the traced window needed (``flops.
prefill_flops``: every layer weight once per row, causal attention over
each row's keys, the head once per prompt), over the traced window's
length times the chip's bf16 peak."""
from benchmarks.chip import flops


def read(run):
    c, m = run["counters"], run["model"]
    pre = [r for s in c["steps_in_trace"] for r in s.admitted]
    if not pre or not c.get("trace_host_s"):
        return None
    work = sum(flops.prefill_flops(m, start, n) for start, n in pre)
    return 100.0 * work / (c["trace_host_s"]
                           * run["peaks"]["bf16_flops_per_s"])
