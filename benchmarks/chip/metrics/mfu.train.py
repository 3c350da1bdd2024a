"""Train step: forward and backward operations per token from shapes
(``flops.train_token_flops``, recomputation not counted) times the
tokens of the steps inside the traced window, over its length times the
chip's bf16 peak."""
from benchmarks.chip import flops


def read(run):
    c, m = run["counters"], run["model"]
    if not c.get("steps_in_trace") or not c.get("trace_host_s"):
        return None
    work = (c["steps_in_trace"] * c["tokens_per_step"]
            * flops.train_token_flops(m, c["seq"]))
    return 100.0 * work / (c["trace_host_s"]
                           * run["peaks"]["bf16_flops_per_s"])
