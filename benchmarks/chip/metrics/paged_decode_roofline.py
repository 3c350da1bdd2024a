"""Kernels: ``paged_decode_attention``'s share of its roofline over the
traced window. Numerator: for every engine tick inside the traced window,
the least time the chip needs for each layer's call (``flops.
paged_decode`` over the live slots' contexts, by the larger of the
compute and the memory bound); denominator: the durations of the
kernel's ops in the device trace. Decode attention reads the whole
attended history per token, so the memory bound holds."""
from benchmarks.chip import flops


def read(run):
    red, m, pk = run["reduction"], run["model"], run["peaks"]
    kern = red.kernel_seconds("paged_decode_attention")
    ticks = [s.tick_ctx for s in run["counters"]["steps_in_trace"]
             if s.tick_ctx]
    if kern <= 0 or not ticks:
        return None
    need = 0.0
    for ctx in ticks:
        f, b = flops.paged_decode(ctx, m["num_heads"], m["num_kv_heads"],
                                  m["head_dim"], run["pool_itemsize"])
        need += m["num_layers"] * flops.roofline_s(f, b, pk)[0]
    return 100.0 * need / kern
