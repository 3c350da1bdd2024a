"""Kernels: ``paged_window_attention``'s share of its roofline over the
traced window: for every admission prefill inside it, the least time
each layer's call needs for the admitted rows (``flops.paged_window``
over each slot's cached prefix and prompt suffix, the larger bound),
over the durations of the kernel's ops in the device trace. Padding rows
of the power-of-two bucket and idle slots are work the kernel does and
the algorithm does not need."""
from benchmarks.chip import flops


def read(run):
    red, m, pk = run["reduction"], run["model"], run["peaks"]
    kern = red.kernel_seconds("paged_window_attention")
    pre = [s.admitted for s in run["counters"]["steps_in_trace"]
           if s.admitted]
    if kern <= 0 or not pre:
        return None
    need = 0.0
    for rows in pre:
        f, b = flops.paged_window(rows, m["num_heads"], m["num_kv_heads"],
                                  m["head_dim"], run["pool_itemsize"])
        need += m["num_layers"] * flops.roofline_s(f, b, pk)[0]
    return 100.0 * need / kern
