"""The trace reduction, on hand-made events and on a recorded trace."""
from pathlib import Path

import pytest

from benchmarks.chip import trace

DATA = Path(__file__).resolve().parent / "data"
D0, D1 = "/device:TPU:0", "/device:TPU:1"


def test_busy_union_kernels_and_gaps():
    spans = [("bench.traced", 0, 100), ("bench.engine_step", 0, 60),
             ("bench.wait_arrival", 60, 100)]
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10, 30),
           ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 20, 40),  # overlap
           ("%paged_decode_attention.7 = (bf16[4,8]{1,0}) custom-call()",
            50, 55),
           ("%while.2 = (s32[]) while()", 90, 120),         # a container
           ("%fusion.3 = f32[8]{0} fusion()", 90, 120)]     # clipped
    r = trace.reduce_events(spans, {D0: ops, "/device:TPU:9": []},
                            kernels=("paged_decode_attention",))
    assert r.devices == 1
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx((30 + 5 + 10) * 1e-9)
    assert r.idle_share == pytest.approx(0.55)
    assert r.kernel_seconds("paged_decode_attention") == pytest.approx(5e-9)
    assert r.ops["fusion.1 = f32[8]"] == pytest.approx(40e-9)
    assert r.ops["fusion.3 = f32[8]"] == pytest.approx(10e-9)
    assert not any(k.startswith("while") for k in r.ops)
    # gaps [0,10) [40,50) [55,90): the first two inside the step span,
    # the last one's middle while the host waited for an arrival
    assert r.gaps == [("bench.engine_step", pytest.approx(10e-9)),
                      ("bench.engine_step", pytest.approx(10e-9)),
                      ("bench.wait_arrival", pytest.approx(35e-9))]
    b = r.breakdown()
    assert b["device_ops"][0][0] == "fusion.1 = f32[8]"
    assert b["idle_gaps"][0] == ["bench.wait_arrival", pytest.approx(35e-9)]


def test_busy_is_averaged_over_devices():
    ops0 = [("a.1", 0, 50)]
    ops1 = [("a.1", 0, 100)]
    r = trace.reduce_events([("bench.traced", 0, 100)], {D0: ops0, D1: ops1})
    assert r.devices == 2 and r.busy_s == pytest.approx(75e-9)


def test_no_device_ops_reads_nothing():
    assert trace.reduce_events([("bench.traced", 0, 10)], {D0: []}) is None


def test_recorded_trace():
    """``data/small.xplane.pb`` was recorded on one TPU v5e chip: three
    host spans ``bench.serve_step``, each around one jitted
    ``paged_decode_attention`` call (4 slots, 16 q / 8 kv heads, 8 pages
    of 16 rows) and a jitted 3-layer scan of 256 x 512 x 512 matmuls. The
    device plane's ``XLA Ops`` line names each op by its HLO text, and
    puts the ``while`` op of the scan around its body's ops."""
    r = trace.reduce(str(DATA / "small.xplane.pb"),
                     kernels=("paged_decode_attention",))
    assert r.devices == 1
    calls = r.kernels["paged_decode_attention"]
    assert len(calls) == 3
    assert all(20e-6 < d < 40e-6 for _, d in calls)
    assert r.kernel_seconds("paged_decode_attention") < r.busy_s < r.window_s
    assert 5e-3 < r.window_s < 20e-3
    top = r.breakdown()["device_ops"][0][0]
    assert top.startswith("paged_decode_attention.")
    assert not any(k.startswith("while") for k in r.ops)
    assert "bench.serve_step" in {n for n, _ in r.gaps}


def test_unaligned_host_window_falls_back_to_device_extent():
    r = trace.reduce_events([("bench.traced", 1000, 2000)],
                            {D0: [("a.1", 0, 40), ("b.2", 60, 100)]})
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(80e-9)
