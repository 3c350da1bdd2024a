"""The engine's phase spans in a profiler trace, the reduction that labels
idle gaps by them, and the readers of the metrics built on them."""
import dataclasses
import glob

import jax
import numpy as np
import pytest

from benchmarks.chip import phases, spec, trace

D0 = "/device:TPU:0"
N_STEPS = 4
PHASES = ("serve.admit", "serve.prefill", "serve.decode", "serve.accept")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny paged engine stepped ``N_STEPS`` times under the profiler:
    two admissions with their prefills, four ticks. Returns the engine
    and the spans of its ``.xplane.pb``."""
    from repro.configs import registry
    from repro.models.zoo import build_model
    from repro.serve.engine import Request, ServeEngine
    cfg = dataclasses.replace(registry.get_config("qwen3-1.7b").smoke(),
                              dtype="float32")
    model = build_model(cfg)
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)),
                      num_slots=2, max_len=32, kv_layout="paged",
                      page_size=8)
    rng = np.random.RandomState(0)
    for i in range(3):
        eng.submit(Request(rid=f"r{i}", max_new_tokens=3,
                           tokens=rng.randint(0, cfg.vocab_size, 6 + i)
                           .astype(np.int32)))
    out = tmp_path_factory.mktemp("engine_trace")
    jax.profiler.start_trace(str(out))
    try:
        for _ in range(N_STEPS):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    return eng, phases.read_events(path)[0]


def test_one_step_span_per_step_with_its_phases_inside(traced):
    eng, spans = traced
    steps = [(s, e) for n, s, e in spans if n == phases.STEP_SPAN]
    assert len(steps) == N_STEPS == eng.stats["steps"]
    names = {n for n, _, _ in spans}
    assert set(PHASES) <= names
    assert "serve.jxperf" not in names          # no JXPerf attached
    for n, s, e in spans:
        if n in PHASES:
            assert any(s0 <= s and e <= e0 for s0, e0 in steps), n
    prefills = [n for n, _, _ in spans if n == "serve.prefill"]
    assert len(prefills) == eng.stats["prefills"] == 2


def test_step_time_holds_its_phases(traced):
    st = traced[0].stats
    assert st["step_s"] >= (st["admit_s"] + st["prefill_s"]
                            + st["decode_s"] + st["accept_s"])
    assert all(st[k] > 0 for k in ("admit_s", "prefill_s", "decode_s",
                                   "accept_s", "step_cpu_s"))
    assert st["jxperf_s"] == 0.0


# a step's phases nested in the benchmark's span: gaps at 0-12 (admit),
# 28-42 (the tick's dispatch), 49-52 (accept) and 56-100 (no step)
SPANS = [("bench.traced", 0, 100), ("bench.engine_step", 0, 60),
         ("serve.step", 1, 59), ("serve.admit", 1, 10),
         ("serve.prefill", 10, 30), ("serve.accept", 30, 34),
         ("serve.decode", 34, 50), ("serve.accept", 50, 58),
         ("bench.wait_arrival", 60, 100)]
OPS = [("%prefill.1 = f32[8]{0} fusion()", 12, 28),
       ("%tick.2 = f32[8]{0} fusion()", 42, 49),
       ("%accept.3 = f32[8]{0} fusion()", 52, 56)]


def hand_made():
    return trace.reduce_events(SPANS, {D0: OPS})


def test_gaps_are_labelled_by_the_engine_phase():
    r = hand_made()
    assert r.gaps == [("serve.admit", pytest.approx(12e-9)),
                      ("serve.decode", pytest.approx(14e-9)),
                      ("serve.accept", pytest.approx(3e-9)),
                      ("bench.wait_arrival", pytest.approx(44e-9))]
    assert phases.steps(r) == 1
    assert list(phases.idle_by_label(r)) == [
        "bench.wait_arrival", "serve.decode", "serve.admit", "serve.accept"]


def test_the_run_trace_is_found_by_its_reduction(tmp_path, monkeypatch):
    """Of the traces under the temporary directory, the one whose window
    and busy time are the run's own; None when none is."""
    monkeypatch.setattr(phases.tempfile, "tempdir", str(tmp_path))
    events = {}
    for name, ops in (("a", OPS), ("b", OPS[:2])):
        d = tmp_path / f"chip_trace_{name}" / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
        events[str(d / "host.xplane.pb")] = (SPANS, {D0: ops})
    monkeypatch.setattr(phases, "read_events", lambda p: events[p])
    run = {"reduction": trace.reduce_events(SPANS, {D0: OPS[:2]})}
    found = phases.reduction(run)
    assert found is run["phases"]
    assert found.busy_s == run["reduction"].busy_s
    assert found.gaps[0][0] == "serve.admit"
    other = {"reduction": trace.reduce_events(SPANS, {D0: OPS[1:]})}
    assert phases.reduction(other) is None


def test_step_idle_ms_reader():
    read = spec.load_reader("step_idle_ms")
    assert read({"phases": hand_made()}) == pytest.approx(1e3 * 29e-9)
    assert read({"phases": None}) is None
    # the parent's trace: no serve.step span
    bench_only = [s for s in SPANS if s[0].startswith("bench.")]
    assert read({"phases": trace.reduce_events(bench_only,
                                               {D0: OPS})}) is None


def counters(**st):
    return {"counters": {"stats_before_trace": st}}


def test_step_host_ms_reader():
    read = spec.load_reader("step_host_ms")
    st = dict(steps=10, step_s=0.5, prefill_s=0.1, decode_s=0.3)
    assert read(counters(**st)) == pytest.approx(10.0)
    assert read(counters(**{**st, "steps": 0})) is None
    # the parent's engine: no step counters
    assert read(counters(prefill_s=0.1, decode_s=0.3)) is None


def test_step_jxperf_ms_reader():
    read = spec.load_reader("step_jxperf_ms")
    assert read(counters(steps=10, jxperf_s=0.02)) == pytest.approx(2.0)
    assert read(counters(steps=10, jxperf_s=0.0)) is None   # no profiler
    assert read(counters(prefill_s=0.1)) is None             # the parent
