"""Object tier (DESIGN.md § Object tier): DJXPerf-style registry with
allocation-site provenance + OJXPerf-style replica detection, and the
content-addressed dedup that turns the replica findings into zero.

The acceptance pair at the bottom is the PR's story: a duplicated-prefix
trace whose duplicates land in the SAME burst (dispatched before either
publishes, with the prefix ending mid-page so granularity boundaries
mismatch) produces bit-identical KV pages across replicas that the
PrefixIndex missed — and the router+engine ``content_dedup`` drives the
cross-replica bytes to exactly 0 with greedy outputs unchanged.
"""
import jax
import numpy as np
import pytest

from repro.configs import registry as arch_registry
from repro.core.findings import TIER_OBJECT, WasteProfile
from repro.core.objects import ObjectRegistry, register_tree
from repro.core.replicas import (FIXES, ReplicaDetector,
                                 cross_replica_bytes, object_digest)
from repro.models.zoo import build_model
from repro.serve.decode import StepCache
from repro.serve.engine import Request, ServeEngine
from repro.serve.router import FleetRouter
from repro.serve.workload import make_trace


# ----------------------------------------------------------------------
# Registry basics: provenance, lifecycle, ownership
# ----------------------------------------------------------------------
def test_registry_provenance_and_lifecycle():
    reg = ObjectRegistry()
    rec = reg.register("replica0/kv/page3", "kv_page", 4096)
    assert rec.site.startswith("test_objects.py:")
    assert rec.func == "test_registry_provenance_and_lifecycle"
    assert rec.owner == "replica0"
    assert rec.object_key == f"kv_page|replica0/kv/page3|{rec.site}"
    assert len(reg) == 1 and reg.get(rec.oid) is rec
    assert reg.nbytes_live("kv_page") == 4096
    reg.release(rec.oid)
    assert len(reg) == 0 and reg.get(rec.oid) is None
    reg.release(rec.oid)                 # double release is a no-op


def test_register_tree_names_and_reader():
    reg = ObjectRegistry()
    tree = {"a": {"w": np.ones((4, 4), np.float32)},
            "b": np.zeros((8,), np.float32)}
    recs = register_tree(reg, "replica1/params", tree)
    names = {r.name for r in recs}
    assert "replica1/params/a.w" in names
    assert all(r.kind == "param" for r in recs)
    assert all(r.owner == "replica1" for r in recs)
    w = next(r for r in recs if r.name.endswith("a.w"))
    assert np.array_equal(w.reader(), np.ones((4, 4), np.float32))
    assert register_tree(None, "x", tree) == []   # registry off: no-op


# ----------------------------------------------------------------------
# Content digest: replicas always collide, non-replicas don't
# ----------------------------------------------------------------------
def test_object_digest_small_and_sampled():
    rng = np.random.RandomState(0)
    small = rng.rand(100).astype(np.float32)
    assert object_digest(small) == object_digest(small.copy())
    other = small.copy()
    other[50] += 1.0
    assert object_digest(small) != object_digest(other)
    # shape/dtype qualify the digest even for identical bytes
    assert object_digest(small) != object_digest(small.reshape(4, 25))
    assert (object_digest(np.zeros(8, np.float32))
            != object_digest(np.zeros(8, np.int32)))
    # large buffers hash sampled chunks: identical still collides,
    # a differing tail (the near-duplicate KV suffix case) never does
    big = rng.rand(1 << 16).astype(np.float64)       # 512 KB > _FULL_BELOW
    assert object_digest(big) == object_digest(big.copy())
    tail = big.copy()
    tail[-1] += 1.0
    assert object_digest(big) != object_digest(tail)


# ----------------------------------------------------------------------
# Replica detector: weights duplicated across fleet replicas
# ----------------------------------------------------------------------
def test_weight_replicas_across_two_replicas():
    reg = ObjectRegistry()
    tree = {"wq": np.arange(64, dtype=np.float32),
            "wk": np.arange(64, dtype=np.float32) * 2}
    register_tree(reg, "replica0/params", tree)
    register_tree(reg, "replica1/params", tree)
    prof = ReplicaDetector(reg).scan()
    groups = [f for f in prof.findings if f.kind == "replica_param"]
    assert len(groups) == 2              # wq pair + wk pair
    for f in groups:
        assert f.tier == TIER_OBJECT
        assert f.count == 1 and f.bytes == 256.0
        assert f.meta["cross_replica"] is True
        assert f.meta["replicas"] == ["replica0", "replica1"]
        assert f.meta["fix"] == FIXES["replica_param"]
        assert f.meta["file"].endswith("test_objects.py")
    # duplicate bytes also billed to the object table (DJXPerf view)
    assert cross_replica_bytes(prof, "replica_param") == 512.0
    billed = {r["name"] for r in prof.top_objects()}
    assert billed == {"replica1/params/wq", "replica1/params/wk"}
    assert "top objects by attributed waste" in prof.render(by="object")


def test_identical_zero_opt_state_is_replica_but_zero_kv_page_is_not():
    reg = ObjectRegistry()
    z = np.zeros(32, np.float32)
    reg.register("opt/m/w", "opt_state", z.nbytes, reader=lambda: z)
    reg.register("opt/v/w", "opt_state", z.nbytes, reader=lambda: z)
    reg.register("replica0/kv/page0", "kv_page", z.nbytes,
                 reader=lambda: z)
    reg.register("replica1/kv/page0", "kv_page", z.nbytes,
                 reader=lambda: z)
    prof = ReplicaDetector(reg).scan()
    kinds = {f.kind for f in prof.findings}
    # zero moments ARE the lazy-materialize finding; all-zero KV pages
    # are unwritten budget capacity, skipped rather than flagged
    assert kinds == {"replica_opt_state"}


def test_scan_profile_merges_and_roundtrips():
    reg = ObjectRegistry()
    a = np.arange(128, dtype=np.float32)
    register_tree(reg, "replica0/params", {"w": a})
    register_tree(reg, "replica1/params", {"w": a})
    prof = ReplicaDetector(reg).scan()
    again = WasteProfile.from_json(prof.to_json())
    assert again.to_json() == prof.to_json()
    merged = WasteProfile(tier=TIER_OBJECT)
    merged.merge(prof)
    merged.merge(prof)
    f = next(f for f in merged.findings if f.kind == "replica_param")
    assert f.count == 2                  # §5.6 coalescing across scans
    row = merged.top_objects(1)[0]
    assert row["waste"]["replica"] == 2 * a.nbytes


# ----------------------------------------------------------------------
# Acceptance: same-burst duplicated prefixes at mismatched page
# boundaries -> cross-replica KV page replicas; content dedup -> zero
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def fleet_env():
    cfg = arch_registry.get_config("qwen3-1.7b").smoke()
    model = build_model(cfg)
    # cast once, as a fleet does: the replicas share one compute-dtype tree
    params = model.compute_params(model.init(jax.random.PRNGKey(0)))
    # every burst is a same-tick pair of duplicates; the 36-token shared
    # prefix ends mid-page (page_size 8), the OJXPerf granularity-
    # boundary mismatch the pow2/page candidate ladder alone won't probe
    trace = make_trace(n_requests=6, vocab_size=cfg.vocab_size, seed=0,
                       arrival="bursty", burst_size=2, burst_gap=4,
                       prompt_len=(48, 48), gen_len=(4, 4), dup_rate=1.0,
                       n_prefixes=1, prefix_len=36)
    return model, params, trace, StepCache(model)


def _run_fleet(model, params, trace, step_cache, *, dedup):
    max_len = trace.max_prompt_len + trace.max_new_tokens + 1
    reg = ObjectRegistry()
    engines = [ServeEngine(model, params, num_slots=2, max_len=max_len,
                           kv_layout="paged", page_size=8,
                           num_pages=4 * (-(-max_len // 8)),
                           step_cache=step_cache, registry=reg,
                           owner=f"replica{i}", content_dedup=dedup)
               for i in range(2)]
    fleet = FleetRouter(engines, policy="prefix", seed=0,
                        content_dedup=dedup)
    fleet.submit_trace(trace)
    fleet.run()
    fleet.check()
    scan = ReplicaDetector(reg).scan()
    outs = {rid: list(r.generated) for rid, r in fleet.finished.items()}
    return fleet, scan, outs


def _single_outputs(model, params, trace, step_cache):
    max_len = trace.max_prompt_len + trace.max_new_tokens + 1
    eng = ServeEngine(model, params, num_slots=4, max_len=max_len,
                      kv_layout="paged", page_size=8,
                      step_cache=step_cache)
    for tr in sorted(trace.requests, key=lambda r: r.arrival):
        eng.submit(Request(rid=tr.rid, tokens=np.asarray(tr.tokens),
                           max_new_tokens=tr.max_new_tokens))
    eng.run()
    return {rid: list(r.generated) for rid, r in eng.finished.items()}


def test_same_burst_duplicates_make_cross_replica_kv_replicas(fleet_env):
    model, params, trace, sc = fleet_env
    fleet, scan, _ = _run_fleet(model, params, trace, sc, dedup=False)
    kv = [f for f in scan.findings
          if f.kind == "replica_kv_page" and f.meta["cross_replica"]]
    assert kv, "expected cross-replica duplicate KV pages pre-dedup"
    assert cross_replica_bytes(scan, "replica_kv_page") > 0
    for f in kv:
        # provenance points at the page allocator, the actionable site
        assert f.meta["file"].endswith("kv_cache.py")
        assert f.meta["fix"] == FIXES["replica_kv_page"]
    assert fleet.stats["content_dedup_routes"] == 0


def test_content_dedup_drives_cross_replica_kv_bytes_to_zero(fleet_env):
    model, params, trace, sc = fleet_env
    fleet, scan, outs = _run_fleet(model, params, trace, sc, dedup=True)
    assert cross_replica_bytes(scan, "replica_kv_page") == 0
    # the fix actually fired: at least one duplicate was co-located and
    # at least one same-group follower was deferred into an index hit
    assert fleet.stats["content_dedup_routes"] >= 1
    assert sum(e.stats["dedup_deferred"] for e in fleet.engines) >= 1
    # and the outputs are exactly the single-engine greedy stream
    assert outs == _single_outputs(model, params, trace, sc)
