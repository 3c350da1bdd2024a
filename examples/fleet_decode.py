"""Fleet serving example: two `ServeEngine` replicas behind the
prefix-aware router, fed a duplicated-prefix trace.

Quickstart (CPU):

    PYTHONPATH=src python examples/fleet_decode.py --arch qwen3-1.7b

What it demonstrates:

  * ``serve.workload.duplicated_prefix_trace`` — a seeded, replayable
    request trace (bursty arrivals, 80% of prompts share one system
    prefix) that serializes to JSON (``--trace-out``);
  * ``serve.global_prefix.GlobalPrefixIndex`` — after the first replica
    prefills the shared prefix, its pages are published fleet-wide
    (pinned through the owner's allocator, refcount-safe);
  * ``serve.router.FleetRouter`` with ``policy="prefix"`` — later
    duplicates route to the replica that already holds the prefix (a
    dispatch lease keeps the pages alive until admission) and prefill
    only their unique suffix, instead of re-paying the prefix on
    whichever replica load balancing would have picked;
  * the same trace under ``policy="random"`` re-prefills the resident
    prefix — the fleet-level Def.-3 ``fleet_silent_prefix_load`` bytes
    the router charges and prefix routing eliminates;
  * both policies emit greedy outputs bit-identical to one big engine.
"""
import argparse

import jax
import numpy as np

from repro.configs import registry
from repro.models.zoo import build_model
from repro.serve.decode import StepCache
from repro.serve.engine import Request, ServeEngine
from repro.serve.router import FleetRouter
from repro.serve.workload import duplicated_prefix_trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b",
                    choices=registry.ARCH_IDS)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None)
    args = ap.parse_args()

    cfg = registry.get_config(args.arch).smoke()
    model = build_model(cfg)
    # cast once: every replica and the single engine share this tree
    params = model.compute_params(model.init(jax.random.PRNGKey(args.seed)))

    # staggered arrivals (one request every few ticks): each duplicate
    # lands after the prefix was published, so the policies differ only
    # in WHERE they send it — the waste comparison below is pure routing
    trace = duplicated_prefix_trace(
        n_requests=args.requests, vocab_size=cfg.vocab_size,
        seed=args.seed, prompt_len=32, prefix_len=24, gen=6,
        burst_size=1, burst_gap=3)
    if args.trace_out:
        trace.save(args.trace_out)
        print(f"trace written to {args.trace_out}")
    max_len = trace.max_prompt_len + trace.max_new_tokens + 1
    page_size = 8
    pages = 4 * (-(-max_len // page_size))   # 2 slots + pin headroom
    step_cache = StepCache(model)            # one compile set, all fleets

    def build_fleet(policy):
        engines = [ServeEngine(model, params, num_slots=2, max_len=max_len,
                               kv_layout="paged", page_size=page_size,
                               num_pages=pages, step_cache=step_cache)
                   for _ in range(args.replicas)]
        fleet = FleetRouter(engines, policy=policy, seed=args.seed)
        fleet.submit_trace(trace)
        fleet.run()
        fleet.check()                        # fleet-wide refcount audit
        return fleet

    outputs = {}
    for policy in ("prefix", "random"):
        fleet = build_fleet(policy)
        outputs[policy] = {rid: list(r.generated)
                           for rid, r in fleet.finished.items()}
        s = fleet.stats
        print(f"[{policy:6s}] dispatched {s['dispatched']} | "
              f"prefix routes {s['prefix_routes']} "
              f"(cross-replica {s['cross_replica_prefix_routes']}) | "
              f"hit fraction {fleet.prefix_hit_fraction():.2f} | "
              f"fleet silent-prefix-load "
              f"{fleet.fleet_waste_bytes():.0f} bytes")

    single = ServeEngine(model, params, num_slots=2 * args.replicas,
                         max_len=max_len, kv_layout="paged",
                         page_size=page_size, step_cache=step_cache)
    for treq in sorted(trace.requests, key=lambda r: r.arrival):
        single.submit(Request(rid=treq.rid, tokens=np.asarray(treq.tokens),
                              max_new_tokens=treq.max_new_tokens))
    single.run()
    ref = {rid: list(r.generated) for rid, r in single.finished.items()}
    same = all(outputs[p] == ref for p in outputs)
    print(f"greedy outputs bit-identical to a single engine: {same}")
    assert same


if __name__ == "__main__":
    main()
