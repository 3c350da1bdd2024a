"""Continuous-batching serving engine (DESIGN.md §2, serving tier).

Production-shaped serving over a fixed-size decode batch:

  * **Batched prefill** — a request's whole prompt fills its KV-cache row
    in ONE jitted `model.prefill` call (not `prompt_len` sequential
    decode steps). Admission groups waiting requests into one padded
    prefill; non-admitted rows are merged back untouched.
  * **Per-slot positions** — the cache write index is a (B,) vector, so
    every slot sits at its own sequence offset: requests arrive, finish
    (EOS / max-new-tokens) and recycle their slot independently while
    the batch keeps stepping.
  * **Honest accounting** — prefill and decode token counts/times are
    tracked separately, decode throughput is measured over *live* slots
    only, and the padded (wasted) prefill tokens burned by power-of-two
    prompt bucketing are counted in `stats`.
  * **Waste detection → elimination** — in the default dense layout the
    decode batch writes K/V for every slot every tick whether or not it
    serves a request, and every duplicated prompt prefix is recomputed;
    `core.detectors.ServingDetectors` traps exactly that waste. With
    ``kv_layout="paged"`` the engine ELIMINATES it (serve/kv_cache.py):
    the cache becomes a refcounted page pool with per-slot page tables,
    idle/finished slots write nothing past their page-table extent
    (Def.-1/2 stores gone), recycling frees pages instead of rewriting
    rows, and a content-digest prefix index maps a duplicated prefix's
    pages into the new slot (copy-on-write for partial pages) instead of
    re-paying its K/V compute (the Def.-3 finding becomes a cache hit).

  * **Speculative decoding** — pass a ``drafter`` (serve/spec.py) and
    every decode tick becomes draft→verify→accept: the drafter proposes
    up to ``spec_k`` tokens per live slot, ONE width-(k+1) verify
    forward (`serve.decode.make_engine_verify` over `LM.verify`) scores
    them, and the greedy-consistent prefix plus a bonus token are
    emitted — outputs bit-identical to plain decode, up to k+1 tokens
    per tick. Rejected drafts are Def.-1 dead KV stores
    (`ServingDetectors.rejected_draft_store`); with
    ``spec_rollback=True`` on the paged layout the commit stops at the
    accept point (`LM.commit_verify`) and they never reach the pool.

The jitted tick/prefill come from `serve.decode`'s step factories
(sharding-context aware, so the engine composes with `tp_serve`). The
engine needs every sub-block to carry an indexed KV cache, so it
supports the "dense" and "moe" families; other families are served by
the legacy token-loop in `launch/serve.py`.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.detectors import ServingDetectors, SlotWrite, VerifyWrite
from repro.serve.decode import (make_engine_prefill, make_engine_tick,
                                make_engine_verify)
from repro.serve.kv_cache import (PagedKV, PoolExhausted, _digest,
                                  make_page_copy)

ENGINE_FAMILIES = ("dense", "moe")
KV_LAYOUTS = ("dense", "paged")


@dataclass
class Request:
    """One serving request: prompt in, greedy continuation out."""
    rid: str
    tokens: np.ndarray                 # (L,) int32 prompt
    max_new_tokens: int = 16
    arrival: int = 0                   # earliest engine step for admission
    # (length, pages) of this prompt's prefix in THIS replica's pool,
    # leased by the fleet router from the global prefix tier at dispatch
    # (serve/global_prefix.py); the engine consumes it at admission and
    # releases the lease
    prefix_hint: Optional[Any] = None
    # filled by the engine:
    generated: List[int] = field(default_factory=list)
    prefill_step: int = -1
    finish_step: int = -1
    reuse_len: int = 0                 # cached-prefix tokens mapped in

    @property
    def done(self) -> bool:
        return self.finish_step >= 0


class MonotonicStats(dict):
    """Engine counters that can only grow.

    The fleet aggregator (serve/router.py, benchmarks) reads periodic
    snapshots and sums per-replica DELTAS, so a counter that ever
    decreased — e.g. zeroed during a recycle sweep between generations —
    silently undercounts fleet totals (`padded_prefill_tokens` across
    generations was the reported symptom). Decrements now raise instead
    of corrupting downstream accounting; `dict(stats)` snapshots keep
    working."""

    def __setitem__(self, key, value):
        cur = self.get(key)
        if (cur is not None and isinstance(cur, (int, float))
                and isinstance(value, (int, float)) and value < cur):
            raise ValueError(
                f"engine stat {key!r} may not decrease ({cur} -> {value}); "
                f"fleet aggregation reads monotonic snapshots")
        super().__setitem__(key, value)


def _bucket(n: int, lo: int = 8) -> int:
    """Pad prompt groups to power-of-two lengths: bounded jit cache."""
    p = lo
    while p < n:
        p *= 2
    return p


class ServeEngine:
    """Fixed-size decode batch + waiting queue + slot recycling."""

    def __init__(self, model, params, *, num_slots: int = 4,
                 max_len: int = 128, eos_id: Optional[int] = None,
                 detectors: Optional[ServingDetectors] = None,
                 kv_dtype=jnp.float32, kv_layout: str = "dense",
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefix_window: int = 32, strategy=None,
                 drafter=None, spec_k: int = 4,
                 spec_rollback: bool = True,
                 kernel_counters: bool = False,
                 step_cache=None,
                 registry=None, owner: str = "engine",
                 content_dedup: bool = False):
        if model.cfg.family not in ENGINE_FAMILIES:
            raise ValueError(
                f"ServeEngine needs an indexed KV cache in every block; "
                f"family {model.cfg.family!r} is served by the legacy "
                f"token-loop driver")
        if kv_layout not in KV_LAYOUTS:
            raise ValueError(f"kv_layout must be one of {KV_LAYOUTS}")
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.detectors = detectors
        self.kv_layout = kv_layout
        self.paged = kv_layout == "paged"
        # speculative decoding: a drafter proposes up to spec_k tokens
        # per tick; one width-(k+1) verify forward accepts the greedy-
        # consistent prefix (outputs stay bit-identical to plain decode)
        self.drafter = drafter
        self.spec = drafter is not None
        if self.spec:
            assert spec_k >= 1, "spec_k must be >= 1 when drafting"
        self.spec_k = spec_k
        # rollback (paged only): rejected draft rows never reach the KV
        # pool; dense always overwrites (the measured waste, kept)
        self.spec_rollback = bool(spec_rollback) and self.paged
        # kernel tier: in-kernel store-site waste counters (paged layout
        # only — the counters ride the paged store path)
        if kernel_counters and not self.paged:
            raise ValueError("kernel_counters needs kv_layout='paged'")
        self.kernel_counters = bool(kernel_counters)
        # object tier (DESIGN.md § Object tier): every allocated page
        # registers as a live kv_page object under this engine's owner
        # name, so the fleet's ReplicaDetector can content-hash pools
        # across replicas
        self.registry = registry
        self.owner = owner
        # same-burst content dedup: an admission group member whose
        # page-aligned prefix duplicates an earlier member's is deferred
        # one tick, so the leader's register_prefix turns the duplicate
        # into an ordinary PrefixIndex hit (see _admit)
        self.content_dedup = bool(content_dedup) and self.paged

        if self.paged:
            max_pages = -(-max_len // page_size)
            if num_pages is None:
                num_pages = num_slots * max_pages
            self.kv = PagedKV(num_slots, page_size, num_pages, max_pages,
                              prefix_window=prefix_window,
                              registry=registry, owner=f"{owner}/kv")
            cache = model.init_paged_cache(
                params, num_slots, max_len, page_size=page_size,
                num_pages=num_pages, kv_dtype=kv_dtype,
                kernel_counters=self.kernel_counters)
            if registry is not None:
                # the allocator registers pages; it needs the pool's
                # per-page byte size and a live-content reader, both
                # only known once the device cache exists
                a = self.kv.alloc
                a.page_bytes = sum(
                    (sub[key].nbytes // num_pages)
                    for sub in cache["main"].values() if "pt" in sub
                    for key in ("k", "v"))
                a.page_reader = self._read_page
            self._copy_fn = (step_cache.get("page_copy")
                             if step_cache is not None
                             else jax.jit(make_page_copy()))
        else:
            self.kv = None
            cache = model.init_cache(params, num_slots, max_len,
                                     kv_dtype=kv_dtype)
        self.cache = model.with_cache_index(
            cache, jnp.zeros((num_slots,), jnp.int32))
        self.tokens = jnp.zeros((num_slots, 1), jnp.int32)
        if self.spec and registry is not None:
            # the drafter's corpus is the engine's long-lived draft
            # window: replicas that served the same traffic hold
            # bit-identical copies (replica_draft_window)
            registry.register(
                f"{owner}/draft/window", "draft_window",
                num_slots * (self.spec_k + 1) * 4,
                reader=self._read_draft_window)

        self.slots: List[Optional[Request]] = [None] * num_slots
        self._lengths = np.zeros(num_slots, np.int64)  # host mirror of idx
        self._queue: Deque[Request] = deque()
        self.finished: Dict[str, Request] = {}
        self.step_no = 0
        self.stats = MonotonicStats(
            {"prefill_tokens": 0, "decode_tokens": 0,
             "prefill_s": 0.0, "decode_s": 0.0, "ticks": 0,
             "prefills": 0,
             # prompt tokens actually pushed through the model
             # (< prefill_tokens when prefixes hit the cache)
             "prefill_computed_tokens": 0,
             # padded-garbage positions the bucketed prefill
             # burned (whole-batch sweep minus useful suffixes)
             "padded_prefill_tokens": 0,
             "prefix_hits": 0, "prefix_hit_tokens": 0,
             "cow_copies": 0, "pages_freed": 0,
             # admissions pushed back by pool pressure (the router's
             # preemption signal: it frees global-prefix pins and the
             # deferred request retries next tick)
             "admit_deferred": 0,
             # admissions pushed back ONE tick by content dedup so a
             # same-burst duplicate prefix admits as an index hit
             # instead of being recomputed into replica pages
             "dedup_deferred": 0,
             # speculative decode accounting
             "spec_ticks": 0, "draft_proposed": 0,
             "draft_accepted": 0, "draft_s": 0.0,
             "verify_s": 0.0, "verified_positions": 0})

        if step_cache is not None:
            assert step_cache.model is model, \
                "step_cache was built for a different model"
            self._tick_fn = step_cache.get("tick", paged=self.paged)
            self._prefill_fn = step_cache.get("prefill", paged=self.paged)
            self._verify_fn = step_cache.get(
                "verify", paged=self.paged,
                rollback=self.spec_rollback) if self.spec else None
        else:
            self._tick_fn = jax.jit(
                make_engine_tick(model, strategy, paged=self.paged))
            self._prefill_fn = jax.jit(
                make_engine_prefill(model, strategy, paged=self.paged))
            self._verify_fn = jax.jit(make_engine_verify(
                model, strategy, paged=self.paged,
                rollback=self.spec_rollback)) if self.spec else None

        # detector geometry: the KV sub-blocks of one scanned superblock
        main = self.cache["main"]
        self._kv_names = [n for n, sub in main.items() if "k" in sub]
        if detectors is not None:
            site = sum(
                2 * int(np.prod(main[n]["k"].shape[3:]))
                * main[n]["k"].dtype.itemsize
                for n in self._kv_names)
            detectors.bind(
                num_layers=model.sched.n_super, site_bytes=site,
                paged=self.paged,
                kv_itemsize=main[self._kv_names[0]]["k"].dtype.itemsize,
                row_elems={n: 2 * int(np.prod(main[n]["k"].shape[3:]))
                           for n in self._kv_names})
            self._peek_fn = jax.jit(self._make_peek())

    # ---------------------------- jitted steps ------------------------
    def _make_peek(self):
        names = self._kv_names

        def peek(cache, layer, page, off):
            # dense layout: (L, B, S, Hkv, D) — page is the slot row;
            # paged layout: (L, P, page_size, Hkv, D) — the pool page.
            outs = []
            for name in names:
                sub = cache["main"][name]
                outs.append(sub["k"][layer, page, off].reshape(-1))
                outs.append(sub["v"][layer, page, off].reshape(-1))
            return jnp.concatenate(outs).astype(jnp.float32)
        return peek

    def _peek(self, layer: int, page: int, off: int) -> np.ndarray:
        return np.asarray(self._peek_fn(self.cache, layer, page, off))

    # --------------------------- object tier ---------------------------
    def _read_page(self, p: int) -> np.ndarray:
        """Live contents of pool page `p` across every paged KV
        sub-block, flat uint8 — the replica detector's content reader
        (reads self.cache at call time, so it tracks the functional
        cache updates)."""
        chunks = []
        for sub in self.cache["main"].values():
            if "pt" not in sub:
                continue
            for key in ("k", "v"):
                a = np.ascontiguousarray(np.asarray(sub[key][:, p]))
                chunks.append(np.frombuffer(a.tobytes(), np.uint8))
        return (np.concatenate(chunks) if chunks
                else np.zeros(0, np.uint8))

    def _read_draft_window(self) -> np.ndarray:
        corpus = (getattr(self.drafter, "_corpus", None)
                  or getattr(self.drafter, "_seqs", None) or [])
        arrs = [np.asarray(a, np.int32).ravel() for a in corpus]
        return (np.concatenate(arrs) if arrs else np.zeros(0, np.int32))

    def _read_kernel_counts(self):
        """The last jitted forward's in-kernel [stored, silent, dropped]
        element counts, per KV sub-block, as (L, B, 3) host arrays —
        or None when the kernel tier is off / unobserved."""
        if not self.kernel_counters or self.detectors is None:
            return None
        counts = self.model.kernel_counters(self.cache)
        if counts is None:
            return None
        return {n: np.asarray(c) for n, c in counts.items()}

    def _emit_kernel_store(self, site: str) -> None:
        counts = self._read_kernel_counts()
        if counts is not None:
            self.detectors.on_kernel_store(self.step_no, site, counts)

    # ------------------------------ schedule ---------------------------
    def submit(self, req: Request) -> None:
        assert req.tokens.ndim == 1 and req.tokens.size >= 1
        assert req.tokens.size < self.max_len, "prompt exceeds cache"
        assert req.max_new_tokens >= 1
        self._queue.append(req)

    @property
    def pending(self) -> int:
        return len(self._queue) + sum(r is not None for r in self.slots)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def live_slots(self) -> int:
        return sum(r is not None for r in self.slots)

    def _note_freed(self, freed: List[int]) -> None:
        """Every page-freeing path goes through here: count the frees
        AND disarm the detectors' now-stale traps on them."""
        self.stats["pages_freed"] += len(freed)
        if self.detectors is not None and freed:
            self.detectors.on_page_free(freed)

    def note_freed(self, freed: List[int]) -> None:
        """Pages freed by an EXTERNAL holder of this replica's pool —
        the fleet's global prefix tier dropping its pins — still need
        their frees counted and their stale traps disarmed here."""
        self._note_freed([int(p) for p in freed])

    def _accept_token(self, slot: int, req: Request, tok: int) -> None:
        req.generated.append(int(tok))
        limit = min(req.max_new_tokens,
                    self.max_len - req.tokens.size)
        if ((self.eos_id is not None and tok == self.eos_id)
                or len(req.generated) >= limit):
            req.finish_step = self.step_no
            self.finished[req.rid] = req
            self.slots[slot] = None        # recycle: slot idles until reuse
            if self.drafter is not None:
                # self-speculation corpus: a served sequence is future
                # draft material (duplicated traffic drafts itself)
                self.drafter.observe(np.concatenate(
                    [req.tokens, np.asarray(req.generated, np.int32)]))
            if self.paged:
                # recycling frees pages instead of leaving rows to be
                # silently rewritten; prefix-index pins keep shared
                # pages. The device page table is synced lazily at the
                # next _admit: a finished slot's writes are already
                # dropped by the idle index sentinel, and freed pages
                # are only re-mapped by an admission (which pushes the
                # fresh table before its prefill).
                self._note_freed(self.kv.free_slot(slot))
            if self.detectors is not None:
                self.detectors.on_finish(self.step_no, slot, req.rid)

    def _dedup_group(self, group: List[Request]) -> List[Request]:
        """Content-addressed same-burst dedup (OJXPerf replica fix).

        Requests admitted in ONE group share a single prefill and only
        register their prefixes AFTER it, so two same-tick arrivals with
        a common prompt prefix each compute it into their own pages —
        the bit-identical kv_page replicas the detector flags even
        though the PrefixIndex "works". Defer every member whose
        page-aligned prefix digest duplicates an earlier member's beyond
        what the index (or a fleet lease) already covers: next tick the
        leader's register_prefix has landed and the duplicate admits as
        an ordinary prefix hit sharing the leader's pages. Outputs stay
        bit-identical — the follower merely starts one tick later."""
        ps = self.kv.page_size
        keep: List[Request] = []
        deferred: List[Request] = []
        seen: Dict[str, int] = {}      # page-aligned prefix digest key
        for req in group:
            toks = np.asarray(req.tokens)
            keys = [f"{m}:{_digest(toks[:m])}"
                    for m in range(ps, int(toks.size), ps)]
            best = max((m for m, k in zip(
                range(ps, int(toks.size), ps), keys) if k in seen),
                default=0)
            have = self.kv.index.match(toks)[0]
            if req.prefix_hint is not None:
                have = max(have, int(req.prefix_hint[0]))
            if best > have:
                req.arrival = self.step_no + 1
                deferred.append(req)
                self.stats["dedup_deferred"] += 1
            else:
                keep.append(req)
                seen.update((k, 1) for k in keys)
        if deferred:
            self._queue.extendleft(reversed(deferred))
        return keep

    def _admit(self) -> None:
        free = [b for b, r in enumerate(self.slots) if r is None]
        group: List[Request] = []
        while free[len(group):] and self._queue \
                and self._queue[0].arrival <= self.step_no:
            group.append(self._queue.popleft())
        if self.content_dedup and len(group) > 1:
            group = self._dedup_group(group)
        if not group:
            return
        B = self.num_slots
        admit = np.zeros(B, bool)
        starts = np.zeros(B, np.int32)
        lengths = np.ones(B, np.int32)
        taken: List[int] = []
        plans: Dict[int, Any] = {}
        admitted: List[Request] = []
        for b, req in zip(free, group):
            L = req.tokens.size
            if self.paged:
                budget = min(req.max_new_tokens, self.max_len - L)
                try:
                    plan = self.kv.admit(b, req.tokens, budget,
                                         hint=req.prefix_hint)
                except PoolExhausted as e:
                    # pool pressure: defer this (and following) requests;
                    # pages the failed eviction pass DID free still need
                    # their stale traps disarmed. The dispatch lease (if
                    # any) stays held for the retry.
                    self._note_freed(e.freed)
                    self.stats["admit_deferred"] += 1
                    self._queue.extendleft(
                        reversed(group[len(admitted):]))
                    break
                if req.prefix_hint is not None:
                    # admit pinned whatever it mapped; the dispatch-time
                    # lease has done its job
                    self._note_freed(self.kv.release(req.prefix_hint[1]))
                    req.prefix_hint = None
                plans[b] = plan
                starts[b] = plan.reuse_len
                req.reuse_len = plan.reuse_len
                if plan.reuse_len:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_hit_tokens"] += plan.reuse_len
                self.stats["cow_copies"] += len(plan.cow)
                self._note_freed(plan.freed)
            admit[b] = True
            lengths[b] = L
            taken.append(b)
            admitted.append(req)
            self.slots[b] = req
            self._lengths[b] = L
            req.prefill_step = self.step_no
        if not admitted:
            return

        # power-of-two padding of the group's (suffix) lengths for a
        # bounded jit cache, capped at the cache extent
        suffixes = [int(lengths[b] - starts[b]) for b in taken]
        P = min(_bucket(max(suffixes)), self.max_len)
        toks = np.zeros((B, P), np.int32)
        for b, req in zip(taken, admitted):
            suf = req.tokens[int(starts[b]):]
            toks[b, :suf.size] = suf
            if self.detectors is not None:
                # dense: the prefill store sweeps the padded extent [0,P)
                # of the slot's row; paged: only freshly-owned pages are
                # written, so there is no stale-row sweep to trap
                self.detectors.on_admit(
                    self.step_no, b, req.rid, req.tokens,
                    padded_len=None if self.paged else P,
                    reuse_len=int(starts[b]))

        if self.paged:
            self.cache = self.model.with_page_table(self.cache, self.kv.pt)
            cows = [c for b in taken for c in plans[b].cow]
            if cows:
                # copy-on-write of partially reused pages, padded to the
                # slot count so one compiled shape serves every group
                src = np.full(B, 0, np.int32)
                dst = np.full(B, self.kv.num_pages, np.int32)  # dropped
                for i, (s, d) in enumerate(cows):
                    src[i], dst[i] = s, d
                self.cache = self._copy_fn(self.cache, jnp.asarray(src),
                                           jnp.asarray(dst))
            # the copy consumed the COW sources (value semantics: this
            # cache already holds the copied rows) — drop their pins
            for b in taken:
                self._note_freed(self.kv.release(plans[b].cow_pins))

        t0 = time.perf_counter()
        toks_out, self.cache = self._prefill_fn(
            self.params, self.cache, jnp.asarray(toks),
            jnp.asarray(admit), jnp.asarray(starts), jnp.asarray(lengths),
            self.tokens)
        toks_out.block_until_ready()
        self.stats["prefill_s"] += time.perf_counter() - t0
        self.stats["prefill_tokens"] += int(sum(r.tokens.size
                                                for r in admitted))
        self.stats["prefill_computed_tokens"] += int(sum(suffixes))
        self.stats["padded_prefill_tokens"] += B * P - int(sum(suffixes))
        self.stats["prefills"] += 1
        self.tokens = toks_out
        self._emit_kernel_store("prefill")
        if self.paged:
            for b, req in zip(taken, admitted):
                self._note_freed(self.kv.register_prefix(b, req.tokens))
        host = np.asarray(toks_out)[:, 0]
        for b, req in zip(taken, admitted):
            self._accept_token(b, req, host[b])

    def _decode_tick(self) -> None:
        if self.spec:
            self._spec_tick()
            return
        active = np.array([r is not None for r in self.slots])
        write_pos = self._lengths.copy()   # the position each slot writes
        t0 = time.perf_counter()
        nxt, self.cache = self._tick_fn(self.params, self.cache,
                                        self.tokens, jnp.asarray(active))
        nxt.block_until_ready()
        self.stats["decode_s"] += time.perf_counter() - t0
        self.stats["decode_tokens"] += int(active.sum())
        self.stats["ticks"] += 1
        self.tokens = nxt
        self._emit_kernel_store("decode")
        self._lengths[active] += 1
        host = np.asarray(nxt)[:, 0]
        slots_now = list(self.slots)
        for b, req in enumerate(slots_now):
            if req is not None:
                self._accept_token(b, req, host[b])
        self._report_tick_writes(slots_now, write_pos)

    def _report_tick_writes(self, slots_now, write_pos) -> None:
        """Tier-3 reporting of one tick's first-position K/V stores."""
        if self.detectors is None:
            return
        writes = []
        for b, req in enumerate(slots_now):
            pos = int(write_pos[b])
            if self.paged:
                # idle slots write NOTHING in the paged layout — the
                # scatter dropped their store, so there is no event;
                # a slot that just finished freed its pages (site
                # lookup comes back unmapped) and is skipped too
                if req is None:
                    continue
                page, off = self.kv.site(b, pos)
                if page < 0:
                    continue
            else:
                page, off = b, pos
            writes.append(SlotWrite(b, req.rid if req is not None
                                    else None, req is not None, pos,
                                    page=page, offset=off))
        self.detectors.on_step(self.step_no, writes, self._peek)

    # ------------------------- speculative tick -----------------------
    def _draft_cap(self, slot: int, req: Request) -> int:
        """Drafts worth proposing for this slot: bounded by spec_k, the
        request's remaining generation allowance (the tick's last token
        is the bonus, so remaining-1 drafts suffice), and — in the paged
        layout — the slot's mapped page-table extent, so an accepted
        draft can never land on an unmapped position."""
        limit = min(req.max_new_tokens, self.max_len - req.tokens.size)
        cap = min(self.spec_k, limit - len(req.generated) - 1)
        pos0 = int(self._lengths[slot])
        if self.paged:
            cap = min(cap, self.kv.slot_extent(slot) - pos0 - 1)
        else:
            cap = min(cap, self.max_len - pos0 - 1)
        return max(0, cap)

    def _spec_tick(self) -> None:
        """One draft→verify→accept step over the whole batch.

        The drafter proposes up to spec_k tokens per live slot (host
        side); ONE width-(k+1) verify forward scores them all; the
        greedy-consistent prefix plus the bonus token are emitted — up
        to spec_k+1 tokens per slot per tick, bit-identical to plain
        decode. With rollback (paged) the rejected rows never reach the
        pool; otherwise they are stored and overwritten — the Def.-1
        dead stores `ServingDetectors.rejected_draft_store` counts."""
        B, W = self.num_slots, self.spec_k + 1
        active = np.array([r is not None for r in self.slots])
        write_pos = self._lengths.copy()
        toks = np.zeros((B, W), np.int32)
        toks[:, 0] = np.asarray(self.tokens)[:, 0]
        dlen = np.zeros(B, np.int32)
        t0 = time.perf_counter()
        for b, req in enumerate(self.slots):
            if req is None:
                continue
            cap = self._draft_cap(b, req)
            if cap <= 0:
                continue
            hist = np.concatenate(
                [req.tokens, np.asarray(req.generated, np.int32)])
            d = np.asarray(self.drafter.propose(hist, cap),
                           np.int32).reshape(-1)[:cap]
            dlen[b] = d.size
            toks[b, 1:1 + d.size] = d
        self.stats["draft_s"] += time.perf_counter() - t0

        t0 = time.perf_counter()
        g, m, nxt, self.cache = self._verify_fn(
            self.params, self.cache, jnp.asarray(toks),
            jnp.asarray(active), jnp.asarray(dlen))
        nxt.block_until_ready()
        dt = time.perf_counter() - t0
        self.stats["verify_s"] += dt
        self.stats["decode_s"] += dt
        self.stats["ticks"] += 1
        self.stats["spec_ticks"] += 1
        self.stats["draft_proposed"] += int(dlen[active].sum())
        self.stats["verified_positions"] += int(active.sum()) * W
        g = np.asarray(g)
        m = np.asarray(m)
        self.stats["draft_accepted"] += int(m[active].sum())
        self.tokens = nxt
        counts = self._read_kernel_counts()
        if counts is not None:
            # overwrite mode: the verify forward's full-window stores;
            # rollback: the commit's accepted-prefix stores (the deferred
            # window stored nothing) — classification against m happens
            # in the detector, measurement stays in-kernel
            self.detectors.on_kernel_verify(self.step_no, counts, m, dlen,
                                            active)
        self._lengths[active] += 1 + m[active]

        slots_now = list(self.slots)
        emitted = 0
        for b, req in enumerate(slots_now):
            if req is None:
                continue
            # emit the accepted chain + bonus; stop at EOS/limit so the
            # output stream is exactly the plain-decode stream
            for j in range(int(m[b]) + 1):
                emitted += 1
                self._accept_token(b, req, int(g[b, j]))
                if req.done:
                    break
        self.stats["decode_tokens"] += emitted

        self._report_tick_writes(slots_now, write_pos)
        if self.detectors is not None:
            entries = []
            for b, req in enumerate(slots_now):
                if req is None or not active[b]:
                    continue
                pos0 = int(write_pos[b])
                # draft rows attributed to the drafter this tick: every
                # PROPOSED row in overwrite mode (so the fraction is
                # exactly 1 - accept-rate), only the accepted prefix
                # under rollback. Overwrite also stores the fixed-width
                # window's padding rows past dlen — dead too, but not
                # the drafter's waste, so they stay out of this site
                n_written = int(m[b]) if self.spec_rollback \
                    else int(dlen[b])
                sites = []
                for j in range(1, n_written + 1):
                    pos = pos0 + j
                    if self.paged:
                        page, off = self.kv.site(b, pos)
                        if page < 0:
                            continue
                    else:
                        if pos >= self.max_len:
                            continue
                        page, off = b, pos
                    sites.append((page, off, j > int(m[b])))
                entries.append(VerifyWrite(b, req.rid, int(m[b]), sites))
            self.detectors.on_verify(self.step_no, entries)

    def step(self) -> None:
        """One scheduler step: admit into free slots, then one decode
        tick over the whole batch."""
        self._admit()
        self._decode_tick()
        self.step_no += 1

    def run(self, max_steps: int = 100_000) -> Dict[str, Request]:
        """Drive until every submitted request has finished."""
        steps = 0
        while self.pending and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    # ---------------------------- reporting ----------------------------
    def throughput(self) -> Dict[str, float]:
        s = self.stats
        out = {
            "prefill_tok_s": (s["prefill_tokens"] / s["prefill_s"]
                              if s["prefill_s"] else 0.0),
            "decode_tok_s": (s["decode_tokens"] / s["decode_s"]
                             if s["decode_s"] else 0.0),
        }
        if self.spec:
            out["draft_tok_s"] = (s["draft_proposed"] / s["draft_s"]
                                  if s["draft_s"] else 0.0)
            out["verify_tok_s"] = (s["verified_positions"] / s["verify_s"]
                                   if s["verify_s"] else 0.0)
            out["accept_rate"] = (s["draft_accepted"] / s["draft_proposed"]
                                  if s["draft_proposed"] else 0.0)
        return out

    def lowered_tick(self):
        """Lowered decode tick (Tier-2 HLO waste analysis subject)."""
        active = jnp.ones((self.num_slots,), bool)
        return self._tick_fn.lower(self.params, self.cache, self.tokens,
                                   active)

    def lowered_prefill(self, prompt_len: int):
        """Lowered admission prefill at one padded prompt length."""
        B = self.num_slots
        zeros = jnp.zeros((B,), jnp.int32)
        return self._prefill_fn.lower(
            self.params, self.cache, jnp.zeros((B, prompt_len), jnp.int32),
            jnp.ones((B,), bool), zeros,
            jnp.full((B,), prompt_len, jnp.int32), self.tokens)
