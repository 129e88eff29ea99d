"""DualPath serving system: scheduler + engines + storage, end to end.

Single-process orchestration of the full request lifecycle with *real*
token generation and *real* KV bytes moving along the dual-path legs —
the functional counterpart of the discrete-event simulator (which owns
the cluster-scale timing claims).  Used by the examples, the online
benchmark and the integration tests.

Per round (paper Fig. 4), as a lifecycle state machine
(serving/events.py)::

  SCHEDULED    client computes the trie hit for ``context ‖ append``
               (§A.4); scheduler assigns (PE, DE) + read path
               (§6.1 / Alg. 1) across every registered PE/DE group
  READING      the chosen side(s)' TrafficManagers carry the FullBlock
               reads (storage→PE directly, or storage→DE→compute
               network→PE; DRAM-tier prefixes skip the SNIC)
  PREFILL      PE runs quota-packed chunked prefill (§6.2) over the
               append chunk, hit KV installed layerwise double-buffered
  PD_TRANSFER  prompt state PE→DE, one submission per attention layer,
               batched per doorbell
  DECODE       DE decodes ``gen`` tokens greedily, slot-batched
  PERSIST      newly-filled FullBlocks + trie entries persist (§A.5)

Two runtimes share every one of those mechanisms:

* **pipelined** (default) — an event-driven tick loop: reads are issued
  non-blocking (``TrafficManager.flush``) and stay in flight while the
  engines ``step()``, completing at the tick's ``poll``; PD transfers
  and persists likewise.  The runtime's wall clock advances by modelled
  seconds, ``max(transfer, compute)`` per tick — transfers overlap
  compute, the paper's online claim.
* **blocking** (``pipelined=False``) — the legacy lock-step loop: every
  submission is drained inline, so the clock charges
  ``transfer + compute``.  Kept as the reference arm; generation and
  byte accounting are bit-identical between the two (pinned by
  tests/test_serving_runtime.py).

``run_offline`` drives all sessions from t=0; ``run_online(arrivals)``
adds online arrivals and inter-round think gaps on the wall clock
(which also gives DRAM-tier TTLs and the think-time prefetcher real
seconds instead of tick counts) and records per-round TTFT/TTST/TPOT
into ``stats()``, mirroring ``Sim.results()``.
"""
from __future__ import annotations

import itertools
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from repro.configs.base import ModelConfig
from repro.core.admission import AdmissionGate
from repro.core.autoscale import (DE_TO_PE, DrainTracker, LoadSignals,
                                  PDController, pick_victim)
from repro.core.blocks import layout_for
from repro.core.config import (ElasticConfig, NetworkConfig,
                               ResilienceConfig, SloConfig, TierConfig,
                               resolve_groups)
from repro.core.scheduler import Request, Scheduler
from repro.core.traffic import TrafficClass, TrafficManager
from repro.engines import kvio
from repro.engines.runtime import (DecodeEngine, EngineRequest,
                                   PrefillEngine, state_bytes,
                                   uses_state_blob)
from repro.obs.schema import conforming
from repro.kvcache.store import MemoryKVStore, StateBlobStore
from repro.kvcache.tiers import DramTier, ThinkTimePrefetcher
from repro.kvcache.trie import BlockTrie
from repro.serving import events
from repro.serving.events import (EngineLifecycle, EventLoop, ReqState,
                                  RoundMetrics, ServingTimeModel, TickIo,
                                  VirtualClock)
from repro.sim.faults import FaultSchedule
from repro.sim.spec import NodeSpec
from repro.sim.traces import Trajectory


@dataclass
class AgentSession:
    traj: Trajectory
    rng: np.random.Generator
    context: List[int] = field(default_factory=list)
    next_round: int = 0
    rounds_done: int = 0
    current: Optional[EngineRequest] = None

    def done(self) -> bool:
        return self.next_round >= self.traj.n_rounds and self.current is None


class ServingSystem:
    def __init__(self, cfg: ModelConfig, params, *, n_pe: int = 1,
                 n_de: int = 1, mode: str = "dualpath",
                 block_tokens: int = 16, max_seq: int = 512,
                 de_slots: int = 8, quota_s: float = 0.3, seed: int = 0,
                 split_reads: bool = False, layerwise: bool = True,
                 pe_group_size: Optional[int] = None,
                 de_group_size: Optional[int] = None,
                 pipelined: bool = True, node: Optional[NodeSpec] = None,
                 tracer=None,
                 tier: Optional[TierConfig] = None,
                 net: Optional[NetworkConfig] = None,
                 elastic=None,
                 resilience: Optional[ResilienceConfig] = None,
                 slo: Optional[SloConfig] = None,
                 **legacy):
        assert mode in ("dualpath", "basic")
        # --- shared config groups (repro.core.config) ------------------
        # The same five groups SimConfig holds; subsystem knobs arrive
        # here (tier=TierConfig(...), elastic=ElasticConfig(...), ...).
        # The old flat kwargs (dram_tier_bytes=..., elastic=True, ...)
        # are folded in through the one-release deprecation shim.
        groups = resolve_groups(legacy, tier=tier, net=net,
                                elastic=elastic, resilience=resilience,
                                slo=slo)
        tcfg = self.tier_cfg = groups["tier"]
        ncfg = self.net_cfg = groups["net"]
        ecfg = self.elastic_cfg = groups["elastic"]
        rcfg = self.resilience_cfg = groups["resilience"]
        scfg = self.slo_cfg = groups["slo"]
        self.cfg = cfg
        self.params = params            # role flips build new engines
        self.mode = mode
        self.max_seq = max_seq
        self.pipelined = pipelined
        self.layout = layout_for(cfg, block_tokens)
        self.store = MemoryKVStore(self.layout)
        self.blob_store = StateBlobStore()
        self.trie = BlockTrie(block_tokens)
        self.sched = Scheduler(alpha=1 << 30, beta=1 << 30,
                               split_reads=split_reads,
                               class_aware=scfg.class_aware)
        # the runtime's wall clock (serving/events.py): modelled seconds,
        # advanced per tick, jumped over idle gaps in online mode.
        # ``collective_group_size > 1`` puts per-layer model collectives
        # on the compute network (repro.network) and makes the clock's
        # cn charges contention-aware under ``net_arbiter``.
        self.time_model = ServingTimeModel.for_model(
            cfg, node, net_arbiter=ncfg.net_arbiter,
            collective_group_size=ncfg.collective_group_size)
        self.clock = VirtualClock()
        self.loop = EventLoop(self.clock)
        self.metrics: Dict[int, RoundMetrics] = {}
        self._online = False
        # node-local DRAM tiers over the remote store (kvcache/tiers.py):
        # reads served from a tier never reach the store (= the SNIC).
        # Tier timestamps come from the modelled wall clock in BOTH
        # offline and online serving (_tier_now), so an agentic-ttl
        # ``tier_ttl_s`` always means seconds — matching the simulator.
        self.tiers: Dict[int, DramTier] = {}
        if tcfg.dram_tier_bytes:
            for node_id in range(n_pe + n_de):
                tier = DramTier(tcfg.dram_tier_bytes,
                                policy=tcfg.tier_policy,
                                ttl_s=tcfg.tier_ttl_s,
                                backing=self.store)
                # clock-agnostic call sites (DE persists through the
                # plain store interface) still stamp modelled seconds
                tier.clock_fn = self._tier_now
                self.tiers[node_id] = tier
        self.prefetcher = ThinkTimePrefetcher(tcfg.prefetch_chunk_blocks) \
            if (tcfg.prefetch and self.tiers) else None
        # engine groups: ``*_group_size`` engines per scheduler group
        # (default: one group spanning all engines of that kind); the
        # fetch loop visits every group, so DE phase-1 balancing across
        # groups runs end-to-end with ≥ 2 DE groups
        self.pes: Dict[Tuple[int, int], PrefillEngine] = {}
        self.des: Dict[Tuple[int, int], DecodeEngine] = {}
        pe_gsz = max(int(pe_group_size or n_pe), 1)
        de_gsz = max(int(de_group_size or n_de), 1)
        for i in range(n_pe):
            eid = (i, 0)
            self.sched.register_engine(eid, node=i, kind="pe",
                                       group=i // pe_gsz)
            self.pes[eid] = PrefillEngine(
                eid, cfg, params, self.store, self.layout, max_seq,
                quota_s, layerwise=layerwise,
                chunk_tokens=scfg.prefill_chunk_tokens,
                class_aware=scfg.class_aware)
        for j in range(n_de):
            eid = (n_pe + j, 0)
            st = self.sched.register_engine(eid, node=n_pe + j, kind="de",
                                            group=1000 + j // de_gsz)
            # the DE persists through its node tier (write-through + tier
            # warm-up) when one is configured
            de_store = self.tiers.get(n_pe + j, self.store)
            de = DecodeEngine(eid, cfg, params, de_store, self.trie,
                              self.layout, max_seq, n_slots=de_slots,
                              blob_store=self.blob_store)
            st.free_hbm_tokens = de_slots * max_seq
            de.defer_persist = pipelined
            self.des[eid] = de
        # --- elastic role reconfiguration (core/autoscale.py) -------------
        # Engines flip between PrefillEngine and DecodeEngine objects at
        # runtime; the controller/tracker plumbing exists even when
        # elastic is off (zero-cost, zero state drift) so stats() always
        # reports the reconfiguration columns.
        if ecfg.drain_policy not in ("idlest", "rotate"):
            raise ValueError(f"unknown drain_policy {ecfg.drain_policy!r}")
        self.elastic = bool(ecfg)
        self.reconfig_interval_s = ecfg.reconfig_interval_s
        self.drain_policy = ecfg.drain_policy
        self.drains = DrainTracker()
        self.controller = PDController(
            hi=ecfg.reconfig_hi, lo=ecfg.reconfig_lo,
            patience=ecfg.reconfig_patience,
            cooldown_s=ecfg.reconfig_cooldown_s,
            idle_floor_s=ecfg.reconfig_idle_floor_s)
        self.engine_lifecycle: Dict[Tuple[int, int], EngineLifecycle] = {
            eid: EngineLifecycle.ACTIVE
            for eid in (*self.pes, *self.des)}
        self._next_gid = itertools.count(5000)
        self._next_obs_t = ecfg.reconfig_interval_s
        self._drain_rotation = 0
        self._reconfig_ready: List = []   # drained DrainRecords to flip
        self._quota_s = quota_s
        self._layerwise = layerwise
        self._de_slots = de_slots
        self.reconfig_weight_bytes = 0.0
        self._rid = itertools.count()
        self._pending_admit: deque = deque()
        self._inflight: Dict[int, EngineRequest] = {}
        self._install_ready: List[EngineRequest] = []
        self._pd_queue: List[EngineRequest] = []
        # milestone timestamps are stamped AFTER the tick's clock advance
        # (a milestone reached during tick t happened by the END of t, and
        # the tick's modelled seconds must count against it) — deferred
        # here until then
        self._pending_stamps: List[Tuple[RoundMetrics, str]] = []
        self._tick_io = TickIo()
        self._tick_compute = 0.0
        # per-tick collective seconds per node's CNIC link + interference
        # accounting (repro.network; zeros when collectives are off)
        self._tick_coll: Dict[int, float] = {}
        self.collective_stall_s = 0.0
        self.transfer_backlog_s = 0.0
        self.net_congestion = 0.0
        self._submit_seconds_seen = 0.0
        self.rng = np.random.default_rng(seed)
        self.read_bytes_by_side = {"pe": 0, "de": 0}
        self.dram_bytes_by_side = {"pe": 0, "de": 0}
        self.n_split_reads = 0
        self.gen_tokens_done = 0
        # --- fault injection (sim/faults.py, shared with the simulator) ---
        # An empty schedule is normalised to None so every fault hook is
        # a structural no-op on the happy path: zero-rate runs stay
        # bit-identical to faults=None (pinned by tests/test_faults.py).
        faults = rcfg.faults
        self.faults = faults if (faults is not None
                                 and not faults.empty) else None
        self.hedge_reads = rcfg.hedge_reads
        self.hedge_min_severity = rcfg.hedge_min_severity
        self._deaths_pending = list(self.faults.deaths) \
            if self.faults is not None else []
        self.dead_engines: List[Tuple[int, int]] = []
        self.recovered_rounds = 0
        self.hedged_reads = 0
        self.hedge_moved_tokens = 0
        # --- online SLO layer (core/config.SloConfig) ------------------
        # gate is None when admission is off (or in offline serving,
        # where there is no arrival process to shed) — arrivals then go
        # straight to sched.submit, structurally identical to pre-SLO
        self.gate = AdmissionGate(scfg) if scfg.admission else None
        self.prefill_chunks = 0
        # --- flight recorder (repro.obs) -------------------------------
        # Optional; ``tracer=None`` keeps every hook a structural no-op
        # so untraced runs stay bit-identical.  Lifecycle spans are
        # closed at end-of-tick (the same deferred-timestamp rule
        # _stamp uses), so span edges match the stamped milestones.
        self.tracer = tracer
        self._pending_states: List[Tuple[EngineRequest, ReqState]] = []
        self._prefill_state_bytes = 0
        if tracer is not None:
            tracer.bind_clock(lambda: self.clock.now)
            if self.faults is not None:
                tracer.annotate_faults(self.faults)
            self.sched.tracer = tracer
            self.controller.tracer = tracer
            for node_id, tier in self.tiers.items():
                tier.tracer = tracer
                tier.track = f"tier/node{node_id}"
            # host regions (Tracer.region): the tick's phases here, the
            # engines' steps, installs and persists, the store's writes
            for eng in (*self.pes.values(), *self.des.values()):
                eng.tracer = tracer
            self.store.tracer = tracer

    # ------------------------------------------------------------------
    def _all_tms(self) -> Iterator[TrafficManager]:
        for pe in self.pes.values():
            yield pe.tm
        for de in self.des.values():
            yield de.tm

    def _tier_now(self) -> float:
        """Tier timestamps: the modelled wall clock, in BOTH modes.
        The clock advances by modelled seconds every tick whether or not
        an arrival process drives the loop, so offline runs get real
        seconds too — an agentic-ttl ``tier_ttl_s`` means seconds
        everywhere, matching the simulator (it used to fall back to the
        tier's internal operation counter offline, so the same TTL
        meant 'operations' there; regression-pinned in
        tests/test_config.py)."""
        return self.clock.now

    # ------------------------------------------------------------------
    # fault-aware service times: the schedule's multipliers compose onto
    # the healthy time model.  With ``faults is None`` both helpers
    # return the base value untouched (same floats, same arithmetic).
    # ------------------------------------------------------------------
    def _snic_s(self, node: int, nbytes: float, rid: Optional[int] = None,
                side: Optional[str] = None) -> float:
        """SNIC service seconds on ``node``, degraded by any active
        slowdown window and — for a storage read leg identified by
        ``(rid, side)`` — the straggler draw.  Tier (DRAM) reads never
        come through here: tier hits are never re-charged to a SNIC."""
        s = self.time_model.snic_seconds(nbytes)
        if self.faults is not None:
            s *= self.faults.snic_factor(node, self.clock.now)
            if rid is not None:
                s *= self.faults.leg_factor(rid, side)
        return s

    def _cn_s(self, nbytes: float) -> float:
        s = self.time_model.cn_seconds(nbytes)
        if self.faults is not None:
            s *= self.faults.net_factor(self.clock.now)
        return s

    # ------------------------------------------------------------------
    def _submit_round(self, sess: AgentSession):
        rnd = sess.traj.rounds[sess.next_round]
        append = list(sess.rng.integers(
            2, self.cfg.vocab_size, size=rnd.append))
        prompt = sess.context + append
        if uses_state_blob(self.cfg):
            blob, hit = self.blob_store.get(sess.context)
            refs = []
            hit = hit if blob is not None else 0
        else:
            hit, refs = self.trie.match(prompt)
            blob = None
        new_tokens = len(prompt) - hit
        if self.gate is not None and self._online:
            # load-aware admission (core/admission.py); offline serving
            # admits unconditionally — no arrival process to shed, and a
            # deferral event would never fire outside the online loop
            sig = self._elastic_signals()
            read_s = self.time_model.snic_seconds(
                hit * self.layout.n_layers *
                self.layout.bytes_per_token_layer)
            prefill_s = self.time_model.pe_step_seconds(
                [(hit, max(new_tokens, 1))])
            verdict = self.gate.decide(
                (sess.traj.tid, sess.next_round),
                self.gate.ttft_estimate(sig, read_s, prefill_s))
            if verdict == "defer":
                self.loop.after(self.slo_cfg.admission_defer_s,
                                lambda s=sess: self._submit_round(s))
                return
            if verdict == "reject":
                # shed the load: the session's trajectory ends here
                sess.next_round = sess.traj.n_rounds
                sess.current = None
                return
        req = Request(rid=next(self._rid), cached_tokens=hit,
                      new_tokens=new_tokens, gen_tokens=rnd.gen,
                      arrival=self.clock.now, slo_class=sess.traj.slo_class)
        er = EngineRequest(req=req, context_tokens=prompt[:hit],
                           append_tokens=prompt[hit:], hit_refs=refs)
        er._blob = blob
        er._session = sess
        er._tier_pinned = None
        er._pd_ready = False
        er._cancelled = False
        er.lifecycle = ReqState.SCHEDULED
        self._trace_submit(er)
        sess.current = er
        sess.next_round += 1
        self._inflight[req.rid] = er
        self.metrics[req.rid] = RoundMetrics(rid=req.rid,
                                             gen_tokens=rnd.gen,
                                             submit_t=self.clock.now,
                                             slo_class=sess.traj.slo_class)
        for tier in self.tiers.values():
            tier.note_alive(sess.traj.tid, now=self._tier_now())
        self.sched.submit(req)

    # ------------------------------------------------------------------
    # scheduling: group fetches + read-path decisions (tick phase 1)
    # ------------------------------------------------------------------
    def _fetch_groups(self):
        """Leader fetch for every registered group — DE groups first
        (HBM reservation), then PE groups, as in the simulator.  With
        ≥ 2 DE groups the fetch exercises ``Scheduler.de_phase1``'s
        cross-group balancing on the global queue."""
        for gid, members in self.sched.groups("de").items():
            reports = {eid: (sum(s is not None for s in self.des[eid].slots),
                             sum(int(n) for n in self.des[eid].lengths),
                             0, self.des[eid].free_slots * self.max_seq)
                       for eid in members}
            for asg in self.sched.on_de_fetch(gid, reports):
                pass
        for gid, members in self.sched.groups("pe").items():
            reports = {eid: (len(self.pes[eid].fifo),
                             sum(w.remaining for w, _ in self.pes[eid].fifo),
                             0)
                       for eid in members}
            for asg in self.sched.on_pe_fetch(gid, reports):
                pass

    def _schedule_tick(self) -> int:
        """Tick phase 1; with a tracer, the host region
        ``serve.schedule`` (the store reads of the issued reads in it)."""
        if self.tracer is None:
            return self._schedule()
        with self.tracer.region("serve", "serve.schedule") as r:
            n = r.args["issued"] = self._schedule()
        return n

    def _schedule(self) -> int:
        self._fetch_groups()
        # decide paths for every ready request first (read queues build up
        # across the batch of decisions, as on a live cluster), then read
        ready = []
        for er in list(self._inflight.values()):
            req = er.req
            if req.pe is None or req.de is None or req.read_path is not None:
                continue
            if self.mode == "basic":
                req.read_path = "pe"
                self.sched.engines[req.pe].read_q += req.cached_tokens
            else:
                tier_tokens = None
                if self.tiers and er.hit_refs:
                    bt = self.layout.block_tokens
                    tier_tokens = {
                        "pe": self.tiers[req.pe[0]]
                              .resident_prefix(er.hit_refs) * bt,
                        "de": self.tiers[req.de[0]]
                              .resident_prefix(er.hit_refs) * bt,
                    }
                self.sched.choose_read_path(
                    req, tier_tokens=tier_tokens,
                    net_congestion=self.net_congestion)
                if self.hedge_reads and self.faults is not None:
                    self._maybe_hedge(req)
                if req.dram_tokens:
                    # pin the tier-resident prefix NOW: reads of other
                    # ready requests admit blocks (and may evict) before
                    # this one's turn — pinned blocks cannot disappear
                    # between the path decision and the read
                    bt = self.layout.block_tokens
                    node = (req.pe if req.dram_side == "pe" else req.de)[0]
                    prefix = er.hit_refs[:req.dram_tokens // bt]
                    self.tiers[node].pin(prefix)
                    er._tier_pinned = (node, prefix)
            ready.append(er)
        for er in ready:
            self._set_state(er, ReqState.READING)
            if self.pipelined:
                self._issue_read(er)
            else:
                self._do_read(er)
        return len(ready)

    def _maybe_hedge(self, req: Request) -> int:
        """Hedged split read (issue-time): if one side's storage leg is
        degraded — straggler draw and/or an active SNIC slowdown window
        on its node — by ``hedge_min_severity``× or more relative to the
        other, re-water-fill that side's *remainder* to the healthy side
        via ``Scheduler.rebalance_remainder`` before the legs are built.
        The serving runtime's reads are issued and completed within one
        tick, so the hedge decision lands at issue; the simulator owns
        the mid-flight variant of the same re-fill.  Tier-hit tokens are
        untouched (they are not SNIC charge to begin with)."""
        toks = req.read_tokens_by_side()
        if not (toks["pe"] > 0 and toks["de"] > 0):
            return 0
        now = self.clock.now
        f = {s: self.faults.leg_factor(req.rid, s) *
             self.faults.snic_factor(
                 (req.pe if s == "pe" else req.de)[0], now)
             for s in ("pe", "de")}
        for slow, fast in (("pe", "de"), ("de", "pe")):
            if f[fast] <= 0 or f[slow] / f[fast] < self.hedge_min_severity:
                continue
            healthy = req.pe if fast == "pe" else req.de
            st = self.sched.engines.get(healthy)
            # backlog ahead of this request on the healthy NIC = its
            # reading queue minus this request's own charge there
            backlog = max((st.read_q if st is not None else 0)
                          - toks[fast], 0)
            moved = self.sched.rebalance_remainder(
                req, slow, toks[slow], f[slow] / f[fast],
                healthy_backlog_tokens=backlog)
            if moved:
                self.hedged_reads += 1
                self.hedge_moved_tokens += moved
            return moved
        return 0

    # ------------------------------------------------------------------
    # the read, split into issue/complete halves
    # ------------------------------------------------------------------
    def _read_transfers(self, er: EngineRequest
                        ) -> List[Tuple[TrafficManager, callable, int]]:
        """Issue half of a read: perform the store/tier accesses and the
        byte accounting NOW and return ``(tm, thunk, nbytes)`` transfer
        descriptors whose execution (the completion half) models the
        bytes landing in the PE's buffers.

        Pure reads ride one side's TrafficManager (storage→PE directly,
        or storage→DE→compute-network→PE).  Split reads (scheduler
        ``split_reads=True``, §6.1 future work) partition the hit
        FullBlocks at page granularity: the PE side reads the leading
        pages while the DE side reads the trailing ones concurrently,
        and only the DE share crosses the compute network — the engine
        realisation of core/loading.split_read_plan.  Transfer seconds
        are charged to the tick's io ledger per physical resource."""
        req = er.req
        pe = self.pes[req.pe]
        de_tm = self.des[req.de].tm
        pe_node, de_node = req.pe[0], req.de[0]
        tmod = self.time_model
        out: List[Tuple[TrafficManager, callable, int]] = []
        if uses_state_blob(self.cfg):
            # one opaque state snapshot: unsplittable, rides the chosen side
            side = req.read_path
            payload = er._blob
            nbytes = len(payload) if payload else 0
            self.read_bytes_by_side[side] += nbytes
            if nbytes and self.tracer is not None:
                self.tracer.event(f"req/{req.rid}", "storage_read",
                                  side=side, nbytes=nbytes)
            er._read_box = {}
            node = pe_node if side == "pe" else de_node
            self._tick_io.add(("snic", node),
                              self._snic_s(node, nbytes, rid=req.rid,
                                           side=side))
            out.append((pe.tm if side == "pe" else de_tm,
                        lambda p=payload, box=er._read_box: box.update(p=p),
                        nbytes))
            if side == "de":
                self._tick_io.add(("cn", pe_node), self._cn_s(nbytes))
                out.append((pe.tm, lambda: None, nbytes))
            return out
        n = len(er.hit_refs)
        tid = er._session.traj.tid
        # ---- source segments: (kind, side, refs, lo) --------------------
        # The DRAM-tier prefix (when any) is served by the tier side's
        # node without touching the store; the cold remainder is read
        # from storage, PE side first then DE side (page order).  The
        # block partition comes from the request itself (the same one
        # the simulator's admission sets use).
        part = req.hit_blocks_by_side(n)
        k_tier, k_pe = part["tier"], part["pe"]
        segs = [("tier", req.dram_side, er.hit_refs[:k_tier], 0),
                ("snic", "pe", er.hit_refs[k_tier:k_tier + k_pe], k_tier),
                ("snic", "de", er.hit_refs[k_tier + k_pe:], k_tier + k_pe)]
        # a split read means both storage NICs served this request (PR 1
        # semantics) — tier-served segments don't count
        if part["pe"] and part["de"]:
            self.n_split_reads += 1
        er._read_payload = [None] * n
        payload = er._read_payload
        for kind, side, refs, lo in segs:
            if not refs:
                continue
            node = pe_node if side == "pe" else de_node
            # read_bytes_by_side stays per-side *storage* (SNIC) traffic,
            # matching the sim's snic accounting; DRAM-served bytes are
            # tracked separately in dram_bytes_by_side
            if kind == "tier":
                tier = self.tiers[node]
                # pinned since the path decision — every ref is resident,
                # so none of these reads reaches the backing store
                blocks = tier.read_blocks(refs, owner=tid,
                                          now=self._tier_now())
                hit_b = sum(b.nbytes for b in blocks)
                self.dram_bytes_by_side[side] += hit_b
                if hit_b and self.tracer is not None:
                    self.tracer.event(f"req/{req.rid}", "tier_hit",
                                      side=side, nbytes=hit_b)
                self._tick_io.add(("dram", node), tmod.dram_seconds(hit_b))
            elif node in self.tiers:
                # read through the node tier: misses hit the store (the
                # SNIC) and are admitted, warming the tier for the next
                # round on this node; stray resident blocks (outside the
                # probed prefix) still serve from DRAM
                tier = self.tiers[node]
                m0, h0 = tier.miss_bytes, tier.dram_hit_bytes
                blocks = tier.read_blocks(refs, owner=tid,
                                          now=self._tier_now())
                miss_b = tier.miss_bytes - m0
                hit_b = tier.dram_hit_bytes - h0
                self.read_bytes_by_side[side] += miss_b
                self.dram_bytes_by_side[side] += hit_b
                if self.tracer is not None:
                    if miss_b:
                        self.tracer.event(f"req/{req.rid}", "storage_read",
                                          side=side, nbytes=miss_b)
                    if hit_b:
                        self.tracer.event(f"req/{req.rid}", "tier_hit",
                                          side=side, nbytes=hit_b)
                self._tick_io.add(("snic", node),
                                  self._snic_s(node, miss_b, rid=req.rid,
                                               side=side))
                self._tick_io.add(("dram", node), tmod.dram_seconds(hit_b))
            else:
                blocks = self.store.read_blocks(refs)
                nb = sum(b.nbytes for b in blocks)
                self._tick_io.add(("snic", node),
                                  self._snic_s(node, nb, rid=req.rid,
                                               side=side))
                self.read_bytes_by_side[side] += nb
                if nb and self.tracer is not None:
                    self.tracer.event(f"req/{req.rid}", "storage_read",
                                      side=side, nbytes=nb)
            nbytes = sum(b.nbytes for b in blocks)
            out.append((pe.tm if side == "pe" else de_tm,
                        lambda blocks=blocks, lo=lo:
                        payload.__setitem__(slice(lo, lo + len(blocks)),
                                            blocks),
                        nbytes))
            if side == "de":
                # DE buffer -> PE over the compute network (layerwise)
                self._tick_io.add(("cn", pe_node), self._cn_s(nbytes))
                out.append((pe.tm, lambda: None, nbytes))
        if er._tier_pinned is not None:
            # the tier segment is read (copied out) — the pin taken at
            # the path decision has done its job
            node, prefix = er._tier_pinned
            self.tiers[node].unpin(prefix)
            er._tier_pinned = None
        return out

    def _do_read(self, er: EngineRequest):
        """Blocking read: every transfer drains inline (one degenerate
        single-item doorbell each) before the hit KV installs."""
        for tm, fn, nbytes in self._read_transfers(er):
            tm.submit(fn, nbytes, TrafficClass.KV_TRANSFER)
            tm.drain()
        self._read_complete(er)

    def _issue_read(self, er: EngineRequest) -> int:
        """Pipelined read: submit every transfer and flush each involved
        TrafficManager once (multi-WR doorbell batches) — the transfers
        stay in flight across this tick's engine compute and land at the
        tick's poll, which marks the request install-ready."""
        transfers = self._read_transfers(er)
        by_tm: Dict[int, Tuple[TrafficManager, list]] = {}
        for tm, fn, nbytes in transfers:
            by_tm.setdefault(id(tm), (tm, []))[1].append((fn, nbytes))
        if not by_tm:
            self._install_ready.append(er)
            return 0
        pending = [len(by_tm)]

        def tm_done():
            pending[0] -= 1
            if pending[0] == 0:
                self._install_ready.append(er)

        for tm, items in by_tm.values():
            for fn, nbytes in items:
                tm.submit(fn, nbytes, TrafficClass.KV_TRANSFER)
            tm.flush(on_complete=tm_done)
        return len(transfers)

    def _read_complete(self, er: EngineRequest):
        """Completion half: release the read-queue charge, record the
        timestamp and install the hit KV on the PE (layerwise
        double-buffered through kvio.layer_stream)."""
        req = er.req
        self._release_read_q(req)
        self._stamp(req.rid, "read_done_t")
        self._set_state(er, ReqState.PREFILL)
        pe = self.pes[req.pe]
        if uses_state_blob(self.cfg):
            pe.install_hit_kv(er, er._read_box.get("p"))
        else:
            pe.install_hit_kv(er, [b for b in er._read_payload
                                   if b is not None])

    def _release_read_q(self, req: Request):
        """Release exactly what choose_read_path charged — with
        split_reads the charge may span both sides."""
        tokens = req.read_tokens_by_side()
        for side in ("pe", "de"):
            if tokens[side]:
                self.sched.on_read_done(
                    req.pe if side == "pe" else req.de, tokens[side])

    # ------------------------------------------------------------------
    # engine phases
    # ------------------------------------------------------------------
    def _charge_collectives(self, node: int, tokens: int) -> None:
        """Per-layer model collectives of a forward/decode step over
        ``tokens`` land on the stepping node's CNIC link; they contend
        with that link's KV traffic at the tick's contention resolution
        (``_apply_net_contention``)."""
        coll = self.time_model.collectives
        if coll is None or tokens <= 0:
            return
        self._tick_coll[node] = self._tick_coll.get(node, 0.0) + \
            self.time_model.collective_seconds(coll.step_bytes(tokens))

    def _step_pes(self) -> int:
        act = 0
        pe_max = 0.0
        for pe in self.pes.values():
            before = pe.prefill_tokens
            done = pe.step()
            pe_max = max(pe_max,
                         self.time_model.pe_step_seconds(pe.last_step_items))
            self._charge_collectives(
                pe.eid[0], sum(b for _, b in pe.last_step_items))
            act += (pe.prefill_tokens - before) + len(done)
            if self.slo_cfg.prefill_chunk_tokens is not None:
                # chunked-prefill sub-state: a capped slice ran and the
                # round stays in the PE fifo for its next slice; decode
                # steps interleave in the meantime.  Entered only when
                # the chunk cap is configured, so unchunked runs keep
                # the legacy PREFILL-only lifecycle event-for-event.
                for er in pe.last_step_chunked:
                    self.prefill_chunks += 1
                    if er.lifecycle != ReqState.PREFILL_CHUNKED:
                        self._set_state(er, ReqState.PREFILL_CHUNKED)
            for er in done:
                self.sched.on_request_done(er.req.pe, er.req)
                self._stamp(er.req.rid, "prefill_done_t")
                self._set_state(er, ReqState.PD_TRANSFER)
                self._queue_pd_transfer(er)
        self._tick_compute += pe_max
        return act

    def _queue_pd_transfer(self, er: EngineRequest):
        # PE -> DE prompt-state transfer (compute network), one
        # submission per attention layer: the DE-side doorbell batching
        # sees the same LayerBlock granularity the layerwise install
        # used on the PE side
        n_l = max(kvio.n_attn_layers(self.cfg), 1)
        nbytes = er.req.prompt_tokens * self.cfg.kv_bytes_per_token()
        de_tm = self.des[er.req.de].tm
        per_layer, rem = divmod(nbytes, n_l)
        for li in range(n_l):
            # last layer carries the remainder: byte totals stay
            # exact across the per-layer submissions
            de_tm.submit(lambda: None,
                         per_layer + (rem if li == n_l - 1 else 0),
                         TrafficClass.KV_TRANSFER)
        self._tick_io.add(("cn", er.req.de[0]), self._cn_s(nbytes))
        if self.pipelined:
            self._pd_queue.append(er)
            de_tm.flush(on_complete=lambda er=er:
                        setattr(er, "_pd_ready", True))
        else:
            de_tm.drain()
            self._pending_admit.append(er)

    def _collect_pd(self) -> int:
        """Move PD-complete requests to the admission queue, preserving
        the order their prefills finished (= the blocking runtime's
        admission order)."""
        still: List[EngineRequest] = []
        n = 0
        for er in self._pd_queue:
            if er._cancelled:
                continue               # re-homed after an engine death
            if er._pd_ready:
                er._pd_ready = False
                self._pending_admit.append(er)
                n += 1
            else:
                still.append(er)
        self._pd_queue = still
        return n

    def _admit_pending(self) -> int:
        n = 0
        still = deque()
        while self._pending_admit:
            er = self._pending_admit.popleft()
            if er._cancelled:
                continue               # re-homed after an engine death
            de = self.des[er.req.de]
            if de.free_slots:
                self._set_state(er, ReqState.DECODE)
                de.admit(er)
                n += 1
            else:
                still.append(er)
        self._pending_admit = still
        return n

    def _step_des(self) -> int:
        act = 0
        de_max = 0.0
        for de in self.des.values():
            de_node = de.eid[0]
            active_before = [er for er in de.slots if er is not None]
            steps0 = de.decode_steps
            b0 = de.tm.bytes[TrafficClass.KV_TRANSFER]
            finished = de.step()
            de_max = max(de_max,
                         self.time_model.de_step_seconds(de.last_step_ctxs))
            self._charge_collectives(de_node, len(de.last_step_ctxs))
            act += (de.decode_steps - steps0) + len(finished)
            persist_b = de.tm.bytes[TrafficClass.KV_TRANSFER] - b0
            if persist_b and self.tracer is not None:
                self.tracer.event(f"engine/node{de_node}", "persist",
                                  nbytes=persist_b)
            self._tick_io.add(("snic", de_node),
                              self._snic_s(de_node, persist_b))
            for er in active_before:
                m = self.metrics.get(er.req.rid)
                if m is None:
                    continue
                if m.first_decode_t < 0:
                    self._stamp(er.req.rid, "first_decode_t")
                if len(er.generated) >= 2 and m.second_token_t < 0:
                    self._stamp(er.req.rid, "second_token_t")
            for er in finished:
                self.sched.on_request_done(er.req.de, er.req)
                self._stamp(er.req.rid, "done_t")
            if self.pipelined:
                pend, de.pending_persist = de.pending_persist, []
                if pend:
                    for er, _ in pend:
                        self._set_state(er, ReqState.PERSIST)

                    def persists_done(pend=pend):
                        for er, fin in pend:
                            if er._cancelled:
                                continue   # engine died; round re-runs
                            if fin is not None:
                                fin()
                            self._finish_round(er)

                    de.tm.flush(on_complete=persists_done)
            else:
                for er in finished:
                    self._finish_round(er)
        self._tick_compute += de_max
        return act

    def _finish_round(self, er: EngineRequest):
        """Round completion (after the persist landed): session context
        rolls forward, tier warm-up/prefetch runs, and the next round
        submits — immediately offline, after the think gap online."""
        sess = er._session
        sess.context = (er.context_tokens + er.append_tokens +
                        er.generated)
        sess.rounds_done += 1
        sess.current = None
        self._set_state(er, ReqState.DONE)
        self.gen_tokens_done += len(er.generated)
        del self._inflight[er.req.rid]
        if self.tiers:
            self._round_finished_tier(sess, er.req.de[0])
        if sess.next_round < sess.traj.n_rounds:
            think = sess.traj.rounds[sess.next_round].think
            if self._online and think > 0:
                self.loop.after(think, lambda s=sess: self._submit_round(s))
            else:
                self._submit_round(sess)

    # ------------------------------------------------------------------
    def _round_finished_tier(self, sess: AgentSession, de_node: int):
        """Inter-round tier maintenance (think-time window).

        1. Warm the decode node's tier with the round's full context —
           every one of those blocks just staged through that node's
           DRAM (decode_start H2D + block persists), so admission moves
           no new storage bytes (``store.peek``).
        2. Think-time prefetch: the next round's predicted hit is
           exactly the trie match of the current context; stage any
           blocks capacity pressure evicted back into the tier ahead of
           the round start.  Reads go through the backing store (real
           SNIC traffic, paid during the idle gap).  The prefetch fires
           right after warm-up — in online mode that is the start of
           the think gap, whose seconds also age the TTL policy; the
           simulator additionally models the late-window issue timing
           (Sim._schedule_prefetch).
        """
        tid = sess.traj.tid
        tier = self.tiers[de_node]
        now = self._tier_now()
        if uses_state_blob(self.cfg):
            return
        if sess.next_round >= sess.traj.n_rounds:
            # finished trajectory: never hit again (§A.4) — warming the
            # tier with it would only evict live sessions' prefixes
            for t in self.tiers.values():
                t.note_done(tid)
            return
        _, refs = self.trie.match(sess.context)
        # tail-first: keeps the leading blocks most recent, so LRU
        # eviction trims the tail and the servable prefix survives
        for r in reversed(refs):
            tier.admit(r, self.layout.full_block_bytes, owner=tid,
                       payload=self.store.peek(r), now=now)
        if self.prefetcher is not None:
            for chunk in self.prefetcher.plan(tier, refs):
                for r in chunk:
                    tier.prefetch_block(r, owner=tid, now=now)

    # ------------------------------------------------------------------
    # the event loop tick
    # ------------------------------------------------------------------
    def _poll_all(self) -> int:
        """Complete every in-flight transfer (tick phase 4): completion
        callbacks mark requests install-ready / PD-ready and run persist
        finalisation + next-round submission.  With a tracer, the host
        region ``serve.poll`` (deferred store writes run in it)."""
        if self.tracer is None:
            return self._poll()
        with self.tracer.region("serve", "serve.poll") as r:
            n = r.args["completed"] = self._poll()
        return n

    def _poll(self) -> int:
        n = 0
        progress = True
        while progress:
            progress = False
            for tm in self._all_tms():
                if tm.queued:
                    tm.flush()
                k = tm.poll()
                if k:
                    progress = True
                    n += k
        return n

    def _run_installs(self) -> int:
        """Install the hit KV of read-complete requests, in decision
        (rid) order — the blocking runtime's install order."""
        ready, self._install_ready = self._install_ready, []
        ready.sort(key=lambda er: er.req.rid)
        n = 0
        for er in ready:
            if er._cancelled:
                continue       # stale completion of a re-homed request:
            n += 1             # its charges were already released
            self._read_complete(er)
        return n

    def _set_state(self, er: EngineRequest, state: ReqState):
        """Lifecycle transition.  With a tracer attached the previous
        state is closed as a span on the request's track at the end of
        the current tick (``_flush_stamps``) so span edges line up with
        the stamped milestones."""
        er.lifecycle = state
        if self.tracer is not None:
            self._pending_states.append((er, state))

    def _trace_submit(self, er: EngineRequest):
        """Open the lifecycle span chain at submission time itself (not
        end-of-tick): the first span's t0 must equal the metrics'
        ``submit_t`` so the attribution window matches measured TTFT."""
        if self.tracer is not None:
            er._span_state = "scheduled"
            er._state_t0 = self.clock.now

    def _stamp(self, rid: int, field_name: str):
        """Defer a milestone timestamp to the end of the current tick
        (after the clock charges the tick's modelled seconds) — stamping
        with the pre-advance time would make every latency metric
        exclude the tick its milestone occurred in."""
        m = self.metrics.get(rid)
        if m is not None:
            self._pending_stamps.append((m, field_name))

    def _flush_stamps(self):
        now = self.clock.now
        for m, fld in self._pending_stamps:
            if getattr(m, fld) < 0:
                setattr(m, fld, now)
                if fld == "prefill_done_t" and self.tracer is not None:
                    # TTFT endpoint (events.RoundMetrics.ttft)
                    self.tracer.event(f"req/{m.rid}", "first_token")
        self._pending_stamps = []
        for er, state in self._pending_states:
            prev = getattr(er, "_span_state", None)
            t0 = getattr(er, "_state_t0", now)
            if prev is not None and now > t0:
                self.tracer.span(f"req/{er.req.rid}", prev, t0, now)
            er._span_state = state.name.lower()
            er._state_t0 = now
        self._pending_states.clear()

    def _submit_overhead_delta(self) -> float:
        tot = sum(tm.submitted_seconds for tm in self._all_tms())
        d = tot - self._submit_seconds_seen
        self._submit_seconds_seen = tot
        return d

    def _apply_net_contention(self) -> None:
        """Resolve this tick's KV-vs-collective contention per CNIC link
        (repro.network.drain_times): each link's KV ledger inflates to
        the contended completion time (``transfer_backlog_s``) and any
        time the collectives finish after their uncontended service —
        model execution stalling on communication — is charged to the
        tick's compute (``collective_stall_s``): ≈ 0 under the VL
        arbiter, growing with transfer load under FIFO sharing.  The
        aggregate collective share of the link becomes the congestion
        signal next tick's read-path choices and KV pacing consume.
        No-op (all-zero ledgers) when collectives are off, keeping the
        legacy clock arithmetic bit-identical."""
        tot_coll = sum(self._tick_coll.values())
        tot_kv = 0.0
        for node, coll_s in self._tick_coll.items():
            if coll_s <= 0:
                continue
            kv_s = self._tick_io.buckets.get(("cn", node), 0.0)
            tot_kv += kv_s
            kv_done, coll_done = self.time_model.cn_drain(kv_s, coll_s)
            if kv_s > 0:
                self._tick_io.buckets[("cn", node)] = kv_done
            stall = max(0.0, coll_done - coll_s)
            self._tick_compute += stall
            self.collective_stall_s += stall
            self.transfer_backlog_s += max(0.0, kv_done - kv_s)
        tot = tot_coll + tot_kv
        self.net_congestion = (tot_coll / tot) if tot > 0 else 0.0
        for tm in self._all_tms():
            tm.net_congestion = self.net_congestion

    # ------------------------------------------------------------------
    # elastic role reconfiguration (core/autoscale.py), driven by the
    # existing tick loop
    # ------------------------------------------------------------------
    def _elastic_signals(self) -> LoadSignals:
        sched = self.sched
        spec = self.time_model.spec
        node = self.time_model.node
        pe_rate = max(node.gpu.flops * node.gpu.mfu_prefill /
                      max(spec.linear_flops_per_token(), 1.0), 1.0)
        pe_queued = sum(r.new_tokens for r in sched.pe_queue)
        pe_busy = sum(w.remaining for pe in self.pes.values()
                      for w, _ in pe.fifo)
        de_busy_tok = 0
        n_active = 0
        ctxs: List[float] = []
        for de in self.des.values():
            for slot, er in enumerate(de.slots):
                if er is None:
                    continue
                n_active += 1
                de_busy_tok += er.req.gen_tokens - len(er.generated)
                ctxs.append(float(de.lengths[slot]))
        de_q_tok = 0
        for q in (sched.de_global_queue, *sched.de_private.values()):
            for r in q:
                de_q_tok += r.gen_tokens
                ctxs.append(float(r.prompt_tokens))
        n_de_now = max(len(self.des), 1)
        n_ref = max(n_active / n_de_now, 1.0)
        ctx_ref = (sum(ctxs) / len(ctxs)) if ctxs else 1.0
        kv_step = spec.decode_step_bytes(ctx_ref)
        w = spec.active_param_bytes_resident(1)
        de_rate = max(n_ref * node.gpu.hbm_bw * node.gpu.mbu_decode /
                      max(n_ref * kv_step + w, 1.0), 1.0)
        kv_tok = max(spec.kv_bytes_per_token, 1)
        snic_tok_rate = max(node.snic_bw / kv_tok, 1.0)
        pe_rq = sum(st.read_q for st in sched.engines.values()
                    if st.kind == "pe" and not st.draining)
        de_rq = sum(st.read_q for st in sched.engines.values()
                    if st.kind == "de" and not st.draining)
        tiers = list(self.tiers.values())
        dram_hit = sum(t.dram_hit_bytes for t in tiers)
        denom = dram_hit + sum(self.read_bytes_by_side.values())
        # class-aware signals: interactive queue depth feeds the elastic
        # controller extra pressure (core/autoscale.LoadSignals); 0.0
        # whenever class scheduling is off so pressures stay identical
        pe_q_int = de_q_int = 0.0
        if sched.class_aware:
            pe_q_int = sum(r.new_tokens for r in sched.pe_queue
                           if r.class_rank == 0) / pe_rate
            de_q_int = sum(r.gen_tokens
                           for q in (sched.de_global_queue,
                                     *sched.de_private.values())
                           for r in q if r.class_rank == 0) / de_rate
        return LoadSignals(
            n_pe=len(sched.admitting("pe")),
            n_de=len(sched.admitting("de")),
            pe_queued_s=pe_queued / pe_rate,
            pe_busy_s=pe_busy / pe_rate,
            de_queued_s=de_q_tok / de_rate,
            de_busy_s=de_busy_tok / de_rate,
            pe_read_q_s=pe_rq / snic_tok_rate,
            de_read_q_s=de_rq / snic_tok_rate,
            net_congestion=self.net_congestion,
            dram_hit_ratio=(dram_hit / denom) if denom else 0.0,
            pe_queued_interactive_s=pe_q_int,
            de_queued_interactive_s=de_q_int,
        )

    def _begin_reconfig(self, action: str):
        src = "de" if action == DE_TO_PE else "pe"
        cands = self.sched.admitting(src)
        if len(cands) <= 1:
            return

        def load_of(st):
            if st.kind == "de":
                de = self.des[st.engine]
                return st.tok + (de.n_slots - de.free_slots) * self.max_seq
            return st.tok + st.read_q

        victim = pick_victim(cands, self.drain_policy, load_of,
                             rotation=self._drain_rotation)
        self._drain_rotation += 1
        self.sched.begin_drain(victim.engine)
        self.sched.requeue_unstarted(
            victim.engine, [er.req for er in self._inflight.values()])
        self.engine_lifecycle[victim.engine] = EngineLifecycle.DRAINING
        self.drains.begin(victim.engine, src,
                          "pe" if src == "de" else "de", self.clock.now)

    def _engine_drained(self, eid: Tuple[int, int], kind: str) -> bool:
        """In-flight lifecycle states emptied?  The scheduler's seq/tok
        gate covers assigned requests end-to-end; the engine-local
        checks cover work the scheduler has already released but whose
        completion half is still parked (deferred persists, unflushed
        doorbells)."""
        if not self.sched.can_finish_drain(eid):
            return False
        if kind == "pe":
            pe = self.pes[eid]
            return not pe.fifo and not pe.tm.busy
        de = self.des[eid]
        return de.free_slots == de.n_slots and not de.pending_persist \
            and not de.tm.busy and \
            not any(er.req.de == eid for er in self._inflight.values())

    def _finish_flip(self, rec):
        eid = rec.engine
        node_id = eid[0]
        gid = next(self._next_gid)
        tier = self.tiers.get(node_id)
        handoff = int(tier.used_bytes) if tier is not None else 0
        if rec.to_kind == "pe":
            del self.des[eid]
            self.pes[eid] = PrefillEngine(
                eid, self.cfg, self.params, self.store, self.layout,
                self.max_seq, self._quota_s, layerwise=self._layerwise,
                chunk_tokens=self.slo_cfg.prefill_chunk_tokens,
                class_aware=self.slo_cfg.class_aware)
            self.sched.finish_drain(eid, kind="pe", group=gid)
        else:
            del self.pes[eid]
            de_store = self.tiers.get(node_id, self.store)
            de = DecodeEngine(eid, self.cfg, self.params, de_store,
                              self.trie, self.layout, self.max_seq,
                              n_slots=self._de_slots,
                              blob_store=self.blob_store)
            de.defer_persist = self.pipelined
            self.des[eid] = de
            self.sched.finish_drain(eid, kind="de", group=gid,
                                    free_hbm_tokens=self._de_slots *
                                    self.max_seq)
        # the DE-group topology changed: re-route queued requests
        self.sched.rebalance_de_private()
        self.engine_lifecycle[eid] = EngineLifecycle.ACTIVE
        rec = self.drains.finish(eid, self.clock.now,
                                 tier_handoff_bytes=handoff)
        if self.tracer is not None:
            eng = self.pes.get(eid) or self.des[eid]
            eng.tracer = self.tracer
            self.tracer.span(
                "reconfig", "drain", rec.t_begin, self.clock.now,
                engine=list(eid),
                direction=f"{rec.from_kind}->{rec.to_kind}")

    def _elastic_tick(self):
        """Phase 0 of an elastic tick: flip engines whose RECONFIGURING
        weight reload was charged last tick, advance active drains
        (drained -> RECONFIGURING + weight-reload io), then let the
        controller observe once per ``reconfig_interval_s``."""
        for rec in self._reconfig_ready:
            self._finish_flip(rec)
        self._reconfig_ready = []
        for eid, rec in list(self.drains.active.items()):
            if rec.t_drained >= 0:
                continue
            if not self._engine_drained(eid, rec.from_kind):
                continue
            self.drains.mark_drained(eid, self.clock.now)
            self.engine_lifecycle[eid] = EngineLifecycle.RECONFIGURING
            w = self.time_model.spec.active_param_bytes_resident(1)
            self.reconfig_weight_bytes += w
            self._tick_io.add(("snic", eid[0]), self._snic_s(eid[0], w))
            self._reconfig_ready.append(rec)
        if self.clock.now >= self._next_obs_t:
            self._next_obs_t = self.clock.now + self.reconfig_interval_s
            if not self.drains.active and not self._reconfig_ready:
                action = self.controller.observe(self._elastic_signals(),
                                                 self.clock.now)
                if action is not None:
                    self._begin_reconfig(action)

    # ------------------------------------------------------------------
    # engine failure (sim/faults.py EngineDeath): fail-stop + re-home
    # ------------------------------------------------------------------
    def _fault_tick(self):
        """Process every death whose time has arrived (tick phase -1,
        before scheduling) — the serving analogue of the simulator's
        death events."""
        while self._deaths_pending and \
                self._deaths_pending[0].t <= self.clock.now:
            d = self._deaths_pending.pop(0)
            self._engine_death(tuple(d.engine))

    def _engine_death(self, eid: Tuple[int, int]):
        """Fail-stop of engine ``eid``: abort any drain it was part of,
        hand unstarted assignments back to the queues, re-home every
        round with physical state on the engine (restart from persisted
        KV — the trie still holds every block persisted *before* the
        death, and blocks whose persist writes had not landed are
        re-persisted exactly once by the recovery run), then remove the
        engine from the scheduler registry so nothing routes to it.
        Role backfill is emergent: the survivors' pressure shift feeds
        the PDController, which proposes a compensating flip."""
        if eid not in self.pes and eid not in self.des:
            return                     # already dead / never existed
        self.dead_engines.append(eid)
        if self.tracer is not None:
            kind = "pe" if eid in self.pes else "de"
            self.tracer.event("faults/deaths", "engine_death",
                              engine=list(eid), kind=kind)
        # a victim dying mid-drain is not a role change: drop the record
        self.drains.abort(eid)
        self._reconfig_ready = [r for r in self._reconfig_ready
                                if r.engine != eid]
        # assigned-but-unstarted requests go back to the queues whole —
        # nothing physical happened for them on this engine
        self.sched.requeue_unstarted(
            eid, [er.req for er in self._inflight.values()])
        # rounds with physical state on the engine restart.  PE
        # involvement ends once the prompt state left for the DE
        # (PD_TRANSFER rides the DE's TrafficManager); DE involvement
        # lasts until the round's persist lands.
        for er in list(self._inflight.values()):
            req = er.req
            if req.de == eid or (req.pe == eid and er.lifecycle in (
                    ReqState.SCHEDULED, ReqState.READING,
                    ReqState.PREFILL)):
                self._resubmit_round(er)
        self.sched.fail_engine(eid)
        self.pes.pop(eid, None)
        self.des.pop(eid, None)
        self.engine_lifecycle[eid] = EngineLifecycle.DEAD
        # the group topology changed: re-route queued DE requests
        self.sched.rebalance_de_private()

    def _resubmit_round(self, er: EngineRequest):
        """Partial-leg cancellation + restart of one re-homed round.

        The old EngineRequest is marked ``_cancelled`` so every stale
        completion half (a surviving read leg's install, a parked PD
        entry, a pending admit) discards itself; its scheduler charges
        are released per lifecycle state (the dead engine's own charges
        are forfeited by the tolerant hooks).  A fresh request under a
        new rid restarts from the *persisted* prefix — the trie match
        of the same prompt tokens, no session-RNG redraw — and inherits
        the original RoundMetrics (same submit_t), so TTFT/TPOT include
        the recovery gap honestly.  Greedy decode regenerates the same
        tokens, which keeps session context and persisted blocks
        identical to a fault-free run."""
        if er._cancelled:
            return
        er._cancelled = True
        req = er.req
        sess = er._session
        if er._tier_pinned is not None:
            node, prefix = er._tier_pinned
            self.tiers[node].unpin(prefix)
            er._tier_pinned = None
        lc = er.lifecycle
        if lc == ReqState.READING:
            # the read never completed: the full path-decision charge is
            # still held on both sides' reading queues
            self._release_read_q(req)
        if lc in (ReqState.SCHEDULED, ReqState.READING, ReqState.PREFILL):
            if req.pe is not None:
                self.sched.on_request_done(req.pe, req)
                pe = self.pes.get(req.pe)
                if pe is not None:
                    pe.fifo = [(w, e) for (w, e) in pe.fifo if e is not er]
        if req.de is not None and lc in (
                ReqState.SCHEDULED, ReqState.READING, ReqState.PREFILL,
                ReqState.PD_TRANSFER, ReqState.DECODE):
            # the DE charge (seq/tok/HBM reservation) is held from
            # assignment until decode finishes
            self.sched.on_request_done(req.de, req)
        del self._inflight[req.rid]
        # -- fresh request over the same tokens -------------------------
        prompt = er.context_tokens + er.append_tokens
        if uses_state_blob(self.cfg):
            blob, hit = self.blob_store.get(sess.context)
            refs = []
            hit = hit if blob is not None else 0
        else:
            hit, refs = self.trie.match(prompt)
            blob = None
        if hit >= len(prompt):         # keep >= 1 token to prefill
            hit = len(prompt) - 1
            refs = refs[:hit // self.layout.block_tokens]
        req2 = Request(rid=next(self._rid), cached_tokens=hit,
                       new_tokens=len(prompt) - hit,
                       gen_tokens=req.gen_tokens,
                       arrival=req.arrival,   # original queue priority
                       slo_class=req.slo_class)
        er2 = EngineRequest(req=req2, context_tokens=prompt[:hit],
                            append_tokens=prompt[hit:], hit_refs=refs)
        er2._blob = blob
        er2._session = sess
        er2._tier_pinned = None
        er2._pd_ready = False
        er2._cancelled = False
        er2.lifecycle = ReqState.SCHEDULED
        self._trace_submit(er2)
        sess.current = er2
        self._inflight[req2.rid] = er2
        m = self.metrics.pop(req.rid)
        m.rid = req2.rid
        self.metrics[req2.rid] = m
        self.recovered_rounds += 1
        if self.tracer is not None:
            self.tracer.event(f"req/{req2.rid}", "recovered",
                              old_rid=req.rid, cached_tokens=hit)
        self.sched.submit(req2)

    def _tick(self) -> int:
        """One event-loop tick; returns an activity count (0 = idle).

        Pipelined: reads issued in phase 1 and PD/persist transfers
        flushed in phases 2–3 stay in flight across the engine compute
        and land at phase 4's poll, so the clock charges
        ``max(transfer, compute)``.  Blocking: the same phases with
        inline drains — the clock charges ``transfer + compute``.
        With a tracer the tick is the host region ``serve.tick``.
        """
        tr = self.tracer
        if tr is None:
            return self._tick_phases()
        steps0 = sum(de.decode_steps for de in self.des.values())
        with tr.region("serve", "serve.tick") as r:
            act = self._tick_phases()
            r.args.update(inflight=len(self._inflight), decode_steps=sum(
                de.decode_steps for de in self.des.values()) - steps0)
        # device bytes of the per-request prefill states alive (installed,
        # prefilling, or waiting for a decode slot), when it changes
        held = sum(state_bytes(er.state) for er in self._inflight.values()
                   if er.state is not None)
        if held != self._prefill_state_bytes:
            self._prefill_state_bytes = held
            tr.counter("serve", prefill_state_bytes=held)
        return act

    def _tick_phases(self) -> int:
        self._tick_io = TickIo()
        self._tick_compute = 0.0
        self._tick_coll = {}
        act = 0
        if self._deaths_pending:
            self._fault_tick()
        if self.elastic:
            self._elastic_tick()
        if self.pipelined:
            act += self._schedule_tick()     # 1. decide + issue reads
            act += self._step_pes()          # 2. prefill compute
            act += self._step_des()          # 3. decode compute
            act += self._poll_all()          # 4. transfer completions
            act += self._run_installs()      # 5. hit-KV installs
            self._collect_pd()
            act += self._admit_pending()     # 6. DE admissions
            self._apply_net_contention()
            dt = max(self._tick_io.parallel_seconds(), self._tick_compute)
        else:
            act += self._schedule_tick()
            act += self._step_pes()
            act += self._admit_pending()
            act += self._step_des()
            self._apply_net_contention()
            dt = self._tick_io.serial_seconds() + self._tick_compute
        self.clock.advance(dt + self._submit_overhead_delta())
        self._flush_stamps()
        return act

    # ------------------------------------------------------------------
    # drivers
    # ------------------------------------------------------------------
    def run_offline(self, trajectories: List[Trajectory],
                    max_iters: int = 100000) -> List[AgentSession]:
        sessions = [AgentSession(t, np.random.default_rng(1000 + t.tid))
                    for t in trajectories]
        self._online = False
        for s in sessions:
            self._submit_round(s)
        for _ in range(max_iters):
            if all(s.done() for s in sessions):
                break
            self._tick()
        else:
            raise RuntimeError("serving system did not converge")
        return sessions

    def run_online(self, trajectories: List[Trajectory],
                   arrivals: List[float],
                   max_iters: int = 1000000) -> List[AgentSession]:
        """Online serving: trajectory i starts at ``arrivals[i]`` seconds
        on the runtime's wall clock; inter-round think gaps
        (``Round.think``) are honoured.  The clock jumps over idle gaps
        instead of sleeping, so a low-rate sweep costs no real time."""
        assert len(arrivals) == len(trajectories), "one arrival per trajectory"
        sessions = [AgentSession(t, np.random.default_rng(1000 + t.tid))
                    for t in trajectories]
        self._online = True
        try:
            for s, t0 in zip(sessions, arrivals):
                self.loop.at(float(t0), lambda s=s: self._submit_round(s))
            # wake-up markers at death times so an idle clock jump never
            # lands past a death (the tick's _fault_tick processes it)
            for d in self._deaths_pending:
                self.loop.at(float(d.t), lambda: None)
            for _ in range(max_iters):
                self.loop.fire_due()
                if all(s.done() for s in sessions) and not self.loop.pending:
                    break
                if self._tick() == 0:
                    nt = self.loop.next_time()
                    if nt is None:
                        raise RuntimeError(
                            "serving runtime stalled with no pending events")
                    if self.tracer is None:
                        self.clock.jump_to(nt)
                    else:
                        with self.tracer.region("serve", "serve.wait"):
                            self.clock.jump_to(nt)
            else:
                raise RuntimeError("serving system did not converge")
        finally:
            self._online = False
        return sessions

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        tiers = list(self.tiers.values())
        return conforming(dict(
            store_reads=self.store.bytes_read,
            store_writes=self.store.bytes_written,
            read_bytes_pe_side=self.read_bytes_by_side["pe"],
            read_bytes_de_side=self.read_bytes_by_side["de"],
            split_reads=self.n_split_reads,
            trie_blocks=self.trie.n_blocks,
            prefill_tokens=sum(p.prefill_tokens for p in self.pes.values()),
            decode_steps=sum(d.decode_steps for d in self.des.values()),
            gen_tokens=self.gen_tokens_done,
            # --- wall clock / submission overhead ----------------------
            wall_s=self.clock.now,
            doorbells=sum(tm.doorbells for tm in self._all_tms()),
            submitted_seconds=sum(tm.submitted_seconds
                                  for tm in self._all_tms()),
            # --- finite compute network (zeros when collectives off) ----
            collective_stall_s=self.collective_stall_s,
            transfer_backlog_s=self.transfer_backlog_s,
            net_congestion=self.net_congestion,
            paced_flushes=sum(tm.paced_flushes for tm in self._all_tms()),
            deferred_wrs=sum(tm.deferred_wrs for tm in self._all_tms()),
            # --- per-round latency (mirrors Sim.results()) -------------
            **events.latency_summary(self.metrics.values()),
            # --- DRAM tier (zeros when disabled) -----------------------
            dram_hit_bytes=sum(t.dram_hit_bytes for t in tiers),
            dram_bytes_pe_side=self.dram_bytes_by_side["pe"],
            dram_bytes_de_side=self.dram_bytes_by_side["de"],
            tier_miss_bytes=sum(t.miss_bytes for t in tiers),
            tier_prefetch_bytes=sum(t.prefetch_bytes for t in tiers),
            tier_evicted_bytes=sum(t.evicted_bytes for t in tiers),
            # --- elastic reconfiguration (zeros when elastic off) -------
            role_changes=self.drains.n_flips,
            role_changes_by_direction=self.drains.flips_by_direction(),
            reconfig_drain_s=self.drains.drain_seconds(),
            reconfig_weight_bytes=self.reconfig_weight_bytes,
            tier_handoff_bytes=self.drains.tier_handoff_bytes(),
            n_pe_final=len(self.pes),
            n_de_final=len(self.des),
            # --- faults / resilience (zeros when faults off) -------------
            engine_deaths=len(self.dead_engines),
            recovered_rounds=self.recovered_rounds,
            hedged_reads=self.hedged_reads,
            hedge_moved_tokens=self.hedge_moved_tokens,
            # --- online SLO layer (zeros/defaults when off) --------------
            admitted_rounds=(self.gate.admitted_rounds
                             if self.gate is not None else len(self.metrics)),
            deferred_rounds=(self.gate.deferred_rounds
                             if self.gate is not None else 0),
            rejected_rounds=(self.gate.rejected_rounds
                             if self.gate is not None else 0),
            prefill_chunks=self.prefill_chunks,
            latency_by_class=events.latency_by_class(self.metrics.values()),
        ), "serving")

    def slo_attainment(self, ttft_slo_s: float = 4.0,
                       tpot_slo_s: float = 0.050) -> float:
        """Fraction of finished rounds meeting both SLOs (paper §7.4
        defaults: TTFT ≤ 4 s, TPOT ≤ 50 ms)."""
        return events.slo_attainment(self.metrics.values(),
                                     ttft_slo_s, tpot_slo_s)
