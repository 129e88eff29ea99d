"""CNIC-centric traffic manager (paper §5).

The paper's mechanism: *every* byte in or out of an accelerator —
including local host↔device copies — is carried by the engine's paired
compute NIC (GPUDirect-RDMA loopback), making the NIC's virtual-lane
arbiter the single QoS scheduler for all PCIe traffic.  Model-execution
collectives ride a high-priority VL with ~99 % of arbitration weight;
KV-cache transfers ride a low-priority VL with a starvation floor.

TPU adaptation (DESIGN.md §2): ICI collectives are hardware-isolated
from host DMA, so the loopback *mechanism* is unnecessary — but the
*policy* (single arbiter, strict priority, batched submission) is kept:
it is what the simulator models and what the engine runtime enforces
for its host-side transfer queues.

This module is runtime-agnostic: the discrete-event simulator uses the
arbiter math (``allocate_bandwidth``) for link sharing, and the engines
use :class:`TrafficManager` to order/batch real (CPU) transfers.
"""
from __future__ import annotations

import heapq
import itertools
from collections import deque
from dataclasses import dataclass, field
from enum import IntEnum
from typing import Callable, Deque, Dict, List, Optional, Tuple


class TrafficClass(IntEnum):
    MODEL_COLLECTIVE = 0      # EP AllToAll, TP ReduceScatter/AllGather, PD KV handoff
    KV_TRANSFER = 1           # dual-path loading, H2D/D2H, storage persists
    BULK = 2                  # checkpoints, dataset reads


@dataclass(frozen=True)
class VLArbiterConfig:
    """InfiniBand-style two-arbiter WRR (paper §A.1 values).

    ``high_weights``/``low_weights``: VL -> WRR weight in the
    high/low-priority arbiter.  ``high_limit=240`` (of 255) ≈ 99 % of
    bandwidth reserved for the high-priority arbiter before the low one
    is consulted; the low-priority table keeps a small weight for the KV
    VL so it never starves.
    """

    n_vls: int = 4
    high_limit: int = 240
    high_weights: Tuple[int, ...] = (192, 192, 0, 192)
    low_weights: Tuple[int, ...] = (192, 192, 64, 192)
    class_to_vl: Tuple[int, ...] = (0, 2, 2)   # TrafficClass -> VL

    def high_fraction(self) -> float:
        """Fraction of link bandwidth the high-priority arbiter owns when
        both arbiters have backlogged traffic."""
        return self.high_limit / 255.0 + (1 - self.high_limit / 255.0) * (
            sum(w for v, w in enumerate(self.low_weights)
                if self.high_weights[v] > 0) /
            max(sum(self.low_weights), 1))


DEFAULT_ARBITER = VLArbiterConfig()


def allocate_bandwidth(active: Dict[TrafficClass, int], link_bw: float,
                       arb: VLArbiterConfig = DEFAULT_ARBITER
                       ) -> Dict[TrafficClass, float]:
    """Share ``link_bw`` among active flows per the VL arbiter.

    ``active``: number of backlogged flows per class.  Classes mapped to
    a high-arbiter VL split the high fraction; low-VL classes share the
    remainder (plus everything when no high traffic is active).  Within
    a class, flows share equally (fair queuing approximation).
    """
    hi_classes = [c for c, n in active.items()
                  if n > 0 and arb.high_weights[arb.class_to_vl[c]] > 0]
    lo_classes = [c for c, n in active.items()
                  if n > 0 and arb.high_weights[arb.class_to_vl[c]] == 0]
    out: Dict[TrafficClass, float] = {c: 0.0 for c in active}
    if hi_classes and lo_classes:
        hf = arb.high_fraction()
        hi_bw, lo_bw = link_bw * hf, link_bw * (1 - hf)
    elif hi_classes:
        hi_bw, lo_bw = link_bw, 0.0
    else:
        hi_bw, lo_bw = 0.0, link_bw
    for pool_bw, classes in ((hi_bw, hi_classes), (lo_bw, lo_classes)):
        if not classes:
            continue
        tot_w = sum(arb.low_weights[arb.class_to_vl[c]] or 1 for c in classes)
        for c in classes:
            w = arb.low_weights[arb.class_to_vl[c]] or 1
            out[c] = pool_bw * w / tot_w
    return out


# ---------------------------------------------------------------------------
# Submission cost model (§5.2): RDMA WR vs cudaMemcpyAsync, doorbell batching
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SubmitCostModel:
    rdma_wr_s: float = 1e-6          # one RDMA work request (mmio writes)
    rdma_doorbell_s: float = 0.3e-6  # one doorbell ring (amortisable)
    cuda_memcpy_s: float = 6e-6      # paper: 5–7 µs per cudaMemcpyAsync

    def rdma_batch_seconds(self, n: int) -> float:
        """Doorbell batching: n WRs posted, one doorbell."""
        return n * self.rdma_wr_s + self.rdma_doorbell_s

    def rdma_unbatched_seconds(self, n: int) -> float:
        return n * (self.rdma_wr_s + self.rdma_doorbell_s)

    def cuda_seconds(self, n: int) -> float:
        return n * self.cuda_memcpy_s


# ---------------------------------------------------------------------------
# Engine-side transfer manager
# ---------------------------------------------------------------------------


@dataclass(order=True)
class _QueuedTransfer:
    sort_key: Tuple[int, int] = field(compare=True)
    fn: Callable[[], None] = field(compare=False)
    nbytes: int = field(compare=False, default=0)
    tclass: TrafficClass = field(compare=False,
                                 default=TrafficClass.KV_TRANSFER)
    # completion obligations: one countdown per flush whose batch this
    # transfer appeared in (a congestion-deferred WR can belong to more
    # than one flush — each on_complete must still see it land)
    cbs: Optional[List[Callable[[], None]]] = field(compare=False,
                                                    default=None)


class TrafficManager:
    """Per-engine transfer orderer.

    Engines enqueue transfer thunks with a traffic class.  The lifecycle
    has two halves, mirroring an RDMA send queue:

    * ``flush()`` — the *issue* half: every queued WR is posted to the
      in-flight ring in VL-arbiter order (strict priority, FIFO within a
      class) and the doorbells are rung — KV WRs are batched per
      doorbell (``doorbell_batch``), which is where the §5.2 submission
      cost is charged.  Non-blocking: no thunk runs here.
    * ``poll()`` — the *completion* half: in-flight thunks execute in
      posted order and per-flush completion callbacks fire once every
      transfer of that flush has landed.

    ``drain()`` (= flush + poll until idle) is the blocking legacy API;
    the lock-step serving runtime still uses it, the pipelined runtime
    flushes at issue points and polls once per event-loop tick so
    storage reads and compute-network transfers stay in flight across
    engine ``step()`` compute.  On real hardware the thunks would be
    RDMA WR posts; on CPU they are the actual numpy/jax copies, so the
    ordering/batching logic is exercised end-to-end by the integration
    tests.
    """

    def __init__(self, cost: SubmitCostModel = SubmitCostModel(),
                 doorbell_batch: int = 32, pace_threshold: float = 0.5):
        self.cost = cost
        self.doorbell_batch = doorbell_batch
        self._q: List[_QueuedTransfer] = []
        self._inflight: Deque[_QueuedTransfer] = deque()
        self._seq = itertools.count()
        self.submitted_seconds = 0.0     # modelled submission overhead
        self.doorbells = 0
        self.stats = {c: 0 for c in TrafficClass}
        self.bytes = {c: 0 for c in TrafficClass}
        # --- compute-network back-pressure (repro.network) --------------
        # ``net_congestion`` ∈ [0, 1] is set by the runtime from the
        # shared link's congestion signal; at or above ``pace_threshold``
        # each flush posts collectives unconditionally but at most ONE
        # doorbell batch of low-priority WRs, deferring the rest — so a
        # collective submitted later still overtakes a backlog of KV WRs
        # and model execution never stalls behind cache movement.
        self.net_congestion = 0.0
        self.pace_threshold = pace_threshold
        self.paced_flushes = 0
        self.deferred_wrs = 0

    def submit(self, fn: Callable[[], None], nbytes: int,
               tclass: TrafficClass):
        heapq.heappush(self._q, _QueuedTransfer(
            (int(tclass != TrafficClass.MODEL_COLLECTIVE), next(self._seq)),
            fn, nbytes, tclass))
        self.stats[tclass] += 1
        self.bytes[tclass] += nbytes

    # -- issue half --------------------------------------------------------
    def flush(self, on_complete: Optional[Callable[[], None]] = None) -> int:
        """Post every queued WR (arbiter order) to the in-flight ring and
        ring the doorbells.  Non-blocking — thunks execute at ``poll``.
        ``on_complete`` fires once every transfer queued at THIS flush
        has executed (immediately when nothing was queued) — including
        WRs the KV pacing defers to a later flush.

        When ``net_congestion >= pace_threshold`` the flush is *paced*:
        collectives post unconditionally, low-priority WRs post at most
        one doorbell batch, and the remainder returns to the queue (in
        order, submission cost uncharged — it is charged when they are
        actually posted).  Returns the number of WRs posted."""
        batch: List[_QueuedTransfer] = []
        while self._q:
            batch.append(heapq.heappop(self._q))
        if not batch:
            if on_complete is not None:
                on_complete()
            return 0
        posted = batch
        deferred: List[_QueuedTransfer] = []
        if self.net_congestion >= self.pace_threshold:
            posted = []
            kv_budget = self.doorbell_batch
            for t in batch:
                if t.tclass == TrafficClass.MODEL_COLLECTIVE:
                    posted.append(t)
                elif kv_budget > 0:
                    posted.append(t)
                    kv_budget -= 1
                else:
                    deferred.append(t)
            if deferred:
                self.paced_flushes += 1
                self.deferred_wrs += len(deferred)
        kv_batch = 0
        for t in posted:
            if t.tclass == TrafficClass.MODEL_COLLECTIVE:
                self.submitted_seconds += self.cost.rdma_batch_seconds(1)
                self.doorbells += 1
            else:
                kv_batch += 1
                if kv_batch == self.doorbell_batch:
                    self.submitted_seconds += \
                        self.cost.rdma_batch_seconds(kv_batch)
                    self.doorbells += 1
                    kv_batch = 0
        if kv_batch:
            self.submitted_seconds += self.cost.rdma_batch_seconds(kv_batch)
            self.doorbells += 1
        if on_complete is not None:
            pending = [len(batch)]

            def countdown():
                pending[0] -= 1
                if pending[0] == 0:
                    on_complete()

            for t in batch:
                if t.cbs is None:
                    t.cbs = []
                t.cbs.append(countdown)
        self._inflight.extend(posted)
        for t in deferred:       # sort_key intact: order is preserved
            heapq.heappush(self._q, t)
        return len(posted)

    # -- completion half ---------------------------------------------------
    def poll(self, max_n: Optional[int] = None) -> int:
        """Execute up to ``max_n`` in-flight transfers (all if None) in
        posted order, firing completion callbacks; returns the count.
        Pop-based, so a callback that re-enters drain/poll cannot
        double-execute a transfer; a payload thunk that faults still
        completes exactly once (callbacks fire, the error propagates) —
        the CQE-reports-errors-exactly-once contract the fault-injection
        tests pin."""
        n = 0
        while self._inflight and (max_n is None or n < max_n):
            t = self._inflight.popleft()
            n += 1
            try:
                t.fn()
            finally:
                cbs, t.cbs = t.cbs, None
                for cb in cbs or ():
                    cb()
        return n

    @property
    def queued(self) -> int:
        return len(self._q)

    @property
    def in_flight(self) -> int:
        return len(self._inflight)

    @property
    def busy(self) -> bool:
        return bool(self._q or self._inflight)

    def drain(self) -> int:
        """Blocking issue+complete: flush and poll until idle; returns
        the number of transfers executed."""
        n = 0
        while self._q or self._inflight:
            self.flush()
            n += self.poll()
        return n
