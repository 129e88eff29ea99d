"""Inference engines: layerwise-prefill PE and paged-decode DE.

Single-process, CPU-runnable versions of the paper's engines that move
*real* KV bytes through the dual-path legs:

* ``PrefillEngine`` — quota-packed chunked prefill (core/intra.py) via
  ``model.append_step`` against a per-request padded state; hit-KV
  arrives as FullBlocks (deserialised into the state before compute);
  the prompt state then transfers to the DE.
* ``DecodeEngine``  — slot-batched continuous decode via
  ``model.decode_step``; persists newly-filled FullBlocks to storage and
  inserts them into the trie (paper: persist per 64-token block).

Transfers ride each engine's TrafficManager with TrafficClass.KV_TRANSFER
so the CNIC-centric ordering/batching logic (§5) is exercised for real.
SSM/hybrid archs carry an opaque state-blob instead of per-token KV
(constant-size recurrent state; see DESIGN.md §5).
"""
from __future__ import annotations

import functools
import pickle
import sys
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.blocks import BlockLayout
from repro.core.intra import (AttnTimeModel, BatchItem, PrefillWork,
                              QuotaPacker, class_insert_index)
from repro.core.scheduler import Request
from repro.core.traffic import TrafficClass, TrafficManager
from repro.engines import kvio
from repro.kvcache.store import MemoryKVStore, StateBlobStore
from repro.kvcache.trie import BlockTrie
from repro.models import decode_step, init_decode_state
from repro.models.model import append_step

PAGED_FAMILIES = ("dense", "vlm", "moe")

# Compiled once per shape with the config static.  Called eagerly, each
# step re-traces its layer scan into a new program and compiles it again.
# Both steps donate their state (argument 3): the cache is updated in
# place, and the state passed in is deleted.  Prefill computes the
# logits of the chunk's last position only, the one the engine samples.
_append_step = jax.jit(functools.partial(append_step, last_only=True),
                       static_argnums=1, donate_argnums=3)
_decode_step = jax.jit(decode_step, static_argnums=1, donate_argnums=3)


def _take(owner, attr: str):
    """``owner.<attr>``, detached from ``owner``, to be donated to a
    step: the state itself, or a copy where something else still holds
    it (:func:`_held_elsewhere`), so that holder keeps what it holds."""
    state = getattr(owner, attr)
    setattr(owner, attr, None)
    if _held_elsewhere(state):
        state = jax.tree.map(jnp.copy, state)
    return state


def _held_elsewhere(state) -> bool:
    """Whether anything but the caller's one name holds ``state`` or a
    dict or array inside it, by CPython's reference counts.  A donated
    step deletes the buffers it is given, under any such holder."""
    # the caller's name, this frame's and getrefcount's argument
    if sys.getrefcount(state) > 3:
        return True
    todo = list(state.values())
    while todo:
        node = todo.pop()
        # its parent, this frame's name and getrefcount's argument
        if sys.getrefcount(node) > 3:
            return True
        if isinstance(node, dict):
            todo.extend(node.values())
    return False


def state_bytes(state) -> int:
    """Device bytes of a (per-request or decode) state."""
    return sum(a.nbytes for a in jax.tree.leaves(state))


def uses_state_blob(cfg: ModelConfig) -> bool:
    return cfg.family in ("ssm", "hybrid")


@dataclass
class EngineRequest:
    """A request with its token payload, as the engines see it."""

    req: Request
    context_tokens: List[int]        # full previous context (hit source)
    append_tokens: List[int]         # new tokens to prefill
    hit_refs: List[int] = field(default_factory=list)
    state: object = None             # per-request (b=1) model state
    length: int = 0                  # tokens materialised in state
    generated: List[int] = field(default_factory=list)
    first_token: Optional[int] = None

    @property
    def prompt_len(self) -> int:
        return len(self.context_tokens) + len(self.append_tokens)


class PrefillEngine:
    #: optional flight recorder (repro.obs.Tracer) for host regions,
    #: attached by the owning runtime; None = untraced
    tracer = None

    def __init__(self, eid, cfg: ModelConfig, params, store: MemoryKVStore,
                 layout: BlockLayout, max_seq: int,
                 quota_s: float = 0.300, layerwise: bool = True,
                 chunk_tokens: Optional[int] = None,
                 class_aware: bool = False):
        self.eid = eid
        self.track = f"engine/pe{eid}"
        self.cfg = cfg
        self.params = params
        self.store = store
        self.layout = layout
        self.max_seq = max_seq
        self.layerwise = layerwise
        self.tm = TrafficManager()
        self.packer = QuotaPacker(cfg, AttnTimeModel.from_config(cfg),
                                  quota_s=quota_s, chunk_tokens=chunk_tokens)
        self.class_aware = class_aware
        self.fifo: List[Tuple[PrefillWork, EngineRequest]] = []
        self.prefill_tokens = 0
        # (cached, bsz) items of the batch the last step() executed — the
        # serving clock's compute-duration input (events.ServingTimeModel)
        self.last_step_items: List[Tuple[int, int]] = []
        # requests whose last-step batch item was a partial (chunked)
        # slice and whose prefill is still unfinished — the serving
        # runtime's PREFILL_CHUNKED sub-state + chunk-counter source
        self.last_step_chunked: List[EngineRequest] = []

    # -- loading ---------------------------------------------------------
    def install_hit_kv(self, er: EngineRequest, payload):
        """payload: list of FullBlocks (paged archs) or a state blob.

        With ``layerwise`` (default, paper §4.1) the hit KV is installed
        one LayerBlock at a time via kvio.layer_stream: each layer's
        rows are gathered through the kernels/kv_gather.py path while
        the next layer's gather is already in flight on this engine's
        TrafficManager (double buffering).  The non-layerwise path is
        the whole-prompt bulk install, kept for the Fig. 12 ablation.
        With a tracer: the host region ``pe.install``, and inside it
        ``pe.install.upload``/``.gather`` (kvio.layer_stream) and one
        ``pe.install.place`` per layer.
        """
        tr = self.tracer
        if tr is None:
            self._install(er, payload)
            return
        with tr.region(self.track, "pe.install", rid=er.req.rid,
                       hit_tokens=er.req.cached_tokens):
            self._install(er, payload)

    def _install(self, er: EngineRequest, payload):
        if er.prompt_len > self.max_seq:
            # a chunk written past the state's end would be shifted back
            # over earlier rows (model._put_chunk), so it is refused here
            raise ValueError(f"prompt of {er.prompt_len} tokens is longer "
                             f"than the state's {self.max_seq}")
        tr = self.tracer
        er.state = init_decode_state(self.cfg, 1, self.max_seq)
        hit = er.req.cached_tokens
        if uses_state_blob(self.cfg):
            if payload is not None:
                er.state = jax.tree.map(jnp.asarray, pickle.loads(payload))
            er.length = hit
        elif payload:
            if self.layerwise:
                for li, rows in kvio.layer_stream(self.cfg, payload,
                                                  tm=self.tm, tracer=tr,
                                                  track=self.track):
                    rows = rows[:hit]
                    if tr is None:
                        er.state = kvio.put_kv_layer(
                            self.cfg, er.state, 0, 0, li, rows)
                    else:
                        with tr.region(self.track, "pe.install.place",
                                       layer=li, bytes=rows.nbytes):
                            er.state = kvio.put_kv_layer(
                                self.cfg, er.state, 0, 0, li, rows)
            else:
                kv_bytes = np.concatenate(payload, axis=1)   # (L, hit, row)
                er.state = kvio.deserialize_kv(self.cfg, er.state, 0, 0,
                                               kv_bytes[:, :hit])
        er.length = hit
        work = PrefillWork(er.req.rid, hit, len(er.append_tokens),
                           rank=er.req.class_rank, arrival=er.req.arrival)
        if self.class_aware:
            # the serving-side mirror of the sim's class-ordered fifo:
            # TTFT wait accrues here, not in the scheduler's global queue
            self.fifo.insert(class_insert_index(
                [w.key() for w, _ in self.fifo], work.key()), (work, er))
        else:
            self.fifo.append((work, er))

    # -- compute ---------------------------------------------------------
    def step(self) -> List[EngineRequest]:
        """Run one quota-packed forward batch; returns requests whose
        prefill completed this step.  With a tracer the batch runs in
        the host region ``pe.prefill``."""
        self.last_step_items = []
        self.last_step_chunked = []
        if not self.fifo:
            return []
        works = [w for w, _ in self.fifo]
        byrid = {w.rid: er for w, er in self.fifo}
        batch = self.packer.pack(works)
        if not batch and works:
            # quota smaller than min_chunk for the head request: force
            # minimal progress so the engine never stalls
            w = works[0]
            bsz = min(w.remaining, self.packer.min_chunk)
            batch = [BatchItem(w.rid, w.cached, bsz, chunked=True)]
            w.advance(bsz)
            if w.remaining == 0:
                works.pop(0)
        self.fifo = [(w, byrid[w.rid]) for w in works]
        self.last_step_items = [(bi.cached, bi.bsz) for bi in batch]
        if self.tracer is None:
            return self._prefill(batch, byrid)
        with self.tracer.region(self.track, "pe.prefill",
                                tokens=sum(bi.bsz for bi in batch),
                                items=len(batch)):
            return self._prefill(batch, byrid)

    def _prefill(self, batch: List[BatchItem], byrid) -> List[EngineRequest]:
        done = []
        for bi in batch:
            er = byrid[bi.rid]
            toks = er.append_tokens[bi.cached - er.req.cached_tokens:
                                    bi.cached - er.req.cached_tokens + bi.bsz]
            t = jnp.asarray([toks], jnp.int32)
            lengths = jnp.asarray([er.length], jnp.int32)
            logits, er.state = _append_step(self.params, self.cfg, t,
                                            _take(er, "state"), lengths)
            er.length += bi.bsz
            self.prefill_tokens += bi.bsz
            if er.length == er.prompt_len:
                er.first_token = int(jnp.argmax(logits[0, -1]))
                done.append(er)
            elif bi.chunked:
                self.last_step_chunked.append(er)
        return done


class DecodeEngine:
    #: optional flight recorder (repro.obs.Tracer) for host regions,
    #: attached by the owning runtime; None = untraced
    tracer = None

    def __init__(self, eid, cfg: ModelConfig, params, store: MemoryKVStore,
                 trie: BlockTrie, layout: BlockLayout, max_seq: int,
                 n_slots: int = 8, blob_store: StateBlobStore | None = None):
        self.eid = eid
        self.track = f"engine/de{eid}"
        self.cfg = cfg
        self.params = params
        self.store = store
        self.blob_store = blob_store
        self.trie = trie
        self.layout = layout
        self.max_seq = max_seq
        self.n_slots = n_slots
        self.tm = TrafficManager()
        self.state = init_decode_state(cfg, n_slots, max_seq)
        self.axes = kvio.batch_axes_of_state(cfg)
        self.slots: List[Optional[EngineRequest]] = [None] * n_slots
        self.lengths = np.zeros(n_slots, np.int32)
        self.next_token = np.zeros(n_slots, np.int32)
        self.decode_steps = 0
        # context lengths the last step() decoded over (serving clock)
        self.last_step_ctxs: List[int] = []
        # pipelined persistence (serving/events.py lifecycle PERSIST):
        # with defer_persist the block writes are *submitted* to the tm
        # but not drained, and (request, finalize) pairs park here until
        # the system flushes the tm — finalize inserts the trie entries
        # once the write completions have landed
        self.defer_persist = False
        self.pending_persist: List[Tuple[EngineRequest,
                                         Optional[callable]]] = []

    @property
    def free_slots(self) -> int:
        return sum(s is None for s in self.slots)

    def admit(self, er: EngineRequest) -> int:
        """Take ``er`` into a free slot, its prefill state written into
        the decode state in place (donated, as a decode step does).
        With a tracer: the host region ``de.admit``."""
        slot = self.slots.index(None)
        self.slots[slot] = er
        if self.tracer is None:
            self._admit_state(slot, er)
        else:
            with self.tracer.region(self.track, "de.admit", slot=slot,
                                    bytes=state_bytes(er.state)):
                self._admit_state(slot, er)
        self.lengths[slot] = er.length
        self.next_token[slot] = er.first_token
        er.generated.append(er.first_token)
        er.state = None                      # DE owns the state now
        return slot

    def _admit_state(self, slot: int, er: EngineRequest) -> None:
        self.state = kvio.put_slot(_take(self, "state"), self.axes, slot,
                                   er.state)

    def step(self) -> List[EngineRequest]:
        """One decode step over all active slots; returns finished.
        With a tracer a step that runs is the host region ``de.decode``
        (the persists of the rounds it finishes nest inside it)."""
        self.last_step_ctxs = [int(self.lengths[s])
                               for s, er in enumerate(self.slots)
                               if er is not None]
        if all(s is None for s in self.slots):
            return []
        if self.tracer is None:
            return self._decode()
        with self.tracer.region(self.track, "de.decode",
                                slots=len(self.last_step_ctxs)):
            return self._decode()

    def _decode(self) -> List[EngineRequest]:
        toks = jnp.asarray(self.next_token, jnp.int32)
        lengths = jnp.asarray(self.lengths, jnp.int32)
        # the step takes the state over (donated): its buffers are then
        # deleted, so a state that is also held outside the engine is
        # stepped as a copy and its holder keeps what it holds
        logits, self.state = _decode_step(self.params, self.cfg, toks,
                                          _take(self, "state"), lengths)
        self.decode_steps += 1
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        finished = []
        for slot, er in enumerate(self.slots):
            if er is None:
                continue
            self.lengths[slot] += 1
            self.next_token[slot] = nxt[slot]
            if len(er.generated) < er.req.gen_tokens:
                er.generated.append(int(nxt[slot]))
            if len(er.generated) >= er.req.gen_tokens:
                self._persist(slot, er)
                finished.append(er)
                self.slots[slot] = None
                self.lengths[slot] = 0
        return finished

    # -- persistence (per full block, as in the paper) --------------------
    def _persist(self, slot: int, er: EngineRequest):
        """Serialise the slot's new state and submit the storage writes.

        The state snapshot (serialize_kv / pickle) is taken NOW — the
        slot may be re-admitted before deferred writes land — but the
        write execution and the trie insert are the *completion* half:
        with ``defer_persist`` they wait parked in ``pending_persist``
        for the system's flush; otherwise they drain inline (the
        blocking runtime's behaviour).  With a tracer: the host region
        ``de.persist``, and inside it ``de.persist.copy``, the state's
        rows brought to the host and cut into contiguous blocks."""
        tr = self.tracer
        if tr is None:
            self._persist_slot(slot, er)
            return
        with tr.region(self.track, "de.persist", rid=er.req.rid) as r:
            r.args["blocks"], r.args["bytes"] = self._persist_slot(slot, er)

    def _persist_slot(self, slot: int, er: EngineRequest) -> Tuple[int, int]:
        """:meth:`_persist`'s work; returns the FullBlocks and bytes it
        submitted."""
        full_tokens = er.context_tokens + er.append_tokens + er.generated
        bt = self.layout.block_tokens
        # the last generated token was never fed back through decode, so
        # the state holds KV for lengths[slot] = len(full_tokens) - 1
        # tokens (all of them when gen == 1): persist only whole blocks
        # of those, or a block ending on that token would store a blank
        # KV row that later rounds reuse as a hit
        n_blocks = int(self.lengths[slot]) // bt
        start_block = er.req.cached_tokens // bt
        if uses_state_blob(self.cfg):
            blob = pickle.dumps(jax.tree.map(
                np.asarray, kvio.slot_get(self.state, self.axes, slot)))
            self.tm.submit(
                lambda b=blob, k=tuple(full_tokens), n=int(self.lengths[slot]):
                self.blob_store.put(k, b, n),
                len(blob), TrafficClass.KV_TRANSFER)
            if self.defer_persist:
                self.pending_persist.append((er, None))
            else:
                self.tm.drain()
            return 0, len(blob)
        if n_blocks <= start_block:
            if self.defer_persist:
                self.pending_persist.append((er, None))
            return 0, 0
        tr = self.tracer
        if tr is None:
            blocks = self._copy_blocks(slot, start_block, n_blocks)
        else:
            with tr.region(self.track, "de.persist.copy") as r:
                blocks = self._copy_blocks(slot, start_block, n_blocks)
                r.args["bytes"] = sum(b.nbytes for b in blocks)
        new_refs = [self.store.alloc_ref() for _ in blocks]
        for ref, blk in zip(new_refs, blocks):
            self.tm.submit(lambda r=ref, b=blk: self.store.write_block(r, b),
                           blk.nbytes, TrafficClass.KV_TRANSFER)
        finalize = lambda toks=full_tokens[:n_blocks * bt], refs=new_refs: \
            self.trie.insert(toks, refs)
        if self.defer_persist:
            self.pending_persist.append((er, finalize))
        else:
            self.tm.drain()
            finalize()
        return len(blocks), sum(b.nbytes for b in blocks)

    def _copy_blocks(self, slot: int, start_block: int,
                     n_blocks: int) -> List[np.ndarray]:
        """The slot's KV rows of blocks [start_block, n_blocks) on the
        host, one contiguous FullBlock each."""
        bt = self.layout.block_tokens
        kv_bytes = kvio.serialize_kv(self.cfg, self.state, slot,
                                     start_block * bt, n_blocks * bt)
        return [np.ascontiguousarray(kv_bytes[:, i * bt:(i + 1) * bt])
                for i in range(n_blocks - start_block)]
