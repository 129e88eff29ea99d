"""KV-Cache storage backends.

``KVStore`` is the abstract distributed store (the paper uses 3FS);
FullBlocks in, FullBlocks out, with byte accounting so simulators,
benchmarks and tests can observe I/O volume.  ``MemoryKVStore`` holds
real numpy FullBlocks (used by the CPU engines); the simulator uses the
accounting-only subclass (no payloads).
"""
from __future__ import annotations

import itertools
import threading
from typing import Dict, List, Sequence

import numpy as np

from repro.core.blocks import BlockLayout


class KVStore:
    """Abstract FullBlock store with read/write byte accounting."""

    def __init__(self, layout: BlockLayout):
        self.layout = layout
        self._refs = itertools.count(1)
        self.bytes_read = 0
        self.bytes_written = 0
        self.reads = 0
        self.writes = 0

    def alloc_ref(self) -> int:
        return next(self._refs)

    def write_block(self, ref: int, block) -> None:
        self.bytes_written += self.layout.full_block_bytes
        self.writes += 1
        self._put(ref, block)

    def read_block(self, ref: int):
        self.bytes_read += self.layout.full_block_bytes
        self.reads += 1
        return self._get(ref)

    def read_blocks(self, refs: Sequence[int]) -> List:
        return [self.read_block(r) for r in refs]

    def peek(self, ref: int):
        """Payload access with NO byte accounting — for warming a DRAM
        tier with blocks that already moved through the node (e.g. the
        decode side's full context at round end): those bytes were paid
        by the plan legs that staged them, so peeking must not charge
        the storage NIC a second time."""
        return self._get(ref)

    # storage-layer hooks
    def _put(self, ref, block):  # pragma: no cover - abstract
        raise NotImplementedError

    def _get(self, ref):  # pragma: no cover - abstract
        raise NotImplementedError


class MemoryKVStore(KVStore):
    """In-memory FullBlock store (engine runtime / tests)."""

    #: optional flight recorder (repro.obs.Tracer) for host regions,
    #: attached by the owning runtime; None = untraced
    tracer = None

    def __init__(self, layout: BlockLayout):
        super().__init__(layout)
        self._data: Dict[int, np.ndarray] = {}
        self._lock = threading.Lock()

    def write_block(self, ref: int, block) -> None:
        """With a tracer, the host region ``store.write``."""
        if self.tracer is None:
            super().write_block(ref, block)
            return
        with self.tracer.region("store", "store.write", bytes=block.nbytes):
            super().write_block(ref, block)

    def _put(self, ref: int, block: np.ndarray):
        assert block.shape == self.layout.full_block_shape(), (
            block.shape, self.layout.full_block_shape())
        with self._lock:
            self._data[ref] = block

    def _get(self, ref: int) -> np.ndarray:
        with self._lock:
            return self._data[ref]

    def delete(self, refs: Sequence[int]):
        with self._lock:
            for r in refs:
                self._data.pop(r, None)

    @property
    def stored_bytes(self) -> int:
        return len(self._data) * self.layout.full_block_bytes


class AccountingKVStore(KVStore):
    """Byte-accounting-only store for the discrete-event simulator."""

    def _put(self, ref, block):
        pass

    def _get(self, ref):
        return None


class StateBlobStore:
    """Exact-prefix state snapshots for SSM/hybrid archs.

    Attention-free layers have no per-token KV — their 'cache' is the
    O(1) recurrent state, only reusable at the exact prefix where it was
    snapshotted.  Agentic replay continues exactly at the previous round
    end, so an exact-match store mirrors the trie's role (DESIGN.md §5).
    """

    def __init__(self):
        self._blobs: Dict[tuple, tuple] = {}
        self.bytes_read = 0
        self.bytes_written = 0

    def put(self, key_tokens: Sequence[int], blob: bytes, length: int):
        self._blobs[tuple(key_tokens)] = (blob, length)
        self.bytes_written += len(blob)

    def get(self, key_tokens: Sequence[int]):
        hit = self._blobs.get(tuple(key_tokens))
        if hit is None:
            return None, 0
        self.bytes_read += len(hit[0])
        return hit
