"""Swap-tier transfer engine: preallocated host buffers + in-flight
transfer records (ISSUE 10).

Two pieces make the pool's second tier physical instead of ad-hoc:

``SwapTier``
    The host side of the hierarchy.  Spilled payloads land in
    PREALLOCATED per-leaf buffers (one page-extent + optional state slot
    per spilled stream) instead of fresh numpy allocations per spill —
    on real hardware these are the pinned staging buffers D2H DMA
    requires; the tier probes whether the platform exposes a
    ``pinned_host`` memory space and records the answer (TPU and, since
    JAX 0.5, CPU both do; the buffers are plain numpy either way).  A
    first-fit extent allocator keeps page ranges contiguous so a landed
    spill is one slice view per leaf, and an overflow path falls back to
    ad-hoc arrays (counted) when the preallocation is exhausted rather
    than failing the spill.

``InFlightSpill``
    One issued-but-unfenced D2H copy.  ``KVBlockPool.spill_issue``
    dispatches the device-side gather (JAX async dispatch: ``jnp.take``
    returns immediately) and parks one of these in the pool's in-flight
    table; decode ticks keep running while the copy drains.  The
    victim's pages are re-granted only when the transfer completes —
    the fence-before-regrant invariant — and the functional storage
    update means the gather snapshots issue-time bytes no matter what
    later ticks write.  ``ready()`` is the poll; the pool's
    ``spill_fence`` is the blocking fence.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import numpy as np

import jax


def pinned_host_available() -> bool:
    """Probe whether the default device exposes a ``pinned_host`` memory
    space.  A device without one answers False; any failure of the probe
    itself propagates."""
    return any(m.kind == "pinned_host"
               for m in jax.devices()[0].addressable_memories())


@dataclasses.dataclass
class TierHandle:
    """One landed spill's home in the tier: a contiguous page extent +
    optional state slot, or an overflow allocation."""
    start: int                    # first page of the extent (-1: overflow)
    pages: int
    state_idx: int                # tier state slot (-1: none/overflow)
    views: List[Any]              # per-leaf numpy views holding the bytes
    overflow: bool = False


@dataclasses.dataclass
class InFlightSpill:
    """An issued, unfenced D2H spill: the victim table, its device-side
    gathered payload, and overlap bookkeeping."""
    table: Any
    pages: int
    had_state: bool
    leaves: List[Any]             # device arrays (async gather result)
    issue_clock: int              # pool poll-clock at issue
    n_bytes: float

    def ready(self) -> bool:
        return all(leaf.is_ready() for leaf in self.leaves
                   if leaf is not None)


class SwapTier:
    """Preallocated host-side storage for spilled pages + state slots.

    Buffers mirror the pool's leaf layout: every token leaf gets a
    ``capacity_pages``-page buffer, every state leaf a
    ``capacity_states``-slot buffer.  ``store`` copies a landed payload
    into a first-fit extent and returns per-leaf views (what
    ``SpillEntry.data`` holds — the restore path scatters them back
    unchanged); ``release`` returns the extent.  When the preallocation
    is full the payload keeps its ad-hoc arrays (``overflow_allocs``
    counts how often — sizing feedback, not an error).
    """

    def __init__(self, storage, spec, capacity_pages: int,
                 capacity_states: int):
        self.spec = spec
        self.capacity_pages = int(capacity_pages)
        self.capacity_states = int(capacity_states)
        self.pinned = pinned_host_available()
        self.overflow_allocs = 0
        self._bufs: List[Optional[np.ndarray]] = []
        for leaf, s in zip(jax.tree.leaves(storage), spec.leaves):
            ax = s.batch_axis
            if s.token_axis is not None:
                shape = (leaf.shape[:ax] + (self.capacity_pages,)
                         + leaf.shape[ax + 1:])
            else:
                shape = (leaf.shape[:ax] + (self.capacity_states,)
                         + leaf.shape[ax + 1:])
            self._bufs.append(np.zeros(shape, dtype=leaf.dtype))
        # first-fit free extents over the page axis + state slot free list
        self._extents: List[Tuple[int, int]] = [(0, self.capacity_pages)]
        self._free_states: List[int] = list(range(self.capacity_states))

    # -- extent allocator --------------------------------------------------
    def _alloc_extent(self, pages: int) -> int:
        for i, (start, length) in enumerate(self._extents):
            if length >= pages:
                if length == pages:
                    self._extents.pop(i)
                else:
                    self._extents[i] = (start + pages, length - pages)
                return start
        return -1

    def _free_extent(self, start: int, pages: int):
        self._extents.append((start, pages))
        # coalesce neighbours so long runs stay allocatable
        self._extents.sort()
        merged: List[Tuple[int, int]] = []
        for s, n in self._extents:
            if merged and merged[-1][0] + merged[-1][1] == s:
                merged[-1] = (merged[-1][0], merged[-1][1] + n)
            else:
                merged.append((s, n))
        self._extents = merged

    # -- store / release ---------------------------------------------------
    def store(self, host_leaves: List[Any], pages: int,
              had_state: bool) -> TierHandle:
        """Copy a landed payload into the tier; returns the handle whose
        ``views`` are the payload's long-term home."""
        start = self._alloc_extent(pages) if pages else 0
        state_idx = -1
        if had_state and self._free_states:
            state_idx = self._free_states.pop()
        need_state = had_state and state_idx < 0
        if (pages and start < 0) or need_state:
            if start >= 0 and pages:
                self._free_extent(start, pages)
            if state_idx >= 0:
                self._free_states.append(state_idx)
            self.overflow_allocs += 1
            views = [np.asarray(h) if h is not None else None
                     for h in host_leaves]
            return TierHandle(-1, pages, -1, views, overflow=True)
        views: List[Any] = []
        for buf, host, s in zip(self._bufs, host_leaves, self.spec.leaves):
            if host is None:
                views.append(None)
                continue
            ax = s.batch_axis
            if s.token_axis is not None:
                view = buf[(slice(None),) * ax
                           + (slice(start, start + pages),)]
            else:
                view = buf[(slice(None),) * ax
                           + (slice(state_idx, state_idx + 1),)]
            view[...] = np.asarray(host)
            views.append(view)
        return TierHandle(start, pages, state_idx, views)

    def release(self, handle: Optional[TierHandle]):
        if handle is None or handle.overflow:
            return
        if handle.pages:
            self._free_extent(handle.start, handle.pages)
        if handle.state_idx >= 0:
            self._free_states.append(handle.state_idx)

    # -- introspection -----------------------------------------------------
    def free_pages(self) -> int:
        return sum(n for _, n in self._extents)

    def stats(self) -> dict:
        return {"capacity_pages": self.capacity_pages,
                "capacity_states": self.capacity_states,
                "free_pages": self.free_pages(),
                "pinned_host": self.pinned,
                "overflow_allocs": self.overflow_allocs}
