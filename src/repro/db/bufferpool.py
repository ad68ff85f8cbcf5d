"""Buffer pool: fixed-size frame cache over heap files with LRU replacement.

The pool is the RDBMS side of DAnA's data handoff: queries fill frames, and
``fetch_batch`` hands *whole pages* (a batched uint32 array) to the accelerator
— page-granular transfer, exactly the paper's amortization argument.

``prefetch_batch`` is the pipelined variant: it runs the same fetch on a
single background thread and returns a :class:`PrefetchHandle`, so the
solver's double-buffered loop can overlap page I/O for chunk k+1 with device
compute on chunk k (the paper's Striders overlapping page access with the
execution engine). All pool state is lock-protected; hit/miss/eviction
accounting is identical whether a fetch ran in the foreground or background.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np

from repro import obs
from repro.db.heap import HeapFile


class PrefetchHandle:
    """Handle to an in-flight background page fetch.

    ``result()`` joins the fetch and returns the ``(n, page_words)`` uint32
    batch; ``fetch_s`` (valid once done) is the wall time the fetch itself
    took. ``wait()`` is the scan loops' join: it splits the fetch's I/O into
    what the caller blocked on (exposed) and what ran under its work
    (overlapped); ``drain()`` leaves the pool quiescent when a loop ends.
    """

    def __init__(self, page_ids: np.ndarray):
        self.page_ids = page_ids
        self.fetch_s = 0.0  # filled in by the worker when the fetch completes
        self._future: Future = Future()

    def done(self) -> bool:
        return self._future.done()

    def cancel(self) -> bool:
        """Best-effort cancel; returns True only if the fetch never started."""
        return self._future.cancel()

    def result(self, timeout: float | None = None) -> np.ndarray:
        return self._future.result(timeout)

    def wait(self) -> tuple[np.ndarray, float, float]:
        """Join the fetch in a ``pool.wait`` span: ``(pages, exposed_s,
        overlapped_s)``, the seconds this call blocked and the seconds of
        the fetch that ran before it."""
        with obs.span("pool.wait", pages=len(self.page_ids)) as rec:
            pages = self.result()
        return pages, rec.seconds, max(self.fetch_s - rec.seconds, 0.0)

    def drain(self) -> None:
        """Cancel the fetch if it has not started, else wait for it. Its
        outcome is dropped: the caller has its answer, or is failing
        already."""
        if not self.cancel():
            try:
                self.result()
            except Exception:
                pass


class BufferPool:
    def __init__(self, pool_bytes: int = 8 * 1024 * 1024, page_bytes: int = 32 * 1024):
        """``pool_bytes`` is the pool's total frame budget in BYTES; capacity
        in pages is ``pool_bytes // page_bytes`` (floor, min 1 frame). The
        default is 8 MB = 256 frames of 32 KB pages. Callers sizing by page
        count should pass ``pool_bytes=n_pages * page_bytes``.

        Frames are the rows of a ``capacity x page_words`` arena, one arena
        a page size; ``_frames`` maps ``(path, page_id)`` to its row in LRU
        order. Pages go in and out by copy, so nothing handed out aliases a
        frame."""
        self.page_bytes = page_bytes
        self.capacity = max(1, pool_bytes // page_bytes)
        self._frames: OrderedDict[tuple[str, int], int] = OrderedDict()
        self._free = list(range(self.capacity))  # arena rows no frame holds
        self._arenas: dict[int, np.ndarray] = {}
        self._pins: dict[tuple[str, int], int] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._lock = threading.RLock()
        self._prefetcher: ThreadPoolExecutor | None = None

    # -- core API ------------------------------------------------------------
    def get_page(self, heap: HeapFile, page_id: int, pin: bool = False) -> np.ndarray:
        """A copy of the page, which stays valid after its frame goes."""
        with self._lock:
            key = (heap.path, page_id)
            slot = self._frames.get(key)
            if slot is not None:
                self.hits += 1
                self._frames.move_to_end(key)
                page = self._arena(heap)[slot].copy()
            else:
                self.misses += 1
                page = heap.read_page(page_id)
                self._arena(heap)[self._insert(key)] = page
            if pin:
                self._pins[key] = self._pins.get(key, 0) + 1
            return page

    def unpin(self, heap: HeapFile, page_id: int) -> None:
        with self._lock:
            key = (heap.path, page_id)
            if key in self._pins:
                self._pins[key] -= 1
                if self._pins[key] <= 0:
                    del self._pins[key]

    def fetch_batch(self, heap: HeapFile, page_ids: np.ndarray,
                    cause: obs.Span | None = None) -> np.ndarray:
        """Batched page fetch -> (n, page_words) uint32, ready for the device,
        in a ``pool.fetch`` span (``cause``: the span that asked for it from
        another thread) counting pages, hits, misses and bytes handed out.

        Hits are copied out of their frames under the lock. The misses are
        read unlocked, straight into their rows of the batch, one read a run
        of consecutive ids (``HeapFile.read_pages``), so a foreground fetch
        is never stalled behind a large background prefetch's I/O (a racing
        fetch of the same page at worst reads it twice — both reads return
        identical bytes and both count as misses; frames stay consistent).
        Then, under the lock again, the misses take frames in order, evicting
        as they go, and the rows that end up resident are copied into their
        frames at once."""
        page_ids = np.asarray(page_ids)
        path = heap.path
        out = np.empty((len(page_ids), heap.layout.page_words), dtype=np.uint32)
        hit_pos, hit_slots, miss_pos, miss_ids = [], [], [], []
        with obs.span("pool.fetch", cause=cause, pages=len(page_ids),
                      bytes=out.nbytes) as rec:
            with self._lock:
                for k, pid in enumerate(page_ids.tolist()):
                    slot = self._frames.get((path, pid))
                    if slot is not None:
                        self._frames.move_to_end((path, pid))
                        hit_pos.append(k)
                        hit_slots.append(slot)
                    else:
                        miss_pos.append(k)
                        miss_ids.append(pid)
                self.hits += len(hit_pos)
                self.misses += len(miss_pos)
                if hit_pos:
                    out[hit_pos] = self._arena(heap)[hit_slots]
            if miss_ids:
                heap.read_pages(miss_ids, out=[out[k] for k in miss_pos])
                with self._lock:
                    self._install(heap, miss_pos, miss_ids, out)
            rec.misses = len(miss_ids)
            rec.hits = len(hit_pos)
        return out

    def prefetch_batch(self, heap: HeapFile, page_ids: np.ndarray) -> PrefetchHandle:
        """Start ``fetch_batch`` on the pool's background thread and return a
        handle immediately. One worker serializes prefetches, so LRU order and
        hit/miss/eviction counters evolve exactly as the equivalent foreground
        fetch sequence would."""
        page_ids = np.asarray(page_ids)
        handle = PrefetchHandle(page_ids)
        cause = obs.current()

        def work():
            if not handle._future.set_running_or_notify_cancel():
                return
            try:
                t0 = time.perf_counter()
                pages = self.fetch_batch(heap, page_ids, cause)
                handle.fetch_s = time.perf_counter() - t0
                handle._future.set_result(pages)
            except BaseException as e:  # surfaced to the caller at result()
                handle._future.set_exception(e)

        self._executor().submit(work)
        return handle

    def warm(self, heap: HeapFile) -> int:
        """Preload as much of the heap as fits (the paper's warm-cache setup).
        Returns the number of resident pages of this heap."""
        n = min(heap.n_pages, self.capacity)
        ids = np.arange(heap.n_pages - n, heap.n_pages)  # keep the tail, like a scan would
        self.fetch_batch(heap, ids)
        return n

    def clear(self) -> None:
        """Cold-cache setup."""
        with self._lock:
            self._frames.clear()
            self._pins.clear()
            self._arenas.clear()
            self._free = list(range(self.capacity))

    @property
    def resident(self) -> int:
        return len(self._frames)

    # -- internals -----------------------------------------------------------
    def _executor(self) -> ThreadPoolExecutor:
        if self._prefetcher is None:
            with self._lock:
                if self._prefetcher is None:
                    self._prefetcher = ThreadPoolExecutor(
                        max_workers=1, thread_name_prefix="bufferpool-prefetch"
                    )
        return self._prefetcher

    def _arena(self, heap: HeapFile) -> np.ndarray:
        pw = heap.layout.page_words
        arena = self._arenas.get(pw)
        if arena is None:
            arena = self._arenas[pw] = np.empty((self.capacity, pw), np.uint32)
        return arena

    def _install(self, heap: HeapFile, positions, page_ids, batch) -> None:
        """Make the pages of ``batch[positions]`` resident, in order, as one
        insert each would, then fill their frames with one copy."""
        slots = []
        try:
            for pid in page_ids:
                slots.append(self._insert((heap.path, pid)))
        finally:
            # a frame taken twice (its page evicted by a later one, or a
            # page asked twice) holds what took it last
            last = dict(zip(slots, positions))
            rows = list(last.values())
            src = batch if rows == list(range(len(batch))) else batch[rows]
            self._arena(heap)[list(last)] = src

    def _insert(self, key) -> int:
        """Make ``key`` the most recently used frame, evicting the least
        recently used unpinned ones to make room; returns its arena row."""
        slot = self._frames.get(key)
        if slot is not None:  # same-key overwrite doesn't grow the pool
            self._frames.move_to_end(key)
            return slot
        while len(self._frames) >= self.capacity:
            victim = next((k for k in self._frames if k not in self._pins), None)
            if victim is None:
                raise RuntimeError("buffer pool exhausted: all frames pinned")
            self._free.append(self._frames.pop(victim))
            self.evictions += 1
        slot = self._frames[key] = self._free.pop()
        return slot
