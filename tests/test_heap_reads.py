"""Run reads of heap pages, and the buffer pool's contract over them.

``HeapFile.read_pages`` reads each maximal run of consecutive page ids with
one vectored read straight into the rows of its output; ``BufferPool``
hands out batches and pages that never alias its frames. The counts below
are literal: they are what the pool has always given for these sequences.
"""
import os
import time

import numpy as np
import pytest

from repro import obs
from repro.db.bufferpool import BufferPool
from repro.db.heap import IOV_MAX, write_table

PAGE_BYTES = 512
N_PAGES = IOV_MAX + 76  # one run longer than a vectored read takes


@pytest.fixture(scope="module")
def heap(tmp_path_factory):
    rng = np.random.default_rng(7)
    probe = write_table(str(tmp_path_factory.mktemp("probe") / "p.heap"),
                        np.zeros((1, 6), np.float32), np.zeros(1, np.float32),
                        page_bytes=PAGE_BYTES)
    n = N_PAGES * probe.layout.tuples_per_page
    h = write_table(str(tmp_path_factory.mktemp("heap") / "t.heap"),
                    rng.normal(size=(n, 6)).astype(np.float32),
                    rng.normal(size=n).astype(np.float32),
                    page_bytes=PAGE_BYTES)
    assert h.n_pages == N_PAGES
    return h


@pytest.fixture(scope="module")
def qheap(tmp_path_factory):
    rng = np.random.default_rng(8)
    return write_table(str(tmp_path_factory.mktemp("q") / "q.heap"),
                       rng.normal(size=(300, 40)).astype(np.float32),
                       rng.normal(size=300).astype(np.float32),
                       page_bytes=1024, quantized=True)


def reference(h, ids):
    """One seek and one read a page: what the heap returned before runs."""
    pb = h.layout.page_bytes
    with open(h.path, "rb") as f:
        rows = []
        for pid in ids:
            f.seek(int(pid) * pb)
            rows.append(np.frombuffer(f.read(pb), dtype=np.uint32))
    return np.stack(rows)


# (name, ids from n_pages, maximal runs, vectored reads)
ID_SETS = [
    ("contiguous", lambda n: np.arange(3, 40), 1, 1),
    ("unsorted", lambda n: np.array([7, 2, 9, 3, 4, 0]), 5, 5),
    ("duplicated", lambda n: np.array([5, 5, 6, 6, 2]), 4, 4),
    ("wraparound", lambda n: np.array([n - 2, n - 1, 0, 1]), 2, 2),
    ("single", lambda n: np.array([3]), 1, 1),
    ("last", lambda n: np.array([n - 1]), 1, 1),
    ("longer_than_iov_max", lambda n: np.arange(n), 1, 2),
]
IDS = [c[0] for c in ID_SETS]


def read_spans(since_ns):
    return [s for s in obs.spans() if s.name == "heap.read" and s.t0 >= since_ns]


@pytest.mark.parametrize("name,ids_of,runs,reads", ID_SETS, ids=IDS)
def test_read_pages_matches_page_by_page(heap, name, ids_of, runs, reads):
    ids = ids_of(heap.n_pages)
    np.testing.assert_array_equal(heap.read_pages(ids), reference(heap, ids))


@pytest.mark.parametrize("name,ids_of,runs,reads", ID_SETS, ids=IDS)
def test_read_pages_quantized_layout(qheap, name, ids_of, runs, reads):
    ids = ids_of(qheap.n_pages) % qheap.n_pages
    np.testing.assert_array_equal(qheap.read_pages(ids), reference(qheap, ids))


@pytest.mark.parametrize("name,ids_of,runs,reads", ID_SETS, ids=IDS)
def test_heap_read_span_counts_one_read_a_run(heap, name, ids_of, runs, reads):
    """``reads`` is one a maximal run of consecutive ids, and one more for
    each further ``IOV_MAX`` pages of a run."""
    ids = ids_of(heap.n_pages)
    t = time.perf_counter_ns()
    heap.read_pages(ids)
    (rec,) = read_spans(t)
    assert (rec.pages, rec.bytes, rec.reads) == (
        len(ids), len(ids) * PAGE_BYTES, reads)
    assert runs <= reads


def test_read_pages_fills_out_in_place(heap):
    ids = np.array([9, 10, 11, 4])
    out = np.full((4, heap.layout.page_words), 7, dtype=np.uint32)
    assert heap.read_pages(ids, out=out) is out
    np.testing.assert_array_equal(out, reference(heap, ids))


def test_read_pages_fills_scattered_rows_of_a_batch(heap):
    """A list of rows is filled in place: a pool reads its misses straight
    into their rows of the batch it hands out."""
    batch = np.zeros((6, heap.layout.page_words), dtype=np.uint32)
    rows = [batch[k] for k in (5, 1, 2)]
    t = time.perf_counter_ns()
    assert heap.read_pages(np.array([20, 21, 22]), out=rows) is rows
    np.testing.assert_array_equal(batch[[5, 1, 2]],
                                  reference(heap, [20, 21, 22]))
    assert not batch[[0, 3, 4]].any()
    assert read_spans(t)[0].reads == 1


def test_read_pages_loops_on_short_reads(heap, monkeypatch):
    """A read that returns less than asked is continued, never taken as
    the page."""
    real = os.preadv

    def short(fd, bufs, offset):
        return real(fd, [memoryview(bufs[0]).cast("B")[:300]], offset)

    monkeypatch.setattr(os, "preadv", short)
    ids = np.array([4, 5, 6, 1])
    t = time.perf_counter_ns()
    np.testing.assert_array_equal(heap.read_pages(ids), reference(heap, ids))
    # 4 pages of 512 B in slices of at most 300 B: 2 + 2 + 2 + 2 reads
    assert read_spans(t)[0].reads == 8


def test_read_pages_past_the_end_raises(heap):
    with pytest.raises(EOFError):
        heap.read_pages(np.array([heap.n_pages - 1, heap.n_pages]))


def test_read_page_is_one_page(heap):
    np.testing.assert_array_equal(heap.read_page(17), reference(heap, [17])[0])


# ------------------------------ the pool --------------------------------------
def small_pool(h, frames):
    return BufferPool(pool_bytes=frames * h.layout.page_bytes,
                      page_bytes=h.layout.page_bytes)


def counts(pool):
    return (pool.hits, pool.misses, pool.evictions, pool.resident)


# (call, page ids, (hits, misses, evictions, resident) after it), capacity 4
SCRIPT = [
    ("fetch", [0, 1, 2], (0, 3, 0, 3)),
    ("fetch", [1, 2, 3, 4], (2, 5, 1, 4)),          # mixed hits and misses
    ("fetch", list(range(5, 11)), (2, 11, 7, 4)),   # more misses than frames
    ("fetch", [10, 0, 9], (4, 12, 8, 4)),
    ("get", [8], (5, 12, 8, 4)),
    ("get", [7], (5, 13, 9, 4)),
    ("fetch", [3, 3], (5, 15, 10, 4)),              # a page twice, both missed
    ("fetch", [40, 41, 42, 43, 44, 45, 3, 8], (7, 21, 16, 4)),  # hits kept, then evicted
    ("fetch", [45, 44, 43, 3], (10, 22, 17, 4)),
]


@pytest.mark.parametrize("steps", range(1, len(SCRIPT) + 1))
def test_pool_counts_for_a_scripted_sequence(heap, steps):
    pool = small_pool(heap, 4)
    for call, ids, want in SCRIPT[:steps]:
        if call == "fetch":
            got = pool.fetch_batch(heap, np.array(ids))
            np.testing.assert_array_equal(got, reference(heap, ids))
        else:
            np.testing.assert_array_equal(pool.get_page(heap, ids[0]),
                                          reference(heap, ids)[0])
        assert counts(pool) == want, (call, ids)


def test_prefetch_counts_match_the_script(heap):
    pool = small_pool(heap, 4)
    for call, ids, want in SCRIPT:
        if call == "fetch":
            got = pool.prefetch_batch(heap, np.array(ids)).result()
            np.testing.assert_array_equal(got, reference(heap, ids))
        else:
            pool.get_page(heap, ids[0])
        assert counts(pool) == want, (call, ids)


def test_batch_does_not_alias_frames(heap):
    pool = small_pool(heap, 8)
    ids = np.arange(4)
    first = pool.fetch_batch(heap, ids)  # misses
    first[:] = 0
    second = pool.fetch_batch(heap, ids)  # hits
    np.testing.assert_array_equal(second, reference(heap, ids))
    second[:] = 1
    np.testing.assert_array_equal(pool.fetch_batch(heap, ids),
                                  reference(heap, ids))
    assert counts(pool) == (8, 4, 0, 4)


@pytest.mark.parametrize("from_hits", [False, True], ids=["misses", "hits"])
def test_batch_survives_eviction_of_its_frames(heap, from_hits):
    pool = small_pool(heap, 4)
    ids = np.arange(10, 14)
    batch = pool.fetch_batch(heap, ids)
    if from_hits:
        batch = pool.fetch_batch(heap, ids)
    pool.fetch_batch(heap, np.arange(20, 28))  # evicts every frame, twice
    np.testing.assert_array_equal(batch, reference(heap, ids))
    assert pool.evictions == 8


@pytest.mark.parametrize("from_hit", [False, True], ids=["miss", "hit"])
def test_get_page_stays_valid_after_eviction(heap, from_hit):
    pool = small_pool(heap, 2)
    page = pool.get_page(heap, 5)
    if from_hit:
        page = pool.get_page(heap, 5)
    page_copy = page.copy()
    page[:] = 3  # does not reach the frame
    np.testing.assert_array_equal(pool.get_page(heap, 5), reference(heap, [5])[0])
    pool.fetch_batch(heap, np.arange(30, 34))
    page[:] = page_copy
    np.testing.assert_array_equal(page, reference(heap, [5])[0])
    assert (5 not in [k[1] for k in pool._frames]) and pool.evictions >= 2


def test_pinned_frames_survive_batches(heap):
    pool = small_pool(heap, 3)
    pool.get_page(heap, 0, pin=True)
    got = pool.fetch_batch(heap, np.arange(1, 9))  # 8 misses through 2 frames
    np.testing.assert_array_equal(got, reference(heap, np.arange(1, 9)))
    assert (heap.path, 0) in pool._frames
    assert counts(pool) == (0, 9, 6, 3)
    np.testing.assert_array_equal(pool.fetch_batch(heap, np.array([0, 8, 7])),
                                  reference(heap, [0, 8, 7]))
    assert counts(pool) == (3, 9, 6, 3)
    pool.unpin(heap, 0)


@pytest.mark.parametrize("call", ["fetch", "get"])
def test_all_pinned_raises(heap, call):
    pool = small_pool(heap, 2)
    pool.get_page(heap, 0, pin=True)
    pool.get_page(heap, 1, pin=True)
    with pytest.raises(RuntimeError, match="all frames pinned"):
        if call == "fetch":
            pool.fetch_batch(heap, np.array([2, 3]))
        else:
            pool.get_page(heap, 2)
    # the pinned frames still hold their pages
    np.testing.assert_array_equal(pool.fetch_batch(heap, np.array([1, 0])),
                                  reference(heap, [1, 0]))
    pool.unpin(heap, 0)
    np.testing.assert_array_equal(pool.fetch_batch(heap, np.array([2, 3])),
                                  reference(heap, [2, 3]))
    assert (heap.path, 1) in pool._frames


def test_pool_over_heaps_of_two_page_sizes(heap, qheap):
    pool = BufferPool(pool_bytes=4 * 1024, page_bytes=1024)
    for ids_a, ids_q in [([0, 1], [0, 1, 2]), ([1, 2], [2, 3]), ([0], [0])]:
        np.testing.assert_array_equal(pool.fetch_batch(heap, np.array(ids_a)),
                                      reference(heap, ids_a))
        np.testing.assert_array_equal(pool.fetch_batch(qheap, np.array(ids_q)),
                                      reference(qheap, ids_q))
    assert counts(pool) == (2, 9, 5, 4)


def test_clear_then_refetch(heap):
    pool = small_pool(heap, 4)
    pool.fetch_batch(heap, np.arange(4))
    pool.clear()
    assert pool.resident == 0
    np.testing.assert_array_equal(pool.fetch_batch(heap, np.arange(2, 8)),
                                  reference(heap, np.arange(2, 8)))
    assert counts(pool) == (0, 10, 2, 4)


def test_threads_sharing_a_pool_keep_frames_and_batches_intact(heap):
    """Twelve threads fetch runs and scattered pages through 16 frames with a
    short switch interval: every batch is its pages, every frame holds its
    page, and no arena row is held by two frames or lost."""
    import sys
    import threading

    table = heap.read_all()
    pool = small_pool(heap, 16)
    asked, failures = [0], []
    count_lock = threading.Lock()

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(25):
                start = int(rng.integers(0, 60))
                ids = np.concatenate([np.arange(start, start + rng.integers(1, 12)),
                                      rng.integers(0, 60, size=3)])
                if rng.random() < 0.5:
                    got = pool.prefetch_batch(heap, ids).result(timeout=30)
                else:
                    got = pool.fetch_batch(heap, ids)
                page = pool.get_page(heap, int(ids[0]))
                with count_lock:
                    asked[0] += len(ids) + 1
                if not (np.array_equal(got, table[ids])
                        and np.array_equal(page, table[ids[0]])):
                    failures.append(seed)
        except Exception as e:  # reported by the assertion below
            failures.append(repr(e))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(s,)) for s in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert failures == []
    assert pool.hits + pool.misses == asked[0]
    slots = list(pool._frames.values())
    assert pool.resident == 16 and len(set(slots)) == len(slots)
    assert sorted(slots + pool._free) == list(range(16))
    arena = pool._arenas[heap.layout.page_words]
    for (path, pid), slot in pool._frames.items():
        np.testing.assert_array_equal(arena[slot], table[pid])
