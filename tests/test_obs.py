"""The program's spans (``repro.obs``): nesting, causes across the buffer
pool's thread, the ring's bound, JAX's compile-path events, and the spans a
statement leaves through ``Session.sql``, with counts that agree with the
pool's own counters and with the chunk arithmetic."""
import collections
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.db import connect
from repro.db.bufferpool import BufferPool
from repro.db.heap import write_table

PAGE_BYTES = 4096


def since(t_ns):
    return [s for s in obs.spans() if s.t0 >= t_ns]


def test_nested_spans_carry_parent_root_counts_and_self_time():
    outer_parent = obs.current()
    with obs.span("outer", pages=2) as outer:
        assert obs.current() is outer
        time.sleep(0.002)
        with obs.span("inner") as inner:
            time.sleep(0.003)
            inner.hits = 5
        with pytest.raises(KeyError):
            with obs.span("failing"):
                raise KeyError("x")
    assert obs.current() is outer_parent
    assert outer.parent == 0 and outer.root == outer.id
    assert inner.parent == outer.id and inner.root == outer.id
    assert (outer.pages, inner.hits) == (2, 5)
    assert outer.tid == inner.tid == threading.get_ident()
    assert outer.t0 <= inner.t0 < inner.t1 <= outer.t1
    ring = obs.spans()
    failing = [s for s in ring if s.name == "failing" and s.parent == outer.id]
    assert len(failing) == 1  # a span left by an exception is still kept
    # self time: the span less the time its children cover
    children = [s for s in ring if s.parent == outer.id]
    self_ns = (outer.t1 - outer.t0) - sum(s.t1 - s.t0 for s in children)
    assert 0.002 <= self_ns / 1e9 < outer.seconds
    assert inner.seconds >= 0.003
    # kept in the order they ended
    ids = [s.id for s in ring]
    assert ids.index(inner.id) < ids.index(outer.id)


def test_prefetch_on_the_pool_thread_names_its_cause(tmp_path):
    rng = np.random.default_rng(0)
    heap = write_table(str(tmp_path / "t.heap"),
                       rng.normal(size=(40, 100)).astype(np.float32),
                       rng.normal(size=40).astype(np.float32),
                       page_bytes=PAGE_BYTES)
    pool = BufferPool(pool_bytes=64 * PAGE_BYTES, page_bytes=PAGE_BYTES)
    ids = np.arange(heap.n_pages)
    t = time.perf_counter_ns()
    with obs.span("ask") as ask:
        pages, exposed, overlapped = pool.prefetch_batch(heap, ids).wait()
        again, _, _ = pool.prefetch_batch(heap, ids[:2]).wait()
    np.testing.assert_array_equal(pages, heap.read_pages(ids))
    got = since(t)
    fetches = [s for s in got if s.name == "pool.fetch"]
    waits = [s for s in got if s.name == "pool.wait"]
    reads = [s for s in got if s.name == "heap.read" and s.root == ask.id]
    assert len(fetches) == len(waits) == 2 and len(reads) == 1
    for f in fetches:
        assert f.cause == ask.id and f.root == ask.id and f.parent == 0
        assert f.tid != ask.tid
    assert [f.pages for f in fetches] == [heap.n_pages, 2]
    assert [(f.hits, f.misses) for f in fetches] == [(0, heap.n_pages), (2, 0)]
    assert fetches[0].bytes == heap.n_pages * PAGE_BYTES
    assert sum(f.hits for f in fetches) == pool.hits
    assert sum(f.misses for f in fetches) == pool.misses
    (read,) = reads
    assert read.parent == fetches[0].id and read.tid == fetches[0].tid
    assert (read.pages, read.bytes) == (heap.n_pages, heap.n_pages * PAGE_BYTES)
    for w in waits:
        assert w.parent == ask.id and w.tid == ask.tid
    assert [w.pages for w in waits] == [heap.n_pages, 2]
    assert exposed == waits[0].seconds and overlapped >= 0.0


def test_the_ring_is_bounded_and_says_what_it_let_go(monkeypatch):
    monkeypatch.setattr(obs, "RING_SPANS", 4)
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=4))
    monkeypatch.setattr(obs, "_dropped_end_ns", 0)
    recs = []
    for i in range(3):
        with obs.span("s", i=i) as r:
            recs.append(r)
    assert obs.oldest_ns() == 0 and len(obs.spans()) == 3
    for i in range(3, 6):
        with obs.span("s", i=i) as r:
            recs.append(r)
    assert [s.i for s in obs.spans()] == [2, 3, 4, 5]
    # every span that ended after recs[1] is still held
    assert obs.oldest_ns() == recs[1].t1


def test_a_retrace_leaves_jax_trace_and_lower_spans():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2.0 + 1.0)
    t = time.perf_counter_ns()
    with obs.span("outer") as outer:
        f(jnp.ones(3))
        f(jnp.ones(5))  # a new shape: traced and lowered again
    jit = [s for s in since(t) if s.name.startswith("jax.")]
    names = collections.Counter(s.name for s in jit)
    assert names["jax.trace"] >= 2 and names["jax.lower"] >= 2
    assert set(names) <= set(obs.JAX_EVENTS.values())
    for s in jit:
        assert s.parent == outer.id and s.root == outer.id
        assert s.tid == outer.tid and outer.t0 <= s.t0 <= s.t1 <= outer.t1


@pytest.fixture
def session(tmp_path, monkeypatch):
    """A 10-page table (4 tuples a page) under a logistic UDF, trained and
    scanned 3 pages a chunk."""
    from repro.algorithms import logistic_regression
    from repro.core import solver
    from repro.db.query import register_udf_from_trace

    monkeypatch.setattr(solver, "MAX_RESIDENT_PAGES", 3)
    rng = np.random.default_rng(1)
    d = 200
    x = rng.normal(size=(40, d)).astype(np.float32)
    y = (rng.random(40) > 0.5).astype(np.float32)
    heap = write_table(str(tmp_path / "t.heap"), x, y, page_bytes=PAGE_BYTES)
    assert heap.layout.tuples_per_page == 4 and heap.n_pages == 10
    sess = connect(str(tmp_path / "cat"), page_bytes=PAGE_BYTES)
    sess.catalog.register_table("t", heap.path, {"n_features": d})
    register_udf_from_trace(
        sess.catalog, "logit",
        lambda: logistic_regression(d, lr=0.5, merge_coef=8, epochs=2),
        layout=heap.layout)
    yield sess, heap
    sess.close()


STATEMENTS = [
    ("SELECT * FROM dana.logit('t');", 2),
    ("SELECT c0, label FROM dana.predict('logit', 't') WHERE c1 > 0;", 1),
    ("SELECT COUNT(*), AVG(prediction) FROM dana.predict('logit', 't') "
     "WHERE c2 <= 0.5;", 1),
]


def test_session_statements_leave_exactly_the_named_spans(session):
    sess, heap = session
    chunks = -(-heap.n_pages // 3)
    fetched = []
    for sql, passes in STATEMENTS:
        t = time.perf_counter_ns()
        res = sess.sql(sql, chunk_pages=3)
        got = since(t)
        (st,) = [s for s in got if s.name == "sql.statement"]
        assert st.verb == res.verb and st.parent == 0
        mine = [s for s in got if s.root == st.id]
        names = collections.Counter(s.name for s in mine
                                    if not s.name.startswith("jax."))
        allowed = {"sql.statement", "sql.plan", "pool.wait", "pool.fetch",
                   "heap.read", "scan.finalize"}
        assert set(names) <= allowed and len(mine) == len(got)
        assert names["sql.statement"] == 1 and names["sql.plan"] == 2
        assert names["pool.wait"] == names["pool.fetch"] == passes * chunks
        assert names["scan.finalize"] == (res.verb == "PREDICT")
        waits = [s for s in mine if s.name == "pool.wait"]
        # every page the statement scanned, once a pass
        assert sum(s.pages for s in waits) == passes * heap.n_pages
        assert [s.pages for s in waits] == [3, 3, 3, 1] * passes
        assert res.exposed_io_s == pytest.approx(sum(s.seconds for s in waits))
        for s in mine:
            if s.name == "pool.fetch":
                assert s.cause == st.id and s.tid != st.tid
                assert s.hits + s.misses == s.pages
            elif s.name == "heap.read":
                assert s.tid != st.tid
            elif s.name != "sql.statement":
                assert s.tid == st.tid
        fin = [s for s in mine if s.name == "scan.finalize"]
        if fin:
            assert fin[0].rows == res.n_rows and fin[0].bytes > 0
        fetched += [s for s in mine if s.name == "pool.fetch"]
    # the spans' hits and misses are the pool's own counts: the second
    # epoch and both scans find the table the first epoch left in the pool
    pool = sess.pool
    assert sum(s.hits for s in fetched) == pool.hits == 3 * heap.n_pages
    assert sum(s.misses for s in fetched) == pool.misses == heap.n_pages
