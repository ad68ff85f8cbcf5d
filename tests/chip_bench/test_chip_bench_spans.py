"""The per-layer metrics that read the program's spans (``repro.obs``), on a
hand-built ring with known answers: clipping to the window, a ring that let
spans go, a program without spans, and the trace reduction naming idle
gaps by the program's spans inside the benchmark's."""
import sys
import types

import pytest

from benchfix import REPO

S = 1_000_000_000  # ns a second
W0, W1 = 10 * S, 20 * S


def load(name):
    from bench import harness

    return harness.load_module(REPO, "metrics", name)


def span(name, t0, t1, tid=1, **counts):
    from repro import obs

    s = obs.Span(name, None, None, counts)
    s.t0, s.t1, s.tid = int(t0), int(t1), tid
    return s


def hand_ring():
    """Window [10 s, 20 s). Statement thread 1, pool thread 2."""
    return [
        span("sql.statement", 9 * S, 21 * S, verb="TRAIN"),
        # compile path on the statement thread: 0.5 s of a trace straddling
        # the start, 0.5 s traced, a 1 s compile holding a 0.6 s cache load
        span("jax.trace", 9.5 * S, 10.5 * S),
        span("jax.trace", 11 * S, 11.5 * S),
        span("jax.compile", 12 * S, 13 * S),
        span("jax.cache_load", 12.2 * S, 12.8 * S),
        span("jax.lower", 14 * S, 15 * S, tid=2),  # not a statement thread
        # the pool: one fetch inside, one half inside, one before the window
        span("pool.fetch", 10 * S, 12 * S, tid=2, pages=100, hits=25,
             misses=75, bytes=2e9),
        span("pool.fetch", 19 * S, 21 * S, tid=2, pages=100, hits=50,
             misses=50, bytes=2e9),
        span("pool.fetch", 5 * S, 6 * S, tid=2, pages=100, hits=100,
             misses=0, bytes=2e9),
        span("heap.read", 10 * S, 11 * S, tid=2, pages=30, bytes=3e9),
        span("heap.read", 20 * S, 21 * S, tid=2, pages=30, bytes=9e9),
        span("pool.wait", 10 * S, 12 * S, pages=100),
        span("scan.finalize", 16 * S, 17 * S, rows=5, bytes=40),
        span("scan.finalize", 19.5 * S, 20.5 * S, rows=5, bytes=40),
    ]


@pytest.fixture
def ring(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "spans", hand_ring)
    monkeypatch.setattr(obs, "oldest_ns", lambda: 0)
    return types.SimpleNamespace(window_t0=W0 / S, window_t1=W1 / S,
                                 window_s=(W1 - W0) / S)


EXPECTED = {
    # covered time 0.5 + 0.5 + 1.0 s on thread 1, over 10 s
    "jit_share.analytics": 20.0,
    # hits 25 + 25 (half of the straddling fetch) over pages 100 + 50
    "pool_hit_share.analytics": 100.0 * 50 / 150,
    # bytes 2e9 + 1e9 over 2 s + 1 s
    "feed_gbps.analytics": 1.0,
    # only the read inside: 3e9 B in 1 s
    "heap_read_gbps.analytics": 3.0,
    # 1 s + 0.5 s of finalize over 10 s
    "result_share.analytics": 15.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_reads_the_hand_built_ring(ring, name):
    assert load(name).read(ring) == pytest.approx(EXPECTED[name])


def test_spans_are_clipped_to_the_window(ring):
    from bench import spans

    clipped = spans.window(ring)
    got = {(s.name, s.t0): (a, b) for s, a, b in clipped}
    assert ("pool.fetch", 5 * S) not in got  # before the window
    assert ("heap.read", 20 * S) not in got  # starts as the window ends
    assert got[("sql.statement", 9 * S)] == (W0, W1)
    assert got[("pool.fetch", 19 * S)] == (19 * S, W1)
    assert spans.seconds(clipped, "pool.fetch") == pytest.approx(3.0)
    assert spans.count(clipped, "pool.fetch", "pages") == pytest.approx(150)
    assert spans.union_seconds([(0, 4), (2, 6), (8, 9), (8, 9)]) == 7e-9


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_ring_that_let_spans_go_in_the_window_gives_no_reading(
        ring, monkeypatch, name):
    from repro import obs

    monkeypatch.setattr(obs, "oldest_ns", lambda: W0 + 1)
    assert load(name).read(ring) is None
    monkeypatch.setattr(obs, "oldest_ns", lambda: W0 - 1)  # lost before it
    assert load(name).read(ring) is not None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_program_without_spans_gives_no_reading(ring, monkeypatch, name):
    import repro

    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert load(name).read(ring) is None


def test_no_statement_or_fetch_in_the_window_gives_no_reading(ring,
                                                              monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "spans", lambda: [
        s for s in hand_ring() if s.name not in ("sql.statement",
                                                 "pool.fetch", "heap.read")])
    for name in ("jit_share.analytics", "pool_hit_share.analytics",
                 "feed_gbps.analytics", "heap_read_gbps.analytics"):
        assert load(name).read(ring) is None
    assert load("result_share.analytics").read(ring) == pytest.approx(15.0)


def test_idle_gaps_are_named_by_the_program_span_inside_the_statement():
    """Session.sql over [100, 1000) on the benchmark's thread, with the
    program's sql.statement, sql.plan and pool.wait inside it; the pool's
    thread fetches meanwhile. Device ops at [0, 100) and [900, 950)."""
    from bench import trace

    host = {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [
            ["bench.window", 0, 1000, ""],
            ["Session.sql", 100, 900, ""],
            ["sql.statement", 110, 880, ""],
            ["sql.plan", 110, 40, ""],
            ["pool.wait", 200, 600, ""],
            ["DevicePut", 850, 10, ""],
        ]},
        {"name": "bufferpool-prefetch_0", "events": [
            ["pool.fetch", 150, 700, ""], ["heap.read", 160, 680, ""]]},
    ]}
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.1)", 0, 100, ""],
        ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p.2)", 900, 50, ""],
    ]}]}
    red = trace.reduce({"planes": [host, dev]})
    gaps = dict(red["breakdown"]["idle_gaps"])
    # gap [100, 900): midpoint 500 in pool.wait; gap [950, 1000): midpoint
    # 975 in the statement's span, which runs to 990; the pool's thread
    # labels nothing
    assert gaps == pytest.approx({"Session.sql/pool.wait": 800e-9,
                                  "Session.sql/sql.statement": 50e-9})
