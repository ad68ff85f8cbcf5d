"""Pipelined decode→train executor: Engine.run_chunk fused program parity,
double-buffered solver.train vs the synchronous ablation, one-device-sync-
per-epoch instrumentation, BufferPool.prefetch_batch accounting, and
PageTokenDataset wraparound/prefetch."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.algorithms import linear_regression
from repro.core import solver
from repro.core.engine import batches_from_stream, init_models, make_engine
from repro.core.translator import trace
from repro.data.pipeline import PageTokenDataset
from repro.data.synthetic import lm_token_batch
from repro.db.bufferpool import BufferPool
from repro.db.heap import write_table

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def linreg_heap(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("pipe")
    rng = np.random.default_rng(5)
    w_true = rng.normal(0, 1, 16).astype(np.float32)
    X = rng.normal(0, 1, (3000, 16)).astype(np.float32)
    y = X @ w_true
    heap = write_table(str(tmp / "lin.heap"), X, y, page_bytes=8192)
    return heap, w_true


# ------------------------- Engine.run_chunk ----------------------------------
def test_run_chunk_matches_decode_then_epoch(linreg_heap):
    """The fused chunk program == separate decode + reshape + epoch dispatches."""
    from repro.kernels.strider import ops as strider_ops

    heap, _ = linreg_heap
    g, part = trace(lambda: linear_regression(16, lr=0.3, merge_coef=64))
    eng = make_engine(g, part)
    models = init_models(g, np.random.default_rng(0), scale=0.01)
    pages = heap.read_pages(np.arange(heap.n_pages))

    feats, labels, mask = strider_ops.decode_pages(jnp.asarray(pages), heap.layout)
    t = feats.shape[0] * feats.shape[1]
    X, Y, M = batches_from_stream(
        feats.reshape(t, heap.layout.n_features), labels.reshape(t),
        mask.reshape(t), eng.merge_coef,
    )
    want, wantg = eng.run_epoch(models, X, Y, M)
    got, gotg = eng.run_chunk(models, pages, heap.layout)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gotg), np.asarray(wantg),
                               rtol=1e-4, atol=1e-5)
    # the program is cached per (layout, kernel-choice, mesh)
    assert len(eng._chunk_fns) == 1
    eng.run_chunk(models, pages, heap.layout)
    assert len(eng._chunk_fns) == 1


def test_lower_chunk_is_the_dispatched_program(linreg_heap):
    """``Engine.lower_chunk`` lowers the very program ``run_chunk`` caches
    and runs (what a chip run inspects for its kernels)."""
    heap, _ = linreg_heap
    g, part = trace(lambda: linear_regression(16, lr=0.3, merge_coef=64))
    eng = make_engine(g, part)
    models = init_models(g, np.random.default_rng(0), scale=0.01)
    pages = heap.read_pages(np.arange(4))
    compiled = eng.lower_chunk(models, pages, heap.layout).compile()
    got, _ = compiled(models, jnp.asarray(pages))
    want, _ = eng.run_chunk(models, pages, heap.layout)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert len(eng._chunk_fns) == 1


@pytest.mark.parametrize("pipelined", [False, True])
def test_train_use_kernel_paths_agree(linreg_heap, monkeypatch, pipelined):
    """``use_kernel`` reaches the decode and the engine: True runs the
    strider kernel (interpret mode here), False the reference decode and the
    vmapped update rule — the plain float32 path — with equal results."""
    heap, _ = linreg_heap
    monkeypatch.setattr(solver, "MAX_RESIDENT_PAGES", 8)
    g, part = trace(lambda: linear_regression(16, lr=0.3, merge_coef=64,
                                              epochs=2))
    seen = []
    real = solver.make_engine
    monkeypatch.setattr(
        solver, "make_engine",
        lambda *a, **kw: seen.append(kw["use_fused_kernel"]) or real(*a, **kw),
    )
    a = solver.train(g, part, heap, seed=3, pipelined=pipelined,
                     use_kernel=True)
    b = solver.train(g, part, heap, seed=3, pipelined=pipelined,
                     use_kernel=False)
    assert seen == [True, False]
    np.testing.assert_allclose(a.models[0], b.models[0], rtol=1e-5, atol=1e-6)


# ------------------------- pipelined solver.train ----------------------------
@pytest.mark.parametrize("mode", ["dana", "dana-nostrider"])
def test_pipelined_matches_synchronous_train(linreg_heap, monkeypatch, mode):
    heap, w_true = linreg_heap
    # force several chunks per epoch so double buffering really rotates
    monkeypatch.setattr(solver, "MAX_RESIDENT_PAGES", 8)
    g, part = trace(lambda: linear_regression(16, lr=0.3, merge_coef=64, epochs=6))
    a = solver.train(g, part, heap, mode=mode, seed=3, pipelined=False)
    b = solver.train(g, part, heap, mode=mode, seed=3, pipelined=True)
    assert (a.epochs_run, a.converged) == (b.epochs_run, b.converged)
    np.testing.assert_allclose(a.models[0], b.models[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(a.grad_norms, b.grad_norms, rtol=1e-4, atol=1e-5)
    assert not a.pipelined and b.pipelined
    # pipelined timing stays honest: io splits into exposed + overlapped
    assert b.io_s == pytest.approx(b.exposed_io_s + b.overlapped_io_s)
    if mode == "dana":
        assert b.decode_s == 0.0  # decode fused into the device program


def test_pipelined_convergence_parity(linreg_heap, monkeypatch):
    heap, w_true = linreg_heap
    monkeypatch.setattr(solver, "MAX_RESIDENT_PAGES", 16)
    g, part = trace(
        lambda: linear_regression(16, lr=0.3, merge_coef=64, conv_factor=0.08,
                                  epochs=200)
    )
    a = solver.train(g, part, heap, mode="dana", pipelined=False)
    b = solver.train(g, part, heap, mode="dana", pipelined=True)
    assert a.converged and b.converged
    assert a.epochs_run == b.epochs_run < 200
    np.testing.assert_allclose(b.models[0], w_true, atol=0.1)


def test_exactly_one_device_sync_per_epoch(linreg_heap, monkeypatch):
    heap, _ = linreg_heap
    monkeypatch.setattr(solver, "MAX_RESIDENT_PAGES", 8)
    calls = {"n": 0}
    real = solver._device_sync

    def spy(tree):
        calls["n"] += 1
        return real(tree)

    monkeypatch.setattr(solver, "_device_sync", spy)
    g, part = trace(lambda: linear_regression(16, lr=0.3, merge_coef=64, epochs=5))
    pool = BufferPool(pool_bytes=heap.n_pages * heap.layout.page_bytes,
                      page_bytes=heap.layout.page_bytes)
    res = solver.train(g, part, heap, pool=pool, mode="dana", pipelined=True)
    assert res.epochs_run == 5
    assert calls["n"] == res.epochs_run  # one hot-loop join per epoch
    assert res.device_syncs == res.epochs_run
    # every page fetched exactly once per epoch: no wasted trailing prefetch
    # after the final chunk of the final epoch (no convergence terminator)
    assert pool.hits + pool.misses == res.epochs_run * heap.n_pages
    # the synchronous ablation pays two joins per chunk
    sync = solver.train(g, part, heap, mode="dana", pipelined=False)
    n_chunks = -(-heap.n_pages // solver.MAX_RESIDENT_PAGES)
    assert sync.device_syncs == 2 * n_chunks * sync.epochs_run


def test_no_trailing_prefetch_on_final_epoch_with_terminator(
    linreg_heap, monkeypatch
):
    """A convergence terminator must not buy a dead chunk-0 prefetch on the
    last possible epoch: the per-epoch check reuses its cached batch, so the
    fetch count stays exactly epochs x pages (+ the one cached conv chunk)."""
    heap, _ = linreg_heap
    monkeypatch.setattr(solver, "MAX_RESIDENT_PAGES", 8)
    g, part = trace(
        lambda: linear_regression(16, lr=0.01, merge_coef=64, conv_factor=1e-9,
                                  epochs=3)
    )
    pool = BufferPool(pool_bytes=heap.n_pages * heap.layout.page_bytes,
                      page_bytes=heap.layout.page_bytes)
    res = solver.train(g, part, heap, pool=pool, mode="dana", pipelined=True)
    assert not res.converged and res.epochs_run == 3
    conv_pages = min(heap.n_pages, 4)  # the cached convergence batch, once
    assert pool.hits + pool.misses == res.epochs_run * heap.n_pages + conv_pages


# ------------------------- BufferPool.prefetch_batch -------------------------
def test_prefetch_batch_hit_miss_eviction_accounting(linreg_heap):
    heap, _ = linreg_heap
    ids = np.arange(6)
    fg = BufferPool(pool_bytes=4 * heap.layout.page_bytes,
                    page_bytes=heap.layout.page_bytes)
    fg.fetch_batch(heap, ids)
    fg.fetch_batch(heap, ids[:2])

    bg = BufferPool(pool_bytes=4 * heap.layout.page_bytes,
                    page_bytes=heap.layout.page_bytes)
    h1 = bg.prefetch_batch(heap, ids)
    pages = h1.result()
    np.testing.assert_array_equal(pages, heap.read_pages(ids))
    assert h1.done() and h1.fetch_s > 0.0
    h2 = bg.prefetch_batch(heap, ids[:2])
    h2.result()
    # background accounting identical to the equivalent foreground sequence
    assert (bg.hits, bg.misses, bg.evictions) == (fg.hits, fg.misses, fg.evictions)
    assert bg.resident == fg.resident == 4
    # a completed handle cannot be cancelled
    assert not h2.cancel()


def test_prefetch_interleaves_with_foreground_fetch(linreg_heap):
    heap, _ = linreg_heap
    pool = BufferPool(pool_bytes=heap.n_pages * heap.layout.page_bytes,
                      page_bytes=heap.layout.page_bytes)
    h = pool.prefetch_batch(heap, np.arange(8))
    fg = pool.fetch_batch(heap, np.arange(4, 12))  # overlapping foreground fetch
    np.testing.assert_array_equal(h.result(), heap.read_pages(np.arange(8)))
    np.testing.assert_array_equal(fg, heap.read_pages(np.arange(4, 12)))
    assert pool.hits + pool.misses == 16
    assert pool.resident == 12


def test_bufferpool_default_is_8mb_of_32k_pages():
    pool = BufferPool()
    assert pool.page_bytes == 32 * 1024
    assert pool.capacity == 256  # 8 MB / 32 KB


# ------------------------- PageTokenDataset ----------------------------------
def test_page_token_dataset_wraparound_spans_heap_end(tmp_path):
    vocab, seq, n_seqs, seed = 211, 16, 80, 4
    ds = PageTokenDataset(str(tmp_path / "tok.heap"), n_seqs=n_seqs,
                          seq_len=seq, vocab=vocab, seed=seed, page_bytes=8192)
    tpp = ds.heap.layout.tuples_per_page
    assert ds.heap.n_pages > 1 and n_seqs % tpp != 0  # partial last page
    batch_size = 12
    step = 6  # start tuple 72: spans the partial last page AND wraps to 0
    start = (step * batch_size) % n_seqs
    assert start + batch_size > n_seqs
    got = ds.batch(step, batch_size)
    assert got["tokens"].shape == (batch_size, seq)
    for row, sid in enumerate((start + np.arange(batch_size)) % n_seqs):
        want = lm_token_batch(seed * 131 + int(sid), 1, seq, vocab)
        np.testing.assert_array_equal(np.asarray(got["tokens"][row]),
                                      want["tokens"][0])
        np.testing.assert_array_equal(np.asarray(got["targets"][row]),
                                      want["targets"][0])
    # no dead page slots leaked into the batch
    assert int((np.asarray(got["tokens"]) == 0).all(axis=1).sum()) == 0


def test_page_token_dataset_prefetch_consumed_on_sequential_steps(tmp_path):
    ds = PageTokenDataset(str(tmp_path / "tok.heap"), n_seqs=64, seq_len=16,
                          vocab=97, seed=1, page_bytes=8192)
    b0 = ds.batch(0, 8)
    assert ds._pending is not None
    key, handle = ds._pending
    b1 = ds.batch(1, 8)  # consumes the prefetched pages
    assert handle.done()
    # random access after a prefetch miss still yields the right sequences
    b5 = ds.batch(5, 8)
    want = lm_token_batch(1 * 131 + 40, 1, 16, 97)
    np.testing.assert_array_equal(np.asarray(b5["tokens"][0]), want["tokens"][0])


# ------------------------- sharded-mesh run_chunk ----------------------------
_SHARDED_SCRIPT = textwrap.dedent(
    """
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax

    from repro.algorithms import linear_regression
    from repro.core import solver
    from repro.core.translator import trace
    from repro.db.heap import write_table
    from repro.dist import meshes

    assert jax.device_count() == 8
    rng = np.random.default_rng(11)
    w_true = rng.normal(0, 1, 12).astype(np.float32)
    X = rng.normal(0, 1, (2048, 12)).astype(np.float32)
    y = X @ w_true
    tmp = tempfile.mkdtemp()
    heap = write_table(os.path.join(tmp, "s.heap"), X, y, page_bytes=8192)
    g, part = trace(lambda: linear_regression(12, lr=0.3, merge_coef=64, epochs=4))

    base = solver.train(g, part, heap, mode="dana", seed=2, pipelined=True)
    mesh = meshes.make_host_mesh()
    assert dict(mesh.shape)["data"] == 8
    shard = solver.train(g, part, heap, mode="dana", seed=2, pipelined=True,
                         mesh=mesh)
    assert shard.device_syncs == shard.epochs_run == 4
    np.testing.assert_allclose(shard.models[0], base.models[0],
                               rtol=1e-4, atol=1e-5)
    print("SHARDED-RUN-CHUNK-OK")
    """
)


def test_pipelined_train_sharded_8_devices_subprocess():
    """The fused chunk program under a real 8-device data axis: decode,
    sharding constraints, and the cross-device merge run inside one jitted
    program per chunk, numerically equal to the single-device pipeline."""
    # forced host devices are CPU devices: the child never takes the chip
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "SHARDED-RUN-CHUNK-OK" in out.stdout


_ENGINE_MESH_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax

    from repro.algorithms import linear_regression
    from repro.core.engine import init_models, make_engine
    from repro.core.translator import trace
    from repro.db.page import PageLayout, build_pages
    from repro.dist import meshes
    from repro.kernels.strider import ops

    lo = PageLayout(n_features=12, page_bytes=4096, quantized=False)
    rng = np.random.default_rng(3)
    n = 10 * lo.tuples_per_page - 3  # 10 pages: padded to 12 over 4 devices
    pages = build_pages(rng.normal(0, 1, (n, 12)).astype(np.float32),
                        rng.normal(0, 1, n).astype(np.float32), lo)
    g, part = trace(lambda: linear_regression(12, lr=0.3, merge_coef=64))
    models = init_models(g, np.random.default_rng(0), scale=0.01)

    seen = []
    real = ops.strider_decode
    ops.strider_decode = lambda p, **kw: seen.append(p.shape[0]) or real(p, **kw)

    want, _ = make_engine(g, part).run_chunk(models, pages, lo, use_kernel=True)
    assert seen == [10], seen
    mesh = meshes.make_host_mesh()
    assert meshes.current_mesh() is None
    eng = make_engine(g, part, mesh=mesh)  # no use_mesh around the run
    got, _ = eng.run_chunk(models, pages, lo, use_kernel=True)
    assert eng.last_sharded_path[0] == "shard_map", eng.last_sharded_path
    # the kernel ran per device on its own 3-page range, not on all 10 pages
    assert seen == [10, 3], seen
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-6)
    print("ENGINE-MESH-DECODE-OK")
    """
)


def test_engine_mesh_shards_the_kernel_decode_subprocess():
    """An engine built with ``mesh=`` and run outside ``use_mesh`` decodes
    on its own mesh: the strider kernel is shard_map'd per device over the
    page range, and the result equals the one-device chunk program."""
    # forced host devices are CPU devices: the child never takes the chip
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _ENGINE_MESH_SCRIPT],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "ENGINE-MESH-DECODE-OK" in out.stdout
