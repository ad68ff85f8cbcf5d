"""End-to-end in-database training driver.

Ties the stack together the way Figure 2 of the paper draws it: the query
layer resolves the UDF from the catalog, the buffer pool streams pages, the
access engine (strider kernel or host path) decodes tuples, and the execution
engine runs the epochs until the terminator fires.

Execution modes (the paper's evaluation axes):
  "dana"            device-side page decode (strider kernel) + threaded engine
  "dana-nostrider"  host-side per-page decode + threaded engine (Fig 11 ablation)
  "madlib"          tuple-at-a-time host baseline (MADlib+PostgreSQL analogue)

Executors (``pipelined=``):
  pipelined (default)  double-buffered: while the device trains chunk k, the
      buffer pool's background thread fetches chunk k+1; in "dana" mode the
      decode + batch reshape + epoch scan run as ONE fused device program
      (``Engine.run_chunk``) and the host joins the device exactly once per
      epoch. I/O that hides under compute is reported as ``overlapped_io_s``;
      only the residue the loop actually blocked on is ``exposed_io_s``.
  synchronous          the paper-figure ablation: fetch -> decode -> sync ->
      batch -> epoch -> sync per chunk, so io_s/decode_s/compute_s add
      instead of overlap.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.engine import (
    Engine,
    batches_from_stream as _batches,
    init_models,
    make_engine,
)
from repro.dist import meshes
from repro.core.hdfg import HDFG
from repro.core.translator import Partition
from repro.db.bufferpool import BufferPool
from repro.db.heap import HeapFile
from repro.db.page import parse_page

MAX_RESIDENT_PAGES = 512  # pages decoded per device chunk (16 MB of 32 KB pages)


@dataclasses.dataclass
class TrainResult:
    """Timing contract: ``total_s`` is wall time. Synchronous executor:
    ``io_s + decode_s + compute_s`` ~= the hot loop (phases add). Pipelined
    executor: ``io_s = exposed_io_s + overlapped_io_s`` is total I/O work;
    only ``exposed_io_s`` contributes to wall time (``overlapped_io_s`` hid
    under device compute), and in "dana" mode ``decode_s`` is 0 because the
    decode is fused into the device program (counted in ``compute_s``).
    ``device_syncs`` counts hot-loop host↔device joins (pipelined: one per
    epoch)."""

    models: list[np.ndarray]
    epochs_run: int
    converged: bool
    grad_norms: list[float]
    decode_s: float
    compute_s: float
    io_s: float
    total_s: float
    exposed_io_s: float = 0.0
    overlapped_io_s: float = 0.0
    device_syncs: int = 0
    pipelined: bool = False


def _device_sync(tree):
    """The hot loop's single host↔device join point (tests instrument this)."""
    return jax.block_until_ready(tree)


def _decode_chunk(pages_np, heap, mode, use_kernel=None):
    layout = heap.layout
    if mode == "dana":
        from repro.kernels.strider import ops as strider_ops

        feats, labels, mask = strider_ops.decode_pages(
            jnp.asarray(pages_np), layout, use_kernel
        )
        t = feats.shape[0] * feats.shape[1]
        return (
            feats.reshape(t, layout.n_features),
            labels.reshape(t),
            mask.reshape(t),
        )
    # host decode (the "without striders" CPU data-transformation path)
    fs, ls = [], []
    for p in pages_np:
        f, l, _ = parse_page(p, layout)
        fs.append(f)
        ls.append(l)
    feats = np.concatenate(fs)
    labels = np.concatenate(ls)
    return (
        jnp.asarray(feats),
        jnp.asarray(labels),
        jnp.ones(feats.shape[0], dtype=jnp.float32),
    )


def train_units(
    g: HDFG,
    part: Partition,
    heap: HeapFile,
    pool: BufferPool | None = None,
    mode: str = "dana",
    engine: Engine | None = None,
    max_epochs: int | None = None,
    merge_coef: int | None = None,
    models=None,
    seed: int = 0,
    mesh: jax.sharding.Mesh | None = None,
    shard_model: bool = False,
    use_kernel: bool | None = None,
):
    """Generator form of the pipelined executor: yields once per device chunk
    *dispatch* — the unit the concurrent query executor (``db/executor.py``)
    interleaves TRAIN epochs with PREDICT scans at — and returns the
    TrainResult via ``StopIteration.value``.

    The op sequence — prefetch order, chunk order, ONE device sync per
    epoch, convergence checks on the cached first-chunk batch — is exactly
    ``train(pipelined=True)``'s (which drains this generator), so the
    trained model is byte-identical whether the scan runs alone or
    interleaved with other queries. Timing fields measure this query's wall
    clock; under interleaving, co-scheduled work shows up as compute time
    (results never change, attribution does).

    ``use_kernel`` picks the device datapath: None runs the Pallas strider
    and GLM kernels on TPU and their jnp references elsewhere; False runs
    the references (strider ``ref.py`` decode, vmapped hDFG update) on any
    backend — the plain float32 path the kernels are checked against; True
    forces the strider kernel (interpret mode on CPU)."""
    t_start = time.perf_counter()
    if engine is not None and shard_model and not engine.shard_model:
        # silently training replicated when the caller asked for a
        # partitioned model would be a lie; the flag belongs to make_engine
        raise ValueError(
            "shard_model=True but the pre-built engine was made without it; "
            "pass make_engine(..., shard_model=True)"
        )
    with obs.span("sql.plan"):
        engine = engine or make_engine(
            g, part, merge_coef=merge_coef, mesh=mesh, shard_model=shard_model,
            use_fused_kernel=use_kernel is not False,
        )
        pool = pool or BufferPool(
            pool_bytes=MAX_RESIDENT_PAGES * heap.layout.page_bytes,
            page_bytes=heap.layout.page_bytes,
        )
        models = (
            models
            if models is not None
            else init_models(g, np.random.default_rng(seed), scale=0.01)
        )
        models = [jnp.asarray(m) for m in models]

    epochs = max_epochs or g.epochs or 100
    coef = engine.merge_coef
    grad_norms: list[float] = []
    decode_s = compute_s = 0.0
    exposed_io_s = overlapped_io_s = 0.0
    device_syncs = 0
    converged = False
    epochs_run = 0
    conv_cache: dict = {}  # decoded first-chunk convergence batch, per call

    page_chunks = [
        np.arange(s, min(s + MAX_RESIDENT_PAGES, heap.n_pages))
        for s in range(0, heap.n_pages, MAX_RESIDENT_PAGES)
    ]
    if not page_chunks:
        raise ValueError("train_units needs a non-empty heap (nothing to scan)")

    mesh_ctx = meshes.use_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    with mesh_ctx:
        # -- double-buffered executor: fetch k+1 under compute on k ----------
        handle = pool.prefetch_batch(heap, page_chunks[0])
        try:
            for epoch in range(epochs):
                t_epoch = time.perf_counter()
                exposed_epoch = decode_epoch = 0.0
                gnorm_dev = None
                for k, chunk_ids in enumerate(page_chunks):
                    pages_np, waited, hidden = handle.wait()
                    exposed_epoch += waited
                    overlapped_io_s += hidden
                    # enqueue the next fetch before dispatching compute;
                    # the epoch wrap primes chunk 0 for the next epoch —
                    # unless this is the last one (the convergence check
                    # reuses its cached batch, so it never needs pages)
                    if k + 1 < len(page_chunks) or epoch + 1 < epochs:
                        nxt = page_chunks[(k + 1) % len(page_chunks)]
                        handle = pool.prefetch_batch(heap, nxt)
                    if mode == "dana":
                        # one fused XLA program: strider decode + batch
                        # reshape + epoch scan; no intermediate sync
                        models, gnorms = engine.run_chunk(
                            models, pages_np, heap.layout, use_kernel
                        )
                    else:
                        t1 = time.perf_counter()
                        feats, labels, mask = _decode_chunk(
                            pages_np, heap, mode, use_kernel
                        )
                        decode_epoch += time.perf_counter() - t1
                        X, Y, M = _batches(feats, labels, mask, coef)
                        models, gnorms = engine.run_epoch(models, X, Y, M)
                    gnorm_dev = gnorms[-1]
                    yield  # chunk dispatched — the scheduling point
                models, gnorm_dev = _device_sync((models, gnorm_dev))
                device_syncs += 1
                exposed_io_s += exposed_epoch
                decode_s += decode_epoch
                compute_s += (
                    time.perf_counter() - t_epoch - exposed_epoch - decode_epoch
                )
                grad_norms.append(float(gnorm_dev))
                epochs_run = epoch + 1
                if g.convergence_id is not None:
                    if _check_convergence(
                        engine, models, heap, pool, mode, coef, conv_cache,
                        use_kernel,
                    ):
                        converged = True
                        break
        finally:
            # drain the trailing (speculative) prefetch so the pool is
            # quiescent on return — a generator closed early (cancelled
            # query) cleans up the same way
            handle.drain()
    return TrainResult(
        models=[np.asarray(m) for m in models],
        epochs_run=epochs_run,
        converged=converged,
        grad_norms=grad_norms,
        decode_s=decode_s,
        compute_s=compute_s,
        io_s=exposed_io_s + overlapped_io_s,
        total_s=time.perf_counter() - t_start,
        exposed_io_s=exposed_io_s,
        overlapped_io_s=overlapped_io_s,
        device_syncs=device_syncs,
        pipelined=True,
    )


def run_units(gen):
    """Drive a unit generator (``train_units``, a scan) to its end and
    return what it returns."""
    while True:
        try:
            next(gen)
        except StopIteration as stop:
            return stop.value


def train(
    g: HDFG,
    part: Partition,
    heap: HeapFile,
    pool: BufferPool | None = None,
    mode: str = "dana",
    engine: Engine | None = None,
    max_epochs: int | None = None,
    merge_coef: int | None = None,
    models=None,
    seed: int = 0,
    mesh: jax.sharding.Mesh | None = None,
    shard_model: bool = False,
    pipelined: bool = True,
    use_kernel: bool | None = None,
) -> TrainResult:
    """``mesh`` (or an enclosing ``meshes.use_mesh``) turns on the engine's
    sharded epoch mode: the decoded tuple stream is split over the mesh's
    data axes — parallel Striders feeding one merge tree — via the
    shard_map'ed per-core datapath when eligible (see
    ``Engine.sharded_path``). ``shard_model=True`` additionally partitions
    the model's feature dim (GLM coefficients, LRMF factors) over the mesh's
    model axis, per the logical axes the algorithm declared.

    ``pipelined=True`` (default) drains the ``train_units`` generator — the
    double-buffered executor; ``pipelined=False`` keeps the fully
    synchronous per-chunk loop (the ablation both tests and benchmarks
    compare against). ``use_kernel`` is as in ``train_units``."""
    if pipelined and heap.n_pages > 0:
        return run_units(train_units(
            g, part, heap, pool=pool, mode=mode, engine=engine,
            max_epochs=max_epochs, merge_coef=merge_coef, models=models,
            seed=seed, mesh=mesh, shard_model=shard_model,
            use_kernel=use_kernel,
        ))

    # -- synchronous executor (phases add; the ablation baseline) ------------
    t_start = time.perf_counter()
    if engine is not None and shard_model and not engine.shard_model:
        raise ValueError(
            "shard_model=True but the pre-built engine was made without it; "
            "pass make_engine(..., shard_model=True)"
        )
    engine = engine or make_engine(
        g, part, merge_coef=merge_coef, mesh=mesh, shard_model=shard_model,
        use_fused_kernel=use_kernel is not False,
    )
    pool = pool or BufferPool(
        pool_bytes=MAX_RESIDENT_PAGES * heap.layout.page_bytes,
        page_bytes=heap.layout.page_bytes,
    )
    models = (
        models
        if models is not None
        else init_models(g, np.random.default_rng(seed), scale=0.01)
    )
    models = [jnp.asarray(m) for m in models]

    epochs = max_epochs or g.epochs or 100
    coef = engine.merge_coef
    grad_norms: list[float] = []
    decode_s = io_s = compute_s = 0.0
    exposed_io_s = overlapped_io_s = 0.0
    device_syncs = 0
    converged = False
    epochs_run = 0
    conv_cache: dict = {}  # decoded first-chunk convergence batch, per call

    page_chunks = [
        np.arange(s, min(s + MAX_RESIDENT_PAGES, heap.n_pages))
        for s in range(0, heap.n_pages, MAX_RESIDENT_PAGES)
    ]

    mesh_ctx = meshes.use_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    with mesh_ctx:
        for epoch in range(epochs):
            last_gnorm = None
            for chunk_ids in page_chunks:
                t0 = time.perf_counter()
                pages_np = pool.fetch_batch(heap, chunk_ids)
                t1 = time.perf_counter()
                feats, labels, mask = _decode_chunk(
                    pages_np, heap, mode, use_kernel
                )
                feats.block_until_ready()
                t2 = time.perf_counter()
                X, Y, M = _batches(feats, labels, mask, coef)
                models, gnorms = engine.run_epoch(models, X, Y, M)
                jax.block_until_ready(models)
                device_syncs += 2
                t3 = time.perf_counter()
                io_s += t1 - t0
                decode_s += t2 - t1
                compute_s += t3 - t2
                last_gnorm = float(gnorms[-1])
            grad_norms.append(
                last_gnorm if last_gnorm is not None else float("nan")
            )
            epochs_run = epoch + 1
            if g.convergence_id is not None and last_gnorm is not None:
                # convergence is evaluated once per epoch (paper §4.4) on
                # the cached first-chunk batch
                if _check_convergence(
                    engine, models, heap, pool, mode, coef, conv_cache,
                    use_kernel,
                ):
                    converged = True
                    break
        exposed_io_s = io_s
    total_s = time.perf_counter() - t_start
    return TrainResult(
        models=[np.asarray(m) for m in models],
        epochs_run=epochs_run,
        converged=converged,
        grad_norms=grad_norms,
        decode_s=decode_s,
        compute_s=compute_s,
        io_s=io_s,
        total_s=total_s,
        exposed_io_s=exposed_io_s,
        overlapped_io_s=overlapped_io_s,
        device_syncs=device_syncs,
        pipelined=pipelined,
    )


def _convergence_batch(engine, heap, pool, mode, coef, cache, use_kernel):
    """Decode the first-chunk convergence batch once per train() call; every
    epoch's terminator check reuses the cached device arrays instead of
    refetching and re-decoding pages."""
    batch = cache.get("batch")
    if batch is None:
        ids = np.arange(min(heap.n_pages, 4))
        pages_np = pool.fetch_batch(heap, ids)
        feats, labels, mask = _decode_chunk(pages_np, heap, mode, use_kernel)
        X, Y, M = _batches(feats, labels, mask, coef)
        batch = cache["batch"] = (X[0], Y[0], M[0])
    return batch


def _check_convergence(engine, models, heap, pool, mode, coef, cache,
                       use_kernel=None) -> bool:
    """Evaluate the terminator on a fresh merged value from the first batch."""
    x0, y0, m0 = _convergence_batch(engine, heap, pool, mode, coef, cache,
                                    use_kernel)
    _, merged = engine.batch_step(models, x0, y0, m0)
    return engine.converged(models, merged)


# ---------------------------------------------------------------------------
def madlib_train(
    g: HDFG,
    part: Partition,
    heap: HeapFile,
    max_epochs: int | None = None,
    models=None,
    seed: int = 0,
    batch: int | None = None,
) -> TrainResult:
    """MADlib+PostgreSQL analogue: tuple-at-a-time host execution. Pages are
    parsed tuple by tuple on the host and the update rule runs per mini-batch
    with numpy — no device, no page-granular decode."""
    from repro.baselines.madlib import run as madlib_run

    return madlib_run(g, part, heap, max_epochs=max_epochs, models=models, seed=seed,
                      batch=batch)
