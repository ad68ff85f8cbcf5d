"""PREDICT executor: SQL-driven batch scoring through the strider path.

This is the paper's strider→engine handoff closed end to end for inference:
a scoring query streams the table's heap pages through the *projected* fused
strider-decode program (`kernels/strider/ops.decode_pages_projected_traced`)
directly into batched model evaluation — per chunk, ONE device program runs
page decode + WHERE filter + model scoring, so decoded tuples never bounce
through the host between the access engine and the execution engine.

Pushdown is compiled, not simulated: the query's projection, filter, and
aggregate columns (plus the model's input columns) define a ProjectionPlan,
and both the Strider ISA program and the Pallas/jnp decode kernels restrict
themselves to those payload words — dropped columns are never read off the
page, and :class:`PushdownStats` carries the static byte/cycle accounting
that proves it (cross-checked against the ISA interpreter's FIFO in tests).
WHERE clauses are arbitrary AND/OR/NOT predicate trees (``db/query.py``);
the whole tree evaluates inside the same jitted chunk program, composing
into the one keep-mask — no extra decode passes. Filtered tuples are masked
out of the engine (GLM: the keep-mask rides the same lane mask the training
kernel uses) or never submitted at all (LM: filtered rows never reach the
BatchedServer).

Aggregate queries (COUNT/SUM/AVG over columns, ``label``, or the model's
``prediction``) reduce per chunk ON DEVICE: the chunk program returns only a
partial (sums, count) pair, partials carry across chunks, and the host
combines them in float32 after the scan's single sync — result pages are
never materialized and per-row predictions never cross the memory boundary.

Model families:
  GLM (linear / logistic / svm)  structural template match on the UDF's hDFG
      (core.engine.match_glm_template); scores via the engine's row-parallel
      predict kernel. The model reads the FIRST d feature columns of the
      scoring table (schema-prefix convention) — wider tables are exactly
      where projection pushdown pays.
  LRMF  single 2-D model (n_items, rank); the prediction is the per-row
      reconstruction error ||x - (xM)Mᵀ|| of the rating row.
  LM    artifacts registered via register_lm_udf; prompts decode from token
      tables (heap.write_token_table) through the same strider path, then a
      short-lived BatchedServer session generates (continuous batching).

Row-returning results flow back as result pages — the projected schema with
a `prediction` column appended, packed by the same page builder the heap
uses — so a scoring query's output composes with the rest of the db/ layer:
``INSERT INTO t SELECT ...`` (or ``into=``) registers it as a catalog table,
rejecting a name collision unless ``OR REPLACE`` is given. Mixed train+score
workloads share one BufferPool; I/O accounting follows the pipelined
executor's exposed-vs-overlapped contract (what the loop blocked on vs what
hid under device compute).

:class:`PredictScan` is the prepared form of a GLM/LRMF statement — plan,
jitted chunk program, page chunk list, finalizer. ``execute_predict`` drives
it through the double-buffered `_scan_chunks` loop; the concurrent executor
(``db/executor.py``) steps the same scan one chunk per scheduling unit.
"""
from __future__ import annotations

import dataclasses
import os
import re
import time

import numpy as np

from repro import obs
from repro.core import striders
from repro.core.solver import run_units
from repro.db.bufferpool import BufferPool
from repro.db.heap import HeapFile, write_table, write_token_table
from repro.db.page import PageLayout

CHUNK_PAGES = 512  # pages decoded per device chunk (matches solver's)


@dataclasses.dataclass(frozen=True)
class PushdownStats:
    """Static pushdown bookkeeping for one PREDICT query.

    ``bytes_decoded`` is what the projected strider streams off the pages
    (``n_tuples * plan.bytes_per_tuple``); ``bytes_full_decode`` is what a
    full decode of the same rows would have streamed. ``strider_cycles`` is
    the access-engine cycle model (hwgen's) summed over the scan, assuming
    full pages. Tests cross-check both against the ISA interpreter's actual
    FIFO length / cycle count on real pages.
    """

    columns_decoded: tuple[int, ...]
    n_columns_total: int
    include_label: bool
    bytes_per_tuple: int
    bytes_per_tuple_full: int
    bytes_decoded: int
    bytes_full_decode: int
    strider_cycles: int
    strider_cycles_full: int

    @property
    def decode_bytes_ratio(self) -> float:
        """full-decode bytes / projected bytes (>= 1; the pushdown win)."""
        return self.bytes_full_decode / max(self.bytes_decoded, 1)


def _pushdown_stats(heap: HeapFile, plan: striders.ProjectionPlan) -> PushdownStats:
    layout = heap.layout
    n = heap.n_tuples
    return PushdownStats(
        columns_decoded=plan.columns,
        n_columns_total=layout.n_features,
        include_label=plan.include_label,
        bytes_per_tuple=plan.bytes_per_tuple,
        bytes_per_tuple_full=plan.bytes_per_tuple_full,
        bytes_decoded=n * plan.bytes_per_tuple,
        bytes_full_decode=n * plan.bytes_per_tuple_full,
        strider_cycles=heap.n_pages * striders.strider_cycles_per_page(layout, plan),
        strider_cycles_full=heap.n_pages * striders.strider_cycles_per_page(layout),
    )


def _column_index(name: str, layout: PageLayout) -> int | None:
    """'c<i>' -> feature index (validated), 'label' -> None."""
    if name == "label":
        return None
    m = re.match(r"^c(\d+)$", name)
    if not m:
        raise ValueError(f"unknown column {name!r}")
    idx = int(m.group(1))
    if idx >= layout.n_features:
        raise ValueError(
            f"column {name!r} out of range: table has {layout.n_features} "
            f"feature columns (c0..c{layout.n_features - 1})"
        )
    return idx


def _glm_family(artifact: dict, udf: str) -> str:
    """Map a UDF artifact to a scorable family: linear/logistic/svm/lrmf."""
    from repro.core.engine import match_glm_template

    g, part = artifact["hdfg"], artifact["partition"]
    act = match_glm_template(g, part)
    if act is not None:
        return act
    if len(g.model_ids) == 1 and len(g.node(g.model_ids[0]).shape) == 2:
        return "lrmf"  # single 2-D factor model: reconstruction-error scoring
    raise ValueError(
        f"UDF {udf!r} does not match a scorable template "
        f"(GLM gradient or 2-D factor model)"
    )


def _scoring_model(artifact: dict, udf: str) -> np.ndarray:
    if "model" not in artifact:
        raise ValueError(
            f"UDF {udf!r} has no trained model; run the TRAIN query "
            f"(SELECT * FROM dana.{udf}('<table>')) first"
        )
    if "strider_program" not in artifact or "design_point" not in artifact:
        raise ValueError(
            f"UDF {udf!r} was registered without a page layout — no strider "
            f"program / design point was compiled; re-register with "
            f"register_udf_from_trace(..., layout=heap.layout)"
        )
    return np.asarray(artifact["model"][0])


def _build_glm_chunk_fn(layout, plan, family, model, where, where_pos,
                        use_kernel, proj_pos, aggregates=None, agg_pos=None):
    """One fused device program per chunk: projected strider decode + WHERE
    keep-mask (the whole predicate tree evaluates traced) + model scoring.

    Row mode returns (preds, keep, columns) device arrays flattened over
    tuples; ``columns`` holds only the SELECT list (``proj_pos``: plan
    positions, None for the label), not every decoded model column.
    Aggregate mode returns only (partial_sums, kept_count) —
    one f32 scalar per aggregate plus a count, reduced on device; XLA
    dead-code-eliminates the scoring math when no aggregate reads
    ``prediction``. Nothing syncs until the caller joins.
    """
    import jax
    import jax.numpy as jnp

    from repro.dist.meshes import current_mesh
    from repro.kernels.engine import ops as engine_ops
    from repro.kernels.strider import ops as strider_ops

    dm = model.shape[0]
    model_pos = jnp.asarray(
        [plan.columns.index(c) for c in range(dm)], dtype=jnp.int32
    )
    w = jnp.asarray(model, dtype=jnp.float32)

    @jax.jit
    def run(pages):
        # scoring has no mesh of its own: it decodes on the use_mesh one
        feats, labels, mask = strider_ops.decode_pages_projected_traced(
            pages, layout, plan, use_kernel, mesh=current_mesh()
        )
        p, t, c = feats.shape
        f2 = feats.reshape(p * t, c)
        lab = labels.reshape(p * t)
        keep = mask.reshape(p * t) > 0
        if where is not None:
            def lookup(name):
                pos = where_pos[name]
                return lab if pos is None else f2[:, pos]

            keep = keep & where.evaluate(lookup)
        x = jnp.take(f2, model_pos, axis=1)
        if family == "lrmf":
            # prediction = per-row reconstruction error ||x - (xM)Mᵀ||
            recon = (x @ w) @ w.T
            d = jnp.where(keep[:, None], x - recon, 0.0)
            preds = jnp.sqrt(jnp.sum(d * d, axis=1))
        else:
            preds = engine_ops.glm_predict_traced(
                x, w, keep.astype(jnp.float32), act=family,
                use_kernel=use_kernel,
            )
        if aggregates is not None:
            sums = []
            for a in aggregates:
                if a.arg is None:  # COUNT(*) — the count output covers it
                    sums.append(jnp.float32(0.0))
                    continue
                if a.arg == "prediction":
                    val = preds
                elif a.arg == "label":
                    val = lab
                else:
                    val = f2[:, agg_pos[a.arg]]
                sums.append(
                    jnp.sum(jnp.where(keep, val.astype(jnp.float32), 0.0))
                )
            return jnp.stack(sums), jnp.sum(keep.astype(jnp.int32))
        cols = jnp.stack([lab if pos is None else f2[:, pos]
                          for pos in proj_pos], axis=1)
        return preds, keep, cols

    return run


def _scan_chunks(heap, pool, chunk_pages, run_chunk):
    """Double-buffered page scan as a unit generator: fetch chunk k+1 on the
    pool's background thread while the device runs chunk k, yielding once
    chunk k is dispatched; ONE host↔device join at the end. Returns (as
    the generator's value) (chunk outputs, exposed_io_s, overlapped_io_s,
    compute_s)."""
    import jax

    page_chunks = [
        np.arange(s, min(s + chunk_pages, heap.n_pages))
        for s in range(0, heap.n_pages, chunk_pages)
    ]
    outs = []
    exposed = overlapped = 0.0
    t0 = time.perf_counter()
    if page_chunks:
        handle = pool.prefetch_batch(heap, page_chunks[0])
        try:
            for k in range(len(page_chunks)):
                pages_np, waited, hidden = handle.wait()
                exposed += waited
                overlapped += hidden
                if k + 1 < len(page_chunks):
                    handle = pool.prefetch_batch(heap, page_chunks[k + 1])
                outs.append(run_chunk(pages_np))
                yield  # chunk dispatched — the scheduling point
        finally:
            # leave the pool quiescent even when a chunk blows up mid-scan
            # or the scan is closed early (a cancelled query)
            handle.drain()
        jax.block_until_ready(outs)
    compute = time.perf_counter() - t0 - exposed
    return outs, exposed, overlapped, compute


def combine_aggregates(aggregates, outs) -> tuple[dict, int]:
    """Host-side combine of per-chunk device partials -> (values, count).

    Accumulates in np.float32 — the same IEEE f32 adds the device would do —
    so a multi-chunk scan is bit-exact against an oracle performing the same
    per-chunk combine. AVG over zero kept rows is NaN (SQL would say NULL).
    """
    total = np.zeros(len(aggregates), np.float32)
    count = 0
    for sums, cnt in outs:
        total = (total + np.asarray(sums, np.float32)).astype(np.float32)
        count += int(cnt)
    values: dict = {}
    for i, a in enumerate(aggregates):
        if a.func == "COUNT":
            values[a.label] = count
        elif a.func == "SUM":
            values[a.label] = float(total[i])
        else:  # AVG — one f32 divide, matching what the device would emit
            values[a.label] = (
                float(np.float32(total[i]) / np.float32(count))
                if count else float("nan")
            )
    return values, count


class PredictScan:
    """A prepared GLM/LRMF PREDICT statement: resolved artifacts, projection
    plan, the jitted chunk program, and the finalizer that turns collected
    chunk outputs into a QueryResult.

    Two drivers share this: ``execute_predict`` runs the whole scan through
    the double-buffered ``_scan_chunks`` loop (one device sync), and the
    concurrent executor (``db/executor.py``) steps the same loop one chunk
    per scheduling unit, so PREDICT scans interleave with TRAIN epochs over
    the shared pool without changing per-query results.
    """

    def __init__(self, stmt, catalog, pool=None, *, use_kernel=None,
                 chunk_pages=None, into=None, or_replace=False):
        with obs.span("sql.plan"):
            self.stmt = stmt
            self.catalog = catalog
            self.into = into
            self.or_replace = or_replace
            self.artifact = catalog.udf(stmt.udf)
            if self.artifact.get("kind") == "lm":
                raise ValueError(
                    f"UDF {stmt.udf!r} is a language model; PredictScan covers "
                    f"GLM/LRMF scoring (the LM path runs a serving session)"
                )
            self.heap = HeapFile(catalog.table(stmt.table)["heap"])
            layout = self.layout = self.heap.layout
            self.chunk = chunk_pages or CHUNK_PAGES
            self.pool = pool or BufferPool(
                pool_bytes=self.chunk * layout.page_bytes,
                page_bytes=layout.page_bytes,
            )

            family = self.family = _glm_family(self.artifact, stmt.udf)
            model = self.model = _scoring_model(self.artifact, stmt.udf)
            dm = model.shape[0]
            if dm > layout.n_features:
                raise ValueError(
                    f"UDF {stmt.udf!r} reads {dm} feature columns but table "
                    f"{stmt.table!r} has only {layout.n_features}"
                )
            if self.into is not None and stmt.aggregates is not None:
                raise ValueError(
                    "aggregate queries reduce on device and never materialize "
                    "result pages; they cannot be INSERTed into a table"
                )

            # ---- pushdown plan: model ∪ projection ∪ filter ∪ aggregate cols ---
            if stmt.aggregates is not None:
                proj_names: list[str] = []  # reductions project no row columns
            elif stmt.columns is None:
                proj_names = [f"c{i}" for i in range(layout.n_features)] + ["label"]
            else:
                proj_names = list(stmt.columns)
            self.proj_names = proj_names
            proj_idx = self.proj_idx = [
                _column_index(n, layout) for n in proj_names
            ]
            include_label = None in proj_idx
            decode_cols = set(range(dm)) | {i for i in proj_idx if i is not None}
            where_map: dict[str, int | None] = {}
            if stmt.where is not None:
                for name in stmt.where.columns():
                    where_map[name] = _column_index(name, layout)
                include_label = include_label or None in where_map.values()
                decode_cols |= {i for i in where_map.values() if i is not None}
            agg_map: dict[str, int | None] = {}
            for a in stmt.aggregates or ():
                if a.arg is None or a.arg == "prediction":
                    continue
                agg_map[a.arg] = _column_index(a.arg, layout)
                include_label = include_label or agg_map[a.arg] is None
                if agg_map[a.arg] is not None:
                    decode_cols.add(agg_map[a.arg])
            plan = self.plan = striders.projection_plan(
                layout, decode_cols, include_label=bool(include_label)
            )
            self.pushdown = _pushdown_stats(self.heap, plan)

            # plan positions (not table indices) for the traced tree/aggregates
            where_pos = {
                name: (None if idx is None else plan.columns.index(idx))
                for name, idx in where_map.items()
            }
            agg_pos = {
                name: plan.columns.index(idx)
                for name, idx in agg_map.items() if idx is not None
            }
            self.run_chunk = _build_glm_chunk_fn(
                layout, plan, family, model, stmt.where, where_pos, use_kernel,
                [None if idx is None else plan.columns.index(idx)
                 for idx in proj_idx],
                aggregates=stmt.aggregates, agg_pos=agg_pos,
            )

    # -- finalization --------------------------------------------------------
    def finalize(self, outs, exposed, overlapped, compute, t_start):
        """Collected chunk outputs (post-sync) -> QueryResult, in a
        ``scan.finalize`` span counting the bytes of the outputs copied to
        the host and the result's rows."""
        nbytes = sum(x.nbytes for o in outs for x in o)
        with obs.span("scan.finalize", bytes=nbytes) as rec:
            res = self._result(outs, exposed, overlapped, compute, t_start)
            rec.rows = res.n_rows
        return res

    def _result(self, outs, exposed, overlapped, compute, t_start):
        from repro.db import query as q

        stmt, heap = self.stmt, self.heap
        if stmt.aggregates is not None:
            values, count = combine_aggregates(stmt.aggregates, outs)
            return q.QueryResult(
                verb="PREDICT",
                udf=stmt.udf,
                table=stmt.table,
                schema=tuple(a.label for a in stmt.aggregates),
                n_rows=1,
                rows_scanned=heap.n_tuples,
                rows_filtered=heap.n_tuples - count,
                total_s=time.perf_counter() - t_start,
                exposed_io_s=exposed,
                overlapped_io_s=overlapped,
                compute_s=compute,
                device_syncs=1,
                pushdown=self.pushdown,
                aggregates=values,
            )

        # ---- host-side result assembly (dynamic row count) -----------------
        if outs:
            preds = np.concatenate([np.asarray(o[0]) for o in outs])
            keep = np.concatenate([np.asarray(o[1]) for o in outs])
            cols = np.concatenate([np.asarray(o[2]) for o in outs])
        else:
            preds = np.zeros(0, np.float32)
            keep = np.zeros(0, bool)
            cols = np.zeros((0, len(self.proj_idx)), np.float32)
        preds, result_feats = preds[keep], cols[keep].astype(np.float32)
        n_kept = int(keep.sum())
        schema = tuple(self.proj_names) + ("prediction",)
        result_layout = PageLayout(
            n_features=len(self.proj_names), page_bytes=self.layout.page_bytes,
            quantized=False,
        )
        if n_kept:
            from repro.db.page import build_pages

            result_pages = build_pages(result_feats, preds, result_layout)
        else:
            result_pages = np.zeros((0, result_layout.page_words), np.uint32)

        if self.into is not None:
            catalog = self.catalog
            if not self.or_replace and catalog.has_table(self.into):
                # refuse BEFORE touching the heap file: the colliding name
                # may own that very path, and a clobbered heap is data loss
                raise ValueError(
                    f"catalog: table {self.into!r} already exists; use "
                    f"INSERT OR REPLACE INTO (or or_replace=True) to "
                    f"overwrite"
                )
            path = os.path.join(catalog.root, f"{self.into}.heap")
            if n_kept:
                write_table(path, result_feats, preds,
                            page_bytes=self.layout.page_bytes)
            else:
                _write_empty_table(path, result_layout)
            catalog.register_table(
                self.into, path,
                {"n_features": len(self.proj_names), "columns": list(schema)},
                or_replace=self.or_replace,
            )

        return q.QueryResult(
            verb="PREDICT",
            udf=stmt.udf,
            table=stmt.table,
            schema=schema,
            n_rows=n_kept,
            predictions=preds,
            rows_scanned=heap.n_tuples,
            rows_filtered=heap.n_tuples - n_kept,
            total_s=time.perf_counter() - t_start,
            exposed_io_s=exposed,
            overlapped_io_s=overlapped,
            compute_s=compute,
            device_syncs=1,
            pushdown=self.pushdown,
            result_pages=result_pages,
            result_layout=result_layout,
        )


def execute_predict(
    stmt,
    catalog,
    pool: BufferPool | None = None,
    *,
    use_kernel: bool | None = None,
    chunk_pages: int | None = None,
    max_new_tokens: int = 32,
    batch_slots: int | None = None,
    into: str | None = None,
    or_replace: bool = False,
):
    """Run a parsed PREDICT statement; returns a query.QueryResult.

    ``into=`` additionally materializes the result pages as a heap table
    registered in the catalog under that name (token table for LM UDFs), so
    a scoring query's output is itself queryable — an existing name is
    rejected unless ``or_replace``.
    """
    t_start = time.perf_counter()
    artifact = catalog.udf(stmt.udf)

    if artifact.get("kind") == "lm":
        heap = HeapFile(catalog.table(stmt.table)["heap"])
        layout = heap.layout
        chunk = chunk_pages or CHUNK_PAGES
        pool = pool or BufferPool(
            pool_bytes=chunk * layout.page_bytes, page_bytes=layout.page_bytes
        )
        if stmt.aggregates is not None:
            raise ValueError(
                "aggregates apply to GLM/LRMF scoring queries; LM PREDICT "
                "returns generated token sequences"
            )
        return _predict_lm(
            stmt, catalog, artifact, heap, pool, chunk, t_start,
            use_kernel=use_kernel, max_new_tokens=max_new_tokens,
            batch_slots=batch_slots, into=into, or_replace=or_replace,
        )

    scan = PredictScan(
        stmt, catalog, pool, use_kernel=use_kernel, chunk_pages=chunk_pages,
        into=into, or_replace=or_replace,
    )
    outs, exposed, overlapped, compute = run_units(_scan_chunks(
        scan.heap, scan.pool, scan.chunk, scan.run_chunk
    ))
    return scan.finalize(outs, exposed, overlapped, compute, t_start)


def _write_empty_table(path: str, layout: PageLayout) -> None:
    """Materialize a zero-row table (a filter can legitimately drop all)."""
    write_table(
        path,
        np.zeros((0, layout.n_features), np.float32),
        np.zeros(0, np.float32),
        page_bytes=layout.page_bytes,
    )


def _predict_lm(stmt, catalog, artifact, heap, pool, chunk, t_start, *,
                use_kernel, max_new_tokens, batch_slots, into, or_replace):
    """LM PREDICT: decode prompts from a token table via the strider path,
    filter, generate on a short-lived continuous-batching session.

    Filtered rows genuinely never reach the server — the predicate tree runs
    on the decoded tuple stream before any request is submitted. Token
    columns compare as int token ids (the strider streams raw words; the
    query layer reinterprets), ``label`` compares as the stored prompt
    length.
    """
    import jax

    from repro.db import query as q
    from repro.dist.meshes import current_mesh
    from repro.kernels.strider import ops as strider_ops
    from repro.serve.serving import score_tokens

    layout = heap.layout
    if stmt.columns is not None:
        raise ValueError("LM PREDICT supports SELECT * only (token tables)")

    plan = striders.full_plan(layout)  # generation reads every token column
    pushdown = _pushdown_stats(heap, plan)

    @jax.jit
    def run(pages):
        return strider_ops.decode_pages_projected_traced(
            pages, layout, plan, use_kernel, mesh=current_mesh()
        )

    outs, exposed, overlapped, compute = run_units(
        _scan_chunks(heap, pool, chunk, run))
    if outs:
        feats = np.concatenate([np.asarray(o[0]) for o in outs])
        labels = np.concatenate([np.asarray(o[1]) for o in outs])
        mask = np.concatenate([np.asarray(o[2]) for o in outs])
    else:
        feats = np.zeros((0, 0, layout.n_features), np.float32)
        labels = np.zeros((0, 0), np.float32)
        mask = np.zeros((0, 0), np.float32)
    tokens = (
        np.ascontiguousarray(feats).view(np.int32).reshape(-1, layout.n_features)
    )
    lengths = labels.reshape(-1).astype(np.int32)
    live = mask.reshape(-1) > 0

    keep = live.copy()
    if stmt.where is not None:
        idx_map = {
            name: _column_index(name, layout)
            for name in stmt.where.columns()
        }

        def lookup(name):
            idx = idx_map[name]
            return lengths if idx is None else tokens[:, idx]

        keep &= np.asarray(stmt.where.evaluate(lookup))

    prompts = [
        tokens[i, : lengths[i]].tolist() for i in np.flatnonzero(keep)
    ]
    gen, metrics = score_tokens(
        artifact["cfg"], artifact["params"], prompts,
        max_new_tokens=max_new_tokens, batch_slots=batch_slots,
    )

    if into is not None:
        if not or_replace and catalog.has_table(into):
            raise ValueError(
                f"catalog: table {into!r} already exists; use "
                f"INSERT OR REPLACE INTO (or or_replace=True) to overwrite"
            )
        path = os.path.join(catalog.root, f"{into}.heap")
        if gen:
            write_token_table(path, gen, page_bytes=layout.page_bytes)
            catalog.register_table(
                into, path,
                {"n_features": max(len(g) for g in gen), "kind": "tokens"},
                or_replace=or_replace,
            )
        # zero-row LM results have no width to materialize; skip registration

    return q.QueryResult(
        verb="PREDICT",
        udf=stmt.udf,
        table=stmt.table,
        schema=("prediction",),
        n_rows=len(gen),
        predictions=gen,
        rows_scanned=heap.n_tuples,
        rows_filtered=int(live.sum()) - len(gen),
        total_s=time.perf_counter() - t_start,
        exposed_io_s=exposed,
        overlapped_io_s=overlapped,
        compute_s=compute,
        device_syncs=1,
        pushdown=pushdown,
        serve_metrics=metrics,
    )
