"""The in-database analytics path as the benchmark drives it: ``Session.sql``
statements over one heap table, through the buffer pool, the strider decode
and the engine.

Set-up opens the configuration's table (drawn once a checkout from its
``data_seed`` and written with the program's heap writer into
``bench/data/``), registers the UDF with the model a PREDICT scores (the
deployment's stored model, drawn from ``model_seed``), and runs the mix's
statements over a small table of the same chunk shapes, so the window's
programs come from the compile cache. ``--seed`` draws what the statements
are given: a TRAIN's initial coefficients and where the cycle starts.
The window issues the mix's statements back to back and closes at the end
of the first whole cycle of them after ``--seconds``. The check draws the
data again on the device and runs the plain reference
(``bench/configs/<ref>.py``) for every statement of the window.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time

import numpy as np

from bench import sqlref, traffic


def _table_dir(run) -> str:
    cfg = run.config
    sig = json.dumps({k: cfg[k] for k in ("n_features", "n_tuples",
                                          "page_bytes", "quantized",
                                          "data_seed")},
                     sort_keys=True)
    tag = hashlib.sha256(sig.encode()).hexdigest()[:12]
    base = run.data_dir()
    # one table per configuration: drop tables of earlier sizes or seeds
    for old in os.listdir(base):
        if old.startswith(cfg["name"] + "-") and not old.endswith(tag):
            shutil.rmtree(os.path.join(base, old), ignore_errors=True)
    return run.data_dir(f"{cfg['name']}-{tag}")


def _udf(cfg):
    import repro.algorithms as algorithms

    u = cfg["udf"]
    fn = getattr(algorithms, u["function"])
    return lambda: fn(cfg["n_features"], lr=u["lr"], merge_coef=u["merge_coef"],
                      epochs=u["epochs"])


def _make_table(run, path: str) -> None:
    """Draw the table and write it as a heap; it persists in ``bench/data``
    for the checkout's later runs."""
    from repro.db.heap import write_table

    cfg = run.config
    t = time.perf_counter()
    x, y = run.ref.generate(cfg)
    xh, yh = np.asarray(x), np.asarray(y)
    del x, y
    write_table(path + ".heap", xh, yh, page_bytes=cfg["page_bytes"],
                quantized=cfg["quantized"])
    run.note(f"table {cfg['table']}: drawn and written in "
             f"{time.perf_counter() - t:.3f} s")


def _warm_table(run, path: str, layout) -> str:
    """A small table of the same layout whose scan runs the real table's
    chunk shapes (one full chunk and the last, shorter one); written once a
    checkout."""
    from repro.db.heap import write_table

    cfg = run.config
    chunk = cfg["device_resident_pages"]
    n_pages = -(-cfg["n_tuples"] // layout.tuples_per_page)
    last = n_pages - (n_pages - 1) // chunk * chunk
    pages = min(chunk, n_pages) + (last if last != chunk else 0)
    warm = path + ".warm.heap"
    if not os.path.exists(warm + ".meta"):
        x, y = run.ref.generate(cfg, n=pages * layout.tuples_per_page)
        write_table(warm, np.asarray(x), np.asarray(y),
                    page_bytes=cfg["page_bytes"], quantized=cfg["quantized"])
    return warm


def setup(run) -> None:
    from repro.db import connect
    from repro.db.heap import HeapFile
    from repro.db.query import register_udf_from_trace

    cfg, mix = run.config, run.traffic
    tdir = _table_dir(run)
    path = os.path.join(tdir, cfg["table"])
    if not os.path.exists(path + ".heap.meta"):
        _make_table(run, path)
    heap = HeapFile(path + ".heap")
    if heap.layout.tuples_per_page != cfg["tuples_per_page"]:
        raise ValueError(f"table holds {heap.layout.tuples_per_page} tuples a "
                         f"page, the configuration states "
                         f"{cfg['tuples_per_page']}")
    cat_dir = run.data_dir("catalog", run.cell["name"])
    shutil.rmtree(cat_dir, ignore_errors=True)
    sess = connect(cat_dir, page_bytes=heap.layout.page_bytes)
    schema = {"n_features": cfg["n_features"]}
    sess.catalog.register_table(cfg["table"], heap.path, schema)
    warm = cfg["table"] + "_warm"
    sess.catalog.register_table(warm, _warm_table(run, path, heap.layout),
                                schema)
    udf_name = cfg["udf"]["name"]
    art = register_udf_from_trace(sess.catalog, udf_name, _udf(cfg),
                                  layout=heap.layout)
    fill = {"table": cfg["table"], "udf": udf_name}
    run.state.update(sess=sess, heap=heap, fill=fill)
    model = [run.ref.score_model(cfg["model_seed"], cfg["n_features"])]
    art["model"] = model
    sess.catalog.register_udf(udf_name, art)

    # warm every program the mix's statements run, through the window's own
    # loop (a program traced from another call path can miss the compile
    # cache): over the small table (same chunk shapes), and where that still
    # compiled (a cold cache), once over the real table too, so that nothing
    # compiles in the window
    c0 = run.compiles.compiled
    for full in (False, True):
        table = cfg["table"] if full else warm
        texts = [t.format(**dict(fill, table=table)) for t in mix["statements"]]
        _run_mix(run, [(k, t, 0) for k, t in enumerate(texts)])
        run.records.clear()
        run.attempted = run.failed = 0
        if run.compiles.compiled == c0:
            break
        if not full:
            run.note(f"cold compile cache: {run.compiles.compiled - c0} "
                     f"programs compiled in warm-up; warming over the real "
                     f"table too")
    art["model"] = model  # a TRAIN in the warm-up stored its own
    sess.catalog.register_udf(udf_name, art)


def run_statement(run, k: int, text: str, st_seed: int) -> dict:
    """Execute one statement through ``Session.sql`` and record it."""
    import jax

    verb = sqlref.parse_select(text)["verb"]
    rec = {"k": k, "sql": text, "verb": verb, "seed": st_seed,
           "t0": time.perf_counter()}
    run.attempted += 1
    try:
        with jax.profiler.TraceAnnotation("Session.sql"):
            res = run.state["sess"].sql(text, seed=st_seed)
    except Exception as e:  # a failed statement is counted, not fatal
        run.failed += 1
        rec.update(t1=time.perf_counter(), error=repr(e), tuples=0)
        run.note(f"statement {k} failed: {e!r}")
    else:
        rec["t1"] = time.perf_counter()
        rec["exposed_io_s"] = float(res.exposed_io_s)
        rec["overlapped_io_s"] = float(res.overlapped_io_s)
        if verb == "TRAIN":
            rec["epochs"] = int(res.train.epochs_run)
            rec["tuples"] = rec["epochs"] * int(res.rows_scanned)
            rec["w"] = np.asarray(res.coefficients[0])
            rec["grad_norms"] = list(res.train.grad_norms)
        else:
            rec["tuples"] = int(res.rows_scanned)
            rec["n_rows"] = int(res.n_rows)
            rec["aggregates"] = res.aggregates
            if res.predictions is not None:
                rec["predictions"] = np.asarray(res.predictions)
            if res.result_pages is not None:  # the selected rows as pages
                rec["result_pages"] = np.asarray(res.result_pages)
    run.records.append(rec)
    return rec


def _run_mix(run, stmts, until: float | None = None) -> None:
    """Run ``(k, sql, seed)`` statements one after another; with ``until``,
    stop at the first end of a whole cycle of the mix's statements past it,
    so that every window holds each statement of the mix equally often."""
    cycle = len(run.traffic["statements"])
    for k, text, st_seed in stmts:
        rec = run_statement(run, k, text, st_seed)
        if until is not None and rec["t1"] >= until and (k + 1) % cycle == 0:
            break


def window(run, tracer) -> None:
    stmts = traffic.statements(run.traffic, run.seed, run.state["fill"])
    if tracer is not None:
        tracer.start()
    _run_mix(run, stmts, until=run.window_t0 + run.seconds)
    run.window_t1 = time.perf_counter()
    if tracer is not None:
        tracer.stop()
    run.counters.update(
        tuples=sum(r["tuples"] for r in run.records),
        exposed_io_s=sum(r.get("exposed_io_s", 0.0) for r in run.records),
    )
    run.note(f"{len(run.records)} statements, {run.counters['tuples']} "
             f"tuples, exposed I/O {run.counters['exposed_io_s']:.3f} s")


def release(run) -> None:
    sess = run.state.pop("sess", None)
    if sess is not None:
        sess.close()


# -- the check ----------------------------------------------------------------
LIMITS = {
    # each between the program's largest reading over a dozen seeds or more
    # and the control's smallest, on the chip (PERF.md gives the readings);
    # rows, the selected columns and COUNT are exact; the AVG limit is the
    # configuration's stated guarantee
    "train_w_rel": 1e-6,
    "train_gnorm_rel": 1e-6,
    "rows": 0.0,
    "proj_mismatch": 0.0,
    "pred_abs": 1.5e-6,
    "count": 0.0,
    "avg_rel": 1e-5,
}


def readings(run, control: str | None = None) -> dict:
    """The numbers the check compares, for every statement of the window:
    the program's answers against the float32 reference. With ``control``
    (a precision of the reference's ``dot``, ``"bf16x3"``) the reference at
    that precision answers in the program's place, on the same
    statements."""
    import jax

    cfg, ref = run.config, run.ref
    u = cfg["udf"]
    x, y = ref.generate(cfg)
    out = {}

    def worst(name, v):
        out[name] = max(out.get(name, 0.0), float(v))

    preds = None
    for rec in run.records:
        if "error" in rec:
            continue
        if rec["verb"] == "TRAIN":
            w0 = ref.init_model(rec["seed"], cfg["n_features"])
            w_ref, gn_ref = (np.asarray(a) for a in ref.train(
                x, y, w0, u["lr"], u["merge_coef"], rec["epochs"]))
            if control:
                w, gn = (np.asarray(a) for a in ref.train(
                    x, y, w0, u["lr"], u["merge_coef"], rec["epochs"],
                    precision=control))
            else:
                w, gn = rec["w"], np.asarray(rec["grad_norms"])
            worst("train_w_rel", np.max(np.abs(w - w_ref)) / np.max(np.abs(w_ref)))
            worst("train_gnorm_rel", np.max(np.abs(gn - gn_ref) / gn_ref))
            continue
        if preds is None:
            w_scan = ref.score_model(cfg["model_seed"], cfg["n_features"])
            preds = np.asarray(ref.predict(x, w_scan))
            if control:
                preds_ctl = np.asarray(ref.predict(x, w_scan, precision=control))
        st = sqlref.parse_select(rec["sql"])
        keep = np.ones(cfg["n_tuples"], bool)
        if st["where"]:
            keep = np.asarray(sqlref.where_mask(
                st["where"], lambda c: x[:, int(c[1:])]))
        n_ref = int(keep.sum())
        if st["aggregates"]:
            avg_ref = float(np.mean(preds[keep].astype(np.float64)))
            if control:
                count, avg = n_ref, float(np.mean(preds_ctl[keep], dtype=np.float32))
            else:
                agg = {k.upper().replace(" ", ""): v
                       for k, v in rec["aggregates"].items()}
                count, avg = agg["COUNT(*)"], agg["AVG(PREDICTION)"]
            worst("count", abs(count - n_ref))
            worst("avg_rel", abs(avg - avg_ref) / abs(avg_ref))
        else:
            got = [preds_ctl[keep]] if control else [rec["predictions"]]
            if not control:
                # the rows as the statement returns them: the selected
                # columns, bit for bit the table's, and the prediction
                cols = [c for c in st["columns"] if c.lower() != "prediction"]
                vals, page_pred = sqlref.read_pages(rec["result_pages"],
                                                    len(cols))
                got.append(page_pred)
                want = np.stack([np.asarray(sqlref.column(x, y, c))[keep]
                                 for c in cols], axis=1)
                worst("proj_mismatch",
                      np.count_nonzero(vals.view(np.uint32) != want.view(np.uint32))
                      if vals.shape == want.shape else np.inf)
            for g in got:
                worst("rows", abs(len(g) - n_ref))
                worst("pred_abs", np.max(np.abs(g - preds[keep]))
                      if len(g) == n_ref else np.inf)
    del x, y
    jax.clear_caches()
    return out


def check(run) -> None:
    got = readings(run)
    for name, v in got.items():
        run.compare(name, v, LIMITS[name])
    if not run.records or all("error" in r for r in run.records):
        run.compare("statements_checked", 1.0, 0.0)
