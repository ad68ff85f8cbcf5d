#!/usr/bin/env python3
"""Drive both halves of the system once on a TPU, at real widths, and check
every result against a reference on the same chip.

Phase 1, analytics (the paper's in-database path at Table 3 width):
``sn_logistic`` — 2,000 f32 features, 387,944 tuples, 4 tuples per 32 KB
page (~3.1 GB of heap pages) — is generated from ``--seed``, written as a
heap table, and driven through ``Session.sql``: a 2-epoch TRAIN (checked
against the same TRAIN with ``use_kernel=False``), a PREDICT with an
AND/OR ``WHERE`` and a projection (checked against the trained model
applied to the kept rows in numpy), ``COUNT(*)``/``AVG(prediction)``
(count exact), and an ``INSERT … SELECT`` (its result pages decoded and
compared). The compiled TRAIN and PREDICT chunk programs must hold the
Pallas kernels (``tpu_custom_call``).

Phase 2, serving: ``minicpm3-4b`` at its published widths (62 layers,
d_model 2560, MLA, vocab 73,448) with random bfloat16 weights from
``--seed`` behind ``BatchedServer(kv="paged", attn_impl="pallas",
prefill_chunk=16)``: 16 requests of ~512 prompt tokens and 32 new tokens
over 8 slots. Midway the paged-attention kernel is compared with its
gather reference on the live KV pool, at the model's own head layout.

``--chips 4`` runs only the engine mesh path instead: the phase-1 TRAIN
through ``solver.train(mesh=make_host_mesh())`` (data-parallel) and on a
data x model mesh with ``shard_model=True``, both against the one-device
TRAIN, each required to take the ``shard_map`` datapath.

The script fails (non-zero exit, no result line) unless JAX's first device
is a TPU. Every phase runs in this one process. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Run:  python3 chip_smoke.py [--chips 4] [--tuples N] [--seed S]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
DATA_DIR = os.path.join(ROOT, ".chip_smoke")  # generated tables (gitignored)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.algorithms import logistic_regression  # noqa: E402
from repro.data.synthetic import WORKLOADS, generate  # noqa: E402
from repro.launch import common  # noqa: E402

WORKLOAD = WORKLOADS["sn_logistic"]
EPOCHS = 2
MERGE_COEF = 512  # tuples per merged update; divides 4 data shards
LR = 0.5
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, as it reports."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.total += duration


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    log(f"  ok: {what}")


def kernels_in(compiled_text: str, names) -> None:
    """Each named Pallas kernel is a ``tpu_custom_call`` in the program."""
    calls = [ln for ln in compiled_text.splitlines() if "tpu_custom_call" in ln]
    for name in names:
        check(any(f"/{name}/" in ln for ln in calls),
              f"kernel {name} is a tpu_custom_call in the compiled program")


def sigmoid(z):
    return 1.0 / (1.0 + np.exp(-z))


def make_table(n_tuples: int, seed: int):
    """Generate the workload from the seed and write it as a heap table."""
    from repro.db.heap import write_table

    scale = n_tuples / WORKLOAD.n_tuples
    X, y = generate(WORKLOAD, scale=scale, seed=seed)
    if len(X) != WORKLOAD.n_tuples:
        log(f"  cut: {len(X)} of {WORKLOAD.n_tuples} tuples (--tuples)")
    heap = write_table(os.path.join(DATA_DIR, "sn_logistic.heap"), X, y,
                       page_bytes=WORKLOAD.page_bytes)
    log(f"  table sn_logistic: {heap.n_tuples} tuples x {WORKLOAD.n_features} "
        f"features, {heap.n_pages} pages of {heap.layout.page_bytes} B "
        f"({heap.layout.tuples_per_page} tuples/page)")
    return X, y, heap


def udf():
    return logistic_regression(WORKLOAD.n_features, lr=LR,
                               merge_coef=MERGE_COEF, epochs=EPOCHS)


def analytics(n_tuples: int, seed: int) -> None:
    from repro.core import solver
    from repro.core.engine import make_engine
    from repro.db import connect
    from repro.db.heap import HeapFile
    from repro.db.query import parse, register_udf_from_trace
    from repro.db.scoring import PredictScan
    from repro.kernels.strider import ops as strider_ops

    t0 = time.perf_counter()
    X, y, heap = make_table(n_tuples, seed)
    log(f"  setup (generate + write): {time.perf_counter() - t0:.3f} s")
    sess = connect(os.path.join(DATA_DIR, "catalog"),
                   page_bytes=heap.layout.page_bytes)
    sess.catalog.register_table("sn_logistic", heap.path,
                                {"n_features": WORKLOAD.n_features})
    register_udf_from_trace(sess.catalog, "logit", udf, layout=heap.layout)

    # -- TRAIN: the plain float32 path first, then the kernels ---------------
    train_sql = "SELECT * FROM dana.logit('sn_logistic');"
    t = time.perf_counter()
    ref = sess.sql(train_sql, use_kernel=False)
    log(f"  TRAIN use_kernel=False: {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    res = sess.sql(train_sql)
    log(f"  TRAIN (kernels): {time.perf_counter() - t:.3f} s, "
        f"{res.train.epochs_run} epochs, exposed_io_s={res.exposed_io_s:.3f}")
    w, w_ref = res.coefficients[0], ref.coefficients[0]
    check(res.train.epochs_run == EPOCHS, f"TRAIN ran {EPOCHS} epochs")
    err = float(np.max(np.abs(w - w_ref)))
    log(f"  max |w_kernel - w_reference| = {err:.3e} (max |w| = "
        f"{float(np.max(np.abs(w_ref))):.3e})")
    np.testing.assert_allclose(w, w_ref, rtol=1e-3, atol=1e-4)
    check(True, "TRAIN coefficients match the use_kernel=False TRAIN")
    acc = float(np.mean((X @ w > 0) == (y > 0.5)))
    log(f"  training accuracy {acc:.4f}")
    check(acc > 0.75, "the trained model separates the classes")

    eng = make_engine(*(sess.catalog.udf("logit")[k]
                        for k in ("hdfg", "partition")))
    pages = heap.read_pages(np.arange(min(heap.n_pages,
                                          solver.MAX_RESIDENT_PAGES)))
    kernels_in(eng.lower_chunk([jnp.asarray(w)], pages, heap.layout)
               .compile().as_text(), ("strider_decode", "glm_grad"))

    # -- PREDICT with a selective AND/OR filter and a projection -------------
    where = "c1 > 0.5 AND (c2 <= 0.0 OR NOT c3 < 1.0)"
    pred_sql = (f"SELECT c0, c7, label FROM dana.predict('logit', "
                f"'sn_logistic') WHERE {where};")
    t = time.perf_counter()
    pred = sess.sql(pred_sql)
    log(f"  PREDICT: {time.perf_counter() - t:.3f} s, {pred.n_rows} of "
        f"{pred.rows_scanned} rows kept")
    kept = (X[:, 1] > 0.5) & ((X[:, 2] <= 0.0) | ~(X[:, 3] < 1.0))
    check(pred.n_rows == int(kept.sum()), "PREDICT keeps exactly the WHERE rows")
    want = sigmoid(X[kept].astype(np.float64) @ w.astype(np.float64))
    perr = float(np.max(np.abs(pred.predictions - want)))
    log(f"  max |prediction - numpy| = {perr:.3e}")
    check(perr < 1e-4, "PREDICT predictions match the model applied in numpy")
    scan = PredictScan(parse(pred_sql), sess.catalog, sess.pool)
    kernels_in(scan.run_chunk.lower(pages).compile().as_text(),
               ("strider_decode", "glm_predict"))

    # -- on-device aggregates ------------------------------------------------
    t = time.perf_counter()
    agg = sess.sql("SELECT COUNT(*), AVG(prediction) FROM dana.predict("
                   "'logit', 'sn_logistic') WHERE c1 > 0.5;")
    log(f"  COUNT/AVG: {time.perf_counter() - t:.3f} s, {agg.aggregates}")
    k1 = X[:, 1] > 0.5
    check(agg.aggregates["count(*)"] == int(k1.sum()), "COUNT(*) is exact")
    avg = float(np.mean(sigmoid(X[k1].astype(np.float64) @ w)))
    check(abs(agg.aggregates["avg(prediction)"] - avg) < 1e-4,
          "AVG(prediction) matches numpy")

    # -- INSERT ... SELECT, read back through the strider ---------------------
    t = time.perf_counter()
    ins = sess.sql("INSERT INTO scored SELECT c0, label FROM dana.predict("
                   "'logit', 'sn_logistic') WHERE c1 > 2.0;")
    log(f"  INSERT ... SELECT: {time.perf_counter() - t:.3f} s, "
        f"{ins.n_rows} rows")
    k3 = X[:, 1] > 2.0
    scored = HeapFile(sess.catalog.table("scored")["heap"])
    check(scored.n_tuples == ins.n_rows == int(k3.sum()),
          "INSERT wrote every kept row")
    f, lab, m = (np.asarray(a) for a in strider_ops.decode_pages(
        scored.read_all(), scored.layout))
    live = m.reshape(-1) > 0
    f = f.reshape(-1, 2)[live]
    check(np.array_equal(f[:, 0], X[k3, 0]) and np.array_equal(f[:, 1], y[k3]),
          "the inserted table holds the selected columns")
    ierr = np.max(np.abs(lab.reshape(-1)[live]
                         - sigmoid(X[k3].astype(np.float64) @ w)))
    check(ierr < 1e-4, "the inserted table holds the predictions")
    sess.close()


def serving(seed: int) -> None:
    from repro.configs import get_config
    from repro.kernels.paged_attn import ops as attn_ops
    from repro.models import model_zoo
    from repro.serve.scheduler import FINISHED
    from repro.serve.serving import BatchedServer, Request

    slots, n_requests, new_tokens, block = 8, 16, 32, 16
    # float32 master weights (~16.4 GB) would not fit the chip's 16 GB
    cfg = dataclasses.replace(get_config("minicpm3-4b"), param_dtype="bfloat16")
    t = time.perf_counter()
    params, _ = model_zoo.init_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    log(f"  {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}, {n_params} params bf16, init "
        f"{time.perf_counter() - t:.3f} s")
    srv = BatchedServer(cfg, params, batch_slots=slots, max_seq=512 + new_tokens,
                        kv="paged", block_size=block, attn_impl="pallas",
                        prefill_chunk=16, seed=seed)
    check(srv.attn_impl == "pallas" and srv.kv_mode == "paged",
          "server runs the paged-attention kernel over paged KV")
    rng = np.random.default_rng(seed)
    prompt_tokens = 0
    for rid in range(n_requests):
        plen = int(rng.integers(480, 513))
        prompt_tokens += plen
        srv.submit(Request(rid=rid, max_new_tokens=new_tokens,
                           prompt=rng.integers(0, cfg.vocab_size, plen).tolist()))

    t = time.perf_counter()
    for _ in range(12):  # every slot is mid-prefill: the pool holds live KV
        srv.step()
    jax.block_until_ready(srv.cache)
    log(f"  first 12 steps (incl. compile): {time.perf_counter() - t:.3f} s")

    # kernel vs gather reference on the live pool, MLA absorbed layout:
    # one kv head, 40 query heads, latent (256) + rope (32) K parts, V latent
    layer = cfg.n_layers // 2
    pool = srv.cache[0]
    c, kr = pool["c"][layer], pool["kr"][layer]
    table = jnp.asarray(srv._paged.tables()[0])
    pos = jnp.asarray(np.maximum(srv._positions - 1, 0))
    kq = jax.random.split(jax.random.PRNGKey(seed + 1))
    q = (jax.random.normal(kq[0], (slots, 1, cfg.n_heads, cfg.kv_lora_rank),
                           jnp.bfloat16),
         jax.random.normal(kq[1], (slots, 1, cfg.n_heads,
                                   cfg.qk_rope_head_dim), jnp.bfloat16))
    kw = dict(block_size=block, max_rows=srv.max_seq,
              scale=(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5)
    got = attn_ops.paged_attention(q, (c, kr), None, table, pos,
                                   use_kernel=True, **kw)
    want = attn_ops.paged_attention(q, (c, kr), None, table, pos,
                                    use_kernel=False, **kw)
    aerr = float(jnp.max(jnp.abs(got - want)))
    log(f"  paged attention on the live pool (layer {layer}, positions "
        f"{np.asarray(pos).tolist()}): max |kernel - reference| = {aerr:.3e}")
    check(bool(jnp.all(jnp.isfinite(got))) and aerr < 2e-2,
          "paged-attention kernel matches the gather reference (bf16 tol)")
    txt = jax.jit(lambda *a: attn_ops.paged_attention(
        *a, use_kernel=True, **kw)).lower(q, (c, kr), None, table, pos)
    kernels_in(txt.compile().as_text(), ("paged_attention",))

    t = time.perf_counter()
    done = srv.run()
    jax.block_until_ready(srv.cache)
    wall = time.perf_counter() - t
    check(len(done) == n_requests
          and all(r.status == FINISHED for r in done),
          f"all {n_requests} requests FINISHED")
    check(all(len(r.out) == new_tokens for r in done),
          f"every request generated {new_tokens} tokens")
    check(all(bool(jnp.all(jnp.isfinite(leaf)))
              for leaf in jax.tree.leaves(srv.cache)),
          "the KV pool holds finite values")
    m = srv.metrics.as_dict()
    log(f"  remaining steps: {wall:.3f} s; {prompt_tokens} prompt + "
        f"{n_requests * new_tokens} generated tokens; {srv.step_no} steps "
        f"total")
    log("  serve metrics: " + json.dumps({k: m[k] for k in (
        "steps", "wall_s", "tok_per_s", "mean_ttft_s", "mean_ttft_steps",
        "occupancy_pct", "kv_blocks_total", "kv_blocks_peak")}))


def mesh_train(n_tuples: int, seed: int) -> None:
    from repro.core import solver
    from repro.core.engine import make_engine
    from repro.core.translator import trace
    from repro.dist.meshes import make_host_mesh

    t0 = time.perf_counter()
    X, y, heap = make_table(n_tuples, seed)
    log(f"  setup (generate + write): {time.perf_counter() - t0:.3f} s")
    g, part = trace(udf)
    t = time.perf_counter()
    one = solver.train(g, part, heap)
    log(f"  TRAIN one device: {time.perf_counter() - t:.3f} s")
    for label, mp, shard_model, want in (
        ("data", 1, False, ("shard_map", ("data",), None)),
        ("data x model", 2, True, ("shard_map", ("data",), "model")),
    ):
        mesh = make_host_mesh(model_parallel=mp)
        eng = make_engine(g, part, mesh=mesh, shard_model=shard_model)
        t = time.perf_counter()
        res = solver.train(g, part, heap, engine=eng, mesh=mesh,
                           shard_model=shard_model)
        log(f"  TRAIN {label} mesh {dict(mesh.shape)}: "
            f"{time.perf_counter() - t:.3f} s, path {eng.last_sharded_path}")
        check(eng.last_sharded_path == want, f"{label} mesh takes {want}")
        err = float(np.max(np.abs(res.models[0] - one.models[0])))
        log(f"  max |w_mesh - w_one_device| = {err:.3e}")
        np.testing.assert_allclose(res.models[0], one.models[0],
                                   rtol=1e-3, atol=1e-4)
        check(True, f"{label} mesh coefficients match the one-device TRAIN")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the engine mesh TRAIN path on four chips")
    ap.add_argument("--tuples", type=int, default=WORKLOAD.n_tuples,
                    help="cut the sn_logistic tuple count (default: full)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    log(f"device: {dev.device_kind}, {len(devices)} device(s), "
        f"platform {dev.platform}")
    log(f"compile cache: {common.enable_compile_cache()}")
    clock = CompileClock()
    phases = ([("mesh_train", lambda: mesh_train(args.tuples, args.seed))]
              if args.chips == 4 else
              [("analytics", lambda: analytics(args.tuples, args.seed)),
               ("serving", lambda: serving(args.seed))])
    shutil.rmtree(DATA_DIR, ignore_errors=True)
    try:
        for name, run in phases:
            log(f"phase {name}")
            t, c = time.perf_counter(), clock.total
            run()
            log(f"phase {name}: wall {time.perf_counter() - t:.3f} s, "
                f"compile {clock.total - c:.3f} s")
    finally:
        shutil.rmtree(DATA_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
