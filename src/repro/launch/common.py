"""Shared CLI flag vocabulary for the launchers.

``train.py`` / ``score.py`` / ``serve.py`` grew their flags independently;
this module is the single definition each argparser composes from, so the
same concept is spelled the same way — same name, same default — everywhere:

  mesh flags       ``--mesh none|host`` + ``--model-parallel N``
                   (``mesh_from_args`` builds the host mesh or returns None)
  kv flags         ``--kv dense|paged`` + ``--block-size`` + ``--kv-blocks``
  scheduler flags  ``--scheduler priority|fifo`` + ``--high-frac`` +
                   ``--deadline-ttft`` / ``--deadline`` (+ the fault knobs
                   where a chaos plan makes sense)
  bench output     ``--bench-out PATH`` writing a JSON rollup

``enable_compile_cache()`` turns on JAX's persistent compilation cache; each
launcher calls it at the top of ``main`` (never at import).

Every helper takes the ``argparse.ArgumentParser`` (or a group) and only
*adds* arguments — launchers keep their workload-specific flags alongside.
"""
from __future__ import annotations

import argparse
import json
import os

# the checkout root (src/repro/launch/common.py -> four levels up)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads it) and
    nothing else is set. Otherwise the cache is ``.jax_cache/`` in the
    checkout: a fixed path, since a cache whose directory moves never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def add_mesh_flags(ap: argparse.ArgumentParser, *, default_mesh: str = "none") -> None:
    """--mesh / --model-parallel: device-mesh topology, shared vocabulary."""
    ap.add_argument("--mesh", choices=["none", "host"], default=default_mesh,
                    help="host: build a mesh over all local devices "
                         "(data x model axes)")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="model-axis size of the host mesh")


def mesh_from_args(args):
    """The mesh the flags asked for: a host mesh, or None (unsharded)."""
    if getattr(args, "mesh", "none") != "host":
        return None
    from repro.dist import meshes

    return meshes.make_host_mesh(model_parallel=args.model_parallel)


def add_kv_flags(ap: argparse.ArgumentParser) -> None:
    """--kv / --block-size / --kv-blocks: KV cache layout (serving)."""
    ap.add_argument("--kv", choices=["dense", "paged"], default="dense",
                    help="paged: block-pool KV cache (serve/kv_pool.py)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged only)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="total blocks in the paged pool (default: "
                         "slots * ceil(max_seq/block_size), i.e. dense-"
                         "equivalent capacity; pass less to oversubscribe)")
    ap.add_argument("--prefix-cache", choices=["auto", "on", "off"],
                    default="auto",
                    help="refcounted prefix-sharing KV blocks (paged, "
                         "attention-only families). auto = on wherever "
                         "eligible; on records a fallback when ineligible")


def prefix_cache_from_args(args) -> bool | None:
    """Map the --prefix-cache tri-state onto BatchedServer's argument
    (None = auto: enabled wherever the model/layout is eligible)."""
    return {"auto": None, "on": True, "off": False}[args.prefix_cache]


def parse_tenant_weights(spec: str | None) -> dict | None:
    """Parse '0=1,1=2,interactive=4' into a tenant->weight dict (keys become
    ints when they look like ints, matching Request.tenant defaults)."""
    if not spec:
        return None
    out: dict = {}
    for part in spec.split(","):
        k, _, v = part.partition("=")
        if not _ or not k:
            raise SystemExit(f"--tenant-weights: bad entry {part!r} "
                             "(want TENANT=WEIGHT,...)")
        key = int(k) if k.strip().lstrip("-").isdigit() else k.strip()
        out[key] = float(v)
    return out


def add_scheduler_flags(ap: argparse.ArgumentParser, *,
                        faults: bool = True) -> None:
    """--scheduler / --high-frac / --deadline-ttft / --deadline (+ fault
    injection knobs when the launcher drives a chaos-capable engine)."""
    ap.add_argument("--scheduler", choices=["priority", "fifo", "wdrr"],
                    default="priority",
                    help="fifo = submission order, no preemption (ablation); "
                         "wdrr = weighted deficit round robin over tenants "
                         "under the priority classes (--tenant-weights)")
    ap.add_argument("--tenant-weights", default=None, metavar="T=W,...",
                    help="per-tenant wdrr weights, e.g. '0=1,1=2,2=4' "
                         "(unlisted tenants weigh 1)")
    ap.add_argument("--high-frac", type=float, default=0.0,
                    help="fraction of the stream in the interactive class "
                         "(priority 0; the rest are priority 2)")
    ap.add_argument("--deadline-ttft", type=float, default=None,
                    help="per-request time-to-first-output budget in "
                         "seconds (miss = cancel)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request end-to-end budget in seconds")
    if faults:
        ap.add_argument("--fault-seed", type=int, default=None,
                        help="replay FaultPlan.random(SEED) against the run "
                             "(seeded chaos: pool shrinkage, forced "
                             "preempts, admission stalls)")
        ap.add_argument("--fault-horizon", type=int, default=24,
                        help="steps of injected chaos before the plan heals")


def add_bench_out_flag(ap: argparse.ArgumentParser) -> None:
    """--bench-out: where to write the run's JSON metrics rollup."""
    ap.add_argument("--bench-out", default=None, metavar="PATH",
                    help="write the run's metrics rollup as JSON to PATH")


def write_bench_out(args, payload: dict) -> None:
    """Write the rollup if --bench-out was given (no-op otherwise)."""
    path = getattr(args, "bench_out", None)
    if path:
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"[bench] wrote {path}")
