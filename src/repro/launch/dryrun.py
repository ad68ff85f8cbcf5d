import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds abstract parameters/optimizer state/caches
(ShapeDtypeStructs — nothing is allocated), resolves NamedShardings from the
logical-axis specs, lowers the jitted step with those in_shardings, compiles,
and records:

  * memory_analysis(): per-device argument/output/temp bytes (proves it fits),
  * cost_analysis(): per-device HLO FLOPs and bytes accessed,
  * collective bytes parsed from the optimized per-device HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
    with ring wire-byte factors per replica-group size),
  * sharding fallbacks (tensors that could not shard on the model axis).

Artifacts go to artifacts/dryrun/<arch>__<shape>__<mesh>.json; the roofline
analysis and EXPERIMENTS.md tables are generated from them.

Usage:
  python -m repro.launch.dryrun --arch internlm2-20b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCH_IDS, get_config  # noqa: E402
from repro.dist import meshes  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import model_zoo  # noqa: E402
from repro.roofline.hlo import collective_stats  # noqa: E402
from repro.launch import common  # noqa: E402
from repro.train import optimizer as opt_mod  # noqa: E402

ARTIFACT_DIR = os.path.join("artifacts", "dryrun")


def _shape_tree(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


# ---------------------------------------------------------------------------
# Layer-count calibration.
#
# XLA's cost_analysis() counts a while-loop body ONCE, not x trip-count, so a
# scanned 48-layer stack reports ~1 layer of FLOPs. We therefore lower small
# calibration variants — every layer kind at count 1, then each kind at 2 —
# and extrapolate linearly:  total = base + sum_k (n_k - 1) * delta_k.
# This is exact for homogeneous scanned segments (which is what scan
# guarantees) and applies identically to FLOPs, bytes, and collective bytes.
# memory_analysis() is taken from the REAL lowering (buffers across scan
# iterations are correctly accounted there).
# ---------------------------------------------------------------------------
def kind_counts(cfg) -> dict[str, int]:
    from repro.models.transformer import segments_for

    if cfg.family == "encdec":
        return {"enc": cfg.enc_layers, "dec": cfg.n_layers}
    counts: dict[str, int] = {}
    for seg in segments_for(cfg):
        counts[seg.kind] = counts.get(seg.kind, 0) + seg.n_layers
    return counts


def with_kind_counts(cfg, counts: dict[str, int]):
    if cfg.family == "encdec":
        return dataclasses.replace(
            cfg, enc_layers=counts["enc"], n_layers=counts["dec"]
        )
    if cfg.family == "hybrid":
        g = counts.get("hybrid_global", 1)
        return dataclasses.replace(
            cfg,
            n_global_layers=g,
            n_layers=g + counts.get("hybrid_swa", 0),
        )
    if cfg.is_moe:
        fd = counts.get("attn_mlp", 0)
        return dataclasses.replace(
            cfg,
            first_dense_layers=fd,
            n_layers=fd + counts.get("attn_moe", 0),
        )
    kind = next(iter(counts))
    return dataclasses.replace(cfg, n_layers=counts[kind])


def calibration_plan(cfg) -> tuple[dict, list[tuple[str, dict]]]:
    real = kind_counts(cfg)
    base = {k: 1 for k in real}
    variants = [("base", base)]
    for k in real:
        if real[k] > 1:
            variants.append((k, {**base, k: 2}))
    return real, variants


def _batch_sharding(specs_map, inputs, mesh):
    logical = {
        "tokens": ("batch", "seq"),
        "targets": ("batch", "seq"),
        "loss_mask": ("batch", "seq"),
        "patches": ("batch", "seq", "embed"),
        "frames": ("batch", "seq", "embed"),
        "pos": (),
    }
    out = {}
    for k, v in inputs.items():
        spec = logical[k][: len(v.shape)]
        out[k] = meshes.named_sharding(spec, tuple(v.shape), mesh, tensor_name=k)
    return out


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    remat: str = "full",
    microbatches: int = 1,
    fsdp: bool = False,
    loss_chunk: int = 0,
    opt_overrides: dict | None = None,
    cfg_overrides: dict | None = None,
):
    """Returns the result record (also the hillclimb entry point: callers
    vary remat / microbatching / FSDP / loss chunking / optimizer dtype /
    sharding rules and re-measure)."""
    cfg = get_config(arch)
    if loss_chunk:
        cfg = dataclasses.replace(cfg, loss_chunk=loss_chunk)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = model_zoo.SHAPES[shape_name]
    applicable, why = model_zoo.shape_applicable(cfg, shape)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "kind": shape.kind,
        "remat": remat,
        "microbatches": microbatches,
        "fsdp": fsdp,
        "loss_chunk": loss_chunk,
    }
    if not applicable:
        rec.update(status="skipped", reason=why)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rec["n_devices"] = mesh.size

    if shape.kind != "train":
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")  # serving dtype

    param_rules = meshes.FSDP_PARAM_RULES if fsdp else None

    # -- 1. REAL lowering: memory analysis + sharding fallbacks ---------------
    t0 = time.perf_counter()
    m_real = _lower_and_measure(
        cfg, shape, mesh, remat, microbatches, param_rules, opt_overrides
    )
    rec["fallbacks"] = m_real.pop("fallbacks")
    rec["lower_s"] = round(time.perf_counter() - t0, 2)
    rec["compile_s"] = m_real["compile_s"]
    rec["memory"] = m_real["memory"]
    rec["cost_raw"] = m_real["cost"]  # scan bodies counted once (see above)

    # -- 2. calibration lowerings: extrapolate flops/bytes/collectives --------
    real_counts, variants = calibration_plan(cfg)
    measures = {}
    for label, counts in variants:
        c = with_kind_counts(cfg, counts)
        measures[label] = _lower_and_measure(
            c, shape, mesh, remat, 1, param_rules, opt_overrides, unroll=True
        )

    def extrapolate(metric):
        base = measures["base"]
        total = metric(base)
        for k, n in real_counts.items():
            if k in measures:
                total += (n - 1) * (metric(measures[k]) - metric(base))
            elif n > 1:  # kind without a 2-layer variant
                total += (n - 1) * metric(base)
        return total

    flops = extrapolate(lambda m: m["cost"]["flops"])
    bytes_acc = extrapolate(lambda m: m["cost"]["bytes_accessed"])
    wire = extrapolate(lambda m: m["collectives"]["total_wire_bytes"])
    coll_result = extrapolate(lambda m: m["collectives"]["total_result_bytes"])

    rec.update(
        status="ok",
        cost={"flops": float(flops), "bytes_accessed": float(bytes_acc)},
        collectives={
            "total_wire_bytes": float(wire),
            "total_result_bytes": float(coll_result),
            "by_kind": measures["base"]["collectives"]["by_kind"],
            "note": "totals layer-extrapolated; by_kind from 1-layer base",
        },
        calibration={
            "real_counts": real_counts,
            "variants": {k: m["cost"] for k, m in measures.items()},
        },
        model={
            "n_params": cfg.n_params(),
            "n_active_params": cfg.n_active_params(),
        },
    )
    return rec


def _lower_and_measure(cfg, shape, mesh, remat, microbatches, param_rules,
                       opt_overrides, unroll=False):
    with meshes.use_mesh(mesh):
        abs_params, specs = model_zoo.init_params(cfg, abstract=True)
        param_sh = meshes.tree_shardings(specs, abs_params, mesh,
                                         rules=param_rules)
        inputs = model_zoo.input_specs(cfg, shape)
        input_sh = _batch_sharding(specs, inputs, mesh)

        if shape.kind == "train":
            ocfg = opt_mod.OptConfig(**(opt_overrides or {}))
            abs_opt = opt_mod.adamw_init(abs_params, ocfg)
            opt_specs = opt_mod.state_specs(specs, ocfg, abs_params)
            opt_shapes = {"mu": abs_params, "nu": abs_params,
                          "step": jax.ShapeDtypeStruct((), jnp.int32)}
            opt_sh = meshes.tree_shardings(opt_specs, opt_shapes, mesh,
                                           rules=param_rules)
            step = opt_mod.make_train_step(
                model_zoo.loss_fn(cfg, remat=remat, unroll=unroll), ocfg,
                microbatches=microbatches,
            )
            jitted = jax.jit(
                step,
                in_shardings=(param_sh, opt_sh, input_sh),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(abs_params, abs_opt, inputs)
        elif shape.kind == "prefill":
            fn = model_zoo.prefill_fn(cfg, remat="none", unroll=unroll)
            jitted = jax.jit(fn, in_shardings=(param_sh, input_sh))
            lowered = jitted.lower(abs_params, inputs)
        else:  # decode
            cache = model_zoo.make_cache(
                cfg, shape.global_batch, shape.seq_len, abstract=True
            )
            c_specs = model_zoo.cache_specs(cache)
            cache_sh = meshes.tree_shardings(c_specs, cache, mesh)
            fn = model_zoo.decode_fn(cfg, unroll=unroll)
            jitted = jax.jit(
                fn,
                in_shardings=(param_sh, input_sh["tokens"], cache_sh, None),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(
                abs_params, inputs["tokens"], cache, inputs["pos"]
            )
        fallbacks = [
            {"tensor": t, "axis": a[0], "dim": a[1], "why": w}
            for t, a, w in meshes.fallbacks()
        ]

    t1 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = round(time.perf_counter() - t1, 2)
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # pre-0.5 jax: [per-module dict]
        cost = cost[0] if cost else {}
    return {
        "compile_s": compile_s,
        "fallbacks": fallbacks,
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        },
        "cost": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "collectives": collective_stats(compiled.as_text()),
    }


def run_and_save(arch, shape_name, mesh_kind, out_dir=ARTIFACT_DIR, **kw):
    multi = mesh_kind == "multi"
    try:
        rec = lower_cell(arch, shape_name, multi, **kw)
    except Exception as e:
        rec = {
            "arch": arch,
            "shape": shape_name,
            "mesh": "pod2x16x16" if multi else "pod16x16",
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-2000:],
        }
    os.makedirs(out_dir, exist_ok=True)
    name = f"{arch}__{shape_name}__{rec['mesh']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(model_zoo.SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", default="full", choices=["none", "full", "dots"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--loss-chunk", type=int, default=0)
    ap.add_argument("--opt-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    common.enable_compile_cache()

    cells = []
    archs = list(ARCH_IDS) if args.all or not args.arch else [args.arch]
    shapes = list(model_zoo.SHAPES) if args.all or not args.shape else [args.shape]
    mesh_kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        for s in shapes:
            for mk in mesh_kinds:
                cells.append((a, s, mk))

    failures = 0
    for a, s, mk in cells:
        mesh_name = "pod2x16x16" if mk == "multi" else "pod16x16"
        path = os.path.join(args.out, f"{a}__{s}__{mesh_name}.json")
        if args.skip_existing and os.path.exists(path):
            with open(path) as f:
                old = json.load(f)
            if old.get("status") in ("ok", "skipped"):
                print(f"[cached] {a} {s} {mesh_name}: {old['status']}")
                continue
        rec = run_and_save(
            a, s, mk, out_dir=args.out, remat=args.remat,
            microbatches=args.microbatch, fsdp=args.fsdp,
            loss_chunk=args.loss_chunk,
            opt_overrides={"state_dtype": args.opt_dtype},
        )
        if rec["status"] == "ok":
            mem = rec["memory"]
            per_dev = (mem["argument_bytes"] + mem["temp_bytes"]) / 2**30
            print(
                f"[ok] {a} {s} {rec['mesh']}: {rec['cost']['flops']:.3e} flops/dev, "
                f"{per_dev:.2f} GiB/dev (args+temp), "
                f"colls={rec['collectives']['total_wire_bytes']:.3e} B, "
                f"compile {rec['compile_s']}s"
            )
            print(f"     memory_analysis: {rec['memory']}")
            print(f"     cost_analysis:   {rec['cost']}")
        elif rec["status"] == "skipped":
            print(f"[skip] {a} {s} {rec['mesh']}: {rec['reason']}")
        else:
            failures += 1
            print(f"[FAIL] {a} {s} {rec['mesh']}: {rec['error']}")
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
