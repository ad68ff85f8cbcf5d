"""repro.dist.meshes: resolver rule precedence, FSDP rules, divisibility
fallbacks + bookkeeping, shard_act identity-with-constraint under a host
mesh, tree shardings, and the engine's sharded epoch mode (single-device in
process; true multi-device parity in a forced-8-device subprocess)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import meshes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def abstract(*pairs):
    sizes = tuple(s for _, s in pairs)
    names = tuple(n for n, _ in pairs)
    return jax.sharding.AbstractMesh(sizes, names)


# ------------------------------ resolver -------------------------------------
def test_default_rules_tensor_parallel_axes():
    mesh = abstract(("data", 2), ("model", 4))
    spec = meshes.resolve_spec(("vocab", "embed"), (128, 64), mesh)
    assert tuple(spec) == ("model", None)
    spec = meshes.resolve_spec(("batch", "seq", "ff"), (8, 16, 32), mesh)
    assert tuple(spec) == ("data", None, "model")


def test_rule_precedence_explicit_rules_override_defaults():
    mesh = abstract(("data", 2), ("model", 4))
    # default: ff -> model; explicit rules replace the whole table
    spec = meshes.resolve_spec(
        ("ff", "embed"), (32, 64), mesh, rules={"ff": "data", "embed": None}
    )
    assert tuple(spec) == ("data", None)
    # a logical axis absent from the rules is replicated
    spec = meshes.resolve_spec(("vocab",), (128,), mesh, rules={})
    assert tuple(spec) == (None,)


def test_fsdp_rules_shard_embed_over_data():
    mesh = abstract(("data", 2), ("model", 4))
    default = meshes.resolve_spec(("embed", "ff"), (64, 128), mesh)
    fsdp = meshes.resolve_spec(
        ("embed", "ff"), (64, 128), mesh, rules=meshes.FSDP_PARAM_RULES
    )
    assert tuple(default) == (None, "model")
    assert tuple(fsdp) == ("data", "model")


def test_multi_axis_batch_spans_pod_and_data():
    mesh = abstract(("pod", 2), ("data", 4), ("model", 2))
    spec = meshes.resolve_spec(("batch", "seq"), (16, 8), mesh)
    assert tuple(spec) == (("pod", "data"), None)


def test_partial_multi_axis_assignment_records_fallback():
    mesh = abstract(("pod", 2), ("data", 4), ("model", 2))
    meshes.clear_fallbacks()
    # 6 % 2 == 0 (pod taken) but 6 % (2*4) != 0 -> data dropped + recorded
    spec = meshes.resolve_spec(("batch",), (6,), mesh, tensor_name="tokens")
    assert tuple(spec) == ("pod",)
    assert any(
        t == "tokens" and ax == "batch" and dim == 0
        for t, (ax, dim), _ in meshes.fallbacks()
    )


def test_degenerate_and_missing_axes_are_not_fallbacks():
    mesh = abstract(("data", 1), ("model", 1))
    meshes.clear_fallbacks()
    spec = meshes.resolve_spec(("batch", "vocab", "ff"), (3, 5, 7), mesh)
    assert all(s is None for s in spec)
    assert meshes.fallbacks() == []  # size-1 axes are skipped silently


def test_no_mesh_axis_reused_within_one_tensor():
    mesh = abstract(("data", 2), ("model", 4))
    spec = meshes.resolve_spec(("vocab", "ff", "heads"), (8, 8, 8), mesh)
    axes = [s for s in spec if s is not None]
    assert axes == ["model"]  # first dim wins; no duplicate assignment


def test_rank_mismatch_raises():
    mesh = abstract(("data", 2), ("model", 4))
    with pytest.raises(ValueError, match="rank mismatch"):
        meshes.resolve_spec(("vocab",), (8, 8), mesh, tensor_name="w")


# --------------------------- fallback bookkeeping -----------------------------
def test_use_mesh_scopes_fallback_log_and_restores_mesh():
    mesh = abstract(("data", 2), ("model", 4))
    meshes.clear_fallbacks()
    meshes.resolve_spec(("kv_heads",), (6,), mesh, tensor_name="outer")
    assert any(t == "outer" for t, _, _ in meshes.fallbacks())
    assert meshes.current_mesh() is None
    with meshes.use_mesh(mesh):
        assert meshes.current_mesh() is mesh
        assert meshes.fallbacks() == []  # fresh log for this block
        meshes.resolve_spec(("kv_heads",), (6,), mesh, tensor_name="inner")
        recs = meshes.fallbacks()
        assert [t for t, _, _ in recs] == ["inner"]
        # duplicate resolutions are logged once
        meshes.resolve_spec(("kv_heads",), (6,), mesh, tensor_name="inner")
        assert len(meshes.fallbacks()) == len(recs)
        # a nested block gets its own log and must not wipe this one
        with meshes.use_mesh(mesh):
            assert meshes.fallbacks() == []
        assert [t for t, _, _ in meshes.fallbacks()] == ["inner"]
    assert meshes.current_mesh() is None
    # exiting restored the outermost log
    assert any(t == "outer" for t, _, _ in meshes.fallbacks())


def test_abstract_mesh_export_accepts_sizes_names_ctor():
    m = meshes.AbstractMesh((2, 4), ("data", "model"))
    assert dict(m.shape) == {"data": 2, "model": 4}
    assert isinstance(m, meshes.AbstractMesh)  # a real type, not a factory
    spec = meshes.resolve_spec(("ff",), (8,), m)
    assert tuple(spec) == ("model",)


# ------------------------------- shard_act ------------------------------------
def test_shard_act_is_identity_with_constraint_under_host_mesh():
    x = jnp.arange(12.0).reshape(3, 4)
    # no mesh: exact identity (same object, no constraint inserted)
    assert meshes.shard_act(x, ("batch", "embed")) is x
    mesh = meshes.make_host_mesh()
    with meshes.use_mesh(mesh):
        y = meshes.shard_act(x, ("batch", "ff"), "act")
        z = jax.jit(lambda a: meshes.shard_act(a * 2.0, ("batch", "ff")))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(z), np.asarray(x) * 2.0)


# --------------------------- tree / named shardings ---------------------------
def test_named_and_tree_shardings():
    mesh = meshes.make_host_mesh()
    sh = meshes.named_sharding(("batch", "ff"), (4, 8), mesh, tensor_name="h")
    assert isinstance(sh, jax.sharding.NamedSharding)
    assert sh.mesh.axis_names == ("data", "model")

    specs = {"w": ("embed", "ff"), "scale": ("embed",), "step": ()}
    tree = {
        "w": jnp.zeros((4, 8)),
        "scale": jnp.zeros((4,)),
        "step": jnp.zeros(()),
    }
    shardings = meshes.tree_shardings(specs, tree, mesh)
    assert set(shardings) == {"w", "scale", "step"}
    for k, s in shardings.items():
        assert isinstance(s, jax.sharding.NamedSharding), k
    placed = jax.tree.map(jax.device_put, tree, shardings)
    np.testing.assert_array_equal(np.asarray(placed["w"]), np.asarray(tree["w"]))


def test_launch_mesh_shim_reexports():
    from repro.launch import mesh as launch_mesh

    assert launch_mesh.make_host_mesh is meshes.make_host_mesh
    assert launch_mesh.make_production_mesh is meshes.make_production_mesh


# --------------------------- engine sharded mode ------------------------------
def _toy_problem(n=512, d=12, coef=64, seed=0):
    from repro.algorithms import linear_regression
    from repro.core.engine import init_models, make_engine
    from repro.core.translator import trace

    rng = np.random.default_rng(seed)
    w = rng.normal(0, 1, d)
    X = rng.normal(0, 1, (n, d)).astype(np.float32)
    y = (X @ w).astype(np.float32)
    g, part = trace(lambda: linear_regression(d, lr=0.3, merge_coef=coef))
    eng = make_engine(g, part, use_fused_kernel=False)
    models = init_models(g)
    Xb = jnp.asarray(X).reshape(-1, coef, d)
    Yb = jnp.asarray(y).reshape(-1, coef)
    Mb = jnp.ones(Yb.shape, jnp.float32)
    return eng, models, Xb, Yb, Mb


def test_engine_sharded_epoch_matches_unsharded_on_host_mesh():
    eng, models, Xb, Yb, Mb = _toy_problem()
    want, wantg = eng.run_epoch(models, Xb, Yb, Mb)
    mesh = meshes.make_host_mesh()
    # explicit sharded call works on any mesh (here: degenerate data axis)
    got, gotg = eng.run_epoch_sharded(models, Xb, Yb, Mb, mesh=mesh)
    assert mesh in eng._sharded_epochs
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(gotg), np.asarray(wantg), rtol=1e-4, atol=1e-5
    )


def test_engine_run_epoch_skips_sharded_path_without_data_parallelism():
    """A mesh with no usable data parallelism must not silently trade the
    fused kernel for device_puts: run_epoch stays on the plain path."""
    if jax.device_count() > 1:
        pytest.skip("requires a degenerate (single-device) host mesh")
    eng, models, Xb, Yb, Mb = _toy_problem()
    with meshes.use_mesh(meshes.make_host_mesh()):
        eng.run_epoch(models, Xb, Yb, Mb)
    assert eng._sharded_epochs == {}


def test_solver_train_accepts_mesh(tmp_path):
    from repro.algorithms import linear_regression
    from repro.core import solver
    from repro.core.translator import trace
    from repro.db.heap import write_table

    rng = np.random.default_rng(21)
    w_true = rng.normal(0, 1, 8).astype(np.float32)
    X = rng.normal(0, 1, (1500, 8)).astype(np.float32)
    y = X @ w_true
    heap = write_table(str(tmp_path / "m.heap"), X, y, page_bytes=8192)
    g, part = trace(lambda: linear_regression(8, lr=0.3, merge_coef=64, epochs=25))
    res = solver.train(g, part, heap, mode="dana", mesh=meshes.make_host_mesh())
    np.testing.assert_allclose(res.models[0], w_true, atol=0.05)


_MULTI_DEVICE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.algorithms import linear_regression
    from repro.core.engine import init_models, make_engine
    from repro.core.translator import trace
    from repro.dist import meshes

    assert jax.device_count() == 8
    rng = np.random.default_rng(0)
    d, coef = 12, 64
    w = rng.normal(0, 1, d)
    X = rng.normal(0, 1, (512, d)).astype(np.float32)
    y = (X @ w).astype(np.float32)
    g, part = trace(lambda: linear_regression(d, lr=0.3, merge_coef=coef))
    eng = make_engine(g, part, use_fused_kernel=False)
    models = init_models(g)
    Xb = jnp.asarray(X).reshape(-1, coef, d)
    Yb = jnp.asarray(y).reshape(-1, coef)
    Mb = jnp.ones(Yb.shape, jnp.float32)

    want, wantg = eng.run_epoch(models, Xb, Yb, Mb)
    mesh = meshes.make_host_mesh()
    assert dict(mesh.shape) == {"data": 8, "model": 1}
    spec = meshes.resolve_spec(("pages", "tuples", "features"), Xb.shape, mesh)
    assert tuple(spec) == (None, "data", None), spec  # threads over data axis
    with meshes.use_mesh(mesh):
        got, gotg = eng.run_epoch(models, Xb, Yb, Mb)
    sh = jax.device_put(
        Xb, meshes.named_sharding(("pages", "tuples", "features"), Xb.shape, mesh)
    ).sharding
    assert len(sh.device_set) == 8  # tuples really distributed over 8 devices
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(gotg), np.asarray(wantg), rtol=1e-3, atol=1e-4
    )
    print("MULTIDEV-OK")
    """
)


def test_engine_sharded_epoch_parity_8_devices_subprocess():
    """True data-parallel run: 8 forced host devices, threads sharded over
    the data axis, results equal to the single-device engine."""
    # forced host devices are CPU devices: the child never takes the chip
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _MULTI_DEVICE_SCRIPT],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "MULTIDEV-OK" in out.stdout
