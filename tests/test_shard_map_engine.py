"""shard_map'ed fused datapath + model-axis sharding (Engine.sharded_path,
_shard_map_epoch, shard_model): path selection and divisibility fallbacks
in-process on abstract meshes; 1-device no-op; degenerate-mesh parity; true
8-device subprocess runs proving the sharded epoch keeps the fused Pallas
GLM kernel path (the vmap thread fallback is poisoned), model-axis parity
for GLM + LRMF, end-to-end solver.train(shard_model=True), and shard_map vs
single-core parity at float64."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algorithms import linear_regression, lrmf, svm
from repro.core.engine import init_models, make_engine
from repro.core.translator import trace
from repro.dist import meshes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def abstract(*pairs):
    sizes = tuple(s for _, s in pairs)
    names = tuple(n for n, _ in pairs)
    return jax.sharding.AbstractMesh(sizes, names)


def _glm_engine(d=16, coef=64, **kw):
    g, part = trace(lambda: linear_regression(d, lr=0.3, merge_coef=coef))
    return make_engine(g, part, **kw)


# ---------------------------- path selection ----------------------------------
def test_sharded_path_prefers_shard_map_on_data_mesh():
    eng = _glm_engine()
    path, data, model = eng.sharded_path(abstract(("data", 8), ("model", 1)))
    assert (path, data, model) == ("shard_map", ("data",), None)
    # pod x data both carry the tuple stream
    path, data, model = eng.sharded_path(
        abstract(("pod", 2), ("data", 4), ("model", 1))
    )
    assert (path, data, model) == ("shard_map", ("pod", "data"), None)


def test_sharded_path_model_axis_requires_shard_model_and_divisibility():
    mesh = abstract(("data", 2), ("model", 4))
    # without shard_model the model axis is never engaged
    assert _glm_engine(d=16).sharded_path(mesh)[2] is None
    # with shard_model and a divisible feature dim it is
    eng = _glm_engine(d=16, shard_model=True)
    assert eng.sharded_path(mesh) == ("shard_map", ("data",), "model")
    # a non-divisible feature dim falls back to replicated, with bookkeeping
    eng13 = _glm_engine(d=13, shard_model=True)
    meshes.clear_fallbacks()
    assert eng13.sharded_path(mesh) == ("shard_map", ("data",), None)
    assert any(
        t == "engine_model" and ax == "features"
        for t, (ax, _), _ in meshes.fallbacks()
    )


def test_sharded_path_coef_divisibility_falls_back_to_gspmd():
    eng = _glm_engine(coef=64)
    meshes.clear_fallbacks()
    path, _, _ = eng.sharded_path(abstract(("data", 8), ("model", 1)), coef=6)
    assert path == "gspmd"
    assert any(t == "engine_batch" for t, _, _ in meshes.fallbacks())
    with pytest.raises(ValueError, match="does not divide"):
        make_engine(
            *trace(lambda: linear_regression(16, merge_coef=6)),
            shard_impl="shard_map",
        ).sharded_path(abstract(("data", 8), ("model", 1)), coef=6)


def test_sharded_path_generic_graph_model_shards_via_gspmd():
    # LRMF has no GLM template: shard_model routes through GSPMD constraints
    g, part = trace(lambda: lrmf(24, rank=4, merge_coef=8))
    eng = make_engine(g, part, shard_model=True)
    assert eng.glm_template is None
    mesh = abstract(("data", 2), ("model", 4))
    path, _, model = eng.sharded_path(mesh)
    assert (path, model) == ("gspmd", None)
    # forcing shard_map must refuse rather than silently measure gspmd
    forced = make_engine(g, part, shard_model=True, shard_impl="shard_map")
    with pytest.raises(ValueError, match="model-axis shard_map"):
        forced.sharded_path(mesh)


def test_sharded_path_forced_gspmd():
    eng = _glm_engine(shard_impl="gspmd")
    assert eng.sharded_path(abstract(("data", 8), ("model", 1)))[0] == "gspmd"


def test_make_engine_rejects_unknown_shard_impl():
    with pytest.raises(ValueError, match="shard_impl"):
        _glm_engine(shard_impl="magic")


def test_solver_rejects_prebuilt_engine_without_shard_model(tmp_path):
    """train(engine=..., shard_model=True) must not silently run replicated."""
    from repro.core import solver
    from repro.db.heap import write_table

    rng = np.random.default_rng(0)
    X = rng.normal(0, 1, (64, 8)).astype(np.float32)
    heap = write_table(str(tmp_path / "e.heap"), X, X @ rng.normal(0, 1, 8),
                       page_bytes=8192)
    g, part = trace(lambda: linear_regression(8, merge_coef=8, epochs=1))
    eng = make_engine(g, part)  # built without shard_model
    with pytest.raises(ValueError, match="shard_model"):
        solver.train(g, part, heap, engine=eng, shard_model=True)
    # a shard_model engine passes through fine
    eng2 = make_engine(g, part, shard_model=True)
    solver.train(g, part, heap, engine=eng2, shard_model=True)


def test_model_logical_axes_declared_by_algorithms():
    from repro.core.engine import model_logical_axes

    g, _ = trace(lambda: svm(8))
    assert model_logical_axes(g) == (("features",),)
    g, _ = trace(lambda: lrmf(12, rank=3))
    assert model_logical_axes(g) == (("features", "rank"),)


# ---------------------------- degenerate meshes -------------------------------
def test_one_device_mesh_is_a_noop():
    """A fully degenerate mesh (1-device host) must not engage the sharded
    dispatch even with shard_model on: nothing to partition."""
    if jax.device_count() > 1:
        pytest.skip("requires a degenerate (single-device) host mesh")
    eng = _glm_engine(shard_model=True)
    d, coef = 16, 64
    rng = np.random.default_rng(0)
    Xb = jnp.asarray(rng.normal(0, 1, (4, coef, d)), jnp.float32)
    Yb = jnp.asarray(rng.normal(0, 1, (4, coef)), jnp.float32)
    Mb = jnp.ones(Yb.shape, jnp.float32)
    with meshes.use_mesh(meshes.make_host_mesh()):
        eng.run_epoch(init_models(eng.g), Xb, Yb, Mb)
    assert eng._sharded_epochs == {}
    assert eng.last_sharded_path is None


def test_explicit_sharded_epoch_parity_on_degenerate_mesh():
    """run_epoch_sharded stays callable on any mesh; on a 1-device mesh the
    shard_map program (fused per-core datapath, no collectives) must equal
    the plain epoch bit-for-bit-tolerant."""
    eng = _glm_engine()
    assert eng.use_fused_kernel
    d, coef = 16, 64
    rng = np.random.default_rng(3)
    Xb = jnp.asarray(rng.normal(0, 1, (6, coef, d)), jnp.float32)
    Yb = jnp.asarray(rng.normal(0, 1, (6, coef)), jnp.float32)
    Mb = jnp.ones(Yb.shape, jnp.float32)
    models = init_models(eng.g)
    want, wantg = eng.run_epoch(models, Xb, Yb, Mb)
    # a real 1x1 mesh even when the process has more devices (CI forces 8)
    one = jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model")
    )
    got, gotg = eng.run_epoch_sharded(models, Xb, Yb, Mb, mesh=one)
    assert eng.last_sharded_path[0] == "shard_map"
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(gotg), np.asarray(wantg), rtol=1e-4, atol=1e-5
    )


# ---------------------------- 8-device subprocess -----------------------------
_MULTI_DEVICE_SCRIPT = textwrap.dedent(
    """
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.algorithms import linear_regression, logistic_regression, lrmf
    from repro.core import solver
    from repro.core.engine import init_models, make_engine
    from repro.core.translator import trace
    from repro.db.heap import write_table
    from repro.dist import meshes
    from repro.kernels.engine import ops as engine_ops

    assert jax.device_count() == 8
    rng = np.random.default_rng(0)
    d, coef = 16, 64
    w = rng.normal(0, 1, d)
    X = rng.normal(0, 1, (1024, d)).astype(np.float32)
    y = (X @ w > 0).astype(np.float32)
    g, part = trace(lambda: logistic_regression(d, lr=0.3, merge_coef=coef))
    Xb = jnp.asarray(X).reshape(-1, coef, d)
    Yb = jnp.asarray(y).reshape(-1, coef)
    Mb = jnp.ones(Yb.shape, jnp.float32)

    # -- 1. data mesh: the sharded epoch keeps the fused Pallas GLM kernel
    # path. Proof: count glm_grad traces AND poison the vmap thread fallback.
    eng = make_engine(g, part)
    assert eng.use_fused_kernel
    models = init_models(g)
    want, wantg = eng.run_epoch(models, Xb, Yb, Mb)

    calls = {"glm_grad": 0}
    real_glm_grad = engine_ops.glm_grad
    def spy(*a, **kw):
        calls["glm_grad"] += 1
        return real_glm_grad(*a, **kw)
    engine_ops.glm_grad = spy
    def poisoned_pre(*a, **kw):
        raise AssertionError("sharded epoch took the vmap thread fallback")
    eng._pre = poisoned_pre

    mesh = meshes.make_host_mesh()
    assert dict(mesh.shape) == {"data": 8, "model": 1}
    with meshes.use_mesh(mesh):
        got, gotg = eng.run_epoch(models, Xb, Yb, Mb)
        got = jax.block_until_ready(got)
    assert eng.last_sharded_path == ("shard_map", ("data",), None), \
        eng.last_sharded_path
    assert calls["glm_grad"] > 0  # per-core fused datapath really traced
    engine_ops.glm_grad = real_glm_grad
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(gotg), np.asarray(wantg), rtol=1e-3, atol=1e-4
    )
    print("FUSED-SHARD-MAP-OK")

    # -- 2. data x model mesh: coefficients partitioned over the model axis
    mesh2 = meshes.make_host_mesh(model_parallel=4)
    assert dict(mesh2.shape) == {"data": 2, "model": 4}
    eng2 = make_engine(g, part, shard_model=True)
    with meshes.use_mesh(mesh2):
        got2, gotg2 = eng2.run_epoch(models, Xb, Yb, Mb)
        got2 = jax.block_until_ready(got2)
    assert eng2.last_sharded_path == ("shard_map", ("data",), "model")
    spec = got2[0].sharding.spec
    assert tuple(spec) == ("model",), spec  # w really feature-partitioned
    np.testing.assert_allclose(
        np.asarray(got2[0]), np.asarray(want[0]), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(gotg2), np.asarray(wantg), rtol=1e-3, atol=1e-4
    )
    print("MODEL-AXIS-OK")

    # -- 3. LRMF factor matrix: generic graph, model-sharded via GSPMD
    n_items, rank, mcoef = 24, 4, 8
    gm, pm = trace(lambda: lrmf(n_items, rank=rank, lr=1e-2, merge_coef=mcoef))
    R = rng.normal(0, 1, (256, n_items)).astype(np.float32)
    Rb = jnp.asarray(R).reshape(-1, mcoef, n_items)
    Zb = jnp.zeros(Rb.shape[:2], jnp.float32)
    Ob = jnp.ones(Zb.shape, jnp.float32)
    engm = make_engine(gm, pm, shard_model=True)
    m0 = init_models(gm, np.random.default_rng(1), scale=0.05)
    wantm, _ = engm._epoch(m0, Rb, Zb, Ob)
    with meshes.use_mesh(mesh2):
        gotm, _ = engm.run_epoch(m0, Rb, Zb, Ob)
        gotm = jax.block_until_ready(gotm)
    assert engm.last_sharded_path[0] == "gspmd"
    assert tuple(gotm[0].sharding.spec) == ("model", None)  # items sharded
    np.testing.assert_allclose(
        np.asarray(gotm[0]), np.asarray(wantm[0]), rtol=1e-4, atol=1e-5
    )
    print("LRMF-GSPMD-OK")

    # -- 4. end-to-end: pipelined solver.train on the data x model mesh
    w_true = rng.normal(0, 1, d).astype(np.float32)
    Xt = rng.normal(0, 1, (2048, d)).astype(np.float32)
    yt = Xt @ w_true
    tmp = tempfile.mkdtemp()
    heap = write_table(os.path.join(tmp, "t.heap"), Xt, yt, page_bytes=8192)
    gt, pt = trace(lambda: linear_regression(d, lr=0.3, merge_coef=64, epochs=4))
    base = solver.train(gt, pt, heap, mode="dana", seed=2, pipelined=True)
    shard = solver.train(gt, pt, heap, mode="dana", seed=2, pipelined=True,
                         mesh=mesh2, shard_model=True)
    assert shard.device_syncs == shard.epochs_run == 4
    np.testing.assert_allclose(shard.models[0], base.models[0],
                               rtol=1e-4, atol=1e-5)
    print("TRAIN-SHARD-MODEL-OK")
    """
)


def test_shard_map_engine_8_devices_subprocess():
    """8 forced host devices: fused-kernel sharded epoch (vmap fallback
    poisoned), model-axis GLM + LRMF parity, solver.train(shard_model=True)."""
    # forced host devices are CPU devices: the child never takes the chip
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _MULTI_DEVICE_SCRIPT],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    for marker in ("FUSED-SHARD-MAP-OK", "MODEL-AXIS-OK", "LRMF-GSPMD-OK",
                   "TRAIN-SHARD-MODEL-OK"):
        assert marker in out.stdout, marker


_FLOAT64_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    import jax.numpy as jnp

    from repro.algorithms import linear_regression
    from repro.core.engine import make_engine
    from repro.core.translator import trace
    from repro.dist import meshes

    assert jax.device_count() == 8
    rng = np.random.default_rng(7)
    d, coef = 12, 64
    X = rng.normal(0, 1, (512, d))
    y = X @ rng.normal(0, 1, d)
    g, part = trace(lambda: linear_regression(d, lr=0.3, merge_coef=coef))
    # the vmap thread path keeps float64 end to end (the fused kernel is an
    # f32 MXU datapath), isolating the psum merge's reduction order
    eng = make_engine(g, part, use_fused_kernel=False)
    models = [jnp.zeros(d, jnp.float64)]
    Xb = jnp.asarray(X).reshape(-1, coef, d)
    Yb = jnp.asarray(y).reshape(-1, coef)
    Mb = jnp.ones(Yb.shape, jnp.float64)
    assert Xb.dtype == jnp.float64

    want, wantg = eng._epoch(models, Xb, Yb, Mb)
    mesh = meshes.make_host_mesh()
    got, gotg = eng.run_epoch_sharded(models, Xb, Yb, Mb, mesh=mesh)
    assert eng.last_sharded_path == ("shard_map", ("data",), None)
    assert np.asarray(got[0]).dtype == np.float64
    # at float64 the 8-way psum reduction-order difference is ~1e-15 relative
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]), rtol=1e-12, atol=1e-12
    )
    np.testing.assert_allclose(
        np.asarray(gotg), np.asarray(wantg), rtol=1e-12, atol=1e-12
    )
    print("FLOAT64-PARITY-OK")
    """
)


def test_shard_map_float64_parity_8_devices_subprocess():
    """shard_map vs single-core at float64: the cross-device psum merge is
    numerically the same sum, so parity tightens to ~1e-12 — float32 gaps in
    the f32 suite are reduction order, not a datapath bug."""
    # forced host devices are CPU devices: the child never takes the chip
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _FLOAT64_SCRIPT],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "FLOAT64-PARITY-OK" in out.stdout
