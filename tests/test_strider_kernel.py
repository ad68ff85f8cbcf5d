"""Pallas strider kernel: interpret-mode validation against the jnp oracle,
the ISA interpreter, and the honest parser — swept over shapes/dtypes."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.striders import compile_strider_program, run_strider
from repro.db.page import PageLayout, build_pages, parse_page
from repro.kernels.strider import ops, ref
from repro.kernels.strider.strider import strider_decode


def _make(n, d, quant=False, page_bytes=8192, seed=0):
    lo = PageLayout(n_features=d, page_bytes=page_bytes, quantized=quant)
    rng = np.random.default_rng(seed)
    feats = rng.normal(0, 2, (n, d)).astype(np.float32)
    labels = rng.normal(0, 2, n).astype(np.float32)
    return lo, feats, labels, build_pages(feats, labels, lo)


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("d", [1, 3, 16, 54, 128])
def test_kernel_matches_ref(d, quant):
    lo, feats, labels, pages = _make(100, d, quant)
    got = strider_decode(jnp.asarray(pages), lo, interpret=True)
    want = ref.decode_pages_ref(jnp.asarray(pages), lo)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_kernel_matches_isa_interpreter():
    lo, feats, labels, pages = _make(60, 11)
    program = compile_strider_program(lo)
    kf, kl, km = strider_decode(jnp.asarray(pages), lo, interpret=True)
    for i, p in enumerate(pages):
        wf, wl, _ = run_strider(program, p, lo)
        n = wf.shape[0]
        np.testing.assert_array_equal(np.asarray(kf[i])[:n], wf)
        np.testing.assert_array_equal(np.asarray(kl[i])[:n], wl)
        assert np.all(np.asarray(km[i])[:n] == 1.0)
        assert np.all(np.asarray(km[i])[n:] == 0.0)


def test_kernel_recovers_exact_tuples():
    lo, feats, labels, pages = _make(200, 33)
    kf, kl, km = strider_decode(jnp.asarray(pages), lo, interpret=True)
    t = lo.tuples_per_page
    flat_f = np.asarray(kf).reshape(-1, 33)
    flat_l = np.asarray(kl).reshape(-1)
    flat_m = np.asarray(km).reshape(-1).astype(bool)
    np.testing.assert_array_equal(flat_f[flat_m], feats)
    np.testing.assert_array_equal(flat_l[flat_m], labels)


def test_ops_wrapper_paths_agree():
    lo, feats, labels, pages = _make(50, 20)
    a = ops.decode_pages(pages, lo, use_kernel=True)
    b = ops.decode_pages(pages, lo, use_kernel=False)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_vmem_guard():
    big = PageLayout(n_features=900, page_bytes=16 * 1024 * 1024)
    with pytest.raises(ValueError, match="VMEM"):
        ops.check_vmem(big)


@pytest.mark.parametrize("page_kb", [8, 16, 32])
def test_page_size_sweep(page_kb):
    lo, feats, labels, pages = _make(64, 9, page_bytes=page_kb * 1024)
    kf, kl, km = strider_decode(jnp.asarray(pages), lo, interpret=True)
    flat_m = np.asarray(km).reshape(-1).astype(bool)
    np.testing.assert_array_equal(np.asarray(kf).reshape(-1, 9)[flat_m], feats)


@settings(max_examples=12, deadline=None)
@given(
    n=st.integers(1, 150),
    d=st.integers(1, 96),
    quant=st.booleans(),
    seed=st.integers(0, 99),
)
def test_kernel_property(n, d, quant, seed):
    lo, feats, labels, pages = _make(n, d, quant, seed=seed)
    kf, kl, km = strider_decode(jnp.asarray(pages), lo, interpret=True)
    # parse_page is the per-tuple honest oracle
    for i, p in enumerate(pages):
        wf, wl, _ = parse_page(p, lo)
        k = wf.shape[0]
        np.testing.assert_array_equal(np.asarray(kf[i])[:k], wf)
        np.testing.assert_array_equal(np.asarray(kl[i])[:k], wl)


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MESH_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.striders import projection_plan
    from repro.db.page import PageLayout, build_pages
    from repro.dist import meshes
    from repro.kernels.strider import ops, ref

    lo = PageLayout(n_features=13, page_bytes=4096, quantized=False)
    rng = np.random.default_rng(0)
    n = 9 * lo.tuples_per_page - 5  # 9 pages: not a multiple of 4 devices
    pages = jnp.asarray(build_pages(rng.normal(0, 1, (n, 13)).astype(np.float32),
                                    rng.normal(0, 1, n).astype(np.float32), lo))
    plan = projection_plan(lo, [0, 4, 5, 12], include_label=True)
    for mp in (1, 2):
        mesh = meshes.make_host_mesh(model_parallel=mp)
        full = jax.jit(lambda p: ops.decode_pages_traced(
            p, lo, True, mesh=mesh))(pages)
        proj = jax.jit(lambda p: ops.decode_pages_projected_traced(
            p, lo, plan, True, mesh=mesh))(pages)
        with meshes.use_mesh(mesh):  # the standalone decodes read use_mesh
            std = ops.decode_pages(pages, lo, use_kernel=True)
        for got, want in zip(std, full):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for got, want in zip(full, ref.decode_pages_ref(pages, lo)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for got, want in zip(proj, ref.decode_pages_projected_ref(pages, lo, plan)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    print("MESH-DECODE-OK")
    """
)


def test_kernel_decode_under_mesh_subprocess():
    """Under a mesh with data axes the kernel runs per device on its own
    page range (shard_map), with the page count padded to the device count
    and the padding sliced off: bit-equal to the reference decode, full and
    projected, on data and data x model meshes."""
    # forced host devices are CPU devices: the child never takes the chip
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "MESH-DECODE-OK" in out.stdout
