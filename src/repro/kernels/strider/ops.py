"""Jitted public wrapper for the strider kernel.

Chooses the execution path per backend: the Pallas kernel compiled natively
on TPU (``tests/test_tpu_compile.py`` compiles it for a v5e) — or, forced on
CPU, in interpret mode, where its semantics are validated against ref.py and
the ISA interpreter — with a VMEM working-set check the hardware generator
performs before 'synthesis'.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.core.striders import ProjectionPlan
from repro.db.page import PageLayout
from repro.dist import meshes as dist_meshes
from repro.kernels.strider import ref
from repro.kernels.strider.strider import PAGES_PER_STEP, strider_decode

VMEM_BYTES = 16 * 1024 * 1024  # v5e per-core VMEM

# logical axes of the raw page stream and its decoded tensors: pages spread
# over the mesh's data axes (each device's Strider decodes a local page
# range); tuple-in-page and feature dims resolve per the active rule table
PAGE_AXES = ("heap_pages", None)
DECODED_AXES = {
    "feats": ("heap_pages", None, "features"),
    "labels": ("heap_pages", None),
    "mask": ("heap_pages", None),
}


def vmem_working_set(layout: PageLayout) -> int:
    """Bytes one grid step holds in VMEM, double-buffered: a group of pages
    plus the slot-major (T, G, D + 2) f32 output tile, lanes padded to 128."""
    g, t = PAGES_PER_STEP, layout.tuples_per_page
    lanes = -(-(layout.n_features + 2) // 128) * 128
    return 2 * (g * layout.page_bytes + 4 * t * g * lanes)


def check_vmem(layout: PageLayout) -> None:
    ws = vmem_working_set(layout)
    if ws > VMEM_BYTES:
        raise ValueError(
            f"strider working set {ws} B exceeds VMEM ({VMEM_BYTES} B); "
            f"use a smaller page or feature tile"
        )


def default_use_kernel() -> bool:
    """Kernel-selection policy, single source of truth: the Pallas kernel on
    TPU, the numerically identical (faster-to-trace) jnp path elsewhere."""
    return jax.default_backend() == "tpu"


def _kernel_decode(pages, layout: PageLayout, plan: ProjectionPlan | None,
                   mesh):
    """The Pallas decode (interpret mode on CPU). On a ``mesh`` with data
    axes it runs per device on the device's own page range via
    ``jax.shard_map`` — a kernel is a per-core program that GSPMD cannot
    split. The page count is padded to the data-axes size with zero pages
    (no live tuples), and the padding is sliced off again."""
    decode = partial(strider_decode, layout=layout, plan=plan,
                     interpret=jax.default_backend() == "cpu")
    data = (dist_meshes.mesh_data_axes(mesh)
            if isinstance(mesh, jax.sharding.Mesh) else ())
    if not data:
        return decode(pages)
    p = pages.shape[0]
    pad = -p % dist_meshes.mesh_axis_size(mesh, *data)
    pages = jnp.pad(pages, ((0, pad), (0, 0)))
    spec = PartitionSpec(data)
    out = jax.shard_map(decode, mesh=mesh, in_specs=spec,
                        out_specs=(spec, spec, spec), check_vma=False)(pages)
    return tuple(o[:p] for o in out)


def _decode(pages, layout, plan, use_kernel, rules, mesh):
    """Shared body of the traced decodes: pin the page stream and the
    decoded tensors over ``mesh`` (identity when None) around the kernel or
    the reference decode."""
    check_vmem(layout)
    if use_kernel is None:
        use_kernel = default_use_kernel()
    pages = jnp.asarray(pages).astype(jnp.uint32)
    pages = dist_meshes.constrain(pages, PAGE_AXES, mesh, "strider_pages", rules)
    if use_kernel:
        out = _kernel_decode(pages, layout, plan, mesh)
    elif plan is None:
        out = ref.decode_pages_ref(pages, layout)
    else:
        out = ref.decode_pages_projected_ref(pages, layout, plan)
    return tuple(
        dist_meshes.constrain(x, DECODED_AXES[k], mesh, f"strider_{k}", rules)
        for k, x in zip(("feats", "labels", "mask"), out)
    )


def decode_pages_traced(
    pages, layout: PageLayout, use_kernel: bool | None = None,
    rules: dict | None = None, mesh=None,
):
    """Trace-time decode body: safe to call inside an enclosing ``jax.jit``.

    This is what ``Engine.run_chunk`` composes with the batch reshape and the
    epoch scan to form one fused device program — the decode never round-trips
    through a separate dispatch. ``check_vmem`` runs at trace time (layout is
    static), exactly as the hardware generator checks before synthesis.

    On a ``mesh`` the page stream and its decoded tensors are constrained
    over the mesh's data axes (``PAGE_AXES`` / ``DECODED_AXES``) and the
    kernel runs per device — each device's Strider walks its own page range.
    ``rules`` selects the rule table (the engine passes ``MODEL_SHARD_RULES``
    when the feature dim is model-sharded). ``mesh=None`` decodes on one
    device; the caller resolves the mesh, so the decode and the program
    around it cannot disagree about it.
    """
    return _decode(pages, layout, None, use_kernel, rules, mesh)


def decode_pages_projected_traced(
    pages, layout: PageLayout, plan: ProjectionPlan,
    use_kernel: bool | None = None, rules: dict | None = None, mesh=None,
):
    """Trace-time pushdown decode body (safe inside an enclosing ``jax.jit``).

    Same fusion and mesh contract as :func:`decode_pages_traced`, but the
    decode is restricted to ``plan``'s payload words — the scoring executor
    composes this with filter evaluation and model scoring into one device
    program, so dropped columns never leave the page buffer and filtered
    tuples never reach the engine. ``plan`` is static (frozen dataclass of
    tuples): it is part of the jit cache key, exactly like the layout.
    """
    return _decode(pages, layout, plan, use_kernel, rules, mesh)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _decode_jit(pages, layout: PageLayout, use_kernel: bool, mesh):
    return decode_pages_traced(pages, layout, use_kernel, mesh=mesh)


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _decode_projected_jit(
    pages, layout: PageLayout, plan: ProjectionPlan, use_kernel: bool, mesh
):
    return decode_pages_projected_traced(pages, layout, plan, use_kernel,
                                         mesh=mesh)


def decode_pages_projected(
    pages: jnp.ndarray, layout: PageLayout, plan: ProjectionPlan,
    use_kernel: bool | None = None,
):
    """Standalone jitted pushdown decode (see decode_pages for path policy)."""
    if use_kernel is None:
        use_kernel = default_use_kernel()
    return _decode_projected_jit(
        jnp.asarray(pages, dtype=jnp.uint32), layout, plan, bool(use_kernel),
        dist_meshes.current_mesh(),
    )


def decode_pages(pages: jnp.ndarray, layout: PageLayout, use_kernel: bool | None = None):
    """Decode a batch of pages on-device (standalone jitted dispatch), on
    the ``use_mesh`` mesh when one is installed.

    use_kernel=None picks the Pallas kernel on TPU and the (numerically
    identical, faster-to-trace) vectorized jnp path on CPU — both are the
    same algorithm; tests assert their equivalence on every shape swept.
    """
    if use_kernel is None:
        use_kernel = default_use_kernel()  # concrete for the jit cache key
    return _decode_jit(jnp.asarray(pages, dtype=jnp.uint32), layout,
                       bool(use_kernel), dist_meshes.current_mesh())
