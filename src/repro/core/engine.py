"""Multi-threaded execution engine (paper §5.2) as a JAX program.

A DAnA *thread* = one instance of the update rule's pre-merge function; the
engine vmaps threads over the merge coefficient and folds their results with
the merge operator (the computationally-enabled tree bus — jnp reductions
lower to the same log-tree). The whole epoch runs under jit as a lax.scan
over batches, so threads, merge, and model update are one fused device
program — the TPU analogue of the paper's statically scheduled accelerator.

The engine also performs GLM template matching: when the pre-merge graph is
numerically identical to ``(act(w.x) - y) * x`` the hardware generator swaps
in the fused Pallas kernel (kernels/engine) — the specialized datapath an
FPGA synthesis would produce for that hDFG.

Sharded epoch mode (repro.dist): under an active ``meshes.use_mesh`` (or an
Engine built with ``mesh=``) whose data axes are non-degenerate,
``run_epoch`` shards the strider-decoded
``(pages, tuples, features)`` batch over the mesh's data axes, so the
threaded GLM update runs data-parallel and the tree-bus merge lowers to a
cross-device reduce — the software analogue of the paper's parallel Striders
feeding one merge tree.

Sharded epochs run under ``jax.shard_map`` whenever the merge is a '+' fold
and the thread dim divides the data axes: each device executes the per-core
datapath — the fused Pallas GLM kernel for template matches, the vmap thread
path otherwise — on its local tuple shard, and the tree-bus merge is an
explicit ``psum``. Meshes/merges outside that envelope fall back to the
GSPMD path (sharding constraints on the vmap program), with the drop
recorded in ``meshes.fallbacks()``.

Model axis (``shard_model=True``): wide GLM coefficient vectors and LRMF
factor matrices are additionally feature-partitioned over the mesh's
``model`` axis using the logical axes each algorithm declares
(``dana.model(..., axes=("features",))``, resolved by
``meshes.MODEL_SHARD_RULES``). GLM templates take the shard_map row-parallel
datapath (feature-dim psum assembles the hypothesis, gradient shards stay
local); non-template graphs (LRMF) keep the GSPMD path with model-sharded
placement. A feature dim that does not divide the model axis falls back to
replicated — bookkept, never wrong.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec

from repro.core.hdfg import HDFG
from repro.core.jax_backend import MERGE_OPS, compile_hdfg
from repro.core.translator import Partition
from repro.dist import meshes as dist_meshes

GLM_TEMPLATES = ("linear", "logistic", "svm")


def default_metas(g: HDFG) -> list[float]:
    return [float(g.node(nid).attrs["value"]) for nid in g.meta_ids]


def model_logical_axes(g: HDFG) -> tuple[tuple[str | None, ...], ...]:
    """Per-model logical sharding axes, as declared by the algorithm
    (``dana.model(..., axes=...)``). Undeclared models resolve replicated."""
    out = []
    for mid in g.model_ids:
        n = g.node(mid)
        axes = n.attrs.get("logical_axes")
        out.append(tuple(axes) if axes is not None else (None,) * len(n.shape))
    return tuple(out)


def init_models(g: HDFG, rng: np.random.Generator | None = None, scale: float = 0.0):
    rng = rng or np.random.default_rng(0)
    out = []
    for mid in g.model_ids:
        shape = g.node(mid).shape
        if scale:
            out.append(jnp.asarray(rng.normal(0, scale, shape), dtype=jnp.float32))
        else:
            out.append(jnp.zeros(shape, dtype=jnp.float32))
    return out


def batches_from_stream(feats, labels, mask, coef):
    """Pad a flat tuple stream to whole merge batches -> (nb, coef, ...) arrays.

    Pure shape math on static shapes, so it composes into jitted programs
    (``Engine.run_chunk``) as well as running eagerly from the solver."""
    n = feats.shape[0]
    nb = -(-n // coef)
    pad = nb * coef - n
    if pad:
        feats = jnp.pad(feats, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    return (
        feats.reshape(nb, coef, -1),
        labels.reshape(nb, coef),
        mask.reshape(nb, coef),
    )


def match_glm_template(g: HDFG, part: Partition) -> str | None:
    """Probabilistic structural matching of the pre-merge graph against the
    GLM gradient templates. Numerical verification on random samples is
    robust to algebraic rewrites in the user's DSL code."""
    if g.merge_id is None or len(g.model_ids) != 1 or len(g.input_ids) != 1:
        return None
    w_shape = g.node(g.model_ids[0]).shape
    x_shape = g.node(g.input_ids[0]).shape
    if len(w_shape) != 1 or x_shape != w_shape:
        return None
    if g.node(g.merge_id).attrs["op"] != "+":
        return None
    pre_fn, _, _, _ = compile_hdfg(g, part)
    metas = default_metas(g)

    def templates(w, x, y):
        z = w @ x
        return {
            "linear": (z - y) * x,
            "logistic": (jax.nn.sigmoid(z) - y) * x,
            "svm": jnp.where(y * z < 1.0, -y, 0.0) * x,
        }

    rng = np.random.default_rng(7)
    candidates = set(GLM_TEMPLATES)
    for trial in range(6):
        w = jnp.asarray(rng.normal(0, 1, w_shape), jnp.float32)
        x = jnp.asarray(rng.normal(0, 1, x_shape), jnp.float32)
        # alternate ±1 class labels with continuous targets: identities like
        # y*y == 1 hold on ±1 labels only, so probing non-±1 y rules out
        # graphs that would otherwise shadow the linear template
        if trial % 2 == 0:
            y = jnp.float32(rng.choice([-1.0, 1.0]))
        else:
            y = jnp.float32(rng.normal(0.0, 2.0))
        try:
            got = pre_fn([w], x, y, metas)
        except Exception:
            return None
        if np.shape(got) != w_shape:
            return None
        t = templates(w, x, y)
        candidates = {
            k for k in candidates if np.allclose(got, t[k], rtol=1e-4, atol=1e-5)
        }
        if not candidates:
            return None
    return sorted(candidates)[0] if candidates else None


@dataclasses.dataclass
class Engine:
    g: HDFG
    part: Partition
    merge_op: str
    merge_coef: int
    metas: list[float]
    glm_template: str | None
    use_fused_kernel: bool
    mesh: jax.sharding.Mesh | None = None
    shard_model: bool = False
    shard_impl: str = "auto"  # "auto" | "shard_map" | "gspmd"

    def __post_init__(self):
        self._pre, self._post, self._conv, _ = compile_hdfg(self.g, self.part)
        self._epoch = jax.jit(self._epoch_impl)
        self._batch = jax.jit(self._batch_impl)
        self._model_axes = model_logical_axes(self.g)
        self._sharded_epochs: dict = {}  # mesh -> {path-key: jitted epoch}
        self._chunk_fns: dict = {}  # (layout, use_kernel, mesh) -> jitted chunk
        self.last_sharded_path: tuple | None = None  # introspection for tests/bench

    # -- one merge batch -------------------------------------------------------
    def _merge(self, vals, mask):
        m = mask.reshape(mask.shape + (1,) * (vals.ndim - 1)).astype(vals.dtype)
        return MERGE_OPS[self.merge_op](vals, m, axis=0)

    def _batch_impl(self, models, xb, yb, mask, fused: bool | None = None):
        fused = self.use_fused_kernel if fused is None else fused
        if fused and self.glm_template is not None:
            from repro.kernels.engine import ops as engine_ops

            merged = engine_ops.glm_grad(
                xb, yb, models[0], mask, act=self.glm_template
            )
        else:
            vals = jax.vmap(self._pre, in_axes=(None, 0, 0, None))(
                models, xb, yb, self.metas
            )
            merged = self._merge(vals, mask)
        new_models = self._post(models, merged, self.metas)
        return new_models, merged

    def batch_step(self, models, xb, yb, mask):
        return self._batch(models, xb, yb, mask)

    # -- one epoch over a resident chunk (scan over batches) -------------------
    def _epoch_impl(self, models, X, Y, mask, fused: bool | None = None):
        def body(carry, batch):
            xb, yb, mb = batch
            new_models, merged = self._batch_impl(carry, xb, yb, mb, fused)
            return new_models, jnp.sqrt(jnp.sum(jnp.square(merged)))

        models, gnorms = jax.lax.scan(body, models, (X, Y, mask))
        return models, gnorms

    # -- sharded epoch (data-parallel threads over the mesh) -------------------
    BATCH_AXES = {
        "X": ("pages", "tuples", "features"),
        "Y": ("pages", "tuples"),
        "mask": ("pages", "tuples"),
    }

    def _active_mesh(self):
        """The engine's mesh (or the ambient ``use_mesh`` one) iff it offers
        parallelism this engine can use: non-degenerate data axes, or a
        non-degenerate model axis when ``shard_model`` is on. None otherwise.
        Single source of truth for the run_epoch/run_chunk sharded dispatch."""
        mesh = self.mesh if self.mesh is not None else dist_meshes.current_mesh()
        if not isinstance(mesh, jax.sharding.Mesh):
            return None
        if dist_meshes.mesh_axis_size(mesh, "pod", "data") > 1:
            return mesh
        if self.shard_model and dist_meshes.mesh_axis_size(mesh, "model") > 1:
            return mesh
        return None

    def _batch_rules(self):
        return dist_meshes.MODEL_SHARD_RULES if self.shard_model else None

    def sharded_path(self, mesh, coef: int | None = None):
        """Decide how an epoch shards on ``mesh``:
        ``("shard_map", data_axes, model_axis)`` — per-device fused/vmap
        datapath under ``jax.shard_map`` with explicit psum merges — or
        ``("gspmd", data_axes, None)`` — sharding constraints on the vmap
        program, XLA inserts the collectives. shard_map is preferred whenever
        the merge is a '+' fold and the thread (merge-coefficient) dim
        divides the data axes; the model axis additionally needs a GLM
        template (row-parallel datapath) and a divisible feature dim.
        Divisibility drops are recorded in ``meshes.fallbacks()``."""
        data = dist_meshes.mesh_data_axes(mesh)
        coef = self.merge_coef if coef is None else int(coef)
        want_model = (
            self.shard_model and dist_meshes.mesh_axis_size(mesh, "model") > 1
        )
        if self.shard_impl == "gspmd":
            return "gspmd", data, None
        n_data = dist_meshes.mesh_axis_size(mesh, *data) if data else 1
        if self.merge_op != "+":
            if self.shard_impl == "shard_map":
                raise ValueError(
                    f"shard_map datapath needs a '+' merge, got {self.merge_op!r}"
                )
            return "gspmd", data, None
        if coef % n_data != 0:
            dist_meshes.record_fallback(
                "engine_batch", "tuples", 1,
                f"merge coef {coef} not divisible by data axes "
                f"{data}={n_data}; falling back to the GSPMD epoch",
            )
            if self.shard_impl == "shard_map":
                raise ValueError(
                    f"merge coef {coef} does not divide data axes {data}={n_data}"
                )
            return "gspmd", data, None
        model_axis = None
        if want_model:
            if self.glm_template is None or len(self.g.model_ids) != 1:
                if self.shard_impl == "shard_map":
                    raise ValueError(
                        "model-axis shard_map needs a single-model GLM "
                        "template (row-parallel datapath); generic graphs "
                        "model-shard via gspmd"
                    )
                # generic graphs (LRMF) model-shard via GSPMD constraints:
                # XLA places the feature-dim collectives the row-parallel
                # shard_map datapath would need a template for
                return "gspmd", data, None
            d = self.g.node(self.g.model_ids[0]).shape[0]
            m_size = dist_meshes.mesh_axis_size(mesh, "model")
            if d % m_size != 0:
                dist_meshes.record_fallback(
                    "engine_model", "features", 0,
                    f"feature dim {d} not divisible by mesh axis "
                    f"'model'={m_size}; model stays replicated",
                )
            else:
                model_axis = "model"
        return "shard_map", data, model_axis

    def _model_shardings(self, models, mesh):
        """Per-model NamedShardings from the declared logical axes — the one
        resolution both host placement (``_place_models``) and the in-program
        GSPMD constraints (``_pin_models``) consume, so they cannot desync."""
        return [
            dist_meshes.named_sharding(
                axes, jnp.shape(m), mesh,
                rules=dist_meshes.MODEL_SHARD_RULES, tensor_name="engine_model",
            )
            for m, axes in zip(models, self._model_axes)
        ]

    def _place_models(self, models, mesh, model_axis=None):
        """Device-place models for a sharded run: replicated, or partitioned
        per the declared logical axes when the model axis is in play."""
        if model_axis is None and not self.shard_model:
            return [
                jax.device_put(m, dist_meshes.replicated(mesh)) for m in models
            ]
        return [
            jax.device_put(m, sh)
            for m, sh in zip(models, self._model_shardings(models, mesh))
        ]

    def _pin_batch(self, X, Y, mask, mesh):
        """Constrain a (X, Y, mask) batch to the mesh inside a jitted program
        — shared by the GSPMD epoch and chunk programs. With ``shard_model``
        the feature dim also resolves (over the model axis)."""
        rules = self._batch_rules()

        def pin(arr, axes, tag):
            sh = dist_meshes.named_sharding(
                axes[: arr.ndim], arr.shape, mesh, rules=rules, tensor_name=tag
            )
            return jax.lax.with_sharding_constraint(arr, sh)

        return (
            pin(X, self.BATCH_AXES["X"], "engine_X"),
            pin(Y, self.BATCH_AXES["Y"], "engine_Y"),
            pin(mask, self.BATCH_AXES["mask"], "engine_mask"),
        )

    def _pin_models(self, models, mesh):
        """Model-axis sharding constraints inside the GSPMD programs."""
        if not self.shard_model:
            return models
        return [
            jax.lax.with_sharding_constraint(m, sh)
            for m, sh in zip(models, self._model_shardings(models, mesh))
        ]

    # -- shard_map datapath ----------------------------------------------------
    def _shard_map_epoch(self, mesh, data_axes, model_axis):
        """The per-device epoch under ``jax.shard_map``: each device runs the
        per-core datapath — the fused Pallas GLM kernel on its local
        (batches, tuple-shard) slice when the template matched, the vmap
        thread path otherwise — and the tree-bus merge is an explicit
        ``psum`` over the data axes. With ``model_axis`` the GLM runs
        row-parallel: the hypothesis is assembled by a feature-dim psum and
        each device keeps its local gradient/coefficient shard. Returns the
        unjitted callable (composes into the fused chunk program)."""
        from repro.kernels.engine import ops as engine_ops

        dspec = (
            None if not data_axes
            else data_axes[0] if len(data_axes) == 1 else data_axes
        )
        m_spec = PartitionSpec(model_axis) if model_axis else PartitionSpec()
        in_specs = (
            [m_spec] * len(self.g.model_ids),
            PartitionSpec(None, dspec, model_axis),
            PartitionSpec(None, dspec),
            PartitionSpec(None, dspec),
        )
        out_specs = ([m_spec] * len(self.g.model_ids), PartitionSpec())
        glm = self.glm_template is not None and (
            self.use_fused_kernel or model_axis is not None
        )

        def epoch(models, X, Y, mask):
            def body(carry, batch):
                xb, yb, mb = batch
                if glm:
                    merged = engine_ops.glm_grad_sharded(
                        xb, yb, carry[0], mb, act=self.glm_template,
                        data_axes=data_axes, model_axis=model_axis,
                    )
                else:
                    vals = jax.vmap(self._pre, in_axes=(None, 0, 0, None))(
                        carry, xb, yb, self.metas
                    )
                    merged = self._merge(vals, mb)
                    if data_axes:
                        merged = jax.lax.psum(merged, data_axes)
                new_models = self._post(carry, merged, self.metas)
                sq = jnp.sum(jnp.square(merged))
                if model_axis is not None:
                    sq = jax.lax.psum(sq, model_axis)
                return new_models, jnp.sqrt(sq)

            return jax.lax.scan(body, models, (X, Y, mask))

        return jax.shard_map(
            epoch, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    def _sharded_epoch_fn(self, mesh, path, data_axes, model_axis):
        per_mesh = self._sharded_epochs.setdefault(mesh, {})
        key = (path, data_axes, model_axis)
        jitted = per_mesh.get(key)
        if jitted is None:
            if path == "shard_map":
                impl = self._shard_map_epoch(mesh, data_axes, model_axis)
            else:

                def impl(models, X, Y, mask):
                    models = self._pin_models(models, mesh)
                    X, Y, mask = self._pin_batch(X, Y, mask, mesh)
                    # vmap thread path: the fused Pallas kernel is a per-core
                    # datapath and does not partition under GSPMD
                    return self._epoch_impl(models, X, Y, mask, fused=False)

            jitted = per_mesh[key] = jax.jit(impl)
        return jitted

    def run_epoch_sharded(self, models, X, Y, mask, mesh=None):
        """Epoch with the merge-coefficient (thread) dim sharded over the
        mesh's data axes — and, with ``shard_model``, the feature dim over
        the model axis: inputs are placed distributed, the per-device
        datapath runs on the shard-local tuples, and the '+' merge becomes a
        cross-device reduce. Numerically identical to ``run_epoch`` up to
        float reduction order."""
        mesh = mesh if mesh is not None else (
            self.mesh if self.mesh is not None else dist_meshes.current_mesh()
        )
        if not isinstance(mesh, jax.sharding.Mesh):
            return self._epoch(models, X, Y, mask)
        path, data_axes, model_axis = self.sharded_path(
            mesh, coef=jnp.shape(X)[1]
        )
        self.last_sharded_path = (path, data_axes, model_axis)
        rules = self._batch_rules()

        def place(arr, axes, tag):
            sh = dist_meshes.named_sharding(
                axes[: jnp.ndim(arr)], jnp.shape(arr), mesh,
                rules=rules, tensor_name=tag,
            )
            return jax.device_put(arr, sh)

        X = place(X, self.BATCH_AXES["X"], "engine_X")
        Y = place(Y, self.BATCH_AXES["Y"], "engine_Y")
        mask = place(mask, self.BATCH_AXES["mask"], "engine_mask")
        models = self._place_models(models, mesh, model_axis)
        fn = self._sharded_epoch_fn(mesh, path, data_axes, model_axis)
        return fn(models, X, Y, mask)

    def run_epoch(self, models, X, Y, mask):
        """X: (n_batches, merge_coef, D) float32; mask marks live tuples.
        Dispatches to the sharded path only when an active real mesh (via
        ``Engine.mesh`` or an enclosing ``meshes.use_mesh``) actually offers
        parallelism this engine can use — a fully degenerate mesh would trade
        the fused Pallas kernel for per-chunk device_puts with nothing
        gained. ``run_epoch_sharded`` remains callable explicitly on any
        mesh."""
        mesh = self._active_mesh()
        if mesh is not None:
            return self.run_epoch_sharded(models, X, Y, mask, mesh=mesh)
        return self._epoch(models, X, Y, mask)

    # -- fused chunk executor (decode + reshape + epoch, one device program) ---
    def _chunk_fn(self, layout, use_kernel: bool, mesh):
        """Build (and cache) the jitted fused chunk program for one page
        geometry. Re-traces only per distinct (layout, pages-shape, mesh)."""
        key = (layout, use_kernel, mesh)
        cached = self._chunk_fns.get(key)
        if cached is not None:
            return cached

        from repro.kernels.strider import ops as strider_ops

        sharded_path = None
        epoch = None
        rules = self._batch_rules()
        if mesh is not None:
            sharded_path = self.sharded_path(mesh)
            path, data_axes, model_axis = sharded_path
            if path == "shard_map":
                epoch = self._shard_map_epoch(mesh, data_axes, model_axis)

        def impl(models, pages):
            if mesh is not None:
                # pin the raw page stream over the data axes so GSPMD runs
                # the decode page-parallel (each device's Strider walks its
                # local page range) before resharding into the epoch layout
                sh = dist_meshes.named_sharding(
                    strider_ops.PAGE_AXES, pages.shape, mesh,
                    rules=rules, tensor_name="engine_pages",
                )
                pages = jax.lax.with_sharding_constraint(pages, sh)
            # the decode runs on this program's mesh (shard_map'd per
            # device), not on whatever ``use_mesh`` is installed at trace time
            feats, labels, mask = strider_ops.decode_pages_traced(
                pages, layout, use_kernel, rules=rules, mesh=mesh,
            )
            t = feats.shape[0] * feats.shape[1]
            X, Y, M = batches_from_stream(
                feats.reshape(t, layout.n_features),
                labels.reshape(t),
                mask.reshape(t),
                self.merge_coef,
            )
            if mesh is None:
                return self._epoch_impl(models, X, Y, M)
            if epoch is not None:
                return epoch(models, X, Y, M)
            models = self._pin_models(models, mesh)
            X, Y, M = self._pin_batch(X, Y, M, mesh)
            # vmap thread path: the fused Pallas GLM kernel is a per-core
            # datapath and does not partition under GSPMD
            return self._epoch_impl(models, X, Y, M, fused=False)

        cached = self._chunk_fns[key] = (jax.jit(impl), sharded_path)
        return cached

    def run_chunk(self, models, pages, layout, use_kernel: bool | None = None):
        """Strider decode + batch reshape + epoch scan over one resident page
        chunk as a SINGLE dispatched XLA program — the paper's pipelined
        access-engine→execution-engine datapath. No intermediate host sync:
        the returned (models, gnorms) are futures the caller may chain into
        the next chunk, syncing once per epoch.

        Under an active mesh the decoded batch is sharded inside the same
        program (parallel Striders feeding one merge tree) — via the
        shard_map'ed per-core datapath when eligible, GSPMD constraints
        otherwise; with no mesh the fused-Pallas/vmap single-core path runs
        exactly as ``run_epoch`` would."""
        from repro.kernels.strider import ops as strider_ops

        mesh = self._active_mesh()
        if use_kernel is None:
            use_kernel = strider_ops.default_use_kernel()
        fn, sharded_path = self._chunk_fn(layout, bool(use_kernel), mesh)
        if mesh is not None:
            self.last_sharded_path = sharded_path
            models = self._place_models(models, mesh, sharded_path[2])
        return fn(models, jnp.asarray(pages))

    def lower_chunk(self, models, pages, layout):
        """The fused chunk program ``run_chunk`` dispatches for these
        arguments by default, lowered: ``.compile().as_text()`` shows the
        kernels in it (``tpu_custom_call``)."""
        from repro.kernels.strider import ops as strider_ops

        fn, _ = self._chunk_fn(layout, strider_ops.default_use_kernel(),
                               self._active_mesh())
        return fn.lower(models, jnp.asarray(pages))

    def converged(self, models, merged) -> bool:
        return bool(self._conv(models, merged, self.metas))

    # -- sequential oracle ------------------------------------------------------
    def sequential_epoch(self, models, X, Y):
        """Tuple-at-a-time SGD with batch = merge_coef via plain scan, used to
        validate the threaded engine (identical for '+' merges)."""

        def body(carry, batch):
            xb, yb = batch
            vals = [
                self._pre(carry, xb[i], yb[i], self.metas)
                for i in range(xb.shape[0])
            ]
            merged = jnp.stack(vals).sum(0) if self.merge_op == "+" else None
            return self._post(carry, merged, self.metas), None

        models, _ = jax.lax.scan(body, models, (X, Y))
        return models


def make_engine(
    g: HDFG,
    part: Partition,
    merge_coef: int | None = None,
    metas: list[float] | None = None,
    use_fused_kernel: bool = True,
    mesh: jax.sharding.Mesh | None = None,
    shard_model: bool = False,
    shard_impl: str = "auto",
) -> Engine:
    if shard_impl not in ("auto", "shard_map", "gspmd"):
        raise ValueError(f"unknown shard_impl {shard_impl!r}")
    if g.merge_id is not None:
        op = g.node(g.merge_id).attrs["op"]
        coef = merge_coef or g.node(g.merge_id).attrs["coef"]
    else:
        op, coef = "+", merge_coef or 1
    tmpl = match_glm_template(g, part)
    return Engine(
        g=g,
        part=part,
        merge_op=op,
        merge_coef=coef,
        metas=metas if metas is not None else default_metas(g),
        glm_template=tmpl,
        use_fused_kernel=use_fused_kernel and tmpl is not None,
        mesh=mesh,
        shard_model=shard_model,
        shard_impl=shard_impl,
    )
