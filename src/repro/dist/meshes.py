"""Logical-axis sharding: rule tables, resolver, meshes, and constraints.

Every parameter, activation, optimizer-state and input tensor in the repo
carries a tuple of *logical* axis names (``("vocab", "embed")``,
``("batch", "seq", "ff")``, ...) built by ``models.params.Maker`` or passed
at the call site. This module resolves those names onto the axes of a
physical device mesh:

  * ``resolve_spec(logical_axes, shape, mesh)`` — logical -> ``PartitionSpec``
    via a rule table, with greedy multi-axis assignment (``batch`` spreads
    over ``("pod", "data")``), per-tensor mesh-axis reuse prevention, and
    divisibility-aware fallback: a dim that does not divide by its mesh-axis
    size is left replicated and the drop is recorded (``fallbacks()``), which
    the dry-run reports as the per-arch sharding-fallback table.
  * ``shard_act(x, logical_axes, tag)`` — identity outside a mesh context,
    ``with_sharding_constraint`` inside one; the Megatron-style activation
    cut points in ``models/`` all go through it.
  * ``named_sharding`` / ``tree_shardings`` — ``NamedSharding`` for one
    tensor / a pytree of logical specs (params, optimizer state, caches).
  * ``use_mesh(mesh)`` — installs the current mesh (consulted by
    ``shard_act`` at trace time) and resets the fallback log, so each
    lowering block gets its own bookkeeping.
  * mesh constructors (``make_production_mesh``, ``make_host_mesh``) — moved
    here from ``repro.launch.mesh`` (which remains a thin re-export shim).
    Defined as functions so importing this module never touches jax device
    state (device count is locked on first jax init — dryrun.py sets
    XLA_FLAGS before importing anything).

Shardings resolved by ``shard_act`` are captured at trace time: enter
``use_mesh`` *before* tracing/jitting (train.py, dryrun.py and the engine's
per-mesh jit cache all do).
"""
from __future__ import annotations

import contextlib
import math
import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec

Mesh = jax.sharding.Mesh
_AUTO = jax.sharding.AxisType.Auto


# ---------------------------------------------------------------------------
# Mesh constructors (absorbed from repro.launch.mesh)
# ---------------------------------------------------------------------------
def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2x16x16 = 512
    chips (pod, data, model); the pod axis carries pure data parallelism
    across the inter-pod (DCN) boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """Degenerate mesh over whatever devices exist (tests, examples)."""
    n = jax.device_count()
    mp = max(1, min(model_parallel, n))
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         axis_types=(_AUTO, _AUTO))


# ---------------------------------------------------------------------------
# Rule tables: logical axis -> mesh axis (str), joint axes (tuple), or None.
# Tuples are assigned greedily left-to-right, each axis subject to the
# divisibility check against the product accepted so far.
# ---------------------------------------------------------------------------
DEFAULT_RULES: dict[str, str | tuple[str, ...] | None] = {
    # data parallelism (pod spans the DCN boundary when present)
    "batch": ("pod", "data"),
    # engine thread dim: the strider-decoded tuple stream (paper's parallel
    # Striders feeding the multi-threaded execution engine)
    "tuples": ("pod", "data"),
    # heap pages streamed into the access engine: decode is page-parallel
    # (each device's Strider decodes its local page range)
    "heap_pages": ("pod", "data"),
    # paged serving KV: the block pool spreads over the data axes (blocks are
    # the unit of placement, like heap pages for the Striders); the in-block
    # token dim never shards
    "kv_blocks": ("pod", "data"),
    # ZeRO-partitioned optimizer-state dim (train.optimizer.state_specs)
    "zero": ("pod", "data"),
    # tensor parallelism (Megatron TP pattern)
    "vocab": "model",
    "ff": "model",
    "heads": "model",
    "kv_heads": "model",
    "experts": "model",
    "inner": "model",
}

# FSDP: params additionally shard their embed dim over the data axes
# (gathered on use), on top of the standard TP rules.
FSDP_PARAM_RULES: dict[str, str | tuple[str, ...] | None] = dict(
    DEFAULT_RULES, embed=("pod", "data")
)

# Engine model-axis sharding (wide GLMs / LRMF): the feature dim of GLM
# coefficient vectors — and of the decoded tuple stream feeding them — is
# partitioned over the mesh's model axis; LRMF factor matrices reuse the
# same "features" name for their item dim (rank stays replicated). Opt-in
# via Engine/solver.train(shard_model=True); DEFAULT_RULES keeps "features"
# unsharded so data-only meshes never pay feature collectives.
MODEL_SHARD_RULES: dict[str, str | tuple[str, ...] | None] = dict(
    DEFAULT_RULES, features="model", rank=None
)

# Serving KV/state caches (serve.BatchedServer(mesh=...)): slots — the cache
# batch dim — spread over the data axes, heads/features over the model axis.
# The sequence dim stays device-local on purpose: continuous batching writes
# every slot's row at its own position each step (a per-row scatter), so
# sharding kv_seq would turn each decode write into a cross-shard update;
# the flash-decode partial-softmax combine the attention module documents
# comes from the head/model partition instead. Explicit Nones document the
# dims that must remain replicated.
SERVE_CACHE_RULES: dict[str, str | tuple[str, ...] | None] = dict(
    DEFAULT_RULES,
    layers=None, kv_seq=None, seq=None, head_dim=None, lora=None,
    state=None, conv=None, embed=None, block=None,
)

# Kernel-path variant: the paged-attention Pallas kernel walks the whole
# block pool through a scalar-prefetched block table (any token may map any
# physical block), so the pool's block dim must stay replicated — a
# data-sharded pool would strand most of a slot's blocks off-device. The
# server records a fallback when a mesh would otherwise have sharded it.
SERVE_KERNEL_CACHE_RULES: dict[str, str | tuple[str, ...] | None] = dict(
    SERVE_CACHE_RULES, kv_blocks=None,
)


# ---------------------------------------------------------------------------
# Current-mesh context + fallback bookkeeping (thread-local: shard_act runs
# on whatever thread is tracing)
# ---------------------------------------------------------------------------
_STATE = threading.local()


def current_mesh():
    """The mesh installed by the innermost ``use_mesh``, or None."""
    return getattr(_STATE, "mesh", None)


def _fallback_log() -> list:
    log = getattr(_STATE, "fallbacks", None)
    if log is None:
        log = _STATE.fallbacks = []
    return log


def fallbacks() -> list[tuple[str | None, tuple[str, int], str]]:
    """Divisibility drops recorded since the current ``use_mesh`` was entered
    (or since ``clear_fallbacks``): ``(tensor_name, (logical_axis, dim), why)``.
    """
    return list(_fallback_log())


def clear_fallbacks() -> None:
    _fallback_log().clear()


def _record_fallback(tensor_name, logical_axis, dim, why):
    entry = (tensor_name, (logical_axis, dim), why)
    log = _fallback_log()
    if entry not in log:
        log.append(entry)


def record_fallback(tensor_name, logical_axis, dim, why) -> None:
    """Public entry for callers that make their own sharding decisions (the
    engine's shard_map path) so their divisibility drops land in the same
    ``fallbacks()`` report as the resolver's."""
    _record_fallback(tensor_name, logical_axis, dim, why)


@contextlib.contextmanager
def use_mesh(mesh):
    """Install ``mesh`` as the current mesh for ``shard_act`` and the engine's
    sharded epoch mode. Each block gets a fresh fallback log so it reports
    its own divisibility drops; the enclosing block's log is restored (not
    lost) on exit."""
    prev_mesh = current_mesh()
    prev_log = _fallback_log()
    _STATE.mesh = mesh
    _STATE.fallbacks = []
    try:
        yield mesh
    finally:
        _STATE.mesh = prev_mesh
        _STATE.fallbacks = prev_log


# ---------------------------------------------------------------------------
# Resolver
# ---------------------------------------------------------------------------
def _axis_sizes(mesh) -> dict[str, int]:
    return {name: int(mesh.shape[name]) for name in mesh.axis_names}


def resolve_spec(
    logical_axes,
    shape,
    mesh,
    rules: dict | None = None,
    tensor_name: str | None = None,
) -> PartitionSpec:
    """Resolve logical axis names against ``mesh`` -> ``PartitionSpec``.

    Per dim, the rule table yields a mesh axis (or a tuple tried greedily
    left-to-right). An axis is assigned iff it exists in the mesh, has size
    > 1, was not already used by an earlier dim of this tensor, and the dim
    size is divisible by the accumulated shard count; a divisibility miss is
    recorded in ``fallbacks()`` and the dim stays (partially) replicated.
    """
    logical_axes = tuple(logical_axes)
    shape = tuple(shape)
    if len(logical_axes) != len(shape):
        raise ValueError(
            f"rank mismatch for {tensor_name or 'tensor'}: "
            f"axes {logical_axes} vs shape {shape}"
        )
    rules = DEFAULT_RULES if rules is None else rules
    sizes = _axis_sizes(mesh)
    used: set[str] = set()
    out = []
    for dim, (name, dim_size) in enumerate(zip(logical_axes, shape)):
        cand = rules.get(name)
        if cand is None:
            out.append(None)
            continue
        if isinstance(cand, str):
            cand = (cand,)
        picked: list[str] = []
        shards = 1
        for axis in cand:
            if axis not in sizes or sizes[axis] <= 1 or axis in used:
                continue  # absent/degenerate/taken: not a fallback, just n/a
            if dim_size % (shards * sizes[axis]) != 0:
                _record_fallback(
                    tensor_name, name, dim,
                    f"dim {dim_size} not divisible by mesh axis "
                    f"'{axis}'={sizes[axis]} (x{shards} already assigned)",
                )
                continue
            picked.append(axis)
            shards *= sizes[axis]
        used.update(picked)
        if not picked:
            out.append(None)
        elif len(picked) == 1:
            out.append(picked[0])
        else:
            out.append(tuple(picked))
    return PartitionSpec(*out)


def named_sharding(
    logical_axes,
    shape,
    mesh,
    *,
    rules: dict | None = None,
    tensor_name: str | None = None,
) -> NamedSharding:
    """``NamedSharding`` for one tensor from its logical axes."""
    spec = resolve_spec(
        logical_axes, shape, mesh, rules=rules, tensor_name=tensor_name
    )
    return NamedSharding(mesh, spec)


def _is_spec(node) -> bool:
    return isinstance(node, tuple) and all(
        isinstance(e, (str, type(None))) for e in node
    )


def tree_shardings(specs, tree, mesh, rules: dict | None = None):
    """NamedShardings for a pytree: ``specs`` is a parallel tree whose leaves
    are logical-axis tuples (params, optimizer state, caches); ``tree`` holds
    arrays or ShapeDtypeStructs. Key paths become the tensor names in the
    fallback report."""

    def one(path, spec, leaf):
        parts = [
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path
        ]
        return named_sharding(
            spec, tuple(leaf.shape), mesh, rules=rules,
            tensor_name="/".join(parts) or None,
        )

    return jax.tree_util.tree_map_with_path(one, specs, tree, is_leaf=_is_spec)


def replicated(mesh) -> NamedSharding:
    """Fully-replicated NamedSharding (models, scalars)."""
    return NamedSharding(mesh, PartitionSpec())


# ---------------------------------------------------------------------------
# Activation constraints
# ---------------------------------------------------------------------------
def shard_act(x, logical_axes, tag: str | None = None, rules: dict | None = None):
    """Constrain activation ``x`` to its resolved sharding under the current
    mesh; identity when no mesh is installed (single-process tests) or when
    the spec resolves fully replicated. Resolution happens at trace time —
    enter ``use_mesh`` before jitting."""
    return constrain(x, logical_axes, current_mesh(), tag=tag, rules=rules)


def constrain(x, logical_axes, mesh, tag: str | None = None,
              rules: dict | None = None):
    """``shard_act`` on an explicit ``mesh`` (None: identity) — for callers
    that resolve their mesh themselves rather than from ``use_mesh``."""
    if mesh is None:
        return x
    spec = resolve_spec(
        logical_axes, x.shape, mesh, rules=rules, tensor_name=tag
    )
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def mesh_axis_size(mesh, *axis_names) -> int:
    """Product of the named axes present in ``mesh`` (missing axes count 1)."""
    sizes = _axis_sizes(mesh)
    return math.prod(sizes.get(a, 1) for a in axis_names)


def mesh_data_axes(mesh) -> tuple[str, ...]:
    """The mesh's non-degenerate data-parallel axes, in rule order — the axes
    the engine's shard_map datapath maps the tuple stream over."""
    sizes = _axis_sizes(mesh)
    return tuple(a for a in ("pod", "data") if sizes.get(a, 1) > 1)


# device-free meshes for the resolver: meshes.AbstractMesh(sizes, names)
AbstractMesh = jax.sharding.AbstractMesh

__all__ = [
    "AbstractMesh",
    "DEFAULT_RULES",
    "FSDP_PARAM_RULES",
    "MODEL_SHARD_RULES",
    "SERVE_CACHE_RULES",
    "SERVE_KERNEL_CACHE_RULES",
    "Mesh",
    "clear_fallbacks",
    "current_mesh",
    "fallbacks",
    "make_host_mesh",
    "make_production_mesh",
    "mesh_axis_size",
    "mesh_data_axes",
    "named_sharding",
    "record_fallback",
    "replicated",
    "resolve_spec",
    "shard_act",
    "shard_map",
    "tree_shardings",
    "use_mesh",
]
