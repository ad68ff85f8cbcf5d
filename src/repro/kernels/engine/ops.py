"""Jitted public wrapper for the fused GLM engine kernel: pads shapes to MXU
tiles, dispatches kernel (TPU) vs. oracle (elsewhere) per backend, unpads."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.engine import ref
from repro.kernels.engine.engine import glm_grad_pallas, glm_predict_pallas

LANES = 128


def _pad_to(x, n, axis):
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


@partial(jax.jit, static_argnames=("act", "use_kernel", "block_rows"))
def _glm_grad(x, y, w, mask, act, use_kernel, block_rows):
    n, d = x.shape
    if not use_kernel:
        return ref.glm_grad_ref(x, y, w, mask, act)
    dp = -(-d // LANES) * LANES
    rows = max(block_rows, LANES)
    np_ = -(-n // rows) * rows
    xp = _pad_to(_pad_to(x.astype(jnp.float32), np_, 0), dp, 1)
    yp = _pad_to(y.astype(jnp.float32), np_, 0)
    mp = _pad_to(mask.astype(jnp.float32), np_, 0)
    wp = _pad_to(w.astype(jnp.float32), dp, 0)
    interpret = jax.default_backend() == "cpu"
    g = glm_grad_pallas(xp, yp, wp, mp, act, block_rows=rows, interpret=interpret)
    return g[:d]


def glm_grad(x, y, w, mask=None, act: str = "linear", use_kernel: bool | None = None,
             block_rows: int = 128):
    """Merged GLM gradient over a tuple batch (the fused engine step).

    use_kernel=None: the Pallas kernel compiled for the TPU there
    (``tests/test_tpu_compile.py`` compiles it for a v5e), the vectorized-jnp
    oracle on CPU (same math; the test suite also runs the kernel in
    interpret mode).
    """
    if mask is None:
        mask = jnp.ones(x.shape[0], dtype=jnp.float32)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    return _glm_grad(x, y, w, mask, act, bool(use_kernel), int(block_rows))


def glm_predict_traced(x, w, mask=None, act: str = "linear",
                       use_kernel: bool | None = None, block_rows: int = 128):
    """Trace-time per-row GLM scoring body: predictions act(X·w), dead rows 0.

    Safe inside an enclosing ``jax.jit`` — the scoring executor fuses this
    with the projected strider decode into one device program. Path policy
    matches glm_grad: Pallas on TPU, jnp oracle elsewhere.
    """
    if mask is None:
        mask = jnp.ones(x.shape[0], dtype=jnp.float32)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return ref.glm_predict_ref(x, w, mask, act)
    n, d = x.shape
    dp = -(-d // LANES) * LANES
    rows = max(int(block_rows), LANES)
    np_ = -(-n // rows) * rows
    xp = _pad_to(_pad_to(x.astype(jnp.float32), np_, 0), dp, 1)
    mp = _pad_to(mask.astype(jnp.float32), np_, 0)
    wp = _pad_to(w.astype(jnp.float32), dp, 0)
    interpret = jax.default_backend() == "cpu"
    p = glm_predict_pallas(xp, wp, mp, act, block_rows=rows, interpret=interpret)
    return p[:n]


@partial(jax.jit, static_argnames=("act", "use_kernel", "block_rows"))
def _glm_predict(x, w, mask, act, use_kernel, block_rows):
    return glm_predict_traced(x, w, mask, act, use_kernel, block_rows)


def glm_predict(x, w, mask=None, act: str = "linear",
                use_kernel: bool | None = None, block_rows: int = 128):
    """Batch GLM scoring (standalone jitted dispatch): (N,) predictions."""
    if mask is None:
        mask = jnp.ones(x.shape[0], dtype=jnp.float32)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    return _glm_predict(x, w, mask, act, bool(use_kernel), int(block_rows))


def glm_grad_sharded(x, y, w, mask=None, act: str = "linear", *,
                     data_axes: tuple[str, ...] = (),
                     model_axis: str | None = None,
                     use_kernel: bool | None = None, block_rows: int = 128):
    """Cross-device merged GLM gradient — call inside ``jax.shard_map``.

    Without a model axis each device runs the per-core fused datapath
    (``glm_grad``, i.e. the Pallas kernel on TPU) on its local tuple shard
    and the tree-bus merge becomes a ``psum`` over the data axes. With a
    model axis the coefficient vector is feature-partitioned: the hypothesis
    ``z = X·w`` is assembled by a feature-dim ``psum`` (row-parallel linear),
    the error is computed redundantly per feature shard, and the returned
    gradient shard stays local to the feature partition — only the data-axis
    merge crosses devices.
    """
    if mask is None:
        mask = jnp.ones(x.shape[0], dtype=jnp.float32)
    if model_axis is None:
        g = glm_grad(x, y, w, mask, act=act, use_kernel=use_kernel,
                     block_rows=block_rows)
    else:
        # the fused kernel keeps z internal; the feature-dim psum must run
        # between the two matmuls, so the model-sharded path is two MXU dots
        xf = x.astype(jnp.float32)
        z = jax.lax.psum(
            jnp.dot(xf, w.astype(jnp.float32), precision=ref.HIGHEST),
            model_axis,
        )
        e = ref.glm_error(z, y.astype(jnp.float32), act) * mask.astype(jnp.float32)
        g = jnp.dot(e, xf, precision=ref.HIGHEST)
    if data_axes:
        g = jax.lax.psum(g, tuple(data_axes))
    return g
