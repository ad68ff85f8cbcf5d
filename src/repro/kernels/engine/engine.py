"""Fused GLM execution-engine Pallas kernel (TPU target).

The specialized datapath DAnA's hardware generator would synthesize for a
GLM-matching hDFG, adapted to the MXU: one kernel fuses the whole multi-
threaded update batch — hypothesis (X·w), error (activation - label), and the
tree-bus merge (Xᵀe accumulated across row tiles) — so per-tuple intermediates
never leave VMEM.

Tiling: grid over row blocks of TB tuples. Per step the kernel holds an
(TB, D) feature tile, the (D,) weight vector, and a (D,) gradient accumulator
in VMEM; the accumulator block is revisited every step (sequential TPU grid)
and initialized on step 0. D and TB are padded to the 128-lane boundary by
ops.py so both matmuls hit the MXU at full tile occupancy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.engine.ref import HIGHEST, glm_act, glm_error


def _hypothesis(x, w):
    """z = w·Xᵀ as a lane-dense (1, TB) row: both MXU dots stay 2-D, which
    is what the TPU compiler accepts (a 1-D contraction over the row axis
    is refused)."""
    return jax.lax.dot_general(
        w, x, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _glm_kernel(x_ref, y_ref, w_ref, mask_ref, out_ref, *, act: str):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]  # (TB, D) f32
    z = _hypothesis(x, w_ref[...])  # (1, TB)
    e = glm_error(z, y_ref[...], act) * mask_ref[...]  # (1, TB)
    out_ref[...] += jax.lax.dot_general(
        e, x, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (1, D)


def glm_grad_pallas(
    x: jnp.ndarray,
    y: jnp.ndarray,
    w: jnp.ndarray,
    mask: jnp.ndarray,
    act: str,
    block_rows: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """x (N, D), y (N,), w (D,), mask (N,) — all padded; returns (D,) grad."""
    n, d = x.shape
    assert n % block_rows == 0, "pad rows to the block size first"
    grid = (n // block_rows,)
    kernel = functools.partial(_glm_kernel, act=act)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
        name="glm_grad",
    )(x, y[None, :], w[None, :], mask[None, :])
    return out[0]


def _glm_predict_kernel(x_ref, w_ref, mask_ref, out_ref, *, act: str):
    z = _hypothesis(x_ref[...], w_ref[...])  # (1, TB)
    out_ref[...] = jnp.where(mask_ref[...] > 0.0, glm_act(z, act), 0.0)


def glm_predict_pallas(
    x: jnp.ndarray,
    w: jnp.ndarray,
    mask: jnp.ndarray,
    act: str,
    block_rows: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Scoring datapath: x (N, D), w (D,), mask (N,) — all padded; returns
    (N,) per-row predictions act(X·w). Same row tiling as the gradient kernel
    but no accumulator — each grid step writes its own output tile, so the
    batch scoring query is one embarrassingly row-parallel pass."""
    n, d = x.shape
    assert n % block_rows == 0, "pad rows to the block size first"
    grid = (n // block_rows,)
    kernel = functools.partial(_glm_predict_kernel, act=act)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_rows), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
        name="glm_predict",
    )(x, w[None, :], mask[None, :])
    return out[0]
