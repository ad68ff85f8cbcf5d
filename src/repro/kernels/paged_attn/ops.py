"""Jitted public wrapper for the paged-attention kernel: dispatches kernel vs.
oracle per backend.

Same contract as ``kernels/engine/ops.py``: ``use_kernel=None`` runs the
Pallas kernel on TPU — compiled natively, blocks span whole trailing dims so
no head count, head dim or block size needs padding — and the gather oracle
on CPU (identical math; the kernel itself is exercised in interpret mode by
the test suite, and callers can force it with ``use_kernel=True``)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.paged_attn import ref
from repro.kernels.paged_attn.kernel import paged_attn_pallas


def _default_use_kernel() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("block_size", "ring_width", "max_rows",
                                   "scale", "use_kernel"))
def _paged_attn(q, k_pool, v_pool, table, pos, block_size, ring_width,
                max_rows, scale, use_kernel):
    kw = dict(block_size=block_size, ring_width=ring_width,
              max_rows=max_rows, scale=scale)
    if not use_kernel:
        return ref.paged_attn_ref(q, k_pool, v_pool, table, pos, **kw)
    return paged_attn_pallas(q, k_pool, v_pool, table, pos,
                             interpret=jax.default_backend() == "cpu", **kw)


def paged_attention(q, k_pool, v_pool, table, pos, *, block_size: int,
                    ring_width: int = 0, max_rows: int, scale: float,
                    use_kernel: bool | None = None):
    """Block-table paged decode attention.

    q (T, KVH, G, Dk) queries (G query heads per kv head); k_pool/v_pool
    (NB, bs, KVH, D*) block pools; table (T, nb_slot) int32 physical block
    ids per token (unmapped entries clamped to 0 — reads through them are
    masked); pos (T,) int32 positions. ``ring_width`` > 0 selects SWA ring
    validity (logical rows are ``pos % ring_width``). The absorbed MLA
    decode passes KVH=1, G=n_heads, q and k_pool as (latent, rope) part
    pairs over headless (NB, bs, D_i) pools, and ``v_pool=None`` (values are
    the latent pool). Returns (T, KVH, G, Dv) float32.
    """
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    return _paged_attn(q, k_pool, v_pool, jnp.asarray(table, jnp.int32),
                       jnp.asarray(pos, jnp.int32), int(block_size),
                       int(ring_width), int(max_rows), float(scale),
                       bool(use_kernel))
