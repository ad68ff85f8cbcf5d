"""Gather oracle for block-table paged decode attention.

Exactly the math ``models/attention.py`` has always used for paged decode:
gather every table-mapped block into a padded ``(T, nb*bs, KVH, D)`` view,
mask invalid rows to NEG (which softmaxes to exactly 0.0 in f32), and run a
plain softmax attention. The Pallas kernel in ``kernel.py`` must match this
oracle on every mapped-block pattern — partial trailing blocks, recycled
(re-mapped, stale-content) blocks, and SWA ring rows included. It takes the
kernel's argument forms: K split into parts (absorbed MLA: latent + rope
pools), headless pools, and values read from the first K part.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e9
HIGHEST = jax.lax.Precision.HIGHEST  # this is the float32 reference


def paged_valid(pos, s_pad, ring_width: int, max_rows: int):
    """(T, s_pad) bool validity over the gathered (ring-ordered for SWA)
    view. Full region: rows <= pos and rows < max_rows. Ring region: rows <=
    pos while cold, every ring row once warm, gather padding always dead."""
    kpos = jnp.arange(s_pad)[None, :]
    if ring_width:
        return (kpos < ring_width) & (
            (kpos <= pos[:, None]) | (pos[:, None] >= ring_width)
        )
    return (kpos <= pos[:, None]) & (kpos < max_rows)


def _gather(pool, table, kvh):
    """Pool blocks mapped by ``table`` as a (T, nb*bs, KVH, D) view (a
    headless (NB, bs, D) pool is one kv head)."""
    x = pool[table]
    if x.ndim == 4:
        x = x[:, :, :, None, :]
    return x.reshape(table.shape[0], -1, kvh, x.shape[-1]).astype(jnp.float32)


def paged_attn_ref(q, k_pool, v_pool, table, pos, *, block_size: int,
                   ring_width: int = 0, max_rows: int, scale: float):
    """q (T, KVH, G, Dk) or a tuple of parts splitting Dk; k_pool (NB, bs,
    KVH, Dk) or matching parts, each possibly headless (NB, bs, Dk_i);
    v_pool (NB, bs, KVH, Dv), or None for values = the first K part; table
    (T, nb_slot) int32 physical block ids; pos (T,) int32 positions.
    Returns (T, KVH, G, Dv) float32."""
    qs = tuple(q) if isinstance(q, (tuple, list)) else (q,)
    ks = tuple(k_pool) if isinstance(k_pool, (tuple, list)) else (k_pool,)
    kvh = qs[0].shape[1]
    gv = _gather(ks[0] if v_pool is None else v_pool, table, kvh)
    scores = sum(
        jnp.einsum("tkgd,tskd->tkgs", qi.astype(jnp.float32),
                   _gather(ki, table, kvh), precision=HIGHEST)
        for qi, ki in zip(qs, ks)
    ) * scale
    valid = paged_valid(pos, gv.shape[1], ring_width, max_rows)
    scores = scores + jnp.where(valid, 0.0, NEG)[:, None, None, :]
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("tkgs,tskd->tkgd", probs, gv, precision=HIGHEST)
