"""Block-table-aware paged decode attention Pallas kernel (TPU target).

The serving analogue of DAnA's access engine walking page layouts directly:
instead of gathering a padded ``(T, nb*bs)`` K/V view (the oracle in
``ref.py``), the kernel's grid walks each token's *mapped* blocks through a
scalar-prefetched block table — the physical block id feeds the K/V
BlockSpec index maps, so only the pages a sequence actually owns are ever
touched, and blocks past the token's position are skipped entirely
(``pl.when`` on the block's first logical row vs the position).

Grid: ``(T, nb_slot)`` — one token per outer step, inner walk over that
token's table row. Each step DMAs one whole pool block — every kv head of
it — in the pool's own ``(NB, bs, KVH, D)`` layout, so the pool is never
copied or transposed; the kernel then loops over the kv heads. Online-softmax
state (running max, sum, value accumulator per kv head) lives in VMEM
scratch, revisited across the sequential inner walk and flushed to the
output block on the last step.

K may come in parts that split the feature dim (the absorbed-MLA decode's
latent and rope pools): scores are the sum of the parts' dot products, so
the parts are never concatenated. A part may be a headless ``(NB, bs, D)``
pool (one kv head). ``v_pool=None`` reads the values from the first K part
(MLA: the latent cache is both K and V). Blocks span whole trailing dims,
which is what the TPU compiler's tiling rule accepts for any head count,
head dim or block size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e9
HIGHEST = jax.lax.Precision.HIGHEST


def _as_parts(x) -> tuple:
    return tuple(x) if isinstance(x, (tuple, list)) else (x,)


def _head(ref, h):
    """(bs, D) rows of kv head ``h`` from a block ref: (1, bs, KVH, D), or
    (1, bs, D) for a headless pool."""
    return ref[0] if len(ref.shape) == 3 else ref[0, :, h, :]


def _scores(q, k):
    """(G, D) x (bs, D) -> (G, bs) f32 scores. Products of two bf16 values
    are exact in f32, so bf16 operands take one MXU pass with f32
    accumulation; wider operands run at full f32 precision."""
    dims = (((1,), (1,)), ((), ()))
    if q.dtype == k.dtype == jnp.bfloat16:
        return jax.lax.dot_general(q, k, dims,
                                   preferred_element_type=jnp.float32)
    return jax.lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                               dims, precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _paged_attn_kernel(table_ref, pos_ref, *refs, n_parts: int, has_v: bool,
                       block_size: int, ring_width: int, max_rows: int,
                       scale: float, nb_slot: int):
    q_refs = refs[:n_parts]
    k_refs = refs[n_parts:2 * n_parts]
    v_ref = refs[2 * n_parts] if has_v else k_refs[0]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * n_parts + int(has_v):]
    kvh = q_refs[0].shape[1]
    t = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p = pos_ref[t]
    # last logical row this token may read: its own position in the full
    # region (clamped to max_rows), the whole ring once warm
    if ring_width:
        last = jnp.where(p >= ring_width, ring_width - 1, p)
    else:
        last = jnp.minimum(p, max_rows - 1)

    @pl.when(j * block_size <= last)
    def _block():
        rows = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1
        )
        if ring_width:
            valid = (rows < ring_width) & ((rows <= p) | (p >= ring_width))
        else:
            valid = (rows <= p) & (rows < max_rows)
        for h in range(kvh):
            s = sum(
                _scores(qr[0, h], _head(kr, h))  # (G, Dk_i) x (bs, Dk_i)
                for qr, kr in zip(q_refs, k_refs)
            ) * scale                                         # (G, bs)
            s = jnp.where(valid, s, NEG)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            probs = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(probs, axis=-1,
                                                  keepdims=True)
            # probs are f32: this dot needs full f32 precision
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                probs, _head(v_ref, h).astype(jnp.float32),
                (((1,), (0,)), ((), ())), precision=HIGHEST,
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = m_new

    @pl.when(j == nb_slot - 1)
    def _flush():
        o_ref[0] = acc_ref[...] / l_ref[...]


def _pool_spec(pool):
    """BlockSpec of one whole pool block, addressed through the table."""
    if pool.ndim == 3:
        return pl.BlockSpec((1,) + pool.shape[1:],
                            lambda ti, j, tbl, ps: (tbl[ti, j], 0, 0))
    return pl.BlockSpec((1,) + pool.shape[1:],
                        lambda ti, j, tbl, ps: (tbl[ti, j], 0, 0, 0))


def paged_attn_pallas(q, k_pool, v_pool, table, pos, *, block_size: int,
                      ring_width: int = 0, max_rows: int, scale: float,
                      interpret: bool = False):
    """q (T, KVH, G, Dk) — or a tuple of parts splitting Dk; k_pool (NB, bs,
    KVH, Dk) — or matching parts, each possibly headless (NB, bs, Dk_i);
    v_pool (NB, bs, KVH, Dv), or None for values = the first K part;
    table (T, nb_slot) int32; pos (T,) int32. Returns (T, KVH, G, Dv) f32."""
    qs, ks = _as_parts(q), _as_parts(k_pool)
    assert len(qs) == len(ks), "one query part per K part"
    t, kvh, g, _ = qs[0].shape
    v = ks[0] if v_pool is None else v_pool
    dv = v.shape[-1]
    nb_slot = table.shape[1]
    kernel = functools.partial(
        _paged_attn_kernel, n_parts=len(qs), has_v=v_pool is not None,
        block_size=block_size, ring_width=ring_width, max_rows=max_rows,
        scale=scale, nb_slot=nb_slot,
    )
    pools = ks + (() if v_pool is None else (v_pool,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t, nb_slot),
        in_specs=[
            pl.BlockSpec((1,) + qi.shape[1:],
                         lambda ti, j, tbl, ps: (ti, 0, 0, 0))
            for qi in qs
        ] + [_pool_spec(pool) for pool in pools],
        out_specs=pl.BlockSpec((1, kvh, g, dv),
                               lambda ti, j, tbl, ps: (ti, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, g, 1), jnp.float32),   # running max
            pltpu.VMEM((kvh, g, 1), jnp.float32),   # running denominator
            pltpu.VMEM((kvh, g, dv), jnp.float32),  # value accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, kvh, g, dv), jnp.float32),
        interpret=interpret,
        name="paged_attention",
    )(table, pos, *qs, *pools)
