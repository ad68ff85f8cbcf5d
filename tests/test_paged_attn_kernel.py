"""Block-table paged-attention Pallas kernel: interpret-mode validation vs.
the gather oracle, swept over head layouts (GQA / MLA-as-MQA), block tables
(partial trailing blocks, recycled / permuted physical ids), SWA rings
(cold and warm), dtypes, and the ops-layer padding path; plus end-to-end
parity of ``attn_impl="pallas"`` against the gather path inside
``gqa_decode_paged`` / ``mla_decode_paged``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.kernels.paged_attn import ops, ref
from repro.kernels.paged_attn.kernel import paged_attn_pallas


def _case(t=5, kvh=2, g=3, dk=8, dv=8, nb_slot=4, bs=4, num_blocks=32,
          ring_width=0, seed=0, dtype=np.float32, shuffle_table=True):
    """Random q/pools + a table whose rows are distinct permuted physical
    blocks (recycled-pool realism: nothing is block-id ordered) and positions
    spanning empty, mid-block, block-boundary, and full coverage."""
    rng = np.random.default_rng(seed)
    q = rng.normal(0, 1, (t, kvh, g, dk)).astype(dtype)
    k = rng.normal(0, 1, (num_blocks, bs, kvh, dk)).astype(dtype)
    v = rng.normal(0, 1, (num_blocks, bs, kvh, dv)).astype(dtype)
    if shuffle_table:
        ids = rng.permutation(num_blocks)[: t * nb_slot]
        table = ids.reshape(t, nb_slot).astype(np.int32)
    else:
        table = np.arange(t * nb_slot, dtype=np.int32).reshape(t, nb_slot)
    max_rows = (nb_slot * bs) if ring_width == 0 else None
    span = ring_width if ring_width else max_rows
    pos = np.minimum(
        np.array([0, 1, bs - 1, bs, span - 1] * (t // 5 + 1))[:t], span - 1
    ).astype(np.int32) if span > 1 else np.zeros(t, np.int32)
    return q, k, v, table, pos, (max_rows or nb_slot * bs)


def _run_both(q, k, v, table, pos, bs, ring_width, max_rows, scale=0.37):
    want = ref.paged_attn_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(table),
        jnp.asarray(pos), block_size=bs, ring_width=ring_width,
        max_rows=max_rows, scale=scale,
    )
    got = paged_attn_pallas(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(table, dtype=jnp.int32), jnp.asarray(pos, jnp.int32),
        block_size=bs, ring_width=ring_width, max_rows=max_rows, scale=scale,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kvh,g,dk,dv", [
    (2, 3, 8, 8),     # GQA: several kv heads, grouped queries
    (1, 6, 24, 16),   # MLA-as-MQA: one kv head, Dk (lora+rope) != Dv (lora)
    (4, 1, 8, 8),     # MHA-as-GQA degenerate group
])
def test_kernel_matches_oracle_head_layouts(kvh, g, dk, dv):
    q, k, v, table, pos, max_rows = _case(kvh=kvh, g=g, dk=dk, dv=dv)
    _run_both(q, k, v, table, pos, bs=4, ring_width=0, max_rows=max_rows)


@pytest.mark.parametrize("bs,nb_slot", [(1, 3), (3, 5), (4, 1), (5, 4)])
def test_kernel_block_geometries(bs, nb_slot):
    """Odd block sizes and single-block tables, positions hitting partial
    trailing blocks."""
    q, k, v, table, pos, max_rows = _case(
        t=6, bs=bs, nb_slot=nb_slot, num_blocks=max(32, 6 * nb_slot), seed=2
    )
    _run_both(q, k, v, table, pos, bs=bs, ring_width=0, max_rows=max_rows)


@pytest.mark.parametrize("ring_width", [4, 6])
def test_kernel_swa_ring_cold_and_warm(ring_width):
    """Ring validity: cold positions read rows <= pos; warm positions read
    the whole ring (rows hold a rotating window, all valid)."""
    t, bs = 6, 2
    nb_slot = -(-ring_width // bs)
    q, k, v, table, _, _ = _case(t=t, bs=bs, nb_slot=nb_slot, seed=3)
    # straddle the warm boundary explicitly, incl. far past it
    pos = np.array([0, 1, ring_width - 1, ring_width, ring_width + 7, 3],
                   np.int32)
    _run_both(q, k, v, table, pos, bs=bs, ring_width=ring_width,
              max_rows=nb_slot * bs)


def test_kernel_max_rows_clips_trailing_block():
    """max_rows < nb_slot * bs: rows past the cap are invalid even when the
    block is mapped and pos points past the cap."""
    q, k, v, table, _, _ = _case(t=4, bs=4, nb_slot=3, seed=4)
    pos = np.array([9, 10, 11, 11], np.int32)
    _run_both(q, k, v, table, pos, bs=4, ring_width=0, max_rows=10)


def test_kernel_bf16_pools():
    q, k, v, table, pos, max_rows = _case(seed=5)
    q, k, v = (jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))
    _run_both(q, k, v, table, pos, bs=4, ring_width=0, max_rows=max_rows)


def test_kernel_shared_blocks_across_tokens():
    """Several tokens of one slot share a table row (the serving layout:
    per-token tables are the slot's table repeated) — each reads through the
    same physical blocks at its own position."""
    q, k, v, _, _, _ = _case(t=6, seed=6)
    table = np.tile(np.array([[7, 3, 11, 0]], np.int32), (6, 1))
    pos = np.array([0, 3, 4, 7, 12, 15], np.int32)
    _run_both(q, k, v, table, pos, bs=4, ring_width=0, max_rows=16)


@pytest.mark.parametrize("ring_width", [0, 6])
def test_kernel_k_parts_headless_pools(ring_width):
    """The absorbed-MLA argument form: K split into a latent and a rope part
    over headless (NB, bs, D_i) pools, values read from the first K part —
    the kernel matches the oracle given the same parts, and the oracle
    matches plain attention over the concatenated K."""
    t, g, bs, nb_slot, num_blocks = 6, 5, 2, 4, 32
    rng = np.random.default_rng(8)
    q_lat = rng.normal(0, 1, (t, 1, g, 16)).astype(np.float32)
    q_rope = rng.normal(0, 1, (t, 1, g, 4)).astype(np.float32)
    c = rng.normal(0, 1, (num_blocks, bs, 16)).astype(np.float32)
    kr = rng.normal(0, 1, (num_blocks, bs, 4)).astype(np.float32)
    table = rng.permutation(num_blocks)[: t * nb_slot].reshape(t, nb_slot)
    pos = np.array([0, 1, 3, 4, 7, 12], np.int32)
    kw = dict(block_size=bs, ring_width=ring_width, max_rows=nb_slot * bs,
              scale=0.3)
    args = ((q_lat, q_rope), (c, kr), None, jnp.asarray(table, jnp.int32),
            jnp.asarray(pos))
    got = paged_attn_pallas(*args, interpret=True, **kw)
    want = ref.paged_attn_ref(*args, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    concat = ref.paged_attn_ref(
        np.concatenate([q_lat, q_rope], -1),
        np.concatenate([c, kr], -1)[:, :, None, :], c[:, :, None, :],
        jnp.asarray(table, jnp.int32), jnp.asarray(pos), **kw,
    )
    np.testing.assert_allclose(np.asarray(want), np.asarray(concat),
                               rtol=2e-5, atol=2e-5)


def test_ops_padding_and_dispatch():
    """Odd G/Dk/Dv go through the jitted wrapper unpadded (blocks span whole
    trailing dims); forced kernel and oracle dispatch agree."""
    q, k, v, table, pos, max_rows = _case(t=3, kvh=2, g=3, dk=5, dv=7, seed=7)
    kw = dict(block_size=4, ring_width=0, max_rows=max_rows, scale=0.21)
    got = ops.paged_attention(q, k, v, table, pos, use_kernel=True, **kw)
    want = ops.paged_attention(q, k, v, table, pos, use_kernel=False, **kw)
    assert got.shape == (3, 2, 3, 7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ops_default_backend_dispatch(monkeypatch):
    """use_kernel=None resolves per backend: oracle on CPU, kernel on TPU."""
    assert ops._default_use_kernel() == (jax.default_backend() == "tpu")


# ---------------------------------------------------------------------------
# End-to-end: attn_impl="pallas" inside the decode attention modules
# ---------------------------------------------------------------------------
def _forced_kernel(monkeypatch):
    monkeypatch.setattr(ops, "_default_use_kernel", lambda: True)


def _attn_params(cfg, key):
    from repro.models import attention as attn
    from repro.models.params import Maker, split_tree

    m = Maker(key)
    made = attn.make_mla(m, cfg) if cfg.attn_kind == "mla" \
        else attn.make_gqa(m, cfg)
    params, _ = split_tree(made)
    return params


@pytest.mark.parametrize("arch", ["internlm2-20b", "minicpm3-4b"])
def test_decode_paged_pallas_matches_gather(monkeypatch, arch):
    """gqa/mla_decode_paged with impl='pallas' (kernel forced, interpret on
    CPU) tracks impl='gather' through the full module — projections, scatter,
    absorbed-MLA mapping, output projection — on recycled block tables."""
    from repro.models import attention as attn

    _forced_kernel(monkeypatch)
    cfg = get_reduced_config(arch)
    b, bs, nb_slot, num_blocks = 3, 4, 3, 16
    max_seq = bs * nb_slot
    key = jax.random.PRNGKey(11)
    kp, kx, kc = jax.random.split(key, 3)
    x = jax.random.normal(kx, (b, 1, cfg.d_model)) * 0.2
    pos = jnp.asarray([0, 5, max_seq - 1], jnp.int32)
    rng = np.random.default_rng(12)
    table = jnp.asarray(
        rng.permutation(num_blocks)[: b * nb_slot].reshape(b, nb_slot),
        jnp.int32,
    )
    if cfg.attn_kind == "mla":
        p = _attn_params(cfg, kp)
        cache = {
            "c": jax.random.normal(
                kc, (num_blocks, bs, cfg.kv_lora_rank), jnp.bfloat16) * 0.1,
            "kr": jax.random.normal(
                kc, (num_blocks, bs, cfg.qk_rope_head_dim),
                jnp.bfloat16) * 0.1,
        }
        run = lambda impl: attn.mla_decode_paged(
            p, x, cache, pos, cfg, table=table, block_size=bs,
            max_seq=max_seq, write_ok=jnp.asarray([True, True, True]),
            impl=impl,
        )
    else:
        p = _attn_params(cfg, kp)
        cache = {
            "k": jax.random.normal(
                kc, (num_blocks, bs, cfg.n_kv_heads, cfg.hd),
                jnp.bfloat16) * 0.1,
            "v": jax.random.normal(
                kc, (num_blocks, bs, cfg.n_kv_heads, cfg.hd),
                jnp.bfloat16) * 0.1,
        }
        run = lambda impl: attn.gqa_decode_paged(
            p, x, cache, pos, cfg, table=table, block_size=bs,
            max_seq=max_seq, write_ok=jnp.asarray([True, True, True]),
            impl=impl,
        )
    out_k, cache_k = run("pallas")
    out_g, cache_g = run("gather")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_g),
                               rtol=2e-5, atol=2e-5)
    for lk, lg in zip(jax.tree_util.tree_leaves(cache_k),
                      jax.tree_util.tree_leaves(cache_g)):
        np.testing.assert_allclose(np.asarray(lk), np.asarray(lg))


def test_gqa_paged_pallas_swa_ring(monkeypatch):
    """The kernel's ring validity inside gqa_decode_paged: a hymba-style SWA
    window served through the ring table, warm and cold slots together."""
    from repro.models import attention as attn
    from repro.models.transformer import segments_for

    _forced_kernel(monkeypatch)
    cfg = dataclasses.replace(get_reduced_config("hymba-1.5b"),
                              n_global_layers=1)
    assert any(s.kind == "hybrid_swa" for s in segments_for(cfg))
    ring_width = min(cfg.swa_window, 16)
    bs = 4
    nb_slot = -(-ring_width // bs)
    num_blocks = 12
    key = jax.random.PRNGKey(21)
    kp, kx, kc = jax.random.split(key, 3)
    p = _attn_params(cfg, kp)
    b = 2
    x = jax.random.normal(kx, (b, 1, cfg.d_model)) * 0.2
    # one cold (pos < ring) and one warm (pos >= ring) slot
    pos = jnp.asarray([2, ring_width + 5], jnp.int32)
    rng = np.random.default_rng(22)
    table = jnp.asarray(
        rng.permutation(num_blocks)[: b * nb_slot].reshape(b, nb_slot),
        jnp.int32,
    )
    cache = {
        "k": jax.random.normal(
            kc, (num_blocks, bs, cfg.n_kv_heads, cfg.hd), jnp.bfloat16) * 0.1,
        "v": jax.random.normal(
            kc, (num_blocks, bs, cfg.n_kv_heads, cfg.hd), jnp.bfloat16) * 0.1,
    }
    run = lambda impl: attn.gqa_decode_paged(
        p, x, cache, pos, cfg, table=table, block_size=bs,
        ring_width=ring_width, max_seq=nb_slot * bs,
        write_ok=jnp.asarray([True, True]), impl=impl,
    )
    out_k, _ = run("pallas")
    out_g, _ = run("gather")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_g),
                               rtol=2e-5, atol=2e-5)
