"""Attention: GQA (full/sliding-window/bidirectional), MLA (latent), decode
paths with sharded KV caches.

Decode-time design (flash-decode without shard_map): the KV cache's sequence
dimension carries the ``kv_seq`` logical axis, mapped to the ``model`` mesh
axis. Scores/softmax/value contractions over that dimension then lower to
partial reductions + small (B,H)-sized cross-shard combines under GSPMD —
the distributed flash-decode pattern — instead of ever all-gathering the
multi-GB cache.

MLA serving uses the absorbed-latent form (queries projected into the KV
latent space), so the cache is only (kv_lora + rope) wide per token — the
deployment trick that makes 32k-cache decode cheap for minicpm3/deepseek-v3.

Decode positions are per-row: every decode entry point accepts ``pos`` as a
scalar (single stream) or a (B,) vector (continuous batching — each cache
row advances at its own position, with per-row validity masks so a freed
slot restarted at pos 0 never sees the previous occupant's stale entries).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.dist.meshes import shard_act
from repro.kernels.paged_attn import ops as paged_attn_ops
from repro.models.config import ModelConfig
from repro.models.layers import apply_norm, apply_rope, make_norm, rope_tables
from repro.models.params import Maker

NEG = -1e9


def _mask(sq: int, skv: int, kind: str, window: int, offset: int = 0):
    """(sq, skv) additive mask. offset = kv position of query row 0."""
    if kind == "bidir":
        return jnp.zeros((sq, skv), jnp.float32)
    qpos = jnp.arange(sq)[:, None] + offset
    kpos = jnp.arange(skv)[None, :]
    ok = kpos <= qpos
    if kind == "swa":
        ok &= (qpos - kpos) < window
    return jnp.where(ok, 0.0, NEG).astype(jnp.float32)


def _sdpa(q, k, v, mask, scale):
    """q (B,Sq,KVH,G,D), k/v (B,Skv,KVH,D), mask (Sq,Skv) or (B,1,1,Sq,Skv)."""
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = scores + (mask if mask.ndim > 2 else mask[None, None, None])
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out


def _sdpa_qchunk(q, k, v, kind, window, scale, q_chunk, qk_bf16: bool = False):
    """Query-chunked attention (flash-style memory behavior, exact math).

    Scores materialize one (B, KVH, G, q_chunk, Skv) tile at a time inside a
    scan with a checkpointed body: peak live memory drops from O(Sq*Skv) to
    O(q_chunk*Skv) per layer, and the backward pass recomputes per tile. This
    is the §Perf lever that converts the naive-attention memory-bound cells
    to compute-bound; on TPU the tile shapes are MXU-aligned by construction
    (q_chunk multiple of 128).
    """
    b, sq, kvh, g, d = q.shape
    q_chunk = min(q_chunk, sq)
    pad = (-sq) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    nq = q.shape[1] // q_chunk
    qt = q.reshape(b, nq, q_chunk, kvh, g, d).transpose(1, 0, 2, 3, 4, 5)
    # qk_bf16: MXU-native bf16 operands with f32 accumulation — halves the
    # attention bytes; softmax statistics stay in f32
    cdt = jnp.bfloat16 if qk_bf16 else jnp.float32
    kf = k.astype(cdt)
    vf = v.astype(cdt)

    @jax.checkpoint
    def block(qb, idx):
        mask = _mask(q_chunk, kf.shape[1], kind, window, offset=idx * q_chunk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb.astype(cdt), kf,
                       preferred_element_type=jnp.float32) * scale
        s = s + mask[None, None, None]
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(cdt), vf,
                          preferred_element_type=jnp.float32)

    def body(_, inp):
        qb, idx = inp
        return None, block(qb, idx)

    _, blocks = jax.lax.scan(body, None, (qt, jnp.arange(nq)))
    dv = v.shape[-1]  # may differ from the q/k dim (MLA)
    out = blocks.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq + pad, kvh, g, dv)
    return out[:, :sq]


# =============================== GQA =========================================
def make_gqa(m: Maker, cfg: ModelConfig, d_in: int | None = None):
    d = d_in or cfg.d_model
    hd = cfg.hd
    return {
        "wq": m.param((d, cfg.n_heads, hd), ("embed", "heads", "head_dim")),
        "wk": m.param((d, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wv": m.param((d, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wo": m.param((cfg.n_heads, hd, d), ("heads", "head_dim", "embed")),
    }


def gqa_project(p, x, cfg: ModelConfig, positions):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    q = shard_act(q, ("batch", "seq", "heads", "head_dim"), "q")
    k = shard_act(k, ("batch", "seq", "kv_heads", "head_dim"), "k")
    cos, sin = rope_tables(positions, cfg.hd, cfg.rope_theta)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def gqa_attend(p, q, k, v, cfg: ModelConfig, kind, window):
    b, sq, h, hd = q.shape
    kvh = cfg.n_kv_heads
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd)
    scale = 1.0 / math.sqrt(hd)
    if cfg.attn_q_chunk:
        out = _sdpa_qchunk(qg, k, v, kind, window, scale, cfg.attn_q_chunk,
                           qk_bf16=cfg.attn_qk_bf16)
    else:
        out = _sdpa(qg, k, v, _mask(sq, k.shape[1], kind, window), scale)
    out = out.reshape(b, sq, h, hd).astype(q.dtype)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(q.dtype))
    return shard_act(out, ("batch", "seq", "embed"), "attn_out")


def gqa_train(p, x, cfg: ModelConfig, positions, kind="causal", window=0):
    q, k, v = gqa_project(p, x, cfg, positions)
    return gqa_attend(p, q, k, v, cfg, kind, window)


def _batch_pos(pos, b: int):
    """Normalize a decode position to per-row form: scalar (whole batch at one
    position, the classic single-stream case) or (B,) vector (continuous
    batching — every slot at its own position)."""
    pos = jnp.asarray(pos, jnp.int32)
    return jnp.full((b,), pos) if pos.ndim == 0 else pos


def gqa_decode(p, x, cache, pos, cfg: ModelConfig, window=0, slot=None,
               write_ok=None):
    """x (B,1,d); cache {k,v}: (B,S,KVH,D) (full) or (B,W,KVH,D) (SWA ring).
    Returns (out (B,1,d), new_cache). ``pos`` is the current position — a
    scalar, or a (B,) vector of per-slot positions (continuous batching).
    ``slot`` (B,) maps batch rows onto cache rows for the token-batched
    serving step (several tokens of one sequence flattened into the batch;
    None keeps the classic row==slot identity); ``write_ok`` (B,) bool gates
    the cache scatter (padding rows write out of range and are dropped)."""
    b = x.shape[0]
    dt = x.dtype
    pos_b = _batch_pos(pos, b)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    cos, sin = rope_tables(pos_b[:, None], cfg.hd, cfg.rope_theta)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)

    s = cache["k"].shape[1]
    nslots = cache["k"].shape[0]
    row = pos_b % s if window else jnp.minimum(pos_b, s - 1)
    rows = jnp.arange(b) if slot is None else slot
    wrow = rows if write_ok is None else jnp.where(write_ok, rows, nslots)
    ck = cache["k"].at[wrow, row].set(k[:, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[wrow, row].set(v[:, 0].astype(cache["v"].dtype))
    ck = shard_act(ck, ("batch", "kv_seq", "kv_heads", "head_dim"), "ck")
    cv = shard_act(cv, ("batch", "kv_seq", "kv_heads", "head_dim"), "cv")

    kvh, hd = cfg.n_kv_heads, cfg.hd
    g = cfg.n_heads // kvh
    # validity (per row): full caches are valid <= pos; ring buffers are fully
    # valid once warm (pos >= ring size) and valid <= pos while still cold —
    # which is also what logically invalidates a freed slot's stale entries
    # when a new request restarts the slot at pos 0
    kpos = jnp.arange(s)[None, :]
    valid = kpos <= pos_b[:, None]
    if window:
        valid |= pos_b[:, None] >= s
    gk, gv = (ck, cv) if slot is None else (ck[slot], cv[slot])
    mask = jnp.where(valid, 0.0, NEG).astype(jnp.float32)[:, None, None, None, :]
    out = _sdpa(q.reshape(b, 1, kvh, g, hd), gk, gv, mask, 1.0 / math.sqrt(hd))
    out = out.reshape(b, 1, cfg.n_heads, hd).astype(dt)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return out, {"k": ck, "v": cv}


def init_gqa_cache(cfg: ModelConfig, batch: int, seq: int, window=0,
                   abstract=False, d_in=None):
    w = min(window, seq) if window else seq
    shape = (batch, w, cfg.n_kv_heads, cfg.hd)
    if abstract:
        z = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    else:
        z = jnp.zeros(shape, jnp.bfloat16)
    return {"k": z, "v": z}


# ============================ paged decode ===================================
# Paged KV: instead of a dense per-slot row (B, S, ...), the cache is a pool
# of fixed-size token blocks (num_blocks, block_size, ...) shared by every
# slot; ``table`` (B, nb_slot) maps a slot's logical block index to a
# physical block id (serve/kv_pool.py owns the allocation). Decode writes the
# current token through the table and gathers the slot's blocks back into a
# (B, nb_slot*block_size, ...) view — the access-engine-walks-page-layouts
# pattern. Padding rows (beyond max_seq / ring width, or in not-yet-mapped
# blocks) are masked with NEG, which softmaxes to exactly 0.0 in f32, so the
# paged path is token-exact vs the dense reference.


def _paged_write_idx(table, pos_b, block_size, ring_width, num_blocks,
                     write_ok):
    """(block id, in-block offset) each row writes. ``ring_width`` > 0 maps
    positions onto ring rows ``pos % ring_width`` (SWA). Rows with
    ``write_ok`` False get an out-of-range block id — the scatter drops
    them (idle chunked-prefill rows, parked slots)."""
    row = pos_b % ring_width if ring_width else pos_b
    blk = table[jnp.arange(pos_b.shape[0]), row // block_size]
    if write_ok is not None:
        blk = jnp.where(write_ok, blk, num_blocks)
    return blk, row % block_size


def _paged_valid(pos_b, s_pad, ring_width, max_rows):
    """Per-row validity over the gathered (ring-ordered for SWA) view.
    Full region: rows <= pos. Ring region: the dense ring's exact rule —
    rows <= pos while cold, every ring row once warm — with the gather
    padding (rows >= width) always invalid."""
    kpos = jnp.arange(s_pad)[None, :]
    if ring_width:
        return (kpos < ring_width) & (
            (kpos <= pos_b[:, None]) | (pos_b[:, None] >= ring_width)
        )
    return (kpos <= pos_b[:, None]) & (kpos < max_rows)


def gqa_decode_paged(p, x, cache, pos, cfg: ModelConfig, table, block_size,
                     ring_width=0, max_seq=None, write_ok=None,
                     impl="gather"):
    """Paged variant of ``gqa_decode``: cache {k,v}: (NB, bs, KVH, D) block
    pools; ``table`` (B, nb_slot) int32. ``ring_width`` > 0 selects SWA ring
    semantics (the table then maps ring rows). ``impl`` picks the attention
    read path: ``"gather"`` (padded-view reference) or ``"pallas"`` (the
    block-walking kernel in kernels/paged_attn). Returns (out, new_cache)."""
    b = x.shape[0]
    dt = x.dtype
    pos_b = _batch_pos(pos, b)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    cos, sin = rope_tables(pos_b[:, None], cfg.hd, cfg.rope_theta)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)

    blk, off = _paged_write_idx(table, pos_b, block_size, ring_width,
                                cache["k"].shape[0], write_ok)
    ck = cache["k"].at[blk, off].set(k[:, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[blk, off].set(v[:, 0].astype(cache["v"].dtype))
    ck = shard_act(ck, ("kv_blocks", "block", "kv_heads", "head_dim"), "ck")
    cv = shard_act(cv, ("kv_blocks", "block", "kv_heads", "head_dim"), "cv")

    kvh, hd = cfg.n_kv_heads, cfg.hd
    g = cfg.n_heads // kvh
    max_rows = (max_seq if max_seq is not None
                else table.shape[1] * block_size)
    scale = 1.0 / math.sqrt(hd)
    if impl == "pallas":
        out = paged_attn_ops.paged_attention(
            q.reshape(b, kvh, g, hd), ck, cv, table, pos_b,
            block_size=block_size, ring_width=ring_width,
            max_rows=max_rows, scale=scale,
        ).reshape(b, 1, cfg.n_heads, hd)
    else:
        gk = ck[table].reshape(b, -1, kvh, hd)
        gv = cv[table].reshape(b, -1, kvh, hd)
        valid = _paged_valid(pos_b, gk.shape[1], ring_width, max_rows)
        mask = jnp.where(valid, 0.0, NEG).astype(
            jnp.float32)[:, None, None, None, :]
        out = _sdpa(q.reshape(b, 1, kvh, g, hd), gk, gv, mask, scale)
        out = out.reshape(b, 1, cfg.n_heads, hd)
    out = jnp.einsum("bshk,hkd->bsd", out.astype(dt), p["wo"].astype(dt))
    return out, {"k": ck, "v": cv}


def init_gqa_cache_paged(cfg: ModelConfig, num_blocks: int, block_size: int,
                         abstract=False):
    shape = (num_blocks, block_size, cfg.n_kv_heads, cfg.hd)
    if abstract:
        z = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    else:
        z = jnp.zeros(shape, jnp.bfloat16)
    return {"k": z, "v": z}


# =============================== MLA =========================================
def make_mla(m: Maker, cfg: ModelConfig):
    d = cfg.d_model
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    p = {}
    if cfg.q_lora_rank:
        p["wq_a"] = m.param((d, cfg.q_lora_rank), ("embed", "lora"))
        p["q_norm"] = make_norm(m, cfg.q_lora_rank)
        p["wq_b"] = m.param(
            (cfg.q_lora_rank, cfg.n_heads, qk), ("lora", "heads", "qk_dim")
        )
    else:
        p["wq"] = m.param((d, cfg.n_heads, qk), ("embed", "heads", "qk_dim"))
    p["wkv_a"] = m.param(
        (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), ("embed", "lora")
    )
    p["kv_norm"] = make_norm(m, cfg.kv_lora_rank)
    p["wkv_b"] = m.param(
        (cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim),
        ("lora", "heads", "qk_dim"),
    )
    p["wo"] = m.param(
        (cfg.n_heads, cfg.v_head_dim, d), ("heads", "head_dim", "embed")
    )
    return p


def _mla_q(p, x, cfg: ModelConfig, positions):
    dt = x.dtype
    if cfg.q_lora_rank:
        cq = jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(dt))
        cq = apply_norm(p["q_norm"], cq, cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"].astype(dt))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    qn = q[..., : cfg.qk_nope_head_dim]
    qr = q[..., cfg.qk_nope_head_dim :]
    cos, sin = rope_tables(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    qr = apply_rope(qr, cos[:, :, None, :], sin[:, :, None, :])
    return qn, qr


def _mla_latent(p, x, cfg: ModelConfig, positions):
    dt = x.dtype
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt))
    c_kv = apply_norm(p["kv_norm"], kv[..., : cfg.kv_lora_rank], cfg.norm_eps)
    kr = kv[..., cfg.kv_lora_rank :][:, :, None, :]  # single shared rope head
    cos, sin = rope_tables(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    kr = apply_rope(kr, cos[:, :, None, :], sin[:, :, None, :])
    return c_kv, kr[:, :, 0, :]


def mla_train(p, x, cfg: ModelConfig, positions, kind="causal", window=0):
    dt = x.dtype
    b, s, _ = x.shape
    qn, qr = _mla_q(p, x, cfg, positions)
    c_kv, kr = _mla_latent(p, x, cfg, positions)
    kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b"].astype(dt))
    kn = kv[..., : cfg.qk_nope_head_dim]
    v = kv[..., cfg.qk_nope_head_dim :]
    k = jnp.concatenate(
        [kn, jnp.broadcast_to(kr[:, :, None, :], (*kn.shape[:3], cfg.qk_rope_head_dim))],
        axis=-1,
    )
    q = jnp.concatenate([qn, qr], axis=-1)
    q = shard_act(q, ("batch", "seq", "heads", "qk_dim"), "mla_q")
    k = shard_act(k, ("batch", "seq", "heads", "qk_dim"), "mla_k")
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    qg = q.reshape(b, s, cfg.n_heads, 1, q.shape[-1])
    if cfg.attn_q_chunk:
        out = _sdpa_qchunk(qg, k, v, kind, window, scale, cfg.attn_q_chunk,
                           qk_bf16=cfg.attn_qk_bf16)
    else:
        out = _sdpa(qg, k, v, _mask(s, s, kind, window), scale)
    out = out.reshape(b, s, cfg.n_heads, cfg.v_head_dim)
    out = jnp.einsum("bshk,hkd->bsd", out.astype(dt), p["wo"].astype(dt))
    return shard_act(out, ("batch", "seq", "embed"), "attn_out")


def mla_decode(p, x, cache, pos, cfg: ModelConfig, slot=None, write_ok=None):
    """Absorbed-latent decode: cache {c (B,S,kv_lora), kr (B,S,rope)}.
    ``pos`` is a scalar or a (B,) vector of per-slot positions. ``slot`` /
    ``write_ok`` map a flattened token batch onto cache rows exactly as in
    ``gqa_decode``."""
    dt = x.dtype
    b = x.shape[0]
    pos_b = _batch_pos(pos, b)
    qn, qr = _mla_q(p, x, cfg, pos_b[:, None])
    c_t, kr_t = _mla_latent(p, x, cfg, pos_b[:, None])

    s = cache["c"].shape[1]
    nslots = cache["c"].shape[0]
    row = jnp.minimum(pos_b, s - 1)
    rows = jnp.arange(b) if slot is None else slot
    wrow = rows if write_ok is None else jnp.where(write_ok, rows, nslots)
    c = cache["c"].at[wrow, row].set(c_t[:, 0].astype(cache["c"].dtype))
    kr = cache["kr"].at[wrow, row].set(kr_t[:, 0].astype(cache["kr"].dtype))
    c = shard_act(c, ("batch", "kv_seq", "lora"), "mla_c")
    kr = shard_act(kr, ("batch", "kv_seq", "head_dim"), "mla_kr")
    gc, gkr = (c, kr) if slot is None else (c[slot], kr[slot])

    w_uk = p["wkv_b"][..., : cfg.qk_nope_head_dim].astype(dt)  # (r, H, nope)
    w_uv = p["wkv_b"][..., cfg.qk_nope_head_dim :].astype(dt)  # (r, H, v)
    q_lat = jnp.einsum("bthk,rhk->bthr", qn, w_uk)  # absorb: query -> latent
    scores = jnp.einsum("bthr,bsr->bhs", q_lat, gc.astype(dt))
    scores = scores + jnp.einsum("bthk,bsk->bhs", qr, gkr.astype(dt))
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    valid = jnp.arange(s)[None, :] <= pos_b[:, None]
    scores = scores.astype(jnp.float32) * scale + jnp.where(valid, 0.0, NEG)[:, None]
    probs = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhs,bsr->bhr", probs, gc.astype(jnp.float32)).astype(dt)
    out = jnp.einsum("bhr,rhv->bhv", out_lat, w_uv)
    out = jnp.einsum("bhv,hvd->bd", out, p["wo"].astype(dt))[:, None, :]
    return out, {"c": c, "kr": kr}


def mla_decode_paged(p, x, cache, pos, cfg: ModelConfig, table, block_size,
                     max_seq=None, write_ok=None, impl="gather"):
    """Paged variant of ``mla_decode``: cache {c: (NB, bs, kv_lora),
    kr: (NB, bs, rope)} block pools gathered through ``table`` (B, nb_slot).
    The latent cache has no head dim, so paging is the only sharding lever
    it gets (blocks over the data axes). ``impl="pallas"`` runs the absorbed
    attention as one MQA call on the block-walking kernel: K is the latent
    pool and the rope pool as two parts shared by every head (never
    concatenated), V is the latent pool."""
    dt = x.dtype
    b = x.shape[0]
    pos_b = _batch_pos(pos, b)
    qn, qr = _mla_q(p, x, cfg, pos_b[:, None])
    c_t, kr_t = _mla_latent(p, x, cfg, pos_b[:, None])

    blk, off = _paged_write_idx(table, pos_b, block_size, 0,
                                cache["c"].shape[0], write_ok)
    c = cache["c"].at[blk, off].set(c_t[:, 0].astype(cache["c"].dtype))
    kr = cache["kr"].at[blk, off].set(kr_t[:, 0].astype(cache["kr"].dtype))
    c = shard_act(c, ("kv_blocks", "block", "lora"), "mla_c")
    kr = shard_act(kr, ("kv_blocks", "block", "head_dim"), "mla_kr")

    w_uk = p["wkv_b"][..., : cfg.qk_nope_head_dim].astype(dt)
    w_uv = p["wkv_b"][..., cfg.qk_nope_head_dim :].astype(dt)
    q_lat = jnp.einsum("bthk,rhk->bthr", qn, w_uk)
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    max_rows = (max_seq if max_seq is not None
                else table.shape[1] * block_size)
    if impl == "pallas":
        # (b, 1, H, *) queries read as (T=b, KVH=1, G=H, *); the latent and
        # rope pools are the two K parts, the latent pool is also V
        out_lat = paged_attn_ops.paged_attention(
            (q_lat, qr), (c, kr), None, table, pos_b,
            block_size=block_size, ring_width=0, max_rows=max_rows,
            scale=scale,
        )[:, 0].astype(dt)
    else:
        gc = c[table].reshape(b, -1, cfg.kv_lora_rank)
        gkr = kr[table].reshape(b, -1, cfg.qk_rope_head_dim)
        s_pad = gc.shape[1]
        scores = jnp.einsum("bthr,bsr->bhs", q_lat, gc.astype(dt))
        scores = scores + jnp.einsum("bthk,bsk->bhs", qr, gkr.astype(dt))
        valid = _paged_valid(pos_b, s_pad, 0, max_rows)
        scores = scores.astype(jnp.float32) * scale \
            + jnp.where(valid, 0.0, NEG)[:, None]
        probs = jax.nn.softmax(scores, axis=-1)
        out_lat = jnp.einsum("bhs,bsr->bhr", probs,
                             gc.astype(jnp.float32)).astype(dt)
    out = jnp.einsum("bhr,rhv->bhv", out_lat, w_uv)
    out = jnp.einsum("bhv,hvd->bd", out, p["wo"].astype(dt))[:, None, :]
    return out, {"c": c, "kr": kr}


def init_mla_cache_paged(cfg: ModelConfig, num_blocks: int, block_size: int,
                         abstract=False):
    sc = (num_blocks, block_size, cfg.kv_lora_rank)
    sk = (num_blocks, block_size, cfg.qk_rope_head_dim)
    if abstract:
        return {
            "c": jax.ShapeDtypeStruct(sc, jnp.bfloat16),
            "kr": jax.ShapeDtypeStruct(sk, jnp.bfloat16),
        }
    return {"c": jnp.zeros(sc, jnp.bfloat16), "kr": jnp.zeros(sk, jnp.bfloat16)}


def init_mla_cache(cfg: ModelConfig, batch: int, seq: int, abstract=False):
    sc = (batch, seq, cfg.kv_lora_rank)
    sk = (batch, seq, cfg.qk_rope_head_dim)
    if abstract:
        return {
            "c": jax.ShapeDtypeStruct(sc, jnp.bfloat16),
            "kr": jax.ShapeDtypeStruct(sk, jnp.bfloat16),
        }
    return {"c": jnp.zeros(sc, jnp.bfloat16), "kr": jnp.zeros(sk, jnp.bfloat16)}


# ============================ cross-attention =================================
def make_cross(m: Maker, cfg: ModelConfig):
    return make_gqa(m, cfg)


def cross_train(p, x, enc_out, cfg: ModelConfig):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", enc_out.astype(dt), p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out.astype(dt), p["wv"].astype(dt))
    b, sq = q.shape[:2]
    kvh, hd = cfg.n_kv_heads, cfg.hd
    mask = jnp.zeros((sq, k.shape[1]), jnp.float32)
    out = _sdpa(q.reshape(b, sq, kvh, cfg.n_heads // kvh, hd), k, v, mask,
                1.0 / math.sqrt(hd))
    out = out.reshape(b, sq, cfg.n_heads, hd).astype(dt)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


def cross_decode(p, x, cross_kv, cfg: ModelConfig):
    """Decode-time cross attention against precomputed encoder K/V."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    b = q.shape[0]
    kvh, hd = cfg.n_kv_heads, cfg.hd
    mask = jnp.zeros((1, cross_kv["k"].shape[1]), jnp.float32)
    out = _sdpa(q.reshape(b, 1, kvh, cfg.n_heads // kvh, hd),
                cross_kv["k"].astype(dt), cross_kv["v"].astype(dt), mask,
                1.0 / math.sqrt(hd))
    out = out.reshape(b, 1, cfg.n_heads, hd).astype(dt)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
