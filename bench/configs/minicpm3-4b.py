"""Plain float32 reference for MiniCPM3-4B as the configuration file states it
(``bench/configs/minicpm3-4b.json``): a pre-norm decoder of multi-head latent
attention (MLA) and SwiGLU layers, tied embeddings.

Per layer, from the published description (hf:openbmb/MiniCPM3-4B):
  h = rms(x) * g1
  q = rms(h Wq_a) * gq @ Wq_b            -> per head [q_nope (64) | q_rope (32)]
  kv = h Wkv_a                           -> [c (256) | k_rope (32)], c = rms(c) * gkv
  k_nope, v = c @ Wkv_b                  -> per head 64 + 64
  q_rope, k_rope rotated (RoPE, theta 10^4, halves), k_rope shared by heads
  a = softmax((q_nope.k_nope + q_rope.k_rope) / sqrt(96), causal) v
  x = x + a Wo;  x = x + (silu(rms(x) g2 Wg) * (rms(x) g2 Wi)) Wo2
logits = rms(x) gf @ E^T. Norm gains are stored as (g - 1).

Departures from the published model, each listed under ``departures`` in the
configuration file, which keeps the published values: no ``scale_emb`` on the
embeddings, no
``scale_depth / sqrt(L)`` on the residual branches, no division of the last
hidden state by ``hidden_size / dim_model_base``, and plain RoPE without the
``longrope`` frequency factors. The program runs the same departures.

Weights are float32 upcasts of the bfloat16 weights the benchmark draws from
the seed; every matmul runs at HIGHEST. The whole sequence is one causal
forward (no cache): the serving path's prefill-then-decode through the paged
pool must agree with it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-5


def init_std(path: tuple[str, ...], shape: tuple[int, ...], cfg: dict) -> float:
    """Standard deviation of one weight leaf (0: zeros). Matrices draw
    N(0, 1/fan_in) over the axes their product contracts; the tied embedding
    N(0, 1/hidden_size) (its fan-in as the output head); norm gains 1."""
    name, parent = path[-1], path[-2] if len(path) > 1 else ""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    if name == "scale":
        return 0.0
    fan_in = {
        "table": d,
        "wq_a": d, "wkv_a": d,
        "wq_b": cfg["q_lora_rank"], "wkv_b": cfg["kv_lora_rank"],
        "wi": d, "wg": d,
    }.get(name)
    if name == "wo":
        fan_in = (cfg["num_attention_heads"] * cfg["v_head_dim"]
                  if parent == "attn" else ff)
    if fan_in is None:
        raise KeyError(f"no initialisation rule for weight {'/'.join(path)}")
    return float(fan_in) ** -0.5


def _rms(x, g):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)
    return x * (1.0 + g)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


@partial(jax.jit, static_argnames=("nope", "theta"))
def _layer(lp, x, pos, n_valid, nope: int, theta: float):
    f = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    at = f["attn"]
    t = x.shape[0]
    h = _rms(x, f["ln1"]["scale"])
    cq = _rms(_dot("td,dr->tr", h, at["wq_a"]), at["q_norm"]["scale"])
    q = _dot("tr,rhk->thk", cq, at["wq_b"])
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, theta)
    kv = _dot("td,dr->tr", h, at["wkv_a"])
    r = at["kv_norm"]["scale"].shape[-1]
    c = _rms(kv[:, :r], at["kv_norm"]["scale"])
    k_rope = _rope(kv[:, r:], pos, theta)
    kvb = _dot("tr,rhk->thk", c, at["wkv_b"])
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    scale = 1.0 / np.sqrt(nope + k_rope.shape[-1])
    s = (_dot("qhk,shk->hqs", q_nope, k_nope)
         + _dot("qhk,sk->hqs", q_rope, k_rope)) * scale
    causal = (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]) \
        & (jnp.arange(t)[None, :] < n_valid)
    s = jnp.where(causal[None], s, -jnp.inf)
    a = _dot("hqs,shv->qhv", jax.nn.softmax(s, axis=-1), v)
    x = x + _dot("qhv,hvd->qd", a, at["wo"])
    h = _rms(x, f["ln2"]["scale"])
    m = f["mlp"]
    hid = jax.nn.silu(_dot("td,df->tf", h, m["wg"])) * _dot("td,df->tf", h, m["wi"])
    return x + _dot("tf,fd->td", hid, m["wo"])


@jax.jit
def _head(final_scale, table, x, rows):
    h = _rms(x[rows], final_scale.astype(jnp.float32))
    return _dot("td,vd->tv", h, table.astype(jnp.float32))


def _bucket(n: int) -> int:
    """Pad sequences to a few lengths, so the reference compiles a few
    programs and finds them in the cache on later runs."""
    b = 64
    while b < n:
        b *= 2
    return b


def logits(params, tokens, rows, cfg: dict, transform=None):
    """Float32 logits (len(rows), vocab) of one causal forward over
    ``tokens`` at positions ``rows``. ``transform`` maps each weight leaf
    before use (the control quantizes them)."""
    tf = transform or (lambda a: a)
    n = len(tokens)
    t = _bucket(n)
    tok = np.zeros(t, np.int32)
    tok[:n] = tokens
    table = tf(params["embed"]["table"])
    x = table[jnp.asarray(tok)].astype(jnp.float32)
    pos = jnp.arange(t, dtype=jnp.int32)
    seg = params["segments"][0]
    n_layers = jax.tree.leaves(seg)[0].shape[0]
    for li in range(n_layers):
        lp = jax.tree.map(lambda a: tf(a[li]), seg)
        x = _layer(lp, x, pos, n, nope=cfg["qk_nope_head_dim"],
                   theta=float(cfg["rope_theta"]))
    out = _head(tf(params["final_norm"]["scale"]), table, x,
                jnp.asarray(np.asarray(rows, np.int32)))
    return out[:, :cfg["vocab_size"]]


def fp8_weights(a):
    """The control's weights: float8 (e4m3) with one absmax scale a tensor,
    dequantized to float32 — the step below the served bfloat16."""
    if a.ndim < 2:
        return a
    a32 = a.astype(jnp.float32)
    s = jnp.max(jnp.abs(a32)) / 448.0
    return (a32 / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


# -- counts from the configuration's shapes (for mfu.serve and the kernel's
# roofline share) ---------------------------------------------------------------
def flops_per_pass(cfg: dict, p: np.ndarray) -> np.ndarray:
    """Model FLOPs of one forward pass at position ``p`` (attending p + 1
    rows), in the absorbed-latent form a decode needs: every weight matmul,
    the query and value absorption, and attention over the latent and rope
    rows. Embedding lookups count nothing; the tied output head counts."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    ql, nope, rope = cfg["q_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd = cfg["v_head_dim"]
    macs = (d * ql + ql * h * (nope + rope) + d * (r + rope)
            + h * nope * r + h * r * vd + h * vd * d + 3 * d * ff)
    attn = np.asarray(p, np.float64) + 1
    per_layer = 2.0 * macs + 2.0 * h * attn * (r + rope) + 2.0 * h * attn * r
    return cfg["num_hidden_layers"] * per_layer + 2.0 * d * cfg["vocab_size"]


def attention_counts(cfg: dict, p: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(FLOPs, bytes) the paged-attention kernel needs for one token at
    position ``p`` in one layer: scores over p + 1 latent + rope rows and
    the value sum over the latent rows; bytes are those rows (bfloat16, read
    once), the bfloat16 query parts and the float32 output."""
    h, r, rope = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    rows = np.asarray(p, np.float64) + 1
    flops = 2.0 * h * rows * (r + rope) + 2.0 * h * rows * r
    nbytes = rows * (r + rope) * 2 + h * (r + rope) * 2 + h * r * 4
    return flops, nbytes
