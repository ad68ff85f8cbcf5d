"""Plain float32 reference for the S/N logistic deployment (paper Table 3).

The data: ``n_tuples`` rows of ``n_features`` standard-normal features and a
0/1 label ``[x . w_true / sqrt(D) + 0.1 n > 0]``, drawn on the device from
the configuration's ``data_seed`` (the same law as the program's synthetic
generator, in JAX's counter-based RNG so the reference can draw it again
after every run).

The semantics: mini-batch logistic regression as the DAnA UDF states it.
Tuples are taken in table order in batches of ``merge_coef``; each batch
sums ``(sigmoid(w . x) - y) x`` over its rows and the model steps
``w <- w - lr * (sum / merge_coef)``; a short last batch sums its rows only.
PREDICT is ``sigmoid(w . x)``. Every dot product runs at ``precision``:
``"highest"`` is float32 (HIGHEST on the TPU); the control's ``"bf16x3"``
is the TPU's HIGH written out, so it reads the same on any backend: each
operand split into a bfloat16 head and tail, and the three products other
than tail x tail summed in float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(a):
    """``a`` rounded to the nearest bfloat16, held in float32. Rounded on the
    bits, so no compiler folds it away as a float32 -> bfloat16 -> float32
    round trip (the TPU compiler does, allowing excess precision)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def dot(a, b, precision: str = "highest"):
    if precision == "highest":
        return jnp.dot(a, b, precision=HIGHEST)
    if precision != "bf16x3":
        raise ValueError(f"unknown precision {precision!r}")
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return (jnp.dot(ah, bh, precision=HIGHEST) + jnp.dot(ah, bl, precision=HIGHEST)
            + jnp.dot(al, bh, precision=HIGHEST))


def key31(seed: int):
    """A PRNG key from any whole-number seed (folded to 31 bits)."""
    s = int(np.random.SeedSequence(int(seed)).generate_state(1)[0]) & 0x7FFFFFFF
    return jax.random.PRNGKey(s)


@partial(jax.jit, static_argnames=("n", "d"))
def _generate(key, n: int, d: int):
    k_w, k_x, k_n = jax.random.split(key, 3)
    w_true = jax.random.normal(k_w, (d,), jnp.float32)
    x = jax.random.normal(k_x, (n, d), jnp.float32)
    z = jnp.dot(x, w_true, precision=HIGHEST) / np.float32(np.sqrt(d))
    y = (z + 0.1 * jax.random.normal(k_n, (n,), jnp.float32) > 0)
    return x, y.astype(jnp.float32)


def generate(cfg: dict, n: int | None = None):
    """(X (n, D), y (n,)) float32 on the device; ``n`` defaults to the
    table's tuples (a smaller ``n`` draws other rows by the same law)."""
    return _generate(key31(cfg["data_seed"]), n or cfg["n_tuples"],
                     cfg["n_features"])


def init_model(seed: int, d: int) -> np.ndarray:
    """A TRAIN statement's initial coefficients: N(0, 0.01^2) from the
    statement's seed (numpy's PCG64 stream)."""
    return np.random.default_rng(seed).normal(0, 0.01, d).astype(np.float32)


def score_model(seed: int, d: int) -> np.ndarray:
    """The coefficients a PREDICT scores: N(0, 1/D) from ``seed``, so
    ``w . x`` is about unit normal and the predictions spread over (0, 1)."""
    rng = np.random.default_rng([int(seed), 1])
    return (rng.standard_normal(d) / np.sqrt(d)).astype(np.float32)


@partial(jax.jit, static_argnames=("coef", "epochs", "precision"))
def train(x, y, w0, lr, coef: int, epochs: int, precision: str = "highest"):
    """Returns (w, per-epoch norm of the last batch's summed gradient)."""
    n, d = x.shape
    nb = -(-n // coef)
    pad = nb * coef - n
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(nb, coef, d)
    yb = jnp.pad(y, (0, pad)).reshape(nb, coef)
    mb = (jnp.arange(nb * coef) < n).astype(jnp.float32).reshape(nb, coef)

    def step(w, b):
        xx, yy, mm = b
        z = dot(xx, w, precision)
        e = (jax.nn.sigmoid(z) - yy) * mm
        g = dot(e, xx, precision)
        return w - lr * (g / coef), jnp.sqrt(jnp.sum(g * g))

    def epoch(w, _):
        w, gn = jax.lax.scan(step, w, (xb, yb, mb))
        return w, gn[-1]

    return jax.lax.scan(epoch, w0, None, length=epochs)


@partial(jax.jit, static_argnames=("precision",))
def predict(x, w, precision: str = "highest"):
    return jax.nn.sigmoid(dot(x, w, precision))


# -- counts from the configuration's shapes (for the analytics rooflines) -------
def page_bytes(cfg: dict, tuples: float) -> float:
    """Bytes of heap pages a scan of ``tuples`` tuples reads: the table's
    pages once for every pass over its tuples."""
    n_pages = -(-cfg["n_tuples"] // cfg["tuples_per_page"])
    return tuples / cfg["n_tuples"] * n_pages * cfg["page_bytes"]


def glm_flops(cfg: dict, verb: str, tuples: float) -> float:
    """GLM FLOPs: a TRAIN tuple a dot and a scaled add of D features, a
    PREDICT tuple a dot."""
    return (4.0 if verb == "TRAIN" else 2.0) * cfg["n_features"] * tuples
