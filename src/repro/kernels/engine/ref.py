"""Pure-jnp oracle for the fused GLM execution-engine kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

ACTS = ("linear", "logistic", "svm")
# float32 matmuls at full precision: the TPU's default would round operands
# to bfloat16, and this module is the float32 reference
HIGHEST = jax.lax.Precision.HIGHEST


def glm_error(z: jnp.ndarray, y: jnp.ndarray, act: str) -> jnp.ndarray:
    if act == "linear":
        return z - y
    if act == "logistic":
        return jax.nn.sigmoid(z) - y
    if act == "svm":
        return jnp.where(y * z < 1.0, -y, 0.0)
    raise ValueError(f"unknown GLM activation {act!r}")


def glm_grad_ref(
    x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray, mask: jnp.ndarray, act: str
) -> jnp.ndarray:
    """Merged (summed) gradient over the batch: X' e, e = err(act(Xw), y)."""
    x = x.astype(jnp.float32)
    z = jnp.dot(x, w.astype(jnp.float32), precision=HIGHEST)
    e = glm_error(z, y.astype(jnp.float32), act) * mask.astype(jnp.float32)
    return jnp.dot(e, x, precision=HIGHEST)


def glm_act(z: jnp.ndarray, act: str) -> jnp.ndarray:
    """Forward activation for scoring: the model's prediction from z = X·w."""
    if act == "linear":
        return z
    if act == "logistic":
        return jax.nn.sigmoid(z)
    if act == "svm":
        return jnp.where(z >= 0.0, 1.0, -1.0)
    raise ValueError(f"unknown GLM activation {act!r}")


def glm_predict_ref(
    x: jnp.ndarray, w: jnp.ndarray, mask: jnp.ndarray, act: str
) -> jnp.ndarray:
    """Per-row predictions act(X·w); dead rows (mask 0) come back as 0."""
    z = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32), precision=HIGHEST)
    return jnp.where(mask.astype(jnp.float32) > 0.0, glm_act(z, act), 0.0)
