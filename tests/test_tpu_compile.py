"""Every Pallas kernel on the main path compiles for a TPU v5e chip at the
widths ``chip_smoke.py`` runs, with no chip attached: the installed TPU
compiler lowers each kernel for a described ``v5e:2x2`` topology and the
compiled program must hold it as a ``tpu_custom_call``.

Interpret-mode tests cannot see what this catches: block shapes that break
the (8, 128) tiling rule, dot forms and primitives the TPU lowering lacks.
The topology is described inside a fixture (never at import), so every
pytest worker collects the same tests and only the one that runs this file
loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.striders import projection_plan
from repro.data.synthetic import WORKLOADS
from repro.db.page import PageLayout
from repro.kernels.engine.engine import glm_grad_pallas, glm_predict_pallas
from repro.kernels.paged_attn.kernel import paged_attn_pallas
from repro.kernels.strider.strider import strider_decode
from repro.kernels.wkv.wkv import wkv_pallas

SN = WORKLOADS["sn_logistic"]
LAYOUT = PageLayout(n_features=SN.n_features, page_bytes=SN.page_bytes)
CHUNK_PAGES = 512  # pages per TRAIN / PREDICT chunk program
D_GLM = 2048  # 2,000 features padded to the lane width
BLOCK, NB, T_TOK = 16, 512, 8  # paged KV: block size, pool blocks, tokens


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _strider(plan):
    return lambda p: strider_decode(p, LAYOUT, plan=plan)


def _glm_grad(x, y, w, m):
    return glm_grad_pallas(x, y, w, m, "logistic", block_rows=128)


def _glm_predict(x, w, m):
    return glm_predict_pallas(x, w, m, "logistic", block_rows=128)


def _attn(q, k, v, table, pos):
    return paged_attn_pallas(q, k, v, table, pos, block_size=BLOCK,
                             max_rows=NB * BLOCK, scale=0.1)


def _attn_mla(q_lat, q_rope, c, kr, table, pos):
    return paged_attn_pallas((q_lat, q_rope), (c, kr), None, table, pos,
                             block_size=BLOCK, max_rows=NB * BLOCK, scale=0.1)


def _wkv(r, k, v, lw, u, s):
    return wkv_pallas(r, k, v, lw, u, s, chunk=32)


PAGES = ((CHUNK_PAGES, LAYOUT.page_words), jnp.uint32)
TABLE = ((T_TOK, 64), jnp.int32)
POS = ((T_TOK,), jnp.int32)
BF16 = jnp.bfloat16
F32 = jnp.float32
B, T_SEQ, H, K = 2, 128, 40, 64  # rwkv6-3b heads x head size

CASES = {
    "strider_full": (_strider(None), [PAGES]),
    "strider_projected": (
        _strider(projection_plan(LAYOUT, list(range(SN.n_features)),
                                 include_label=True)), [PAGES]),
    "strider_projected_narrow": (
        _strider(projection_plan(LAYOUT, [0, 1, 2, 3, 7], include_label=True)),
        [PAGES]),
    "glm_grad": (_glm_grad, [((512, D_GLM), F32), ((512,), F32),
                             ((D_GLM,), F32), ((512,), F32)]),
    "glm_predict": (_glm_predict, [((2048, D_GLM), F32), ((D_GLM,), F32),
                                   ((2048,), F32)]),
    "paged_attn_gqa": (_attn, [((T_TOK, 8, 6, 128), BF16),
                               ((NB, BLOCK, 8, 128), BF16),
                               ((NB, BLOCK, 8, 128), BF16), TABLE, POS]),
    "paged_attn_mla": (_attn_mla, [((T_TOK, 1, 40, 256), BF16),
                                   ((T_TOK, 1, 40, 32), BF16),
                                   ((NB, BLOCK, 256), BF16),
                                   ((NB, BLOCK, 32), BF16), TABLE, POS]),
    "wkv": (_wkv, [((B, T_SEQ, H, K), F32)] * 4
            + [((H, K), F32), ((B, H, K, K), F32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    text = _compiled_text(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text
