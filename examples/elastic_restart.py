"""Elastic reshard: checkpoint a training job, then restore it onto a
DIFFERENT mesh shape — the checkpoint stores logical arrays, so a job that
loses nodes (or gains them) resumes with re-resolved shardings.

This example forces 8 host devices and moves a run from a (4 data x 2 model)
mesh to (2 data x 4 model).

Run:  PYTHONPATH=src python examples/elastic_restart.py
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import tempfile

import jax

from repro.configs import get_reduced_config
from repro.data.pipeline import synthetic_data_fn
from repro.dist import meshes
from repro.models import model_zoo
from repro.train import checkpoint as ckpt
from repro.train.optimizer import OptConfig, adamw_init, make_train_step


def mesh_of(shape):
    return jax.make_mesh(shape, ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def place(params, specs, mesh):
    sh = meshes.tree_shardings(specs, params, mesh)
    return jax.tree.map(jax.device_put, params, sh)


def main():
    tmp = tempfile.mkdtemp(prefix="elastic_")
    cfg = get_reduced_config("internlm2-20b", d_model=64, n_heads=4,
                             n_kv_heads=4)
    params, specs = model_zoo.init_params(cfg, jax.random.PRNGKey(0))
    data_fn = synthetic_data_fn(cfg, batch=8, seq=32)
    opt_cfg = OptConfig(lr=1e-3, warmup_steps=2)
    step_fn = jax.jit(make_train_step(model_zoo.loss_fn(cfg, remat="none"),
                                      opt_cfg))

    # --- phase 1: 4x2 mesh ----------------------------------------------------
    mesh1 = mesh_of((4, 2))
    with meshes.use_mesh(mesh1):
        p = place(params, specs, mesh1)
        opt = adamw_init(p, opt_cfg)
        for s in range(5):
            p, opt, m = step_fn(p, opt, data_fn(s))
        ckpt.save(os.path.join(tmp, "ck"), 5, {"params": p, "opt": opt})
        loss_a = float(m["loss"])
    print(f"phase 1 on mesh (4 data x 2 model): step 5, loss {loss_a:.4f}")

    # --- phase 2: restore on 2x4 (as if half the data hosts were lost) --------
    mesh2 = mesh_of((2, 4))
    with meshes.use_mesh(mesh2):
        template = {"params": params, "opt": adamw_init(params, opt_cfg)}
        param_sh = meshes.tree_shardings(specs, params, mesh2)
        restored, step = ckpt.restore(os.path.join(tmp, "ck"), template)
        p2 = jax.tree.map(jax.device_put, restored["params"], param_sh)
        opt2 = jax.tree.map(jax.numpy.asarray, restored["opt"])
        for s in range(step, step + 5):
            p2, opt2, m2 = step_fn(p2, opt2, data_fn(s))
    print(f"phase 2 on mesh (2 data x 4 model): resumed at {step}, "
          f"loss {float(m2['loss']):.4f}")
    ex = jax.tree.leaves(p2)[0]
    print(f"resharded example leaf sharding: {ex.sharding}")
    assert int(opt2["step"]) == 10
    print("OK")


if __name__ == "__main__":
    main()
