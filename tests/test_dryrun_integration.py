"""Dry-run integration: one real (arch x shape x mesh) cell lowered +
compiled in a subprocess with 512 forced host devices, validating the full
deliverable-(e) path (mesh build, shardings, calibration, HLO parsing),
plus artifact well-formedness checks when a sweep has been run."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_cell_subprocess(tmp_path):
    # forced host devices are CPU devices: the child never takes the chip
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # dryrun.py must set it itself (first lines)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "rwkv6-3b", "--shape", "decode_32k", "--mesh", "multi",
         "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    path = tmp_path / "rwkv6-3b__decode_32k__pod2x16x16.json"
    rec = json.loads(path.read_text())
    assert rec["status"] == "ok"
    assert rec["n_devices"] == 512
    assert rec["cost"]["flops"] > 0
    assert rec["memory"]["argument_bytes"] > 0
    assert "calibration" in rec and rec["calibration"]["real_counts"] == {"rwkv": 32}


ARTIFACTS = os.path.join(REPO, "artifacts", "dryrun")


@pytest.mark.skipif(not os.path.isdir(ARTIFACTS), reason="sweep not run")
def test_sweep_artifacts_complete():
    from repro.configs import ARCH_IDS
    from repro.models.model_zoo import SHAPES

    files = {f for f in os.listdir(ARTIFACTS) if f.endswith(".json")}
    assert len(files) == len(ARCH_IDS) * len(SHAPES) * 2  # both meshes
    n_ok = n_skip = 0
    for f in files:
        rec = json.load(open(os.path.join(ARTIFACTS, f)))
        assert rec["status"] in ("ok", "skipped"), (f, rec.get("error"))
        if rec["status"] == "ok":
            n_ok += 1
            assert rec["cost"]["flops"] > 0
            assert rec["collectives"]["total_wire_bytes"] >= 0
        else:
            n_skip += 1
            assert rec["shape"] == "long_500k"
    assert n_ok == 64 and n_skip == 16


@pytest.mark.skipif(not os.path.isdir(ARTIFACTS), reason="sweep not run")
def test_multi_pod_shards_the_pod_axis():
    """Per-device numbers must drop from 256 -> 512 chips (train cells)."""
    import json

    def load(name):
        with open(os.path.join(ARTIFACTS, name)) as f:
            return json.load(f)

    for arch in ("deepseek-67b", "rwkv6-3b", "seamless-m4t-medium"):
        single = load(f"{arch}__train_4k__pod16x16.json")
        multi = load(f"{arch}__train_4k__pod2x16x16.json")
        assert multi["n_devices"] == 2 * single["n_devices"]
        ratio = multi["cost"]["flops"] / single["cost"]["flops"]
        assert 0.4 < ratio < 0.75, (arch, ratio)  # ~halved per device
