"""``chip_smoke.py`` rehearsed on the CPU at smoke size.

The script's phases are called directly with smoke configs — the TPU
gate in ``main`` is left out, and the kernels run in interpret mode —
so a wrong path, argument or check fails here before it costs a chip
run.  The script itself must refuse to run without a TPU, and without
the rest of the repository.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_smoke_config
from repro.serving import ServeConfig

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_phase_matches_oracles(smoke):
    errs = smoke.kernel_phase(B=3, H=4, K=2, hd=32, bs=8, n_blk=4, S=3)
    assert set(errs) == {"paged_attention_f32", "paged_attention_bf16",
                         "paged_attention_int8", "paged_extend_int8"}
    # interpreted kernels compute in f32 like the oracle: far inside the
    # tolerance the chip run is held to
    assert max(errs.values()) < 1e-4 < smoke.KERNEL_TOL


def test_served_phase_serves_every_request(smoke):
    cfg = get_smoke_config(smoke.ARCH)
    scfg = ServeConfig(max_slots=8, max_len=192, prefill_buckets=(16, 32),
                       kv_block_size=8, use_pallas_paged=True, catch_chunk=4)
    out = smoke.served_phase(cfg, scfg)
    assert out["requests"] == 16
    assert out["tokens"] == 16 * smoke.MAX_NEW
    assert out["prefix_hits"] >= 4
    # interpreted kernels leave no custom call in the decode wave: the
    # chip run's tpu_custom_call check tells compiled from interpreted
    assert "tpu_custom_call" not in out["decode_hlo"]


def _run(script: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _json_last_line(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        json.loads(lines[-1])
    except ValueError:
        return False
    return True


def test_refuses_to_run_without_tpu():
    res = _run(ROOT / "chip_smoke.py", ROOT)
    assert res.returncode != 0
    assert "no TPU found" in res.stderr
    assert not _json_last_line(res.stdout)


def test_refuses_to_run_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    res = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert res.returncode != 0
    assert not _json_last_line(res.stdout)
