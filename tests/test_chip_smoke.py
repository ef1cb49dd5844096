"""``chip_smoke.py`` rehearsed on the CPU at tiny sizes: its phases pass
on small graphs, and the script refuses to report success without a TPU
or outside a checkout."""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, cwd, env=None):
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    r = _run([str(SCRIPT)], ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_fails_outside_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _run([SCRIPT.name], tmp_path, env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_dense_and_update_phases(smoke):
    g, eng, queries, answers = smoke.dense_phase(2000, 8, 12000, 48, seed=0)
    assert len(queries) > 24
    smoke.update_phase(g, eng, queries, answers, seed=0, num_edges=16)


def test_ring_phase_through_kernel(smoke):
    # the interpreted kernel on the CPU: force it for every wavefront of
    # at least 64 tasks, as the default threshold does on a TPU
    smoke.ring_phase(1000, 8, 4000, 24, seed=0, kernel_threshold=64,
                     compiled=False)


def test_ring_phase_requires_kernel_dispatches(smoke):
    with pytest.raises(smoke.SmokeFailure, match="nfa_step"):
        smoke.ring_phase(300, 4, 900, 8, seed=0,
                         kernel_threshold=float("inf"), compiled=False)


def test_four_chip_phase_on_virtual_devices():
    code = textwrap.dedent(f"""
        import importlib.util, os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      {str(SCRIPT)!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        cs.four_chip_phase((2000, 8, 12000), (1000, 8, 4000), 24, seed=0)
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(ROOT / "src")}
    r = _run(["-c", code], ROOT, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    for line in ("dense shards=4 vs one device", "ring shards=4 vs one device",
                 "edge bytes per device {0:"):
        assert line in r.stdout, r.stdout
