"""chip_smoke.py's path on the CPU: the same server, checks and
reference at smoke widths, with the Pallas kernels in interpret mode."""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import configs
from repro.kernels import ops as kops

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod     # its dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


def _tiny(smoke):
    # 128 wide and 8 slots, so every MLP and paged-attention shape is
    # kernel-eligible (the sidebar MLP takes rows in multiples of 8)
    cfg = dataclasses.replace(configs.get_smoke_config("nemotron-4-15b"),
                              d_model=128, use_pallas=True)
    traffic = smoke.Traffic(requests=4, slots=8, block_size=8, prefix=32,
                            own=(4, 16), new_tokens=8, prefill_chunk=16,
                            segment=4)
    return cfg, traffic


def test_smoke_path_passes_every_check_off_the_chip(smoke):
    assert kops.interpret_kernels()
    cfg, traffic = _tiny(smoke)
    lines = []
    failed = smoke.run(cfg, traffic, seed=0, num_blocks=64,
                       log=lines.append)
    assert failed == [], failed
    assert any(s.startswith("logit_rel_l2") for s in lines)


def test_smoke_check_catches_a_reference_fallback(smoke):
    """A dispatch that fell to the jnp reference fails the run."""
    cfg, traffic = _tiny(smoke)
    cfg = dataclasses.replace(cfg, d_ff=200)   # untileable: MLP -> ref
    failed = smoke.run(cfg, traffic, seed=0, num_blocks=64,
                       log=lambda s: None)
    assert any("without the Pallas kernel" in f and "sidebar_mlp" in f
               for f in failed), failed


_TP_CHILD = r"""
import dataclasses, importlib.util, json, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
sys.modules["chip_smoke"] = smoke
spec.loader.exec_module(smoke)
from repro import configs
from repro.launch.mesh import make_serving_mesh
from repro.launch.serve import make_tp_spec
from repro.models.registry import get_model

cfg = dataclasses.replace(configs.get_smoke_config("nemotron-4-15b"),
                          d_model=128, num_kv_heads=4, d_ff=512,
                          use_pallas=True)
traffic = smoke.Traffic(requests=4, slots=8, block_size=8, prefix=32,
                        own=(4, 16), new_tokens=8, prefill_chunk=16,
                        segment=4)
mesh = make_serving_mesh((1, 4))
shardings = make_tp_spec(cfg, get_model(cfg), mesh).param_shardings()
print(json.dumps(smoke.run(cfg, traffic, seed=0, num_blocks=64, mesh=mesh,
                           param_shardings=shardings, log=lambda s: None)))
"""


def test_smoke_path_on_a_four_device_mesh():
    """The ``--chips 4`` path: tensor-parallel serving on a (1, 4) mesh
    of virtual CPU devices, parameters made already partitioned, against
    the plain forward under GSPMD on the same devices."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    res = subprocess.run(
        [sys.executable, "-c", _TP_CHILD, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    assert json.loads(res.stdout.strip().splitlines()[-1]) == []


def test_smoke_script_fails_off_the_chip():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=ROOT)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "needs a TPU" in res.stderr
