"""``chip_smoke.py`` at tiny size on the CPU: its phases run and check what
they check, and the script itself refuses to run without a TPU.

On the CPU the pricing kernel runs as its interpret-mode twin, so the
compiled-kernel check must refuse it; everything else is the path the
script drives on the chip.
"""
from __future__ import annotations

import importlib
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load():
    """Import the script as a module that pool workers can import too: they
    unpickle its probe by name."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    return importlib.import_module("chip_smoke")


def _env(**extra) -> dict:
    return dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
                JAX_PLATFORMS="cpu", **extra)


def test_script_refuses_to_run_without_a_tpu():
    proc = subprocess.run([sys.executable, SCRIPT], env=_env(), cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_one_chip_phases_at_tiny_size():
    from repro.configs import get_config
    from repro.search import DenseGridSpec

    cs = _load()
    grid = cs.dse_phase(DenseGridSpec().spec(), workers=2)
    assert grid["winners_identical"] and grid["cells"] == 864
    with pytest.raises(AssertionError, match="interpret mode"):
        cs.check_pricing_compiled(grid["priced_rows"])
    err = cs.serve_phase(get_config("olmo_1b", smoke=True), requests=2,
                         prompt_len=8, tokens=4, ref_len=8)
    assert 0.0 < err < cs.LOGIT_TOL


def test_four_chip_phase_on_four_cpu_devices():
    body = f"""
    import importlib.util
    spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    from repro.configs import get_config

    delta = cs.train_phase(get_config("olmo_1b", smoke=True), steps=2,
                           batch=8, seq=32)
    assert delta < cs.LOSS_TOL, delta
    print("train phase OK", delta)
    """
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"OUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    assert "params on 4 devices" in proc.stdout
