"""The shipped examples must run end-to-end (fast configurations)."""
from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=600, extra_env=None):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", **(extra_env or {}))
    proc = subprocess.run([sys.executable, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, f"OUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


def test_quickstart_example():
    out = _run(["examples/quickstart.py"])
    assert "DFModel dataflow" in out and "speedup" in out


def test_train_e2e_example():
    out = _run(["examples/train_e2e.py", "--steps", "12", "--batch", "2",
                "--seq", "64"])
    assert "done;" in out


def test_serve_batched_example():
    out = _run(["examples/serve_batched.py", "--tokens", "4",
                "--batch", "2"])
    assert "TPOT" in out


def test_dse_scenario_example():
    out = _run(["examples/dse_scenario.py"])
    assert "best throughput utilization" in out


def test_serve_dse_example():
    out = _run(["examples/serve_dse.py"])
    assert "dedup hits" in out
    assert "zero new solves" in out
    assert "certified=True" in out
    assert "serve_dse: OK" in out


def test_launch_train_module():
    out = _run(["-m", "repro.launch.train", "--arch", "olmo_1b", "--smoke",
                "--steps", "4", "--mesh", "2x4", "--fsdp"],
               extra_env={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    assert "done" in out


def test_dryrun_main_exits_nonzero_when_a_cell_fails(monkeypatch):
    """A failed cell is recorded and the sweep goes on, but the CLI's exit
    status reports it."""
    import pytest

    from repro.launch import dryrun

    def fail(arch, shape, multi_pod, **_):
        raise RuntimeError(f"{arch}/{shape} does not compile")

    monkeypatch.setattr(dryrun, "run_cell", fail)
    # main installs XLA_FLAGS with setdefault; keep it out of this process
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    monkeypatch.setattr(sys, "argv", ["dryrun", "--arch", "olmo_1b",
                                      "--shape", "train_4k"])
    with pytest.raises(SystemExit) as exc:
        dryrun.main()
    assert exc.value.code not in (0, None)
    assert "olmo_1b/train_4k" in str(exc.value.code)
