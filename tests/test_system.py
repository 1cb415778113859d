"""End-to-end system tests: the training and serving drivers run as a user
would invoke them, and the suite keeps seeing one CPU device."""
import subprocess
import sys


def _run(mod, *args, timeout=900):
    return subprocess.run(
        [sys.executable, "-m", mod, *args], capture_output=True, text=True,
        timeout=timeout, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})


def test_train_driver_end_to_end():
    r = _run("repro.launch.train", "--arch", "qwen1.5-4b", "--smoke",
             "--clients", "2", "--rounds", "1", "--steps-per-round", "1",
             "--batch", "2", "--seq", "32")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "round 1/1" in r.stdout
    assert "eps=" in r.stdout  # privacy accounted


def test_serve_driver_end_to_end():
    r = _run("repro.launch.serve", "--arch", "qwen1.5-4b", "--smoke",
             "--batch", "2", "--prompt-len", "8", "--gen", "3")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "decoded" in r.stdout


def test_no_program_module_forces_a_host_device_count():
    """No program module, nor the suite's shared set-up, sets the XLA flag
    that multiplies the CPU devices: the suite sees one device, and a test
    that needs more starts a child process with its own flags."""
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    files = sorted((root / "src/repro").rglob("*.py"))
    assert files
    files += [root / "tests/conftest.py", root / "benchmarks/run.py"]
    for f in files:
        assert "force_host_platform_device_count" not in f.read_text(), f


def test_single_device_visible_in_tests():
    import jax
    assert len(jax.devices()) == 1
