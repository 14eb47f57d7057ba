"""chip_smoke.py must not rot between chip runs.

The script's real run needs the chip (the chip tool; CHANGES.md records
the passes). Here: it refuses to do anything without an accelerator, and
its explicit CPU rehearsal drives every phase — the same entry points at
a tiny size, Pallas in interpret mode — without ever printing ok=true.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("train", "serve", "kernels", "sequence")


def _run(args, tmp_path, n_devices=1, timeout=900):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jaxcc")
    env.pop("JAX_ENABLE_X64", None)          # the chip's default: x64 off
    if n_devices > 1:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={n_devices}"
    else:
        env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=timeout)


def _check_rehearsal(out, tmp_path, phases, count):
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert lines[0].startswith("platform=cpu ")
    for name in phases:
        assert any(l.startswith(f"PHASE {name} PASS ") for l in lines), \
            f"phase {name} did not report a pass:\n{out.stdout[-2000:]}"
    last = json.loads(lines[-1])
    assert last == {"ok": False, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": count}}
    assert not any('"ok": true' in l for l in lines)
    # the compile cache went where the environment said
    assert any((tmp_path / "jaxcc").iterdir())


def test_refuses_to_run_without_an_accelerator(tmp_path):
    out = _run([], tmp_path, timeout=120)
    assert out.returncode == 2
    assert out.stdout == ""                  # no result of any kind
    assert "no accelerator" in out.stderr
    assert not (tmp_path / "jaxcc").exists()     # before doing any work


def test_cpu_rehearsal_drives_every_phase(tmp_path):
    out = _run(["--rehearse-cpu"], tmp_path)
    _check_rehearsal(out, tmp_path, PHASES, 1)


@pytest.mark.slow  # three more ResNet-50 compiles on the virtual mesh
def test_cpu_rehearsal_four_devices_adds_the_multichip_phase(tmp_path):
    out = _run(["--rehearse-cpu"], tmp_path, n_devices=4)
    _check_rehearsal(out, tmp_path, PHASES + ("multichip",), 4)
