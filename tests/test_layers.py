"""The package's layering: importing any module of one ``repro`` package
loads no package above it.

    kernels, obs, checkpoint, data   (bottom: each loads only itself)
    core      over kernels, checkpoint
    configs   over core
    serve     over core and obs
    testing   over serve
    launch    (top: anything)

Each case imports every module of one package in a fresh interpreter, so
``sys.modules`` holds only what that package pulls in."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

_SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

_BOTTOM = ("kernels", "obs", "checkpoint", "data")
_CORE = ("core", "kernels", "checkpoint")

ALLOWED = {
    "kernels": ("kernels",),
    "obs": ("obs",),
    "checkpoint": ("checkpoint",),
    "data": ("data",),
    "core": _CORE,
    "configs": _CORE + ("configs",),
    "serve": ("serve", "core", "obs", "kernels", "checkpoint"),
    "testing": ("testing", "core", "serve", "obs", "kernels", "checkpoint"),
    "launch": _BOTTOM + ("core", "configs", "serve", "testing", "launch"),
}

_PROBE = """
import importlib, json, pkgutil, sys
pkg = importlib.import_module("repro." + sys.argv[1])
for info in pkgutil.walk_packages(pkg.__path__, pkg.__name__ + "."):
    importlib.import_module(info.name)
print(json.dumps(sorted(m for m in sys.modules if m.startswith("repro."))))
"""


def _loaded(pkg):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(_SRC)] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                       if p])
    proc = subprocess.run([sys.executable, "-c", _PROBE, pkg], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("pkg", list(ALLOWED))
def test_package_loads_only_lower_layers(pkg):
    loaded = _loaded(pkg)
    assert any(m.startswith(f"repro.{pkg}.") for m in loaded), loaded
    stray = [m for m in loaded if m.split(".")[1] not in ALLOWED[pkg]]
    assert not stray, f"repro.{pkg} loads {stray}"
