"""Serving entry points: one process per chip, compile-cache placement,
published widths by flag.

A chip belongs to one process at a time. The HTTP smoke and the load
generator sit beside a server that needs the chip, so importing them (or
``repro.serve``) must not initialise a JAX backend: the check runs each
import in a fresh interpreter and asks JAX whether any backend came up.
"""
import argparse
import os
import pathlib
import subprocess
import sys

import pytest

from repro.launch import cli

REPO = pathlib.Path(__file__).resolve().parents[1]

_IMPORTS = {
    "repro.serve": "import repro.serve",
    "tools/http_smoke.py": (
        "import importlib.util as u\n"
        "s = u.spec_from_file_location('http_smoke', 'tools/http_smoke.py')\n"
        "s.loader.exec_module(u.module_from_spec(s))"),
    "benchmarks/serve_http_load.py": (
        "import sys\n"
        "sys.path.insert(0, 'benchmarks')\n"
        "import serve_http_load"),
}


@pytest.mark.parametrize("target", sorted(_IMPORTS))
def test_import_initialises_no_backend(target):
    code = (_IMPORTS[target] + "\n"
            "from jax._src import xla_bridge\n"
            "print('initialised', xla_bridge.backends_are_initialized())\n")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env, cwd=str(REPO))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "initialised False" in r.stdout, r.stdout


def test_compilation_cache_dir_honours_environment():
    env = {"JAX_COMPILATION_CACHE_DIR": "/srv/jax-cache"}
    assert cli.compilation_cache_dir(env) == "/srv/jax-cache"


def test_compilation_cache_dir_defaults_to_fixed_checkout_path():
    path = cli.compilation_cache_dir({})
    assert path == str(REPO / ".jax_cache")
    assert path == cli.compilation_cache_dir({})       # never varies
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("reduced", [False, True])
def test_reduced_flag_selects_widths(reduced):
    ap = cli.add_serving_flags(argparse.ArgumentParser())
    args = ap.parse_args(["--arch", "bert-base"]
                         + (["--reduced"] if reduced else []))
    cfg = cli.resolve_config(args)
    assert cfg.d_model == (64 if reduced else 768)
    assert args.backend == "auto"
