"""A configuration file of the benchmark, and the modules it is read with.

``configs/<name>.json`` holds a configuration as it is run: its ``model``
block keyed as the model's published ``config.json``, its ``engine`` block,
and under ``reference`` the path (relative to ``chipbench/``) of the plain
reference module that computes its block. That module also reads the
``model`` block into the shape record the yardstick uses
(``chipbench/reference/__init__.py`` states the contract).
"""
from __future__ import annotations

import functools
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parent


def load_module(path) -> ModuleType:
    """The Python file at ``path``, run as a module of its own: metric
    readers and references are found by path, never imported by name."""
    path = Path(path).resolve()
    name = "chipbench_file" + re.sub(r"\W", "_", str(path.with_suffix("")))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def reference(path: str) -> ModuleType:
    """The reference module a configuration names (its ``reference`` key),
    loaded once per process."""
    return load_module(ROOT / path)


def load_config(path) -> dict:
    """A configuration file, with its reference's shape record of the
    ``model`` block under ``"dims"``."""
    cfg = json.loads(Path(path).read_text())
    if "reference" not in cfg:
        raise KeyError(f"{path}: a configuration names its reference module "
                       "under 'reference'")
    cfg["dims"] = reference(cfg["reference"]).dims_of(cfg["model"])
    return cfg
