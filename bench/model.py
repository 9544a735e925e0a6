"""What every configuration shares: the benchmark's files and seeds.

``load_config(name)`` reads ``bench/configs/<name>.json``: the model's
published ``config.json`` keys (as run: a cut key is listed in
``reduced``), its SALS setting, the deployment it stands for, and two
modules by their path from the checkout's root: ``"architecture"``, which
makes the weights and maps them onto the program (``bench/archs/``), and
``"reference"``, the plain reference that decides ``correct``
(``bench/reference/``).  :func:`load_module` loads either.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def path(*parts) -> str:
    """A file of the benchmark's own, under ``HERE``."""
    return os.path.join(HERE, *parts)


def load_json(*parts) -> dict:
    with open(path(*parts)) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    cfg = load_json("configs", f"{name}.json")
    if cfg.get("name") != name:
        raise ValueError(f"configs/{name}.json names itself {cfg.get('name')!r}")
    return cfg


def load_module(rel: str):
    """A module of the benchmark's own, by its path from the checkout's
    root (``bench/...``) or absolute.  It is entered in ``sys.modules``
    under its path (a dataclass looks its module up there)."""
    f = os.path.join(os.path.dirname(HERE), rel)
    spec = importlib.util.spec_from_file_location(f"bench_file:{f}", f)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` 32-bit words from any whole-number seed (large ones too)."""
    return np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
