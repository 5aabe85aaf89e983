"""Finds a piece of the benchmark by the name a data file gives it:
``load("readers", "stage_share")`` is the module ``readers/stage_share.py``.
A family (``families/``), a traffic driver (``drivers/``) and a reader
(``readers/``) are found so, which is why a new one is a new file."""

from __future__ import annotations

import importlib.util
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
_LOADED: dict = {}


def load(kind: str, name: str):
    if (kind, name) not in _LOADED:
        path = os.path.join(BENCH, kind, name + ".py")
        if not os.path.exists(path):
            raise SystemExit(f"no {kind}/{name}.py under {BENCH}")
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[kind, name] = mod
    return _LOADED[kind, name]
