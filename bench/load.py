"""Modules of the benchmark found by name, ``<root>/bench/<kind>/<name>.py``:
a per-layer metric's reader, a row representation's data or service side.
Each is loaded by its path, so a file that a later change adds is found
without a table to edit."""

from __future__ import annotations

import importlib.util
import os

__all__ = ["module"]


def module(root: str, kind: str, name: str):
    """The module ``<root>/bench/<kind>/<name>.py``; a ``SystemExit`` that
    names the file where there is none."""
    rel = os.path.join("bench", kind, name + ".py")
    path = os.path.join(root, rel)
    if not os.path.isfile(path):
        raise SystemExit(f"{kind} {name!r} has no {rel}")
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
