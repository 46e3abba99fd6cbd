"""The program's kernel-tile picks, kept from the first run in a checkout.

Each kernel's tile autotuner (``best_tile``) times its candidate tiles
in every process, and on the chip two processes can pick different
tiles: the candidates run within a few percent of each other. The tile
is a static argument of the compiled experiment, so a run whose picks
differ from every earlier run's compiles its blocks anew in set-up
instead of loading them from the compilation cache (set-up then reads
two to four times as long).

So the harness keeps the picks of the first run in a checkout, in
``bench_out/tile_picks.json`` under the device's kind, and has later
runs use them. The autotuners still time their candidates in every run,
so that cost stays in set-up; only their answer is replaced by the
first run's. Each kernel's cost file names its autotuners
(``AUTOTUNERS`` in ``bench/kernels/<kernel>.py``: dotted paths); one the
program no longer has is skipped.
"""
from __future__ import annotations

import importlib
import json
import os
import sys
from typing import Callable, Dict, Iterable


def autotuners(root: str):
    """The dotted paths that ``bench/kernels/*.py`` name."""
    from bench import cells
    out = []
    for name in sorted(os.listdir(os.path.join(root, "bench", "kernels"))):
        if name.endswith(".py"):
            out += getattr(cells.module(root, "kernels", name[:-3]),
                           "AUTOTUNERS", ())
    return out


def record_path(root: str) -> str:
    return os.path.join(root, "bench_out", "tile_picks.json")


def _load(path: str) -> Dict[str, Dict[str, int]]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _rebind(old: Callable, new: Callable) -> None:
    """Every loaded module's name for ``old`` now means ``new``."""
    for mod in list(sys.modules.values()):
        names = [k for k, v in list(getattr(mod, "__dict__", {}).items())
                 if v is old]
        for k in names:
            setattr(mod, k, new)


def keep(root: str, kind: str, autotuners: Iterable[str]) -> None:
    """Make each named autotuner answer with the pick the first run in
    this checkout made for the same arguments on this device kind."""
    path = record_path(root)
    picks = _load(path).get(kind, {})

    def save() -> None:
        table = _load(path)
        table[kind] = picks
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)

    for dotted in autotuners:
        mod_name, attr = dotted.rsplit(".", 1)
        try:
            orig = getattr(importlib.import_module(mod_name), attr)
        except (ImportError, AttributeError):
            continue

        def kept(*args, _orig=orig, _name=dotted, **kw):
            pick = _orig(*args, **kw)
            key = f"{_name}{args!r}{sorted(kw.items())!r}"
            if key not in picks:
                picks[key] = pick
                save()
            return picks[key]

        _rebind(orig, kept)
