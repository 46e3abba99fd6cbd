"""The first run's tile picks are kept for the later runs of a checkout."""
import itertools
import json
import sys
import types

from bench import tiles
from bench.run import ROOT


def fake_program():
    """A module whose autotuner answers differently on every call, and a
    second module that imported it by name."""
    ops, user = types.ModuleType("fake_ops"), types.ModuleType("fake_user")
    answers = itertools.count(64, 64)
    ops.best_tile = lambda n, m: next(answers)
    user.best_tile = ops.best_tile
    sys.modules.update(fake_ops=ops, fake_user=user)
    return ops, user


def test_later_runs_take_the_first_runs_pick(tmp_path):
    root = str(tmp_path)
    try:
        ops, user = fake_program()
        tiles.keep(root, "chip", ["fake_ops.best_tile", "gone.best_tile"])
        assert ops.best_tile(1000, 12) == 64
        assert user.best_tile(1000, 12) == 64     # the autotuner said 128
        assert ops.best_tile(50, 3) == 192
        ops, user = fake_program()                # a new process
        tiles.keep(root, "chip", ["fake_ops.best_tile"])
        assert ops.best_tile(50, 3) == 192        # the autotuner said 64
        assert user.best_tile(1000, 12) == 64     # ... and 128
        ops, user = fake_program()
        tiles.keep(root, "other chip", ["fake_ops.best_tile"])
        assert ops.best_tile(1000, 12) == 64      # picks are per device kind
        assert ops.best_tile(50, 3) == 128
        with open(tiles.record_path(root)) as f:
            assert set(json.load(f)) == {"chip", "other chip"}
    finally:
        for name in ("fake_ops", "fake_user"):
            sys.modules.pop(name, None)


def test_every_kernel_names_its_autotuner():
    found = tiles.autotuners(ROOT)
    assert len(found) == 3 and all(p.endswith(".best_tile") for p in found)
