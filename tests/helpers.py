"""Shared pure-Python test helpers (importable from any test module).

Kept separate from ``conftest.py`` so test modules can import them with a
plain ``from helpers import ...`` — cross-importing between test *modules*
(e.g. ``from test_graph import ...``) breaks under isolated collection
(``pytest tests/test_lower_sets.py`` alone, or xdist workers).
"""

import importlib
import itertools
import sys
from pathlib import Path

from repro.core.graph import Graph


def brute_lower_sets(g: Graph):
    """All lower sets of ``g`` by brute force over 2^V — the test oracle."""
    out = set()
    for r in range(g.n + 1):
        for comb in itertools.combinations(range(g.n), r):
            if g.is_lower_set(comb):
                out.add(frozenset(comb))
    return out


def _chipbench(name: str):
    bench = str(Path(__file__).resolve().parents[1] / "chipbench")
    if bench not in sys.path:
        sys.path.append(bench)
    return importlib.import_module(name)


def phase_reader():
    """The benchmark's reader of a compiled step's phases
    (``chipbench/phases.py``), which the program's named scopes feed."""
    return _chipbench("phases")


def kernel_reader():
    """The benchmark's reader of the flash kernels in a compiled program
    (``chipbench/devtrace.py``), which tells them apart by their results."""
    return _chipbench("devtrace")
