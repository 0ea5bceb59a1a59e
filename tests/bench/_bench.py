"""The benchmark's modules for its tests.  They import one another by
name, as ``bench/run.py`` runs them, so ``bench/`` goes on the path."""
import importlib
import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench")


def module(name: str):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(name)
