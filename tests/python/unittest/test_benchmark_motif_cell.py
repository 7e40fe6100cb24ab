"""benchmark/cells/tests/test_motif_cell.py in tier-1: the Motif-3 cell's
driver, run end to end from its files on the CPU. A module of its own beside
`test_benchmark_kimi_cell.py`: each defines a module-scoped ``spec_root``."""
from load_by_path import load_into

load_into(globals(), "benchmark", "cells", "tests", "test_motif_cell.py")
