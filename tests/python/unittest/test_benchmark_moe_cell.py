"""benchmark/cells/tests/test_moe_cell.py in tier-1: the latent-attention
expert cell's driver, run end to end from its files on the CPU. A module of
its own beside `test_benchmark_cells.py`: the two files each define a
module-scoped ``spec_root``."""
from load_by_path import load_into

load_into(globals(), "benchmark", "cells", "tests", "test_moe_cell.py")
