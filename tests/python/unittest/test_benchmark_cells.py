"""benchmark/cells/tests/test_cell_benchmark.py in tier-1: the training and
GPT-2 decode cells' drivers, run end to end from their files on the CPU, so
a change to the decode-model seam that breaks a driver fails here and not
on the chip."""
from load_by_path import load_into

load_into(globals(), "benchmark", "cells", "tests", "test_cell_benchmark.py")
