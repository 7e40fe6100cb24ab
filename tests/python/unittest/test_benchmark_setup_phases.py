"""benchmark/cells/tests/test_setup_phases.py in tier-1: the four set-up
phase metrics and their reader, on hand-made counters and in a rehearsed
traced run of a tiny train cell."""
from load_by_path import load_into

load_into(globals(), "benchmark", "cells", "tests", "test_setup_phases.py")
