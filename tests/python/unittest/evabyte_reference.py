"""The benchmark's plain reference of the EvaByte decoder,
`benchmark/cells/references/evabyte.py`, under the name the tests import:
one file, loaded by path."""
from load_by_path import load_into

load_into(globals(), "benchmark", "cells", "references", "evabyte.py")
