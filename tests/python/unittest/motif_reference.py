"""The benchmark's plain reference of the Motif-3 decoder,
`benchmark/cells/references/motif3.py`, under the name the tests import:
one file, loaded by path."""
from load_by_path import load_into

load_into(globals(), "benchmark", "cells", "references", "motif3.py")
