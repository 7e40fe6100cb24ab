"""Run a file of this repo that lives outside ``tests/`` as a module and take
its public names: how tier-1 (``pytest tests/``) reaches the benchmark's own
tests and its plain references without holding a copy of either."""
import importlib.util
import os

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), *[".."] * 3))


def load_into(namespace, *rel):
    """Execute ``REPO/rel...`` under its own file name and copy what it
    defines (tests, fixtures, helpers) into ``namespace``."""
    path = os.path.join(REPO, *rel)
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location("by_path_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    namespace.update({k: v for k, v in vars(module).items()
                      if not k.startswith("__")})
