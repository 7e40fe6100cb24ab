"""Reader: a ratio of the run's own counts.

``args``: ``num`` and ``den`` are lists of fact names (their products are
divided; ``den`` may be empty), ``scale`` multiplies. A fact that the run did
not record means there is nothing to read.
"""


def read(run, args):
    value = float(args.get("scale", 1.0))
    for name in args.get("num", []):
        if run.facts.get(name) is None:
            return None
        value *= run.facts[name]
    for name in args.get("den", []):
        if not run.facts.get(name):
            return None
        value /= run.facts[name]
    return value
