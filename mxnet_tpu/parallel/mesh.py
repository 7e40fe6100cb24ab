"""Device-mesh helpers (reference analog: ctx lists + group2ctx placement).

The TPU-native scaling model (SURVEY.md §2.8): pick a `jax.sharding.Mesh`,
annotate shardings, let XLA insert collectives over ICI. Axes follow the
standard recipe: dp (data), tp (tensor/model), pp (pipeline), sp (sequence),
and ep (experts: `parallel/moe.py::routed_experts` sums its shares over it).
"""
from __future__ import annotations

import numpy as _np

import jax
from jax.sharding import Mesh, PartitionSpec, NamedSharding

__all__ = ["get_mesh", "data_parallel_mesh", "ShardingConfig", "PartitionSpec",
           "NamedSharding"]


def data_parallel_mesh(devices=None):
    """1-D dp mesh over all (or given) devices."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(_np.asarray(devices), ("dp",))  # tpulint: allow-host-sync device handle list, not a device array


def get_mesh(dp=1, tp=1, pp=1, sp=1, ep=1, devices=None):
    """Build an (dp, tp, pp, sp, ep) mesh; trailing unit axes are kept for uniform specs."""
    devices = devices if devices is not None else jax.devices()
    n = dp * tp * pp * sp * ep
    if n != len(devices):
        raise ValueError("mesh size %d != device count %d" % (n, len(devices)))
    arr = _np.asarray(devices).reshape(dp, tp, pp, sp, ep)  # tpulint: allow-host-sync device handle list, not a device array
    return Mesh(arr, ("dp", "tp", "pp", "sp", "ep"))


class ShardingConfig:
    """Declarative parameter-sharding rules: name-pattern -> PartitionSpec.

    The TPU-native successor of `group2ctx` model parallelism: instead of
    pinning subgraphs to devices (reference: PlaceDevice pass,
    graph_executor.cc:406), parameters/activations get named-axis shardings.
    """

    def __init__(self, mesh, rules=(), default=PartitionSpec()):
        self.mesh = mesh
        self.rules = list(rules)  # (substring, PartitionSpec)
        self.default = default

    def spec_for(self, name):
        for pat, spec in self.rules:
            if pat in name:
                return spec
        return self.default

    def sharding_for(self, name):
        return NamedSharding(self.mesh, self.spec_for(name))

    def batch_sharding(self):
        return NamedSharding(self.mesh, PartitionSpec("dp"))
