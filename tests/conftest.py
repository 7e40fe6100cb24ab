"""Test config: 8-device virtual CPU platform so multi-device code paths
(kvstore device lists, sharding meshes) run without TPU hardware, plus
full-precision matmuls so numeric-gradient checks have resolution.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
