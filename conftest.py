"""Root conftest: force the JAX CPU backend with a virtual 8-device mesh.

The reference's distributed tests run multi-process on one host with Gloo
(SURVEY.md §4); the TPU-native analog is a fake 8-device CPU platform via
``--xla_force_host_platform_device_count=8`` so mesh/sharding logic is
exercised without real chips.  This must run before the first ``import jax``
anywhere.  The pin holds for the test session only: ``chip_smoke.py``,
``perfbench.run`` and ``__graft_entry__.py`` do NOT import this and take
the platform JAX finds.  A pytest process therefore never holds a chip, and a
child that a test starts with ``JAX_PLATFORMS=tpu`` is the one process
that may.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
