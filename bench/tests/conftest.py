"""The tests of the 2x2 cell run on a 2x2 mesh of host devices: the XLA
flag is set before jax is imported, to the same count as the repository's
own ``tests/conftest.py`` sets, so the two agree whichever comes first."""
import os
import sys

if "jax" not in sys.modules:
    os.environ.setdefault(
        "XLA_FLAGS",
        (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8").strip(),
    )
