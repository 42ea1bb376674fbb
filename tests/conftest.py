"""Test fixture: 8 virtual CPU devices stand in for an 8-chip TPU slice.

The reference runs every test both single-process and under ``mpirun -np 2``
(SURVEY §4).  The TPU-native equivalent: force the host platform to expose 8
XLA CPU devices so the rank mesh, shardings, and collectives execute exactly
as they would across chips; separate multi-process tests (test_multiprocess*)
launch real extra processes over the distributed control plane.

Must run before jax is imported anywhere.
"""

import os
import sys

# Escape hatch for hardware tests: with HOROVOD_TPU_TEST_REAL_TPU=1 AND an
# explicit test_flash_tpu.py target on the command line, the run uses
# whatever platform JAX resolves (a real TPU chip) instead of the virtual
# CPU mesh.  The argv guard keeps an exported var from silently changing
# the device topology of the full suite, whose tests assume the 8-device
# virtual slice.
_REAL_TPU = (os.environ.get("HOROVOD_TPU_TEST_REAL_TPU") == "1"
             and any("test_flash_tpu" in a for a in sys.argv))

if not _REAL_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


@pytest.fixture()
def hvd():
    import horovod_tpu as hvd
    hvd.init()
    yield hvd
    # State is process-global; leave initialized across tests for speed.
