"""Test environment: pin JAX to a virtual 8-device CPU mesh so the sharding
paths compile and execute without TPU hardware. The suite says nothing about
the TPU backend; the chip is checked by chip_smoke.py (the served path) and
tests/test_tpu_compile.py (the TPU compiler, no device attached)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_ENABLE_X64", "1")
# Keep the bcrypt-stand-in cheap under test (production default is 600k;
# the count is tagged into each hash, so both verify correctly).
os.environ.setdefault("ETCD_PBKDF2_ITERS", "4096")

from etcd_tpu.utils.platform import enable_compile_cache, force_cpu  # noqa: E402

force_cpu(8)
enable_compile_cache()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running chaos/e2e test")
