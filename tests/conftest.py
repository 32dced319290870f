"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Mirrors the reference's tier-2 trick of testing multi-node behavior with
many daemons on one box (ref: qa/standalone/ceph-helpers.sh): here,
multi-chip sharding is exercised with 8 virtual CPU devices. Must run
before jax is imported anywhere in the test process.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache (r20): the suite's placement /
# kernel cells recompile the same programs every run — ~55 s of
# test_crush's 81 s alone is compile. Placed by the repo's one rule
# (utils/jax_cache.py): JAX_COMPILATION_CACHE_DIR where it is set, else
# the fixed in-checkout directory. Safe across xdist workers — jax
# writes cache entries atomically.
from ceph_tpu.utils.jax_cache import (  # noqa: E402
    enable_persistent_compile_cache)

enable_persistent_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
