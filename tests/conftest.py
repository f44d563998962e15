"""Test harness config.

Tests run on CPU with 8 virtual devices (reference test strategy SURVEY.md
§4: cpu is the reference backend).  The multi-device tests
(tests/test_parallel.py) build a jax.sharding.Mesh over these virtual
devices and run the same shard_map/pjit code paths that run on a real
v5e-8, the way the reference's nightly dist tests use local
multi-process kvstore.
"""
import os

prev = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8").strip()
# The suite's contract is the CPU backend with 8 virtual devices,
# whatever the machine holds: the chip is exercised by chip_smoke.py,
# never by pytest.
os.environ["JAX_PLATFORMS"] = "cpu"
# The suite compiles thousands of small programs, many of them twice
# (fresh blocks with the same HLO, the workers of the multi-process
# tests): let the persistent compile cache keep them too, not only
# the ones that take JAX's default of a second.  Inherited by every
# child the tests start.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def paired_params(a, b):
    """Structurally-paired parameters of two same-architecture blocks.

    The obvious ``zip(sorted(a.collect_params().items()), ...)`` idiom
    is order-fragile: gluon's auto-name counter is process-global, and
    once it passes 9, ``dense10_weight`` sorts BEFORE ``dense9_weight``
    -- so whether the pairing is correct depends on how many blocks
    earlier tests created.  Structural prefixes are position-stable.
    """
    pa = a._collect_params_with_prefix()
    pb = b._collect_params_with_prefix()
    assert set(pa) == set(pb)
    return [(pa[k], pb[k]) for k in sorted(pa)]


@pytest.fixture()
def v5e_peaks(monkeypatch):
    """A CPU has no published peak, so no MFU and no roofline section;
    tests of those sections run against the v5e row of the one table
    (``profiling.roofline.DEVICE_PEAKS``)."""
    from mxnet_tpu.profiling import roofline
    monkeypatch.setattr(roofline, "device_peaks",
                        lambda kind=None: roofline.DEVICE_PEAKS["TPU v5e"])


@pytest.fixture(autouse=True)
def _seed_everything():
    """Per-test deterministic seeding (reference:
    ``tests/python/unittest/common.py :: with_seed``)."""
    import mxnet_tpu as mx
    np.random.seed(0)
    mx.random.seed(0)
    yield
