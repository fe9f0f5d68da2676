"""Test configuration.

Reference test strategy (SURVEY.md §4): one suite, many contexts; numpy as
oracle; seed discipline via MXNET_TEST_SEED.

Two platforms (the reference's cpu/gpu re-import trick, context-parametrized
at the process level):

- default: 8-virtual-device CPU mesh (``xla_force_host_platform_device
  _count``) — fast, and required for the mesh/parallel tests; the analog of
  the reference's multi-process-on-one-box launcher tests.
- ``MXTPU_TEST_PLATFORM=tpu``: run the same suites on the real TPU chip
  (one pytest process — a chip belongs to one process at a time; single
  device; multi-device tests auto-skip). bf16-aware tolerances come from
  test_utils.default_rtol_atol; README.md "Running it" lists the
  tolerances widened for TPU transcendentals. Example:

      MXTPU_TEST_PLATFORM=tpu python -m pytest tests/test_operator.py \
          tests/test_ndarray.py tests/test_gluon.py -q
"""

import os

_PLATFORM = os.environ.get("MXTPU_TEST_PLATFORM", "cpu")

if _PLATFORM == "cpu":
    # Must run before jax is imported anywhere.
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import jax

if _PLATFORM == "cpu":
    # pin the platform in jax's own config too, so the suite runs on the
    # 8-virtual-device CPU backend (fast, and required for mesh tests)
    # whatever JAX_PLATFORMS said when jax was imported
    jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running training tests")
    config.addinivalue_line(
        "markers", "the_rule: flash_attention picks its own implementation "
        "(tests/test_pallas_attention.py pins the kernels everywhere else)")


def pytest_collection_modifyitems(config, items):
    if len(jax.devices()) > 1:
        return
    # single-chip run (MXTPU_TEST_PLATFORM=tpu): the multi-device SPMD /
    # distributed suites need the virtual CPU mesh
    multi_dev = ("test_parallel", "test_distributed", "test_bert_seqparallel")
    skip = pytest.mark.skip(reason="needs a multi-device mesh "
                                   "(run on the CPU test platform)")
    for item in items:
        if any(m in item.nodeid for m in multi_dev):
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _seed_everything():
    """Seed discipline: every test runs with a logged, overridable seed
    (reference @with_seed / MXNET_TEST_SEED)."""
    seed = int(os.environ.get("MXTPU_TEST_SEED",
                              os.environ.get("MXNET_TEST_SEED", "42")))
    np.random.seed(seed)
    import incubator_mxnet_tpu as mx

    mx.random.seed(seed)
    yield
