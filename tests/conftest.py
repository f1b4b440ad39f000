"""Test harness setup.

Mirrors the reference's test strategy (reference: tests/conftest.py:20-76 and
tests/test_algos/test_algos.py:16-53): multi-device coverage without real
hardware.  Here that means forcing the CPU XLA backend with 8 virtual devices
(``xla_force_host_platform_device_count``) *before* JAX initializes, so mesh /
sharding / collective code paths run everywhere.
"""

import os

# Must happen before any jax import anywhere in the test session: the tests
# run on the CPU backend only (the chip is exercised by chip_smoke.py).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# XLA:CPU runs a program's independent collectives concurrently, each holding a
# worker of its intra-op pool until every participant has arrived, and sizes
# that pool from NPROC (else one worker per core).  8 virtual devices at three
# such collectives need up to 24 workers: with 8, on a loaded host, the waiting
# ones hold them all, the rest never get one, and the rendezvous aborts the
# process after 40 s (an xdist "node down": tier-1's one red from PR 27 to
# PR 31, the pipelined DV3 phase of tests/test_parallel/test_pipeline.py).
os.environ.setdefault("NPROC", "32")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: multi-process / long-running tests")


@pytest.fixture(autouse=True)
def _restore_env():
    """Detect and undo environment-variable leaks between tests."""
    saved = dict(os.environ)
    yield
    for k in set(os.environ) - set(saved):
        del os.environ[k]
    for k, v in saved.items():
        if os.environ.get(k) != v:
            os.environ[k] = v


@pytest.fixture()
def tmp_logdir(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    return tmp_path
