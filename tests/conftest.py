"""Test env: force CPU backend with 8 virtual devices so multi-chip sharding
tests run without TPU hardware (SURVEY §4: the stand-in for the reference's
fork-based multi-process tests).

Backends initialise lazily, so setting the platform here — before any test
touches a device — is what keeps the whole suite on the CPU.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# the program turns the persistent compile cache on by default
# (pathway_tpu/warmup.py); the suite neither reads nor writes it, so a run
# never depends on what an earlier run left in <repo>/.jax_cache (and
# XLA:CPU logs a machine-feature error for every executable it reloads)
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _no_leaked_reader_threads():
    """Every test must leave no live connector reader threads behind: a
    leaked poll thread in a long-lived process is a real bug (round-3
    finding — the sharepoint poller outlived the whole suite). Runtimes
    started on background threads are stopped via the registry."""
    yield
    import threading
    import time

    from pathway_tpu.engine import streaming

    streaming.stop_all(join_timeout=5.0)
    deadline = time.monotonic() + 5.0
    leaked = []
    while time.monotonic() < deadline:
        leaked = [t for t in threading.enumerate()
                  if t.name.startswith("pathway-tpu-src-") and t.is_alive()]
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, f"leaked connector reader threads: {leaked}"
