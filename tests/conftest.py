"""Test harness config.

Forces JAX onto 8 virtual CPU devices (standard trick, SURVEY §4) so
Mesh/pjit/shard_map tests exercise real multi-device semantics with no TPU.

The suite is pinned to the CPU whatever the machine holds: its timings
and virtual-device meshes mean nothing on a chip, a chip belongs to one
process at a time while the suite runs under several workers, and what
must be proven on the chip is proven by ``chip_smoke.py``. The driver
exports JAX_PLATFORMS=cpu; the ``jax.config.update`` below gives the same
to a bare ``pytest`` and has to come before any backend client exists.
XLA_FLAGS is read when the backend starts, so setting it here (before
the first jax op) still works.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture()
def tmp_swarm(tmp_path):
    """A SwarmDB over a fresh LocalBroker with save_dir in tmp."""
    from swarmdb_tpu.broker.local import LocalBroker
    from swarmdb_tpu.core.runtime import SwarmDB

    db = SwarmDB(broker=LocalBroker(), save_dir=str(tmp_path / "history"))
    yield db
    db.close()


@pytest.fixture()
def restore_cache_config():
    """Put the session's persistent-compile-cache settings back after a
    test that changes them."""
    from jax.experimental.compilation_cache import compilation_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    compilation_cache.reset_cache()


@pytest.fixture()
def compile_cache_dir(tmp_path, monkeypatch, restore_cache_config):
    """A private persistent compile cache for one test, placed the way a
    deployment places it: through JAX_COMPILATION_CACHE_DIR. JAX reads
    that variable when it is imported, so the fixture — standing in for
    a process that started with it set — also puts it in ``jax.config``;
    ``enable_compile_cache()`` must then leave the directory alone.
    Every program is cached (threshold 0)."""
    from jax.experimental.compilation_cache import compilation_cache

    from swarmdb_tpu.utils.xla_cache import enable_compile_cache

    path = str(tmp_path / "xla")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
    jax.config.update("jax_compilation_cache_dir", path)
    compilation_cache.reset_cache()
    assert enable_compile_cache() == path
    assert jax.config.jax_compilation_cache_dir == path
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def pytest_sessionfinish(session, exitstatus):
    """With a runtime sanitizer on (SWARMDB_LOCKCHECK=1 /
    SWARMDB_PAGECHECK=1 / SWARMDB_KERNCHECK=1 — the CI `lockcheck`,
    `pagecheck` and `kerncheck` jobs run the chaos/HA/partition/ragged
    suites this way), a green suite that exercised a violation is
    still a FAILURE: the chaos harnesses generate the hostile
    interleavings, these hooks make them assert lock ordering, page
    safety and kernel contracts, not just liveness. Tests that provoke
    violations deliberately (tests/test_lockcheck.py,
    tests/test_pagecheck.py, tests/test_kernelcheck.py) reset the
    registries in their fixture teardown, so anything left here was
    exercised by production code paths."""
    lines = []
    if os.environ.get("SWARMDB_LOCKCHECK", "0") not in ("", "0"):
        try:
            from swarmdb_tpu.obs import lockcheck

            cycles = lockcheck.registry().cycles()
        except Exception:
            cycles = []
        if cycles:
            lines.append("lock sanitizer detected inversion cycle(s):")
            for c in cycles:
                lines.append(
                    "  " + " -> ".join(c["sites"] + [c["sites"][0]]))
    if os.environ.get("SWARMDB_PAGECHECK", "0") not in ("", "0"):
        try:
            from swarmdb_tpu.obs import pagecheck

            violations = pagecheck.registry().violations()
        except Exception:
            violations = []
        if violations:
            lines.append("page sanitizer detected violation(s):")
            for v in violations:
                lines.append(f"  [{v['kind']}] pool={v['pool']} "
                             f"pages={v['pages']}: {v['message']}")
    if os.environ.get("SWARMDB_KERNCHECK", "0") not in ("", "0"):
        try:
            from swarmdb_tpu.obs import kerncheck

            kviol = kerncheck.registry().violations()
        except Exception:
            kviol = []
        if kviol:
            lines.append("kernel sanitizer detected violation(s):")
            for v in kviol:
                lines.append(f"  [{v['kind']}] kernel={v['kernel']}: "
                             f"{v['message']}")
    if not lines:
        return
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    if tr is not None:
        tr.write_line("")
        for line in lines:
            tr.write_line(line, red=True)
    else:  # pragma: no cover - terminal plugin always present in CI
        print("\n".join(lines))
    session.exitstatus = 3


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Expose each test's call-phase outcome on the item so teardown
    fixtures can act on failure (the HA chaos tests dump their flight
    rings to SWARMDB_FLIGHT_DIR for the CI artifact upload)."""
    out = yield
    rep = out.get_result()
    if rep.when == "call":
        item.rep_call = rep
