"""Test harness configuration.

Multi-"node" behavior is tested the way the reference tests multi-node
clusters on one box (src/test/regress/pg_regress_multi.pl launches a
coordinator + workers on localhost): we force JAX onto the host platform
with 8 virtual devices so every sharding/collective path runs exactly as
it would on an 8-device mesh.  The tests therefore check programs and
answers, never the chip: what runs on a TPU is ``chip_smoke.py``'s to
prove (tests/test_chip_bringup.py rehearses it here).

The cpu pin below is also what lets the executor run at all: it uses
the CPU platform only when asked for by name
(citus_tpu/parallel/mesh.py ``executor_devices``).  XLA_FLAGS must be
set before the backend initializes.  Tier-1 is
``JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow'``.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

assert len(jax.devices()) == 8, f"expected 8 cpu devices, got {jax.devices()}"


@pytest.fixture()
def tmp_cluster(tmp_path):
    import citus_tpu as ct

    cluster = ct.Cluster(str(tmp_path / "db"))
    yield cluster


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture()
def limit_devices(monkeypatch):
    """``limit_devices(n)``: run the executor on the first ``n`` of the
    harness's 8 virtual devices — 1 takes the single-device scan loops,
    4 the mesh loops."""
    def limit(n):
        from citus_tpu.parallel import mesh
        devs = jax.devices()[:n]
        monkeypatch.setattr(mesh, "executor_devices", lambda: devs)
    return limit


@pytest.fixture()
def producers(monkeypatch):
    """``producers(n)``: the machine has the cores for ``n`` threads
    decoding a streamed scan's batches at once (a scan still takes no
    more than it has streams); 1 = the one decode thread."""
    def allow(n):
        from citus_tpu.storage import reader
        monkeypatch.setattr(reader, "usable_cores",
                            lambda: n * reader._THREADS_A_PRODUCER)
    return allow


@pytest.fixture(scope="session", autouse=True)
def _sanitizer_teardown_gate():
    """When the suite runs under CITUS_SANITIZE, an empty
    citus_sanitizer_report() at teardown is part of the contract —
    findings any individual test missed still fail the run."""
    yield
    from citus_tpu.utils import sanitizer

    if sanitizer.enabled():
        findings = sanitizer.report()
        assert not findings, (
            "concurrency sanitizer findings at teardown: %r" % findings)
