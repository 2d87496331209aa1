"""Test bootstrap: force an 8-device virtual CPU mesh before JAX imports.

Mirrors the reference's envtest strategy (SURVEY.md §4): everything below
e2e runs without real hardware. Multi-chip sharding tests use the 8 virtual
CPU devices; real-TPU behavior is covered by chip_smoke.py, the
benchmark's cells (benchmark/) and tests/test_chip_compile.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the ambient env may pin a TPU
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# An interpreter that imported jax before this file ran read the
# environment too early; jax.config still wins as long as no backend
# has been initialized.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# The suite writes no persistent compile cache: launchers under test
# (operator main, MoverJaxServer.start) place one, and CPU executables
# of an 8-device virtual mesh are not worth keeping.
jax.config.update("jax_enable_compilation_cache", False)

# Pin segment batching OFF for the suite (the default is backend-aware
# — ON for TPU): tests that exercise batching opt in explicitly with
# monkeypatch.setenv, and every "unbatched reference" run stays
# genuinely unbatched even if this suite ever runs against a real chip
# or under an ambient VOLSYNC_BATCH_SEGMENTS=1.
os.environ["VOLSYNC_BATCH_SEGMENTS"] = "0"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(1234)


@pytest.fixture(autouse=True)
def _fresh_breakers():
    """Process-wide circuit breakers (resilience.breaker_for) must not
    leak state between tests — a breaker tripped open by one test would
    fail-fast every later test against the same backend name."""
    yield
    from volsync_tpu.resilience import reset_breakers

    reset_breakers()


@pytest.fixture
def batch_segments(monkeypatch):
    """-> force(on): what follows runs with VOLSYNC_BATCH_SEGMENTS
    forced on (the shared batcher and ``chunk_hash_segments``: the way
    every cell of the benchmark reaches the one-chip engine) or off
    (the single-lane ``chunk_hash_segment`` the suite pins above). The
    batchers the test started are stopped when it ends."""
    from volsync_tpu.ops import batcher

    monkeypatch.setattr(batcher, "_SHARED", {})

    def force(on: bool) -> None:
        monkeypatch.setenv("VOLSYNC_BATCH_SEGMENTS", "1" if on else "0")

    yield force
    for b in batcher._SHARED.values():
        b.stop()


@pytest.fixture(params=[False, True], ids=["single-lane", "batcher"])
def batched(request, batch_segments):
    """Both ways to the one-chip engine, a case each."""
    batch_segments(request.param)
    return request.param


@pytest.fixture
def tmp_volume(tmp_path):
    """A small 'PVC': a directory tree with a few files."""
    root = tmp_path / "vol"
    root.mkdir()
    (root / "a.txt").write_bytes(b"hello world\n" * 100)
    (root / "sub").mkdir()
    (root / "sub" / "b.bin").write_bytes(bytes(range(256)) * 512)
    (root / "empty").write_bytes(b"")
    return root


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run tests marked slow (the full tier)")


def pytest_collection_modifyitems(config, items):
    """Two-tier suite (the reference splits unit/envtest from e2e the
    same way — SURVEY.md §4): the default run stays a fast iteration
    loop; ``--runslow`` / VOLSYNC_TEST_FULL=1 runs everything (CI and
    round-end)."""
    from volsync_tpu.envflags import env_bool

    if config.getoption("--runslow") or env_bool("VOLSYNC_TEST_FULL"):
        return
    skip = pytest.mark.skip(reason="slow tier: run with --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
