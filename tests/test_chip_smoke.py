"""CPU rehearsal of chip_smoke.py: the script's own phase functions at a
tiny size with the device check stubbed, so its control flow is guarded
without a chip — plus the refusals that must hold off the chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from volsync_tpu import compile_cache  # noqa: E402

CACHE_ENV = compile_cache._ENV  # the one place the name is spelled

MiB, KiB = 1 << 20, 1 << 10

#: dedup_min < 0: an 8 MiB file has too few chunks for the repeated
#: half to be found reliably; the chip's 1 GiB file is held to 256 MiB.
TINY = chip_smoke.Sizes(
    big=8 * MiB, dedup_min=-MiB, mids=(1536 * KiB, 700 * KiB),
    n_small=24, small_lo=1 * KiB, small_hi=900 * KiB, append=64 * KiB,
    stream=3 * MiB, delta=2 * MiB, spans=12, rs_pack=256 * KiB,
    mesh_file=6 * MiB)


def _cpu_device(chips):
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def test_one_chip_phases_rehearsal(tmp_path, monkeypatch, capsys):
    """Every one-chip phase, in the script's order, through run()."""
    proofs = []
    monkeypatch.setenv(CACHE_ENV, str(tmp_path / "cache"))
    monkeypatch.setattr(chip_smoke, "require_tpu", _cpu_device)
    monkeypatch.setattr(chip_smoke, "phase_kernel_proof", proofs.append)
    device = chip_smoke.run(1, 7, TINY, tmp_path)
    assert device["platform"] == "cpu" and proofs == [7]
    lines = capsys.readouterr().out.strip().splitlines()
    phases = [json.loads(ln)["phase"] for ln in lines]
    assert phases == [
        "start", "make-volume", "backup", "churn", "backup-incremental",
        "restore", "verify-restore", "repo-check", "service",
        "rsync-delta", "rclone-spans-md5", "rs-4+2", "total"]


def test_mesh_phase_rehearsal(tmp_path, capsys):
    """The --chips 4 phase on the suite's virtual CPU devices: mesh and
    single-chip engines write identical snapshots, a shard on every
    device."""
    import jax

    chip_smoke.phase_mesh(tmp_path, 7, TINY, jax.device_count())
    out = capsys.readouterr().out
    assert '"phase": "mesh-compare"' in out
    assert f'"shard_devices": {jax.device_count()}' in out


def test_mesh_phase_counts_devices(tmp_path):
    """On one device the seq mesh is silently a one-device mesh; the
    phase must refuse to call that a four-chip run."""
    import jax

    with pytest.raises(chip_smoke.SmokeFailure, match="device_count"):
        chip_smoke.phase_mesh(tmp_path, 7, TINY, jax.device_count() + 1)


def test_kernel_proof_fails_without_kernels():
    """On the CPU backend the compiled segment programs hold no Mosaic
    kernel: the proof phase must fail, not pass on the XLA stand-in."""
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.phase_kernel_proof(7)


def test_refuses_volsync_overrides():
    with pytest.raises(chip_smoke.SmokeFailure, match="VOLSYNC_ENGINE"):
        chip_smoke.refuse_overrides({"VOLSYNC_ENGINE": "mesh", "HOME": "/x"})
    chip_smoke.refuse_overrides({"HOME": "/x", "JAX_PLATFORMS": "cpu"})


def test_script_exits_nonzero_on_cpu_backend(tmp_path):
    """``JAX_PLATFORMS=cpu python chip_smoke.py``: non-zero, no result
    line, before any phase ran."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("VOLSYNC_")}
    env.update({"JAX_PLATFORMS": "cpu", "TMPDIR": str(tmp_path),
                CACHE_ENV: str(tmp_path / "cache")})
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no accelerator" in r.stderr
    assert list(tmp_path.glob("chip_smoke_*")) == []  # workdir removed


def test_compile_cache_placement(monkeypatch):
    """Placed from outside when the environment names a directory; at
    the fixed <checkout>/.jax_cache when it does not."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(CACHE_ENV, "/some/dir")
        assert compile_cache.configure() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == "/some/dir"
        monkeypatch.delenv(CACHE_ENV)
        want = str(ROOT / ".jax_cache")
        assert compile_cache.configure() == want
        assert os.environ[CACHE_ENV] == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        monkeypatch.delenv(CACHE_ENV, raising=False)
        jax.config.update("jax_compilation_cache_dir", prev)
