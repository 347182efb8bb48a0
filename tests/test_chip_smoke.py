"""chip_smoke.py on the host: `--platform cpu` runs every phase (kernels
in interpret mode, native rebuild, serve over HTTP, restart) at a tiny
size and passes; one disagreeing oracle, a missing accelerator, or a copy
of the script without the repo must each leave a non-zero exit code and
no result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (JAX-free by contract — see below)


def _result_line(stdout):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except ValueError:
        return None


def test_parent_never_imports_jax():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke\n"
         "chip_smoke.north_oracle(chip_smoke.north_planes(0, 2))\n"
         "chip_smoke.mixed_oracle(chip_smoke.mixed_data(0, 1, 50))\n"
         "import pilosa_tpu.server.client, pilosa_tpu.roaring\n"
         "assert 'jax' not in sys.modules, 'parent touched jax'\n"],
        cwd=REPO, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr


def test_cpu_dry_run_passes_every_phase():
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--platform", "cpu",
         "--seed", "7"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    assert _result_line(out.stdout) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu",
                   "count": _result_line(out.stdout)["device"]["count"]}}
    body = out.stdout
    for needle in (
            "kernels: 22/22 kernel checks passed",
            "build: rebuilt native/libpilosa_native.so",
            "serve: ok   north bits acknowledged by import_roaring",
            "serve: ok   32 concurrent Counts",
            "(strategy, dispatches) = ('stacked', 1)",
            "serve: ok   Set: (stack patches, planes re-uploaded) = (1, 1)",
            "serve: ok   mixed: GroupBy(Rows(a), Rows(b))",
            "restart: ok   north: first query Count(Row(f=1)) "
            "(acknowledged Set included)",
            "restart: ok   mixed: Sum(field=v)",
            "restart: compile cache:",
            "summary: reduced: [\"north: 8 shards instead of 954"):
        assert needle in body, needle
    # every line names the device it ran on, as a child/server reported it
    for line in body.strip().splitlines()[:-1]:
        assert line.startswith("smoke [platform=cpu kind='cpu' count=") \
            or line.startswith("smoke [device=not-yet-reported]") \
            or line.startswith("    "), line
    assert "FAIL" not in body
    assert not os.path.exists(chip_smoke.DATA_DIR)  # cleaned up


def test_disagreeing_oracle_fails_the_run(monkeypatch, capsys):
    real = chip_smoke.north_oracle

    def off_by_one(planes):
        out = real(planes)
        out["Count(Xor(Row(f=2), Row(g=2)))"] += 1
        return out

    monkeypatch.setattr(chip_smoke, "north_oracle", off_by_one)
    rc = chip_smoke.main(["--platform", "cpu"])
    out = capsys.readouterr().out
    assert rc != 0
    assert _result_line(out) is None
    assert "FAIL north: Count(Xor(Row(f=2), Row(g=2)))" in out
    assert "restart: skipped: an earlier phase failed" in out


def test_phase_build_holds_the_native_build_lock(tmp_path, monkeypatch):
    """The unlink and the make run under native/.build.lock, which
    pilosa_tpu/native.py takes around its own make: a worker that
    imports the library meanwhile waits instead of building into the
    same files (`mv: cannot stat libpilosa_native.so.tmp`)."""
    import fcntl
    import types

    (tmp_path / "libpilosa_native.so").write_bytes(b"stale")
    (tmp_path / "libpilosa_native.so.tmp").write_bytes(b"half built")
    seen = {}

    def make(argv, **kwargs):
        seen["argv"] = argv
        seen["left"] = sorted(os.listdir(tmp_path))
        with open(tmp_path / ".build.lock", "w") as other:
            try:
                fcntl.flock(other, fcntl.LOCK_EX | fcntl.LOCK_NB)
                seen["held"] = False
            except BlockingIOError:
                seen["held"] = True
        return types.SimpleNamespace(returncode=2, stderr="no compiler")

    monkeypatch.setattr(chip_smoke, "NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(chip_smoke.subprocess, "run", make)
    sm = chip_smoke.Smoke(None)
    assert chip_smoke.phase_build(sm) is False
    assert sm.failures == ["build: make -C native exited 2: no compiler"]
    assert seen == {"argv": ["make", "-C", str(tmp_path)],
                    "left": [".build.lock"], "held": True}
    # let go once the make is over
    with open(tmp_path / ".build.lock", "w") as other:
        fcntl.flock(other, fcntl.LOCK_EX | fcntl.LOCK_NB)


def test_without_an_accelerator_it_fails_and_prints_no_result():
    """The default run demands a TPU even where the environment exports
    JAX_PLATFORMS=cpu (this sandbox does)."""
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, capture_output=True,
        text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert _result_line(out.stdout) is None
    assert "needs a TPU but jax.default_backend() is 'cpu'" in out.stdout
    assert "serve: ok" not in out.stdout


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "pilosa_tpu/ is not next to this script" in out.stderr
