"""utils/device.boot — the one place that decides what a process runs on:
a TPU, or the host CPU only under an explicit JAX_PLATFORMS=cpu; the
compile cache at JAX_COMPILATION_CACHE_DIR or one fixed path inside the
checkout; one log line; the same facts on /info."""

import json
import os
import subprocess
import sys

import pytest

from pilosa_tpu.utils import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Recorder:
    """Stands in for jax.config.update so a boot() under test cannot
    re-point the pytest process's own compile cache."""

    def __init__(self):
        self.calls = {}

    def __call__(self, name, value):
        self.calls[name] = value


@pytest.fixture
def fake_boot(monkeypatch):
    """boot() with a faked backend name and a recorded jax.config.update."""
    import jax

    rec = _Recorder()
    monkeypatch.setattr(jax.config, "update", rec)
    monkeypatch.setattr(device, "_FACTS", None)

    def run(backend, platforms_env, cache_env=None):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        for name, value in (("JAX_PLATFORMS", platforms_env),
                            ("JAX_COMPILATION_CACHE_DIR", cache_env)):
            if value is None:
                monkeypatch.delenv(name, raising=False)
            else:
                monkeypatch.setenv(name, value)
        return device.boot()

    run.config = rec
    return run


def test_explicit_cpu_boots_on_cpu(fake_boot, capsys):
    facts = fake_boot("cpu", "cpu")
    assert facts["platform"] == "cpu" and facts["deviceCount"] >= 1
    assert set(facts) == {"platform", "deviceKind", "deviceCount",
                          "localDeviceCount"}
    line = capsys.readouterr().err.strip()
    assert line.count("\n") == 0  # ONE line
    for word in ("platform=cpu", "device_kind=", "local_devices=",
                 "global_devices=", f"compile_cache={device.cache_dir()}"):
        assert word in line
    assert device.boot() is facts  # resolved once


@pytest.mark.parametrize("backend,env", [
    ("cpu", None),          # no chip: JAX fell back to the host silently
    ("cpu", "tpu,cpu"),     # a fallback list is not an explicit cpu
    ("gpu", None),
    ("tpu", "cpu"),         # asked for cpu, got something else
])
def test_wrong_backend_exits_nonzero(fake_boot, backend, env):
    with pytest.raises(SystemExit) as ei:
        fake_boot(backend, env)
    assert ei.value.code not in (0, None)
    assert repr(backend) in str(ei.value.code)
    assert device._FACTS is None  # nothing cached: nothing may serve


def test_tpu_backend_boots_without_env(fake_boot):
    assert fake_boot("tpu", None)["deviceCount"] >= 1


def test_uninitialisable_platform_exits_nonzero(fake_boot, monkeypatch):
    import jax

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(device, "_FACTS", None)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(jax.config, "update", fake_boot.config)
    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(SystemExit) as ei:
        device.boot()
    assert "Unable to initialize" in str(ei.value.code)


def test_cache_dir_from_env_wins_and_nothing_else_is_set(fake_boot):
    fake_boot("cpu", "cpu", cache_env="/somewhere/else")
    assert "jax_compilation_cache_dir" not in fake_boot.config.calls
    assert device.cache_dir() == "/somewhere/else"


def test_default_cache_dir_is_fixed_and_inside_the_checkout(fake_boot):
    fake_boot("cpu", "cpu")
    placed = fake_boot.config.calls["jax_compilation_cache_dir"]
    assert placed == device.DEFAULT_CACHE_DIR == device.cache_dir()
    assert os.path.commonpath([placed, REPO]) == REPO
    # no pid, time or temp name in it: two fresh processes agree
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    seen = {subprocess.run(
        [sys.executable, "-c",
         "from pilosa_tpu.utils import device; print(device.cache_dir())"],
        cwd=REPO, env=env, capture_output=True, text=True,
        check=True).stdout.strip() for _ in range(2)}
    assert seen == {placed}


def test_backends_are_initialized_never_initialises_one():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "from pilosa_tpu.utils import device\n"
         "assert device.backends_are_initialized() is False\n"
         "assert 'jax' not in sys.modules\n"
         "import jax\n"
         "assert device.backends_are_initialized() is False\n"
         "jax.devices()\n"
         "assert device.backends_are_initialized() is True\n"],
        cwd=REPO, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr


@pytest.mark.parametrize("argv", [
    ["-m", "pilosa_tpu.cli", "server", "--bind", "127.0.0.1:0"],
    ["-c", "import __graft_entry__; __graft_entry__.entry()"],
])
def test_entry_points_refuse_to_run_without_a_chip(argv, tmp_path):
    """No TPU here and JAX_PLATFORMS unset: every entry point exits
    non-zero, naming what it found, before serving or measuring."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PILOSA_TPU_DATA_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, *argv], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "needs a TPU but jax.default_backend() is 'cpu'" in out.stderr
    assert "listening on" not in out.stdout
    assert '"metric"' not in out.stdout


def test_info_serves_the_device_facts():
    from tests.harness import ServerHarness

    h = ServerHarness()
    try:
        info = h.client.info()
    finally:
        h.close()
    import jax

    assert info["platform"] == jax.devices()[0].platform == "cpu"
    assert info["deviceKind"] == jax.devices()[0].device_kind
    assert info["deviceCount"] == len(jax.devices())
    assert info["localDeviceCount"] == len(jax.local_devices())
    assert info["shardWidth"] and info["version"]
    json.dumps(info)
