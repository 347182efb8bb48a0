"""Device boot: the one place that decides what this process runs on.

Every entry point that reaches a JAX backend — `cli server` (which
`benchmark/run.py` starts), `__graft_entry__.py`, the children of
`chip_smoke.py` — calls `boot()` before its first backend use. The
package is written for a TPU, so a TPU is what `boot()` demands: the host
CPU is used only when the operator asked for it with `JAX_PLATFORMS=cpu`
(the test suite), never because detection found nothing better. Installed
JAX answers a missing chip by printing a libtpu error and handing back
`[CpuDevice(id=0)]`; without this check a server would serve from the host
and a benchmark would time it.

`boot()` also places the persistent compile cache. Where
`JAX_COMPILATION_CACHE_DIR` is set JAX already uses that directory and
nothing is set here; otherwise the cache lives at one fixed path inside
the checkout (`.jax_cache/`, git-ignored) — never a temporary, pid- or
time-derived name, because a cache that moves never hits.

`--spmd` servers must call `jax.distributed.initialize` before any backend
exists (cli.cmd_server), so `boot()` is the FIRST thing to initialise a
backend and nothing in it runs at import time.
"""

import os
import sys

__all__ = ["DEFAULT_CACHE_DIR", "boot", "facts", "memory_bytes", "cache_dir",
           "backends_are_initialized"]

#: compile cache used when JAX_COMPILATION_CACHE_DIR is unset
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_FACTS = None  # resolved once per process (the backend cannot change)
_UNREAD = object()
_MEMORY = _UNREAD  # bytes of one local device, or None; read once, as _FACTS


def backends_are_initialized():
    """True when this process already holds a JAX backend. Telemetry
    (RuntimeMonitor gauges, /debug/hbm device_memory) asks before touching
    `jax.local_devices()`: a metrics sampler must never be what
    initialises the backend — under --spmd that has to wait for
    `jax.distributed.initialize`."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def cache_dir():
    """The compile cache directory this process uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def _read_facts():
    import jax

    first = jax.devices()[0]
    return {
        "platform": first.platform,
        "deviceKind": first.device_kind,
        "deviceCount": len(jax.devices()),
        "localDeviceCount": len(jax.local_devices()),
    }


def _read_memory():
    """`memory_stats()["bytes_limit"]` of one local device (the least,
    should they differ), or None where the backend reports none: the
    host CPU does not."""
    import jax

    limits = []
    for d in jax.local_devices():
        stats = d.memory_stats() if callable(
            getattr(d, "memory_stats", None)) else None
        if not stats or not stats.get("bytes_limit"):
            return None
        limits.append(int(stats["bytes_limit"]))
    return min(limits) if limits else None


def memory_bytes():
    """How many bytes of memory one of this process's devices has, as the
    device reports it, or None: the backend reports none (the host CPU),
    or no backend exists yet. The one place that says so — the stack
    budgets of `exec/stacked.py` are shares of it. `boot()` reads it
    beside `facts()`; an in-process API (tests) reads it on the first call
    after a backend exists, and a call before that initialises nothing
    (the rule `backends_are_initialized` is for)."""
    global _MEMORY
    if _MEMORY is _UNREAD:
        if not backends_are_initialized():
            return None
        _MEMORY = _read_memory()
    return _MEMORY


def facts():
    """{platform, deviceKind, deviceCount, localDeviceCount} as JAX
    reports them (`jax.devices()[0].platform`, `.device_kind`,
    `len(jax.devices())`) — what `/info` serves so a client can tell what
    answered it. Cached: `boot()` fills it in a server; an in-process API
    (tests) resolves it on first use."""
    global _FACTS
    if _FACTS is None:
        _FACTS = _read_facts()
    return _FACTS


def boot():
    """Resolve the backend, place the compile cache, log one line.
    Returns `facts()`. Exits non-zero (SystemExit with a message) when
    the backend is not a TPU and `JAX_PLATFORMS=cpu` was not set
    explicitly."""
    global _FACTS, _MEMORY
    if _FACTS is not None:
        return _FACTS
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # JAX's default keeps only programs that took >= 1 s to compile; a
    # restarted server (and every fresh chip-tool call) re-runs hundreds
    # of sub-second compiles, so keep them all.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    want = "cpu" if os.environ.get(
        "JAX_PLATFORMS", "").strip().lower() == "cpu" else "tpu"
    try:
        backend = jax.default_backend()
    except RuntimeError as e:
        # JAX_PLATFORMS named a platform that failed to initialise
        raise SystemExit(
            f"pilosa_tpu: no usable JAX backend "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}): {e}")
    if backend != want:
        raise SystemExit(
            f"pilosa_tpu: needs a TPU but jax.default_backend() is "
            f"{backend!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}, devices="
            f"{jax.devices()}). Set JAX_PLATFORMS=cpu to run on the "
            f"host CPU on purpose.")
    _FACTS = _read_facts()
    _MEMORY = _read_memory()
    print(f"pilosa_tpu device: platform={_FACTS['platform']} "
          f"device_kind={_FACTS['deviceKind']!r} "
          f"local_devices={_FACTS['localDeviceCount']} "
          f"global_devices={_FACTS['deviceCount']} "
          f"device_memory_bytes={_MEMORY} "
          f"compile_cache={cache_dir()}", file=sys.stderr, flush=True)
    return _FACTS
