"""CPU seconds of the server process by thread group (GET /debug/vars
`threads`): which thread is burning the CPU.

Read only when asked for, never on a request's path: each live task's
`utime + stime` from `/proc/self/task/<tid>/stat`, its thread found by
`threading.enumerate()`'s `native_id`. Groups:

- `request`: the HTTP handler threads (one a connection, named
  REQUEST_THREAD) and the shared work pool's workers;
- one group a named thread Python started (`cache-flush`,
  `fsync-interval`, `pilosa-watchdog`, `MainThread`, ...), a numeric
  suffix taken off (`pilosa-workpool-3` is `pilosa-workpool`);
- `runtime`: threads Python did not start (the device runtime's, the
  compiler's), with their kernel `comm` names, suffixes taken off, under
  `comm`;
- `exited`: `time.process_time()` less the live threads' sum: what
  threads that have ended burned (and the tick the live ones' clocks
  are rounded down to).

Each group is `{"cpu_seconds", "threads"}`. The kernel counts a task's
time in clock ticks (10 ms on common hosts), so a sum moves in ticks.
Where `/proc` has no task table the section is absent.
"""

import os
import re
import threading
import time

#: the name every HTTP handler thread takes (server/http_server.py)
REQUEST_THREAD = "pilosa-request"

#: thread groups that serve requests
REQUEST_GROUPS = frozenset((REQUEST_THREAD, "pilosa-workpool"))

_TASKS = "/proc/self/task"
_SUFFIX = re.compile(r"[-_.: ]*\d+$")
_TARGET = re.compile(r" \(.*\)$")   # "Thread-7 (serve)": the default name

try:
    _TICK = float(os.sysconf("SC_CLK_TCK"))
except (AttributeError, ValueError, OSError):
    _TICK = 100.0


def group_name(name):
    """A thread's name less a trailing target and number."""
    return _SUFFIX.sub("", _TARGET.sub("", name)) or name


def _task_seconds(tid):
    """(comm, utime + stime in seconds) of one task, None once it ended."""
    try:
        with open(f"{_TASKS}/{tid}/stat", "rb") as f:
            stat = f.read()
    except OSError:
        return None
    # comm sits in parentheses and may itself hold spaces and ")"
    head, _, rest = stat.rpartition(b")")
    fields = rest.split()
    comm = head.partition(b"(")[2].decode("utf-8", "replace")
    return comm, (int(fields[11]) + int(fields[12])) / _TICK


def cpu_by_group():
    """({group: {"cpu_seconds", "threads"}}, process CPU seconds), the
    groups None where the task table cannot be read."""
    try:
        tids = os.listdir(_TASKS)
    except OSError:
        return None, time.process_time()
    python = {t.native_id: t for t in threading.enumerate()}
    groups = {}
    runtime = {"cpu_seconds": 0.0, "threads": 0, "comm": {}}
    live = 0.0
    for tid in tids:
        task = _task_seconds(tid)
        if task is None:
            continue
        comm, seconds = task
        live += seconds
        thread = python.get(int(tid))
        if thread is None or isinstance(thread, threading._DummyThread):
            row = runtime
            name = group_name(comm)
            runtime["comm"][name] = runtime["comm"].get(name, 0.0) + seconds
        else:
            name = group_name(thread.name)
            if name in REQUEST_GROUPS:
                name = "request"
            row = groups.setdefault(name, {"cpu_seconds": 0.0, "threads": 0})
        row["cpu_seconds"] += seconds
        row["threads"] += 1
    process = time.process_time()
    groups["runtime"] = runtime
    groups["exited"] = {"cpu_seconds": process - live, "threads": 0}
    return dict(sorted(groups.items())), process
