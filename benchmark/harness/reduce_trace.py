"""From a profiler trace to device busy time, device operations and the
idle gaps named by what the host was doing.

Two steps, so that the arithmetic can be checked on a small recording:

    extract(path)   reads `*.xplane.pb` with `jax.profiler.ProfileData`
                    into plain lists (the only step that needs JAX)
    reduce(events)  pure Python over those lists

A device's operations are the events of the `XLA Ops` line of a
`/device:TPU:<n>` plane. On the host platform (the CPU rehearsal) XLA's
own threads stand in: events of `/host:CPU` that carry an `hlo_op` stat,
as device 0. Host events are every other event of `/host:CPU`.

The harness marks the span it counts queries in with a host event named
SPAN_MARK. The profiler records from inside `start_trace` to the end of
`stop_trace`, some tenths of a second more than that span; `reduce` clips
the device's operations to the mark, so that busy time and window are of
the span the queries are counted in. A trace without the mark (one made by
hand) is reduced over all it holds.
"""

import glob
import os

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
SKIP_HOST = ("ThreadpoolListener",)
SPAN_MARK = "benchmark.traced_span"


def op_name(text):
    """A device op's event name is its whole HLO instruction
    (`%convert_reduce_fusion = s32[954]{...} fusion(...)`): keep the
    instruction's own name."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def extract(path):
    """-> {"devices": {plane: [[name, start_ns, dur_ns], ...]},
           "host": [[name, start_ns, dur_ns], ...]}"""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        [op_name(e.name), e.start_ns, e.duration_ns]
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SKIP_HOST):
                        continue
                    row = [e.name, e.start_ns, e.duration_ns]
                    if any(k == "hlo_op" for k, _ in e.stats):
                        devices.setdefault("host-xla", []).append(row)
                    else:
                        host.append(row)
    return {"devices": devices, "host": host}


def describe(path, head=3):
    """Planes, lines and their first events, as text: what to look at by
    hand before trusting `extract` on a new device or JAX version."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name}: {len(events)} events")
            out.extend(f"    {e.name} start={e.start_ns} dur={e.duration_ns}"
                       for e in events[:head])
    return "\n".join(out)


def union(intervals):
    """Sorted, merged [start, end] list of (start, end) pairs."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _gaps(busy, lo, hi):
    out, at = [], lo
    for start, end in busy:
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if hi > at:
        out.append((at, hi))
    return out


def _name_gaps(gaps, host):
    """Seconds of device idleness by the host event covering each gap's
    middle: the innermost (shortest) such event, else "unattributed". One
    sweep over gaps and events in time order, keeping the events still
    open: a wait that polls in thousands of short events stays in view
    however many of them started since it began."""
    host = sorted(host, key=lambda e: e[1])
    totals, open_events, nxt = {}, [], 0
    for lo, hi in sorted(gaps):
        mid = (lo + hi) / 2
        while nxt < len(host) and host[nxt][1] <= mid:
            open_events.append(host[nxt])
            nxt += 1
        open_events = [e for e in open_events if e[1] + e[2] >= mid]
        best = min(open_events, key=lambda e: e[2], default=None)
        key = best[0] if best else "unattributed"
        totals[key] = totals.get(key, 0.0) + (hi - lo) / 1e9
    return totals


def _top(totals, n=10):
    return [[k, v] for k, v in sorted(
        totals.items(), key=lambda kv: -kv[1])[:n]]


def reduce(events):
    """-> busy_s and idle share per device, the mean over devices, the
    traced window's length, the operations that took most device time
    (seconds summed over devices) and the longest idle gaps of the
    busiest device by host event. None when no operation ran on a device."""
    marks = [e for e in events["host"] if e[0] == SPAN_MARK]
    host = [e for e in events["host"] if e[0] != SPAN_MARK]
    every = [e for ops in events["devices"].values() for e in ops] + host
    if marks:
        lo, hi = marks[0][1], marks[0][1] + marks[0][2]
    elif every:
        lo = min(e[1] for e in every)
        hi = max(e[1] + e[2] for e in every)
    devices = {}
    for plane, ops in events["devices"].items():
        clipped = [[name, max(s, lo), min(s + d, hi) - max(s, lo)]
                   for name, s, d in ops if s < hi and s + d > lo]
        if clipped:
            devices[plane] = clipped
    if not devices:
        return None
    window_s = (hi - lo) / 1e9
    busy, op_totals = {}, {}
    merged = {}
    for plane, ops in devices.items():
        merged[plane] = union((s, s + d) for _, s, d in ops)
        busy[plane] = sum(e - s for s, e in merged[plane]) / 1e9
        for name, _, dur in ops:
            op_totals[name] = op_totals.get(name, 0.0) + dur / 1e9
    busiest = max(busy, key=busy.get)
    gap_totals = _name_gaps(_gaps(merged[busiest], lo, hi), host)
    return {
        "window_s": window_s,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_by_device": busy,
        "idle_share_by_device": {
            k: 1.0 - v / window_s for k, v in busy.items()},
        "device_ops": _top(op_totals),
        "idle_gaps": _top(gap_totals),
    }
