"""Reduce a profiler trace of the measured window to device metrics.

The server child wraps the window in ``jax.profiler.trace`` and marks it
with a host annotation named :data:`WINDOW`; around its calls into each
layer it records host annotations named in :data:`HOST_LAYERS`. From the
``.xplane.pb`` this module takes:

- ``window_s``: the length of the :data:`WINDOW` annotation;
- ``busy_s``: the union of the intervals in which an operation ran on the
  device (each device plane's op and async-op lines), clipped to the window
  and averaged over the devices;
- ``compute_s``: the same union over non-transfer operations only (the op
  line, less any transfer), which a kernel's roofline share divides by. It
  is not filtered by kernel name, so that a renamed or fused kernel still
  counts;
- ``device_ops``: device seconds by operation (``opcode shape name``),
  largest first;
- ``idle_gaps``: the device's idle seconds in the window, by the innermost
  host layer that was running meanwhile (``idle:none`` when no annotated
  layer was).
"""

from __future__ import annotations

import glob
import os
import re

#: host annotation spanning exactly the measured window
WINDOW = "bench.window"
#: host layer annotations, innermost first: idle device time is charged to
#: the first of these that was running
HOST_LAYERS = (
    "bench.kernel.decode_batch",      # copy in, kernel dispatch, read back
    "bench.kernel.multiget_decode",   # token matrix packing around it
    "bench.store.decode_misses",      # per-miss token lists
    "bench.store.multiget",           # range checks and cache probes
    "bench.rpc",                      # frame decode, service queue, reply
)
#: device operations that move data between host and device, not compute
TRANSFER_RE = re.compile(
    r"(?i)(^|[^a-z])(copy-start|copy-done|send|recv|infeed|outfeed|"
    r"host-to-device|device-to-host|transfer|memcpy)([^a-z]|$)")
#: the line of a device plane whose events are operations; the async line
#: holds the device's DMA transfers, which keep it busy but compute nothing
DEVICE_OP_LINE = "XLA Ops"
DEVICE_ASYNC_LINE = "Async XLA Ops"
#: ``%name = type[shape]{layout} opcode(...)``: how the TPU profiler names
#: an operation
HLO_RE = re.compile(r"^(%\S+) = (\w+\[[\d,]*\])\S* ([\w-]+)\(")
#: planes of the chips themselves (``/device:TPU:0``)
DEVICE_PLANE_RE = re.compile(r"^/device:[A-Za-z_]+:\d+$")


def union(intervals):
    """Sorted disjoint union of ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def intersect(a, b):
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b):
    """``a`` minus ``b``, both sorted disjoint interval lists."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def reduce_events(device_lines, host_events, window):
    """Metrics of one window from plain event lists.

    ``device_lines``: one list per device of ``(name, start, end, is_op)``
    events, ``is_op`` false for the async (transfer) line; ``host_events``:
    ``(name, start, end)`` host annotations; ``window``: ``(start, end)``. Times in any one unit; seconds out when
    given seconds in.
    """
    win = [tuple(window)]
    busy, compute, per_op = [], [], {}
    for events in device_lines:
        ops = union((s, e) for _, s, e, _ in events)
        busy.append(total(intersect(ops, win)))
        comp = union((s, e) for n, s, e, is_op in events
                     if is_op and not TRANSFER_RE.search(n))
        compute.append(total(intersect(comp, win)))
        for n, s, e, _ in events:
            clipped = min(e, window[1]) - max(s, window[0])
            if clipped > 0:
                label = op_label(n)
                per_op[label] = per_op.get(label, 0.0) + clipped
    n_dev = max(len(device_lines), 1)
    # idle gaps of the first device, charged to the innermost host layer
    first = union((s, e) for _, s, e, _ in (device_lines[0] if device_lines
                                            else []))
    idle = subtract(win, first)
    gaps = {}
    for layer in HOST_LAYERS:
        spans = union((s, e) for n, s, e in host_events if n == layer)
        got = intersect(idle, spans)
        if got:
            gaps["idle:" + layer.removeprefix("bench.")] = total(got)
            idle = subtract(idle, spans)
    if total(idle) > 0:
        gaps["idle:none"] = total(idle)
    return {
        "window_s": float(window[1] - window[0]),
        "busy_s": sum(busy) / n_dev,
        "compute_s": sum(compute) / n_dev,
        "device_ops": sorted(per_op.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:10],
    }


def op_label(name: str) -> str:
    """``opcode shape name`` of a profiler op name; its HLO name alone where
    the result is a tuple, and its first 80 characters where it is not HLO
    text."""
    m = HLO_RE.match(name)
    if m:
        return f"{m.group(3)} {m.group(2)} {m.group(1)}"
    return name.split(" = ", 1)[0] if " = " in name else name[:80]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}: {paths}")
    return paths[0]


def read_xplane(path: str):
    """``(device_lines, host_events, window, inventory)`` from a trace, in
    seconds. ``inventory`` lists each plane's lines and event counts (what
    a reader of the trace needs before trusting a reduction)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device_lines, host_events, inventory = [], [], []
    window = None
    for plane in data.planes:
        lines = list(plane.lines)
        inventory.append([plane.name, [[ln.name, len(list(ln.events))]
                                       for ln in lines]])
        if DEVICE_PLANE_RE.match(plane.name):
            device_lines.append([
                (ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                 ln.name == DEVICE_OP_LINE)
                for ln in lines if ln.name in (DEVICE_OP_LINE,
                                               DEVICE_ASYNC_LINE)
                for ev in ln.events])
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    elif ev.name.startswith("bench."):
                        host_events.append((ev.name, ev.start_ns * 1e-9,
                                            ev.end_ns * 1e-9))
    if window is None:
        raise RuntimeError(f"no {WINDOW} annotation in {path}")
    return device_lines, host_events, window, inventory


def reduce_trace(log_dir: str) -> dict:
    device_lines, host_events, window, inventory = read_xplane(
        find_xplane(log_dir))
    out = reduce_events(device_lines, host_events, window)
    out["n_devices"] = len(device_lines)
    out["inventory"] = inventory
    return out
