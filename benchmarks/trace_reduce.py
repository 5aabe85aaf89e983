"""From a ``jax.profiler`` trace to what the per-layer readers read.

Device operations are the events of each TPU plane's ``XLA Ops`` line;
``busy_s`` is the union of their intervals, averaged over the chips the cell
uses. The host's spans are the harness's own ``bench:`` annotations (one per
file), on the same clock, so an idle gap can be named by the file it fell in.
"""

from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir: str, notes_path: str | None = None, rehearsal: bool = False):
    """(per-device list of (name, start_ns, duration_ns), host spans alike).
    A rehearsal has no TPU plane: XLA's CPU threads stand in, so that the
    readers run; what they read there is no device number."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(newest_xplane(trace_dir))
    devices, spans, notes = [], [], []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in line.events]
            notes.append(f"{plane.name}\t{line.name}\t{len(events)}\t"
                         + "; ".join(sorted({e[0] for e in events})[:12]))
            if is_device and line.name == OPS_LINE:
                devices.append(events)
            elif rehearsal and line.name.startswith("tf_XLAPjRtCpuClient"):
                devices.append([e for e in events if e[2] > 0])
            elif not is_device:
                spans += [e for e in events if e[0].startswith(SPAN_PREFIX)]
    if notes_path:
        os.makedirs(os.path.dirname(notes_path), exist_ok=True)
        with open(notes_path, "w", encoding="utf-8") as fh:
            fh.write("\n".join(notes) + "\n")
    return devices, sorted(spans, key=lambda e: e[1])


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def busy_seconds(events) -> float:
    return sum(hi - lo for lo, hi in union([(s, s + d) for _, s, d in events])) / 1e9


def top_ops(events, k: int = 10) -> list[list]:
    total: dict[str, float] = {}
    for name, _, dur in events:
        total[name] = total.get(name, 0.0) + dur / 1e9
    return [[n[:120], s] for n, s in sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(events, spans, k: int = 10) -> list[list]:
    """Idle time of one device, summed by what the harness knows the host was
    doing: which file's span the gap began in, and whether before that file's
    first operation, between its operations or after its last."""
    busy = union([(s, s + d) for _, s, d in events])
    total: dict[str, float] = {}

    def add(label, lo, hi):
        if hi > lo:
            total[label] = total.get(label, 0.0) + (hi - lo) / 1e9

    edges = [(s, s + d, n[len(SPAN_PREFIX):]) for n, s, d in spans]
    for lo, hi, name in edges:
        inside = [(max(a, lo), min(b, hi)) for a, b in busy if b > lo and a < hi]
        if not inside:
            add(f"{name}: no device operation", lo, hi)
            continue
        add(f"{name}: before its first device operation", lo, inside[0][0])
        for (_, a), (b, _) in zip(inside, inside[1:]):
            add(f"{name}: between device operations", a, b)
        add(f"{name}: after its last device operation", inside[-1][1], hi)
    for (_, a, _), (b, _, _) in zip(edges, edges[1:]):
        add("between files", a, b)
    return [[n[:120], s] for n, s in sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def reduce(trace_dir: str, n_chips: int, notes_path: str | None = None,
           rehearsal: bool = False) -> dict:
    devices, spans = load(trace_dir, notes_path, rehearsal)
    devices = devices[:n_chips]
    if not devices or not any(devices):
        raise RuntimeError("the trace holds no device operation: the cell did "
                           "not drive the device path")
    every = [e for d in devices for e in d]
    return {
        "device_events": devices, "spans": spans,
        "busy_s": sum(busy_seconds(d) for d in devices) / len(devices),
        "breakdown": {"device_ops": top_ops(every),
                      "idle_gaps": idle_gaps(devices[0], spans)},
    }
