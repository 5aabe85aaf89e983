"""The program's own spans and the device's operations, from one traced
run's xplane, opened once for all the readers that need more than
``trace_reduce`` hands them.

The program writes a ``jax.profiler.TraceAnnotation`` named ``vctpu:<span>``
for every ``utils.trace.stage`` while obs is on, with two stats: ``trace``
(the chunk's trace id) and ``thread`` (the Python thread's name; every
Python thread's line is called ``python`` in the trace). They sit in the
host plane on the same clock as the TPU planes' ``XLA Ops`` lines, whose
events carry their stats too (on a TPU one of them holds the
``jax.named_scope`` path of the operation). A program without such spans
gives empty lists, never an error.
"""

from __future__ import annotations

import os

import trace_reduce

SPAN_PREFIX = "vctpu:"
BENCH = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(BENCH, ".work", "trace")
NOTES = os.path.join(os.path.dirname(BENCH), "chiprun_out", "benchmarks",
                     "program_spans.txt")
_LOADED: dict = {}


def load(trace_dir: str = TRACE_DIR) -> dict:
    """``{"threads": {thread name: [(span, start_ns, duration_ns, trace id)]},
    "devices": [[(name, start_ns, duration_ns, stats)] per device]}`` of the
    newest xplane under ``trace_dir``. Without a TPU plane (a rehearsal)
    XLA's CPU client threads stand in for the devices, as in
    ``trace_reduce``."""
    from jax.profiler import ProfileData

    path = trace_reduce.newest_xplane(trace_dir)
    key = (path, os.path.getmtime(path))
    if key in _LOADED:
        return _LOADED[key]
    data = ProfileData.from_file(path)

    def operations(line):
        return [(e.name, float(e.start_ns), float(e.duration_ns), dict(e.stats))
                for e in line.events if e.duration_ns > 0]

    threads: dict[str, list] = {}
    tpu, cpu = [], []
    for plane in data.planes:
        is_tpu = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_tpu:
                if line.name == trace_reduce.OPS_LINE:
                    tpu.append(operations(line))
            elif line.name.startswith("tf_XLAPjRtCpuClient"):
                cpu.append(operations(line))
            else:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        stats = dict(e.stats)
                        threads.setdefault(
                            str(stats.get("thread") or f"{plane.name}/{line.name}"),
                            []).append((e.name[len(SPAN_PREFIX):],
                                        float(e.start_ns), float(e.duration_ns),
                                        str(stats.get("trace", ""))))
    for spans in threads.values():
        spans.sort(key=lambda s: (s[1], -s[2]))
    out = {"threads": threads, "devices": tpu or cpu, "on_tpu": bool(tpu)}
    _LOADED.clear()
    _LOADED[key] = out
    write_notes(out)
    return out


def write_notes(loaded: dict, path: str = NOTES) -> None:
    """What a builder needs to see once by hand: which spans the trace
    holds, and which stat keys the device's events carry (and which of
    them mention a ``vctpu_`` scope)."""
    count: dict[str, int] = {}
    for spans in loaded["threads"].values():
        for name, *_ in spans:
            count[name] = count.get(name, 0) + 1
    keys: dict[str, int] = {}
    scoped: dict[str, int] = {}
    example = {}
    for dev in loaded["devices"][:1]:
        for name, _, _, stats in dev:
            for k, v in stats.items():
                keys[k] = keys.get(k, 0) + 1
                if "vctpu_" in str(v):
                    scoped[k] = scoped.get(k, 0) + 1
                    example.setdefault(k, (name[:60], str(v)[:200]))
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(f"on_tpu\t{loaded['on_tpu']}\n")
            fh.write(f"threads with vctpu: spans\t{len(loaded['threads'])}\n")
            for name, n in sorted(count.items()):
                fh.write(f"span\t{name}\t{n}\n")
            for k, n in sorted(keys.items()):
                fh.write(f"device stat\t{k}\t{n} events\t"
                         f"{scoped.get(k, 0)} mention vctpu_\n")
            for k, (op, v) in sorted(example.items()):
                fh.write(f"example\t{k}\t{op}\t{v}\n")
            for dev in loaded["devices"][:1]:
                for name, _, dur, stats in sorted(dev, key=lambda e: -e[2])[:3]:
                    fh.write(f"longest\t{name[:60]}\t{dur / 1e9:.6f}s\t"
                             + "; ".join(f"{k}={str(v)[:160]}"
                                         for k, v in sorted(stats.items())) + "\n")
    except OSError:
        pass  # notes are for reading by hand; a metric never depends on them
