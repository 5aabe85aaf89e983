"""Device 0's idle time inside the traced interval, put down to the LAYER the
host's threads were in, as a share of the traced interval.

The idle time is cut at every start and end of a ``vctpu:`` span. In each
piece every thread votes with the layer of its innermost open span. The layer
of a span name is what the program says it is: the ``layer`` field of the
traced files' own obs ``span`` events (``utils.trace.LAYER_OF`` there), never
a table here, so a span a later PR adds is counted the day it is written. A
thread whose innermost span is of the layer ``wait`` does not vote (it causes
nothing: blocked on the device or a slot, or a container waiting for its
children's results), nor one whose span's name no event gives a layer. The
piece's length is shared equally among the layers that got a vote; a piece
with no vote is ``unnamed`` (``layer: null``), and so is what the harness's
traced interval holds beyond the files' own spans. All the layers' shares
and ``unnamed`` add up to ``device_idle_share``; every one of them is written
to ``chiprun_out/benchmarks/idle_by_layer.txt`` for reading by hand, with
``unnamed``'s two parts as they were counted (voted for by nobody inside the
window; idle beyond it), which is the check on the sum: ``unnamed`` is the
remainder, and a remainder under what nobody was voted for says the layers
were given too much. Nothing to read where no span event carries a layer (a
program from before them)."""

import bisect
import os

import program_spans
import trace_reduce

NO_VOTE = "wait"
UNNAMED = "unnamed"
NOTES = os.path.join(os.path.dirname(program_spans.NOTES), "idle_by_layer.txt")


def attribute(threads: dict, busy: list, window: tuple, group_of) -> dict:
    """Seconds of idle time inside ``window`` (ns) by group, from the spans
    of each thread ``{thread: [(span, start_ns, duration_ns, ...)]}``, the
    device's busy intervals ``[(lo_ns, hi_ns)]`` and ``group_of(span name)``
    (None: the span does not vote). Idle pieces no thread votes in are
    under ``UNNAMED``."""
    lo_w, hi_w = window
    edges = []  # (time, 1 open / 0 close, thread, order, group)
    for thread, spans in threads.items():
        for k, (name, start, dur, *_) in enumerate(spans):
            group = group_of(name)
            edges.append((start, 1, thread, k, group))
            edges.append((start + dur, 0, thread, k, group))
    edges.sort(key=lambda e: (e[0], e[1]))  # at one instant: close, then open
    cuts = sorted({lo_w, hi_w} | {t for t, *_ in edges if lo_w < t < hi_w})
    # the groups voted for in each piece [cuts[i], cuts[i + 1])
    open_spans: dict[str, dict] = {}
    votes, j = [], 0
    for cut in cuts[:-1]:
        while j < len(edges) and edges[j][0] <= cut:
            _, opening, thread, k, group = edges[j]
            if opening:
                open_spans.setdefault(thread, {})[k] = group
            else:
                open_spans.get(thread, {}).pop(k, None)
            j += 1
        # a thread's spans nest, and are kept in order of their start:
        # the innermost open one is the one that opened last
        votes.append({s[max(s)] for s in open_spans.values() if s} - {None})
    out: dict = {UNNAMED: 0.0}
    gaps, at = [], lo_w
    for a, b in busy:
        if b <= lo_w or a >= hi_w:
            continue
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if at < hi_w:
        gaps.append((at, hi_w))
    for a, b in gaps:
        i = max(0, bisect.bisect_right(cuts, a) - 1)
        while i < len(cuts) - 1 and cuts[i] < b:
            piece = (min(b, cuts[i + 1]) - max(a, cuts[i])) / 1e9
            if piece > 0:
                for g in votes[i] or (UNNAMED,):
                    out[g] = out.get(g, 0.0) + piece / max(1, len(votes[i]))
            i += 1
    return out


def layers_of(obs_events) -> dict:
    """Span name -> layer, as the program's own span events give it."""
    return {e["name"]: e["layer"] for e in obs_events
            if e.get("kind") == "span" and e.get("layer") and e.get("name")}


def shares(ctx) -> dict | None:
    if "_idle_by_layer" not in ctx:
        layer_of = layers_of(ctx["obs_events"])
        threads, result = program_spans.load()["threads"], None
        if layer_of and threads and ctx["device_events"] and ctx["traced_s"] > 0:
            events = ctx["device_events"][0]
            busy = trace_reduce.union([(s, s + d) for _, s, d in events])
            files = [(s, s + d) for _, s, d in ctx["spans"]] or busy
            window = (min(lo for lo, _ in files), max(hi for _, hi in files))
            voting = {n: g for n, g in layer_of.items() if g != NO_VOTE}
            by_layer = attribute(threads, busy, window, voting.get)
            for layer in voting.values():
                by_layer.setdefault(layer, 0.0)  # named, and never idle under it
            # unnamed, counted two ways: the pieces inside the files' window
            # nobody was voted for, and what the layers leave of the idle
            # time. The second is the metric (with the layers it adds up to
            # ``device_idle_share``) and exceeds the first by the idle time
            # the harness's interval holds beyond the files' spans; both go
            # to the notes. Under zero it is no reading: the layers were
            # given more idle time than the interval has
            voted = by_layer.pop(UNNAMED)
            idle = ctx["traced_s"] - trace_reduce.busy_seconds(events)
            left = idle - sum(by_layer.values())
            share = 100.0 / ctx["traced_s"]
            result = {g: s * share for g, s in by_layer.items()}
            if left >= -1e-9 * ctx["traced_s"]:
                result[UNNAMED] = max(0.0, left) * share
            write_notes(result, {"unnamed_voted_in_window": voted * share,
                                 "idle_beyond_window": (left - voted) * share})
        ctx["_idle_by_layer"] = result
    return ctx["_idle_by_layer"]


def write_notes(result: dict, check: dict) -> None:
    try:
        os.makedirs(os.path.dirname(NOTES), exist_ok=True)
        with open(NOTES, "w", encoding="utf-8") as fh:
            for layer, share in sorted(result.items(), key=lambda kv: -kv[1]):
                fh.write(f"{layer}\t{share:.4f}\n")
            fh.write(f"sum\t{sum(result.values()):.4f}\n")
            # unnamed = the first + the second; a second below zero is the
            # layers given more idle time than the traced interval has
            for what, share in check.items():
                fh.write(f"{what}\t{share:.4f}\n")
    except OSError:
        pass  # notes are for reading by hand; a metric never depends on them


def read(ctx, layer):
    """``layer: null`` reads the idle time no layer was voted for."""
    got = shares(ctx)
    return None if got is None else got.get(UNNAMED if layer is None else layer)
