"""Device 0's idle time inside the traced interval, put down to what the
host's threads were doing, as a share of the traced interval.

The idle time is cut at every start and end of a ``vctpu:`` span. In each
piece every thread votes with its innermost open span: for ``program`` if
that is ``fused_program`` or ``dispatch_enqueue``, for ``feed`` if
``host_featurize``, ``prepare_inputs``, ``dispatch_feed`` or
``score_finalize``, for ``io`` if ``ingest``, ``parse``, ``render_stage`` or
``writeback``. A thread inside ``dispatch_wait`` does not vote (waiting is no
cause), nor one whose innermost span is in no group. The piece's length is
shared equally among the groups that got a vote; a piece with no vote is
``unexplained``, and so is what the harness's traced interval holds beyond
the files' own spans. The four shares add up to ``device_idle_share``.
Nothing to read where the program wrote no such span."""

import bisect

import program_spans
import trace_reduce

GROUPS = {
    "program": ("fused_program", "dispatch_enqueue"),
    "feed": ("host_featurize", "prepare_inputs", "dispatch_feed", "score_finalize"),
    "io": ("ingest", "parse", "render_stage", "writeback"),
}
GROUP_OF = {span: g for g, spans in GROUPS.items() for span in spans}


def attribute(threads: dict, busy: list, window: tuple) -> dict:
    """Seconds of idle time inside ``window`` (ns) by group, from the spans
    of each thread ``{thread: [(span, start_ns, duration_ns, ...)]}`` and
    the device's busy intervals ``[(lo_ns, hi_ns)]``."""
    lo_w, hi_w = window
    edges = []  # (time, thread, +1 open / -1 close, order, span)
    for thread, spans in threads.items():
        for k, (name, start, dur, *_) in enumerate(spans):
            edges.append((start, 1, thread, k, name))
            edges.append((start + dur, 0, thread, k, name))
    edges.sort(key=lambda e: (e[0], e[1]))  # at one instant: close, then open
    cuts = sorted({lo_w, hi_w} | {t for t, *_ in edges if lo_w < t < hi_w})
    # the groups voted for in each piece [cuts[i], cuts[i + 1])
    open_spans: dict[str, dict] = {}
    votes, j = [], 0
    for cut in cuts[:-1]:
        while j < len(edges) and edges[j][0] <= cut:
            _, opening, thread, k, name = edges[j]
            if opening:
                open_spans.setdefault(thread, {})[k] = name
            else:
                open_spans.get(thread, {}).pop(k, None)
            j += 1
        # a thread's spans nest, and are kept in order of their start:
        # the innermost open one is the one that opened last
        votes.append({GROUP_OF[s[max(s)]] for s in open_spans.values()
                      if s and s[max(s)] in GROUP_OF})
    out = dict.fromkeys(list(GROUPS) + ["unexplained"], 0.0)
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
                for g in votes[i] or ("unexplained",):
                    out[g] += piece / max(1, len(votes[i]))
            i += 1
    return out


def shares(ctx) -> dict | None:
    if "_idle_by_span" not in ctx:
        loaded = program_spans.load()
        threads, result = loaded["threads"], None
        if threads and ctx["device_events"] and ctx["traced_s"] > 0:
            events = ctx["device_events"][0]
            busy = trace_reduce.union([(s, s + d) for _, s, d in events])
            files = [(s, s + d) for _, s, d in ctx["spans"]] or busy
            window = (min(lo for lo, _ in files), max(hi for _, hi in files))
            by_group = attribute(threads, busy, window)
            idle = ctx["traced_s"] - trace_reduce.busy_seconds(events)
            by_group["unexplained"] = idle - sum(by_group[g] for g in GROUPS)
            result = {g: 100.0 * s / ctx["traced_s"] for g, s in by_group.items()}
        ctx["_idle_by_span"] = result
    return ctx["_idle_by_span"]


def read(ctx, group):
    got = shares(ctx)
    return None if got is None else got[group]
