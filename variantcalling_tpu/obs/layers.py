"""The ONE table of span names: which layer (PERF.md section 3) each belongs
to, and which of them read the thread CPU clock.

It lives below both its users: ``obs`` (the stream's own ``obs_open`` /
``obs_close`` spans, the executor's generic ``obs.span``) and
``utils.trace`` (the span primitive, which re-exports :data:`LAYER_OF`).
EVERY name handed to ``stage`` / ``timed`` in the package is here
(tests/unit/test_obs_spans.py walks the sources): the layer rides the span
event and the attribution row, so a reader groups spans by what the program
says they are and keeps no table of its own. ``wait`` holds the spans in
which a thread causes nothing (blocked on the device, a slot, a collective)
and the pure containers, whose own time is a thread waiting for its
children's results."""

from __future__ import annotations

_LAYERS = {
    "entry": ("run_open", "model_load", "stream_open", "stream_close"),
    "daemon": ("serve_request", "serve_state", "serve_respond"),
    "ingest": ("ingest", "parse", "inflate"),
    "feed": ("host_featurize", "featurize_stage", "prepare_inputs",
             "dispatch_feed", "score_finalize", "megabatch_pack"),
    "program": ("fused_program", "dispatch_enqueue"),
    "render": ("render_stage",),
    "commit": ("writeback", "journal_append", "compress_stage", "tabix_index",
               "commit"),
    "setup": ("genome_upload", "backend_probe"),
    "tracing": ("obs_open", "profile_emit", "obs_close"),
    "wait": ("dispatch_wait", "serve_admit", "dist.allgather_concat",
             "stream", "score_stage", "scaleout", "featurize+score"),
}
#: span name -> layer; looked up only on a live span
LAYER_OF: dict[str, str] = {name: layer for layer, names in _LAYERS.items()
                            for name in names}

#: the spans that read the calling thread's CPU clock (``cpu`` on the event,
#: ``cpu_s`` on the row): the families whose on-CPU share a benchmark metric
#: or ``vctpu obs bottleneck``'s column is read for, each tens of
#: milliseconds of a pooled worker or a stage thread by the chunk, where
#: "working or waiting for the interpreter" is the question. No other span
#: reads it: on the chip's host the clock ticks at 10 ms, so a span under
#: that reads 0 or a tick, and a reading is a slow, serialized call: with
#: every span reading it (26 readings a chunk) a traced 0.4 s file took
#: 35 ms longer (PERF.md section 6, PR 38). These are 4 readings a chunk of
#: a plain-text file (``parse``, ``render_stage``), 10 and a file's inflate
#: shards' of a ``.vcf.gz`` one
CPU_SPANS = frozenset(("parse", "inflate", "render_stage", "compress_stage",
                       "tabix_index"))
