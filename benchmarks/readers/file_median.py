"""Median wall time of the window's calls into the entry (harness clock)."""
import statistics


def read(ctx):
    return statistics.median(ctx["file_walls"]) if ctx["file_walls"] else None
