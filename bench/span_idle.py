"""Device idle time inside the program's own spans.

The solve driver (``exec/base.solve_with_executor``) puts its spans on
the profiler's host plane under these names, on the clock of the
device's ops. A device gap that overlaps a span is charged to it for the
overlap, to the nanosecond, so a gap that straddles two spans is split
between them. Spans of one name are merged first, so nesting counts the
time once.
"""
from __future__ import annotations

SOLVE = "admm_solve"    # setup to final iterates, one per fit
SETUP = "gram_setup"    # executor setup (the Gram) and its factor
XSOLVE = "x_solve"      # enqueue of the x-update
SWEEP = "sweep"         # enqueue of the step; the first also traces it
STOP = "stop_rule"      # the blocking reads of the sweep's scalars
TRACE = "step_trace"    # the step's Python body, run only while traced


def spans(run, name: str):
    """(start, end) of each host span ``name`` that starts in the window."""
    lo, hi = run.window
    return [s for s in run.trace.host_spans(run.events, name)
            if lo <= s[0] < hi]


def split_sweeps(run):
    """(sweeps that enclose a ``step_trace``, the other sweeps)."""
    starts = [s for s, _ in spans(run, TRACE)]
    retrace, plain = [], []
    for s, e in spans(run, SWEEP):
        (retrace if any(s <= t < e for t in starts) else plain).append((s, e))
    return retrace, plain


def overlap_ns(a, b) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(hi - lo, 0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_ns(run, intervals) -> float:
    """Device idle time inside ``intervals``, averaged over the chips."""
    lo, hi = run.window
    merged = run.trace.union(intervals)
    per_dev = [overlap_ns(run.trace.gaps(ops, lo, hi), merged)
               for ops in run.ops_by_device()]
    return sum(per_dev) / len(per_dev) if per_dev else 0.0


def instrumented(run) -> bool:
    """Whether the program put its solve loop's spans in this trace, and the
    trace has device ops to set them against."""
    return bool(run.fits and spans(run, SWEEP)
                and any(run.ops_by_device()))


def iterations(run) -> int:
    return sum(f["iters"] for f in run.fits)
