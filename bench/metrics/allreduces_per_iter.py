"""Collective ops the step program issues per iteration, on the first
chip: ``exec/shard_map._shard_fns``'s step asks for seven psums, and this
counts how many the compiler left after combining them. An async
collective counts once, by its start."""

import harness


def read(run):
    ar = harness.bench_module("metrics.allreduce_ms")
    iters = sum(f["iters"] for f in run.fits)
    ops = run.ops_by_device()[0] if run.devices else []
    issued = sum(1 for o in ops if ar.is_exchange(o[0], o[1])
                 and ar.COLLECTIVE.search(o[0]).group(2) != "-done")
    if not iters or not issued:
        return None
    return issued / iters
