"""Device time of the exchange per iteration, in ms, on the slowest chip:
the collective ops of the step program, which are the all-reduces of
``exec/shard_map._shard_fns``'s step (d, w, v and four scalars). An
async collective's start and done ops both count; the wait between
them, while other ops run, does not."""

import re

import harness

COLLECTIVE = re.compile(r"^%?(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?\b")


def is_exchange(op: str, module: str) -> bool:
    step = harness.bench_module("metrics.sweep_ms").is_sweep
    return bool(COLLECTIVE.search(op)) and step(op, module)


def read(run):
    iters = sum(f["iters"] for f in run.fits)
    per_dev = [run.trace.time_ns(ops, is_exchange)
               for ops in run.ops_by_device()]
    if not iters or not max(per_dev, default=0):
        return None
    return max(per_dev) / 1e6 / iters
