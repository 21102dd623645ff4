#!/usr/bin/env python3
"""Compile the cells' programs at full size for a described TPU v5e, with
no chip attached, and print what each needs:

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [star|fig1|x4 ...]

For every program: the bytes of its temporaries and arguments, and the
collectives the compiler put in (op, count, bytes). What the chip's
compiler would refuse (more VMEM than a kernel may use, more HBM than the
chip has) fails here, at no chip time. Programs: the data generation and
sign flip of each configuration; for ``fig1_share`` the Gram, the
per-iteration step and the x-update; for ``star_share_x4`` (14,848,000 x
307 f32 over the four chips of a 2x2 v5e) the harness's row-sharded
generation and flip, and the ``ShardMapExecutor``'s Gram and step.
"""
from __future__ import annotations

import json
import os
import re
import sys
from collections import Counter
from pathlib import Path

BENCH = Path(__file__).resolve().parent
COLLECTIVE = re.compile(r"= (\(.*?\)|\S+) (all-reduce|all-gather|reduce-scatter|"
                        r"all-to-all|collective-permute)(?:-start)?\(")


def summary(name: str, compiled) -> dict:
    mem = compiled.memory_analysis()
    coll = Counter()
    for shape, op in COLLECTIVE.findall(compiled.as_text()):
        coll[f"{op} {shape}"] += 1
    row = {"program": name,
           "temp_gb": mem.temp_size_in_bytes / 1e9,
           "args_gb": mem.argument_size_in_bytes / 1e9,
           "out_gb": mem.output_size_in_bytes / 1e9,
           "alias_gb": mem.alias_size_in_bytes / 1e9,
           "collectives": dict(coll),
           "pallas": "tpu_custom_call" in compiled.as_text()}
    print(json.dumps(row), flush=True)
    return row


def main(groups=("star", "fig1", "x4")):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(BENCH.parent / "src"))
    sys.path.insert(0, str(BENCH))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import data as bench_data

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    arg = lambda shape, dt=jnp.float32, s=one: jax.ShapeDtypeStruct(
        shape, dt, sharding=s)
    cfgs = {p.stem: json.loads(p.read_text())
            for p in (BENCH / "configs").glob("*.json")}

    for name in ("star_share", "fig1_share"):
        if name.split("_")[0] not in groups:
            continue
        c = cfgs[name]
        m, n, dt = c["rows"], c["features"], jnp.dtype(c["dtype"])
        summary(f"{name} generate", bench_data.generator(
            c["data"], m, n, dt, (one, one)).lower().compile())
        summary(f"{name} flip", bench_data.flip.lower(
            arg((m, n), dt), arg((n,))).compile())

    if "fig1" in groups:
        fig1(cfgs["fig1_share"], arg)
    if "x4" in groups:
        x4(cfgs["star_share_x4"], topo, arg)


def fig1(c, arg):
    import jax
    import jax.numpy as jnp
    from repro.core import gram as gram_lib
    from repro.engine import IterationEngine, autotune
    from repro.exec.local import _fused_step
    from repro.kernels.gram.ops import gram as pallas_gram
    from repro.launch.fit import _admm_params

    m, n, dt = c["rows"], c["features"], jnp.dtype(c["dtype"])
    loss, rho, tau, _ = _admm_params("svm")
    eng = IterationEngine(loss=loss, tau=tau, backend="pallas")
    bm, bn = autotune.gram_blocks(m, n, dt)
    summary("fig1_share gram", jax.jit(
        lambda D: pallas_gram(D, block_m=bm, block_n=bn)).lower(
        arg((m, n), dt)).compile())
    vec = arg((m,))
    summary("fig1_share step", _fused_step(eng).lower(
        arg((m, n), dt), vec, vec, vec, arg((n,))).compile())
    summary("fig1_share factor", jax.jit(
        lambda G: gram_lib.gram_factor(G, ridge=rho / tau)).lower(
        arg((n, n))).compile())
    summary("fig1_share x-update", jax.jit(gram_lib.gram_solve).lower(
        arg((n, n)), arg((n,))).compile())


def x4(c, topo, arg):
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import data as bench_data
    from repro.engine import IterationEngine
    from repro.exec.shard_map import _shard_fns
    from repro.launch.fit import _admm_params

    m, n, dt, chips = c["rows"], c["features"], jnp.dtype(c["dtype"]), \
        c["chips"]
    loss, rho, tau, _ = _admm_params("logistic")
    mesh = bench_data.row_mesh(topo.devices[:chips])
    rows, rvec = bench_data.row_shardings(mesh)
    summary("star_share_x4 generate", bench_data.generator(
        c["data"], m, n, dt, (rows, rvec)).lower().compile())
    D = arg((m, n), dt, rows)
    summary("star_share_x4 flip", bench_data.flip.lower(
        D, arg((n,), jnp.float32, NamedSharding(mesh, P()))).compile())
    gram_fn, _, step_fn = _shard_fns(
        IterationEngine(loss=loss, tau=tau, backend="pallas"),
        (bench_data.ROWS,), mesh, True, False)
    summary("star_share_x4 gram", gram_fn.lower(D).compile())
    v = arg((m,), jnp.float32, rvec)
    summary("star_share_x4 step", step_fn.lower(
        D, v, v, v, arg((n,), jnp.float32, NamedSharding(mesh, P())),
        arg((chips, n), jnp.float32, rows)).compile())


if __name__ == "__main__":
    main(tuple(sys.argv[1:]) or ("star", "fig1", "x4"))
