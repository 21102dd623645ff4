"""ShardMapExecutor — paper Alg. 2 across a device mesh, driver-paced.

The same per-shard fused body ``DistributedUnwrappedADMM.build`` runs
inside its fixed-iteration ``lax.scan``, but exposed as the three
executor primitives so the SHARED driver owns the stopping rule, warm
starts and checkpointing — capabilities the scan-based path never had.
Rows are zero-padded host-side to a shard multiple (exact: zero rows
contribute nothing to any reduction); y/lam live on-device as sharded
arrays between sweeps, and only n-sized reductions (one psum per
quantity, optionally int8 error-feedback compressed for d) come back
replicated. Device arrays whose rows already divide evenly are placed
as they are, never through the host.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import gram as gram_lib
from repro.core.distributed import compressed_psum, shard_rows
from repro.engine.streaming import SweepResult
from repro.exec.base import SolveExecutor
from repro.obs import annotate
from repro.sharding.compat import shard_map

Array = jax.Array


def default_mesh(axes: Tuple[str, ...] = ("data",)) -> Mesh:
    devs = np.array(jax.devices())
    return Mesh(devs.reshape((len(devs),) + (1,) * (len(axes) - 1)), axes)


class ShardMapExecutor(SolveExecutor):
    name = "shard_map"
    checkpoint_kind = "shard_map_solve"
    kind_label = "shard_map"

    def __init__(self, engine, D, aux: Optional[Array] = None,
                 mesh: Optional[Mesh] = None,
                 data_axes: Tuple[str, ...] = ("data",),
                 compress: bool = False):
        self.engine = engine
        self.axes = tuple(data_axes)
        self.mesh = mesh if mesh is not None else default_mesh(self.axes)
        nshards = 1
        for a in self.axes:
            nshards *= self.mesh.shape[a]
        self.nshards = nshards
        if D.ndim == 3:                    # node-stacked convention
            D = D.reshape(-1, D.shape[-1])
        self.m, self.n = D.shape
        self.pad = -(-self.m // nshards) * nshards - self.m
        if isinstance(D, jax.Array) and not self.pad:
            # device data (launch.fit generates it row-sharded) stays on
            # the devices: placing it is a no-op when already sharded so
            pad_rows = lambda a: a
        else:
            D = np.asarray(D)
            pad_rows = lambda a: np.pad(
                np.asarray(a), ((0, self.pad),) + ((0, 0),) * (a.ndim - 1))
        self.ycols = getattr(engine.loss, "ycols", 1)
        self.acc = gram_lib._acc_dtype(D.dtype)
        self.backend = engine.resolve(D.dtype)
        # int8 EF compression quantizes flat n-vectors; matrix-valued d
        # (multinomial) falls back to the plain psum
        self.compress = bool(compress) and self.ycols == 1
        self._D = shard_rows(self.mesh, pad_rows(D), self.axes)
        self._aux = None
        if aux is not None:
            self._aux = shard_rows(self.mesh, pad_rows(aux.reshape(self.m)),
                                   self.axes)
        self.has_aux = aux is not None
        self._y = None
        self._lam = None
        self._err = None
        self._fns = _shard_fns(engine, self.axes, self.mesh,
                               self.has_aux, self.compress)

    def _yshape(self):
        mp = self.m + self.pad
        return (mp,) if self.ycols == 1 else (mp, self.ycols)

    def _place_iterate(self, host: np.ndarray) -> Array:
        return shard_rows(self.mesh, host, self.axes)

    def setup(self, obs) -> Array:
        gram_fn, _, _ = self._fns
        return gram_fn(self._D)

    def init(self, x0: Optional[Array]) -> Array:
        shape = self._yshape()
        if x0 is None:
            self._y = self._place_iterate(
                np.zeros(shape, jnp.dtype(self.acc).name))
            self._lam = self._place_iterate(
                np.zeros(shape, jnp.dtype(self.acc).name))
            self._zero_err()
            return self.zero_x()
        _, init_fn, _ = self._fns
        self._y, d = init_fn(self._D, jnp.asarray(x0, self.acc))
        self._lam = self._place_iterate(
            np.zeros(shape, jnp.dtype(self.acc).name))
        self._zero_err()
        return d

    def _zero_err(self):
        self._err = shard_rows(
            self.mesh, np.zeros((self.nshards, self.n), np.float32),
            self.axes)

    def sweep(self, x: Array, k: int) -> SweepResult:
        _, _, step_fn = self._fns
        self._y, self._lam, self._err, sw = step_fn(
            self._D, self._aux, self._y, self._lam,
            jnp.asarray(x, self.acc), self._err)
        return sw

    def pad_objective(self) -> float:
        if self.pad == 0:
            return 0.0
        z = jnp.zeros((self.pad,) if self.ycols == 1
                      else (self.pad, self.ycols), jnp.float32)
        a = jnp.zeros((self.pad,), jnp.float32)
        return float(self.engine.loss.value(z, a if self.has_aux
                                            else None))

    def extra_record(self) -> dict:
        return {"shards": self.nshards}

    # -- checkpointing ------------------------------------------------------
    def state_arrays(self, k: int) -> dict:
        return {"y": jnp.asarray(np.asarray(self._y)[:self.m]),
                "lam": jnp.asarray(np.asarray(self._lam)[:self.m])}

    def restore_state(self, k: int, tree: dict) -> Array:
        shape = self._yshape()

        def repad(a):
            host = np.zeros(shape, jnp.dtype(self.acc).name)
            host[:self.m] = np.asarray(a)
            return self._place_iterate(host)

        self._y = repad(tree["y"])
        self._lam = repad(tree["lam"])
        self._zero_err()                 # EF error restarts at zero: it
        # is a wire optimization, not solver state — resume stays exact
        return tree["d"]

    def final_iterates(self):
        y = jnp.asarray(np.asarray(self._y)[:self.m])
        lam = jnp.asarray(np.asarray(self._lam)[:self.m])
        return y[None], lam[None]


@lru_cache(maxsize=64)
def _shard_fns(engine, axes, mesh, has_aux: bool, compress: bool):
    """Jitted (gram, init, step) shard_map bodies for one engine config.

    Memoized on every argument (the frozen engine, the axes tuple, the
    ``Mesh`` and two flags), like ``exec.local._fused_step``: executors
    with equal arguments share the jitted bodies and trace them once."""
    yspec = P(axes)                       # rows sharded, trailing dims full
    loss = engine.loss

    def gram_body(D):
        G, _ = engine.gram(D)
        return jax.lax.psum(G, axes)

    def init_body(D, x0):
        acc = gram_lib._acc_dtype(D.dtype)
        y = D.astype(acc) @ x0.astype(acc)
        d = jax.lax.psum(D.astype(acc).T @ y, axes)
        return y, d

    def step_body(D, aux, y, lam, x, err):
        with annotate("step_trace"):     # runs only while jit traces
            Dres = engine.prepare(D)
            st = engine.iterate(Dres, aux, y, lam, x, want_dual=True)
            Dx = st.lam - lam + st.y
            if compress:
                d, e = compressed_psum(st.d, axes, err[0])
                err = e[None]
            else:
                d = jax.lax.psum(st.d, axes)
            sw = SweepResult(
                d, jax.lax.psum(st.w, axes), jax.lax.psum(st.v, axes),
                jax.lax.psum(jnp.sum((st.lam - lam) ** 2), axes),
                jax.lax.psum(jnp.sum(Dx * Dx), axes),
                jax.lax.psum(jnp.sum(st.y * st.y), axes),
                jax.lax.psum(loss.value(Dx, aux), axes))
            return st.y, st.lam, err, sw

    dspec = P(axes, None)
    espec = P(axes, None)
    rspec = SweepResult(*([P()] * 7))
    gram_fn = jax.jit(shard_map(gram_body, mesh=mesh, in_specs=(dspec,),
                                out_specs=P(), check_vma=False))
    init_fn = jax.jit(shard_map(init_body, mesh=mesh,
                                in_specs=(dspec, P()),
                                out_specs=(yspec, P()), check_vma=False))
    aspec = P(axes) if has_aux else None
    step_fn = jax.jit(shard_map(
        step_body, mesh=mesh,
        in_specs=(dspec, aspec, yspec, yspec, P(), espec),
        out_specs=(yspec, yspec, espec, rspec), check_vma=False))
    return gram_fn, init_fn, step_fn
