"""Out-of-core streaming backend — the fused iteration body over a
:class:`~repro.data.store.ShardedMatrixStore` (DESIGN.md §9).

The in-memory engine (``engine.engine``) assumes D is device-resident.
This module removes that assumption: each solver pass walks the store's
row blocks, runs the SAME fused body (``IterationEngine.iterate``) on one
device-resident block at a time, and persists the m-sized iterates
``(y, lam)`` back to host per block — device memory is bounded by one
block regardless of m.

Double-buffering rule: a host prefetch thread stages ``jax.device_put``
of block k+1 (D, aux, and the host-resident y/lam slices) while the
device computes block k; device→host writeback of block k's iterates
trails the compute by one block. With JAX's async dispatch the three
streams (H2D, compute, D2H) overlap, so a sweep costs ~max(transfer,
compute) instead of their sum — ``benchmarks/streaming_bench.py`` writes
the measured overlap efficiency to ``BENCH_streaming.json``.

Host-resident iterate contract: ``y`` and ``lam`` live in caller-owned
(m,) numpy arrays, mutated in place block-by-block each sweep; only the
n-sized reductions (d, w, v) and the stopping-rule scalars stay on the
device between sweeps. Tail-block padding is exact (zero D rows
contribute nothing to any reduction); the one
non-exact quantity, the objective's value on pad rows, is a constant
(pad iterates stay at zero) subtracted once at setup.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from functools import lru_cache
from typing import Callable, Iterable, Iterator, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gram as gram_lib
from repro.data.store import ShardedMatrixStore
from repro.engine.engine import IterationEngine

Array = jax.Array

_ERROR = object()          # sentinel wrapping producer-thread exceptions
_DONE = object()


# ---------------------------------------------------------------------------
# staged iteration: the double-buffer primitive
# ---------------------------------------------------------------------------

def staged(items: Iterable, stage: Callable, depth: int) -> Iterator:
    """Yield ``stage(item)`` for each item, running ``stage`` up to
    ``depth`` items ahead on a host thread. ``depth=0`` degrades to the
    naive synchronous loop (the benchmark baseline)."""
    if depth <= 0:
        for it in items:
            yield stage(it)
        return
    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()

    def producer():
        try:
            for it in items:
                if stop.is_set():
                    return
                q.put(stage(it))
        except BaseException as e:           # surface in the consumer
            q.put((_ERROR, e))
            return
        q.put((_DONE, None))

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    try:
        while True:
            got = q.get()
            # identity checks only: `in`/`==` would invoke __eq__ on
            # staged payloads (numpy arrays raise on truth-testing)
            if isinstance(got, tuple) and len(got) == 2 and (
                    got[0] is _ERROR or got[0] is _DONE):
                if got[0] is _ERROR:
                    raise got[1]
                return
            yield got
    finally:
        # Consumer abandoned mid-stream (exception in the step, generator
        # closed): unblock the producer so it exits and its staged device
        # buffers are dropped instead of pinned behind a full queue.
        stop.set()
        while not q.empty():
            try:
                q.get_nowait()
            except queue.Empty:
                break


# ---------------------------------------------------------------------------
# jitted per-block bodies (cached per engine instance)
# ---------------------------------------------------------------------------

def _zero_sweep(n: int, dtype, ycols: int = 1) -> "SweepResult":
    """Fresh (unaliased) zero accumulators — donation-safe carry init.
    ``ycols > 1`` (multinomial) widens the n-vectors to (n, ycols)."""
    shape = (n,) if ycols == 1 else (n, ycols)
    return SweepResult(*(jnp.zeros(shape, dtype) for _ in range(3)),
                       *(jnp.zeros((), dtype) for _ in range(4)))


class SweepResult(NamedTuple):
    """Accumulated over all blocks of one sweep — everything the driver
    needs for the x-update and Boyd's stopping rule, all n-sized or
    scalar (module docstring: nothing m-sized survives a sweep on
    device)."""

    d: Array          # sum_b D_b^T(y_b' - lam_b')
    w: Array          # sum_b D_b^T(y_b' - y_b)
    v: Array          # sum_b D_b^T lam_b'
    r_sq: Array       # ||lam' - lam||^2 = ||Dx - y'||^2
    dx_sq: Array      # ||Dx||^2
    y_sq: Array       # ||y'||^2
    obj: Array        # f(Dx) (pad-corrected by the driver)


@lru_cache(maxsize=64)
def _block_fns(engine: IterationEngine, has_aux: bool,
               want_dual: bool = True, sparse: bool = False):
    """Jitted per-block step / init / gram bodies for one engine config.

    Cached so every sweep reuses the same traced functions (jit's own
    shape cache handles the uniform block shape). The sweep accumulators
    ride THROUGH the step as a donated carry: one dispatch per block
    instead of one per reduction, which is what lets the double-buffered
    pipeline stay dispatch-bound-free (DESIGN.md §9). ``want_dual=False``
    is the lean hot-path body (d-reduction only, no stopping-rule/
    telemetry quantities — the streaming analogue of ``make_step``).
    ``sparse=True`` stages one-block BlockCSR pytrees; the step body is
    the engine's own format dispatch, only the warm-start init differs
    (gather matvec instead of the dense one)."""

    def step(D_b, aux_b, y_b, lam_b, x, acc):
        st = engine.iterate(D_b, aux_b if has_aux else None, y_b, lam_b, x,
                            want_dual=want_dual)
        if not want_dual:
            return st.y, st.lam, acc._replace(d=acc.d + st.d)
        Dx = st.lam - lam_b + st.y
        obj = engine.loss.value(Dx, aux_b if has_aux else None)
        new = SweepResult(
            acc.d + st.d, acc.w + st.w, acc.v + st.v,
            acc.r_sq + jnp.sum((st.lam - lam_b) ** 2),
            acc.dx_sq + jnp.sum(Dx * Dx),
            acc.y_sq + jnp.sum(st.y * st.y), acc.obj + obj)
        return st.y, st.lam, new

    def init(D_b, x0):
        """Warm start: y_b = D_b x0 and its d-contribution (lam = 0)."""
        if sparse:
            from repro.kernels.spgram import ops as spgram_ops
            acc = gram_lib._acc_dtype(D_b.dtype)
            y_b = spgram_ops.matvec(D_b, x0.astype(acc))
            return y_b, spgram_ops.rmatvec(D_b, y_b)
        acc = gram_lib._acc_dtype(D_b.dtype)
        y_b = D_b.astype(acc) @ x0.astype(acc)
        if y_b.ndim > 1:                   # matrix iterates (multinomial)
            return y_b, D_b.astype(acc).T @ y_b
        return y_b, y_b @ D_b.astype(acc)

    def gram(G, D_b):
        Gb, _ = engine.gram(D_b)
        return G + Gb

    return (jax.jit(step, donate_argnums=(2, 3, 5)), jax.jit(init),
            jax.jit(gram, donate_argnums=(0,)))


# Public alias: the cluster worker (repro.cluster.worker) drives the same
# jitted per-block fused body over ITS owned blocks — one implementation
# of the iteration step for the streaming and multi-process paths.
block_step_fns = _block_fns


def store_pad_objective(store: ShardedMatrixStore, loss) -> float:
    """f's value on the tail block's pad rows. Pad iterates stay at
    zero (zero D rows, zero aux), so this is a CONSTANT the driver
    subtracts from each sweep's objective — the only pad quantity that
    is not exactly zero (e.g. logistic: log 2 per pad row). One
    definition for the streaming driver and the cluster coordinator."""
    pad = store.nblocks * store.block_rows - store.m
    if pad == 0:
        return 0.0
    ycols = getattr(loss, "ycols", 1)
    z = jnp.zeros((pad,) if ycols == 1 else (pad, ycols), jnp.float32)
    a = jnp.zeros((pad,), jnp.float32)
    return float(loss.value(z, a if store.has_aux else None))


# ---------------------------------------------------------------------------
# the streaming engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StreamingEngine:
    """Block-streaming driver around an :class:`IterationEngine`.

    ``prefetch`` is the double-buffer depth (device_put of block k+1
    overlapped with compute on block k); ``prefetch=0`` is the naive
    synchronous baseline the benchmark compares against.
    """

    engine: IterationEngine
    prefetch: int = 2
    device_dtype: Optional[str] = None   # None -> store dtype; else the
    # device-residency dtype (e.g. "float32" for an f64 host store): the
    # cast happens AT STAGING TIME on the host, so the double-buffered
    # path overlaps the conversion with compute — the store keeps the
    # data as collected, the device only ever holds residency-dtype
    # blocks (the engine's residency idea applied at the H2D boundary).

    def _cast(self, a):
        if self.device_dtype is None:
            return a
        if hasattr(a, "astype") and not isinstance(a, np.ndarray):
            # BlockCSR: casts value arrays, indices stay int32
            return a.astype(self.device_dtype)
        if a.dtype == np.dtype(self.device_dtype):
            return a
        return a.astype(self.device_dtype)

    def _stage(self, store: ShardedMatrixStore, y: np.ndarray,
               lam: np.ndarray):
        """Build the prefetch stage: host block k -> device-resident
        (k, D_b, aux_b, y_b, lam_b), tail zero-padded to the uniform
        block shape, residency-cast on the host."""
        br = store.block_rows

        def stage(k):
            D_b, a_b = store.block(k, padded=True)
            sl = store.block_slice(k)
            valid = sl.stop - sl.start
            y_b = np.zeros((br,) + y.shape[1:], y.dtype)
            y_b[:valid] = y[sl]
            lam_b = np.zeros((br,) + lam.shape[1:], lam.dtype)
            lam_b[:valid] = lam[sl]
            return (k, jax.device_put(self._cast(D_b)),
                    jax.device_put(self._cast(a_b))
                    if a_b is not None else None,
                    jax.device_put(y_b), jax.device_put(lam_b))

        return stage

    def residency_dtype(self, store: ShardedMatrixStore):
        """dtype of the blocks the device actually sees."""
        return jnp.dtype(self.device_dtype or store.dtype.name)

    # -- setup: Gram over the store, one block resident at a time ----------
    def gram_from_store(self, store: ShardedMatrixStore) -> Array:
        if store.sparse:
            # Sparse gram is a HOST pass (kernels/spgram/ops.py): the
            # blocks are host arrays already, so nothing is staged to
            # the device — the O(nnz) CSR matmul folds block by block.
            # No residency cast either: device_dtype exists to cut H2D
            # bytes, and quantizing a host-only pass would only degrade G.
            G = None
            for k in range(store.nblocks):
                D_b, _ = store.block(k, padded=True)
                Gb, _ = self.engine.gram(D_b)
                G = Gb if G is None else G + Gb
            return G
        _, _, gram = _block_fns(self.engine, store.has_aux)
        acc = gram_lib._acc_dtype(self.residency_dtype(store))
        G = jnp.zeros((store.n, store.n), acc)
        blocks = staged(range(store.nblocks),
                        lambda k: jax.device_put(self._cast(
                            store.block(k, padded=True)[0])),
                        self.prefetch)
        for D_b in blocks:
            G = gram(G, D_b)
        return G

    # -- warm start: y = D x0 per block, d = D^T y in the same pass --------
    def init_from_x0(self, store: ShardedMatrixStore, x0: Array,
                     y: np.ndarray) -> Array:
        _, init, _ = _block_fns(self.engine, store.has_aux,
                                sparse=store.sparse)
        x0 = jax.device_put(x0)
        d = None
        blocks = staged(range(store.nblocks),
                        lambda k: (k, jax.device_put(self._cast(
                            store.block(k, padded=True)[0]))),
                        self.prefetch)
        for k, D_b in blocks:
            y_b, d_b = init(D_b, x0)
            d = d_b if d is None else d + d_b
            sl = store.block_slice(k)
            y[sl] = np.asarray(y_b)[: sl.stop - sl.start]
        return d

    # -- one full iteration sweep ------------------------------------------
    def sweep(self, store: ShardedMatrixStore, x: Array, y: np.ndarray,
              lam: np.ndarray, overlap: Optional[bool] = None,
              want_dual: bool = True) -> SweepResult:
        """Stream every block through the fused body once: updates the
        host-resident (y, lam) in place and returns the n-sized /scalar
        accumulators. ``overlap=False`` forces the synchronous baseline
        (transfer, wait, compute, wait, write back) regardless of the
        configured prefetch depth. ``want_dual=False`` runs the lean
        hot-path body (d only; the other accumulators come back as their
        zero init)."""
        depth = self.prefetch if overlap in (None, True) else 0
        step, _, _ = _block_fns(self.engine, store.has_aux, want_dual,
                                sparse=store.sparse)
        x = jax.device_put(x)
        facc = gram_lib._acc_dtype(self.residency_dtype(store))
        # one buffer per field: the carry is DONATED into the step, and
        # XLA rejects donating one buffer through two arguments
        acc = _zero_sweep(store.n, facc,
                          getattr(self.engine.loss, "ycols", 1))
        pending = None            # (slice, y_dev, lam_dev): lag-1 writeback

        def writeback(item):
            sl, y_b, lam_b = item
            valid = sl.stop - sl.start
            y[sl] = np.asarray(y_b)[:valid]
            lam[sl] = np.asarray(lam_b)[:valid]

        for k, D_b, a_b, y_b, lam_b in staged(
                range(store.nblocks), self._stage(store, y, lam), depth):
            if depth == 0:
                jax.block_until_ready((D_b, y_b, lam_b))
            y_new, lam_new, acc = step(D_b, a_b, y_b, lam_b, x, acc)
            if depth == 0:
                jax.block_until_ready((y_new, lam_new, acc))
            if pending is not None:
                writeback(pending)
                pending = None
            item = (store.block_slice(k), y_new, lam_new)
            if depth == 0:
                writeback(item)
            else:
                pending = item
        if pending is not None:
            writeback(pending)
        return acc

    # -- pad-objective correction ------------------------------------------
    def pad_objective(self, store: ShardedMatrixStore) -> float:
        """See :func:`store_pad_objective` — shared with the cluster
        coordinator so the two drivers cannot drift."""
        return store_pad_objective(store, self.engine.loss)


# ---------------------------------------------------------------------------
# the out-of-core solve driver (UnwrappedADMM.solve_streaming delegates here)
# ---------------------------------------------------------------------------

def solve_streaming(solver, store: ShardedMatrixStore, max_iters: int = 500,
                    x0: Optional[Array] = None, record: bool = False,
                    overlap: bool = True, prefetch: int = 2,
                    device_dtype: Optional[str] = None,
                    checkpoint_dir: Optional[str] = None,
                    checkpoint_every: int = 0, resume: bool = False,
                    obs=None):
    """Out-of-core unwrapped ADMM over a row-block store.

    Same semantics as ``UnwrappedADMM.solve`` (Boyd stopping rule, warm
    start) but D never needs to be device- or even host-array-resident:
    setup is one Gram sweep, each iteration is one fused sweep, and the
    m-sized iterates live in host numpy buffers. Returns an
    ``ADMMResult`` with ``y``/``lam`` shaped (1, m) (the node-stacked
    convention with N=1); ``history`` is populated when ``record``.

    Long solves survive kills: ``checkpoint_dir`` + ``checkpoint_every
    = K`` persist the full loop state (x, y, lam, d, iter) through
    :class:`repro.checkpoint.manager.CheckpointManager` every K
    iterations (atomic commits — a SIGKILL mid-save leaves the previous
    step intact), and ``resume=True`` restores the newest step and
    continues BITWISE-compatibly: the restored state is exactly the
    live state, so the remaining iterations replay the identical
    op sequence (``tests/test_cluster.py`` asserts bit equality).
    ``record`` history restarts from the resume point. The checkpoint
    is bound to the store's content fingerprint — resuming against
    different data refuses instead of converging somewhere else.

    ``obs`` (an :class:`repro.obs.Observability`) instruments the HOST
    loop only: spans around the Gram setup and each sweep, one telemetry
    JSONL record per iteration. ``None`` is the disabled fast path.

    This is a thin wrapper: the loop itself lives in the shared executor
    driver (``repro.exec``) behind a :class:`~repro.exec.StreamingExecutor`.
    """
    from repro.exec import StreamingExecutor, solve_with_executor

    ex = StreamingExecutor(solver.engine, store, overlap=overlap,
                           prefetch=prefetch, device_dtype=device_dtype)
    return solve_with_executor(
        ex, loss=solver.loss, tau=solver.tau, rho=solver.rho,
        eps_rel=solver.eps_rel, eps_abs=solver.eps_abs,
        max_iters=max_iters, x0=x0, record=record,
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        resume=resume, obs=obs)
