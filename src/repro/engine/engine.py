"""The iteration engine — the ONE place solver iteration bodies live.

Every hot path of the repo (``core/unwrapped``, ``core/distributed``,
``service/stats`` ingestion, the benchmarks) dispatches its per-iteration /
per-ingest pass over the data matrix D through this module instead of
inlining einsums. The engine owns three interchangeable backends
(DESIGN.md §8):

  * ``pallas``            — TPU: the fused ``kernels/admm_iter`` kernel.
                            ONE HBM pass over D per iteration (Dx, prox,
                            lam-update and ALL THREE transpose reductions
                            d = D^T(y'-lam'), w = D^T(y'-y), v = D^T lam'
                            while each row panel is VMEM-resident); Gram
                            setup via the fused Gram+RHS kernel in
                            ``kernels/gram``.
  * ``pallas_interpret``  — same kernels in interpreter mode (CPU CI).
  * ``chunked``           — CPU/GPU: a ``lax.scan`` over row blocks with
                            the same one-pass-fused body; each block stays
                            cache-hot between its Dx and D^T uses, halving
                            memory traffic vs the two-pass formulation.
  * ``sparse``            — padded block-CSR data (``data/sparse.BlockCSR``):
                            the same scan shape with O(nnz) per-block work
                            (``kernels/spgram``) — gather-based Dx and
                            gather-based transpose reductions over the
                            per-block local CSC (DESIGN.md §10). Selected
                            by the DATA TYPE: BlockCSR input takes this
                            path under every backend except an explicit
                            ``reference`` (which densifies — the parity
                            oracle).
  * ``reference``         — the textbook two-pass jnp oracle (Dx pass,
                            then a D^T pass); parity baseline.

``auto`` resolves per device (TPU -> pallas, else chunked), then falls
back by capability: Pallas needs a kernel-supported coordinatewise prox
(logistic / hinge / l1 / least_squares / quantile, f32 or bf16 rows);
chunked needs a coordinatewise prox; everything else lands on reference.
Only ``auto`` chooses: an explicit ``pallas`` (or ``pallas_interpret``)
that the loss or dtype cannot run raises instead of quietly running
another backend. ``pallas_interpret`` is a test mode: ``auto`` never
resolves to it.

bf16 data residency (``residency="bf16"``) halves iteration HBM bytes
again on top of the fused pass — all accumulation stays f32 in-register
regardless. ``residency="auto"`` picks bf16 on the real-TPU pallas
backend, where the saved bytes should pay (not measured on a chip yet);
on CPU/chunked backends the per-block upcast costs more than the saved
bytes (CPU timings in BENCH_engine.json: 0.55x/1.88x), so auto resolves
to None there (DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import gram as gram_lib
from repro.core.prox import ProxLoss
from repro.data.sparse import BlockCSR
from repro.engine import autotune
from repro.kernels.admm_iter.ops import admm_iter_full
from repro.kernels.gram import ops as gram_ops
from repro.kernels.spgram import ops as spgram_ops

Array = jax.Array

BACKENDS = ("reference", "chunked", "sparse", "pallas", "pallas_interpret")

# Prox kinds the fused Pallas iteration kernel evaluates in-register.
PALLAS_KINDS = frozenset(
    {"logistic", "hinge", "l1", "least_squares", "quantile"})

# "auto" resolves per backend at prepare()-time: bf16 on real-TPU pallas,
# where halving the HBM bytes should pay (not measured on a chip), None on
# CPU/chunked backends where the per-block upcast is a CPU-measured
# slowdown (DESIGN.md §8).
RESIDENCY_DTYPES = {None: None, "bf16": jnp.bfloat16, "auto": "auto"}


class EngineStep(NamedTuple):
    """One fused iteration: updated iterates plus the n-vector reductions
    accumulated in the same pass over D. The w/v differences are formed
    row-wise in-register BEFORE reducing (not by differencing accumulated
    D^T y across iterations, which cancels catastrophically near
    convergence)."""

    y: Array           # y^{k+1} = prox_f(Dx + lam)
    lam: Array         # lam^{k+1} = lam + Dx - y^{k+1}
    d: Array           # D^T(y^{k+1} - lam^{k+1}) — next x-update RHS
    w: Optional[Array]   # D^T(y^{k+1} - y^k) — Boyd dual residual s = tau||w||
    v: Optional[Array]   # D^T lam^{k+1} — dual tolerance needs tau||v||


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "chunked"


def gram_stats(D: Array, b: Optional[Array] = None, *,
               backend: str = "auto",
               block_rows: Optional[int] = None) -> Tuple[Array, Optional[Array]]:
    """Backend-dispatched (D^T D, D^T b) in one streaming pass (paper §4).

    The single Gram entry point for solver setup and service ingestion.
    ``b`` may be None (Gram only), (m,), or (m, r) stacked right-hand
    sides; returns (G, c) with c None iff b is None. ``block_rows``
    bounds the chunked backend's live block (None -> autotuned); the
    Pallas backends tile from the autotuner's VMEM budget instead.
    """
    if isinstance(D, BlockCSR):
        if backend == "reference":
            # parity oracle: densify, then the textbook dense gram
            Dd = D.to_dense()
            if b is None:
                return gram_lib.gram(Dd), None
            return gram_lib.gram(Dd), gram_lib.gram_rhs(Dd, b)
        # HOST-ONLY pass (scipy CSR matmul; see kernels/spgram/ops.py) —
        # sparse setup runs outside jit, like every other store-driven
        # setup pass in the repo.
        return spgram_ops.sparse_gram_rhs(D, b)
    chosen = backend in ("auto", "sparse")   # "sparse" is data-format-
    if chosen:                               # selected; dense input streams
        backend = default_backend()
    m, n = D.shape
    if backend in ("pallas", "pallas_interpret") and D.dtype == jnp.float64:
        if not chosen:
            raise ValueError(f"backend {backend!r} runs f32/bf16 data, "
                             f"not {D.dtype}; use backend='auto'")
        backend = "chunked"
    if backend in ("pallas", "pallas_interpret"):
        interp = backend == "pallas_interpret"
        rhs = 0 if b is None else (b.shape[1] if b.ndim > 1 else 1)
        bm, bn = autotune.gram_blocks(m, n, D.dtype, rhs=rhs)
        if b is None:
            return gram_ops.gram(D, block_m=bm, block_n=bn,
                                 interpret=interp), None
        return gram_ops.gram_and_rhs(D, b, block_m=bm, block_n=bn,
                                     interpret=interp)
    if backend == "chunked":
        br = block_rows or autotune.chunked_block_rows(m, n, D.dtype)
        if b is None:
            return gram_lib.gram_chunked(D, br), None
        return gram_lib.gram_and_rhs_chunked(D, b, br)
    if backend == "reference":
        if b is None:
            return gram_lib.gram(D), None
        return gram_lib.gram(D), gram_lib.gram_rhs(D, b)
    raise ValueError(f"unknown backend {backend!r}; expected one of "
                     f"{BACKENDS + ('auto',)}")


@dataclasses.dataclass(frozen=True)
class IterationEngine:
    """Per-device fused iteration body for unwrapped ADMM (paper Alg. 2
    lines 5-8 plus both telemetry reductions).

    Operates on flat local data: D (m, n), aux/y/lam (m,), x (n,) — the
    node-stacked solvers flatten, the distributed solver passes its shard.
    Composes under shard_map (the cross-shard psum of ``d`` stays with the
    caller, per Alg. 2 line 6).
    """

    loss: ProxLoss
    tau: float = 1.0
    backend: str = "auto"
    block_m: Optional[int] = None          # None -> autotuned
    residency: Optional[str] = None        # None | "bf16"

    def __post_init__(self):
        if self.backend not in BACKENDS + ("auto",):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.residency not in RESIDENCY_DTYPES:
            raise ValueError(f"unknown residency {self.residency!r}")

    @property
    def delta(self) -> float:
        return 1.0 / self.tau

    # -- backend selection (rules documented in DESIGN.md §8) ---------------
    def resolve(self, dtype=jnp.float32) -> str:
        # "sparse" is a data-format backend: dense arrays have no sparse
        # body, so a dense resolve lands on the device default (the format
        # dispatch in iterate() picks sparse for BlockCSR under every
        # backend except an explicit reference).
        chosen = self.backend in ("auto", "sparse")
        b = default_backend() if chosen else self.backend
        if b in ("pallas", "pallas_interpret") and (
                self.loss.name not in PALLAS_KINDS
                or jnp.dtype(dtype) == jnp.float64):
            if not chosen:
                raise ValueError(
                    f"backend {b!r} cannot run loss {self.loss.name!r} on "
                    f"{jnp.dtype(dtype).name} data (its kernel takes "
                    f"{sorted(PALLAS_KINDS)} on f32/bf16); use "
                    "backend='auto' to let the engine choose")
            b = "chunked"
        if b == "chunked" and not self.loss.coordinatewise:
            b = "reference"
        return b

    def resolve_residency(self, dtype=jnp.float32) -> Optional[str]:
        """DESIGN.md §8 residency rule: explicit settings are honored
        as-is; ``"auto"`` casts to bf16 only on the real-TPU pallas
        backend (not measured on a chip) — on CPU/chunked (and
        interpret-mode) backends the per-block upcast dominates the saved
        bytes (CPU timings 0.55x/1.88x in BENCH_engine.json), so auto
        resolves to None."""
        if self.residency != "auto":
            return self.residency
        return "bf16" if self.resolve(dtype) == "pallas" else None

    # -- data residency -----------------------------------------------------
    def prepare(self, D) -> Array:
        """Cast D ONCE to its iteration-residency dtype (bf16 halves the
        per-iteration HBM bytes; accumulation stays f32 in-register).
        BlockCSR casts its value arrays; indices stay int32."""
        dt = RESIDENCY_DTYPES[self.resolve_residency(D.dtype)]
        if dt is None or D.dtype == dt:
            return D
        return D.astype(dt)

    # -- setup: Gram (+ RHS) in one data pass -------------------------------
    def gram(self, D, b: Optional[Array] = None,
             block_rows: Optional[int] = None):
        backend = self._gram_backend(D.dtype)
        if isinstance(D, BlockCSR) and self.backend == "reference":
            # the densify parity oracle must stay reachable for sparse
            # Gram too (the reference->chunked mapping below is a
            # dense-path preference, not an oracle bypass)
            backend = "reference"
        return gram_stats(D, b, backend=backend, block_rows=block_rows)

    def _gram_backend(self, dtype) -> str:
        # "auto" passes through so gram_stats may choose by dtype
        return "chunked" if self.backend == "reference" else self.backend

    # -- transpose application: D^T u without a dense upcast ----------------
    def rmatvec(self, D, u: Array) -> Array:
        """D^T u in accumulation precision, backend-dispatched like every
        other pass over D: the dense ``gram_rhs`` up-casts ALL of D to
        accumulation precision at once, which would materialize a full
        f32 copy of a bf16-resident D — the streaming-class backends
        (chunked, pallas, sparse) up-cast one block at a time instead.
        Setup-time and telemetry passes (warm-start d, run()'s grad_sq)
        route here; ``u`` may be (m,) or (m, r)."""
        if isinstance(D, BlockCSR):
            return spgram_ops.rmatvec(D, u)
        b = default_backend() if self.backend == "auto" else self.backend
        if b == "reference":
            return gram_lib.gram_rhs(D, u)
        m, n = D.shape
        br = self.block_m or autotune.chunked_block_rows(m, n, D.dtype)
        return gram_lib.gram_rhs_chunked(D, u, br)

    # -- warm-start init: d from existing iterates, one pass ----------------
    def transpose_d(self, D, y: Array, lam: Array):
        """d = D^T(y - lam) — setup-time only (cold starts get zeros
        without touching D; warm starts pay one column pass). The
        dispatch lives in :meth:`rmatvec` (there is no rhs-only Pallas
        kernel and the scan is setup-time, not per-iteration)."""
        return self.rmatvec(D, y - lam)

    # -- the fused iteration body -------------------------------------------
    def iterate(self, D, aux: Optional[Array], y: Array, lam: Array,
                x: Array, want_dual: bool = True) -> EngineStep:
        """Given x^{k+1}: stream D once, producing y^{k+1}, lam^{k+1} and
        the reduction(s) that drive iteration k+2 and the stopping rule.
        ``D`` is a dense (m, n) array or a :class:`BlockCSR`."""
        if isinstance(D, BlockCSR):
            if self.backend == "reference":
                return self._iterate_reference(D.to_dense(), aux, y, lam,
                                               x, want_dual)
            return self._iterate_sparse(D, aux, y, lam, x, want_dual)
        backend = self.resolve(D.dtype)
        if (backend == "chunked" and self.backend == "auto"
                and D.size * D.dtype.itemsize <= 16 * autotune.CACHE_BUDGET):
            # Small-D auto rule (measured in BENCH_engine.json): once D fits
            # in last-level cache the two-pass reference body re-reads it
            # for free and the scan's block bookkeeping only costs; the
            # one-pass stream wins when D spills. Explicit backend requests
            # are honored as-is.
            backend = "reference"
        if backend in ("pallas", "pallas_interpret"):
            return self._iterate_pallas(D, aux, y, lam, x,
                                        interpret=backend
                                        == "pallas_interpret",
                                        want_dual=want_dual)
        if backend == "chunked":
            return self._iterate_chunked(D, aux, y, lam, x,
                                         want_dual=want_dual)
        return self._iterate_reference(D, aux, y, lam, x,
                                       want_dual=want_dual)

    def _iterate_reference(self, D, aux, y, lam, x, want_dual):
        acc = gram_lib._acc_dtype(D.dtype)
        Df = D.astype(acc)
        Dx = Df @ x.astype(acc)
        y_new = self.loss.prox(Dx + lam, self.delta, aux)
        lam_new = lam + Dx - y_new
        if want_dual:
            if y_new.ndim > 1:
                # matrix iterates (m, K): three stacked multi-RHS products
                DfT = Df.T
                return EngineStep(y_new, lam_new, DfT @ (y_new - lam_new),
                                  DfT @ (y_new - y), DfT @ lam_new)
            dwv = Df.T @ jnp.stack(
                [y_new - lam_new, y_new - y, lam_new], axis=1)
            return EngineStep(y_new, lam_new, dwv[:, 0], dwv[:, 1],
                              dwv[:, 2])
        return EngineStep(y_new, lam_new, Df.T @ (y_new - lam_new),
                          None, None)

    def _iterate_chunked(self, D, aux, y, lam, x, want_dual):
        m, n = D.shape
        acc = gram_lib._acc_dtype(D.dtype)
        br = self.block_m or autotune.chunked_block_rows(m, n, D.dtype)
        xc = x.astype(acc)
        arrays = (D, y, lam) + ((aux,) if aux is not None else ())

        def body(carry, blk):
            d, w, v = carry
            Db, yb, lb = blk[0].astype(acc), blk[1], blk[2]
            ab = blk[3] if aux is not None else None
            Dx = Db @ xc
            y_b = self.loss.prox(Dx + lb, self.delta, ab)
            l_b = lb + Dx - y_b
            d = d + (y_b - l_b) @ Db
            if want_dual:
                w = w + (y_b - yb) @ Db
                v = v + l_b @ Db
            return (d, w, v), (y_b, l_b)

        zero = jnp.zeros((n,), acc)
        (d, w, v), (ys, ls) = gram_lib.scan_row_blocks(
            body, (zero, zero, zero), arrays, br)
        return EngineStep(ys, ls, d,
                          w if want_dual else None,
                          v if want_dual else None)

    def _iterate_sparse(self, D: BlockCSR, aux, y, lam, x, want_dual):
        """O(nnz) fused body: lax.scan over the static-shaped block-CSR
        blocks, gather-based Dx and gather-based d/w/v over each block's
        local CSC (kernels/spgram, DESIGN.md §10)."""
        y_new, lam_new, d, w, v = spgram_ops.sparse_admm_iter_full(
            D, aux, y, lam, x, loss=self.loss, delta=self.delta,
            want_dual=want_dual)
        return EngineStep(y_new, lam_new, d, w, v)

    def _iterate_pallas(self, D, aux, y, lam, x, interpret, want_dual):
        m, n = D.shape
        bm = self.block_m or autotune.iter_block_m(m, n, D.dtype)
        aux_arr = aux if aux is not None else jnp.zeros_like(y)
        y_new, lam_new, d, w, v = admm_iter_full(
            D, aux_arr, y, lam, x, kind=self.loss.name,
            delta=self.loss.kernel_delta_scale * self.delta,
            block_m=bm, interpret=interpret,
            param=self.loss.kernel_param)
        return EngineStep(y_new, lam_new, d, w if want_dual else None,
                          v if want_dual else None)

    # -- host-loop step with buffer donation --------------------------------
    def make_step(self, D: Array, aux: Optional[Array], L: Array):
        """Jitted ``step(y, lam, d) -> (y', lam', d', x)`` closing over the
        prepared data and Gram factor, with the (y, lam) iterate pair
        DONATED — host-driven loops (serving, benchmarks) update in place
        instead of allocating fresh iterate buffers every call."""
        Dres = self.prepare(D)

        @partial(jax.jit, donate_argnums=(0, 1))
        def step(y, lam, d):
            x = gram_lib.gram_solve(L, d)
            st = self.iterate(Dres, aux, y, lam, x, want_dual=False)
            return st.y, st.lam, st.d, x

        return step
