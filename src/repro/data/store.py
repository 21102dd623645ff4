"""ShardedMatrixStore — host-RAM / memory-mapped row-block data store.

The out-of-core half of the paper's regime (DESIGN.md §9): the 5 Tb
datasets of §10 never fit an accelerator, but every solver object is a
reduction over ROW BLOCKS of D — Gram setup, the d/w/v transpose
reductions, the prox. This store holds the rows where they fit (host RAM,
or on disk behind ``numpy`` memory maps) and hands the streaming engine a
uniform iterator of ``(D_block, aux_block)`` pairs; device memory is then
bounded by one block regardless of m.

Layout contract:

  * rows are split into fixed-height blocks of ``block_rows``; the tail
    block is stored UNPADDED (logical length) and zero-padded on read when
    ``padded=True`` — zero rows are exact under every transpose reduction,
    so padded reads need no masks;
  * ``aux`` (labels / right-hand sides) rides along row-aligned, optional;
  * every block carries a content fingerprint computed at WRITE time, so
    downstream ingestion (``SufficientStats.from_store``) folds the
    store's fingerprints instead of re-hashing gigabytes on every pass.

On-disk format (``save`` / ``open``): a directory of ``block_*.npy`` (+
``aux_*.npy``) files, loaded back with ``mmap_mode="r"`` — the OS page
cache becomes the block cache and the prefetch thread of the streaming
engine overlaps page-in with compute.

SPARSE stores (:meth:`from_sparse`) hold padded block-CSR blocks
(``data/sparse.BlockCSR``): each block is its four index/value arrays,
so store bytes scale with nnz — the out-of-core path fits ~1/density
more rows per device budget. Sparse blocks carry static shapes (padding
is free in sparse-land: pad rows are zero-nnz), so ``padded`` only
selects the block's LOGICAL row count; ``block()`` returns a one-block
``BlockCSR`` in place of the dense array.

Fingerprinting lives HERE (the data layer owns content identity);
``repro.service.stats`` re-exports the helpers for backward compatibility.
"""
from __future__ import annotations

import hashlib
import json
import os
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

ZERO_FINGERPRINT = "0" * 64

_META_NAME = "store_meta.json"


def fingerprint_array(*arrays) -> str:
    """sha256 content fingerprint of host-backed arrays (shape + bytes)."""
    h = hashlib.sha256()
    for a in arrays:
        if a is None:
            h.update(b"none")
            continue
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def combine_fingerprints(fp_a: str, fp_b: str, sign: int = 1) -> str:
    """Commutative, associative, multiplicity-sensitive fold.

    Addition mod 2^256 (not XOR): ingest order cannot matter, but ingesting
    the same block twice must NOT cancel back to the original fingerprint —
    the stats really do contain it twice. ``sign=-1`` is the downdate
    inverse, so retiring a block restores the prior fingerprint exactly.
    """
    return format((int(fp_a, 16) + sign * int(fp_b, 16)) % (1 << 256),
                  "064x")


def _pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    """Zero-pad the leading axis up to ``rows`` (no-op when already there)."""
    k = a.shape[0]
    if k == rows:
        return a
    out = np.zeros((rows,) + a.shape[1:], a.dtype)
    out[:k] = a
    return out


class ShardedMatrixStore:
    """Row-block store for a tall (m, n) design matrix + row-aligned aux.

    Blocks are host ``numpy`` arrays — plain RAM when built with
    :meth:`from_arrays`, read-only memory maps when opened from disk with
    :meth:`open`. The solver never sees more than one block at a time.
    """

    def __init__(self, blocks_D: Sequence,
                 blocks_aux: Optional[Sequence[np.ndarray]],
                 block_rows: int,
                 fingerprints: Sequence[str],
                 path: Optional[str] = None,
                 sparse_meta: Optional[dict] = None):
        if not blocks_D:
            raise ValueError("store needs at least one block")
        if blocks_aux is not None and len(blocks_aux) != len(blocks_D):
            raise ValueError("aux block count != D block count")
        if len(fingerprints) != len(blocks_D):
            raise ValueError("fingerprint count != block count")
        self._blocks_D = list(blocks_D)
        self._blocks_aux = list(blocks_aux) if blocks_aux is not None else None
        self.block_rows = int(block_rows)
        self.fingerprints = list(fingerprints)
        self.path = path
        self.sparse_meta = dict(sparse_meta) if sparse_meta else None
        if self.sparse_meta:
            # blocks are (indices, values, col_indices, col_values) tuples
            self.n = int(self.sparse_meta["n"])
            self.m = int(self.sparse_meta["m"])
            self.dtype = np.dtype(self.sparse_meta["dtype"])
        else:
            self.n = int(blocks_D[0].shape[1])
            self.m = int(sum(b.shape[0] for b in blocks_D))
            self.dtype = np.dtype(blocks_D[0].dtype)

    @property
    def sparse(self) -> bool:
        return self.sparse_meta is not None

    # -- construction -------------------------------------------------------
    @classmethod
    def from_arrays(cls, D, aux=None,
                    block_rows: int = 4096) -> "ShardedMatrixStore":
        """Split host arrays into row blocks (tail unpadded) and fingerprint
        each block once at build time."""
        D = np.asarray(D)
        if D.ndim == 3:                       # node-stacked (N, m_i, n)
            D = D.reshape(-1, D.shape[-1])
        if aux is not None:
            aux = np.asarray(aux).reshape(-1)
            if aux.shape[0] != D.shape[0]:
                raise ValueError(
                    f"aux rows {aux.shape[0]} != D rows {D.shape[0]}")
        m = D.shape[0]
        block_rows = int(min(block_rows, m))
        starts = range(0, m, block_rows)
        blocks_D = [np.ascontiguousarray(D[s:s + block_rows]) for s in starts]
        blocks_aux = (None if aux is None else
                      [np.ascontiguousarray(aux[s:s + block_rows])
                       for s in starts])
        fps = [fingerprint_array(bd, None if blocks_aux is None
                                 else blocks_aux[i])
               for i, bd in enumerate(blocks_D)]
        return cls(blocks_D, blocks_aux, block_rows, fps)

    @classmethod
    def from_sparse(cls, bcsr, aux=None) -> "ShardedMatrixStore":
        """Store a :class:`repro.data.sparse.BlockCSR`: one store block
        per CSR block (``block_rows = bcsr.block_m``), bytes scaling with
        nnz. Fingerprints hash each block's (indices, values, aux) at
        write time, like the dense path."""
        from repro.data.sparse import host_blocks
        idx, val, cidx, cval = host_blocks(bcsr)
        nb = idx.shape[0]
        if aux is not None:
            aux = np.asarray(aux).reshape(-1)
            if aux.shape[0] != bcsr.m:
                raise ValueError(
                    f"aux rows {aux.shape[0]} != D rows {bcsr.m}")
        blocks, blocks_aux, fps = [], [], []
        for k in range(nb):
            blocks.append((np.ascontiguousarray(idx[k]),
                           np.ascontiguousarray(val[k]),
                           np.ascontiguousarray(cidx[k]),
                           np.ascontiguousarray(cval[k])))
            a_b = None
            if aux is not None:
                s = k * bcsr.block_m
                a_b = np.ascontiguousarray(
                    aux[s:s + min(bcsr.block_m, bcsr.m - s)])
                blocks_aux.append(a_b)
            fps.append(fingerprint_array(blocks[-1][0], blocks[-1][1],
                                         a_b))
        meta = {"m": bcsr.m, "n": bcsr.n, "nnz": bcsr.nnz,
                "kp": bcsr.kp, "kc": bcsr.kc,
                "dtype": np.dtype(bcsr.dtype).name}
        return cls(blocks, blocks_aux if aux is not None else None,
                   bcsr.block_m, fps, sparse_meta=meta)

    # -- persistence (memory-mapped reopen) ---------------------------------
    _SPARSE_PARTS = ("idx", "val", "cidx", "cval")

    def save(self, path: str) -> str:
        """Write blocks as .npy files + a JSON manifest; reopen with
        :meth:`open` for memory-mapped (out-of-RAM) access."""
        os.makedirs(path, exist_ok=True)
        for i, b in enumerate(self._blocks_D):
            if self.sparse:
                for part, arr in zip(self._SPARSE_PARTS, b):
                    np.save(os.path.join(path,
                                         f"block_{i:06d}_{part}.npy"), arr)
            else:
                np.save(os.path.join(path, f"block_{i:06d}.npy"), b)
            if self._blocks_aux is not None:
                np.save(os.path.join(path, f"aux_{i:06d}.npy"),
                        self._blocks_aux[i])
        meta = {"m": self.m, "n": self.n, "block_rows": self.block_rows,
                "nblocks": self.nblocks, "dtype": self.dtype.name,
                "has_aux": self._blocks_aux is not None,
                "fingerprints": self.fingerprints,
                "sparse": self.sparse_meta}
        with open(os.path.join(path, _META_NAME), "w") as f:
            json.dump(meta, f, indent=1)
        return path

    @classmethod
    def open(cls, path: str) -> "ShardedMatrixStore":
        """Memory-map a saved store; blocks page in lazily on first touch,
        so opening a multi-terabyte store costs only the manifest read."""
        with open(os.path.join(path, _META_NAME)) as f:
            meta = json.load(f)
        sparse_meta = meta.get("sparse")
        if sparse_meta:
            blocks_D = [tuple(
                np.load(os.path.join(path, f"block_{i:06d}_{part}.npy"),
                        mmap_mode="r") for part in cls._SPARSE_PARTS)
                for i in range(meta["nblocks"])]
        else:
            blocks_D = [np.load(os.path.join(path, f"block_{i:06d}.npy"),
                                mmap_mode="r")
                        for i in range(meta["nblocks"])]
        blocks_aux = None
        if meta["has_aux"]:
            blocks_aux = [np.load(os.path.join(path, f"aux_{i:06d}.npy"),
                                  mmap_mode="r")
                          for i in range(meta["nblocks"])]
        return cls(blocks_D, blocks_aux, meta["block_rows"],
                   meta["fingerprints"], path=path,
                   sparse_meta=sparse_meta)

    # -- block access -------------------------------------------------------
    @property
    def nblocks(self) -> int:
        return len(self._blocks_D)

    @property
    def has_aux(self) -> bool:
        return self._blocks_aux is not None

    @property
    def nbytes(self) -> int:
        if self.sparse:
            return sum(a.nbytes for b in self._blocks_D for a in b)
        return sum(b.nbytes for b in self._blocks_D)

    @property
    def fingerprint(self) -> str:
        """Order-independent fold of the per-block fingerprints — equals the
        fingerprint of ingesting every block through
        ``SufficientStats.update``."""
        fp = ZERO_FINGERPRINT
        for b in self.fingerprints:
            fp = combine_fingerprints(fp, b)
        return fp

    def block_slice(self, k: int) -> slice:
        """Logical row range [start, stop) of block k (tail may be short)."""
        start = k * self.block_rows
        if self.sparse:
            stop = min(start + self.block_rows, self.m)
        else:
            stop = start + self._blocks_D[k].shape[0]
        return slice(start, stop)

    def block(self, k: int, padded: bool = False
              ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Block k as host arrays. ``padded=True`` zero-pads the tail block
        to the uniform (block_rows, n) shape so every device step compiles
        once — exact, per the zero-row argument above. Sparse stores
        return a one-block :class:`~repro.data.sparse.BlockCSR` whose
        arrays are ALWAYS full-shape (pad rows are zero-nnz); ``padded``
        only selects whether its logical ``m`` is the uniform block_rows
        or the tail's true row count."""
        a_b = self._blocks_aux[k] if self._blocks_aux is not None else None
        if self.sparse:
            from repro.data.sparse import BlockCSR
            idx, val, cidx, cval = self._blocks_D[k]
            sl = self.block_slice(k)
            rows = self.block_rows if padded else sl.stop - sl.start
            # nnz is static pytree aux: it must be block-INDEPENDENT
            # (slot capacity, never an exact count) or the streaming
            # step would retrace per block AND pay a full host scan of
            # the (possibly memory-mapped) values every sweep.
            D_b = BlockCSR(indices=np.asarray(idx)[None],
                           values=np.asarray(val)[None],
                           col_indices=np.asarray(cidx)[None],
                           col_values=np.asarray(cval)[None],
                           m=int(rows), n=self.n,
                           nnz=int(self.block_rows) * int(idx.shape[-1]))
            if padded and a_b is not None and a_b.shape[0] != self.block_rows:
                a_b = _pad_rows(np.asarray(a_b), self.block_rows)
            return D_b, a_b
        D_b = self._blocks_D[k]
        if padded and D_b.shape[0] != self.block_rows:
            D_b = _pad_rows(np.asarray(D_b), self.block_rows)
            if a_b is not None:
                a_b = _pad_rows(np.asarray(a_b), self.block_rows)
        return D_b, a_b

    def verify_block(self, k: int) -> bool:
        """Re-hash block k's CONTENT and compare against its write-time
        fingerprint. The cluster runtime's reassignment path calls this
        before a new owner computes on an orphaned block: ownership
        moves by index, so the fingerprint is what guarantees the
        survivor's store really holds the same rows the dead worker
        held (a stale or torn mmap fails here instead of corrupting the
        solve). Hashes exactly what write time hashed: the UNPADDED
        dense block (or the sparse index/value arrays) plus aux."""
        a_b = self._blocks_aux[k] if self._blocks_aux is not None else None
        if self.sparse:
            idx, val, _, _ = self._blocks_D[k]
            fp = fingerprint_array(np.ascontiguousarray(idx),
                                   np.ascontiguousarray(val), a_b)
        else:
            fp = fingerprint_array(self._blocks_D[k], a_b)
        return fp == self.fingerprints[k]

    def verify_blocks(self, blocks) -> list:
        """Batch :meth:`verify_block`; returns the block indices whose
        content does NOT match (empty = all verified). The elastic-join
        path uses this so a joiner can report every bad block of an
        assignment at once instead of dying on the first."""
        return [int(k) for k in blocks if not self.verify_block(int(k))]

    def iter_blocks(self, padded: bool = False
                    ) -> Iterator[Tuple[np.ndarray, Optional[np.ndarray]]]:
        """The store's contract with the streaming engine: ``(D_block,
        aux_block)`` pairs in row order (aux_block is None for unlabeled
        stores)."""
        for k in range(self.nblocks):
            yield self.block(k, padded=padded)

    def __repr__(self) -> str:
        where = f"mmap:{self.path}" if self.path else "ram"
        kind = (f"sparse nnz={self.sparse_meta['nnz']}, "
                if self.sparse else "")
        return (f"ShardedMatrixStore(m={self.m}, n={self.n}, "
                f"block_rows={self.block_rows}, nblocks={self.nblocks}, "
                f"{kind}dtype={self.dtype.name}, {where})")
