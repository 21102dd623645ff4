"""The benchmark's data: a synthetic fit problem made on the device, and
the exact transformation that gives every seed, and every fit of a run,
data of its own.

Every seed fits one dataset under signs of its own. Entry (r, j) of the
dataset is drawn from the counter (r, j) by Threefry-2x32 under a fixed
key, so the whole of D is one elementwise program that writes D's own
buffer. The seed picks the signs of the columns (:func:`signs`), before
the warm-up fit and again before every fit of the window. Negating
columns is exact in floating point: it negates those entries of x and
the matching rows and columns of the Gram and leaves Dx, the labels and
every iterate's magnitude bit for bit as they were, so every seed and
every fit takes the same iterations and does the same work. (Reordering
the rows would not: at these sizes the stopping rule's residuals sit
near their rounding floor, and a new order of the same rows moves the
stop by one or two iterations; PERF.md.)

The generator follows ``classification_problem`` in the program's
``data/synthetic.py`` (paper §10.1), taken flat (m, n) and without the
per-node heterogeneity shift, and imports nothing from it, so that a
change to the program cannot move the yardstick; nor can a change to
how JAX splits its keys, since the counter hash is written out here. A
config names its generator under ``"data"``.

On several chips the rows are split over them in blocks, in order
(:func:`row_shardings`): each chip generates only its own block, the
bytes are those one chip would generate, and the check gets the blocks
one by one (:func:`blocks`).
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BASE_KEY = (0x243F6A88, 0x85A308D3)     # the one dataset of every seed
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def threefry(x0, x1, key=BASE_KEY):
    """Threefry-2x32, 20 rounds (Salmon et al., SC 2011), of uint32 arrays;
    the first output word."""
    k0, k1 = (jnp.uint32(k) for k in key)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(0x1BD11BDA))
    x0, x1 = x0 + ks[0], x1 + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = ((x1 << r) | (x1 >> (32 - r))) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0


def normal(bits):
    """Standard normal f32 from uint32 bits, as ``jax.random.normal``
    makes it: a uniform on (-1, 1) through sqrt(2) erfinv."""
    f = jax.lax.bitcast_convert_type(
        (bits >> 9) | jnp.uint32(0x3F800000), jnp.float32) - 1.0
    lo = np.nextafter(np.float32(-1.0), np.float32(0.0))
    return np.float32(math.sqrt(2.0)) * jax.lax.erf_inv(
        jnp.maximum(lo, 2.0 * f - 1.0))


def classification(m: int, n: int, dtype, informative: int = 5,
                   mean_shift: float = 1.0):
    """Two Gaussian classes, every other row each; class +1 has mean
    ``mean_shift`` in its first ``informative`` columns (the classes
    overlap). Returns (D (m, n) dtype, labels (m,) f32)."""
    row = jax.lax.broadcasted_iota(jnp.uint32, (m, 1), 0)
    labels = jnp.where(row % 2 == 1, 1.0, -1.0).astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.uint32, (m, n), 1)
    shift = jnp.where(col < informative, jnp.float32(mean_shift), 0.0)
    D = normal(threefry(jnp.broadcast_to(row, (m, n)), col))
    D = D + jnp.where(labels > 0, shift, 0.0)
    return D.astype(dtype), labels[:, 0]


GENERATORS = {"classification": classification}
ROWS = "data"        # the mesh axis the rows are split over


def row_mesh(devices) -> Mesh:
    """A 1-D mesh over ``devices`` whose one axis splits the rows."""
    return Mesh(np.array(devices), (ROWS,))


def row_shardings(mesh: Mesh):
    """(D's, the labels') shardings with the rows split over ``mesh``, as
    ``core/distributed.shard_rows`` places them: chip i holds the i-th
    block of rows, whole."""
    return (NamedSharding(mesh, P((ROWS,), None)),
            NamedSharding(mesh, P((ROWS,))))


def generator(kind: str, m: int, n: int, dtype, shardings=None):
    """The jitted program that generates the dataset. Without
    ``shardings`` it writes D on the default device; with them (see
    :func:`row_shardings`) each chip writes only its own rows."""
    gen = GENERATORS[kind]
    fn = lambda: gen(m, n, dtype)
    if shardings is None:
        return jax.jit(fn)
    return jax.jit(fn, out_shardings=shardings)


def make(kind: str, m: int, n: int, dtype, shardings=None):
    """The dataset, generated on the device in one jitted program that
    writes D's own buffer (:func:`generator`). It is the same for every
    seed, and for every placement: the seed acts through :func:`signs`."""
    return generator(kind, m, n, dtype, shardings)()


def blocks(D, aux):
    """[(D_i, labels_i), ...]: the rows and labels each chip holds, in row
    order, each block an array on its own chip (no copy); one block, the
    arrays themselves, on one chip."""
    if len(D.addressable_shards) == 1:
        return [(D, aux)]
    first_row = lambda s: s.index[0].start
    out = []
    for d, a in zip(sorted(D.addressable_shards, key=first_row),
                    sorted(aux.addressable_shards, key=first_row)):
        if d.device != a.device or d.index[0] != a.index[0]:
            raise ValueError(f"rows {d.index[0]} of D on {d.device} but "
                             f"labels {a.index[0]} on {a.device}")
        out.append((d.data, a.data))
    return out


def signs(seed: int, fit: int, n: int) -> np.ndarray:
    """The +-1 column signs that turn fit ``fit - 1``'s data into fit
    ``fit``'s, drawn on the host from (seed, fit); fit 0's turn the
    dataset into the seed's own."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, int(fit)])
    return np.where(rng.random(n) < 0.5, -1.0, 1.0).astype(np.float32)


def _flip(D, s):
    return (D * s.astype(D.dtype)[None, :]).astype(D.dtype)


# D is donated: the flip rewrites D's own buffer, so no second copy of D
# is ever live. Flipping column j negates x_j and G's row and column j and
# leaves Dx, the labels and every iterate's magnitude exactly as they were.
flip = jax.jit(_flip, donate_argnums=0)
