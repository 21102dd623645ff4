"""Compile rehearsal of the main path for a TPU v5e chip that is described,
not attached: no chip is needed, and what the chip's compiler would refuse
(more VMEM than a kernel may use, more HBM than the chip has, an unaligned
block) fails here.

Every shape is the paper's star share: Table 1's 950,272,000 x 307 rows
over 256 chips, 3,712,000 x 307 per chip, 4.56 GB of f32 D. The topology
is described inside a fixture, never at import, and the persistent compile
cache is off around the compiles (an entry compiled for a described chip
cannot be read back without one). This is the repository's only test that
describes a chip.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.prox import make_logistic
from repro.engine import IterationEngine, autotune
from repro.exec.local import _fused_step
from repro.kernels import tiling
from repro.kernels.admm_iter.ops import admm_iter_full
from repro.kernels.gram.ops import gram_and_rhs

M, N = 3_712_000, 307
D_BYTES = M * N * 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _arg(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _runtime_layout(topo, shape, dtype):
    dev = topo.devices[0]
    return str(dev.client.get_default_layout(jnp.dtype(dtype), shape, dev))


def _assert_streams_d_in_place(compiled):
    """No temporaries to speak of, a Pallas kernel, and nothing but
    parameters and bitcasts ever holds a D-sized array."""
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.1 * D_BYTES, f"{temp / 1e9:.2f} GB of temporaries"
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    d_sized = re.compile(rf"= [a-z0-9]+\[(?:1,)?({M},{N}|{N},{M})\]"
                         r"\{[^}]*\} (\S+?)\(")
    ops = {m.group(2) for m in d_sized.finditer(hlo)}
    assert ops <= {"parameter", "bitcast"}, ops


@pytest.mark.parametrize("m,n,dtype", [
    (M, N, jnp.float32), (M, N, jnp.bfloat16),      # star share
    (1_440_000, 2_000, jnp.bfloat16),               # Fig-1 share
    (65_536, 512, jnp.float32), (1_000, 307, jnp.float32),
])
def test_layout_rule_matches_the_v5e_runtime(topo, m, n, dtype):
    """The kernels read D the way it sits in HBM (kernels/tiling.py); off
    the chip they predict the runtime's choice with a padding rule, which
    must agree with what the v5e runtime answers."""
    minor_to_major = _runtime_layout(topo, (m, n), dtype).split(":")[0]
    assert tiling._padding_rule(m, n, dtype) == (minor_to_major == "{0,1")


@pytest.mark.parametrize("kind", ["logistic", "hinge"])
def test_fused_iteration_kernel_fits_at_star_share(one_chip, kind):
    bm = autotune.iter_block_m(M, N, jnp.float32)
    vec = _arg(one_chip, (M,))
    compiled = jax.jit(
        lambda D, aux, y, lam, x: admm_iter_full(
            D, aux, y, lam, x, kind=kind, delta=10.0, block_m=bm)
    ).lower(_arg(one_chip, (M, N)), vec, vec, vec,
            _arg(one_chip, (N,))).compile()
    _assert_streams_d_in_place(compiled)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_setup_fits_at_star_share(one_chip, dtype):
    bm, bn = autotune.gram_blocks(M, N, dtype, rhs=1)
    compiled = jax.jit(
        lambda D, b: gram_and_rhs(D, b, block_m=bm, block_n=bn)
    ).lower(_arg(one_chip, (M, N), dtype), _arg(one_chip, (M,))).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.1 * D_BYTES, f"{temp / 1e9:.2f} GB of temporaries"
    assert "tpu_custom_call" in compiled.as_text()


def test_local_step_reads_d_without_a_copy(topo, one_chip):
    """The LocalExecutor's per-iteration program on the pallas backend,
    with D's parameter layout pinned to the one data arrives in on the
    chip: the runtime's default for the shape, which is column-major for
    (3,712,000, 307) — the kernel then streams D^T panels as a bitcast."""
    from jax.experimental.layout import Format, Layout
    layout = _runtime_layout(topo, (M, N), jnp.float32)
    assert layout.startswith("{0,1"), layout
    pinned = Format(Layout(major_to_minor=(1, 0)), one_chip)
    step = _fused_step(IterationEngine(loss=make_logistic(), tau=0.1,
                                       backend="pallas"))
    vec = _arg(one_chip, (M,))
    compiled = step.lower(jax.ShapeDtypeStruct((M, N), jnp.float32,
                                               sharding=pinned),
                          vec, vec, vec, _arg(one_chip, (N,))).compile()
    assert compiled.input_formats[0][0].layout.major_to_minor == (1, 0)
    _assert_streams_d_in_place(compiled)
