"""The four-chip path on the CPU: a tiny cell over four virtual devices
(``--xla_force_host_platform_device_count=4``, set before JAX starts, so
each case runs in a process of its own) has its rows generated split
over the chips, is fitted through the ``ShardMapExecutor``, is checked
against the reference one chip's block at a time, and comes out correct;
with its timed path broken underneath it comes out not correct, once for
each fault a sharded fit can have. The one-chip placement is held to the
bytes it has always generated.
"""
import hashlib
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import harness

ROWS, FEATURES = 4 * 4000, 64

DRIVE = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    root, cell, fault = Path(sys.argv[1]), sys.argv[2], sys.argv[3]
    sys.path[:0] = [str(root / "bench"), str(root / "src")]
    import control, harness
    import jax.numpy as jnp
    import repro.exec as rexec
    from repro.exec import ShardMapExecutor, base

    data = harness.bench_module("data")
    reference = harness.bench_module("reference")
    seen = {"executors": [], "reference_blocks": []}

    init = ShardMapExecutor.__init__

    def spy_init(self, engine, D, *a, **kw):
        seen["executors"].append(sorted(
            [s.index[0].start, s.data.shape[0], s.device.id]
            for s in D.addressable_shards))
        init(self, engine, D, *a, **kw)

    admm = reference.admm

    def spy_admm(blocks, **kw):
        seen["reference_blocks"].append(
            [[D.shape[0], sorted(d.id for d in D.devices())]
             for D, _ in blocks])
        return admm(blocks, **kw)

    ShardMapExecutor.__init__ = spy_init
    reference.admm = spy_admm

    def state_unchanged():
        def sweep(self, x, k):
            _, _, self._err, sw = self._fns[2](
                self._D, self._aux, self._y, self._lam,
                jnp.asarray(x, self.acc), self._err)
            return sw                    # y and lam are never updated
        ShardMapExecutor.sweep = sweep

    def answer_altered():
        solve = base.solve_with_executor
        def altered(*a, **kw):
            res = solve(*a, **kw)
            return res._replace(
                x=res.x.at[0].add(1e-2 * jnp.max(jnp.abs(res.x))))
        base.solve_with_executor = rexec.solve_with_executor = altered

    planted = control.exchange_dropped()   # kept: its exit undoes it
    if fault == "exchange_dropped":
        planted.__enter__()
    elif fault == "state_unchanged":
        state_unchanged()
    elif fault == "answer_altered":
        answer_altered()
    else:
        assert fault == "none", fault
    res = harness.run_cell(cell, 2 ** 31 + 7, 0.2, False, root=root,
                           require_chip=False,
                           log=lambda m: print(m, file=sys.stderr))
    print(json.dumps({**res, **seen}))
""")


def _drive(root, cell, fault):
    out = subprocess.run(
        [sys.executable, "-c", DRIVE, str(root), cell, fault],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def four_chips(tmp_path_factory):
    from conftest import make_root

    return make_root(tmp_path_factory.mktemp("x4"), rows=ROWS,
                     features=FEATURES, chips=4)


def test_sound_four_chip_run_is_correct(four_chips):
    res = _drive(*four_chips, "none")
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    # every fit's executor got D where the harness generated it: each
    # chip its own quarter of the rows, in order, nothing gathered
    quarters = [[i * ROWS // 4, ROWS // 4, i] for i in range(4)]
    assert res["executors"] and all(e == quarters
                                    for e in res["executors"])
    # the reference went over the chips' rows one chip at a time
    assert res["reference_blocks"] == [[[ROWS // 4, [i]]
                                        for i in range(4)]]


@pytest.mark.parametrize("fault", ["exchange_dropped", "state_unchanged",
                                   "answer_altered"])
def test_broken_four_chip_path_is_not_correct(four_chips, fault):
    res = _drive(*four_chips, fault)
    assert res["executors"], "the fault ran no sharded fit"
    assert not res["correct"], res["checks"]


SPLIT = textwrap.dedent("""
    import sys
    sys.path.insert(0, sys.argv[1])
    import jax, numpy as np
    import data
    mesh = data.row_mesh(jax.devices())
    D, a = data.make("classification", 16000, 64, "float32",
                     data.row_shardings(mesh))
    D1, a1 = data.make("classification", 16000, 64, "float32")
    assert sorted(len(s.data) for s in D.addressable_shards) == [4000] * 4
    assert np.array_equal(np.asarray(D), np.asarray(D1))
    assert np.array_equal(np.asarray(a), np.asarray(a1))
    D = data.flip(D, data.signs(3, 0, 64))
    assert D.sharding == data.row_shardings(mesh)[0]
    print("ok")
""")


def test_split_rows_are_the_one_chip_rows():
    """Rows split over four chips are, byte for byte, the rows one chip
    generates, and the column flip keeps them where they are."""
    out = subprocess.run(
        [sys.executable, "-c", SPLIT, str(harness.BENCH)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0 and out.stdout.strip() == "ok", \
        out.stderr[-3000:]


# sha256 of D (4000 x 64 f32) and of its labels as generated on the CPU
# by one chip's placement before cells could span chips.
ONE_CHIP = ("253547bfd5136a9b7435250d840010849b216027ae35d81b0b632a6d7179e457",
            "2e327c7cd10342010cedf1cb3703153c1ca8a9b6d55ba02dd83df7677239214e")


def test_one_chip_placement_is_unchanged():
    data = harness.bench_module("data")
    D, a = data.make("classification", 4000, 64, "float32")
    assert isinstance(D.sharding, SingleDeviceSharding)
    assert isinstance(a.sharding, SingleDeviceSharding)
    digest = lambda x: hashlib.sha256(np.asarray(x).tobytes()).hexdigest()
    assert (digest(D), digest(a)) == ONE_CHIP
    assert data.blocks(D, a) == [(D, a)]
