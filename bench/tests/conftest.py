"""The benchmark's own tests, run by hand on the CPU:

    python -m pytest bench/tests

(``pytest.ini`` collects only ``tests/``.) They import the bench modules
the way the harness does."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import json
import os
import shutil

import pytest


def make_root(tmp, rows=4000, features=64, dtype="float32",
              traffic="logistic", max_iters=None, limits=None, chips=1):
    """A checkout in ``tmp``: BENCHMARK.json and bench/ copied, src/
    linked, plus a configuration ``tiny`` of the given size and a cell
    ``tiny.<traffic>`` that runs it, held to ``star.logistic``'s limits
    except where ``limits`` says otherwise. With ``chips`` 4 the
    configuration and the limits are ``star_share_x4``'s and
    ``star_x4.logistic``'s instead, rows split over four chips. Returns
    (root, cell name)."""
    base, base_cell = (("star_share", "star.logistic") if chips == 1
                       else ("star_share_x4", "star_x4.logistic"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(ROOT / "src", tmp / "src")
    cfg = json.loads((BENCH / "configs" / f"{base}.json").read_text())
    cfg.update(name="tiny", rows=rows, features=features, dtype=dtype,
               chips=chips)
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    if max_iters is not None:
        mix = json.loads((BENCH / "mixes" / f"{traffic}.json").read_text())
        mix["max_iters"] = max_iters
        traffic = f"{traffic}_capped"
        (tmp / "bench" / "mixes" / f"{traffic}.json").write_text(
            json.dumps(mix))
    cell = f"tiny.{traffic}"
    spec = json.loads((tmp / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": ["rows"], "why": "test"})
    spec["workloads"].append({"name": cell, "config": "tiny",
                              "traffic": traffic, "chips": chips,
                              "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    spec_limits = json.loads((BENCH / "limits" / f"{base_cell}.json")
                             .read_text())
    if limits is not None:
        spec_limits["limits"] = {**spec_limits["limits"], **limits}
    (tmp / "bench" / "limits" / f"{cell}.json").write_text(
        json.dumps(spec_limits))
    return tmp, cell


@pytest.fixture
def tiny_root(tmp_path):
    return lambda **kw: make_root(tmp_path, **kw)
