"""The comparison that decides ``correct`` refuses the control and every
fault a cell can have, at a size a CPU holds.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/test_correct.py

Each case drives a whole rehearsal of the cell (``run.py --rehearse``,
which skips the look for a chip) with the control or a fault planted in
the timed path (``faults.py``), and reads the run's own ``correct``; the
program as it is must come out correct.
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import control, faults  # noqa: E402


def rehearse(workload: str, kind: str, variant) -> dict:
    return control.reading(workload, kind, variant, 2 ** 33 + 11, 1,
                           rehearse=True)


def failed(line: dict) -> list:
    return [k for k, c in line["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("workload", ["sift1m.batch64", "deep1m.batch64",
                                      "sift1m.serve-poisson"])
@pytest.mark.parametrize("variant", (None,) + faults.QUERY_VARIANTS)
def test_search_faults_make_a_run_incorrect(workload, variant):
    kind = "open_poisson" if "serve" in workload else "closed_batch"
    line = rehearse(workload, kind, variant)
    assert line["correct"] is (variant is None), (variant, line["checks"])
    assert bool(failed(line)) is (variant is not None)


@pytest.mark.parametrize("variant", (None,) + faults.BUILD_VARIANTS)
def test_build_faults_make_a_run_incorrect(variant):
    line = rehearse("sift1m.build", "build_loop", variant)
    assert line["correct"] is (variant is None), (variant, line["checks"])
    assert bool(failed(line)) is (variant is not None)
