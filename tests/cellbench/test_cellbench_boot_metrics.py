"""The compile surface's per-layer metrics (PR 42): each is a data file
on the ``state_value`` reader, reads its key off the replicas' last
/state, and reads NOTHING (instead of raising) where the program serves
no such key, as the parent of the PR that added the key does not."""

import json
import os

import pytest

from cellbench import run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: metric -> the /state key it reads (milliseconds; the metric is seconds)
KEYS = {
    "boot_ready_s": "boot_ready_ms",
    "boot_import_s": "boot_import_ms",
    "boot_backend_s": "boot_backend_ms",
    "engine_build_s": "boot_engine_ms",
    "late_load_s": "xla_late_ms",
    "late_load_retrieval_s": "xla_late_retrieval_ms",
}

#: what a replica of this PR serves, and what its parent served
STATE = {"boot_ready_ms": 31250.0, "boot_import_ms": 2300.5,
         "boot_backend_ms": 9100.0, "boot_engine_ms": 4020.25,
         "xla_late_ms": 21875.0, "xla_late_retrieval_ms": 12500.0,
         "warmup_ms": 13800.0}
PARENT_STATE = {"warmup_ms": 13800.0, "weights_init_ms": 1100.0}


def ctx(*states):
    return {"snap2": {"states": list(states)}}


@pytest.mark.parametrize("name", KEYS)
def test_definition_file_is_data_on_the_state_value_reader(name):
    with open(os.path.join(
            REPO, "cellbench", "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert set(spec) == {"name", "reader", "args", "about"}
    assert spec["name"] == name and spec["reader"] == "state_value"
    assert spec["args"] == {"key": KEYS[name], "scale": 0.001}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    assert entry == {
        "name": name, "unit": "s", "better": "lower",
        "source": "program_counter", "layer": "compile surface",
        "moves": "setup_s"}  # no ``workloads``: every cell, as warmup_s


@pytest.mark.parametrize("name", KEYS)
def test_reads_its_key_in_seconds_and_the_slowest_replicas(name):
    assert run.read_metric("layer", name, ctx(STATE)) == \
        pytest.approx(STATE[KEYS[name]] / 1e3)
    slower = {k: 2 * v for k, v in STATE.items()}
    assert run.read_metric("layer", name, ctx(STATE, slower)) == \
        pytest.approx(2 * STATE[KEYS[name]] / 1e3)


@pytest.mark.parametrize("name", KEYS)
def test_reads_nothing_from_a_parent_without_the_key(name):
    assert run.read_metric("layer", name, ctx(PARENT_STATE)) is None
    # while the accepted metric beside it still reads there
    assert run.read_metric("layer", "warmup_s", ctx(PARENT_STATE)) == \
        pytest.approx(13.8)


def test_a_late_load_of_zero_is_a_reading_not_a_silence():
    quiet = dict(STATE, xla_late_ms=0.0, xla_late_retrieval_ms=0.0)
    assert run.read_metric("layer", "late_load_s", ctx(quiet)) == 0.0
