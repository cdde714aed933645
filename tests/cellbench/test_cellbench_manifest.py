"""``BENCHMARK.json`` against the contract and against the files it
names: every name has its file, names and units use only the allowed
characters, each per-layer metric moves a metric its cells report, and
each mix's lead-in tour reaches every program shape its lengths can."""

import importlib
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    M = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(
    r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim"
    r"|expansion|num_experts_per_tok")


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def cells_of(metric):
    return metric.get("workloads") or [w["name"] for w in M["workloads"]]


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["cellbench", "tests/cellbench"]
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in M["paths"])
    assert 1 <= len(M["command"]) <= 32
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["configs"]) <= 24 and 1 <= len(M["workloads"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16 and 1 <= len(M["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 4)


def test_command_names_only_files_under_paths():
    for word in M["command"][1:]:
        if os.path.exists(os.path.join(REPO, word)):
            assert any(word.startswith(p + "/") for p in M["paths"])


@pytest.mark.parametrize("kind,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
])
def test_entries_have_exactly_the_contracts_keys(kind, keys):
    names = [e["name"] for e in M[kind]]
    assert len(names) == len(set(names))
    for e in M[kind]:
        assert set(e) - {"workloads"} == keys, e["name"]
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k], (e["name"], k)


@pytest.mark.parametrize("metric", M["end_to_end"] + M["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_fields(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    known = {w["name"] for w in M["workloads"]}
    assert set(cells_of(metric)) <= known
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_moves_a_metric_its_cells_report(metric):
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert metric["moves"] in e2e
    assert set(cells_of(metric)) <= set(cells_of(e2e[metric["moves"]]))


@pytest.mark.parametrize("kind,folder", [("end_to_end", "e2e_metrics"),
                                         ("per_layer", "layer_metrics")])
def test_every_metric_has_its_file_and_its_reader(kind, folder):
    # one file a QUANTITY: ``<quantity>.<variant>`` reads ``<quantity>``'s
    on_disk = {f[:-5] for f in os.listdir(
        os.path.join(REPO, "cellbench", folder)) if f.endswith(".json")}
    assert on_disk == {m["name"].split(".", 1)[0] for m in M[kind]}
    for m in M[kind]:
        quantity = m["name"].split(".", 1)[0]
        spec = load("cellbench", folder, quantity + ".json")
        assert spec["name"] == quantity
        reader = importlib.import_module(
            "cellbench.readers." + spec["reader"])
        assert callable(reader.read)


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = [m["name"] for m in M["end_to_end"] if cell["name"] in cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell["name"] in cells_of(m) for m in M["per_layer"])
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    assert cell["config"] in {c["name"] for c in M["configs"]}
    assert os.path.exists(os.path.join(
        REPO, "cellbench", "traffic", cell["traffic"] + ".json"))


@pytest.mark.parametrize("entry", M["configs"], ids=lambda c: c["name"])
def test_configuration_file(entry):
    assert any(entry["file"].startswith(p + "/") for p in M["paths"])
    assert PATH.match(entry["file"])
    assert any(w["config"] == entry["name"] for w in M["workloads"])
    assert len({c["file"] for c in M["configs"]}) == len(M["configs"])
    doc = load(entry["file"])
    cb = doc["cellbench"]
    assert cb["name"] == entry["name"] and cb["source"] == entry["source"]
    assert cb["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
        assert key in cb["assumed"], f"{key}: say how it was cut"
    assert os.path.exists(os.path.join(
        REPO, "cellbench", "module_groups", cb["module_groups"] + ".json"))
    assert cb["replicas"] * 1 == cb["chips"] or cb["replicas"] == 1
    for w in M["workloads"]:
        if w["config"] == entry["name"]:
            assert w["chips"] == cb["chips"]


def test_published_widths():
    q = load("cellbench/configs/qwen2-7b-1chip.json")
    assert (q["hidden_size"], q["intermediate_size"], q["num_hidden_layers"],
            q["num_attention_heads"], q["num_key_value_heads"],
            q["vocab_size"], q["rope_theta"]) == \
        (3584, 18944, 28, 28, 4, 152064, 1e6)
    m = load("cellbench/configs/mixtral-8x7b-1chip.json")
    assert (m["hidden_size"], m["intermediate_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["num_local_experts"], m["num_experts_per_tok"],
            m["vocab_size"], m["rope_theta"]) == \
        (4096, 14336, 32, 8, 8, 2, 32000, 1e6)


def test_every_file_under_paths_has_a_contract_name():
    for root in M["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, root)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert PATH.match(rel), rel


# -- the lead-in tour covers what the mix's lengths can reach ------------

#: tokens the chat templates add around one user message (llama3 22,
#: chatml about as many); the tour must hold over this whole range
TEMPLATE = range(14, 33)


def _flag(flags, name, default):
    return int(flags[flags.index(name) + 1]) if name in flags else default


def _shapes(n_prompt, n_out, flags):
    """(decode page bucket, prefill tail (S, page bucket) or None) of a
    request, as tpuserve/engine.py buckets it: pow2 pages covering
    prompt + answer; prompts over the chunk size run [1,chunk] steps
    and a tail padded to the pow2 ladder from 64."""
    page = _flag(flags, "--page-size", 128)
    chunk = _flag(flags, "--prefill-chunk-tokens", 256)
    cap = _flag(flags, "--max-seq-len", 2048)
    pages = -(-min(n_prompt + n_out, cap) // page)
    bucket = 1
    while bucket < pages:
        bucket *= 2
    if n_prompt <= chunk:
        return bucket, None
    tail = n_prompt - chunk * ((n_prompt - 1) // chunk)
    S = 64
    while S < tail:
        S *= 2
    return bucket, (S, bucket)


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_tour_reaches_every_shape_the_mix_can(cell):
    cfg = load(named_file(cell["config"]))["cellbench"]
    mix = load("cellbench", "traffic", cell["traffic"] + ".json")
    flags = cfg["serve_flags"] + mix.get("serve_flags", [])
    assert _flag(flags, "--prefill-bucket-rungs", 2) == 1
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    need_decode, need_tail = set(), set()
    for t in (TEMPLATE[0], TEMPLATE[-1]):
        for n in range(p["min"] + t, p["max"] + t + 1):
            for out in (o["min"], o["max"]):
                d, tail = _shapes(n, out, flags)
                need_decode.add(d)
                if tail:
                    need_tail.add(tail)
                    # chunks run at the same page bucket as the tail
                    need_tail.add((256, tail[1]))
    cap = _flag(flags, "--max-seq-len", 2048)
    assert p["max"] + TEMPLATE[-1] + o["max"] <= cap
    # what every template length in range agrees the tour covers
    have_decode, have_tail, have_joined = None, None, None
    for t in TEMPLATE:
        d_t, tail_t, joined_t = set(), set(), set()
        for step in mix["lead_in"]["tour"]:
            for content, out, *_ in step:
                d, tail = _shapes(content + t, out, flags)
                d_t.add(d)
                # both windows of the adaptive pair: young, then steady
                assert out >= 40
                if tail:
                    tail_t.add(tail)
            if len(step) > 1:
                # a later request joins the first while it decodes: the
                # row-update program at the first one's page bucket
                first = _shapes(step[0][0] + t, step[0][1], flags)[0]
                assert all(r[2] > 0 for r in step[1:])
                assert all(_shapes(r[0] + t, r[1], flags)[0] <= first
                           for r in step[1:])
                assert step[0][1] >= 100   # still decoding when joined
                joined_t.add(first)
        have_decode = d_t if have_decode is None else have_decode & d_t
        have_tail = tail_t if have_tail is None else have_tail & tail_t
        have_joined = joined_t if have_joined is None \
            else have_joined & joined_t
    assert need_decode - {1} <= have_decode   # bucket 1 is warm-up's
    assert need_decode - {1} <= have_joined
    assert need_tail <= have_tail
    if any(n <= 256 for n in (p["min"] + TEMPLATE[0],)):
        # prompts that fit one chunk use the batched [G,S] programs:
        # 64, 128 and 256 are the three smallest rungs
        assert _flag(flags, "--warm-prefill-buckets", 0) == 3


def named_file(config_name):
    return next(c["file"] for c in M["configs"] if c["name"] == config_name)
