"""``BENCHMARK.json`` against the benchmark's contract, and discovery of
configurations, mixes and metric readers by name alone."""

import json
import os
import re
import textwrap

import pytest

from perfbench import driver, generator, spec
from perfbench.stats import percentile

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_paths(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["command"] == ["python3", "perfbench/run.py"]
    assert doc["paths"] == ["perfbench"]
    assert isinstance(doc["run_seconds"], int) and \
        1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_every_name_unit_and_line_is_well_formed(doc):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in doc[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for e in doc["configs"] + doc["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]


def test_configs_are_used_files_under_paths(doc):
    used = {w["config"] for w in doc["workloads"]}
    files = set()
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"].startswith("perfbench/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert sorted(c["reduced"]) == sorted(conf["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) or "head" in k
                       for k in c["reduced"])
        assert conf["source"] == c["source"]


def test_cells_name_known_files_and_fit_their_engine(doc):
    pairs = set()
    bench = spec.Bench()
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        conf = bench.config(w["config"])
        mix = bench.traffic(w["traffic"])
        assert generator.max_context(mix) <= conf["engine"]["max_context_len"]
        assert bench.limits(w["name"])["widest_logit_gap"] > 0


def test_metrics_follow_the_contract(doc):
    cells = {w["name"] for w in doc["workloads"]}
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    layers = set()
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.add(m["layer"])
        assert os.path.exists(os.path.join(
            spec.HERE, "metrics", m["name"] + ".py"))
    for cell in cells:
        assert any(cell in m.get("workloads", cells)
                   for m in doc["per_layer"])
        assert len([m for m in doc["end_to_end"]
                    if cell in m.get("workloads", cells)]) >= 2


def test_a_new_mix_and_metric_are_found_by_name_alone(tmp_path):
    """A later cell brings its own files; nothing here is edited."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["workloads"].append({"name": "danube-dummy",
                             "config": "h2o-danube-1.8b",
                             "traffic": "dummy", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "dummy_metric", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "scheduler", "moves": "ttft_p50_s",
                             "workloads": ["danube-dummy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    cfg_dir = tmp_path / "perfbench" / "configs"
    cfg_dir.mkdir(parents=True)
    for c in doc["configs"]:
        (tmp_path / c["file"]).write_text(
            open(os.path.join(ROOT, c["file"])).read())
    for sub in ("traffic", "metrics", "limits"):
        (tmp_path / sub).mkdir()
    mix = {"arrivals": {"kind": "poisson", "rate_per_s": 3.0},
           "lead_in_s": 2, "drain_s": 5,
           "prompt": {"kind": "unique",
                      "len": {"dist": "uniform", "min": 10, "max": 20}},
           "output": {"dist": "uniform", "min": 2, "max": 4},
           "check": {"min_tokens": 4, "max_requests": 2}}
    (tmp_path / "traffic" / "dummy.json").write_text(json.dumps(mix))
    (tmp_path / "limits" / "danube-dummy.json").write_text(
        json.dumps({"widest_logit_gap": 1.0}))
    (tmp_path / "metrics" / "dummy_metric.py").write_text(textwrap.dedent(
        """
        def read(run):
            return 1e3 * run.window_s
        """))
    bench = spec.Bench(root=str(tmp_path), base=str(tmp_path))
    cell = bench.cell("danube-dummy")
    got = bench.traffic(cell["traffic"])
    specs = generator.Traffic(got, 1, 32000).open_loop(4.0)
    assert len(specs) == 6 + 12
    names = [m["name"] for m in bench.metrics("per_layer", "danube-dummy")]
    assert "dummy_metric" in names and "prefix_hit_share" not in names
    run = driver.Run({}, {}, 4.0, [], [], {})
    assert bench.reader("dummy_metric")(run) == 4000.0
    # the shipped readers are found from there too, and read nothing here
    assert bench.reader("decode_step_ms")(run) is None
    assert percentile([bench.limits("danube-dummy")["widest_logit_gap"]],
                      50) == 1.0
