"""``BENCHMARK.json`` against the benchmark's contract, and the command
with no TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "bench/run.py"]
    assert manifest["paths"] == ["bench"]
    assert 1 <= manifest["run_seconds"] <= 51
    # a full check of 24 cells fits its 43200 seconds
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) \
        + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
    for group in ("configs", "workloads"):
        seen = [e["name"] for e in manifest[group]]
        assert len(seen) == len(set(seen))
    metric_names = [e["name"] for g in ("end_to_end", "per_layer")
                    for e in manifest[g]]
    assert len(metric_names) == len(set(metric_names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for c in manifest["configs"]:
        assert _line(c["source"]) and _line(c["why"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _line(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in manifest["per_layer"]:
        assert _line(m["layer"])
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert len(json.dumps(manifest)) <= 64 * 1024


def test_end_to_end_metrics(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert {"round_s", "peak_hbm_gib", "setup_s"} <= set(e2e)
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] <= 0.25


def test_every_cell_finds_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    pairs = set()
    for w in manifest["workloads"]:
        assert w["config"] in configs
        used.add(w["config"])
        pairs.add((w["config"], w["traffic"]))
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", f"{w['traffic']}.json"))
    assert len(pairs) == len(manifest["workloads"])
    assert used == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        assert c["file"] == f"bench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) \
        <= max(1, len(manifest["workloads"]) // 2)


def test_per_layer_metrics_have_readers_and_cells(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py"))
        for w in m.get("workloads", []):
            assert w in cells
            # the cell reports the end-to-end metric this one moves
            assert w in e2e[m["moves"]].get("workloads", cells)
    for w in cells:
        assert any("workloads" not in m or w in m["workloads"]
                   for m in manifest["per_layer"])


def test_every_config_names_a_model_kind(manifest):
    sys.path.insert(0, BENCH)
    import run
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        kind = run.config_kind(config)
        for m in run.model_dicts(config, kind):
            assert {"vocab_size", "seq_len", "n_classes"} <= set(m)
            assert kind.forward_flops_per_token(m) > 0


def test_four_chip_cell_and_its_collectives(manifest):
    cells = {w["name"]: w for w in manifest["workloads"]}
    x4 = cells["distilbert.feddf.x4"]
    assert x4["chips"] == 4 and x4["config"] == "distilbert"
    with open(os.path.join(BENCH, "traffic", f"{x4['traffic']}.json")) as f:
        job = json.load(f)
    active = round(job["client_fraction"] * job["n_clients"])
    assert active == 16 and active % x4["chips"] == 0
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    coll = metrics["collective_ms"]
    assert coll["workloads"] == ["distilbert.feddf.x4"]
    assert coll["layer"] == "collectives" and coll["moves"] == "round_s"
    for name in ("collective_ms.py", "collective_ms.json"):
        assert os.path.isfile(os.path.join(BENCH, "metrics", name))


def test_c8_cell_is_the_cohort_one_chip_holds(manifest):
    cells = {w["name"]: w for w in manifest["workloads"]}
    c8 = cells["distilbert.feddf.c8"]
    assert c8["chips"] == 1 and c8["config"] == "distilbert"
    jobs = {}
    for name in (c8["traffic"], cells["distilbert.feddf"]["traffic"]):
        with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
            jobs[name] = json.load(f)
    job, base = jobs[c8["traffic"]], jobs["feddf"]
    assert {k for k in set(job) | set(base) if job.get(k) != base.get(k)} \
        == {"about", "n_clients", "n_samples"}
    assert round(job["client_fraction"] * job["n_clients"]) == 8
    rows = lambda j: j["n_samples"] * (1 - j["val_frac"] - j["test_frac"]) \
        / j["n_clients"]
    assert rows(job) == pytest.approx(rows(base))  # 840 rows a client
    for m in manifest["per_layer"]:
        assert (c8["name"] in m["workloads"]) == (m["name"] !=
                                                    "collective_ms")


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_exits_without_a_chip(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147483999", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "run.py: the cell needs" in proc.stderr
    assert proc.stdout.strip() == ""


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "distilbert.feddf", "--seed", "2147483999", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
