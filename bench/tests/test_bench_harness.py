"""The harness finds a cell's parts by name, and BENCHMARK.json keeps to
the benchmark's contract."""
import json
import re
import shutil

import pytest

from bench import harness
from bench.tests import tiny

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def test_new_config_mix_and_metric_are_files_only(tmp_path):
    """A configuration, a mix and a metric added as new files plus new
    entries: the harness finds and runs them, and no file it had changed."""
    before = {p: p.read_bytes() for p in harness.BENCH_DIR.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    root = tmp_path
    shutil.copytree(harness.BENCH_DIR / "configs", root / "bench" / "configs")
    for sub in ("traffic", "metrics"):
        (root / "bench" / sub).mkdir(parents=True)
    cfg = json.loads((harness.BENCH_DIR / "configs" / "sift1m.json")
                     .read_text())
    cfg.update(name="deep1m", d=96, n=2000)
    (root / "bench" / "configs" / "deep1m.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "small_batch.json").write_text(json.dumps(
        {"mode": "closed_loop", "batch": 32, "batches": 2, "k": 10,
         "ef": 64, "visited": "hashed"}))
    (root / "bench" / "metrics" / "search.calls.small.py").write_text(
        "def read(run):\n    return float(len(run.counters['calls']))\n")
    bench = dict(BENCH)
    bench["configs"] = BENCH["configs"] + [dict(
        BENCH["configs"][0], name="deep1m", file="bench/configs/deep1m.json")]
    bench["workloads"] = BENCH["workloads"] + [dict(
        name="deep1m.small_batch", config="deep1m", traffic="small_batch",
        chips=1, why="test")]
    bench["per_layer"] = BENCH["per_layer"] + [dict(
        name="search.calls.small", unit="calls", better="higher",
        source="host_clock", layer="search", moves="query_qps",
        workloads=["deep1m.small_batch"])]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    s = harness.resolve("deep1m.small_batch", root)
    assert s.config["d"] == 96 and s.traffic["visited"] == "hashed"
    assert [m["name"] for m in s.per_layer] == ["search.calls.small"]

    class Run:
        counters = {"calls": [[0.0, 1.0], [1.0, 2.0]]}
    assert s.per_layer[0]["read"](Run()) == 2.0
    s.config["build"].update(chunk_size=1024, t1=2, t2=2)
    s.config["data"]["points_per_cluster"] = 400
    line = tiny.run(s)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"recall_at_10", "setup_s"}
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.resolve("no.such.cell")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_to_the_contract():
    b = BENCH
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    assert all((harness.ROOT / p).is_dir() for p in b["paths"])
    names = [c["name"] for c in b["configs"]]
    cells = {w["name"]: w for w in b["workloads"]}
    assert len(set(names)) == len(names) and len(cells) == len(b["workloads"])
    used = {w["config"] for w in b["workloads"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and NAME.match(c["name"])
        assert c["file"].startswith(b["paths"][0] + "/")
        assert (harness.ROOT / c["file"]).is_file()
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert (harness.BENCH_DIR / "traffic" / f"{w['traffic']}.json").is_file()
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {}
    for m in b["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        e2e[m["name"]] = set(m.get("workloads", cells))
    assert b["end_to_end"][-1]["name"] == "setup_s" or "setup_s" in e2e
    assert e2e["setup_s"] == set(cells)
    for m in b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e and set(m["workloads"]) <= e2e[m["moves"]]
        assert (harness.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    for cell in cells:
        others = [n for n, ws in e2e.items() if cell in ws and n != "setup_s"]
        assert others, cell
        assert any(cell in m["workloads"] for m in b["per_layer"]), cell
