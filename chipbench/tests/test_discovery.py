"""A mix, a cell and a metric added as files are found by name, with no
edit to the harness's code."""
import json
import shutil
from pathlib import Path

import cell as cellmod
import layer

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent


def test_new_files_are_found_by_name(tmp_path):
    base = tmp_path / "chipbench"
    shutil.copytree(HERE, base, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((base / "mixes" / "slice_mix.json").read_text())
    mix["name"] = "probe_mix"
    mix["classes"] = [c for c in mix["classes"] if c["kind"] == "qa"]
    (base / "mixes" / "probe_mix.json").write_text(json.dumps(mix))
    (base / "cells" / "smollm-360m.probe_mix.json").write_text(
        json.dumps({"rate_per_s": 0.25, "limits": {"max_gap": 1.0,
                                                    "mean_gap": 0.1}}))
    (base / "metrics" / "probe_metric.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx is not None else None\n")
    bench["workloads"].append({"name": "smollm-360m.probe_mix",
                               "config": "smollm-360m",
                               "traffic": "probe_mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "probe_metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "kernels", "moves": "tpot_p90_ms",
                               "workloads": ["smollm-360m.probe_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = cellmod.load("smollm-360m.probe_mix", root=tmp_path, base=base)
    assert c.rate_per_s == 0.25 and c.mix["name"] == "probe_mix"
    assert [m["name"] for m in c.per_layer] == ["probe_metric"]
    assert layer.reader("metrics", "probe_metric", base)(object()) == 42.0
    old = cellmod.load("smollm-360m.slice_mix", root=tmp_path, base=base)
    assert "probe_metric" not in [m["name"] for m in old.per_layer]


def test_every_named_metric_has_its_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(layer.reader("metrics", m["name"]))
    for m in bench["end_to_end"]:
        assert callable(layer.reader("e2e", m["name"]))
    for w in bench["workloads"]:
        c = cellmod.load(w["name"])
        assert c.chips == c.config["chips"] == w["chips"]


def test_benchmark_json_keeps_its_shape():
    import re
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for group, want in keys.items():
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        for e in bench[group]:
            assert set(e) - {"workloads"} == want, (group, e["name"])
            assert name.match(e["name"])
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
            if "unit" in e:
                assert unit.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    cells = {w["name"]: w for w in bench["workloads"]}
    for w in cells.values():
        assert w["chips"] in (1, 4)
        got = [m for m in e2e.values()
               if w["name"] in m.get("workloads", [w["name"]])]
        assert len(got) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for c in m.get("workloads", cells):
            assert c in e2e[m["moves"]].get("workloads", cells)
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 2)
    # a full check of 24 cells fits the check's time
    rs = bench["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
