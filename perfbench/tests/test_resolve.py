"""A further cell resolves from new files and entries alone."""

import json
import os
import shutil

from perfbench import run

ROOT = run.ROOT


def _snapshot(root):
    out = {}
    for dirpath, _, files in os.walk(os.path.join(root, "perfbench")):
        for f in files:
            if "__pycache__" not in dirpath:
                p = os.path.join(dirpath, f)
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, root)] = fh.read()
    return out


def test_every_cell_resolves():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = run.resolve(bench, w["name"])
        assert cell.config["name"] == w["config"]
        assert set(cell.limits) and callable(cell.loop.window)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer and set(cell.readers) == {m["name"] for m in cell.per_layer}


def test_new_cell_from_new_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "perfbench"), os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _snapshot(root)
    bench_dir = os.path.join(root, "perfbench")

    config = json.load(open(os.path.join(bench_dir, "configs", "w8a.json")))
    config.update(name="phishing", d=69, n_i=77)
    json.dump(config, open(os.path.join(bench_dir, "configs", "phishing.json"), "w"))
    traffic = json.load(open(os.path.join(bench_dir, "traffic", "solve_topk.json")))
    traffic.update(tol=1e-9)
    json.dump(traffic, open(os.path.join(bench_dir, "traffic", "solve_topk_loose.json"), "w"))
    json.dump({"x_rel": 1e-9}, open(os.path.join(bench_dir, "limits", "phishing-solve.json"), "w"))
    with open(os.path.join(bench_dir, "metrics", "solves_per_window.solve.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.counters['solves'])\n")

    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "phishing", "source": "https://arxiv.org/abs/2410.08760",
                             "file": "perfbench/configs/phishing.json", "reduced": [],
                             "why": "d = 69"})
    bench["workloads"].append({"name": "phishing-solve", "config": "phishing",
                               "traffic": "solve_topk_loose", "chips": 1, "why": "d = 69"})
    bench["per_layer"].append({"name": "solves_per_window.solve", "unit": "1",
                               "better": "higher", "source": "host_clock", "layer": "api",
                               "moves": "solve_s", "workloads": ["phishing-solve"]})

    cell = run.resolve(bench, "phishing-solve", root=root)
    assert cell.config["d"] == 69 and cell.traffic["tol"] == 1e-9
    assert cell.limits == {"x_rel": 1e-9}
    assert "solves_per_window.solve" in cell.readers
    ctx = run.Context(run.Spans(), {"solves": 3}, None, None, None)
    assert cell.readers["solves_per_window.solve"].read(ctx) == 3.0
    after = _snapshot(root)
    assert all(after[p] == b for p, b in before.items())  # nothing edited
