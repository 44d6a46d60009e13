"""A later change adds a configuration, a traffic mix, a per-layer metric and
a cell as new files and entries only: the harness finds them by name.

The listing this test walks through, in a copy of the benchmark:

1. ``configs/<config>.json``, and a ``configs`` entry naming it;
2. ``traffic/<mix>.json``, whose ``loop`` names an existing loop;
3. ``metrics/<metric>.py`` with ``read(ctx)``, and a ``per_layer`` entry;
4. a ``workloads`` entry pairing the configuration with the mix.

No file that is already there changes."""
import hashlib
import json
import shutil

from perfbench import core


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_fifth_cell_third_config_new_metric_are_files_and_entries(tmp_path):
    shutil.copy(core.ROOT / "BENCHMARK.json", tmp_path)
    bench = tmp_path / "perfbench"
    shutil.copytree(core.BENCH_DIR, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(tmp_path)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())

    cfg = json.loads((bench / "configs" / "paper_sufficient.json").read_text())
    cfg["name"] = "tenant8"
    cfg["apps"] = cfg["apps"] + [dict(a, name=a["name"] + "-b") for a in cfg["apps"]]
    cfg["caps"] = {"r_cpu": 240.0, "r_mem": 80.0}
    (bench / "configs" / "tenant8.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "replan_drift.json").read_text())
    mix["threshold"] = 0.05
    (bench / "traffic" / "replan_tight.json").write_text(json.dumps(mix))
    (bench / "metrics" / "decisions.per_s.py").write_text(
        "def read(ctx):\n    return ctx.counters['requests'] / ((ctx.hi - ctx.lo) / 1e9)\n")

    spec["configs"].append({"name": "tenant8", "source": "https://example.org/tenant8",
                            "file": "perfbench/configs/tenant8.json", "reduced": [],
                            "why": "an 8-tenant node"})
    spec["workloads"].append({"name": "tenant8.replan_tight", "config": "tenant8",
                              "traffic": "replan_tight", "chips": 1, "why": "tight threshold"})
    spec["per_layer"].append({"name": "decisions.per_s", "unit": "1/s", "better": "higher",
                              "source": "host_clock", "layer": "Policy API",
                              "moves": "replan_p50_ms", "workloads": ["tenant8.replan_tight"]})
    for m in spec["end_to_end"]:
        if m["name"].startswith("replan_"):
            m["workloads"].append("tenant8.replan_tight")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = core.resolve(json.loads((tmp_path / "BENCHMARK.json").read_text()),
                        "tenant8.replan_tight", tmp_path)
    assert len(cell.config["apps"]) == 8 and cell.traffic["threshold"] == 0.05
    assert cell.loop.__name__.endswith("replan")
    assert "decisions.per_s" in cell.readers
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "replan_p50_ms", "replan_p95_ms"}

    after = _digest(tmp_path)
    changed = {p for p in before if before[p] != after[p]}
    assert changed == {type(next(iter(before)))("BENCHMARK.json")}  # entries only
    assert set(after) - set(before) == {
        p.relative_to(tmp_path) for p in (bench / "configs" / "tenant8.json",
                                          bench / "traffic" / "replan_tight.json",
                                          bench / "metrics" / "decisions.per_s.py")}
