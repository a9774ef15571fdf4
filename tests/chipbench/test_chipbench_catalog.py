"""Configurations, traffic mixes, checks and metric readers are files the
harness finds by name: adding one edits no file that is there."""

import json

import pytest
from tinybench import run_tiny

from chipbench import catalog


def test_every_cell_of_the_benchmark_finds_its_files():
    bench = catalog.load_benchmark()
    for w in bench["workloads"]:
        cell = catalog.find_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.checks is not None, w["name"]
        cell.counts(), cell.reference()
        assert callable(cell.step().program_config)
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cell.reader(m["name"]).read)
    names = [c["name"] for c in bench["configs"]]
    for c in bench["configs"]:
        cfg = json.loads((catalog.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
    assert len(set(names)) == len(names)


def test_unknown_names_are_refused():
    with pytest.raises(catalog.CatalogError):
        catalog.find_cell("no.such.cell")
    with pytest.raises(catalog.CatalogError):
        catalog.load_json("configs", "../BENCHMARK")


def test_an_added_cell_and_metric_run_with_no_edit(bench_copy):
    """A new configuration, traffic mix, cell and metric, added as new files
    and entries to a copy, show up in the run's line."""
    bdir = bench_copy / "chipbench"
    before = {p: p.read_bytes() for p in bdir.rglob("*") if p.is_file()}
    cfg = json.loads((bdir / "configs" / "tiny-gqa.json").read_text())
    cfg.update(name="tiny-gqa-3L", num_hidden_layers=3)
    (bdir / "configs" / "tiny-gqa-3L.json").write_text(json.dumps(cfg))
    traffic = json.loads((bdir / "traffic" / "tiny_closed2.json").read_text())
    traffic["arrivals"]["clients"] = 3
    (bdir / "traffic" / "tiny_closed3.json").write_text(json.dumps(traffic))
    (bdir / "checks" / "tiny3.closed3.json").write_text(
        json.dumps({"widest_logit_gap": 0.03}))
    (bdir / "metrics" / "jobs_done_per_s.py").write_text(
        "def read(ctx):\n"
        "    return sum(r.finished is not None for r in ctx.jobs)"
        " / ctx.seconds\n")
    bench = json.loads((bench_copy / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny3.closed3",
                               "config": "tiny-gqa-3L",
                               "traffic": "tiny_closed3", "chips": 1,
                               "why": "added"})
    bench["end_to_end"].append({"name": "jobs_done_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["tiny3.closed3"]})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(bench))

    out = run_tiny(bench_copy, "tiny3.closed3", seconds=0.5)
    assert out.line["metrics"]["jobs_done_per_s"]["value"] > 0
    assert out.context.cell.config["num_hidden_layers"] == 3
    assert all(p.read_bytes() == b for p, b in before.items())
    other = run_tiny(bench_copy, "tiny.closed2", seconds=0.5)
    assert "jobs_done_per_s" not in other.line["metrics"]
