"""The harness's data: BENCHMARK.json, cells, configurations, modes and
metric readers are found by name, and new ones by adding files alone."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def test_every_workload_file_is_a_cell_and_loads():
    assert sorted(CELLS) == harness.workload_names()
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    for cell in BENCHMARK["workloads"]:
        wl = harness.load_workload(cell["name"])
        assert wl["config"] == cell["config"] and wl["chips"] == cell["chips"]
        assert wl["why"] == cell["why"]
        assert (ROOT / "bench/modes" / f"{wl['mode']}.py").is_file()
        cfg = configs[cell["config"]]
        assert (ROOT / cfg["file"]).is_file()
        assert harness.load_config(cfg["name"])["source"] == cfg["source"]
        assert wl["limits"], "a cell compares at least one number"


def test_names_units_and_readers_are_legal():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCHMARK[kind]:
            assert harness.NAME_RE.match(entry["name"]), entry["name"]
    for w in BENCHMARK["workloads"]:
        assert harness.NAME_RE.match(w["traffic"]) and len(w["why"]) <= 200
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert harness.UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()


def test_every_cell_reports_what_the_contract_asks():
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    for cell in CELLS:
        ends = {m["name"] for m in harness.metrics_for(BENCHMARK, cell, False)}
        assert "setup_s" in ends and len(ends) >= 2
        layers = harness.metrics_for(BENCHMARK, cell, True)
        assert layers
        for m in layers:
            assert m["moves"] in ends, (cell, m["name"])
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e


def test_new_cell_config_mode_and_metric_are_found_by_adding_files(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench)
    (bench / "configs" / "tiny-vgg.json").write_text(json.dumps(
        dict(harness.load_config("vgg16-cifar10"), name="tiny-vgg")))
    (bench / "modes" / "train_q8.py").write_text("def run(ctx):\n    return None\n")
    (bench / "workloads" / "tiny-vgg.train.q8.json").write_text(json.dumps({
        "config": "tiny-vgg", "mode": "train_q8", "chips": 1,
        "traffic": {"clients": 4, "edges": 2, "batch": 2, "samples": 64},
        "why": "a cell added by a file", "limits": {"loss_gap": 0.01}}))
    (bench / "metrics" / "codec_share.py").write_text(
        "def read(rec):\n    return rec.counters.get('codec_s')\n")
    assert "tiny-vgg.train.q8" in harness.workload_names(bench)
    wl = harness.load_workload("tiny-vgg.train.q8", bench)
    assert wl["config_data"]["name"] == "tiny-vgg"
    assert harness.mode_module("train_q8", bench).run(None) is None
    rec = SimpleNamespace(counters={"codec_s": 2.5})
    spec = [{"name": "codec_share", "unit": "%"}, {"name": "setup_s", "unit": "s"}]
    rec.setup_s = None  # a reader that finds nothing is left out
    assert harness.read_metrics(spec, rec, bench) == {
        "codec_share": {"value": 2.5, "unit": "%"}}


def test_bad_cells_are_refused(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench)
    with pytest.raises(harness.BenchError):
        harness.load_workload("no.such.cell", bench)
    with pytest.raises(harness.BenchError):
        harness.load_workload("bad name", bench)
    (bench / "workloads" / "x.json").write_text(json.dumps(
        {"config": "vgg16-cifar10", "mode": "nope", "chips": 1, "traffic": {}, "why": "x",
         "limits": {}}))
    with pytest.raises(harness.BenchError, match="no mode"):
        harness.load_workload("x", bench)


def test_seeds_of_any_size():
    seeds = [0, 1, 2**31 - 1, 2**31 + 7, 2**32 + 1, 2**40]
    derived = [harness.derive_seed(s) for s in seeds]
    assert len(set(derived)) == len(seeds)
    assert all(0 <= d < 2**31 for d in derived)
    assert harness.derive_seed(2**40) == harness.derive_seed(2**40)


def _run(cwd, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("cell", ["vgg16.train.paper", "smollm135m.train.seq256"])
def test_without_a_tpu_no_result(cell):
    p = _run(ROOT, cell)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_no_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copytree(ROOT / "tests/hsfl_bench", tmp_path / "tests/hsfl_bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path, "vgg16.train.paper")
    assert p.returncode != 0 and p.stdout.strip() == ""
