"""The harness finds cells by name and refuses to run without a chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as bench_run  # noqa: E402


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves_to_its_files():
    bench = _bench()
    for w in bench["workloads"]:
        loaded = bench_run.load_cell(bench, w["name"])
        assert loaded["traffic"]["generator"]
        gen = bench_run.generator_module(loaded["traffic"])
        for fn in ("setup", "window", "end_to_end", "attempted_failed",
                   "release", "check"):
            assert callable(getattr(gen, fn)), (w["name"], fn)
        names = {m["name"] for m in loaded["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert loaded["per_layer"], w["name"]
        for m in loaded["per_layer"]:
            assert callable(bench_run.layer_reader(m["name"]).read)


def test_a_cell_added_as_files_only_is_found(tmp_path):
    """A later PR adds a configuration, a traffic mix and a per-layer
    metric as new files and entries, and edits no file of the harness."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _bench()
    with open(os.path.join(ROOT, "bench", "configs",
                           "mlp_paper.json")) as f:
        cfg = json.load(f)
    cfg.update(name="mlp_wide", U=40)
    (root / "bench" / "configs" / "mlp_wide.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "grid_small.json").write_text(json.dumps(
        {"generator": "grid", "policies": ["inflota"], "seeds_per_policy": 4,
         "rounds": 30, "data_seed": 1}))
    (root / "bench" / "layer_metrics" / "grids_in_window.small.py"
     ).write_text("def read(ctx):\n    return float(ctx.window['grids'])\n")
    bench["configs"].append({"name": "mlp_wide", "source": "x",
                             "file": "bench/configs/mlp_wide.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "mlp_wide.grid_small",
                               "config": "mlp_wide",
                               "traffic": "grid_small", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "grid_exp_rounds_per_s":
            m["workloads"].append("mlp_wide.grid_small")
    bench["per_layer"].append({
        "name": "grids_in_window.small", "unit": "grids", "better": "higher",
        "source": "host_clock", "layer": "sweep cohorts (sweep/grid.py)",
        "moves": "grid_exp_rounds_per_s",
        "workloads": ["mlp_wide.grid_small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    loaded = bench_run.load_cell(bench, "mlp_wide.grid_small",
                                 root=str(root))
    assert loaded["config"]["U"] == 40
    assert loaded["traffic"]["seeds_per_policy"] == 4
    assert [m["name"] for m in loaded["per_layer"]] == [
        "grids_in_window.small"]
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "grid_exp_rounds_per_s", "setup_s"}
    gen = bench_run.generator_module(loaded["traffic"], root=str(root))
    assert gen.__file__.startswith(str(root))
    reader = bench_run.layer_reader("grids_in_window.small", root=str(root))

    class Ctx:
        window = {"grids": 3}
    assert reader.read(Ctx) == 3.0


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mlp_paper.grid",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode == 1, p.stderr
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_without_the_system(tmp_path):
    """A directory that holds only the benchmark's files runs nothing."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mlp_paper.grid",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("name", ["mlp_paper", "linreg_paper"])
def test_config_files_state_their_source_and_limits(name):
    bench = _bench()
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == name
    assert cfg["reduced"] == entry["reduced"]
    assert os.path.exists(os.path.join(ROOT, cfg["reference"]))
    assert cfg["limits"] and all(v > 0 for v in cfg["limits"].values())
