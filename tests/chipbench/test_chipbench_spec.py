"""The harness finds every cell, configuration, mix and metric by name, and
refuses to run off a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import chipbench_tiny as tiny
from chipbench import spec

BENCH = spec.load_benchmark(tiny.REPO)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads(name):
    cell = spec.Cell(tiny.REPO, BENCH, name)
    assert cell.mix["kind"] in ("train",)
    assert hasattr(cell.reference, "make_weights")
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(cell.reader(m["name"]).read)


def test_contract_shape():
    assert BENCH["command"][:2] == ["python3", "chipbench/run.py"]
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for p in BENCH["paths"]:
        assert (tiny.REPO / p).is_dir()


def test_new_mix_file_is_found_without_an_edit(tmp_path):
    root = tiny.make_root(tmp_path)
    mix = json.loads((root / "chipbench/mixes/tiny_train.json").read_text())
    mix["seq_len"] = 64
    (root / "chipbench/mixes/tiny_long.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append(dict(bench["workloads"][0], name="tiny.long",
                                   traffic="tiny_long"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.Cell(root, spec.load_benchmark(root), "tiny.long")
    assert cell.mix["seq_len"] == 64


def test_unknown_names_are_refused(tmp_path):
    root = tiny.make_root(tmp_path)
    with pytest.raises(spec.SpecError):
        spec.Cell(root, spec.load_benchmark(root), "no.such.cell")
    (root / "chipbench/mixes/tiny_train.json").unlink()
    with pytest.raises(spec.SpecError):
        spec.Cell(root, spec.load_benchmark(root), tiny.CELL)


ARGS = ["--workload", "mamba2-370m.train_2k", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, cmd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_run_off_a_tpu_exits_nonzero_without_a_result():
    proc = _run(tiny.REPO, [sys.executable, "chipbench/run.py"] + ARGS)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(tiny.REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    # Past the chip check (which would stop it first here), the run finds
    # no program to measure.
    script = ("import sys; sys.path.insert(0, '.'); "
              "from chipbench import run; "
              f"sys.exit(run.main({ARGS!r}, require_chip=False, "
              "peaks={'bf16_flops_per_s': 1.0}))")
    proc = _run(tmp_path, [sys.executable, "-c", script])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "repro" in proc.stderr
