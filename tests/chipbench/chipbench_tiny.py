"""A benchmark root at a size a CPU test can hold: the mamba2-370m cell's
files at 4 layers of width 128 (the program's reduced mamba2 otherwise),
2 rows of 64 tokens a step."""
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "tiny.train"
TINY_MODEL = dict(name="tiny", num_layers=4, d_model=128, vocab_size=97,
                  ssm_state=16, ssm_head_dim=16, ssm_chunk=8,
                  vocab_pad_multiple=8, gen_feature_dim=8, remat=False)
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def make_root(tmp: Path, **model) -> Path:
    """Copy of the benchmark's own files for ``mamba2-370m.train_2k`` under
    ``tmp``, shrunk to the tiny widths (``model`` overrides them)."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    src = REPO / "chipbench"
    dst = tmp / "chipbench"
    (dst / "configs").mkdir(parents=True, exist_ok=True)
    (dst / "mixes").mkdir(parents=True, exist_ok=True)
    shutil.copytree(src / "metrics", dst / "metrics", dirs_exist_ok=True)
    shutil.copy(src / "configs" / "mamba2-370m.py", dst / "configs/tiny.py")
    cfg = json.loads((src / "configs/mamba2-370m.json").read_text())
    cfg["model"].update(TINY_MODEL, **model)
    (dst / "configs/tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((src / "mixes/train_2k.json").read_text())
    mix.update(batch=2, seq_len=64, pool=3)
    (dst / "mixes/tiny_train.json").write_text(json.dumps(mix))
    entry = next(c for c in bench["configs"] if c["name"] == "mamba2-370m")
    bench["configs"] = [dict(entry, name="tiny",
                             file="chipbench/configs/tiny.json")]
    cell = next(w for w in bench["workloads"]
                if w["name"] == "mamba2-370m.train_2k")
    bench["workloads"] = [dict(cell, name=CELL, config="tiny",
                               traffic="tiny_train")]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "mamba2-370m.train_2k" in m.get("workloads", [CELL]):
            m["workloads"] = [CELL]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run_cell(root: Path, capsys, seed: int = 5, seconds: float = 0.5):
    """Drive a whole run past the chip check; returns (rc, result)."""
    from chipbench import run
    rc = run.main(["--workload", CELL, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0"],
                  root=root, require_chip=False, peaks=CPU_PEAKS)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
