"""Entry points fail loudly: the chip smoke without a TPU or outside a
checkout, the serving launcher when a request errors, and where the
launchers keep the compile cache."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _run_smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_fails_without_a_tpu():
    proc = _run_smoke(ROOT)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_fails_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_compile_cache_leaves_env_dir_alone(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []


def test_compile_cache_defaults_to_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]


def test_serve_launcher_exits_nonzero_when_a_request_errors(monkeypatch,
                                                            capsys):
    from repro.launch import serve
    from repro.serve import Engine

    def failing_prefill(self, handles):
        raise RuntimeError("RESOURCE_EXHAUSTED: prefill does not fit")

    monkeypatch.setattr(Engine, "_launch_prefill", failing_prefill)
    with pytest.raises(SystemExit) as exc:
        serve.main(["--arch", "mamba2-370m", "--batch", "2",
                    "--prompt-len", "4", "--gen", "2"])
    assert exc.value.code                   # a message: exit status 1
    out = capsys.readouterr().out
    assert out.count("RESOURCE_EXHAUSTED") == 2


def test_train_launcher_installs_a_given_generator():
    """``gen_fit_fn`` replaces the fit at ``--gen-warmup``: two runs can
    share one fitted generator."""
    from repro.launch import train
    args = ["--arch", "mamba2-370m", "--batch", "2", "--seq", "16",
            "--steps", "3", "--gen-warmup", "1"]
    first = train.main(args)
    fitted = first["state"].head_state
    want = jax.device_get(fitted)   # the run donates what it installs
    seen = []

    def give_fitted(state):
        seen.append(int(state.step))
        return fitted

    second = train.main(args, gen_fit_fn=give_fitted)
    assert seen == [1] and second["gen_fit_step"] == 1
    for a, b in zip(jax.tree.leaves(second["state"].head_state),
                    jax.tree.leaves(want)):
        assert (a == b).all()
    assert [m["loss"] for m in second["step_metrics"]] == second["losses"]
    assert all(set(m) >= {"loss", "grad_norm"}
               for m in second["step_metrics"])
