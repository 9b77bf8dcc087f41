"""The comparison that decides ``correct``, driven on the CPU at a tiny
size: a sound run passes, and the control and each planted fault of a
training cell fail it."""
import pytest

import chipbench_tiny as tiny


@pytest.fixture
def root(tmp_path):
    return tiny.make_root(tmp_path)


def test_sound_run_is_correct(root, capsys):
    rc, line = tiny.run_cell(root, capsys)
    assert rc == 0
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {"grad_norm_gap", "change_gap"}
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0


def _unchanged(*a, **k):
    from repro.train.step import make_train_step
    step = make_train_step(*a, **k)

    def broken(state, batch, rng):
        new, metrics = step(state, batch, rng)
        return state._replace(step=new.step), metrics

    return broken


def _half_batch(*a, **k):
    from repro.train.step import make_train_step
    step = make_train_step(*a, **k)

    def broken(state, batch, rng):
        return step(state, {n: v[:v.shape[0] // 2]
                            for n, v in batch.items()}, rng)

    return broken


@pytest.mark.parametrize("builder", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_planted_fault_is_not_correct(root, capsys, monkeypatch, builder):
    from chipbench import train_cell
    monkeypatch.setattr(train_cell, "STEP_BUILDER", builder)
    rc, line = tiny.run_cell(root, capsys)
    assert rc == 0
    assert line["correct"] is False


def test_control_is_not_correct(root):
    """The float8 control, put in the program's place, fails a limit."""
    from chipbench import calibrate, checks, spec
    cell = spec.Cell(root, spec.load_benchmark(root), tiny.CELL)
    readings = dict(calibrate.train_readings(cell, seed=7))
    ok, compared = checks.judge(readings["control"], cell.config["limits"])
    assert not ok, compared
