"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``): idle share, per-program device time, and idle gaps
named by the host span open in them."""
import json
from pathlib import Path

import pytest

import chipbench_tiny  # noqa: F401  (puts the checkout on sys.path)
from chipbench import devtrace

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def reduced():
    meta = json.loads((DATA / "small.json").read_text())
    return devtrace.reduce(str(DATA / "small.xplane.pb"), meta["window_s"])


def test_busy_and_idle(reduced):
    assert 0 < reduced.busy_s < reduced.window_s
    # three 10 ms pauses with the chip idle: at least 30 ms of the window
    assert reduced.window_s - reduced.busy_s >= 0.03
    assert 0 < reduced.idle_share < 1


def test_program_device_time(reduced):
    for name in ("jit_matmul_tanh", "jit_sum_squares"):
        secs, runs = reduced.program_seconds(f"^{name}$")
        assert runs == 3
        assert 0 < secs < reduced.busy_s
    mm, _ = reduced.program_seconds("^jit_matmul_tanh$")
    ss, _ = reduced.program_seconds("^jit_sum_squares$")
    assert mm > ss            # a 1024^3 matmul outlasts a sum of squares


def test_idle_gaps_are_named_by_host_spans(reduced):
    longest = sorted(reduced.gaps, key=lambda g: -g[1])[:3]
    assert [name for name, _ in longest] == ["bench/phase/pause"] * 3
    assert all(s >= 0.009 for _, s in longest)
    bd = reduced.breakdown()
    assert bd["idle_gaps"][0][0] == "bench/phase/pause"
    assert len(bd["device_ops"]) <= 10


def test_union_and_exclusive_times():
    assert devtrace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    ex = devtrace.exclusive_times([(0, 10, "while"), (1, 3, "a"),
                                   (4, 9, "b"), (5, 6, "c")])
    assert ex == {"while": 3, "a": 2, "b": 4, "c": 1}
    assert devtrace.short_op("%fusion.12 = f32[2] fusion(x)") == "fusion.12"
    assert devtrace.module_name("jit_step(123)") == "jit_step"


def test_per_layer_readers(reduced):
    from chipbench import spec
    root = chipbench_tiny.REPO
    cell = spec.Cell(root, spec.load_benchmark(root), "mamba2-370m.train_2k")
    ctx = {"trace": reduced, "window_s": 10.0, "tokens": 110_000,
           "flops_per_token": 2e9, "chips": 1, "data_span_mean_s": 2e-5,
           "peaks": {"bf16_flops_per_s": 197e12}}
    got = {m["name"]: cell.reader(m["name"]).read(ctx)
           for m in cell.per_layer}
    assert got["device_idle.train"] == pytest.approx(
        100 * reduced.idle_share)
    assert got["step_mfu.train"] == pytest.approx(
        100 * 2e9 * 11_000 / 197e12)
    assert got["data_ms.train"] == pytest.approx(0.02)
    empty = {"peaks": ctx["peaks"], "chips": 1}
    assert all(cell.reader(m["name"]).read(empty) is None
               for m in cell.per_layer)
