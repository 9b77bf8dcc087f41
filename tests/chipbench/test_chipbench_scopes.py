"""The train step's named scopes and their reduction: every op of the tiny
cell's compiled step lies under ``trunk``, ``head`` or ``optimizer``; the
per-scope sums of a hand-made op list and of a small trace recorded on a
TPU v5e (``record_scoped_trace.py``); the readers of the scope metrics."""
import json
import re
from pathlib import Path

import pytest

import chipbench_tiny as tiny
from chipbench import scopes, spec

DATA = Path(__file__).resolve().parent / "data"
READERS = ("trunk_ms.train", "head_ms.train", "sampler_ms.train",
           "optimizer_ms.train", "host_ms.train")


@pytest.fixture(scope="module")
def tiny_step_text(tmp_path_factory):
    """The tiny cell's train step, compiled as the cell compiles it."""
    from chipbench import split_step
    root = tiny.make_root(tmp_path_factory.mktemp("root"))
    cell = spec.Cell(root, spec.load_benchmark(root), tiny.CELL)
    step_fn, state, pool, _, key = split_step.build_step(cell, seed=5)
    return split_step.compiled_text(step_fn, state, pool[0], key)


def _op_names(text):
    """Instruction name -> raw op_name, for every instruction with one."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r'^\s+(?:ROOT\s+)?%?([\w.\-]+) = .*op_name="([^"]*)"', text, re.M)}


def test_op_scopes_find_every_scope(tiny_step_text):
    paths = set(scopes.op_scopes(tiny_step_text).values())
    for scope in ("trunk", "head", "head/sample", "optimizer"):
        assert any(p == scope or p.startswith(scope + "/") for p in paths), \
            scope


def test_entry_ops_lie_under_the_top_level_scopes(tiny_step_text):
    names = scopes.op_scopes(tiny_step_text)
    entry = next(ins for name, ins in scopes._computations(
        tiny_step_text).items() if name.startswith("main"))
    stray = [(n, names[n]) for n, _, _ in entry
             if names[n] and scopes.top_scope(names[n]) == scopes.UNSCOPED]
    assert stray == []
    assert sum(1 for n, _, _ in entry if names[n]) > 50


def test_backward_of_trunk_maps_to_trunk(tiny_step_text):
    names = scopes.op_scopes(tiny_step_text)
    backward = [n for n, op in _op_names(tiny_step_text).items()
                if "transpose(jvp(trunk))" in op]
    assert backward
    assert all(scopes.top_scope(names[n]) == "trunk" for n in backward)


def test_scope_path():
    assert scopes.scope_path("jit(f)/transpose(jvp(trunk))/mul") == \
        "trunk/mul"
    assert scopes.scope_path("jit(f)/head/sample/jit(_uniform)/add") == \
        "head/sample/jit(_uniform)/add"
    assert scopes.scope_path("jit(f)/vmap(jvp(optimizer))/sqrt") == \
        "optimizer/sqrt"
    assert scopes.scope_path("state.params['embed']") == ""
    assert scopes.scope_path("reduce_sum") == ""


HLO = """HloModule jit_f, entry_computation_layout={()->f32[]}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %a = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/head/mul"}
  ROOT %b = f32[4]{0} add(%a, %param_0), metadata={op_name="jit(f)/transpose(jvp(trunk))/add_any"}
}

%fused_computation.2 (param_0: f32[4]) -> bf16[4] {
  %param_0 = f32[4]{0} parameter(0)
  %c = f32[4]{0} negate(%param_0), metadata={op_name="jit(f)/head/neg"}
  ROOT %d = bf16[4]{0} convert(%c)
}

ENTRY %main (p: f32[4]) -> bf16[4] {
  %p = f32[4]{0} parameter(0), metadata={op_name="p"}
  %copy.0 = f32[4]{0} copy(%p)
  %fusion.1 = f32[4]{0} fusion(%copy.0), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/head/mul"}
  %sqrt.2 = f32[4]{0} sqrt(%fusion.1), metadata={op_name="jit(f)/optimizer/sqrt"}
  %copy.3 = f32[4]{0} copy(%sqrt.2)
  ROOT %fusion.4 = bf16[4]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.2
}
"""


def test_fusion_and_compiler_ops_are_attributed():
    names = scopes.op_scopes(HLO)
    assert names["fusion.1"] == "head/mul"        # its own metadata
    assert names["fusion.4"] == "head/neg"        # root has none: last
    assert names["sqrt.2"] == "optimizer/sqrt"
    assert names["copy.3"] == "optimizer/sqrt"    # by the value it reads
    assert names["copy.0"] == "head/mul"          # a parameter's: by user
    assert scopes.mixed_fusions(HLO) == {"fusion.1"}
    assert scopes.module_name_of(HLO) == "jit_f"


def test_split_sums_exclusive_time_per_scope():
    names = {"while.1": "trunk/while", "dot.2": "trunk/dot",
             "sample.3": "head/sample/add", "loss.4": "head/mul",
             "adagrad.5": "optimizer/sqrt", "const.6": ""}
    events = [(0, 10, "while.1"), (1, 3, "dot.2"), (4, 9, "sample.3"),
              (5, 6, "loss.4"), (10, 12, "adagrad.5"), (12, 13, "const.6"),
              (13, 14, "not_in_map.7")]
    got = scopes.split(events, 2, names, mixed=frozenset({"sample.3"}))
    assert got == {"trunk": 2.5, "head": 2.5, "head/sample": 2.0,
                   "optimizer": 1.0, "unscoped": 1.0, "mixed": 2.0,
                   "total": 7.0}


def test_chip_trace_splits_into_its_scopes():
    from chipbench.devtrace import exclusive_times
    meta = json.loads((DATA / "scoped.json").read_text())
    text = (DATA / "scoped.hlo.txt").read_text()
    path = str(DATA / "scoped.xplane.pb")
    names = scopes.op_scopes(text)
    module = scopes.module_name_of(text)
    assert module == meta["module"]
    split = scopes.read(path, module, names)
    (events, runs, module_s), = scopes.module_ops(path, module)
    assert runs == meta["runs"]
    op_s = sum(exclusive_times(events).values()) / runs
    assert split["total"] == pytest.approx(op_s)
    assert split["trunk"] + split["head"] + split["optimizer"] == \
        pytest.approx(op_s - split["unscoped"])
    assert split["trunk"] > 0 and split["head"] > 0
    assert 0 < op_s <= split["module_s"] == pytest.approx(module_s / runs)
    # the trace's ops are the compiled module's instructions
    assert {op for _, _, op in events} <= set(names)


def test_host_spans_read_from_the_trace():
    meta = json.loads((DATA / "small.json").read_text())
    pauses = scopes.host_spans(str(DATA / "small.xplane.pb"),
                               "bench/phase/pause")
    assert len(pauses) == meta["runs"]
    assert all(meta["pause_s"] <= s < 2 * meta["pause_s"] for s in pauses)


def test_scope_readers():
    root = tiny.REPO
    cell = spec.Cell(root, spec.load_benchmark(root), "mamba2-370m.train_2k")
    split = {"trunk": 2.9, "head": 0.02, "head/sample": 0.004,
             "optimizer": 0.015, "unscoped": 0.001}
    ctx = {"scopes": split, "host_span_mean_s": 0.004}
    got = {name: cell.reader(name).read(ctx) for name in READERS}
    assert got == {"trunk_ms.train": pytest.approx(1e3 * split["trunk"]),
                   "head_ms.train": pytest.approx(1e3 * split["head"]),
                   "sampler_ms.train": pytest.approx(
                       1e3 * split["head/sample"]),
                   "optimizer_ms.train": pytest.approx(
                       1e3 * split["optimizer"]),
                   "host_ms.train": pytest.approx(4.0)}
    empty = {"peaks": {"bf16_flops_per_s": 1e12}, "chips": 1}
    assert all(cell.reader(name).read(empty) is None for name in READERS)
