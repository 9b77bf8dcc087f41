"""Chaos suite (DESIGN.md §13): deterministic fault injection and the
graceful-degradation ladder across train / genfit / serve / checkpoint.

The load-bearing invariants:

* recoverable fault schedules leave training BIT-EQUAL to a fault-free
  run (rollback-replay advances the injection counters, so a replayed
  region is clean by construction);
* the serving engine never leaks lanes or pages, whatever combination of
  poison prefills, sheds, and deadline aborts a schedule throws at it;
* checkpoint restore never returns corrupt state — damage degrades the
  restore point, it never silently feeds back bad bytes;
* disabled injection is free enough to leave in hot paths permanently.
"""
import dataclasses
import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs as cfg_lib
from repro.checkpoint import (latest_step, restore_checkpoint,
                              save_checkpoint, verify_checkpoint)
from repro.data import lm_batch_fn
from repro.data.pipeline import HostShardedLoader, ProducerError
from repro.genfit.refresh import AsyncRefresher, RefreshTimeout
from repro.models import lm_head, transformer
from repro.models.config import ModelConfig
from repro.obs import Registry, start_metrics_server
from repro.obs.export import read_jsonl, validate_events
from repro.optim import OptimizerConfig
from repro.resilience import faults
from repro.resilience.faults import Fault, FaultPlan, InjectedFault
from repro.serve import Engine, Request, ServeConfig
from repro.train import (LoopConfig, init_train_state, make_train_step,
                         run_loop)

pytestmark = pytest.mark.resilience


# ---------------------------------------------------------------------------
# faults core: plans, counters, scoping, cost
# ---------------------------------------------------------------------------

def test_plan_fires_at_exact_nth():
    plan = FaultPlan([Fault("a/site", 2, "raise")])
    with faults.install(plan) as reg:
        faults.fire("a/site")
        faults.fire("a/site")
        with pytest.raises(InjectedFault) as exc:
            faults.fire("a/site")
        assert (exc.value.site, exc.value.nth) == ("a/site", 2)
        faults.fire("a/site")          # nth=3: past the schedule, clean
        assert reg.count("a/site") == 4
        assert reg.fired == [plan.get("a/site", 2)]
    assert faults.active() is None


def test_install_is_scoped_and_nests():
    assert faults.active() is None
    with faults.install(FaultPlan()) as outer:
        assert faults.active() is outer
        with faults.install(FaultPlan()) as inner:
            assert faults.active() is inner
        assert faults.active() is outer
    assert faults.active() is None


def test_install_restored_on_exception():
    with pytest.raises(ValueError):
        with faults.install(FaultPlan()):
            raise ValueError("boom")
    assert faults.active() is None


def test_corrupt_poisons_copy_not_original():
    batch = {"tokens": np.arange(6, dtype=np.int32),
             "mask": np.ones(6, np.float32)}
    with faults.install(FaultPlan([Fault("t/b", 0, "corrupt")])):
        out = faults.inject("t/b", batch)
    assert np.isnan(out["mask"]).any()
    assert not np.isnan(batch["mask"]).any(), "original must be untouched"
    np.testing.assert_array_equal(out["tokens"], batch["tokens"])


def test_delay_sleeps_roughly_requested():
    t0 = time.perf_counter()
    with faults.install(FaultPlan([Fault("d", 0, "delay", seconds=0.05)])):
        faults.fire("d")
    assert time.perf_counter() - t0 >= 0.04


def test_plan_json_roundtrip_and_random_plan_determinism():
    plan = faults.random_plan(7, ["x", "y"], 5)
    again = faults.random_plan(7, ["x", "y"], 5)
    assert plan.to_json() == again.to_json()
    back = FaultPlan.from_json(plan.to_json())
    assert sorted(back.faults, key=str) == sorted(plan.faults, key=str)


def test_env_var_plan_installs_in_subprocess():
    plan = FaultPlan([Fault("sub/site", 0, "raise")])
    code = ("from repro.resilience import faults\n"
            "assert faults.active() is not None\n"
            "try:\n"
            "    faults.fire('sub/site')\n"
            "except faults.InjectedFault:\n"
            "    print('FIRED')\n")
    env = dict(os.environ, REPRO_FAULT_PLAN=plan.to_json(),
               PYTHONPATH=_src_path())
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "FIRED" in out.stdout


def test_disabled_injection_is_cheap():
    """Loose ceiling, not a benchmark: 200k disabled fire() calls must
    stay well under a second — one attribute load + compare each."""
    assert faults.active() is None
    t0 = time.perf_counter()
    for _ in range(200_000):
        faults.fire("hot/site")
    assert time.perf_counter() - t0 < 1.0


# ---------------------------------------------------------------------------
# AsyncRefresher: retries, exhaustion, hang watchdog
# ---------------------------------------------------------------------------

def test_refresher_retries_absorb_transient_failure():
    calls = []

    def flaky(state):
        calls.append(state)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "fitted"

    r = AsyncRefresher(flaky, retries=2, backoff_s=0.001)
    r.submit("snap", 5)
    out, step = r.result()
    assert (out, step) == ("fitted", 5)
    assert len(calls) == 3


def test_refresher_exhausted_retries_raise_last_error():
    def always(state):
        raise RuntimeError("permanent")

    r = AsyncRefresher(always, retries=1, backoff_s=0.001)
    r.submit("snap", 5)
    with pytest.raises(RuntimeError, match="permanent"):
        r.result()
    assert not r.in_flight
    assert r.submit_step == 5          # survives for the failure handler


def test_refresher_watchdog_abandons_hung_fit():
    release = []

    def hung(state):
        while not release:             # daemon thread; freed at test end
            time.sleep(0.01)
        return "late"

    r = AsyncRefresher(hung, timeout_s=0.2)
    r.submit("snap", 3)
    with pytest.raises(RefreshTimeout):
        r.result()
    assert not r.in_flight             # a new submit is immediately legal
    r.submit("snap2", 9)

    def ok(state):
        return "fresh"

    r._fit_fn = ok                     # the hung thread keeps the old fn
    release.append(True)
    out, step = r.result()
    # Whichever thread finished this job, the result belongs to submit 9.
    assert step == 9


# ---------------------------------------------------------------------------
# checkpoint integrity: verify / fallback / never-corrupt-restore
# ---------------------------------------------------------------------------

def _tiny_tree(seed):
    rng = np.random.default_rng(seed)
    return {"w": rng.normal(size=(4, 3)).astype(np.float32),
            "b": rng.normal(size=(3,)).astype(np.float32)}


_DAMAGE = ["flip_byte", "truncate_arr", "delete_manifest",
           "garbage_manifest", "delete_arr"]


def _damage(path, mode):
    arr = os.path.join(path, "arr_00000.npy")
    man = os.path.join(path, "manifest.json")
    if mode == "flip_byte":
        with open(arr, "r+b") as f:
            f.seek(-1, 2)
            last = f.read(1)
            f.seek(-1, 2)
            f.write(bytes([last[0] ^ 0xFF]))
    elif mode == "truncate_arr":
        with open(arr, "r+b") as f:
            f.truncate(max(os.path.getsize(arr) // 2, 1))
    elif mode == "delete_manifest":
        os.remove(man)
    elif mode == "garbage_manifest":
        with open(man, "w") as f:
            f.write("{not json")
    elif mode == "delete_arr":
        os.remove(arr)


@settings(max_examples=len(_DAMAGE), deadline=None)
@given(mode=st.sampled_from(_DAMAGE))
def test_restore_never_returns_corrupt_state(mode):
    import tempfile
    d = tempfile.mkdtemp(prefix=f"ck_{mode.replace('/', '_')}_")
    for step in (1, 2, 3):
        save_checkpoint(d, step, _tiny_tree(step), keep=0)
    newest = os.path.join(d, "step_00000003")
    assert verify_checkpoint(newest)
    _damage(newest, mode)
    assert not verify_checkpoint(newest)
    # Fallback: the damaged newest entry degrades the restore point.
    assert latest_step(d) == 2
    tree, got = restore_checkpoint(d, _tiny_tree(0))
    assert got == 2
    np.testing.assert_array_equal(tree["w"], _tiny_tree(2)["w"])
    # An explicit request for the damaged step must raise, never return.
    with pytest.raises((IOError, FileNotFoundError)):
        restore_checkpoint(d, _tiny_tree(0), step=3)


def test_latest_step_ignores_stale_tmp_dirs(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 5, _tiny_tree(5), keep=0)
    os.makedirs(os.path.join(d, ".tmp_ckpt_dead"))
    with open(os.path.join(d, ".tmp_ckpt_dead", "arr_00000.npy"),
              "wb") as f:
        f.write(b"\x00" * 16)
    assert latest_step(d) == 5


def test_injected_raise_mid_save_leaves_no_tmp(tmp_path):
    d = str(tmp_path)
    with faults.install(FaultPlan([Fault("checkpoint/write", 0, "raise")])):
        with pytest.raises(InjectedFault):
            save_checkpoint(d, 1, _tiny_tree(1), keep=0)
    assert not any(n.startswith(".tmp") for n in os.listdir(d))
    assert latest_step(d) is None
    save_checkpoint(d, 1, _tiny_tree(1), keep=0)   # clean retry succeeds
    assert latest_step(d) == 1


# ---------------------------------------------------------------------------
# train loop: skip / rollback / genfit degradation — bit-equality
# ---------------------------------------------------------------------------

def _setup(seed=0):
    cfg = dataclasses.replace(cfg_lib.reduced_config("stablelm-3b"),
                              num_layers=1, dtype="float32")
    hcfg = lm_head.head_config(cfg, "adversarial_ns", reg=1e-4)
    opt = OptimizerConfig(name="adagrad", learning_rate=0.05, clip_norm=1.0)
    state = init_train_state(jax.random.PRNGKey(seed), cfg, opt,
                             "adversarial_ns")
    step_fn = jax.jit(make_train_step(cfg, hcfg, opt, skip_nonfinite=True))
    make = lm_batch_fn(cfg.vocab_size, global_batch=4, seq_len=16, seed=1)
    batch_fn = lambda s: {k: jnp.asarray(v)                 # noqa: E731
                          for k, v in make(s).items()}
    return cfg, state, step_fn, batch_fn


def _gen_fit_fn(cfg):
    from repro.train.generator_fit import make_gen_fit_fn
    make = lm_batch_fn(cfg.vocab_size, global_batch=4, seq_len=16, seed=9)
    batch_fn = lambda s: {k: jnp.asarray(v)                  # noqa: E731
                          for k, v in make(s).items()}
    return make_gen_fit_fn(cfg, batch_fn, kind="adversarial_ns",
                           max_tokens=128, n_batches=2)


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_nonfinite_skip_counts_and_completes(tmp_path):
    """A transiently poisoned batch is skipped in-graph: the run finishes
    with a finite loss, the skip is counted, and the event log validates
    against the schema (incl. the new resilience event types)."""
    cfg, state, step_fn, batch_fn = _setup(seed=2)
    jsonl = str(tmp_path / "ev.jsonl")
    loop = LoopConfig(total_steps=8, checkpoint_dir=None, log_every=100,
                      metrics_jsonl=jsonl)
    plan = FaultPlan([Fault("train/batch", 3, "corrupt")])
    with faults.install(plan) as reg:
        state, hist = run_loop(state, step_fn, batch_fn, loop,
                               jax.random.PRNGKey(2),
                               registry=Registry())
    assert reg.count("train/batch") == 8
    assert hist["nonfinite_steps"] == [3]
    assert hist["metrics"]["train/nonfinite_skipped"]["value"] == 1
    assert np.isfinite(hist["loss"][-1])
    events = read_jsonl(jsonl)
    validate_events(events)
    assert [e["step"] for e in events
            if e["event"] == "nonfinite_skip"] == [3]


def test_host_span_once_per_executed_step():
    """``train/phase/host`` (the loop's work after each step's sync) is
    observed once per executed step, the in-graph skip step included."""
    cfg, state, step_fn, batch_fn = _setup(seed=2)
    loop = LoopConfig(total_steps=6, checkpoint_dir=None, log_every=100)
    plan = FaultPlan([Fault("train/batch", 2, "corrupt")])
    with faults.install(plan):
        _, hist = run_loop(state, step_fn, batch_fn, loop,
                           jax.random.PRNGKey(2), registry=Registry())
    assert hist["nonfinite_steps"] == [2]
    snap = hist["metrics"]
    assert snap["train/phase/host"]["count"] == 6
    assert snap["train/phase/step"]["count"] == 6
    assert snap["train/steps"]["value"] == 6


def test_rollback_replay_is_bit_equal_to_fault_free(tmp_path):
    """THE tentpole invariant: a corrupt batch that escalates to
    rollback-restore leaves the final parameters bit-identical to an
    uninterrupted run — the replayed region sees fresh injection indices,
    so the fault does not re-fire."""
    n = 10
    ref_loop = LoopConfig(total_steps=n, checkpoint_dir=None, log_every=100)
    cfg, ref_state, step_fn, batch_fn = _setup(seed=3)
    ref_state, _ = run_loop(ref_state, step_fn, batch_fn, ref_loop,
                            jax.random.PRNGKey(7))

    loop = LoopConfig(total_steps=n, checkpoint_every=2,
                      checkpoint_dir=str(tmp_path / "ck"), log_every=100,
                      max_consecutive_nonfinite=1, max_rollbacks=2)
    _, state, _, _ = _setup(seed=3)
    plan = FaultPlan([Fault("train/batch", 5, "corrupt")])
    with faults.install(plan):
        state, hist = run_loop(state, step_fn, batch_fn, loop,
                               jax.random.PRNGKey(7), registry=Registry())
    assert hist["rollback_steps"] == [[5, 4]]
    assert hist["metrics"]["train/rollbacks"]["value"] == 1
    _assert_trees_equal(ref_state.params, state.params)
    _assert_trees_equal(ref_state.opt_state, state.opt_state)


def test_unguarded_nonfinite_rolls_back_immediately(tmp_path):
    """Without the in-graph guard the state is already poisoned when the
    host sees the NaN — the ladder must go straight to rollback, and the
    replay still ends bit-equal to fault-free."""
    n = 8
    cfg, _, _, batch_fn = _setup(seed=4)
    hcfg = lm_head.head_config(cfg, "adversarial_ns", reg=1e-4)
    opt = OptimizerConfig(name="adagrad", learning_rate=0.05, clip_norm=1.0)
    unguarded = jax.jit(make_train_step(cfg, hcfg, opt))   # no guard

    def fresh():
        return init_train_state(jax.random.PRNGKey(4), cfg, opt,
                                "adversarial_ns")

    ref_loop = LoopConfig(total_steps=n, checkpoint_dir=None, log_every=100)
    ref_state, _ = run_loop(fresh(), unguarded, batch_fn, ref_loop,
                            jax.random.PRNGKey(5))

    loop = LoopConfig(total_steps=n, checkpoint_every=2,
                      checkpoint_dir=str(tmp_path / "ck"), log_every=100)
    with faults.install(FaultPlan([Fault("train/batch", 5, "corrupt")])):
        state, hist = run_loop(fresh(), unguarded, batch_fn, loop,
                               jax.random.PRNGKey(5))
    assert hist["rollback_steps"] == [[5, 4]]
    _assert_trees_equal(ref_state.params, state.params)


def test_rollback_budget_exhaustion_raises(tmp_path):
    """A persistent cause (every batch poisoned) re-fires after every
    rollback; the budget converts it into the legacy crash."""
    cfg, state, step_fn, batch_fn = _setup(seed=5)
    # Poison every batch from step 2 on (after the first checkpoint
    # exists, so the ladder gets to roll back before giving up).
    plan = FaultPlan([Fault("train/batch", n, "corrupt")
                      for n in range(2, 64)])
    loop = LoopConfig(total_steps=8, checkpoint_every=2,
                      checkpoint_dir=str(tmp_path / "ck"), log_every=100,
                      max_consecutive_nonfinite=1, max_rollbacks=2)
    with faults.install(plan):
        with pytest.raises(FloatingPointError, match="budget"):
            run_loop(state, step_fn, batch_fn, loop, jax.random.PRNGKey(2))


def test_nonfinite_policy_raise_fails_fast():
    cfg, state, step_fn, batch_fn = _setup(seed=6)
    loop = LoopConfig(total_steps=6, checkpoint_dir=None, log_every=100,
                      nonfinite_policy="raise")
    with faults.install(FaultPlan([Fault("train/batch", 2, "corrupt")])):
        with pytest.raises(FloatingPointError):
            run_loop(state, step_fn, batch_fn, loop, jax.random.PRNGKey(2))


def test_genfit_transient_failure_retried_bit_equal():
    """A generator fit that fails once and succeeds on retry installs the
    identical head state (fits are deterministic in (state, config)) —
    the whole run stays bit-equal to fault-free."""
    n = 6
    cfg, state, step_fn, batch_fn = _setup(seed=7)
    gen_fit = _gen_fit_fn(cfg)
    loop = LoopConfig(total_steps=n, gen_warmup_steps=2, log_every=100,
                      gen_fit_retries=2, gen_fit_backoff_s=0.001)
    ref_state, ref_hist = run_loop(state, step_fn, batch_fn, loop,
                                   jax.random.PRNGKey(3),
                                   gen_fit_fn=gen_fit)

    _, state2, _, _ = _setup(seed=7)
    with faults.install(FaultPlan([Fault("genfit/fit", 0, "raise")])) as r:
        state2, hist = run_loop(state2, step_fn, batch_fn, loop,
                                jax.random.PRNGKey(3), gen_fit_fn=gen_fit)
    assert r.count("genfit/fit") == 2          # attempt 0 raised, 1 fit
    assert "gen_refresh_failed_steps" not in hist
    assert hist["gen_swap_steps"] == ref_hist["gen_swap_steps"]
    _assert_trees_equal(ref_state.params, state2.params)
    _assert_trees_equal(ref_state.head_state, state2.head_state)


def test_genfit_permanent_failure_keeps_stale_generator():
    """Retries exhausted: the loop records gen_refresh_failed, keeps the
    stale generator, and the NEXT scheduled refresh succeeds."""
    cfg, state, step_fn, batch_fn = _setup(seed=8)
    gen_fit = _gen_fit_fn(cfg)
    loop = LoopConfig(total_steps=10, gen_warmup_steps=2,
                      gen_refresh_steps=3, log_every=100,
                      gen_fit_retries=1, gen_fit_backoff_s=0.001)
    # Blocking fits: warmup at 2 (attempts nth 0,1 — both raise), next
    # refresh at 5 (nth 2 — clean).
    plan = FaultPlan([Fault("genfit/fit", 0, "raise"),
                      Fault("genfit/fit", 1, "raise")])
    with faults.install(plan):
        state, hist = run_loop(state, step_fn, batch_fn, loop,
                               jax.random.PRNGKey(3), gen_fit_fn=gen_fit,
                               registry=Registry())
    assert hist["gen_refresh_failed_steps"] == [2]
    assert 5 in hist["gen_swap_steps"]
    assert hist["metrics"]["genfit/refresh_failed"]["value"] == 1
    assert np.isfinite(hist["loss"][-1])


def test_genfit_async_hang_watchdog_keeps_training(tmp_path):
    """A hung background fit trips the watchdog at the swap step; the run
    keeps the stale generator, completes, and a later refresh installs."""
    cfg, state, step_fn, batch_fn = _setup(seed=9)
    gen_fit = _gen_fit_fn(cfg)
    loop = LoopConfig(total_steps=10, gen_warmup_steps=2,
                      gen_refresh_steps=4, gen_async=True,
                      gen_swap_delay=2, log_every=100,
                      checkpoint_dir=str(tmp_path / "ck"),
                      checkpoint_every=4,
                      gen_fit_retries=0, gen_fit_timeout_s=5.0)
    # Submits at 2 (hang: worker sleeps past the watchdog — and past
    # process exit, so it never wakes into a dying interpreter) and 6
    # (clean; the 5s watchdog is generous against a warm ~1s fit).
    plan = FaultPlan([Fault("genfit/fit", 0, "hang", seconds=1200.0)])
    with faults.install(plan):
        state, hist = run_loop(state, step_fn, batch_fn, loop,
                               jax.random.PRNGKey(3), gen_fit_fn=gen_fit,
                               registry=Registry())
    assert hist["gen_refresh_failed_steps"] == [4]      # swap step 2+2
    assert hist["gen_swap_steps"] == [8]                # submit 6 + 2
    assert int(jax.device_get(state.gen_fit_step)) == 6
    assert np.isfinite(hist["loss"][-1])


def test_checkpoint_delay_schedule_is_bit_equal(tmp_path):
    """Pure-delay faults on the checkpoint writer are invisible to the
    training trajectory."""
    n = 8
    ref_loop = LoopConfig(total_steps=n, checkpoint_dir=None, log_every=100)
    cfg, ref_state, step_fn, batch_fn = _setup(seed=10)
    ref_state, _ = run_loop(ref_state, step_fn, batch_fn, ref_loop,
                            jax.random.PRNGKey(4))
    loop = LoopConfig(total_steps=n, checkpoint_every=2, log_every=100,
                      checkpoint_dir=str(tmp_path / "ck"))
    _, state, _, _ = _setup(seed=10)
    plan = FaultPlan([Fault("checkpoint/write", 0, "delay", seconds=0.02),
                      Fault("checkpoint/commit", 1, "delay", seconds=0.02)])
    with faults.install(plan):
        state, _ = run_loop(state, step_fn, batch_fn, loop,
                            jax.random.PRNGKey(4))
    _assert_trees_equal(ref_state.params, state.params)
    assert latest_step(str(tmp_path / "ck")) == n


# ---------------------------------------------------------------------------
# serving engine: shed / deadline / poison — no lane or page leaks
# ---------------------------------------------------------------------------

CFG = ModelConfig(
    name="resilience-test", num_layers=1, d_model=32, d_ff=64,
    vocab_size=100, num_heads=2, num_kv_heads=2, vocab_pad_multiple=128,
    gen_feature_dim=8, dtype="float32", remat=False)
HCFG = lm_head.head_config(CFG, "adversarial_ns")
PARAMS = transformer.init_params(jax.random.PRNGKey(0), CFG)
HEAD_STATE = lm_head.default_head_state(jax.random.PRNGKey(1), CFG,
                                        "adversarial_ns")
MAX_LEN = 12
N_SLOTS = 2

_ENGINES = {}


def shared_engine(max_queue=0, enforce_deadlines=False) -> Engine:
    """One engine per resilience config (jit caches stay warm); between
    runs all lanes/pages are free and the queues empty."""
    key = (max_queue, enforce_deadlines)
    if key not in _ENGINES:
        _ENGINES[key] = Engine(CFG, HCFG, PARAMS, HEAD_STATE, ServeConfig(
            n_slots=N_SLOTS, max_len=MAX_LEN, beam=8, page_len=3,
            n_pages=8, cache_dtype=jnp.float32, max_queue=max_queue,
            enforce_deadlines=enforce_deadlines))
    return _ENGINES[key]


def _prompts(seed, n, lo=2, hi=4):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size,
                         rng.integers(lo, hi + 1)).astype(np.int32)
            for _ in range(n)]


def _assert_drained(eng):
    eng.pool.check_invariants()
    assert eng.pool.num_free_lanes == N_SLOTS
    assert eng.pool.num_free_pages == eng.pool.n_pages
    assert eng.num_pending == 0 and eng.num_active == 0


def test_engine_shed_on_bounded_queue():
    eng = shared_engine(max_queue=2)
    before = eng.shed_count
    handles = [eng.submit(Request(prompt=p, max_new_tokens=3))
               for p in _prompts(0, 5)]
    shed = [h for h in handles if h.status == "shed"]
    assert len(shed) == 3 and eng.shed_count - before == 3
    assert all(h.done and not h.tokens for h in shed)
    eng.run()
    assert all(h.done for h in handles)
    kept = [h for h in handles if h.status == "ok"]
    assert len(kept) == 2 and all(len(h.tokens) == 3 for h in kept)
    _assert_drained(eng)
    assert eng.health()["ready"]       # queue drained: ready again


def test_engine_deadline_abort_reclaims_resources():
    eng = shared_engine(enforce_deadlines=True)
    expired = [eng.submit(Request(prompt=p, max_new_tokens=3,
                                  deadline_s=0.0))
               for p in _prompts(1, 3)]
    alive = eng.submit(Request(prompt=_prompts(2, 1)[0], max_new_tokens=3))
    eng.run()
    assert all(h.done and h.status == "deadline" for h in expired)
    assert alive.status == "ok" and len(alive.tokens) == 3
    assert eng.deadline_aborts >= 3
    _assert_drained(eng)


def test_engine_poisoned_prefill_is_isolated():
    """A request whose prefill raises is failed alone; the rest of the
    batch completes with byte-identical tokens to a fault-free run."""
    eng = shared_engine()
    prompts = _prompts(3, 4)
    ref = [eng.submit(Request(prompt=p, max_new_tokens=3))
           for p in prompts]
    eng.run()
    _assert_drained(eng)

    plan = FaultPlan([Fault("serve/prefill", 1, "raise")])
    with faults.install(plan):
        handles = [eng.submit(Request(prompt=p, max_new_tokens=3))
                   for p in prompts]
        eng.run()
    assert all(h.done for h in handles)
    errored = [i for i, h in enumerate(handles) if h.status == "error"]
    assert len(errored) == 1
    for i, h in enumerate(handles):
        if h.status == "ok":
            assert h.tokens == ref[i].tokens, f"request {i} diverged"
    _assert_drained(eng)
    assert eng.poisoned_count >= 1


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10 ** 6))
def test_engine_never_leaks_under_chaos(seed):
    """Seeded chaos: random raise/delay faults on serve/prefill plus
    delays on serve/step, random deadline mix — every request reaches a
    terminal state and the pool drains back to empty."""
    rng = np.random.default_rng(seed)
    plan_faults = []
    for _ in range(int(rng.integers(1, 5))):
        plan_faults.append(Fault("serve/prefill", int(rng.integers(0, 6)),
                                 str(rng.choice(["raise", "delay"])),
                                 seconds=0.002))
    for _ in range(int(rng.integers(0, 3))):
        plan_faults.append(Fault("serve/step", int(rng.integers(0, 8)),
                                 "delay", seconds=0.002))
    eng = shared_engine(enforce_deadlines=True)
    prompts = _prompts(seed, int(rng.integers(2, 6)))
    with faults.install(FaultPlan(plan_faults)):
        handles = []
        for p in prompts:
            ddl = (0.0 if rng.random() < 0.3 else None)
            handles.append(eng.submit(Request(
                prompt=p, max_new_tokens=int(rng.integers(1, 4)),
                deadline_s=ddl)))
        eng.run()
    assert all(h.done for h in handles)
    assert all(h.status in ("ok", "error", "deadline", "shed")
               for h in handles)
    _assert_drained(eng)


def test_engine_health_snapshot_in_stats():
    eng = shared_engine()
    h = eng.stats()["health"]
    for k in ("ready", "compiled", "queue_depth", "active", "lanes_free",
              "pages_free", "shed", "poisoned", "deadline_aborts"):
        assert k in h, k
    assert h["queue_depth"] == 0 and h["active"] == 0


# ---------------------------------------------------------------------------
# /healthz + /readyz on the metrics server
# ---------------------------------------------------------------------------

def _get(port, path):
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_health_endpoints():
    snap = {"ready": False, "queue_depth": 0}
    reg = Registry()
    reg.counter("x").inc()
    with start_metrics_server(reg, 0, host="127.0.0.1",
                              health_fn=lambda: dict(snap)) as srv:
        code, body = _get(srv.port, "/healthz")
        assert code == 200 and json.loads(body)["ready"] is False
        code, _ = _get(srv.port, "/readyz")
        assert code == 503                      # alive but not ready
        snap["ready"] = True
        code, body = _get(srv.port, "/readyz")
        assert code == 200 and json.loads(body)["ready"] is True
        code, body = _get(srv.port, "/metrics")
        assert code == 200 and "x" in body      # scrape path untouched


def test_health_endpoints_404_without_health_fn():
    with start_metrics_server(Registry(), 0, host="127.0.0.1") as srv:
        assert _get(srv.port, "/healthz")[0] == 404
        assert _get(srv.port, "/readyz")[0] == 404


def test_engine_readyz_flips_after_compile():
    eng = shared_engine()
    eng._compiled = False              # fresh-process readiness gate
    with start_metrics_server(eng.registry, 0, host="127.0.0.1",
                              health_fn=eng.health) as srv:
        assert _get(srv.port, "/readyz")[0] == 503
        h = eng.submit(Request(prompt=_prompts(9, 1)[0], max_new_tokens=2))
        eng.run()
        assert h.status == "ok"
        assert _get(srv.port, "/readyz")[0] == 200


# ---------------------------------------------------------------------------
# data pipeline: producer failure propagation
# ---------------------------------------------------------------------------

def test_pipeline_producer_exception_propagates():
    def boom(step):
        if step >= 2:
            raise ValueError("bad shard")
        return {"tokens": np.zeros((4, 4), np.int32)}

    ld = HostShardedLoader(boom, 4, prefetch=2)
    seen = []
    with pytest.raises(ProducerError, match="bad shard"):
        for s, b in ld:
            seen.append(s)
            if len(seen) > 10:          # must not loop forever
                break
    assert seen == [0, 1]
    assert not ld.failed               # producer exited; nothing leaked


def test_pipeline_injected_producer_fault():
    make = lm_batch_fn(64, 4, 8)
    ld = HostShardedLoader(make, 4, prefetch=2)
    with faults.install(FaultPlan([Fault("data/produce", 2, "raise")])):
        with pytest.raises(ProducerError) as exc:
            for s, b in ld:
                pass
    assert isinstance(exc.value.__cause__, InjectedFault)


def test_pipeline_wedged_producer_marks_failed():
    started = []

    def slow(step):
        started.append(step)
        if step == 0:
            return {"tokens": np.zeros((4, 4), np.int32)}
        time.sleep(1200)               # daemon thread; dies with pytest

    ld = HostShardedLoader(slow, 4, prefetch=1)
    it = iter(ld)
    next(it)                           # step 0 flows; step 1 wedges
    deadline = time.perf_counter() + 5
    while len(started) < 2 and time.perf_counter() < deadline:
        time.sleep(0.01)
    ld.close()
    assert ld.failed                   # join timed out: loudly poisoned
    with pytest.raises(AssertionError):
        next(iter(ld))                 # refuses to restart


# ---------------------------------------------------------------------------
# kill -9 mid-checkpoint / mid-gensnap: resume replays bit-exact
# ---------------------------------------------------------------------------

_VICTIM = """
import dataclasses, sys
import jax, jax.numpy as jnp
from repro import configs as cfg_lib
from repro.data import lm_batch_fn
from repro.models import lm_head
from repro.optim import OptimizerConfig
from repro.train import (LoopConfig, init_train_state, make_train_step,
                         run_loop)

ckpt, variant = sys.argv[1], sys.argv[2]
gen = variant == "gen"
cfg = dataclasses.replace(cfg_lib.reduced_config("stablelm-3b"),
                          num_layers=1, dtype="float32")
hcfg = lm_head.head_config(cfg, "adversarial_ns", reg=1e-4)
opt = OptimizerConfig(name="adagrad", learning_rate=0.05, clip_norm=1.0)
state = init_train_state(jax.random.PRNGKey(0), cfg, opt, "adversarial_ns")
step_fn = jax.jit(make_train_step(cfg, hcfg, opt, skip_nonfinite=True))
make = lm_batch_fn(cfg.vocab_size, global_batch=4, seq_len=16, seed=1)
batch_fn = lambda s: {k: jnp.asarray(v) for k, v in make(s).items()}
gen_fit = None
if gen:
    from repro.train.generator_fit import make_gen_fit_fn
    gen_fit = make_gen_fit_fn(cfg, batch_fn, kind="adversarial_ns",
                              max_tokens=128, n_batches=2)
loop = LoopConfig(total_steps=12, checkpoint_every=4, checkpoint_dir=ckpt,
                  log_every=100,
                  gen_warmup_steps=2 if gen else 0,
                  gen_refresh_steps=4 if gen else 0,
                  gen_async=gen, gen_swap_delay=2 if gen else 0)
state, hist = run_loop(state, step_fn, batch_fn, loop,
                       jax.random.PRNGKey(7), gen_fit_fn=gen_fit)
print("DONE", int(jax.device_get(state.step)), flush=True)
"""


def _src_path():
    return os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                        "src"))


def _run_victim(script, ckpt, variant, extra_env=None, wait=True):
    env = dict(os.environ, PYTHONPATH=_src_path())
    env.pop("REPRO_FAULT_PLAN", None)
    if extra_env:
        env.update(extra_env)
    proc = subprocess.Popen([sys.executable, script, ckpt, variant],
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    if not wait:
        return proc
    out, err = proc.communicate(timeout=420)
    assert proc.returncode == 0, err
    assert "DONE 12" in out, (out, err)
    return proc


def _final_crcs(ckpt):
    with open(os.path.join(ckpt, "step_00000012", "manifest.json")) as f:
        meta = json.load(f)
    return [leaf["crc32"] for leaf in meta["leaves"]]


@pytest.mark.slow
@pytest.mark.parametrize("variant,site,nth", [
    ("plain", "checkpoint/commit", 1),     # kill mid-commit of ckpt 8
    ("gen", "checkpoint/write", 2),        # kill mid-write of gensnap 6
])
def test_sigkill_mid_save_resumes_bit_exact(tmp_path, variant, site, nth):
    """SIGKILL a training process while a checkpoint (or gensnap) is
    mid-write: the interrupted artifact must be invisible to resume, and
    the resumed run must replay to a bit-identical final state."""
    script = str(tmp_path / "victim.py")
    with open(script, "w") as f:
        f.write(_VICTIM)

    ref = str(tmp_path / "ref")
    _run_victim(script, ref, variant)
    ref_crcs = _final_crcs(ref)

    kill_dir = str(tmp_path / "kill")
    plan = FaultPlan([Fault(site, nth, "delay", seconds=600.0)])
    proc = _run_victim(script, kill_dir, variant, wait=False,
                       extra_env={"REPRO_FAULT_PLAN": plan.to_json()})
    try:
        # The delayed save begins only after step_00000004 is committed;
        # a .tmp_ckpt_* dir appearing after that means the writer is
        # parked inside the injected delay.
        deadline = time.perf_counter() + 360
        while time.perf_counter() < deadline:
            if proc.poll() is not None:
                out, err = proc.communicate()
                raise AssertionError(f"victim exited early: {err}")
            names = (os.listdir(kill_dir) if os.path.isdir(kill_dir)
                     else [])
            if ("step_00000004" in names
                    and any(n.startswith(".tmp_ckpt_") for n in names)):
                break
            time.sleep(0.05)
        else:
            raise AssertionError("victim never reached the delayed save")
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()

    # The torn artifact is on disk but must not be a restore candidate.
    leftovers = [n for n in os.listdir(kill_dir)
                 if n.startswith(".tmp_ckpt_")]
    assert leftovers, "kill landed outside the save window"
    assert latest_step(kill_dir) == 4

    _run_victim(script, kill_dir, variant)      # fresh process: auto-resume
    assert latest_step(kill_dir) == 12
    assert _final_crcs(kill_dir) == ref_crcs
