"""repro.obs: histogram quantile fidelity vs numpy, the disabled-mode
zero-overhead contract, JSONL schema round-trips, span nesting, and the
documented metric names actually emitted by an instrumented train loop
and serving engine (DESIGN.md §10)."""
import dataclasses
import gc
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import lm_head
from repro.obs import (Counter, Gauge, Histogram, JsonlExporter,
                       NULL_COUNTER, NULL_EWMA, NULL_GAUGE, NULL_HISTOGRAM,
                       NULL_REGISTRY, ProfileWindow, Registry,
                       console_summary, current_spans, exp_buckets,
                       linear_buckets, prometheus_text, read_jsonl, span,
                       validate_events)
from repro.obs.trace import _NULL_SPAN


# ---------------------------------------------------------------------------
# Instruments
# ---------------------------------------------------------------------------

def test_counter_gauge_ewma_basics():
    c = Counter("c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(AssertionError):
        c.inc(-1)                       # counters are monotone

    g = Gauge("g")
    assert g.value is None              # unset until first write
    g.set(2)
    g.set(1.5)
    assert g.value == 1.5

    r = Registry()
    e = r.ewma("e", alpha=0.5)
    e.update(1.0)
    assert e.value == 1.0               # first update seeds
    e.update(3.0)
    assert e.value == 2.0 and e.count == 2


def test_registry_get_or_create_and_type_guard():
    r = Registry()
    assert r.counter("x") is r.counter("x")
    h = r.histogram("h", bounds=[1.0, 2.0])
    assert r.histogram("h") is h        # buckets fixed by first call
    with pytest.raises(AssertionError):
        r.gauge("x")                    # same name, different type
    assert r.names() == ["h", "x"]


def test_bucket_builders():
    b = exp_buckets(1e-3, 1.0, per_decade=10)
    assert b == sorted(b) and b[0] == pytest.approx(1e-3)
    assert b[-1] >= 1.0
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert all(r == pytest.approx(10 ** 0.1) for r in ratios)
    assert linear_buckets(0.0, 1.0, 4) == [0.25, 0.5, 0.75, 1.0]


def test_histogram_quantiles_vs_numpy():
    """Interpolated bucket quantiles track numpy.quantile within one
    bucket ratio of relative error (the exp_buckets guarantee)."""
    rng = np.random.default_rng(0)
    vals = rng.uniform(0.011, 0.9, size=5000)
    per_decade = 50                     # ratio 10^(1/50) ~ 4.7%
    h = Histogram("h", bounds=exp_buckets(1e-2, 1.0, per_decade))
    for v in vals:
        h.observe(v)
    assert h.count == len(vals)
    assert h.mean == pytest.approx(vals.mean())
    for q in (0.05, 0.25, 0.5, 0.9, 0.95, 0.99):
        ref = float(np.quantile(vals, q))
        got = h.quantile(q)
        assert abs(got - ref) / ref < 10 ** (1 / per_decade) - 1 + 0.01, \
            (q, got, ref)
    snap = h.snapshot()
    assert snap["min"] == vals.min() and snap["max"] == vals.max()


def test_histogram_edge_cases():
    h = Histogram("h", bounds=[1.0, 2.0, 4.0])
    assert h.quantile(0.5) is None and h.mean is None   # empty
    h.observe(1.7)
    for q in (0.0, 0.5, 1.0):           # single value: exact everywhere
        assert h.quantile(q) == 1.7
    h2 = Histogram("h2", bounds=[1.0])
    h2.observe(5.0)                     # overflow bucket
    h2.observe(7.0)
    for q in (0.1, 0.5, 0.9):           # clamped to the observed range
        assert 5.0 <= h2.quantile(q) <= 7.0
    assert h2.quantile(1.0) == 7.0
    h3 = Histogram("h3", bounds=[1.0, 2.0])
    for _ in range(10):
        h3.observe(1.5)                 # constant stream
    assert h3.quantile(0.99) == 1.5


# ---------------------------------------------------------------------------
# Disabled mode: the hot-path contract
# ---------------------------------------------------------------------------

def test_disabled_registry_hands_out_shared_singletons():
    r = Registry(enabled=False)
    assert r.counter("a") is NULL_COUNTER is r.counter("b")
    assert r.gauge("a") is NULL_GAUGE
    assert r.ewma("a") is NULL_EWMA
    assert r.histogram("a") is NULL_HISTOGRAM
    assert span("x", r) is _NULL_SPAN is span("y", None)
    NULL_COUNTER.inc()
    NULL_GAUGE.set(3.0)
    NULL_HISTOGRAM.observe(1.0)
    assert NULL_COUNTER.value == 0 and NULL_GAUGE.value is None
    assert r.snapshot() == {} and r.names() == []
    assert NULL_REGISTRY.enabled is False


def _retained_bytes(loop) -> int:
    loop()                              # warm caches outside the window
    gc.collect()
    tracemalloc.start()
    t0 = tracemalloc.get_traced_memory()[0]
    loop()
    gc.collect()
    t1 = tracemalloc.get_traced_memory()[0]
    tracemalloc.stop()
    return t1 - t0


def test_disabled_mode_allocates_nothing():
    """The instrumented-every-step train loop relies on disabled mode
    being allocation-free: no instrument objects, no span objects."""
    r = Registry(enabled=False)

    def loop():
        for _ in range(200):
            r.counter("train/steps").inc()
            r.gauge("train/loss").set(1.0)
            r.histogram("train/step_time_s").observe(0.01)
            with span("train/phase/step", r):
                pass

    retained = _retained_bytes(loop)
    assert retained < 512, f"disabled-mode loop retained {retained} bytes"


def test_disabled_host_span_allocates_nothing():
    """The loop's per-step host span is the shared no-op when disabled."""
    assert span("train/phase/host", NULL_REGISTRY) is _NULL_SPAN

    def loop():
        for _ in range(200):
            with span("train/phase/host", NULL_REGISTRY):
                pass

    retained = _retained_bytes(loop)
    assert retained < 512, f"disabled host span retained {retained} bytes"


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

def test_span_nesting_and_timing():
    r = Registry()
    assert current_spans() == ()
    with span("outer", r) as outer:
        assert current_spans() == ("outer",)
        with span("inner", r):
            assert current_spans() == ("outer", "inner")
        assert current_spans() == ("outer",)
    assert current_spans() == ()
    inner_h = r.histogram("inner")
    assert inner_h.count == 1
    assert outer.seconds >= inner_h.vmax    # parent encloses child


def test_span_stack_restored_on_exception():
    r = Registry()
    with pytest.raises(RuntimeError):
        with span("outer", r):
            with span("inner", r):
                raise RuntimeError("boom")
    assert current_spans() == ()            # both frames popped
    assert r.histogram("inner").count == 1  # duration still recorded
    assert r.histogram("outer").count == 1


def test_profile_window_inert_without_dir():
    p = ProfileWindow(None, n_steps=2)
    for s in range(5):
        p.tick(s)
    p.stop()
    p.stop()                                # idempotent


def test_profile_window_raises_when_trace_cannot_start(monkeypatch,
                                                     tmp_path):
    import jax.profiler

    def refuse(log_dir, *a, **k):
        raise RuntimeError("profiler already running")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    p = ProfileWindow(str(tmp_path), n_steps=2)
    with pytest.raises(RuntimeError, match="profiler already running"):
        p.tick(0)
    p.stop()                                # nothing started: no-op


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def test_jsonl_round_trip_and_schema(tmp_path):
    path = str(tmp_path / "m.jsonl")
    events = [
        {"event": "compile", "step": 0, "compile_time_s": 1.2},
        {"event": "step", "step": 1, "loss": 3.5, "step_time_s": 0.01,
         "snr_proxy": 0.4, "snr_ewma": 0.41, "straggler": False},
        {"event": "gen_submit", "step": 3},
        {"event": "gen_swap", "step": 5, "old_fit_step": -1,
         "new_fit_step": 3, "fit_wall_s": 0.7, "steps_stale_at_swap": 2},
        {"event": "snr_trigger", "step": 9},
        {"event": "request", "request_id": 0, "tokens": 8,
         "admission_wait_s": 0.001, "ttft_s": 0.02, "latency_s": 0.09},
        {"event": "serve_step", "engine_step": 4, "queue_depth": 1,
         "active": 2, "page_occupancy": 0.5},
        {"event": "summary", "metrics": {}},
    ]
    with JsonlExporter(path) as ex:
        for ev in events:
            ex.emit(ev)
    assert ex.n_events == len(events)
    ex.emit({"event": "step"})              # closed: silent no-op
    back = read_jsonl(path)
    assert back == events
    validate_events(back)

    with pytest.raises(AssertionError):     # unknown type
        validate_events([{"event": "bogus"}])
    with pytest.raises(AssertionError):     # missing required field
        validate_events([{"event": "step", "step": 1, "loss": 2.0}])
    with pytest.raises(AssertionError):     # non-numeric timing
        validate_events([{"event": "compile", "step": 0,
                          "compile_time_s": "fast"}])
    with pytest.raises(AssertionError):
        validate_events([])


def test_pathless_exporter_is_noop(tmp_path):
    ex = JsonlExporter(None)
    ex.emit({"event": "step", "step": 0})
    assert ex.n_events == 0
    ex.close()


def test_prometheus_text_and_console_summary():
    r = Registry()
    r.counter("train/steps").inc(7)
    r.gauge("snr/ewma").set(0.43)
    h = r.histogram("train/step_time_s", bounds=[0.01, 0.1, 1.0])
    h.observe(0.05)
    h.observe(0.06)
    text = prometheus_text(r)
    assert "# TYPE train_steps counter" in text
    assert "train_steps 7" in text
    assert "# TYPE snr_ewma gauge" in text
    assert "# TYPE train_step_time_s summary" in text
    assert 'train_step_time_s{quantile="0.5"}' in text
    assert "train_step_time_s_count 2" in text

    out = console_summary(r, title="t")
    assert out.startswith("== t ==")
    assert "train/steps" in out and "n=2" in out
    assert console_summary(Registry()) == "== metrics: (empty) =="


def test_metrics_server_scrape_round_trip():
    """The pull endpoint serves a LIVE registry: scrape, mutate, re-scrape
    sees the new value; unknown paths 404; ephemeral port on port=0."""
    import urllib.error
    import urllib.request

    from repro.obs import start_metrics_server

    r = Registry()
    c = r.counter("serve/requests")
    c.inc(3)
    with start_metrics_server(r, port=0, host="127.0.0.1") as srv:
        assert srv.port > 0
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert body == prometheus_text(r)
        assert "serve_requests 3" in body
        c.inc(2)            # live registry, not a snapshot at bind time
        body = urllib.request.urlopen(base + "/").read().decode()
        assert "serve_requests 5" in body
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{base}/nope")
        assert e.value.code == 404
    # Server is down after close().
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(f"{base}/metrics", timeout=0.5)


# ---------------------------------------------------------------------------
# Integration: the documented metric names are what the systems emit
# ---------------------------------------------------------------------------

def test_train_loop_emits_documented_metrics(tmp_path):
    """One instrumented run covers the acceptance contract: per-step SNR
    + step-time samples and genfit lifecycle events parse back from the
    JSONL log, and the registry carries the DESIGN.md §10 names."""
    from repro import configs as cfg_lib
    from repro.data import lm_batch_fn
    from repro.models import lm_head
    from repro.optim import OptimizerConfig
    from repro.train import (LoopConfig, init_train_state,
                             make_train_step, run_loop)
    from repro.train.generator_fit import make_gen_fit_fn

    cfg = dataclasses.replace(cfg_lib.reduced_config("stablelm-3b"),
                              num_layers=1, dtype="float32")
    hcfg = lm_head.head_config(cfg, "adversarial_ns", reg=1e-4)
    opt = OptimizerConfig(name="adagrad", learning_rate=0.05,
                          clip_norm=1.0)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt,
                             "adversarial_ns")
    step_fn = jax.jit(make_train_step(cfg, hcfg, opt))
    make = lm_batch_fn(cfg.vocab_size, global_batch=4, seq_len=16, seed=1)
    batch_fn = lambda s: {k: jnp.asarray(v)               # noqa: E731
                          for k, v in make(s).items()}
    gen_fit = make_gen_fit_fn(cfg, batch_fn, kind="adversarial_ns",
                              max_tokens=128, n_batches=2)

    path = str(tmp_path / "train.jsonl")
    total = 6
    loop = LoopConfig(total_steps=total, gen_warmup_steps=2,
                      gen_async=True, gen_swap_delay=2,
                      metrics_jsonl=path, metrics_interval=1)
    reg = Registry()
    _, hist = run_loop(state, step_fn, batch_fn, loop,
                       jax.random.PRNGKey(2), gen_fit_fn=gen_fit,
                       registry=reg)

    # Compile separated from steady state.
    assert hist["compile_time_s"] > 0
    assert len(hist["step_times"]) == total - 1
    assert hist["compile_time_s"] not in hist["step_times"]

    snap = hist["metrics"]
    for name in ("train/steps", "train/loss", "train/step_time_s",
                 "train/compile_time_s", "train/phase/data",
                 "train/phase/step", "train/phase/host", "snr/proxy",
                 "snr/ewma",
                 "genfit/submits", "genfit/swaps", "genfit/fit_wall_s",
                 "genfit/staleness_at_swap"):
        assert name in snap, f"missing documented metric {name}"
    assert snap["train/steps"]["value"] == total
    assert snap["train/step_time_s"]["count"] == total - 1
    assert snap["genfit/swaps"]["value"] == 1
    assert snap == reg.snapshot()

    events = read_jsonl(path)
    validate_events(events)
    by = {}
    for ev in events:
        by.setdefault(ev["event"], []).append(ev)
    assert [e["step"] for e in by["step"]] == list(range(1, total))
    assert all("snr_proxy" in e and "snr_ewma" in e for e in by["step"])
    assert [e["step"] for e in by["gen_submit"]] == [2]
    swap, = by["gen_swap"]
    assert (swap["step"], swap["new_fit_step"],
            swap["steps_stale_at_swap"]) == (4, 2, 2)
    assert by["summary"][-1]["metrics"] == snap


@pytest.mark.serve
def test_engine_emits_latency_histograms_and_events(tmp_path):
    from repro.models import transformer
    from repro.models.config import ModelConfig
    from repro.serve import Engine, Request, ServeConfig

    cfg = ModelConfig(
        name="obs-engine", num_layers=1, d_model=32, d_ff=64,
        vocab_size=100, num_heads=2, num_kv_heads=2,
        vocab_pad_multiple=128, gen_feature_dim=8, dtype="float32",
        remat=False)
    hcfg = lm_head.head_config(cfg, "adversarial_ns")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    head_state = lm_head.default_head_state(jax.random.PRNGKey(1), cfg,
                                            "adversarial_ns")
    path = str(tmp_path / "serve.jsonl")
    ex = JsonlExporter(path)
    engine = Engine(cfg, hcfg, params, head_state,
                    ServeConfig(n_slots=2, max_len=12, beam=8,
                                cache_dtype=jnp.float32),
                    exporter=ex, metrics_interval=1)
    rng = np.random.default_rng(3)
    n_req, gen = 3, 4
    handles = [engine.submit(Request(
        prompt=rng.integers(0, cfg.vocab_size, 4).astype(np.int32),
        max_new_tokens=gen)) for _ in range(n_req)]
    engine.run()
    ex.close()
    assert all(len(h.tokens) == gen for h in handles)

    stats = engine.stats()
    lat = stats["latency"]
    for key in ("admission_wait", "ttft", "total"):
        assert lat[key]["count"] == n_req, (key, lat[key])
        assert lat[key]["p50"] is not None
    assert lat["total"]["min"] >= lat["ttft"]["min"]
    assert stats["tokens"] == n_req * gen
    snap = stats["metrics"]
    for name in ("serve/admission_wait_s", "serve/ttft_s",
                 "serve/latency_s", "serve/tokens", "serve/queue_depth",
                 "serve/active", "serve/page_occupancy",
                 "serve/phase/prefill", "serve/phase/decode",
                 "serve/decode_steps", "serve/completed"):
        assert name in snap, f"missing documented metric {name}"
    assert snap["serve/tokens"]["value"] == n_req * gen

    events = read_jsonl(path)
    validate_events(events)
    by = {}
    for ev in events:
        by.setdefault(ev["event"], []).append(ev)
    assert len(by["request"]) == n_req
    for ev in by["request"]:
        assert 0 <= ev["ttft_s"] <= ev["latency_s"]
        assert ev["tokens"] == gen
    assert by["serve_step"], "no serve_step samples"
    assert all(ev["queue_depth"] >= 0 and 0 <= ev["page_occupancy"] <= 1
               for ev in by["serve_step"])
