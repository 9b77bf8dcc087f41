"""Serving launcher: continuous-batching engine (default) or the legacy
lock-step decode, with the paper's Eq. 5 bias removal in the sampling path.

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b --reduced \
      --batch 4 --prompt-len 16 --gen 8 [--topk-beam 32]

Decode paths, selected by ``--topk-beam``:

- dense (default, ``--topk-beam 0``): every step computes all-C logits
  (O(C·K) matmul) plus the dense tree pass for log p_n (O(C·k)). Exact
  argmax; per-token cost grows linearly in the vocabulary. Right for eval
  and small C.
- beam (``--topk-beam B``, B > 0): beam search descends the adversarial
  generator tree to propose B candidates in O(B·k·log C), scores only those
  (gather-and-dot / gather_scores kernel), and applies Eq. 5 debiasing on
  the candidate set. Per-token cost is logarithmic in C — the serving path
  for extreme vocabularies. ``--shard-scores`` routes the candidate scoring
  through ``sharded_candidate_scores`` on the mesh's model axis.

By default requests run through ``repro.serve.Engine``: a paged KV pool
(``--slots`` decode lanes, ``--page-len``/``--n-pages`` page geometry —
defaults reproduce the monolithic one-buffer-per-lane capacity; undersize
``--n-pages`` to pack more lanes into the same device bytes on mixed-length
traffic), FIFO admission with batched multi-request prefill, per-request
EOS / max-length retirement (``--eos-id``) with page reclamation, and the
prefix-keyed candidate cache on the beam path. ``--lockstep`` restores the
fixed-batch loop (still with EOS handling) for A/B comparison; the two
emit identical tokens.

``main(argv)`` returns the run's result (engine path: each request's
status, tokens and error). A request that ends with status "error" while
no fault plan is installed (``repro.resilience.faults``) is a failure:
the launcher prints each error and exits non-zero. Run as a script, it
keeps JAX's persistent compilation cache in ``$JAX_COMPILATION_CACHE_DIR``
or else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfg_lib
from repro.models import lm_head, transformer
from repro.parallel import cache_shardings, params_shardings
from repro.resilience import faults
from repro.train import make_prefill, make_serve_step


def run_lockstep(args, cfg, mesh, params, head_state, hcfg):
    """Fixed-batch decode: one lock-step batch, no admission. Rows that emit
    ``--eos-id`` are frozen (their subsequent tokens pinned to EOS) and the
    loop exits early once every row has finished."""
    max_len = args.prompt_len + args.gen
    cache = transformer.init_cache(cfg, args.batch, max_len)
    cache_sh = cache_shardings(cfg, mesh, jax.eval_shape(lambda: cache),
                               args.batch)
    cache = jax.device_put(cache, cache_sh)

    prefill = jax.jit(make_prefill(cfg))
    serve_step = jax.jit(make_serve_step(
        cfg, hcfg, topk_beam=args.topk_beam,
        mesh=mesh if args.shard_scores else None))

    prompts = jax.random.randint(jax.random.PRNGKey(2),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    t0 = time.time()
    _, cache = prefill(params, prompts, cache)
    jax.block_until_ready(jax.tree.leaves(cache)[0])
    print(f"prefill {args.batch}x{args.prompt_len}: "
          f"{(time.time()-t0)*1e3:.0f} ms")

    token = prompts[:, -1:]
    finished = np.zeros((args.batch,), bool)
    toks = []
    t0 = time.time()
    steps = 0
    for t in range(args.gen):
        token, cache = serve_step(params, head_state, token, cache,
                                  jnp.int32(args.prompt_len + t))
        steps += 1
        if args.eos_id >= 0:
            row = np.asarray(token[:, 0])
            row = np.where(finished, args.eos_id, row)
            finished |= row == args.eos_id
            token = jnp.asarray(row[:, None])
            toks.append(row[:, None])
            if finished.all():
                break
        else:
            toks.append(np.asarray(token))
    jax.block_until_ready(token)
    dt = time.time() - t0
    out = np.concatenate(toks, 1)
    if args.eos_id >= 0:
        # Real tokens only: everything after a row's first EOS is padding.
        hit = out == args.eos_id
        real = np.where(hit.any(1), hit.argmax(1) + 1, out.shape[1]).sum()
    else:
        real = out.size
    path = (f"beam={args.topk_beam}" if args.topk_beam
            else "dense debiased scores")
    print(f"decode {steps} steps: {dt*1e3:.0f} ms "
          f"({real/dt:.1f} tok/s) [{path}, lock-step]")
    print("sample:", out[0].tolist())
    return {"tokens": out.tolist()}


def run_engine(args, cfg, mesh, params, head_state, hcfg):
    from repro.obs import JsonlExporter, console_summary
    from repro.obs.trace import ProfileWindow
    from repro.serve import Engine, Request, ServeConfig

    slots = args.slots or args.batch
    exporter = (JsonlExporter(args.metrics_jsonl) if args.metrics_jsonl
                else None)
    engine = Engine(cfg, hcfg, params, head_state, ServeConfig(
        n_slots=slots, max_len=args.prompt_len + args.gen,
        page_len=args.page_len, n_pages=args.n_pages,
        beam=args.topk_beam,
        mesh=mesh if args.shard_scores else None,
        eos_id=args.eos_id if args.eos_id >= 0 else None,
        cache_dtype=jnp.bfloat16,
        prefix_sharing=args.prefix_sharing,
        spec_decode=args.spec_decode, max_draft=args.max_draft,
        preemption=args.preemption, page_growth=args.page_growth,
        max_queue=args.max_queue,
        enforce_deadlines=args.enforce_deadlines),
        exporter=exporter, metrics_interval=args.metrics_interval)
    metrics_server = None
    if args.metrics_port is not None:
        from repro.obs import start_metrics_server
        metrics_server = start_metrics_server(engine.registry,
                                              args.metrics_port,
                                              health_fn=engine.health)
        print(f"metrics endpoint: http://0.0.0.0:{metrics_server.port}"
              "/metrics (+ /healthz, /readyz)")
    if args.profile_dir:
        engine.registry.annotate = True     # spans label the trace
    prompts = jax.random.randint(jax.random.PRNGKey(2),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    prompts = np.asarray(prompts)

    t0 = time.time()
    handles = [engine.submit(Request(prompt=p, max_new_tokens=args.gen))
               for p in prompts]
    profiler = ProfileWindow(args.profile_dir, n_steps=10**9)
    profiler.tick(0)            # whole-run capture; bounded by --gen
    engine.run()
    profiler.stop()
    dt = time.time() - t0
    tokens = sum(len(h.tokens) for h in handles)
    path = (f"beam={args.topk_beam}" if args.topk_beam
            else "dense debiased scores")
    print(f"engine: {len(handles)} requests over {slots} slots in "
          f"{dt*1e3:.0f} ms ({len(handles)/dt:.1f} req/s, "
          f"{tokens/dt:.1f} tok/s) [{path}]")
    stats = engine.stats()
    lat = stats["latency"]
    print("stats:", {k: v for k, v in stats.items()
                     if k not in ("latency", "metrics")})
    for name in ("admission_wait", "ttft", "total"):
        s = lat[name]
        if s["count"]:
            print(f"  {name}: p50={s['p50']*1e3:.1f}ms "
                  f"p95={s['p95']*1e3:.1f}ms p99={s['p99']*1e3:.1f}ms "
                  f"(n={s['count']})")
    print(console_summary(engine.registry, title="serve metrics"))
    if exporter is not None:
        summary = {"event": "summary", "metrics": engine.registry.snapshot()}
        exporter.emit(summary)
        exporter.close()
        print(f"metrics JSONL: {args.metrics_jsonl}")
    if metrics_server is not None:
        metrics_server.close()
    print("sample:", handles[0].result().tolist())
    return {"wall_s": dt,
            "requests": [{"id": h.request_id, "status": h.status,
                          "tokens": list(h.tokens), "error": h.error}
                         for h in handles]}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b",
                    choices=list(cfg_lib.ARCHS))
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--head", default="adversarial_ns")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests (and lock-step batch size)")
    ap.add_argument("--slots", type=int, default=0,
                    help="engine decode lanes (0 = --batch)")
    ap.add_argument("--page-len", type=int, default=0,
                    help="KV page size in positions (0 = one max_len page "
                         "per request: monolithic-equivalent; ignored for "
                         "pure-SSM archs, which have no KV arena)")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="KV arena capacity in pages (0 = full per-lane "
                         "reservation; smaller packs more lanes into the "
                         "same device bytes on mixed-length traffic; "
                         "ignored for pure-SSM archs)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8,
                    help="max new tokens per request")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop token id (-1 = disabled)")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--topk-beam", type=int, default=0,
                    help="0 = dense O(C) scoring; B > 0 = tree-guided beam "
                         "search over B candidates, O(B k log C) per token")
    ap.add_argument("--shard-scores", action="store_true",
                    help="score beam candidates via sharded_candidate_"
                         "scores on the mesh model axis")
    ap.add_argument("--lockstep", action="store_true",
                    help="legacy fixed-batch decode instead of the engine")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="stream repro.obs request/serve_step JSONL "
                         "events (DESIGN.md §10) to this path (engine "
                         "path only)")
    ap.add_argument("--metrics-interval", type=int, default=1,
                    help="emit a 'serve_step' event every N engine "
                         "iterations")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of the engine run "
                         "into this directory")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve the engine registry as Prometheus text on "
                         "this port (/metrics, stdlib HTTP thread; 0 = "
                         "ephemeral port, engine path only)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="share identical prompt-prefix KV pages across "
                         "requests (radix trie + refcounts + COW tails; "
                         "attention archs only)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative decode with the fitted generator "
                         "tree as draft model (byte-identical outputs; "
                         "attention archs only)")
    ap.add_argument("--max-draft", type=int, default=4,
                    help="draft chain cap per speculative verify step")
    ap.add_argument("--preemption", action="store_true",
                    help="allow higher-priority admissions to spill "
                         "lower-priority lanes (byte-exact restore)")
    ap.add_argument("--page-growth", default="reserve",
                    choices=["reserve", "ondemand"],
                    help="KV page policy: worst-case reservation at "
                         "admission vs on-demand growth at page "
                         "boundaries")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="shed (reject with status='shed') submissions "
                         "once this many requests are pending (0 = "
                         "unbounded queue)")
    ap.add_argument("--enforce-deadlines", action="store_true",
                    help="abort queued/active requests whose deadline_s "
                         "expired (status='deadline'), reclaiming their "
                         "lanes and pages")
    args = ap.parse_args(argv)

    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(model_axis=args.model_axis)
    cfg = (cfg_lib.reduced_config(args.arch) if args.reduced
           else cfg_lib.get_config(args.arch))

    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(params, params_shardings(
        cfg, mesh, jax.eval_shape(lambda: params)))
    head_state = lm_head.default_head_state(jax.random.PRNGKey(1), cfg,
                                            args.head)
    hcfg = lm_head.head_config(cfg, args.head)

    if args.lockstep:
        return run_lockstep(args, cfg, mesh, params, head_state, hcfg)
    result = run_engine(args, cfg, mesh, params, head_state, hcfg)
    failed = [r for r in result["requests"] if r["status"] == "error"]
    if failed and faults.active() is None:
        for r in failed:
            print(f"request {r['id']} failed: {r['error']}", flush=True)
        raise SystemExit(f"{len(failed)} of {len(result['requests'])} "
                         f"requests failed with no fault plan installed")
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
