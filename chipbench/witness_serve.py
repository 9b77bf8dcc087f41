"""Witness of the serving fault that keeps the serving cells out of the
benchmark: the engine conditions each answer on its prompt with the
prompt's last token fed a second time.

    python3 chipbench/witness_serve.py --seeds 11,12,13 [--tiny]

For each seed the program's ``serve.Engine`` (beam 16, as the serving
cells would run it, or the dense head with ``--beam 0``) answers ``--requests`` prompts of ``--prompt`` tokens
with ``--new`` tokens each. The plain reference then reads, at every served
position, the gap by which the served token's debiased score lies below the
best of the reference's beam (``served_gaps``), over two inputs:

- ``gap_prompt``: the prompt, then the served tokens (what a client sent);
- ``gap_repeated``: the prompt, its last token again, then the served
  tokens (what the engine's decode feeds).

A second witness is the program's own cache-free path: its training-mode
forward over the prompt and the served tokens, then its beam head
(``lm_predictive_topk``). Its tokens are read against the reference
(``gap_nocache``) and against the engine's (``agree_nocache``). One JSON
line per seed. ``--tiny`` runs the program's reduced hymba widths on any
backend; without it the run needs a TPU.
"""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402

ROOT = HERE.parent


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=1000)
    ap.add_argument("--new", type=int, default=24)
    ap.add_argument("--beam", type=int, default=None,
                    help="the engine's beam (default: the configuration's;"
                         " 0 serves with the dense head)")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import device, spec, traffic
    from repro import configs
    from repro.core.heads import Generator, HeadParams
    from repro.core.tree import Tree
    from repro.models import lm_head, transformer
    from repro.models.config import ModelConfig
    from repro.serve.engine import Engine, Request, ServeConfig

    cfile = root / "chipbench/configs/hymba-1.5b.json"
    spec_ = json.loads(cfile.read_text())
    ref = spec.load_module(cfile.with_suffix(".py"), "hymba-1.5b")
    model = spec_["model"]
    if args.tiny:
        small = dataclasses.asdict(configs.reduced_config("hymba-1.5b"))
        model = {k: small[k] for k in model}
    else:
        device.enable_compile_cache(root)
        device.check_chip(1)
    cfg = ModelConfig(**model)
    hcfg = lm_head.head_config(cfg, spec_["serve"]["head"])
    width = int(spec_["serve"]["beam"] if args.beam is None else args.beam)
    chunk = model["ssm_chunk"]
    total = args.prompt + args.new
    seq = -(-total // chunk) * chunk
    make_w = jax.jit(lambda k: ref.make_weights(k, model, spec_["weights"]))
    ref_fwd = jax.jit(lambda p, t: ref.forward(p, t, model))
    gaps = jax.jit(
        (lambda p, tr, pj, h, s: ref.served_gaps(p, tr, pj, h, s, model,
                                                 width)) if width else
        (lambda p, tr, pj, h, s: ref.dense_gaps(p, tr, pj, h, s, model)))
    prog_fwd = jax.jit(lambda p, t: transformer.forward(p, cfg, t)[0])
    prog_head = jax.jit(
        (lambda p, hs, h: lm_head.lm_predictive_topk(
            cfg, hcfg, HeadParams(**p["head"]), hs, h, topk=1,
            beam=width)[1][:, 0]) if width else
        (lambda p, hs, h: jnp.argmax(lm_head.lm_predictive_scores(
            cfg, hcfg, HeadParams(**p["head"]), hs, h), -1)))

    for seed in [int(s) for s in args.seeds.split(",")]:
        params, tree, proj = make_w(device.seed_key(seed, "weights"))
        hs = lm_head.LMHeadState(gen=Generator(tree=Tree(**tree)), proj=proj)
        engine = Engine(cfg, hcfg, params, hs, ServeConfig(
            n_slots=args.requests, max_len=total, beam=width))
        prompts = traffic.zipf_cluster_tokens(
            model["vocab_size"], args.requests, args.prompt, seed, 1.2, 64,
            0.8)
        handles = [engine.submit(Request(prompt=p, max_new_tokens=args.new))
                   for p in prompts]
        engine.run()
        row = {"seed": seed, "beam": width, "gap_prompt": [],
               "gap_repeated": [],
               "gap_nocache": [], "agree_nocache": []}
        P = args.prompt
        for prompt, h in zip(prompts, handles):
            served = np.asarray(h.result(), np.int32)
            plain = np.zeros((1, seq), np.int32)
            plain[0, :P + args.new - 1] = np.concatenate([prompt,
                                                          served[:-1]])
            rep = np.zeros((1, seq), np.int32)
            rep[0, :P + args.new] = np.concatenate([prompt, prompt[-1:],
                                                    served[:-1]])
            h_plain = ref_fwd(params, plain)[0, P - 1:P - 1 + args.new]
            h_rep = ref_fwd(params, rep)[0, P:P + args.new]
            row["gap_prompt"].append(float(jnp.max(gaps(
                params, tree, proj, h_plain, served))))
            row["gap_repeated"].append(float(jnp.max(gaps(
                params, tree, proj, h_rep, served))))
            hp = prog_fwd(params, plain)[0, P - 1:P - 1 + args.new]
            mine = np.asarray(prog_head(params, hs, hp), np.int32)
            row["gap_nocache"].append(float(jnp.max(gaps(
                params, tree, proj, h_plain, mine))))
            row["agree_nocache"].append(float(np.mean(mine == served)))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
