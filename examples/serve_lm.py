"""Batched serving example: prefill + decode rounds with throughput stats.

A reduced qwen2.5-3b serves a queue of random-prompt requests in batched
rounds; the planner first recommends how to split a chip budget between
replicas for the decode shape (the paper's replication = serving replicas).

    PYTHONPATH=src python examples/serve_lm.py                # single-device
    PYTHONPATH=src python examples/serve_lm.py --pipeline     # planned STG

``--pipeline`` serves the same queue through the decode pipeline: the
planner's decode-shape plan is placed on the local device pool
(plan -> placement -> prefill/decode stage programs -> LMServer), request
groups stream concurrently through the stages, per-stage KV-cache slices
stay resident on their placement slices, and sampled tokens feed back
over a continuous token-stream channel.  Completions are token-identical
to the single-device backend under greedy sampling.

``--trace out.json`` (with ``--pipeline``) records the serve through the
runtime tracer and exports a Chrome-trace JSON — open it in Perfetto or
chrome://tracing to see one lane per (stage, replica), wait spans
annotated with the blamed FIFO, and FIFO-occupancy counter tracks.

``--lint-only`` builds the same pipelined plan, runs the static verifier
(`core.verify.verify_decode_plan` — channel/cycle credits, fusion
legality, placement consistency, cache-donation avals), prints the full
verification report, and exits without serving — exit status 1 on any
ERROR finding.
"""
import sys

sys.path.insert(0, "src")

import json

import numpy as np

from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeCfg
from repro.core import planner
from repro.runtime.server import LMServer, Request


def main(pipeline: bool = False, trace_path: str | None = None,
         lint_only: bool = False):
    arch = "qwen2.5-3b"
    cfg_full = get_config(arch)

    # planner: how should 64 chips serve decode_32k traffic?
    p = planner.plan(cfg_full, SHAPES["decode_32k"], chips=64)
    print("planner (64-chip serving budget):")
    print(p.summary())
    print()

    # actual serving at CPU scale with the reduced config
    cfg = cfg_full.reduced()
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(2, cfg.vocab,
                                        rng.integers(4, 25)).tolist(),
                    max_new=16)
            for i in range(12)]
    pipe = None
    if pipeline or lint_only:
        from repro.graphs import lm_graph
        from repro.runtime.pipeline import DecodePipeline

        # re-plan the reduced config at pool scale, then place + compile it
        shape = ShapeCfg("decode_smoke", 64, 16, "decode")
        small = planner.plan(cfg, shape, chips=8, max_tp=4)
        stg, _ = lm_graph.build_stg(cfg, shape, max_tp=4)
        pipe = DecodePipeline(cfg, stg, small, warmup=not lint_only)
        print("pipelined backend:")
        print(pipe.placement.summary())
        print()
    if lint_only:
        from repro.core import verify
        from repro.models import blocks
        from repro.runtime.server import _bucket

        # the same plan tuple the serve below would preflight: 12
        # requests grouped max_batch=4 at a time
        shapes = []
        for lo in range(0, len(reqs), 4):
            chunk = reqs[lo:lo + 4]
            bucket = _bucket(max(len(r.prompt) for r in chunk))
            cap = blocks.attn_cache_capacity(
                cfg, bucket + max(r.max_new for r in chunk))
            shapes.append((len(chunk), bucket, cap))
        report = verify.verify_decode_plan(
            pipe, n_groups=len(shapes), group_shapes=shapes)
        print(report.render())
        sys.exit(0 if report.ok() else 1)
    tracer = None
    if trace_path is not None:
        if pipe is None:
            sys.exit("--trace needs --pipeline (the single-device backend "
                     "has no stage pipeline to trace)")
        from repro.runtime.pipeline import Tracer
        tracer = Tracer()
    srv = LMServer(cfg, max_batch=4, temperature=0.0, pipeline=pipe,
                   tracer=tracer)
    outs = srv.serve(reqs)
    for c in outs[:3]:
        print(f"req {c.uid}: {c.prompt_len} prompt tok -> "
              f"{len(c.tokens)} generated {c.tokens[:8]}...")
    print(json.dumps(srv.stats.summary(), indent=1))
    if tracer is not None:
        tracer.save(trace_path)
        print(f"wrote Chrome trace to {trace_path} "
              f"(open in Perfetto / chrome://tracing)")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    args = sys.argv[1:]
    trace = args[args.index("--trace") + 1] if "--trace" in args else None
    main(pipeline="--pipeline" in args, trace_path=trace,
         lint_only="--lint-only" in args)
