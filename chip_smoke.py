"""Bring-up check of the serving path on TPU chips.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the same plan spread over four chips

Serves qwen2.5-3b at its published widths (all 36 layers, d_model 2048,
16/2 heads of 128, d_ff 11008, the full vocabulary) with random bfloat16
weights drawn from ``--seed``, through the library's own entry points:
planner -> `lm_graph.build_stg` -> `DecodePipeline` -> `LMServer.serve`.
Traffic: 8 requests, prompts of 64-512 tokens, 32 new tokens each, served
as 2 groups of 4.

What comes out is checked on the chip by comparing logits, not sampled
tokens (with random weights the largest logit changes on rounding):

  * one chip: the default (Pallas) single-device path against
    ``impl="ref"`` on the same params, for the prefill and the first
    decode steps; and the pipelined serve's head logits against the
    single-device server fed the same tokens;
  * ``--chips 4``: only the pipelined serve over four chips against the
    single-device server on the first chip.

The run fails unless JAX's first device is a TPU, the kernel tier
resolves to ``"pallas"``, and the compiled prefill and decode programs
hold a ``tpu_custom_call``.  Timings printed here are smoke observations,
not metrics.  The last line of standard output is one JSON object:
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failure gives ``ok`` false and exit code 1.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2.5-3b"
N_REQUESTS, GROUP, NEW_TOKENS = 8, 4, 32
PROMPT_RANGE = (64, 512)
REF_STEPS = 4          # decode steps of the Pallas-against-ref comparison

# Logits bounds, in bfloat16 ulps of the largest |logit| of the path
# compared against (2**-8 of it; the compute dtype is bfloat16).
#
# Pipelined serve against the single-device server: the same kernels and
# ops, but a stage program indexes its one period of the stack where the
# single-device program scans all 36, and XLA may fuse the two apart.
# Both measured 0 ulps, on the CPU and on a v5e chip.
PIPE_MAX_ULPS = 2.0
# Kernel path against impl="ref": the two round bf16 activations at
# different points (blocked online softmax against one pass, kernel f32
# against the chip's matmul passes), and each of the 36 layers can move
# an activation by an ulp.  At depth 36 and reduced width the CPU
# measured 3-5 ulps between kernel tiers (and 4 between bf16 and f32
# compute); a v5e chip measured 3.5 at full width.  Leaving the newest
# token out of decode attention moved the logits of a 4-layer model by
# 14.6 ulps.
REF_MAX_ULPS = 8.0


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def make_requests(cfg, seed: int, prompt_range=PROMPT_RANGE):
    import numpy as np
    from repro.runtime.server import Request
    rng = np.random.default_rng(seed)
    lo, hi = prompt_range
    return [Request(uid=i, prompt=rng.integers(
                2, cfg.vocab, int(rng.integers(lo, hi + 1))).tolist(),
                    max_new=NEW_TOKENS)
            for i in range(N_REQUESTS)]


def ulp_gap(want: list, got: list) -> float:
    """Largest |got - want| over all steps, in bf16 ulps of the largest
    |want|; fails on a shape mismatch or a non-finite logit."""
    import numpy as np
    want = [np.asarray(w, np.float32) for w in want]
    got = [np.asarray(g, np.float32) for g in got]
    check(len(want) == len(got), f"{len(got)} logit steps, want {len(want)}")
    for w, g in zip(want, got):
        check(w.shape == g.shape, f"logits shape {g.shape} != {w.shape}")
        check(bool(np.isfinite(w).all() and np.isfinite(g).all()),
              "non-finite logits")
    ulp = 2.0 ** -8 * max(float(np.abs(w).max()) for w in want)
    check(ulp > 0, "all-zero logits")
    return max(float(np.abs(g - w).max()) for w, g in zip(want, got)) / ulp


def build_pipeline(cfg, params, devices, *, impl=None):
    """The planner's one-chip decode plan for this traffic, placed as it
    is on ``devices``."""
    from repro.configs.base import ShapeCfg
    from repro.core import planner
    from repro.graphs import lm_graph
    from repro.runtime.pipeline import DecodePipeline
    shape = ShapeCfg("chip_smoke", PROMPT_RANGE[1] + NEW_TOKENS,
                     N_REQUESTS, "decode")
    plan = planner.plan(cfg, shape, chips=1)
    stg, _ = lm_graph.build_stg(cfg, shape)
    pipe = DecodePipeline(cfg, stg, plan, devices=devices, params=params,
                          impl=impl)
    pl = pipe.placement
    print(f"plan: {plan.total_chips:g} chip(s) wanted "
          f"(feasible={plan.feasible}), {len(pipe.stage_names)} stages, "
          f"placed on {pl.n_devices} device(s): "
          f"x{pl.oversubscription:g} oversubscribed")
    return pipe


def serve_pipelined(cfg, pipe, reqs, *, impl=None):
    from repro.runtime.server import LMServer
    srv = LMServer(cfg, max_batch=GROUP, pipeline=pipe, impl=impl,
                   keep_logits=True)
    check(srv.params is pipe._init_params,
          "the server built a second parameter tree")
    t0 = time.perf_counter()
    outs = srv.serve(reqs)
    wall = time.perf_counter() - t0
    n_tok = sum(len(c.tokens) for c in outs)
    check(len(outs) == len(reqs), f"{len(outs)} completions")
    check(all(1 <= len(c.tokens) <= r.max_new for c, r in zip(outs, reqs)),
          "a completion broke its token budget")
    stats = pipe.compile_stats
    print(f"served {len(outs)} requests in {len(srv.last_run.groups)} "
          f"groups, {n_tok} tokens, wall {wall:.3f}s with preflight and "
          f"warmup, engine {srv.last_run.wall_s:.3f}s "
          f"(smoke timings, not metrics)")
    print(f"pipeline compiles: {stats.compiles} in {stats.compile_s:.1f}s, "
          f"compile_stats.late={stats.late}")
    check(stats.late == 0, f"{stats.late} compiles landed inside the serve")
    return srv


def compare_logits(cfg, params, run, *, with_ref: bool, impl=None) -> None:
    """The pipelined serve's head logits against the single-device server
    fed the tokens the pipeline fed back, group by group; with
    ``with_ref``, also the single-device kernel path against
    ``impl="ref"`` for the prefill and the first `REF_STEPS` steps."""
    from repro.runtime.server import LMServer
    single = LMServer(cfg, max_batch=GROUP, params=params, impl=impl)
    t0 = time.perf_counter()
    want = [single.forced_logits(g.tokens, g.fed, g.cap) for g in run.groups]
    print(f"single-device forced logits: {time.perf_counter() - t0:.1f}s "
          f"with compiles")
    gap = max(ulp_gap(w, g.logits) for w, g in zip(want, run.groups))
    print(f"pipelined vs single-device logits: max {gap:.3f} ulps "
          f"(bound {PIPE_MAX_ULPS:g}), {len(run.groups)} groups x "
          f"{len(want[0])} steps")
    check(gap <= PIPE_MAX_ULPS, "pipelined serve disagrees with single device")
    if not with_ref:
        return
    ref = LMServer(cfg, max_batch=GROUP, params=params, impl="ref")
    gap = max(ulp_gap(ref.forced_logits(g.tokens, g.fed[:REF_STEPS], g.cap),
                      w[:REF_STEPS + 1])
              for w, g in zip(want, run.groups))
    print(f"kernel path vs ref logits: max {gap:.3f} ulps "
          f"(bound {REF_MAX_ULPS:g}), prefill + {REF_STEPS} decode steps")
    check(gap <= REF_MAX_ULPS, "kernel path disagrees with impl='ref'")


def check_spread(pipe, devices) -> None:
    """Every stage runs where its placement slice says, and the stages
    cover every device."""
    stage_of = pipe.graph_stage_map()          # graph node -> stage name
    used = set()
    for s, desc in enumerate(pipe.stage_descs):
        owners = [n for n, name in stage_of.items() if name == desc.name]
        planned = [sl.resolve(devices)[0] for o in owners
                   for sl in pipe.placement.replicas_of(o)]
        check(pipe.stage_devices[s] == planned,
              f"stage {desc.name} runs on {pipe.stage_devices[s]}, "
              f"placed on {planned}")
        used.update(pipe.stage_devices[s])
    per_dev = {str(d): sum(d in devs for devs in pipe.stage_devices)
               for d in devices}
    print(f"stages per device: {per_dev}")
    check(used == set(devices), f"stages use {len(used)} of "
          f"{len(devices)} devices")


def require_kernels(pipe) -> None:
    for prog in (pipe._block_prefill, pipe._block_decode):
        texts = prog.compiled_texts()
        check(bool(texts) and all("tpu_custom_call" in t for t in texts),
              f"{prog.name}: no Pallas kernel in the compiled program")
    print("compiled block prefill and decode hold tpu_custom_call")


def run(cfg, devices, *, seed: int, chips: int, impl=None,
        prompt_range=PROMPT_RANGE, on_tpu: bool = True) -> None:
    """The smoke phases over ``devices`` (``chips`` of them are used).
    ``on_tpu`` False skips only the compiled-kernel check, so the same
    phases can be rehearsed on CPU devices in interpret mode."""
    import jax
    from repro.kernels import fused_decode
    from repro.models import lm

    a = cfg.attn
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{a.n_heads}/{a.n_kv_heads} heads of {a.head_dim}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, params {cfg.param_dtype}")
    t0 = time.perf_counter()
    params = jax.block_until_ready(lm.init_params(cfg, jax.random.PRNGKey(seed)))
    nbytes = sum(l.nbytes for l in jax.tree.leaves(params))
    print(f"params: {nbytes / 1e9:.3f} GB on {devices[0]}, "
          f"init {time.perf_counter() - t0:.1f}s")
    reqs = make_requests(cfg, seed, prompt_range)
    cap = max(len(r.prompt) for r in reqs) + NEW_TOKENS
    path = fused_decode.step_path(
        impl or "pallas", cfg.d_model, a.n_heads, a.n_kv_heads, a.head_dim,
        cap)
    print(f"decode attention path: {path} "
          f"({'one fused Pallas kernel' if path == 'fused' else 'XLA ops around the decode_attention kernel'})")

    pipe = build_pipeline(cfg, params, devices[:chips], impl=impl)
    if chips > 1:
        print(pipe.placement.summary().splitlines()[0])
        check_spread(pipe, devices[:chips])
    srv = serve_pipelined(cfg, pipe, reqs, impl=impl)
    if on_tpu:
        require_kernels(pipe)
    compare_logits(cfg, params, srv.last_run, with_ref=chips == 1, impl=impl)
    stats = devices[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = {"platform": None, "kind": None, "count": 0}
    ok = False
    try:
        import jax
        devices = jax.devices()
        d0 = devices[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devices)}
        print(f"platform {d0.platform}, device_kind {d0.device_kind}, "
              f"{len(devices)} device(s)")
        check(d0.platform == "tpu", f"no TPU: JAX's first device is {d0}")
        check(len(devices) >= args.chips,
              f"{args.chips} chips asked for, {len(devices)} present")
        from repro import compile_cache
        from repro.configs import get_config
        from repro.kernels import ops
        impl = ops.resolve_impl()
        print(f"kernel impl: {impl}")
        check(impl == "pallas", f"kernel impl forced to {impl!r}, not pallas")
        print(f"compile cache: {compile_cache.enable()}")
        run(get_config(ARCH), devices, seed=args.seed, chips=args.chips)
        ok = True
    except Exception:
        traceback.print_exc()
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
