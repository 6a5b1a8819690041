"""Decode-shape serving pipelines (runtime/pipeline/decode + engine core).

Acceptance contract:
  * decode through the pipelined `LMServer` produces token-identical
    completions to the single-device ``serve_round`` (greedy sampling) —
    in-process and on an 8-device pool (subprocess);
  * per-stage prefill/decode math is the *same code* the single-device
    path runs (`models/lm.prefill_blocks` / `decode_blocks` over each
    stage's periods of the shared stack);
  * `channels.StreamChannel` carries the continuous decode token stream
    with open/close semantics;
  * the graph-generic engine drives dynamically-growing op queues to
    quiescence and frees channel credits when an op's body raises.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs.base import ShapeCfg
from repro.configs.tiny import CONFIG as tiny
from repro.core import planner
from repro.graphs import lm_graph
from repro.runtime.pipeline import (DecodePipeline, Engine, Fifo, Op,
                                    StreamChannel)
from repro.runtime.server import LMServer, Request


@pytest.fixture(scope="module")
def decode_setup():
    shape = ShapeCfg("decode_test", 64, 16, "decode")
    plan = planner.plan(tiny, shape, chips=8, max_tp=4)
    stg, _ = lm_graph.build_stg(tiny, shape, max_tp=4)
    return plan, stg


def _reqs(n, seed=0, max_new=8):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(2, tiny.vocab,
                                        rng.integers(4, 20)).tolist(),
                    max_new=max_new)
            for i in range(n)]


# ===========================================================================
# token parity with the single-device server
# ===========================================================================
def test_pipelined_server_token_identical(decode_setup):
    """Same seed, same grouping: the pipelined backend must generate the
    exact token sequences of the single-device prefill/decode loop."""
    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan)
    reqs = _reqs(8)
    out_p = LMServer(tiny, max_batch=4, pipeline=pipe).serve(reqs)
    out_r = LMServer(tiny, max_batch=4).serve(reqs)
    assert len(out_p) == len(out_r) == len(reqs)
    for a, b in zip(out_p, out_r):
        assert a.uid == b.uid
        assert a.tokens == b.tokens, (a.uid, a.tokens, b.tokens)
        assert a.prompt_len == b.prompt_len


def test_pipelined_serve_logits_match_single_device(decode_setup):
    """The head logits a pipelined serve keeps (``keep_logits``) are the
    single-device server's logits on the same token history — prefill
    and every decode step — and the server serves the pipeline's own
    weights instead of building a second tree."""
    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan)
    srv = LMServer(tiny, max_batch=4, pipeline=pipe, keep_logits=True)
    assert srv.params is pipe._init_params
    srv.serve(_reqs(8, max_new=5))
    single = LMServer(tiny, max_batch=4, params=srv.params)
    for g in srv.last_run.groups:
        want = single.forced_logits(g.tokens, g.fed, g.cap)
        assert len(g.logits) == len(want) == len(g.fed) + 1
        for got, ref in zip(g.logits, want):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_one_device_pipeline_holds_one_copy_of_weights(decode_setup):
    """Folded onto one device, every stage reads the caller's parameter
    buffers: block stages index the shared stack by period, and the tied
    or untied head is the stored matrix, not a transposed copy."""
    import jax
    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan, devices=jax.devices()[:1])
    full = pipe._init_params
    ptrs = {l.unsafe_buffer_pointer() for l in jax.tree.leaves(full)}
    seen = set()
    for s, desc in enumerate(pipe.stage_descs):
        for p in pipe.stage_params[s].values():
            leaves = jax.tree.leaves(
                {k: v for k, v in p.items() if k != "periods"})
            seen |= {l.unsafe_buffer_pointer() for l in leaves}
            if desc.span is not None:
                lo, hi = desc.span
                assert list(np.asarray(p["periods"])) == list(range(lo, hi))
    assert seen <= ptrs, "a stage holds a copy of the model's weights"


def test_pipelined_server_respects_budgets(decode_setup):
    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan)
    reqs = _reqs(4, seed=1, max_new=3)
    outs = LMServer(tiny, max_batch=4, pipeline=pipe).serve(reqs)
    for c in outs:
        assert 1 <= len(c.tokens) <= 3
        assert c.prefill_s >= 0 and c.decode_s >= 0


def test_pipelined_server_overlap_off_matches(decode_setup):
    """The serial A/B baseline (overlap=False) runs the same stage graph
    and must produce identical tokens."""
    plan, stg = decode_setup
    reqs = _reqs(8, seed=2)
    on = LMServer(tiny, max_batch=4,
                  pipeline=DecodePipeline(tiny, stg, plan)).serve(reqs)
    off = LMServer(tiny, max_batch=4,
                   pipeline=DecodePipeline(tiny, stg, plan,
                                           overlap=False)).serve(reqs)
    for a, b in zip(on, off):
        assert a.tokens == b.tokens


def test_serve_run_measurement_surface(decode_setup):
    """A pipelined serve emits the engine's measurement surface: stage
    completion streams, decode tokens/s, per-token latency samples."""
    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan)
    run = pipe.serve([list(range(2, 12))] * 8, 12, group_size=4)
    assert run.decode_tokens > 0 and run.prefill_tokens > 0
    assert run.decode_tokens_per_s() > 0
    lats = run.token_latencies_s()
    assert lats and all(l >= 0 for l in lats)
    assert set(run.stage_done_s) == set(pipe.stage_names)
    # every stage fired once per scheduled op (prefill + decode steps)
    firings = set(run.stage_firings.values())
    assert len(firings) == 1            # linear chain: same op count per stage
    assert run.fifo_stats["feedback"].pushes > 0


def test_serve_run_is_a_calibration_source(decode_setup):
    """A serve run's completion streams flow through the same
    measure.compare_lm core as LM microbatch runs (one comparison logic,
    no serving special case) and on into planner.replan."""
    from repro.runtime.pipeline import as_selection, compare_lm

    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan)
    run = pipe.serve([list(range(2, 12))] * 8, 16, group_size=4)
    rep = compare_lm(stg, as_selection(plan), run,
                     stage_map=pipe.graph_stage_map())
    assert rep.bottleneck_measured in rep.stages
    ratios = rep.ratios()
    assert ratios and all(r > 0 for r in ratios.values())
    new, diff = planner.replan(
        tiny, ShapeCfg("decode_test", 64, 16, "decode"), plan,
        new_chips=8, measured_ratio=ratios, max_tp=4)
    assert new.feasible and "throughput_ratio" in diff


def test_pipelined_server_token_identical_with_attention_window(decode_setup):
    """SWA configs ring-buffer the KV cache at the attention window: the
    pipeline must apply the same capacity clamp as lm.prefill or it
    attends further back than the single-device server."""
    from dataclasses import replace
    swa = replace(tiny, name="tiny-swa", attn=replace(tiny.attn, window=16))
    shape = ShapeCfg("decode_swa", 64, 16, "decode")
    plan = planner.plan(swa, shape, chips=8, max_tp=4)
    stg, _ = lm_graph.build_stg(swa, shape, max_tp=4)
    pipe = DecodePipeline(swa, stg, plan)
    # prompts longer than the window so the ring buffer actually wraps
    reqs = _reqs(4, seed=7, max_new=8)
    for r in reqs:
        r.prompt = (r.prompt * 4)[:30]
    out_p = LMServer(swa, max_batch=4, pipeline=pipe).serve(reqs)
    out_r = LMServer(swa, max_batch=4).serve(reqs)
    for a, b in zip(out_p, out_r):
        assert a.tokens == b.tokens, (a.uid, a.tokens, b.tokens)


def test_serve_rejects_empty_queue_and_samples_with_temperature(decode_setup):
    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan)
    with pytest.raises(ValueError, match="at least one prompt"):
        pipe.serve([], [])
    # ... but the server entry point mirrors the single-device backend
    # and drains an empty queue to an empty list
    assert LMServer(tiny, max_batch=4, pipeline=pipe).serve([]) == []
    assert LMServer(tiny, max_batch=4).serve([]) == []
    # LMServer forwards its temperature: the stochastic path runs end to
    # end (draws use per-group key streams, so only shape is asserted)
    srv = LMServer(tiny, max_batch=4, temperature=0.8, pipeline=pipe)
    outs = srv.serve(_reqs(4, seed=5, max_new=4))
    assert all(1 <= len(c.tokens) <= 4 for c in outs)


def test_decode_pipeline_rejects_encdec():
    from repro.configs import get_config
    cfg = get_config("seamless-m4t-medium").reduced()
    stg, _ = lm_graph.build_stg(cfg, ShapeCfg("encdec", 16, 8, "decode"),
                                max_tp=2)
    from repro.core.stg import Selection
    with pytest.raises(ValueError, match="decoder pipelines only"):
        DecodePipeline(cfg, stg, Selection.smallest(stg))


# ===========================================================================
# stream channel: continuous decode traffic
# ===========================================================================
def test_stream_channel_open_close_semantics():
    ch = StreamChannel(block=1, capacity_blocks=4)
    ch.push([(0, "a")], 0.0)
    assert not ch.exhausted
    ch.close()
    assert ch.closed and not ch.exhausted    # still a token to drain
    with pytest.raises(RuntimeError, match="after close"):
        ch.push([(1, "b")], 1.0)
    assert ch.pop(1) == [(0, "a")]
    assert ch.exhausted


def test_stream_channel_is_still_a_bounded_fifo():
    ch = StreamChannel(block=1, capacity_blocks=2)
    ch.push([1, 2], 0.0)
    assert not ch.can_push(1)
    with pytest.raises(OverflowError):
        ch.push([3], 0.0)


# ===========================================================================
# engine core
# ===========================================================================
@pytest.mark.parametrize("overlap", [True, False])
def test_engine_releases_held_slots_when_op_raises(overlap):
    """An op whose body raises must not leak its channel credits: the
    engine frees op.releases on the failure path — pooled and inline
    execution alike — so the fifo returns to full capacity instead of
    wedging later consumers."""
    fifo = Fifo(block=1, capacity_blocks=2)
    fifo.push([(0, "x")], 0.0)

    class Consumer:
        name = "cons"
        n_replicas = 1

        def __init__(self):
            self.done = False

        def pending(self):
            return 0 if self.done else 1

        def peek(self):
            return None if self.done else Op(stage=0, kind="F", seq=0, rep=0)

        def ready(self, op, count_stall=False):
            return 0.0 if fifo.can_pop(1) else None

        def dispatch(self, op, driver):
            self.done = True
            fifo.pop_hold(1)
            op.releases.append((fifo, 1))

            def boom():
                raise RuntimeError("op body failed")
            return boom, ()

        def retire(self, op, result, engine):
            raise AssertionError("retire must not run for a failed op")

        def describe(self):
            return "cons"

    eng = Engine([Consumer()], overlap=overlap, workers=2)
    with pytest.raises(RuntimeError, match="op body failed"):
        eng.run()
    assert fifo.free == fifo.capacity


def test_engine_detects_deadlock_with_program_state():
    class Stuck:
        name = "stuck"
        n_replicas = 1

        def pending(self):
            return 1

        def peek(self):
            return Op(stage=0, kind="F", seq=0, rep=0)

        def ready(self, op, count_stall=False):
            return None             # forever blocked, nothing in flight

        def dispatch(self, op, driver):
            raise AssertionError

        def retire(self, *a):
            raise AssertionError

        def describe(self):
            return "stuck: 0/1"

    with pytest.raises(RuntimeError, match="deadlock.*stuck: 0/1"):
        Engine([Stuck()], overlap=False).run()


# ===========================================================================
# multi-device pool (subprocess: XLA_FLAGS must be set before jax import)
# ===========================================================================
_SERVE_MULTIDEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, numpy as np
    from repro.configs.base import ShapeCfg
    from repro.configs.tiny import CONFIG as tiny
    from repro.core import planner
    from repro.graphs import lm_graph
    from repro.runtime.pipeline import DecodePipeline
    from repro.runtime.server import LMServer, Request

    assert len(jax.devices()) == 8
    shape = ShapeCfg("decode_par", 64, 16, "decode")
    plan = planner.plan(tiny, shape, chips=8, max_tp=4)
    stg, _ = lm_graph.build_stg(tiny, shape, max_tp=4)
    pipe = DecodePipeline(tiny, stg, plan)
    spread = {d for devs in pipe.stage_devices for d in devs}
    assert len(spread) > 1, f"stages all folded onto {spread}"
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i,
                    prompt=rng.integers(2, tiny.vocab,
                                        rng.integers(4, 20)).tolist(),
                    max_new=10)
            for i in range(12)]
    out_p = LMServer(tiny, max_batch=4, pipeline=pipe).serve(reqs)
    out_r = LMServer(tiny, max_batch=4).serve(reqs)
    for a, b in zip(out_p, out_r):
        assert a.tokens == b.tokens, (a.uid, a.tokens, b.tokens)
    assert sum(len(c.tokens) for c in out_p) > 12
    print("DECODE_PARITY_OK")
""")


def test_multidevice_decode_parity():
    """On an 8-device pool the decode pipeline spreads stages over real
    devices (caches resident per slice, activations device-to-device) and
    still generates token-identical completions to the single-device
    serve_round."""
    r = subprocess.run([sys.executable, "-c", _SERVE_MULTIDEV],
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    assert "DECODE_PARITY_OK" in r.stdout


# ===========================================================================
# act-edge staging: only the activation goes to the device; a token's
# group id and a decode op's position stay host scalars
# ===========================================================================
_STAGING_MULTIDEV = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ShapeCfg
    from repro.configs.tiny import CONFIG as tiny
    from repro.core import planner
    from repro.graphs import lm_graph
    from repro.runtime.pipeline import DecodePipeline, as_selection
    from repro.runtime.pipeline.decode import _ServeRun

    shape = ShapeCfg("staging", 64, 16, "decode")
    plan = planner.plan(tiny, shape, chips=8, max_tp=4)
    stg, _ = lm_graph.build_stg(tiny, shape, max_tp=4)
    sel = as_selection(plan)
    # two replicas on blocks00, so edge 0 (embed -> blocks00) has a
    # consumer device to choose by group
    L = len(tiny.block_pattern)
    for n in stg.topo_order():
        if n.startswith("block") and int(n[5:]) < L:
            sel.set(n, sel.choices[n][0], 2)
    pipe = DecodePipeline(tiny, stg, sel, warmup=False)
    cons_devs = pipe.stage_devices[1]
    assert len(set(cons_devs)) == 2, cons_devs

    def staged(seq, gid, y, rep_map=None):
        run = _ServeRun(pipe, [], eos_id=1, capacity_blocks=2,
                        overlap=True)
        run.programs[1].rep_map.update(rep_map or {})
        fifo = run.acts[0]
        fifo.push([(seq, (gid, y))], 0.0)
        assert fifo.stats.prefetches == 1
        return fifo.pop_hold(1)[0]

    def y_on_producer():
        return jax.device_put(jnp.ones((4, 1, tiny.d_model)),
                              pipe.stage_devices[0][0])

    def gid_is_host_int():
        seq_got, (gid_got, _) = staged(0, 1, y_on_producer())
        assert type(seq_got) is int and seq_got == 0, seq_got
        assert type(gid_got) is int and gid_got == 1, gid_got

    def y_on_gid_replica():
        # seq 0 and group 1 pick different replicas of the consumer
        y = y_on_producer()
        _, (gid_got, y_got) = staged(0, 1, y)
        assert not isinstance(gid_got, jax.Array), gid_got
        assert y_got.devices() == {cons_devs[1]}, y_got.devices()
        assert (np.asarray(y_got) == np.asarray(y)).all()

    def y_follows_rep_map():
        # failover moved group 1 to replica 0: staging reads the
        # consumer program's routing, not the affinity rule
        _, (_, y_got) = staged(1, 1, y_on_producer(), rep_map={1: 0})
        assert y_got.devices() == {cons_devs[0]}, y_got.devices()

    def donated_y_raises():
        y = y_on_producer()
        y.delete()
        try:
            staged(0, 1, y)
        except RuntimeError as e:
            assert "prefetch on act edge 0->1 (gid=1)" in str(e), e
            assert "deleted (donated)" in str(e), e
        else:
            raise AssertionError("a donated activation was staged")

    out = {}
    for case in (gid_is_host_int, y_on_gid_replica, y_follows_rep_map,
                 donated_y_raises):
        try:
            case()
            out[case.__name__] = "ok"
        except Exception as e:
            out[case.__name__] = f"{type(e).__name__}: {e}"[:500]
    print("STAGING", json.dumps(out))
""")


@pytest.fixture(scope="module")
def staging_cases():
    r = subprocess.run([sys.executable, "-c", _STAGING_MULTIDEV],
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    line = [l for l in r.stdout.splitlines() if l.startswith("STAGING ")]
    assert line, r.stdout[-2000:]
    return json.loads(line[-1][len("STAGING "):])


@pytest.mark.parametrize("case", ["gid_is_host_int", "y_on_gid_replica",
                                  "y_follows_rep_map", "donated_y_raises"])
def test_act_edge_staging_puts_only_the_activation(staging_cases, case):
    """An act edge's prefetch stages a queued ``(seq, (gid, y))``: the
    group id comes back the host int pushed, only ``y`` moves, onto the
    device of the consumer replica serving the group, and a donated
    ``y`` raises the donation error naming the edge (8 CPU devices,
    subprocess)."""
    assert staging_cases[case] == "ok", staging_cases[case]


@pytest.mark.parametrize("field", ["seq", "gid"])
def test_act_edge_order_check_fires(decode_setup, monkeypatch, field):
    """A token reaching an act edge's consumer under the wrong
    ``(seq, gid)`` stops the serve with ``fifo order broke``."""
    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan)
    push = Fifo.push_reserved
    bad = []

    def misorder(self, tokens, ready_time):
        if not bad:
            ((seq, (gid, y)),) = tokens
            tokens = [(seq + 1, (gid, y)) if field == "seq"
                      else (seq, (gid + 1, y))]
            bad.append(tokens)
        return push(self, tokens, ready_time)

    monkeypatch.setattr(Fifo, "push_reserved", misorder)
    with pytest.raises(AssertionError, match="fifo order broke"):
        pipe.serve([list(range(2, 12))] * 8, 4, group_size=4)
    assert bad


def test_served_wave_hands_dispatch_host_scalars(decode_setup, monkeypatch):
    """With overlap on, every token an act edge hands ``dispatch`` carries
    its group id as a host int, and every decode op's position is a host
    int32 scalar — no device value where the engine thread compares or
    converts a scalar — and the tokens are those of the unwrapped
    serve."""
    import jax
    from repro.runtime.pipeline.decode import _ServeStageProgram

    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan, overlap=True)
    prompts = [list(range(2, 12))] * 8
    ref = pipe.serve(prompts, 6, group_size=4).tokens

    popped, positions = [], []
    pop_hold, task_for = Fifo.pop_hold, _ServeStageProgram._task_for

    def recording_pop_hold(self, n=None):
        out = pop_hold(self, n)
        popped.extend(out)
        return out

    def recording_task_for(self, kind, gid, pos, payload, rep):
        fn, args = task_for(self, kind, gid, pos, payload, rep)
        if kind == "D" and self.pipe.stage_descs[self.s].span is not None:
            positions.append(args[2][2])
        return fn, args

    monkeypatch.setattr(Fifo, "pop_hold", recording_pop_hold)
    monkeypatch.setattr(_ServeStageProgram, "_task_for", recording_task_for)
    got = pipe.serve(prompts, 6, group_size=4).tokens

    assert got == ref
    assert popped and positions
    for seq, (gid, _y) in popped:
        assert type(seq) is int and type(gid) is int, (seq, gid)
    for pos in positions:
        assert not isinstance(pos, jax.Array), pos
        assert isinstance(pos, np.int32), type(pos)
