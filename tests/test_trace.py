"""Observability layer (runtime/pipeline/trace + metrics + straggler).

Acceptance contract:
  * every dispatched op retires exactly once on its own track, and op
    spans on one replica never overlap (hypothesis, virtual clock);
  * watched-FIFO occupancy stays within [0, capacity] at every event;
  * both clock drivers emit *identical* per-track event sequences for
    the same `Program` (timestamps aside) — the one-event-model claim;
  * stall-based bottleneck attribution blames the stage the costs say
    is slow (credit waits blame the consumer, starves the producer);
  * the metrics registry, serving-SLO percentiles, straggler detector,
    deadlock diagnostics, and the measure-layer stall/starve columns
    behave as documented.
"""
import json

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.runtime.pipeline import (Engine, Fifo, MetricsRegistry, Op,
                                    Tracer, attribute_bottleneck,
                                    detect_replica_stragglers, fill_drain,
                                    one_f_one_b, registry_from_trace,
                                    run_event_loop, schedule_programs,
                                    serving_slo, simulate_schedule,
                                    stall_bottleneck)
from repro.runtime.pipeline.metrics import Histogram, percentile
from repro.runtime.pipeline.trace import (EV_DISPATCH, EV_POP, EV_PUSH,
                                          EV_RETIRE)


def _traced_virtual(sched, f_cost=1.0, b_cost=None):
    tr = Tracer()
    simulate_schedule(sched, f_cost=f_cost, b_cost=b_cost, tracer=tr)
    return tr


# ===========================================================================
# event-stream invariants (hypothesis)
# ===========================================================================
@settings(max_examples=20)
@given(p=st.integers(1, 5), mult=st.integers(1, 4), train=st.booleans())
def test_one_dispatch_retire_pair_per_op(p, mult, train):
    m = p * mult
    sched = one_f_one_b(p, m) if train else fill_drain(p, m)
    tr = _traced_virtual(sched)
    per_track: dict = {}
    for ev in tr.events:
        if ev.kind in (EV_DISPATCH, EV_RETIRE):
            per_track.setdefault(ev.track, []).append(ev)
    assert per_track, "no op events traced"
    n_ops = 0
    for track, evs in per_track.items():
        open_ops: set = set()
        for ev in evs:
            key = (ev.name, ev.seq, ev.chunk)
            if ev.kind == EV_DISPATCH:
                assert key not in open_ops, f"double dispatch {key} on {track}"
                open_ops.add(key)
            else:
                assert key in open_ops, f"retire without dispatch {key}"
                open_ops.remove(key)
                n_ops += 1
        assert not open_ops, f"{track}: ops never retired: {open_ops}"
    assert n_ops == len(sched.flatten())


@settings(max_examples=20)
@given(p=st.integers(1, 5), mult=st.integers(1, 4), train=st.booleans())
def test_replica_spans_never_overlap(p, mult, train):
    m = p * mult
    sched = one_f_one_b(p, m) if train else fill_drain(p, m)
    tr = _traced_virtual(sched, f_cost=2.0, b_cost=3.0)
    spans: dict = {}
    for ev in tr.events:
        if ev.kind == EV_RETIRE:
            spans.setdefault(ev.track, []).append((ev.t0, ev.t))
    for track, ss in spans.items():
        ss.sort()
        for (a0, a1), (b0, b1) in zip(ss, ss[1:]):
            assert a1 <= b0 + 1e-9, \
                f"{track}: span ({a0},{a1}) overlaps ({b0},{b1})"


@settings(max_examples=20)
@given(p=st.integers(2, 5), mult=st.integers(1, 4), cap=st.integers(1, 3))
def test_fifo_occupancy_within_bounds(p, mult, cap):
    sched = one_f_one_b(p, p * mult)
    programs, _ = schedule_programs(sched, capacity_blocks=cap)
    tr = Tracer()
    for i, f in enumerate(programs[0].acts):
        tr.watch_fifo(f, f"act{i}")
    for i, f in enumerate(programs[0].grds):
        tr.watch_fifo(f, f"grd{i}")
    run_event_loop({pr.name: pr for pr in programs}, tracer=tr)
    seen = 0
    for ev in tr.events:
        if ev.kind in (EV_PUSH, EV_POP):
            seen += 1
            capacity = tr.fifo_watch[ev.track].capacity
            assert 0 <= ev.value <= capacity, \
                f"{ev.track}: occupancy {ev.value} outside [0, {capacity}]"
    assert seen > 0


@settings(max_examples=15)
@given(p=st.integers(1, 4), mult=st.integers(1, 3), train=st.booleans())
def test_wall_and_virtual_drivers_emit_identical_sequences(p, mult, train):
    """The one-event-model contract: the same Program under the wall
    clock (serial engine) and the virtual clock produces the same
    per-(stage, replica) op sequence — only timestamps differ.  FIFO
    tracks are compared as per-kind counts, not interleavings: when two
    stages are simultaneously ready the drivers may pick them in
    different (both valid) orders, so the cross-stage interleave of
    pushes and pops on one edge is scheduler policy, not contract —
    what must match is every edge moving the same number of tokens."""
    m = p * mult
    sched = one_f_one_b(p, m) if train else fill_drain(p, m)

    def run_driver(wall: bool):
        programs, _ = schedule_programs(sched)
        tr = Tracer()
        for i, f in enumerate(programs[0].acts):
            tr.watch_fifo(f, f"act{i}")
        for i, f in enumerate(programs[0].grds):
            tr.watch_fifo(f, f"grd{i}")
        if wall:
            Engine(programs, overlap=False, tracer=tr).run()
        else:
            run_event_loop({pr.name: pr for pr in programs}, tracer=tr)
        assert all(pr.pending() == 0 for pr in programs)
        ops, fifo_counts = {}, {}
        for track, seq in tr.track_sequences().items():
            if track in tr.fifo_watch:
                counts = fifo_counts.setdefault(track, {})
                for ev in seq:
                    counts[ev[0]] = counts.get(ev[0], 0) + 1
            else:
                ops[track] = seq
        return ops, fifo_counts

    assert run_driver(wall=True) == run_driver(wall=False)


# ===========================================================================
# bottleneck attribution
# ===========================================================================
def test_attribution_blames_slow_stage():
    """Make stage1 3x slower than its peers: upstream credit-waits into
    it, downstream starves behind it — both blame stage1."""
    sched = fill_drain(3, 12)
    tr = _traced_virtual(
        sched, f_cost=lambda s, op: 3.0 if s == 1 else 1.0)
    assert stall_bottleneck(tr) == "stage1"
    ranked = attribute_bottleneck(tr)
    blamed = {e.stage: e.blamed for e in ranked}
    assert blamed["stage1"] > blamed.get("stage0", 0.0)
    assert blamed["stage1"] > blamed.get("stage2", 0.0)
    # the fast neighbours wait more than they cause: excess capacity
    by_stage = {e.stage: e for e in ranked}
    assert by_stage["stage0"].excess > 0
    assert by_stage["stage1"].excess < 0


def test_attribution_matches_analytic_bottleneck_on_stg():
    """The interpreter path: stall attribution and the analytic model
    must finger the same stage on a graph with one clear bottleneck.
    The nearly-idle sink downstream of `encode` collects almost as much
    raw *blame* (encode credit-blocks on the burst-rate encode->
    bitstream edge), which is exactly the misattribution the busy-capped
    `stall_bottleneck` verdict exists to reject."""
    from repro.core.fork_join import JPEG_CALIBRATED
    from repro.core.stg import Selection
    from repro.core.throughput import analyze
    from repro.graphs import jpeg
    from repro.runtime.pipeline import execute

    g = jpeg.build_stg()
    sel = Selection.fastest(g)
    tr = Tracer()
    execute(g, sel, {"camera": jpeg.random_blocks(64)},
            fj=JPEG_CALIBRATED, tracer=tr)
    assert stall_bottleneck(tr) == analyze(g, sel).bottleneck


# ===========================================================================
# metrics registry
# ===========================================================================
def test_percentile_nearest_rank():
    xs = [10.0, 20.0, 30.0, 40.0]
    assert percentile(xs, 50) == 20.0
    assert percentile(xs, 99) == 40.0
    assert percentile([7.0], 50) == 7.0
    assert percentile([], 50) != percentile([], 50)      # nan


def test_histogram_ring_keeps_exact_count_and_max():
    h = Histogram()
    for i in range(10000):
        h.observe(float(i))
    assert h.count == 10000
    assert h.vmax == 9999.0
    assert len(h.samples) <= 4096
    assert h.summary()["count"] == 10000


def test_registry_labels_and_type_guard():
    reg = MetricsRegistry()
    reg.counter("x", stage="a").inc(2)
    reg.counter("x", stage="a").inc(3)
    reg.counter("x", stage="b").inc(1)
    assert reg.counter("x", stage="a").value == 5.0
    assert len(reg.find("x")) == 2
    with pytest.raises(TypeError):
        reg.gauge("x", stage="a")


def test_registry_from_trace_builds_stage_metrics():
    sched = fill_drain(3, 9)
    tr = _traced_virtual(sched, f_cost=2.0)
    reg = registry_from_trace(tr, wall_s=60.0)
    busy = {tuple(sorted(l.items())): m.value
            for l, m in reg.find("pipeline.busy_s")}
    assert busy[(("replica", "0"), ("stage", "stage0"))] == pytest.approx(18.0)
    hists = reg.find("pipeline.retire_latency_us")
    assert {dict(l)["stage"] for l, _ in hists} == \
        {"stage0", "stage1", "stage2"}
    for _, h in hists:
        assert h.count == 9 and h.percentile(50) == pytest.approx(2e6)
    utils = {dict(l)["stage"]: m.value
             for l, m in reg.find("pipeline.utilization")}
    assert 0.0 < utils["stage1"] <= 1.0


def test_serving_slo_shape():
    slo = serving_slo([0.001, 0.002], [0.1, 0.2], [0.01, 0.02, 0.03])
    assert set(slo) == {f"{p}_p{q}_ms" for p in
                        ("queue_wait", "ttft", "token_gap")
                        for q in (50, 95, 99)}
    assert slo["ttft_p50_ms"] == pytest.approx(100.0)
    assert slo["token_gap_p99_ms"] == pytest.approx(30.0)


# ===========================================================================
# straggler detection
# ===========================================================================
def _reg_with_replicas(lat_by_rep: dict[int, float], n: int = 32):
    reg = MetricsRegistry()
    for rep, lat in lat_by_rep.items():
        h = reg.histogram("pipeline.retire_latency_us",
                          stage="blk", replica=str(rep))
        for _ in range(n):
            h.observe(lat)
    return reg


def test_straggler_flags_slow_replica():
    reg = _reg_with_replicas({0: 100.0, 1: 100.0, 2: 300.0})
    out = detect_replica_stragglers(reg)
    assert [(s.stage, s.replica) for s in out] == [("blk", 2)]
    assert out[0].ratio == pytest.approx(3.0)
    assert "blk/r2" in out[0].describe()


def test_straggler_quiet_on_healthy_and_sparse_data():
    assert detect_replica_stragglers(
        _reg_with_replicas({0: 100.0, 1: 110.0, 2: 95.0})) == []
    # below min_samples: no verdict, even with a huge skew
    assert detect_replica_stragglers(
        _reg_with_replicas({0: 100.0, 1: 900.0}, n=3)) == []
    # single replica: no peers to lag behind
    assert detect_replica_stragglers(_reg_with_replicas({0: 100.0})) == []


# ===========================================================================
# chrome-trace export
# ===========================================================================
def test_chrome_trace_has_tracks_and_counters():
    sched = one_f_one_b(3, 6)
    tr = _traced_virtual(sched)
    ct = tr.to_chrome_trace()
    json.dumps(ct)                                   # serializable
    evs = ct["traceEvents"]
    tracks = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"stage0/r0", "stage1/r0", "stage2/r0"} <= tracks
    slices = [e for e in evs if e["ph"] == "X"]
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert len(slices) >= len(sched.flatten())
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in slices)
    assert "fifo act0" in counters and "fifo grd0" in counters


def test_save_roundtrip(tmp_path):
    tr = _traced_virtual(fill_drain(2, 4))
    path = tr.save(str(tmp_path / "trace.json"))
    with open(path) as f:
        assert json.load(f)["traceEvents"]


# ===========================================================================
# deadlock diagnostics
# ===========================================================================
def test_deadlock_report_attaches_fifo_and_trace_detail():
    fifo = Fifo(block=1, capacity_blocks=1)
    fifo.push([0], 0.0)                              # full from the start

    class Stuck:
        name = "writer"
        n_replicas = 1
        wait_reason = ("credit", fifo)

        def pending(self):
            return 1

        def peek(self):
            return Op(stage=0, kind="F", seq=0, rep=0)

        def ready(self, op, count_stall=False):
            return None

        def dispatch(self, op, driver):
            raise AssertionError

        def retire(self, *a):
            raise AssertionError

        def describe(self):
            return "writer: 0/1"

    tr = Tracer()
    tr.watch_fifo(fifo, "out", src="writer", dst="reader")
    eng = Engine([Stuck()], overlap=False, tracer=tr,
                 fifos={"out": fifo})
    with pytest.raises(RuntimeError, match="deadlock.*writer: 0/1") as ei:
        eng.run()
    msg = str(ei.value)
    assert "out=1/1" in msg                          # occupancy snapshot
    assert "credit" in msg and "on out" in msg       # who waits on what


def test_deadlock_message_first_line_unchanged_without_tracer():
    """The enriched report appends lines; the regex the engine tests pin
    (`deadlock.*stuck: 0/1`) keeps matching the first line untouched."""

    class Stuck:
        name = "stuck"
        n_replicas = 1

        def pending(self):
            return 1

        def peek(self):
            return Op(stage=0, kind="F", seq=0, rep=0)

        def ready(self, op, count_stall=False):
            return None

        def dispatch(self, op, driver):
            raise AssertionError

        def retire(self, *a):
            raise AssertionError

        def describe(self):
            return "stuck: 0/1"

    with pytest.raises(RuntimeError, match="deadlock.*stuck: 0/1"):
        Engine([Stuck()], overlap=False).run()


# ===========================================================================
# measure-layer surfacing
# ===========================================================================
def test_measure_summary_stall_columns_and_json_omission():
    from repro.core.fork_join import JPEG_CALIBRATED
    from repro.core.stg import Selection
    from repro.graphs import jpeg
    from repro.runtime.pipeline import compare, execute

    g = jpeg.build_stg()
    sel = Selection.fastest(g)
    blocks = jpeg.random_blocks(64)
    tr = Tracer()
    rep = compare(g, sel, execute(g, sel, {"camera": blocks},
                                  fj=JPEG_CALIBRATED, tracer=tr))
    assert "stall" in rep.summary() and "starve" in rep.summary()
    assert "host -" in rep.summary()                 # virtual clock: n/a
    stages = json.loads(rep.to_json())["stages"]
    assert all("host_us" not in s for s in stages.values())
    assert any("stall" in s for s in stages.values())

    rep2 = compare(g, sel, execute(g, sel, {"camera": blocks},
                                   fj=JPEG_CALIBRATED))
    stages2 = json.loads(rep2.to_json())["stages"]
    assert all("stall" not in s and "starve" not in s
               for s in stages2.values())            # untraced: omitted
    assert "None" not in rep2.summary()


def test_overhead_untraced_path_identical_results():
    """Tracing off must not change execution: same outputs, same cycle
    count, no tracer attribute left on any fifo."""
    from repro.core.fork_join import JPEG_CALIBRATED
    from repro.core.stg import Selection
    from repro.graphs import jpeg
    from repro.runtime.pipeline import execute

    g = jpeg.build_stg()
    sel = Selection.fastest(g)
    blocks = jpeg.random_blocks(64)
    tr = Tracer()
    traced = execute(g, sel, {"camera": blocks}, fj=JPEG_CALIBRATED,
                     tracer=tr)
    plain = execute(g, sel, {"camera": blocks}, fj=JPEG_CALIBRATED)
    assert traced.outputs == plain.outputs
    assert traced.cycles == plain.cycles
    assert all(f.tracer is None for f in plain.channels.fifos.values())
    assert not plain.wait_cycles and traced.wait_cycles


# ===========================================================================
# profiler spans: the serving path's own spans in the profiler's trace
# ===========================================================================
@pytest.fixture(scope="module")
def serve_setup():
    import numpy as np

    from repro.configs.base import ShapeCfg
    from repro.configs.tiny import CONFIG as tiny
    from repro.core import planner
    from repro.graphs import lm_graph
    from repro.runtime.pipeline import DecodePipeline
    from repro.runtime.server import Request

    shape = ShapeCfg("decode_test", 64, 16, "decode")
    plan = planner.plan(tiny, shape, chips=8, max_tp=4)
    stg, _ = lm_graph.build_stg(tiny, shape, max_tp=4)
    pipe = DecodePipeline(tiny, stg, plan)

    def reqs(n, max_new):
        rng = np.random.default_rng(n)
        return [Request(uid=i, prompt=rng.integers(
                    2, tiny.vocab, rng.integers(4, 20)).tolist(),
                    max_new=max_new) for i in range(n)]
    return tiny, pipe, reqs


def _host_spans(log_dir):
    """(name, start_ns, dur_ns, stats) of every program span in the one
    xplane under ``log_dir``."""
    import glob

    from jax.profiler import ProfileData

    from repro.runtime.pipeline.trace import SPAN_NAMES, SPAN_PREFIXES
    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    assert len(files) == 1
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name == "/host:CPU":
            out.extend((ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
                       for line in plane.lines for ev in line.events
                       if ev.name in SPAN_NAMES
                       or ev.name.startswith(SPAN_PREFIXES))
    return out


def test_span_is_shared_null_without_profiler(serve_setup, monkeypatch):
    """No profiler active: `span` hands back one shared null context that
    takes metadata and does nothing, and a whole pipelined serve builds
    no `TraceAnnotation` at all."""
    from repro.runtime.pipeline import trace as trace_mod
    from repro.runtime.server import LMServer

    tiny, pipe, reqs = serve_setup
    assert trace_mod.span("a") is trace_mod.span("b", k=1) \
        is trace_mod.NULL_SPAN
    with trace_mod.span("a") as sp:
        sp.set_metadata(k=1)
    built = []

    class Counting(trace_mod.TraceAnnotation):
        def __init__(self, *args, **kw):
            built.append(args)
            super().__init__(*args, **kw)
    monkeypatch.setattr(trace_mod, "TraceAnnotation", Counting)
    out = LMServer(tiny, max_batch=4, pipeline=pipe).serve(reqs(8, 4))
    assert len(out) == 8 and all(len(c.tokens) == 4 for c in out)
    assert built == []


def test_profiled_serve_writes_every_span(serve_setup, tmp_path):
    """Under `jax.profiler.trace` a serve writes every span of the table,
    a stage span per program, compile spans for the shapes it warms, and
    the counters: the end mark's slots and real tokens are what
    ``server.slot_waste_share`` computes from the groups, the sweeps'
    ``dispatched`` add up to the ops the engine ran.  The `Tracer`'s
    events, placed on the profiler's clock from the start mark, lie
    between the engine's two marks."""
    import jax

    from repro.runtime.pipeline.trace import (SPAN_ENGINE_END,
                                              SPAN_ENGINE_START, SPAN_NAMES)
    from repro.runtime.server import LMServer

    tiny, pipe, reqs = serve_setup
    tr = Tracer()
    srv = LMServer(tiny, max_batch=3, pipeline=pipe, tracer=tr)
    batch = reqs(6, 5)
    with jax.profiler.trace(str(tmp_path)):
        out = srv.serve(batch)
    spans = _host_spans(tmp_path)
    names = {n for n, *_ in spans}
    assert set(SPAN_NAMES) <= names
    programs = (pipe._embed, pipe._block_prefill, pipe._block_decode,
                pipe._head)
    assert {p.stage_span for p in programs} <= names
    assert {f"compile.{p.name}" for p in programs} <= names

    run = srv.last_run
    (end,) = [st for n, _, _, st in spans if n == SPAN_ENGINE_END]
    assert end["slots"] == sum(g.batch * g.bucket + g.batch * g.steps
                               for g in run.groups)
    assert end["real_tokens"] == sum(
        len(r.prompt) + max(len(c.tokens) - 1, 0)
        for r, c in zip(batch, out))
    assert end["late_compiles"] == 0
    assert sum(st.get("dispatched", 0) for n, _, _, st in spans
               if n == "engine.sweep") == sum(run.stage_firings.values())
    stage = [st for n, _, _, st in spans if n.startswith("stage.")]
    assert len(stage) == sum(run.stage_firings.values())
    assert {st["kind"] for st in stage} == {"P", "D"}
    assert {st["stage"] for st in stage} == set(range(len(pipe.stage_names)))
    heads = [st for n, _, _, st in spans if n == "head.sample"]
    assert len(heads) == sum(1 + g.steps for g in run.groups)
    assert {st["batch"] for st in heads} == {3}

    (t_start,) = [s for n, s, _, _ in spans if n == SPAN_ENGINE_START]
    (t_end,) = [s for n, s, _, _ in spans if n == SPAN_ENGINE_END]
    assert t_end - t_start == pytest.approx(run.wall_s * 1e9, rel=0.05)
    events = tr.to_profiler_ns(t_start)
    assert events
    slack = 1e6                      # the marks are read just after t0/wall
    assert all(t_start <= s and s + d <= t_end + slack
               for _, _, s, d in events)
    assert set(run.slo()) >= {"ttft_p50_ms", "token_gap_p95_ms"}


def test_stage_programs_carry_their_names(serve_setup):
    """Each stage program compiles as a module named after its program,
    so a device trace tells them apart."""
    _tiny, pipe, _reqs = serve_setup
    for prog in (pipe._embed, pipe._block_prefill, pipe._block_decode,
                 pipe._head):
        texts = prog.compiled_texts()
        assert texts and all(t.startswith(f"HloModule jit_{prog.name},")
                             for t in texts)


def test_tracer_to_profiler_ns():
    """Op, wait and failover spans move onto the profiler's clock from the
    start mark; fifo events are not spans; a virtual trace has no
    profiler time."""
    tr = Tracer()
    tr.bind_wall(0.0)
    tr.op_retire("blocks00", 0, "D", 7, 0, 0.5, 0.75)
    tr.wait("head", "starve", "act3", 1.0, 1.25)
    tr.failover("blocks00", 1, "crash", 2.0, 2.5, 3)
    tr.fifo_event(EV_PUSH, "act0", 1)
    got = tr.to_profiler_ns(1e9)
    assert got == [("blocks00/r0", "D7", 1.5e9, 0.25e9),
                   ("head", "starve", 2.0e9, 0.25e9),
                   ("blocks00/r1", "failover (crash)", 3.0e9, 0.5e9)]
    virt = _traced_virtual(fill_drain(2, 2))
    with pytest.raises(ValueError):
        virt.to_profiler_ns(0)


def test_engine_start_end_hooks():
    """The generic engine calls its start hook as it reads ``t0`` and its
    end hook once ``wall_s`` is set."""
    calls = []

    class One:
        name, n_replicas = "one", 1

        def __init__(self):
            self.left = 2

        def pending(self):
            return self.left

        def peek(self):
            return Op(stage=0, kind="F", seq=2 - self.left, rep=0) \
                if self.left else None

        def ready(self, op, count_stall=False):
            return 0.0

        def dispatch(self, op, driver):
            self.left -= 1
            return (lambda: "done"), ()

        def retire(self, op, result, driver):
            assert result == "done"
            return driver.t0

        def describe(self):
            return "one"

    eng = Engine([One()], overlap=False,
                 on_start=lambda e: calls.append(("start", e.t0)),
                 on_end=lambda e: calls.append(("end", e.result.wall_s)))
    res = eng.run()
    assert [c[0] for c in calls] == ["start", "end"]
    assert calls[0][1] == eng.t0 and calls[1][1] == res.wall_s
    assert res.stage_firings["one"] == 2
