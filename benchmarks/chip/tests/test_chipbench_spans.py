"""The program's spans read back: the plain form from an xplane with its
counters, the four ``program_span`` readers, each span's self time and
the device's idle time by the innermost open span — on a hand-made
trace whose values are worked out below."""
import pytest

import chipbench_testkit
from chipbench import registry, spans

MS = 1_000_000                                     # ns
READERS = ("serve.prepare_ms", "engine.wait_share", "engine.sweep_us_per_op",
           "head.sample_ms")


def _hand_trace():
    """Window [0, 10 ms).  The caller/engine thread prepares one wave,
    runs its engine from the start mark at 1 ms to the end mark at 7 ms —
    sweeps at [1, 2) [4, 4.5) [6, 7) dispatching 2 + 1 + 1 ops, waits at
    [2, 4) and [4.5, 6), retirements with the head's sampling nested in
    the first and third sweep — and folds the result [7, 7.5).  The next
    wave's prepare starts at 8 ms and is cut by the window's end; one
    before the window does not count.  A worker runs three stage ops."""
    main, pool = "main/0", "pool/1"
    return {
        "window": [0, 10 * MS],
        "spans": [
            [main, "serve.prepare", -3 * MS, 2 * MS, {"groups": 2, "requests": 8}],
            [main, "serve.prepare", 0, 1 * MS, {"groups": 2, "requests": 8}],
            [main, "serve.engine.start", 1 * MS, 0, {}],
            [main, "engine.sweep", 1 * MS, 1 * MS, {"dispatched": 2}],
            [main, "engine.retire", 1_200_000, 500_000, {}],
            [main, "head.sample", 1_300_000, 300_000, {"kind": "P", "batch": 4}],
            [main, "engine.wait", 2 * MS, 2 * MS, {"reason": "worker"}],
            [main, "engine.sweep", 4 * MS, 500_000, {"dispatched": 1}],
            [main, "engine.wait", 4_500_000, 1_500_000, {"reason": "device"}],
            [main, "engine.sweep", 6 * MS, 1 * MS, {"dispatched": 1}],
            [main, "engine.retire", 6_100_000, 800_000, {}],
            [main, "head.sample", 6_200_000, 500_000, {"kind": "D", "batch": 4}],
            [main, "serve.engine.end", 7 * MS, 0, {"slots": 40, "real_tokens": 36}],
            [main, "serve.finish", 7 * MS, 500_000, {}],
            [main, "serve.prepare", 8 * MS, 3 * MS, {"groups": 2, "requests": 8}],
            [pool, "stage.embed", 1_100_000, 50_000, {"stage": 0, "kind": "P"}],
            [pool, "stage.block.prefill", 2_500_000, 1 * MS, {"stage": 1, "kind": "P"}],
            [pool, "stage.head", 4_600_000, 200_000, {"stage": 2, "kind": "D"}],
        ],
        # device 0 busy [0, .5) [1.65, 2.2) [3, 3.2) [7.2, 7.6) [8, 10) ms;
        # device 1 is not one of the cell's
        "device_ops": [[0, 0, 500_000], [0, 1_650_000, 550_000], [0, 3 * MS, 200_000],
                       [0, 7_200_000, 400_000], [0, 8 * MS, 2 * MS], [1, 0, 10 * MS]],
    }


def test_readers_on_the_hand_trace():
    tr = _hand_trace()
    # prepare: 1 ms, and 2 ms of the cut one
    assert spans.prepare_ms(tr) == pytest.approx(1.5)
    # waits 2 + 1.5 ms of the engine's 6 ms
    assert spans.engine_ns(tr) == 6 * MS
    assert spans.wait_share(tr) == pytest.approx(100 * 3.5 / 6)
    # sweep self time .5 + .5 + .2 ms over 4 ops
    assert spans.sweep_us_per_op(tr) == pytest.approx(300.0)
    # the head's sampling .3 and .5 ms
    assert spans.sample_ms(tr) == pytest.approx(0.4)


def test_self_times_on_the_hand_trace():
    got = spans.self_times(_hand_trace())
    assert got["engine.sweep"] == [3, 2.5 * MS, 1.2 * MS]
    assert got["engine.retire"] == [2, 1.3 * MS, 0.5 * MS]
    assert got["head.sample"] == [2, 0.8 * MS, 0.8 * MS]
    assert got["engine.wait"] == [2, 3.5 * MS, 3.5 * MS]
    assert got["serve.prepare"] == [2, 3 * MS, 3 * MS]
    assert got["stage.block.prefill"] == [1, 1 * MS, 1 * MS]
    assert got["serve.engine.start"] == [1, 0, 0]


def test_idle_by_innermost_span_on_the_hand_trace(capsys):
    """Idle [.5, 1.65) [2.2, 3) [3.2, 7.2) [7.6, 8) ms, each instant put
    down to the open span that started last: the worker's prefill inside
    the engine's wait takes [2.5, 3.5) less the busy [3, 3.2)."""
    tr = _hand_trace()
    got = dict(spans.idle_by_span(tr, [0]))
    want = {"serve.prepare": 0.5, "engine.sweep": 0.85, "stage.embed": 0.05,
            "engine.retire": 0.45, "head.sample": 0.8, "engine.wait": 2.1,
            "stage.block.prefill": 0.8, "stage.head": 0.2, "serve.finish": 0.2,
            "untraced": 0.4}
    assert set(got) == set(want)
    for name, ms in want.items():
        assert got[name] == pytest.approx(ms * 1e-3), name
    assert sum(got.values()) == pytest.approx(6.35e-3)
    assert list(got)[0] == "engine.wait"            # longest first
    spans.report(tr, 1)
    err = capsys.readouterr().err
    assert "device idle 0.006350 s of a 0.010000 s window" in err
    assert "  untraced 0.000400 s (6.30%)" in err


def test_metric_files_read_the_spans(monkeypatch):
    """Each metric file reads its value off the run's spans, and nothing
    off a run whose program wrote none (one from before the spans)."""
    tr = _hand_trace()
    monkeypatch.setattr(spans, "of", lambda ctx: tr)
    values = {name: registry.metric(name).read(None) for name in READERS}
    assert values == {"serve.prepare_ms": pytest.approx(1.5),
                      "engine.wait_share": pytest.approx(100 * 3.5 / 6),
                      "engine.sweep_us_per_op": pytest.approx(300.0),
                      "head.sample_ms": pytest.approx(0.4)}
    monkeypatch.setattr(spans, "of", lambda ctx: None)
    assert all(registry.metric(name).read(None) is None for name in READERS)


def test_readers_read_nothing_without_their_spans():
    tr = {"window": [0, 10], "spans": [["main/0", "serve.finish", 1, 2, {}]],
          "device_ops": []}
    assert spans.prepare_ms(tr) is None and spans.sample_ms(tr) is None
    assert spans.wait_share(tr) is None and spans.sweep_us_per_op(tr) is None


def test_load_xplane_keeps_counters(tmp_path, monkeypatch):
    """A profile recorded on the host: the window mark, the program's
    spans with their counters, and no other host event; `of` loads it
    once per reading context, and a trace with no program span, or no
    trace, gives None."""
    import jax
    from chipbench import cli

    with jax.profiler.trace(str(tmp_path / "t")):
        with jax.profiler.TraceAnnotation("engine.sweep", dispatched=3):
            pass
        with jax.profiler.TraceAnnotation("chipbench.window"):
            with jax.profiler.TraceAnnotation("engine.sweep", dispatched=5):
                with jax.profiler.TraceAnnotation("stage.block.decode", stage=4, kind="D"):
                    pass
            with jax.profiler.TraceAnnotation("not.a.program.span"):
                pass
    tr = spans.load_xplane(str(tmp_path / "t"), {0})
    lo, hi = tr["window"]
    assert lo < hi and tr["device_ops"] == []
    got = {(n, tuple(sorted(st.items()))) for _, n, *_, st in tr["spans"]}
    assert got == {("engine.sweep", (("dispatched", 3),)),
                   ("engine.sweep", (("dispatched", 5),)),
                   ("stage.block.decode", (("kind", "D"), ("stage", 4)))}
    inside = spans.clipped(tr)
    assert [(n, st) for _, n, _, _, st in inside] == [
        ("engine.sweep", {"dispatched": 5}), ("stage.block.decode", {"stage": 4, "kind": "D"})]

    class Ctx:
        chips = 1
    monkeypatch.setattr(cli, "TRACE_DIR", tmp_path / "t")
    ctx = Ctx()
    first = spans.of(ctx)
    assert first is not None and spans.of(ctx) is first
    with jax.profiler.trace(str(tmp_path / "bare")):
        with jax.profiler.TraceAnnotation("chipbench.window"):
            pass
    monkeypatch.setattr(cli, "TRACE_DIR", tmp_path / "bare")
    assert spans.of(Ctx()) is None
    monkeypatch.setattr(cli, "TRACE_DIR", tmp_path / "none")
    assert spans.of(Ctx()) is None


def test_metrics_are_in_the_benchmark():
    bench = chipbench_testkit.bench_with_test_cells()
    rows = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert rows[name]["source"] == "program_span"
        assert rows[name]["workloads"] == ["mamba2-370m.chat-p512"]
