"""Client entry: waves of a closed loop through the program's serving
path, planner -> `lm_graph.build_stg` -> `DecodePipeline` -> `LMServer`.

`LMServer.serve` takes a whole list of requests and returns when every
group is done, so a closed loop of ``clients`` callers becomes waves of
``clients`` requests, handed over together.  Each request is timed on
the harness's clock from that hand-over.  The program reports when each
group's tokens were sampled relative to its engine's own start, which
the result does not carry; that start is placed at the serve's return
minus the engine's wall time (`ServeRunResult.wall_s`), which puts every
token a few milliseconds late at most: the bookkeeping between the
engine's end and the serve's return.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from dataclasses import dataclass, field

from chipbench.registry import REPO

NO_EOS = -1            # no sampled id equals it: every request runs to max_new


@dataclass
class Done:
    prompt_len: int
    max_new: int
    handover: float                 # harness clock, s
    tokens: list
    times: list                     # harness clock, s, one per token


@dataclass
class WaveRecord:
    handover: float
    requests: list = field(default_factory=list)     # Done
    groups: list = field(default_factory=list)       # (batch, bucket, decode steps)
    dispatch_s: float = 0.0
    firings: int = 0


def _import_program() -> None:
    """The system under test is the repository's ``src`` tree."""
    src = str(REPO / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def _attr(obj, path: str):
    for name in path.split("."):
        obj = getattr(obj, name)
    return json.loads(json.dumps(obj))          # tuples as lists, as JSON has them


def program_config(spec: dict):
    """The program's `ModelConfig` for a configuration file, checked
    against the sizes the file states: the file's ``program`` section
    names the program's field for each key of its ``model`` section
    (``fields``) and the values other fields must hold (``fixed``)."""
    _import_program()
    from repro.configs import get_config
    p = spec["program"]
    cfg = get_config(p["arch"])
    if p.get("reduced"):
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, **p.get("replace", {}))
    m = spec["model"]
    unchecked = set(m) - set(p["fields"]) - {"kind"}
    if unchecked:
        raise ValueError(f"{cfg.name}: no program field named for {sorted(unchecked)}")
    wrong = {k: (_attr(cfg, f), m[k]) for k, f in p["fields"].items() if _attr(cfg, f) != m[k]}
    wrong.update({f: (_attr(cfg, f), v) for f, v in p["fixed"].items() if _attr(cfg, f) != v})
    if wrong:
        raise ValueError(f"{cfg.name}: the program's config departs from the "
                         f"configuration file (program, file): {wrong}")
    return cfg


def bucket(n: int) -> int:
    """The server's prompt bucket: the least power of two >= n, from 16."""
    b = 16
    while b < n:
        b *= 2
    return b


def shape_classes(wave, group: int) -> tuple:
    """(batch, prompt bucket, longest budget) of each serving group of a
    wave, in the order the server forms them: the programs a wave needs."""
    return tuple((len(g), bucket(max(len(r.prompt) for r in g)), max(r.max_new for r in g))
                 for g in (wave[i:i + group] for i in range(0, len(wave), group)))


class Session:
    """One server over the chip, serving the cell's waves."""

    def __init__(self, spec: dict, mix: dict, params, devices, seq_len: int):
        _import_program()
        import jax
        from repro.configs.base import ShapeCfg
        from repro.core import planner
        from repro.graphs import lm_graph
        from repro.models import lm
        from repro.runtime.pipeline import DecodePipeline
        from repro.runtime.server import LMServer
        from chipbench import weights

        cfg = program_config(spec)
        want = weights.layout_of(jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0))))
        have = weights.layout_of(params)
        if want != have:
            raise ValueError(f"the program's parameter tree differs from the benchmark's: "
                             f"{sorted(set(want.items()) ^ set(have.items()))[:6]}")
        self.group = mix["max_batch"]
        shape = ShapeCfg("chipbench", seq_len, mix["clients"], "decode")
        plan = planner.plan(cfg, shape, chips=len(devices))
        stg, _ = lm_graph.build_stg(cfg, shape)
        self.pipe = DecodePipeline(cfg, stg, plan, devices=list(devices), params=params)
        self.server = LMServer(cfg, max_batch=self.group, pipeline=self.pipe, eos_id=NO_EOS)
        self.stage_count = len(self.pipe.stage_names)

    def warm(self, waves) -> None:
        """Compile and run once every shape class the waves hold, through
        `DecodePipeline.serve` with an admission pause after one decode
        step: each group prefills, decodes one token and parks."""
        seen = set()
        for wave in waves:
            classes = shape_classes(wave, self.group)
            if classes in seen:
                continue
            seen.add(classes)
            self.pipe.serve([r.prompt.tolist() for r in wave], [r.max_new for r in wave],
                            eos_id=NO_EOS, group_size=self.group, pause_after_tokens=1)

    @property
    def late_compiles(self) -> int:
        return self.pipe.compile_stats.late

    def serve(self, wave) -> WaveRecord:
        from repro.runtime.server import Request
        reqs = [Request(uid=i, prompt=r.prompt.tolist(), max_new=r.max_new)
                for i, r in enumerate(wave)]
        t_hand = time.perf_counter()
        outs = self.server.serve(reqs)
        t_ret = time.perf_counter()
        run = self.server.last_run
        t0 = t_ret - run.wall_s
        rec = WaveRecord(handover=t_hand,
                         dispatch_s=sum(run.stage_dispatch_s.values()),
                         firings=sum(run.stage_firings.values()))
        rec.groups = [(g.batch, g.bucket, g.steps) for g in run.groups]
        for i, (r, c) in enumerate(zip(wave, outs)):
            g = run.groups[run.group_of[i]]
            stamps = [g.t_prefill_done] + list(g.decode_done_s)
            rec.requests.append(Done(prompt_len=len(r.prompt), max_new=r.max_new,
                                     handover=t_hand, tokens=list(c.tokens),
                                     times=[t0 + s for s in stamps[:len(c.tokens)]]))
        return rec

    def close(self) -> None:
        self.server = self.pipe = None
