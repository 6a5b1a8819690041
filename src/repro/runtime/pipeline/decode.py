"""Decode-shape serving pipelines: prefill + token streams over placed stages.

The jax microbatch pipeline (`jax_pipe`) exercises train/prefill-style
traffic: a fixed list of microbatches, a schedule known up front.  Serving
is the other shape the planner prices (`SHAPES["decode_32k"]`): request
groups prefill once, then emit one token per step until every slot hits
EOS or its budget — traffic whose length is decided *by the pipeline's own
output*.  This module runs that shape on the same executor core:

  * stages are built from the *same model code* the single-device server
    runs — `models/lm.prefill_blocks` / `decode_blocks` over the stage's
    periods of the stacked parameters — so a pipelined serve is
    token-identical to `LMServer.serve_round` under greedy sampling; on
    the device that holds the model the stages read its one copy of the
    weights (`_StageLayers`);
  * every block stage keeps its **KV/SSM cache slice resident on its
    placement slice**: the prefill op constructs the stage's cache shard
    on the stage's device, decode ops update it **in place** — the
    decode program donates the incoming cache (``donate_argnums``), so
    every leaf aliases onto the resident buffers and a token step
    allocates no new cache memory — and only the (B, 1, d_model) hidden
    state crosses inter-stage FIFOs;
  * request groups map to stage replicas by ``gid % nr`` (cache
    affinity), so a replicated stage serves groups concurrently exactly
    like the plan's round-robin replication;
  * the head stage samples on retirement and feeds the token back to the
    embed stage over a `channels.StreamChannel` — the continuous
    token-stream mode: decode ops are *scheduled as tokens arrive* (the
    engine's pending-or-inflight termination), and the stream closes when
    the last group drains;
  * all stage programs are `aot.AotProgram`s, AOT-compiled against each
    group's concrete shapes before the engine's clock starts
    (``warmup=``), and op bodies dispatch without host syncs — the
    engine retires them off completion futures — so no served request
    ever sees a compile or a per-op ``block_until_ready`` stall.

Placement folds tp > 1 slices onto their first device (decode stage
bodies are single-device jits; sharding decode over a sub-mesh is a
ROADMAP item) — the plan's replica structure, not its intra-stage
sharding, is what this backend executes.  Encoder-decoder and multimodal
frontends are rejected: the pipeline runs embed -> blocks -> head only.

`runtime/server.LMServer` uses this as its pipelined backend
(``LMServer(cfg, pipeline=DecodePipeline(...))``); see
`examples/serve_lm.py --pipeline` and `benchmarks/bench_serve.py`.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ...configs.base import ModelConfig
from ...core.stg import STG
from ...models import blocks, lm
from ...models.common import dtype_of, rmsnorm
from ..server import _bucket            # one bucketing rule: token parity
from .aot import AotProgram, CompileStats
from .channels import Fifo, StreamChannel, check_not_donated
from .engine import AsyncResult, Engine, EngineResult, Op, describe_position
from .placement import Placement, place
from .trace import (SPAN_ENGINE_END, SPAN_ENGINE_START, SPAN_HEAD_SAMPLE,
                    SPAN_SERVE_FINISH, SPAN_SERVE_PREPARE, mark, span)


# ===========================================================================
# stage computation (models/lm over period slices)
# ===========================================================================
def _embed_prefill_fn(cfg: ModelConfig):
    dt = dtype_of(cfg.compute_dtype)

    def fn(p, tokens):
        return jnp.take(p["embed"], tokens, axis=0).astype(dt)
    return fn


# A block-owning stage's params are {"layers": stack, "periods": int32
# index vector}: the periods it runs, read out of ``stack`` inside the
# program (`lm._scan_periods`).  On the device that holds the whole model
# the stack is the model's own (no copy); elsewhere it is the stage's
# slice, indexed from 0.
def _block_prefill_fn(cfg: ModelConfig, impl: str | None = None):
    def fn(p, x, cap):
        S = x.shape[1]
        return lm.prefill_blocks(cfg, p["layers"], x, jnp.arange(S), cap=cap,
                                 impl=impl, periods=p["periods"])
    return fn


def _block_decode_fn(cfg: ModelConfig, impl: str | None = None):
    def fn(p, cache, x, pos):
        return lm.decode_blocks(cfg, p["layers"], cache, x, pos, impl=impl,
                                periods=p["periods"])
    return fn


def _logits(cfg: ModelConfig, p, h):
    """Final norm + head on the last position.  ``p["w"]`` is the
    embedding itself when the head is tied: transposed inside the
    program, so no transposed copy is ever stored."""
    h = rmsnorm(h[:, -1:], p["norm"], cfg.norm_eps)
    w = p["w"].T if cfg.tie_embeddings else p["w"]
    return h @ w.astype(h.dtype)


def _head_fn(cfg: ModelConfig):
    def fn(p, x):
        return _logits(cfg, p, x)
    return fn


# Fused (combined) stage bodies: the sequential composition of the member
# stages as ONE jitted program — the executable form of
# `core.restructure.combine`.  A fused stage that absorbed embed takes raw
# token ids instead of hidden states; one that absorbed head emits logits.
# The member math is identical to the unfused programs (same models/lm
# calls in the same order), and `optimization_barrier` pins each member
# boundary as a materialisation point — numerically exactly what the
# deleted fifo hop did — so XLA cannot fuse across it and re-round the
# bf16 activations: token parity with the unfused pipeline is structural,
# not coincidental.
def _fused_prefill_fn(cfg: ModelConfig, has_embed: bool, has_head: bool,
                      impl: str | None = None):
    dt = dtype_of(cfg.compute_dtype)

    def fn(p, x, cap):
        if has_embed:
            x = jnp.take(p["embed"], x, axis=0).astype(dt)
            x = jax.lax.optimization_barrier(x)
        S = x.shape[1]
        y, cache = lm.prefill_blocks(cfg, p["layers"], x, jnp.arange(S),
                                     cap=cap, impl=impl, periods=p["periods"])
        if has_head:
            y = _logits(cfg, p, jax.lax.optimization_barrier(y))
        return y, cache
    return fn


def _fused_decode_fn(cfg: ModelConfig, has_embed: bool, has_head: bool,
                     impl: str | None = None):
    dt = dtype_of(cfg.compute_dtype)

    def fn(p, cache, x, pos):
        if has_embed:
            x = jnp.take(p["embed"], x, axis=0).astype(dt)
            x = jax.lax.optimization_barrier(x)
        y, cache = lm.decode_blocks(cfg, p["layers"], cache, x, pos,
                                    impl=impl, periods=p["periods"])
        if has_head:
            y = _logits(cfg, p, jax.lax.optimization_barrier(y))
        return y, cache
    return fn


@dataclass(frozen=True)
class _StageDesc:
    """One executed pipeline stage, possibly the fusion of several base
    stages.  ``members`` are the base stage names in chain order;
    ``span`` is the union of the members' block-period spans (None for a
    lone embed/head)."""
    name: str
    members: tuple[str, ...]
    has_embed: bool
    span: tuple[int, int] | None
    has_head: bool


class _StageLayers:
    """Where each block stage reads its periods from.  A stage on a device
    that holds the whole stacked ``layers`` indexes that stack; elsewhere
    the stage's slice is copied to its device once, shared by every
    replica placed there."""

    def __init__(self, layers):
        self.layers = layers
        self.home = set().union(*(leaf.devices()
                                  for leaf in jax.tree.leaves(layers)
                                  if isinstance(leaf, jax.Array)))
        self._slices: dict = {}

    def on(self, dev, lo: int, hi: int) -> dict:
        if self.home == {dev}:
            stack, first = self.layers, lo
        else:
            key = (dev, lo, hi)
            if key not in self._slices:
                self._slices[key] = jax.device_put(
                    lm.slice_periods(self.layers, lo, hi), dev)
            stack, first = self._slices[key], 0
        periods = jax.device_put(
            jnp.arange(first, first + hi - lo, dtype=jnp.int32), dev)
        return {"layers": stack, "periods": periods}


# ===========================================================================
# run state
# ===========================================================================
@dataclass
class _Group:
    """One serving slot group: a batch of requests decoding in lockstep,
    mirroring `LMServer.serve_round`'s round semantics exactly (same
    bucketing, same EOS/budget bookkeeping) so completions are
    token-identical."""
    gid: int
    tokens: np.ndarray                 # (B, bucket) right-aligned prompts
    bucket: int
    cap: int
    budget: np.ndarray
    prompt_tokens: int = 0             # real (unpadded) prompt tokens
    done: np.ndarray = None
    out_tokens: list = None
    steps: int = 0                     # completed decode steps
    cur: np.ndarray = None             # last sampled token per slot (B,)
    t_start: float = 0.0
    t_prefill_done: float = 0.0
    t_last: float = 0.0
    decode_done_s: list = field(default_factory=list)
    logits: list = field(default_factory=list)
    # head logits of the prefill and of each decode step, kept only when
    # the serve asks for them (``keep_logits``)
    fed: list = field(default_factory=list)
    # token history: fed[j] is the (B,) token batch fed back for decode
    # step j.  out_tokens is NOT enough to replay a cache — done slots
    # keep feeding their last sampled token in lockstep without emitting
    # it — so failover/rescale cache rebuilds read this instead.

    @property
    def batch(self) -> int:
        return self.tokens.shape[0]


@dataclass
class ServeRunResult(EngineResult):
    """One pipelined serve: per-request tokens + the engine's measurement
    surface (stage completion streams, fifo stats, trace).  As an
    `EngineResult` it exposes ``stage_inverse_us``, so a serve run feeds
    `measure.compare_lm(stg, sel, run,
    stage_map=pipe.graph_stage_map())` exactly like an LM microbatch run
    — serving traffic is a calibration source for re-planning too."""
    tokens: list = field(default_factory=list)   # per request, generated
    group_of: list = field(default_factory=list)  # request index -> group id
    groups: list = field(default_factory=list)   # _Group bookkeeping
    fifo_stats: dict = field(default_factory=dict)
    placement: Placement | None = None
    paused: bool = False               # admission-paused mid-stream
    resume_state: object = None        # `ResumeState` when paused

    @property
    def decode_tokens(self) -> int:
        return sum(len(t) for t in self.tokens)

    @property
    def prefill_tokens(self) -> int:
        return sum(g.batch * g.bucket for g in self.groups)

    def decode_done_s(self) -> list[float]:
        """Merged decode-step completion times across groups (run-relative,
        sorted) — the serving-side analogue of a stage's completion
        stream."""
        return sorted(t for g in self.groups for t in g.decode_done_s)

    def decode_tokens_per_s(self) -> float:
        """Steady-state generated tokens/s from the merged decode
        completion stream (excludes prefill and the fill ramp; falls back
        to wall-clock for very short runs)."""
        ts = self.decode_done_s()
        toks_per_step = (sum(g.batch for g in self.groups)
                         / max(1, len(self.groups)))
        if len(ts) >= 3:
            k = max(1, len(ts) // 4)
            w = ts[k:]
            if len(w) >= 2 and w[-1] > w[0]:
                return toks_per_step * (len(w) - 1) / (w[-1] - w[0])
        return self.decode_tokens / max(self.wall_s, 1e-9)

    def token_latencies_s(self) -> list[float]:
        """Per-token latency samples: gaps between successive decode-step
        completions *within* each group (what a client slot observes)."""
        out = []
        for g in self.groups:
            ts = [g.t_prefill_done] + list(g.decode_done_s)
            out.extend(b - a for a, b in zip(ts, ts[1:]))
        return out

    def slo(self) -> dict:
        """Per-request serving SLO percentiles (flat ms dict): queue wait
        (submit -> first prefill dispatch), TTFT (submit -> first sampled
        token), and inter-token gap — `metrics.serving_slo` over the
        group timings.  Groups are the unit a client slot experiences, so
        samples are per group, gaps per decoded token."""
        from .metrics import serving_slo
        return serving_slo(
            queue_wait_s=[g.t_start for g in self.groups],
            ttft_s=[g.t_prefill_done for g in self.groups],
            token_gap_s=self.token_latencies_s())


# ===========================================================================
# stage programs
# ===========================================================================
class _ServeStageProgram:
    """One serving stage's op queue on the shared engine.

    Ops arrive dynamically: prefill ops for all groups are enqueued up
    front; each decode op is enqueued (to *every* stage, with one global
    sequence number) the moment the head samples the previous token — the
    queue order is therefore identical across stages and every FIFO sees
    a contiguous seq stream, re-sorted by the engine's reorder buffers
    when replicas retire out of order."""

    def __init__(self, s: int, pipe: "DecodePipeline", run: "_ServeRun"):
        self.s = s
        self.S = len(pipe.stage_names)
        self.name = pipe.stage_names[s]
        self.pipe = pipe
        self.run = run
        self.n_replicas = len(pipe.stage_devices[s])
        self.queue: list = []          # (kind, gid, seq, pos)
        self.pos_i = 0
        self.stall_mark = -1
        self.wait_reason = None   # (reason, fifo) of the last deferral
        self.caches: dict[int, object] = {}    # gid -> resident cache slice
        # failover/rebalance state: group routing defaults to the cache-
        # affinity rule gid % n_replicas; rep_map overrides it after a
        # replica dies (or a straggler sheds load), dead marks replicas
        # the engine must never route to again
        self.rep_map: dict[int, int] = {}
        self.dead: set[int] = set()
        self.redo: list = []           # (kind, gid, seq, pos, payload):
        #                                lost ops re-issued under their
        #                                ORIGINAL seq so reorder holes fill
        self.done_count: dict[int, int] = {}   # gid -> retired ops here
        self.inflight: dict[int, int] = {}     # gid -> dispatched-unretired

    def enqueue(self, kind: str, gid: int, seq: int, pos: int) -> None:
        self.queue.append((kind, gid, seq, pos))

    def pending(self) -> int:
        return len(self.queue) - self.pos_i + len(self.redo)

    def rep_of(self, gid: int) -> int:
        return self.rep_map.get(gid, gid % self.n_replicas)

    def peek(self) -> Op | None:
        if self.redo:
            kind, gid, seq, _pos, _payload = self.redo[0]
            return Op(stage=self.s, kind=kind, seq=seq, rep=self.rep_of(gid))
        if self.pos_i >= len(self.queue):
            return None
        kind, gid, seq, _ = self.queue[self.pos_i]
        return Op(stage=self.s, kind=kind, seq=seq, rep=self.rep_of(gid))

    def ready(self, op: Op, count_stall: bool = False) -> float | None:
        s, S, run = self.s, self.S, self.run
        if self.redo:
            # a replayed op re-runs from its saved inputs and retires into
            # the slot its original dispatch already reserved — no fifo
            # state to wait for
            return 0.0
        if s > 0 and not run.acts[s - 1].can_pop(1):
            self.wait_reason = ("starve", run.acts[s - 1])
            return None
        if s == 0 and op.kind == "D" and not run.feedback.can_pop(1):
            self.wait_reason = ("starve", run.feedback)
            return None
        if s < S - 1 and not run.acts[s].can_push(1):
            if self.stall_mark != self.pos_i:
                self.stall_mark = self.pos_i
                run.acts[s].note_stall()
            self.wait_reason = ("credit", run.acts[s])
            return None
        return 0.0

    def idle_reason(self):
        """Why this stage's op queue is *empty*: the head hasn't sampled
        the token that schedules the next op yet, so the stage is starved
        on its input edge (the feedback stream for stage 0, the upstream
        act fifo otherwise).  None once the token stream closed — run
        drained, idleness isn't a wait.  The drivers consult this under
        tracing so source stages (embed) appear in
        ``stage_wait_s``/``per_stage_starve_ms`` instead of being
        silently absent (their queue is refilled and their feedback
        satisfied in the same head retirement, so the nonempty-queue wait
        path never fires for them)."""
        run = self.run
        if run.feedback.closed:
            return None
        src = run.feedback if self.s == 0 else run.acts[self.s - 1]
        return ("starve", src)

    def _task_for(self, kind: str, gid: int, pos: int, payload, rep: int):
        """Build the op body from in-hand inputs (``payload`` is the
        embedded/popped value) — shared by the normal dispatch path and
        failover replay, so a redo runs the exact math the lost op
        would have."""
        s, pipe = self.s, self.pipe
        g = self.run.groups[gid]
        desc = pipe.stage_descs[s]
        dev = pipe.stage_devices[s][rep]
        params = pipe.stage_params[s][rep]
        at = (s, kind)
        if desc.span is None:                             # lone embed / head
            prog = pipe._embed if desc.has_embed else pipe._head
            return (_run_stage, (prog, params, (payload,), dev, at))
        if desc.has_embed or desc.has_head:               # fused stage
            pre, dec = pipe._fused[(desc.has_embed, desc.has_head)]
        else:                                             # plain block stage
            pre, dec = pipe._block_prefill, pipe._block_decode
        if kind == "P":
            return (_run_stage_static_cap,
                    (pre, params, payload, g.cap, dev, at))
        # the position stays a host scalar: the op body puts it on the
        # op's device, so the engine thread launches no convert per op
        cache = self.caches[gid]
        return (_run_stage,
                (dec, params, (cache, payload, np.int32(pos)), dev, at))

    def dispatch(self, op: Op, driver):
        s, S, run = self.s, self.S, self.run
        if self.redo:
            # replay of a lost op: inputs were saved at its original
            # dispatch; that dispatch's downstream reservation is still
            # outstanding, so no pop and no reserve here — retirement
            # fills the reorder hole under the original seq
            kind, gid, seq, pos, payload = self.redo.pop(0)
            self.inflight[gid] = self.inflight.get(gid, 0) + 1
            return self._task_for(kind, gid, pos, payload, op.rep)
        kind, gid, seq, pos = self.queue[self.pos_i]
        self.pos_i += 1
        g = run.groups[gid]
        if s == 0:                                        # embed
            if kind == "P":
                g.t_start = time.perf_counter()
                payload = jnp.asarray(g.tokens)
            else:
                seq_got, (gid_got, toks) = run.feedback.pop(1)[0]
                assert (seq_got, gid_got) == (seq, gid), \
                    f"feedback order broke: {(seq_got, gid_got)}!={(seq, gid)}"
                payload = toks
        else:
            seq_got, (gid_got, x) = run.acts[s - 1].pop_hold(1)[0]
            assert (seq_got, gid_got) == (seq, gid), \
                f"fifo order broke: {(seq_got, gid_got)}!={(seq, gid)}"
            op.releases.append((run.acts[s - 1], 1))
            payload = x
        if s < S - 1:
            run.acts[s].reserve(1)
        op.recover = (kind, gid, seq, pos, payload)
        self.inflight[gid] = self.inflight.get(gid, 0) + 1
        return self._task_for(kind, gid, pos, payload, op.rep)

    def retire(self, op: Op, result, engine: Engine) -> float:
        s, run = self.s, self.run
        out, t_done = result
        gid = run.gid_of[op.seq]
        self.done_count[gid] = self.done_count.get(gid, 0) + 1
        self.inflight[gid] = self.inflight.get(gid, 1) - 1
        desc = self.pipe.stage_descs[s]
        y = out
        if desc.span is not None:                         # cache stays
            y, cache = out                                # resident here
            self.caches[gid] = cache
        if desc.has_head:                                 # head: sample
            run.on_head(op, y, t_done, engine)
        else:
            engine.ordered_push(run.acts[s], op.seq, (gid, y), t_done)
        return t_done

    # -- failover & rebalance -----------------------------------------------
    def fail_replica(self, rep: int, driver, lost: list) -> None:
        """Replica ``rep`` died: remap its groups onto survivors, rebuild
        the resident cache slices that died with it (deterministic replay
        from prompt + fed-token history — bitwise what the dead replica
        held), and queue the drained in-flight ops for redo under their
        original sequence numbers.  No survivors -> `PipelineFailure`
        (the engine attaches its diagnostic bundle)."""
        from ..failures import PipelineFailure
        self.dead.add(rep)
        alive = [r for r in range(self.n_replicas) if r not in self.dead]
        if not alive:
            raise PipelineFailure(
                f"stage {self.name}: replica r{rep} was the last one — "
                f"nothing left to fail over to",
                stage=self.name, replica=rep)
        moved = [gid for gid in range(len(self.run.groups))
                 if self.rep_of(gid) == rep]
        for i, gid in enumerate(moved):
            self.rep_map[gid] = alive[i % len(alive)]
        for op in lost:
            kind, gid, seq, pos, payload = op.recover
            self.inflight[gid] = self.inflight.get(gid, 1) - 1
            self.redo.append((kind, gid, seq, pos, payload))
        for gid in moved:
            if gid in self.caches and self.done_count.get(gid, 0) > 0:
                self.caches[gid] = self.pipe._replay_cache(
                    self.run, self.run.groups[gid], self.s,
                    self.done_count[gid], self.rep_map[gid])
            else:
                self.caches.pop(gid, None)

    def migrate_gid(self, gid: int, to_rep: int) -> bool:
        """Move one group to another replica between its ops (straggler
        shedding): the resident cache slice is *copied* to the new
        owner's device — the source replica is alive, so no replay is
        needed — and routing flips.  Refused while the group has an op
        in flight anywhere at this stage."""
        if self.inflight.get(gid) or to_rep in self.dead:
            return False
        if self.rep_of(gid) == to_rep:
            return True
        self.rep_map[gid] = to_rep
        if gid in self.caches:
            self.caches[gid] = jax.device_put(
                self.caches[gid], self.pipe.stage_devices[self.s][to_rep])
        return True

    def shed_replica(self, rep: int, max_groups: int = 1) -> int:
        """Shift dispatch share off a slow replica: migrate up to
        ``max_groups`` of its idle groups to the least-loaded healthy
        peer.  Returns how many actually moved."""
        peers = [r for r in range(self.n_replicas)
                 if r not in self.dead and r != rep]
        if not peers:
            return 0
        n_groups = len(self.run.groups)
        moved = 0
        for gid in range(n_groups):
            if moved >= max_groups:
                break
            g = self.run.groups[gid]
            if self.rep_of(gid) != rep or gid not in self.caches \
                    or g.done is not None and g.done.all():
                continue
            load = {r: sum(1 for g2 in range(n_groups)
                           if self.rep_of(g2) == r) for r in peers}
            to = min(peers, key=lambda r: (load[r], r))
            if self.migrate_gid(gid, to):
                moved += 1
        return moved

    def describe(self) -> str:
        return describe_position(
            self.name, self.pos_i, self.queue,
            lambda q: f"{q[0]}(gid={q[1]},seq={q[2]})")


def _run_stage(fn, params, args, dev, at):
    """Dispatch one stage program and return without a host sync: the
    engine retires the op off the watch set's completion future.  Watch
    the first output leaf only — a block stage's (hidden, cache) pair
    materialises together (one executable), and the resident cache slice
    is rebound at retirement, after that future fires.  ``at``: the
    (stage index, op kind) the op's ``stage.<program>`` span carries."""
    with span(fn.stage_span, stage=at[0], kind=at[1]):
        args = tuple(jax.device_put(a, dev) if hasattr(a, "shape") else a
                     for a in args)
        out = fn(params, *args)
    return AsyncResult((out,), watch=jax.tree.leaves(out)[:1])


def _run_stage_static_cap(fn, params, x, cap, dev, at):
    with span(fn.stage_span, stage=at[0], kind=at[1]):
        x = jax.device_put(x, dev)
        out = fn(params, x, cap)
    return AsyncResult((out,), watch=jax.tree.leaves(out)[:1])


class _ServeRun:
    """Shared state of one pipelined serve: groups, channels, the global
    op sequence, and the head-side sampling/bookkeeping."""

    def __init__(self, pipe: "DecodePipeline", groups: list, *,
                 eos_id: int, capacity_blocks: int, overlap: bool,
                 temperature: float | None = None,
                 pause_at: int | None = None,
                 open_groups: int | None = None,
                 feedback_capacity: int | None = None,
                 keep_logits: bool = False):
        self.pipe = pipe
        self.keep_logits = keep_logits
        self.groups = groups
        self.eos_id = eos_id
        self.temperature = temperature
        self.pause_at = pause_at       # admission pause: groups reaching
        self.parked: list[int] = []    # this many decode steps park (their
        #                                caches stay resident for export)
        #                                instead of feeding back
        self.gid_of: list[int] = []            # seq -> gid
        self.programs = [_ServeStageProgram(s, pipe, self)
                         for s in range(len(pipe.stage_names))]
        S = len(self.programs)
        self.acts = [pipe._edge_fifo(s, capacity_blocks, overlap,
                                     self.programs[s + 1].rep_of)
                     for s in range(S - 1)]
        # the continuous token stream: head -> embed feedback.  At most
        # one token per live group is ever in flight (a group's next op
        # consumes it before its next push), so n_groups slots suffice.
        # The head pushes here *unconditionally* at retirement, which is
        # why `verify_decode_plan` requires capacity >= n_groups — an
        # override below that statically fails preflight.
        fb_cap = feedback_capacity if feedback_capacity is not None \
            else max(2, len(groups))
        self.feedback = StreamChannel(block=1, capacity_blocks=1,
                                      min_capacity=fb_cap)
        self.open_groups = len(groups) if open_groups is None else open_groups
        # what this run's ops execute, for the ``serve.engine.end`` mark:
        # token slots (batch x bucket per prefill, batch per decode step)
        # and the real tokens among them (prompt tokens, and each
        # generated token after a request's first)
        self.slots = 0
        self.real_tokens = 0

    def enqueue(self, kind: str, gid: int, pos: int) -> int:
        g = self.groups[gid]
        if kind == "P":
            self.slots += g.batch * g.bucket
            self.real_tokens += g.prompt_tokens
        else:
            self.slots += g.batch
        seq = len(self.gid_of)
        self.gid_of.append(gid)
        for p in self.programs:
            p.enqueue(kind, gid, seq, pos)
        return seq

    def on_head(self, op: Op, logits, t_done: float, engine: Engine) -> None:
        """Sample at head retirement and schedule the group's next decode
        step (or retire the group) — `LMServer.serve_round` bookkeeping,
        verbatim, so completions are token-identical."""
        g = self.groups[self.gid_of[op.seq]]
        if self.keep_logits:
            g.logits.append(logits)
        with span(SPAN_HEAD_SAMPLE, kind=op.kind, batch=g.batch):
            nxt = np.asarray(self.pipe._sample(logits, g.gid,
                                               self.temperature))
        if op.kind == "P":
            g.t_prefill_done = t_done - engine.t0
            g.cur = nxt.astype(np.int32)
            for i in range(g.batch):
                g.out_tokens[i] = [int(nxt[i])]
            g.done = np.array([t[0] == self.eos_id for t in g.out_tokens])
        else:
            g.steps += 1
            g.decode_done_s.append(t_done - engine.t0)
            for i in range(g.batch):
                if not g.done[i] and g.steps < g.budget[i]:
                    tok = int(nxt[i])
                    g.out_tokens[i].append(tok)
                    self.real_tokens += 1
                    if tok == self.eos_id:
                        g.done[i] = True
                elif not g.done[i]:
                    g.done[i] = True
            g.cur = nxt.astype(np.int32)
        if (not g.done.all()) and g.steps < g.budget.max() - 1:
            if self.pause_at is not None and g.steps >= self.pause_at:
                # admission pause: park the group instead of feeding its
                # token back — caches stay resident for the rescale
                # export, g.cur is the un-fed token resume() re-feeds
                self.parked.append(g.gid)
                self.open_groups -= 1
                if self.open_groups == 0:
                    self.feedback.close()
            else:
                seq = self.enqueue("D", g.gid, g.bucket + g.steps)
                g.fed.append(g.cur.copy())
                self.feedback.push([(seq, (g.gid, g.cur[:, None]))], t_done)
        else:
            g.t_last = t_done - engine.t0
            for p in self.programs:            # free the group's resident
                p.caches.pop(g.gid, None)      # cache slices immediately
            self.open_groups -= 1
            if self.open_groups == 0:
                self.feedback.close()


@dataclass
class ResumeState:
    """Everything a drained, admission-paused serve hands the next
    pipeline: the group bookkeeping (prompts, budgets, sampled-token
    history, the un-fed ``cur`` token) and each block stage's resident
    cache slices keyed by the stage's period span.  A resuming pipeline
    whose stage spans match *transfers* the slices (device_put — the
    cheap path); mismatched spans are rebuilt by deterministic replay
    from prompt + fed-token history, so a rescale can change the stage
    partitioning without touching in-flight requests."""
    groups: list                       # _Group objects, indexed by gid
    group_of: list                     # request index -> gid
    eos_id: int
    stage_caches: dict = field(default_factory=dict)
    # stage name -> {"span": (lo, hi), "caches": {gid: cache pytree}}

    def live_groups(self) -> list:
        return [g for g in self.groups
                if g.done is not None and not g.done.all()
                and g.steps < g.budget.max() - 1]


# ===========================================================================
# the pipeline
# ===========================================================================
class DecodePipeline:
    """A placed serving pipeline: prefill + decode token streams through a
    planned, placed, replicated LM stage graph.

    ``stg``/``sel`` come from the planner on a decode shape
    (`as_selection` accepts the PlanResult directly);
    ``periods_per_stage`` groups adjacent block-pattern periods into one
    stage (the decode analogue of ``layers_per_stage``).  ``params``
    overrides the default `models/lm.init_params(cfg, PRNGKey(seed))` —
    pass the single-device server's params for A/B parity.  ``warmup``
    (default True) AOT-compiles every stage program for each group shape
    before the engine starts; ``compile_stats.late`` counts compiles
    that landed inside a timed serve (kept at zero by the default).

    ``fusion_plan``: planner-selected stage combining
    (`core.restructure`).  ``None`` runs every base stage as its own
    program (the historical layout); ``"auto"`` scores candidate fusions
    with `planner.plan_fusion`-equivalent rules on the analytic graph;
    an explicit plan is a contiguous partition of the base stage chain,
    e.g. ``[("embed", "blocks00"), ("blocks01",), ("blocks02",),
    ("blocks03", "head")]``.  A fused stage runs ONE AOT program for the
    member sequence — one host dispatch and one fewer FIFO hop per fused
    boundary — with the member math unchanged (bitwise token parity vs
    the unfused pipeline) and cache donation / KV-slice residency
    preserved per member.
    """

    def __init__(self, cfg: ModelConfig, stg: STG, sel, *,
                 devices=None, periods_per_stage: int = 1,
                 capacity_blocks: int = 2, seed: int = 0,
                 overlap: bool = True, replica_queue: int = 2,
                 workers: int | None = None, params=None,
                 temperature: float = 0.0, warmup: bool = True,
                 fusion_plan=None, impl: str | None = None):
        from . import as_selection
        sel = as_selection(sel)
        if cfg.encdec or cfg.frontend:
            raise ValueError(
                f"{cfg.name}: DecodePipeline runs embed->blocks->head "
                f"decoder pipelines only (enc-dec / multimodal frontends "
                f"are a ROADMAP item)")
        self.cfg = cfg
        self.stg = stg                 # kept for static verification
        self.sel = sel                 # (core.verify.verify_decode_plan)
        self.overlap = overlap
        self.replica_queue = max(1, replica_queue)
        self.workers = workers
        self.temperature = temperature
        self.impl = impl               # kernel tier for every stage program
        #                                (kernels.ops.resolve_impl; None =
        #                                auto, "ref" = historical A/B path)
        devices = list(devices if devices is not None else jax.devices())
        self._keys = {}
        self._base_key = jax.random.PRNGKey(seed ^ 0xC0FFEE)

        L = len(cfg.block_pattern)
        pps = max(1, periods_per_stage)
        graph_blocks = [n for n in stg.topo_order()
                        if n not in ("embed", "head")]
        if not all(n.startswith("block") for n in graph_blocks):
            raise ValueError(
                f"graph nodes {graph_blocks} are not decoder blocks: "
                f"DecodePipeline executes embed->blocks->head only")
        if len(graph_blocks) != cfg.n_layers:
            raise ValueError(
                f"graph has {len(graph_blocks)} block nodes but the model "
                f"has {cfg.n_layers} layers — plan and model disagree")

        params = params if params is not None \
            else lm.init_params(cfg, jax.random.PRNGKey(seed))
        self._init_params = params     # full tree (references, not copies):
        self.periods_per_stage = pps   # what elastic.rescale_serving needs
        self.seed = seed               # to rebuild this pipeline elsewhere
        head_w = params["embed"] if cfg.tie_embeddings else params["head"]
        stage_layers = _StageLayers(params["layers"])

        # stage list: embed, one per pps-period group, head — then the
        # fusion plan partitions that base chain into executed stages.
        # Each block-owning stage owns periods [a, b) == layers
        # [a*L, b*L); its params and its runtime cache are
        # `slice_periods` of the stacked pytrees.
        self.stage_names: list[str] = []
        self.stage_params: list[dict] = []     # stage -> {rep: pytree}
        self.stage_devices: list[list] = []
        self.period_span: list = []            # stage -> (lo, hi) or None
        pl = place(stg, sel, devices)
        self.placement = pl

        def owners_of(lo_p, hi_p):
            return [f"block{li:02d}" for li in range(lo_p * L, hi_p * L)]

        spans = [(a, min(a + pps, cfg.n_periods))
                 for a in range(0, cfg.n_periods, pps)]
        base = [("embed", None)] + [
            (f"blocks{idx:02d}", sp) for idx, sp in enumerate(spans)] \
            + [("head", None)]
        groups = self._resolve_fusion(base, fusion_plan, stg, sel)
        self.fusion_plan = (tuple(groups)
                            if any(len(g) > 1 for g in groups) else None)
        base_span = dict(base)
        self.stage_descs: list[_StageDesc] = []
        for grp in groups:
            m_spans = [base_span[m] for m in grp if base_span[m] is not None]
            span = (m_spans[0][0], m_spans[-1][1]) if m_spans else None
            self.stage_descs.append(_StageDesc(
                name="+".join(grp), members=tuple(grp),
                has_embed="embed" in grp, span=span,
                has_head="head" in grp))
        for desc in self.stage_descs:
            owners = ["embed"] if desc.has_embed else []
            if desc.span is not None:
                block_owners = owners_of(*desc.span)
                owners.extend(block_owners)
                picks = {sel.choices[o] for o in block_owners}
                if len(picks) > 1:
                    raise ValueError(
                        f"stage {desc.name} groups graph nodes "
                        f"{block_owners} whose plan choices differ "
                        f"({sorted(picks)}) — use periods_per_stage=1 "
                        f"or align the plan")
            if desc.has_head:
                owners.append("head")
            head_p = {"norm": params["final_norm"], "w": head_w}
            # fused stage: member param trees keyed by role — the ONE
            # fused program reads them all (one dispatch for the whole
            # member sequence)
            stage_p = {}
            if desc.has_embed:
                stage_p["embed"] = params["embed"]
            if desc.has_head:
                stage_p.update(head_p)
            # replica pool: every member owner's placement slices (same
            # rule as jax_pipe — nr x n_owners copies, each doing the
            # whole fused stage's work, same planned capacity)
            slices = [sl for owner in owners for sl in pl.replicas_of(owner)]
            # decode stages are single-device jits: a tp>1 slice folds
            # onto its first device (plan replicas, not intra-stage
            # sharding, are what this backend executes)
            devs = [sl.resolve(devices)[0] for sl in slices] or [devices[0]]
            reps = {}
            for k, dev in enumerate(devs):
                rep_p = dict(stage_p)
                if desc.span is not None:
                    rep_p.update(stage_layers.on(dev, *desc.span))
                # device_put onto the device a leaf already lives on
                # aliases it: the one-device case holds one copy in all
                reps[k] = jax.device_put(rep_p, dev)
            self.stage_names.append(desc.name)
            self.stage_devices.append(devs)
            self.stage_params.append(reps)
            self.period_span.append(desc.span)

        # one embed program serves prefill AND decode traffic (one compile
        # cache — the old pair of jax.jit instances of the same function
        # paid two compiles for identical math whenever avals coincided).
        # The block decode program DONATES its incoming cache slice
        # (argnum 1): each token step aliases the update onto the resident
        # buffers instead of allocating a fresh KV/SSM pytree per token
        # per stage — `models/lm.decode_blocks` guarantees the returned
        # cache matches the input structure leaf-for-leaf, so every leaf
        # aliases.  All programs are `aot.AotProgram`s: serve() precompiles
        # them against each group's concrete shapes before the engine's
        # clock starts (``warmup=`` is the escape hatch; late compiles are
        # counted in ``compile_stats.late``).
        self.warmup = warmup
        self.compile_stats = CompileStats()
        self._warmed: set = set()
        self._embed = AotProgram(_embed_prefill_fn(cfg), name="embed",
                                 stats=self.compile_stats)
        self._block_prefill = AotProgram(_block_prefill_fn(cfg, impl),
                                         name="block.prefill",
                                         stats=self.compile_stats,
                                         static_argnums=(2,))
        self._block_decode = AotProgram(_block_decode_fn(cfg, impl),
                                        name="block.decode",
                                        stats=self.compile_stats,
                                        donate_argnums=(1,))
        self._head = AotProgram(_head_fn(cfg), name="head",
                                stats=self.compile_stats)
        # fused-stage programs, one (prefill, decode) pair per signature
        # actually present in the plan.  The decode program donates the
        # member cache exactly like the plain block program — fusion
        # changes dispatch granularity, not the residency discipline.
        self._fused: dict = {}
        for desc in self.stage_descs:
            key = (desc.has_embed, desc.has_head)
            if desc.span is None or not any(key) or key in self._fused:
                continue
            tag = "+".join((["embed"] if key[0] else [])
                           + ["blocks"] + (["head"] if key[1] else []))
            self._fused[key] = (
                AotProgram(_fused_prefill_fn(cfg, *key, impl),
                           name=f"fused.{tag}.prefill",
                           stats=self.compile_stats, static_argnums=(2,)),
                AotProgram(_fused_decode_fn(cfg, *key, impl),
                           name=f"fused.{tag}.decode",
                           stats=self.compile_stats, donate_argnums=(1,)))

    def _resolve_fusion(self, base, fusion_plan, stg, sel):
        """Normalize ``fusion_plan`` to a contiguous partition of the base
        stage chain.  ``"auto"`` scores candidates on the analytic graph
        (`core.restructure.auto_fusion`): span-bearing block stages are
        ``heavy`` (they never fuse together — that axis is
        ``periods_per_stage``), so the scorer absorbs the stateless
        embed/head endpoints into their neighbours, minimizing host
        dispatches per token."""
        names = [n for n, _ in base]
        if fusion_plan is None:
            return [(n,) for n in names]
        if fusion_plan == "auto":
            from ...core import restructure
            L = len(self.cfg.block_pattern)
            dev, reps = {}, {}
            for name, span in base:
                owners = [name] if span is None else [
                    f"block{li:02d}"
                    for li in range(span[0] * L, span[1] * L)]
                dev[name] = sum(sel.impl_of(stg, o).ii for o in owners)
                reps[name] = min(sel.replicas(o) for o in owners)
            heavy = [n for n, sp in base if sp is not None]
            return [tuple(g) for g in restructure.auto_fusion(
                names, dev_us=dev, heavy=heavy, replicas=reps,
                dev_in_score=False).groups]
        groups = [(g,) if isinstance(g, str) else tuple(g)
                  for g in fusion_plan]
        flat = [n for g in groups for n in g]
        if flat != names:
            raise ValueError(
                f"fusion_plan {groups} is not a contiguous partition of "
                f"the stage chain {names}")
        return groups

    # -- sampling -----------------------------------------------------------
    def _sample(self, logits, gid: int, temperature: float | None = None):
        """Greedy by default (token-identical to the single-device
        server); temperature > 0 samples from a per-group key stream —
        statistically equivalent to, but not draw-identical with, the
        single-device server's single key stream."""
        t = self.temperature if temperature is None else temperature
        if t <= 0.0:
            return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        key = self._keys.get(gid, jax.random.fold_in(self._base_key, gid))
        key, sub = jax.random.split(key)
        self._keys[gid] = key
        return jax.random.categorical(
            sub, logits[:, -1, :] / t, axis=-1).astype(jnp.int32)

    def _edge_fifo(self, s: int, capacity_blocks: int, overlap: bool,
                   rep_of=None) -> Fifo:
        """The act edge ``s -> s + 1``.  Same slot accounting as the LM
        pipeline: reservations from producer dispatch to consumer
        retirement, plus buffered slack.  With ``overlap`` a queued token
        ``(seq, (gid, y))`` is staged early: ``y`` alone goes to the
        device of the consumer replica serving ``gid`` (``rep_of``, the
        consumer program's routing, so it follows failover), and ``seq``
        and ``gid`` stay host ints for the consumer's order check."""
        prod = len(self.stage_devices[s])
        cons = len(self.stage_devices[s + 1])
        cons_devs = self.stage_devices[s + 1]

        def staging(tok):
            seq, (gid, y) = tok
            check_not_donated(y, f"act edge {s}->{s + 1} (gid={gid})")
            return (seq, (gid, jax.device_put(y, cons_devs[rep_of(gid)])))

        slots = (prod + cons) * self.replica_queue
        return Fifo(block=1, capacity_blocks=capacity_blocks,
                    min_capacity=capacity_blocks + slots,
                    prefetch_fn=staging if overlap else None,
                    prefetch_depth=cons * self.replica_queue)

    def _n_workers(self) -> int:
        if self.workers is not None:
            return max(1, self.workers)
        return min(16, max(2, sum(len(d) for d in self.stage_devices)))

    def _warm_group_shape(self, batch: int, bucket: int, cap: int) -> None:
        """AOT-compile every program one group shape class will execute —
        embed/head at prefill (B, bucket) and decode (B, 1) avals, block
        prefill with its static cap, block decode against the cache
        struct that prefill produces — on every replica's device, plus
        one greedy-sampler eager warm per head device.  Runs before the
        engine's clock starts; no served request ever sees a compile."""
        from jax.sharding import SingleDeviceSharding
        key = (batch, bucket, cap)
        if key in self._warmed:
            return
        cfg = self.cfg
        dt = dtype_of(cfg.compute_dtype)
        d = cfg.d_model
        for s, desc in enumerate(self.stage_descs):
            for rep, dev in enumerate(self.stage_devices[s]):
                sh = SingleDeviceSharding(dev)
                params = self.stage_params[s][rep]

                def sds(*shape, dtype=dt):
                    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

                if desc.span is None:
                    if desc.has_embed:
                        self._embed.precompile(params, sds(batch, bucket,
                                                           dtype=jnp.int32))
                        self._embed.precompile(params, sds(batch, 1,
                                                           dtype=jnp.int32))
                    else:
                        self._head.precompile(params, sds(batch, bucket, d))
                        self._head.precompile(params, sds(batch, 1, d))
                else:
                    if desc.has_embed or desc.has_head:
                        pre, dec = self._fused[(desc.has_embed,
                                                desc.has_head)]
                        xp = sds(batch, bucket, dtype=jnp.int32) \
                            if desc.has_embed else sds(batch, bucket, d)
                        xd = sds(batch, 1, dtype=jnp.int32) \
                            if desc.has_embed else sds(batch, 1, d)
                    else:
                        pre, dec = self._block_prefill, self._block_decode
                        xp, xd = sds(batch, bucket, d), sds(batch, 1, d)
                    pre.precompile(params, xp, cap)
                    _, cache_s = jax.eval_shape(
                        lambda p, x: pre.fn(p, x, cap), params, xp)
                    cache_sh = jax.tree.map(
                        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                       sharding=sh), cache_s)
                    dec.precompile(params, cache_sh, xd,
                                   sds(dtype=jnp.int32))
                if desc.has_head and (self.temperature or 0.0) <= 0.0:
                    # greedy sampling is eager jnp ops: execute once
                    # per device so the op cache is warm too
                    z = jax.device_put(
                        jnp.zeros((batch, 1, cfg.padded_vocab), dt), dev)
                    self._sample(z, gid=-1)
        self._warmed.add(key)

    def graph_stage_map(self) -> dict[str, str]:
        """graph node -> executed stage name (block nodes collapse onto
        the period-group stage that owns them) — the ``stage_map``
        `measure.compare_lm` needs to read a serve run's completion
        streams against the decode-shape plan."""
        L = len(self.cfg.block_pattern)
        out = {}
        for desc in self.stage_descs:
            if desc.has_embed:
                out["embed"] = desc.name
            if desc.span is not None:
                for li in range(desc.span[0] * L, desc.span[1] * L):
                    out[f"block{li:02d}"] = desc.name
            if desc.has_head:
                out["head"] = desc.name
        return out

    def _replay_cache(self, run: "_ServeRun", g: _Group, s_target: int,
                      k: int, new_rep: int):
        """Recompute stage ``s_target``'s resident cache slice for group
        ``g`` as it stood after ``k`` retired ops (prefill + k-1 decode
        steps), landing it on replica ``new_rep``'s device.

        The replay re-runs the same AOT executables the live traffic uses
        (embed -> preceding block stages -> target stage) from the
        prompt and the fed-token history, so on a deterministic platform
        the rebuilt slice is bitwise the one the dead replica held.
        Healthy stages are untouched: intermediate stages compute into
        *temporary* caches (their donated buffers are fresh allocations,
        never the resident slices), honoring the donation discipline."""
        gid = g.gid

        def par_dev(s):
            rep = new_rep if s == s_target else run.programs[s].rep_of(gid)
            return self.stage_params[s][rep], self.stage_devices[s][rep]

        def progs(desc):
            if desc.has_embed or desc.has_head:
                return self._fused[(desc.has_embed, desc.has_head)]
            return self._block_prefill, self._block_decode

        caches = {}
        x = jnp.asarray(g.tokens)
        for s in range(s_target + 1):
            desc = self.stage_descs[s]
            par, dev = par_dev(s)
            if desc.span is None:              # lone embed (head is last,
                x = self._embed(               # never precedes a target)
                    par, jax.device_put(x, dev))
                continue
            pre, _dec = progs(desc)
            x, caches[s] = pre(par, jax.device_put(x, dev), g.cap)
        for j in range(k - 1):
            x = jnp.asarray(g.fed[j][:, None])
            pos = jnp.asarray(g.bucket + j, jnp.int32)
            for s in range(s_target + 1):
                desc = self.stage_descs[s]
                par, dev = par_dev(s)
                if desc.span is None:
                    x = self._embed(par, jax.device_put(x, dev))
                    continue
                _pre, dec = progs(desc)
                x, caches[s] = dec(par, caches[s],
                                   jax.device_put(x, dev), pos)
        return caches[s_target]

    # -- serving ------------------------------------------------------------
    def serve(self, prompts: list[list[int]], max_new, *, eos_id: int = 1,
              group_size: int = 8, capacity_blocks: int = 2,
              overlap: bool | None = None,
              temperature: float | None = None,
              tracer=None, injector=None, health=None,
              pause_after_tokens: int | None = None,
              preflight: bool = True,
              feedback_capacity: int | None = None,
              keep_logits: bool = False) -> ServeRunResult:
        """Serve ``prompts`` in ``group_size`` slot groups streamed
        concurrently through the pipeline.  Grouping, bucketing, and
        EOS/budget bookkeeping mirror `LMServer.serve_round` on each
        group, so a single-device server with ``max_batch=group_size``
        produces token-identical completions.  ``temperature`` overrides
        the pipeline-level default for this run.  ``tracer``: optional
        `trace.Tracer` — the serve emits op spans, credit/starve waits,
        and fifo occupancy (incl. the head->embed feedback stream);
        warmup stays untraced.  ``injector``: optional
        `failures.ReplicaFaultPlan` chaos schedule (see
        `fail_replica` for the failover semantics).  ``health``: optional
        `health.HealthController` ticked from the engine's retire path.
        ``pause_after_tokens``: admission pause — groups reaching that
        many decode steps park instead of scheduling further work; the
        returned result has ``paused=True`` and a ``resume_state`` that
        `resume()` (on this or a rescaled pipeline) continues without
        dropping any in-flight request.  ``preflight``: run the static
        plan verifier (`core.verify.verify_decode_plan`) before
        launching — channel/cycle credits, fusion legality, placement
        consistency, cache-donation avals — raising
        `PlanVerificationError` on any ERROR (False = escape hatch for
        deliberately unsafe experiments; the deadlock report will note
        preflight was skipped).  ``feedback_capacity``: override the
        head->embed stream's capacity (default ``max(2, n_groups)``) —
        mainly for demonstrating that an undersized feedback path is
        rejected statically.  ``keep_logits``: keep every head logit row
        on its group (``groups[g].logits``: prefill, then each decode
        step) for a logits comparison against another path."""
        if not prompts:
            raise ValueError("serve() needs at least one prompt")
        overlap = self.overlap if overlap is None else overlap
        if isinstance(max_new, int):
            max_new = [max_new] * len(prompts)
        if len(max_new) != len(prompts):
            raise ValueError("max_new must be a scalar or match prompts")
        # everything before the engine starts: group and bucket the
        # prompts, preflight the plan, warm every group shape, queue the
        # prefills and build the engine
        with span(SPAN_SERVE_PREPARE, requests=len(prompts)) as prep:
            groups: list[_Group] = []
            group_of: list[int] = []
            for gid, lo in enumerate(range(0, len(prompts), group_size)):
                chunk = prompts[lo:lo + group_size]
                budgets = np.array(max_new[lo:lo + group_size])
                plen = max(len(p) for p in chunk)
                bucket = _bucket(plen)
                # same capacity clamp as lm.prefill: SWA archs ring-buffer
                # the cache at the attention window — an unclamped cap
                # would let the pipeline attend further back than the
                # single-device server and break token parity on windowed
                # configs
                cap = blocks.attn_cache_capacity(
                    self.cfg, bucket + int(budgets.max()))
                toks = np.zeros((len(chunk), bucket), np.int32)
                for i, p in enumerate(chunk):      # right-align prompts so
                    toks[i, bucket - len(p):] = p  # last token is real
                groups.append(_Group(
                    gid=gid, tokens=toks, bucket=bucket, cap=cap,
                    budget=budgets,
                    prompt_tokens=sum(len(p) for p in chunk),
                    out_tokens=[None] * len(chunk)))
                group_of.extend([gid] * len(chunk))

            report = None
            if preflight:
                report = self._preflight(
                    n_groups=len(groups), capacity_blocks=capacity_blocks,
                    feedback_capacity=feedback_capacity,
                    group_shapes=[(g.batch, g.bucket, g.cap)
                                  for g in groups])

            if self.warmup:
                for g in groups:
                    self._warm_group_shape(g.batch, g.bucket, g.cap)

            run = _ServeRun(self, groups, eos_id=eos_id,
                            capacity_blocks=capacity_blocks,
                            overlap=overlap,
                            temperature=temperature,
                            pause_at=pause_after_tokens,
                            feedback_capacity=feedback_capacity,
                            keep_logits=keep_logits)
            for g in groups:
                run.enqueue("P", g.gid, 0)
            engine = self._engine(run, overlap=overlap, tracer=tracer,
                                  injector=injector, health=health,
                                  static_report=report)
            prep.set_metadata(groups=len(groups))
        res = self._launch(run, engine, group_of)
        for g in groups:                       # run-relative group timings
            g.t_start = max(0.0, g.t_start - engine.t0)
        return res

    def _preflight(self, *, n_groups: int, capacity_blocks: int,
                   feedback_capacity: int | None, group_shapes):
        """Static verification of this serve's plan tuple; raises
        `core.verify.PlanVerificationError` on any ERROR and caches the
        accepted report (donation avals don't change per serve) on
        ``self.last_preflight``."""
        from ...core import verify as _verify
        key = (n_groups, capacity_blocks, feedback_capacity,
               frozenset(group_shapes))
        cached = getattr(self, "_preflight_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1].raise_if_errors("DecodePipeline.serve")
        report = _verify.verify_decode_plan(
            self, n_groups=n_groups, capacity_blocks=capacity_blocks,
            feedback_capacity=feedback_capacity, group_shapes=group_shapes)
        self._preflight_cache = (key, report)
        self.last_preflight = report
        return report.raise_if_errors("DecodePipeline.serve")

    def _engine(self, run: "_ServeRun", *, overlap: bool, tracer, injector,
                health, static_report=None) -> Engine:
        """Wire channels and build the engine that drives ``run`` — shared
        by `serve` and `resume`.  The engine writes the run's profiler
        marks: ``serve.engine.start`` as it reads ``t0`` and
        ``serve.engine.end`` (slots, real tokens, late compiles) as it
        reads ``wall_s``."""
        names = self.stage_names
        fifo_map = {f"act{s}": run.acts[s] for s in range(len(run.acts))}
        fifo_map["feedback"] = run.feedback
        if tracer is not None:
            for s in range(len(run.acts)):
                tracer.watch_fifo(run.acts[s], f"act{s}",
                                  src=names[s], dst=names[s + 1])
            tracer.watch_fifo(run.feedback, "feedback",
                              src=names[-1], dst=names[0])
        late0 = self.compile_stats.late

        def end_mark(_engine):
            mark(SPAN_ENGINE_END, slots=run.slots,
                 real_tokens=run.real_tokens,
                 late_compiles=self.compile_stats.late - late0)

        return Engine(run.programs, overlap=overlap,
                      workers=self._n_workers(),
                      replica_queue=self.replica_queue,
                      tracer=tracer, fifos=fifo_map, injector=injector,
                      on_tick=None if health is None else health.tick,
                      tick_every=64 if health is None
                      else health.check_every,
                      static_report=static_report,
                      on_start=lambda _engine: mark(SPAN_ENGINE_START),
                      on_end=end_mark)

    def _launch(self, run: "_ServeRun", engine: Engine,
                group_of: list) -> ServeRunResult:
        """Drive the engine to quiescence and fold its result into a
        `ServeRunResult` (exporting a `ResumeState` when the run
        admission-paused) — shared by `serve` and `resume`."""
        with self.compile_stats.window():
            er = engine.run()
        assert run.feedback.exhausted, \
            "token stream not drained: a group retired with tokens in flight"
        with span(SPAN_SERVE_FINISH):
            return self._fold(run, er, group_of)

    def _fold(self, run: "_ServeRun", er: EngineResult,
              group_of: list) -> ServeRunResult:
        """The engine's result, the run's tokens and fifo stats, and the
        resume state of a paused run, as one `ServeRunResult`."""
        names = self.stage_names
        res = ServeRunResult(
            tokens=[], group_of=group_of, groups=run.groups,
            stage_done_s=er.stage_done_s, stage_seconds=er.stage_seconds,
            stage_firings=er.stage_firings,
            stage_dispatch_s=er.stage_dispatch_s, op_trace=er.op_trace,
            max_inflight=er.max_inflight, wall_s=er.wall_s,
            stage_wait_s=er.stage_wait_s, failovers=er.failovers,
            placement=self.placement)
        idx_in_group: dict[int, int] = {}
        for gid in group_of:
            i = idx_in_group.get(gid, 0)
            idx_in_group[gid] = i + 1
            res.tokens.append(run.groups[gid].out_tokens[i])
        for s in range(len(run.acts)):
            res.fifo_stats[("act", s)] = run.acts[s].stats
        res.fifo_stats["feedback"] = run.feedback.stats
        if run.parked:
            res.paused = True
            res.resume_state = ResumeState(
                groups=run.groups, group_of=list(group_of),
                eos_id=run.eos_id,
                stage_caches={
                    names[s]: {"span": self.period_span[s],
                               "caches": dict(run.programs[s].caches)}
                    for s in range(len(names))
                    if self.period_span[s] is not None})
        return res

    def resume(self, state: ResumeState, *, capacity_blocks: int = 2,
               overlap: bool | None = None,
               temperature: float | None = None, tracer=None,
               injector=None, health=None,
               pause_after_tokens: int | None = None,
               preflight: bool = True,
               feedback_capacity: int | None = None) -> ServeRunResult:
        """Continue an admission-paused serve on THIS pipeline — possibly
        a different plan, partitioning, or device pool than the one that
        drained (`elastic.rescale_serving` builds that pipeline).  Live
        groups' cache slices are adopted: *transferred* (device_put)
        when this pipeline's stage spans match the exporter's, rebuilt
        by deterministic replay from prompt + fed-token history when
        they don't.  Each group's parked token is fed back and decoding
        continues, so no in-flight request is dropped and the combined
        streams are bitwise what an uninterrupted serve yields."""
        overlap = self.overlap if overlap is None else overlap
        live = state.live_groups()
        if not live:
            raise ValueError("resume() on a state with no live groups")
        report = None
        if preflight:
            # the channel is sized for every exported group (finished
            # ones hold no tokens), but only live groups circulate
            fb_cap = feedback_capacity if feedback_capacity is not None \
                else max(2, len(state.groups))
            report = self._preflight(
                n_groups=len(live), capacity_blocks=capacity_blocks,
                feedback_capacity=fb_cap,
                group_shapes=[(g.batch, g.bucket, g.cap) for g in live])
        if self.warmup:
            for g in live:
                self._warm_group_shape(g.batch, g.bucket, g.cap)
        run = _ServeRun(self, state.groups, eos_id=state.eos_id,
                        capacity_blocks=capacity_blocks, overlap=overlap,
                        temperature=temperature,
                        pause_at=pause_after_tokens,
                        open_groups=len(live),
                        feedback_capacity=feedback_capacity)
        S = len(self.stage_names)
        by_span = {tuple(v["span"]): v["caches"]
                   for v in state.stage_caches.values()}
        for s in range(S):
            prog = run.programs[s]
            p_span = self.period_span[s]
            donors = by_span.get(tuple(p_span)) if p_span is not None \
                else None
            for g in live:
                k = 1 + g.steps        # every stage retired prefill +
                prog.done_count[g.gid] = k     # g.steps decode ops
                if p_span is None:
                    continue
                if donors is not None and g.gid in donors:
                    prog.caches[g.gid] = jax.device_put(
                        donors[g.gid],
                        self.stage_devices[s][prog.rep_of(g.gid)])
                else:
                    prog.caches[g.gid] = self._replay_cache(
                        run, g, s, k, prog.rep_of(g.gid))
        for g in live:
            seq = run.enqueue("D", g.gid, g.bucket + g.steps)
            g.fed.append(g.cur.copy())
            run.feedback.push([(seq, (g.gid, g.cur[:, None]))], 0.0)
        engine = self._engine(run, overlap=overlap, tracer=tracer,
                              injector=injector, health=health,
                              static_report=report)
        return self._launch(run, engine, state.group_of)
