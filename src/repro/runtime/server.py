"""Batched LM serving runtime (prefill + decode rounds).

Round-based batching: take up to ``max_batch`` queued requests, left-align
them into a padded prompt matrix, one jitted prefill builds the KV/SSM
caches, then jitted single-token decode steps run until every slot hits
EOS or its token budget.  Prompt lengths are bucketed to powers of two so
the prefill compiles once per bucket, not once per request mix.

Two backends:

  * **single-device** (default): one jitted prefill + decode loop over the
    whole model, rounds served sequentially.
  * **pipelined** (``pipeline=runtime.pipeline.DecodePipeline(...)``):
    rounds become serving-slot *groups* streamed concurrently through a
    planned, placed, replicated stage pipeline — per-stage KV-cache
    slices stay resident on their placement slices and sampled tokens
    feed back over a continuous token-stream channel.  Completions are
    token-identical to the single-device backend under greedy sampling
    (same grouping, bucketing, and EOS/budget bookkeeping).

Throughput accounting distinguishes prefill tokens (prompt side) from
decode tokens (generated) — the two shapes the dry-run cells
(``prefill_32k`` / ``decode_32k``) lower at production scale.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from .. import sharding_ctx as sctx
from ..configs.base import ModelConfig
from ..models import build_model
from .pipeline.trace import SPAN_SERVE_FINISH, span


@dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new: int = 32


@dataclass
class Completion:
    uid: int
    tokens: list[int]
    prompt_len: int
    prefill_s: float
    decode_s: float


@dataclass
class ServeStats:
    requests: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    rounds: int = 0
    compiles: set = field(default_factory=set)
    decode_step_s: list = field(default_factory=list)
    # per-decode-step wall gaps (single-device backend): the `int(nxt[i])`
    # conversions host-sync every step, so each gap is a real step time —
    # honest p50/p95 material, not a per-request mean smeared flat

    def summary(self) -> dict:
        return {
            "requests": self.requests,
            "rounds": self.rounds,
            "prefill_tok_per_s": self.prefill_tokens / self.prefill_s
            if self.prefill_s else 0.0,
            "decode_tok_per_s": self.decode_tokens / self.decode_s
            if self.decode_s else 0.0,
            "decode_tokens": self.decode_tokens,
        }


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class LMServer:
    def __init__(self, cfg: ModelConfig, *, max_batch: int = 8,
                 eos_id: int = 1, params=None, seed: int = 0,
                 mesh=None, temperature: float = 0.0, pipeline=None,
                 tracer=None, injector=None, health=None,
                 preflight: bool = True, impl: str | None = None,
                 keep_logits: bool = False):
        """``pipeline``: a `runtime.pipeline.DecodePipeline` — when set,
        ``serve``/``serve_round`` stream request groups through it instead
        of the single-device prefill/decode loop; without ``params`` the
        server shares the pipeline's weights instead of building its own.
        ``injector`` (a `failures.ReplicaFaultPlan`) and ``health`` (a
        `pipeline.health.HealthController`) ride along on every pipelined
        serve — chaos drills and self-healing, pipelined backend only.
        ``preflight``: statically verify each pipelined serve's plan
        (`core.verify`) before launch; False skips the check (the
        single-device backend has no plan to verify either way).
        ``impl``: kernel implementation for every model call
        (`kernels.ops.resolve_impl` tier — None = auto; ``"ref"`` pins
        the bitwise-historical decode path for A/B runs).
        ``keep_logits``: pipelined serves keep every head logit row on
        their groups (``last_run.groups[g].logits``) for a logits
        comparison against `forced_logits`."""
        self.cfg = cfg
        self.max_batch = max_batch
        self.eos_id = eos_id
        self.temperature = temperature
        self.mesh = mesh
        self.pipeline = pipeline
        self.preflight = preflight
        self.tracer = tracer         # optional pipeline Tracer (pipelined
        #                              backend only; None = tracing off)
        self.injector = injector     # optional ReplicaFaultPlan (chaos)
        self.health = health         # optional HealthController
        self.impl = impl
        self.keep_logits = keep_logits
        self.last_run = None         # the last pipelined ServeRunResult
        self.model = build_model(cfg, impl)
        if params is None:             # a pipeline already holds the weights
            params = pipeline._init_params if pipeline is not None \
                else self.model.init(jax.random.PRNGKey(seed))
        self.params = params
        self.stats = ServeStats()
        self._prefill = jax.jit(
            lambda p, batch, cap: self.model.prefill(p, batch, capacity=cap),
            static_argnums=(2,))
        # the cache is donated: `decode_step` returns it with identical
        # avals leaf-for-leaf (`decode_cache_structs` contract), so the
        # steady-state decode loop updates the ring buffers in place —
        # zero new cache allocations per token.  The loop below rebinds
        # `cache` every step and never touches the donated value again.
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(1,))
        self._key = jax.random.PRNGKey(seed ^ 0xC0FFEE)

    # -- one round ----------------------------------------------------------
    def _sample(self, logits):
        if self.temperature <= 0.0:
            return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        self._key, sub = jax.random.split(self._key)
        return jax.random.categorical(
            sub, logits[:, -1, :] / self.temperature, axis=-1).astype(jnp.int32)

    def serve_round(self, reqs: list[Request]) -> list[Completion]:
        if self.pipeline is not None:
            return self._serve_pipelined(reqs)
        assert 0 < len(reqs) <= self.max_batch
        B = len(reqs)
        plen = max(len(r.prompt) for r in reqs)
        bucket = _bucket(plen)
        cap = bucket + max(r.max_new for r in reqs)
        self.stats.compiles.add((B, bucket, cap))
        toks = np.zeros((B, bucket), np.int32)
        for i, r in enumerate(reqs):               # right-align prompts so
            toks[i, bucket - len(r.prompt):] = r.prompt   # last token is real
        batch = {"tokens": jnp.asarray(toks)}

        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, batch, cap)
        last = self._sample(logits)
        jax.block_until_ready(last)
        t_prefill = time.perf_counter() - t0

        out_tokens = [[int(last[i])] for i in range(B)]
        done = np.array([t[0] == self.eos_id for t in out_tokens])
        budget = np.array([r.max_new for r in reqs])

        t1 = time.perf_counter()
        t_step = t1
        steps = 0
        cur = last[:, None]
        while not done.all() and steps < budget.max() - 1:
            logits, cache = self._decode(self.params, cache, cur)
            nxt = self._sample(logits)
            steps += 1
            for i in range(B):
                if not done[i] and steps < budget[i]:
                    tok = int(nxt[i])
                    out_tokens[i].append(tok)
                    if tok == self.eos_id:
                        done[i] = True
                elif not done[i]:
                    done[i] = True
            now = time.perf_counter()
            self.stats.decode_step_s.append(now - t_step)
            t_step = now
            cur = nxt[:, None]
        jax.block_until_ready(cur)
        t_decode = time.perf_counter() - t1

        self.stats.requests += B
        self.stats.rounds += 1
        self.stats.prefill_tokens += B * bucket
        self.stats.decode_tokens += sum(len(t) for t in out_tokens)
        self.stats.prefill_s += t_prefill
        self.stats.decode_s += t_decode
        return [Completion(uid=r.uid, tokens=out_tokens[i],
                           prompt_len=len(r.prompt),
                           prefill_s=t_prefill, decode_s=t_decode)
                for i, r in enumerate(reqs)]

    def forced_logits(self, tokens, fed, cap: int) -> list:
        """Logits of this server's own prefill and decode programs on a
        fixed token history: ``tokens`` (B, bucket) right-aligned prompts,
        then one decode step per (B,) row of ``fed``.  Returns the (B, 1,
        vocab) logits of the prefill and of each step.  Two paths fed the
        same history are compared on these, not on sampled tokens: with
        random weights the largest logit changes on rounding."""
        logits, cache = self._prefill(
            self.params, {"tokens": jnp.asarray(tokens, jnp.int32)}, cap)
        out = [logits]
        for f in fed:
            logits, cache = self._decode(
                self.params, cache, jnp.asarray(f, jnp.int32)[:, None])
            out.append(logits)
        return out

    def serve(self, reqs: list[Request]) -> list[Completion]:
        """Drain a queue in max_batch-sized rounds.  The pipelined backend
        streams *all* rounds concurrently through the stage pipeline (each
        round = one serving-slot group); the single-device backend serves
        them sequentially."""
        if self.pipeline is not None:
            return self._serve_pipelined(reqs)
        out: list[Completion] = []
        for i in range(0, len(reqs), self.max_batch):
            ctx = sctx.activate(sctx.from_mesh(self.mesh)) if self.mesh \
                else _null()
            with ctx:
                out.extend(self.serve_round(reqs[i:i + self.max_batch]))
        return out

    def _serve_pipelined(self, reqs: list[Request]) -> list[Completion]:
        """Stream request groups through the decode pipeline.

        Per-completion prefill/decode times are the group's pipeline spans
        (dispatch -> first sampled token -> last token).  Aggregate stats
        use run-level wall windows — groups overlap in the pipeline, so
        summing per-group spans would double-count time."""
        if not reqs:
            return []          # match the single-device backend on an
        #                        empty queue instead of raising
        run = self.pipeline.serve(
            [r.prompt for r in reqs], [r.max_new for r in reqs],
            eos_id=self.eos_id, group_size=self.max_batch,
            temperature=self.temperature, tracer=self.tracer,
            injector=self.injector, health=self.health,
            preflight=self.preflight, keep_logits=self.keep_logits)
        self.last_run = run
        with span(SPAN_SERVE_FINISH):
            return self._fold(reqs, run)

    def _fold(self, reqs: list[Request], run) -> list[Completion]:
        """A pipelined serve's result as stats and completions."""
        self.stats.requests += len(reqs)
        self.stats.rounds += len(run.groups)
        self.stats.prefill_tokens += run.prefill_tokens
        self.stats.decode_tokens += run.decode_tokens
        # wall windows (they overlap under pipelining): prefill counts
        # until the LAST group's prefill lands — interleaved decode makes
        # the reported prefill rate a lower bound, never an inflated one
        first_prefill = min(g.t_prefill_done for g in run.groups)
        self.stats.prefill_s += max(g.t_prefill_done for g in run.groups)
        self.stats.decode_s += max(
            max(g.t_last for g in run.groups) - first_prefill, 0.0)
        for g in run.groups:
            self.stats.compiles.add((g.batch, g.bucket, g.cap))
        out: list[Completion] = []
        for i, (r, toks) in enumerate(zip(reqs, run.tokens)):
            g = run.groups[run.group_of[i]]
            out.append(Completion(
                uid=r.uid, tokens=toks, prompt_len=len(r.prompt),
                prefill_s=g.t_prefill_done - g.t_start,
                decode_s=max(g.t_last - g.t_prefill_done, 0.0)))
        return out


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
