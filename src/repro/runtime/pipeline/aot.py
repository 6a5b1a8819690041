"""AOT-precompiled stage programs: zero compiles inside a timed window.

The executors' hot paths used to call plain ``jax.jit`` functions, so the
first firing of every (stage, shape, device) combination paid its XLA
compile *inside* the engine's timed run — skewing the very measurements
`measure.replan_to_fixed_point` feeds back into the planner, and landing
multi-hundred-ms stalls in the middle of served requests.  ``jax.jit``'s
own dispatch cache cannot be warmed ahead of time from shapes alone
(``fn.lower(x).compile()`` does NOT populate it — verified: the next
``fn(x)`` call recompiles), so this module routes the hot path through
the ahead-of-time executables themselves:

  * `AotProgram` wraps one function the way the executors used to wrap it
    in ``jax.jit`` — same lowering, same executable, **bitwise-identical
    results** — but keeps a per-(aval, sharding) cache of
    ``.lower(...).compile()`` products and calls those.  ``precompile()``
    accepts concrete arrays or `jax.ShapeDtypeStruct`s (with shardings),
    so a pipeline compiles every stage program against its real shapes
    and placements before the first op of a run.
  * Tracing still works: when any argument is a JAX tracer (``jax.vjp``
    over a stage forward, ``jax.eval_shape`` shape chaining), the call
    transparently falls through to the wrapped ``jax.jit`` function — an
    `AotProgram` is a drop-in replacement for the jit it replaces.
  * ``donate_argnums`` flows through to both paths: the compiled
    executable aliases donated inputs to outputs (the KV-cache /
    grad-accumulator zero-copy updates), and a donated buffer is deleted
    at dispatch — a use-after-donate is a loud error, never silent reuse.
  * Every compile is accounted in a shared `CompileStats`: compiles that
    happen inside ``precompile()`` are *planned*; compiles triggered by a
    cache-miss call are *late* (they landed where a timed run could see
    them).  Pipelines expose this as ``pipe.compile_stats`` and tests
    assert ``late == 0`` after warmup.
"""
from __future__ import annotations

import functools
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax

from .trace import SPAN_COMPILE, SPAN_STAGE, span


@dataclass
class CompileStats:
    """Aggregate compile accounting for one pipeline's programs."""
    compiles: int = 0              # distinct executables built
    compile_s: float = 0.0         # total wall time spent compiling
    late: int = 0                  # compiles that landed INSIDE a timed
    #                                window (the engine was running) — the
    #                                number warmup exists to keep at zero
    misses: int = 0                # cache-miss compiles outside any window
    #                                (reference paths, warmup=False runs)
    calls: int = 0                 # hot-path calls routed through executables
    warm_exec_s: float = 0.0       # wall time of warmup *executions* (the
    #                                train vjp chain, which must keep its
    #                                eager call structure — see LMPipeline)
    in_window: bool = False        # set by the pipeline around engine.run()
    programs: dict[str, int] = field(default_factory=dict)  # name -> compiles
    # one stats object is shared by every program of a pipeline, and op
    # bodies run on the engine's worker pool — counter updates take a lock
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    def note(self, name: str, seconds: float, on_miss: bool) -> None:
        with self._lock:
            self.compiles += 1
            self.compile_s += seconds
            self.programs[name] = self.programs.get(name, 0) + 1
            if on_miss:
                if self.in_window:
                    self.late += 1
                else:
                    self.misses += 1

    def count_call(self) -> None:
        with self._lock:
            self.calls += 1

    @contextmanager
    def window(self):
        """Mark a timed window (the engine is running): cache-miss
        compiles inside it count as ``late``.  Pipelines wrap
        ``engine.run()`` in this."""
        self.in_window = True
        try:
            yield
        finally:
            self.in_window = False

    def summary(self) -> str:
        per = ", ".join(f"{n}: {c}" for n, c in sorted(self.programs.items()))
        return (f"{self.compiles} compiles in {self.compile_s:.2f}s "
                f"({self.late} late, {self.misses} out-of-window misses), "
                f"{self.calls} aot calls, "
                f"warm exec {self.warm_exec_s:.2f}s [{per}]")


def _leaf_key(leaf):
    """Hashable identity of one argument leaf: shape, dtype, and placement
    (sharding participates — the same shapes lowered for two devices are
    two executables)."""
    shape = getattr(leaf, "shape", None)
    if shape is None:                     # python scalar: aval by type only
        return ("py", type(leaf).__name__)
    dtype = getattr(leaf, "dtype", None)
    return (tuple(shape), str(dtype), getattr(leaf, "sharding", None))


def _has_tracer(args) -> bool:
    return any(isinstance(l, jax.core.Tracer) for l in jax.tree.leaves(args))


class AotProgram:
    """One stage program, ahead-of-time compiled per (shape, placement).

    Drop-in for the ``jax.jit(fn, ...)`` it replaces: calling with
    concrete arrays routes through the per-aval compiled executable
    (compiling on miss, counted as *late*); calling under a trace
    (``jax.vjp``, ``jax.eval_shape``, an enclosing jit) falls through to
    the wrapped jit so the program stays composable.  ``precompile``
    takes the same positional args — concrete or `ShapeDtypeStruct` —
    and builds the executable without running it.

    The jitted function carries ``name``, so the compiled module is
    ``jit_<name>`` (XLA turns ``+`` into ``_``) in a device trace, and an
    op body running this program is the profiler span ``stage.<name>``
    (`stage_span`).
    """

    def __init__(self, fn, *, name: str = "", stats: CompileStats | None = None,
                 static_argnums: tuple = (), donate_argnums: tuple = ()):
        self.fn = fn
        self.name = name or getattr(fn, "__name__", "program")
        self.stage_span = SPAN_STAGE + self.name
        self._compile_span = SPAN_COMPILE + self.name
        self.stats = stats if stats is not None else CompileStats()
        self._static = tuple(static_argnums)

        @functools.wraps(fn)
        def named(*args):
            return fn(*args)
        named.__name__ = named.__qualname__ = self.name
        self._jit = jax.jit(named, static_argnums=static_argnums,
                            donate_argnums=donate_argnums)
        self._compiled: dict = {}
        # op bodies run on the engine's worker pool: the compile path and
        # the stats counters are shared mutable state across threads
        self._lock = threading.Lock()

    def key_of(self, args) -> tuple:
        parts = []
        for i, a in enumerate(args):
            if i in self._static:
                parts.append(("static", a))
            else:
                leaves, treedef = jax.tree.flatten(a)
                parts.append((treedef, tuple(_leaf_key(l) for l in leaves)))
        return tuple(parts)

    def _compile(self, key: tuple, args, *, on_miss: bool):
        with self._lock:
            exe = self._compiled.get(key)
            if exe is not None:          # another thread won the race —
                return exe               # one compile, not two stalls
            t0 = time.perf_counter()
            with span(self._compile_span, on_miss=on_miss):
                exe = self._jit.lower(*args).compile()
            self.stats.note(self.name, time.perf_counter() - t0, on_miss)
            self._compiled[key] = exe
            return exe

    def precompile(self, *args) -> None:
        """Build (or reuse) the executable for these args — concrete
        arrays or ShapeDtypeStructs with shardings attached."""
        key = self.key_of(args)
        if key not in self._compiled:
            self._compile(key, args, on_miss=False)

    @property
    def n_compiled(self) -> int:
        return len(self._compiled)

    def compiled_texts(self) -> list[str]:
        """Compiled HLO of every executable built so far — where a Pallas
        kernel was lowered for a TPU it shows as ``tpu_custom_call``."""
        return [exe.as_text() for exe in self._compiled.values()]

    def __call__(self, *args):
        if _has_tracer(args):             # composing under vjp/eval_shape/jit
            return self._jit(*args)
        key = self.key_of(args)
        exe = self._compiled.get(key)
        if exe is None:
            exe = self._compile(key, args, on_miss=True)
        self.stats.count_call()
        if self._static:                  # statics are baked into the
            args = tuple(a for i, a in enumerate(args)   # executable
                         if i not in self._static)
        return exe(*args)


def tree_add_program(name: str, stats: CompileStats) -> AotProgram:
    """The donated gradient accumulator: ``acc <- acc + update`` as ONE
    compiled program whose output aliases the donated ``acc`` buffer —
    the pytree is updated in place on its resident device instead of a
    host-driven per-leaf dispatch allocating a fresh tree per microbatch.
    Bitwise-identical to ``jax.tree.map(jnp.add, acc, update)``."""
    import jax.numpy as jnp

    def tree_add(acc, update):
        return jax.tree.map(jnp.add, acc, update)

    return AotProgram(tree_add, name=name, stats=stats, donate_argnums=(0,))
