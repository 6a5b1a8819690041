"""Zero-copy hot path: buffer donation, AOT precompile, async retirement.

Acceptance contract of the donation/AOT rework:
  * decode steady state allocates **no new KV-cache buffers per token** —
    the donated block-decode program aliases every cache leaf in place
    (verified by buffer pointer), and the donation contract of
    `models/lm.decode_blocks` (cache-out avals == cache-in avals) holds
    structurally for every leaf;
  * donation changes *allocation behaviour, not results*: donated decode
    tokens are identical to the non-donated single-device `serve_round`,
    and donated-accumulate 1F1B / interleaved grads stay bitwise-equal to
    sequential autodiff;
  * no use-after-donate under overlap + prefetch (stale reads raise, the
    pipelines never trigger one);
  * every stage program is compiled before the first op of a timed run
    (``compile_stats.late == 0``), and the engine exposes per-stage host
    dispatch overhead as its own measurement column.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import ShapeCfg
from repro.configs.tiny import CONFIG as tiny
from repro.core import planner
from repro.core.stg import Selection
from repro.graphs import lm_graph
from repro.models import lm
from repro.runtime.pipeline import (AotProgram, CompileStats, DecodePipeline,
                                    LMPipeline, selection_from_plan)
from repro.runtime.server import LMServer, Request



@pytest.fixture(scope="module")
def decode_setup():
    shape = ShapeCfg("donate_test", 64, 16, "decode")
    plan = planner.plan(tiny, shape, chips=8, max_tp=4)
    stg, _ = lm_graph.build_stg(tiny, shape, max_tp=4)
    return plan, stg


@pytest.fixture(scope="module")
def lm_setup():
    shape = ShapeCfg("donate_lm", 16, 8, "train")
    plan = planner.plan(tiny, shape, chips=16, max_tp=4)
    stg, _ = lm_graph.build_stg(tiny, shape, max_tp=4)
    pipe = LMPipeline(tiny, stg, selection_from_plan(plan))
    rng = np.random.default_rng(3)
    mbs = [jnp.asarray(rng.integers(0, tiny.vocab, (2, 16)), jnp.int32)
           for _ in range(5)]
    return pipe, mbs


# ===========================================================================
# donation mechanics
# ===========================================================================
def test_decode_cache_donation_aliases_every_leaf(decode_setup):
    """One decode step through the donated block program updates the
    resident cache slice IN PLACE: the old buffers are deleted, the new
    cache's leaves live at the same addresses (zero new allocations), and
    reading a donated buffer raises instead of silently reusing it."""
    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan)
    s = 1                                          # first block stage
    params = pipe.stage_params[s][0]
    dev = pipe.stage_devices[s][0]
    B, bucket, cap = 2, 16, 24
    x = jax.device_put(jnp.zeros((B, bucket, tiny.d_model), jnp.bfloat16),
                       dev)
    _, cache = pipe._block_prefill(params, x, cap)
    old_leaves = jax.tree.leaves(cache)
    ptrs_in = [l.unsafe_buffer_pointer() for l in old_leaves]
    xd = jax.device_put(jnp.zeros((B, 1, tiny.d_model), jnp.bfloat16), dev)
    pos = jax.device_put(jnp.asarray(bucket, jnp.int32), dev)
    h, cache2 = pipe._block_decode(params, cache, xd, pos)
    jax.block_until_ready(h)
    assert all(l.is_deleted() for l in old_leaves), \
        "donated cache inputs must be consumed"
    ptrs_out = [l.unsafe_buffer_pointer() for l in jax.tree.leaves(cache2)]
    assert ptrs_out == ptrs_in, \
        "every cache leaf must alias in place (no new buffers per token)"
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(old_leaves[0])                  # use-after-donate is loud


def test_decode_blocks_signature_is_donation_safe():
    """`lm.decode_cache_structs`: the cache a decode step returns matches
    the cache it consumed aval-for-aval — the structural precondition for
    full aliasing, checked for every leaf of a real (sub-)stack."""
    params = lm.init_params(tiny, jax.random.PRNGKey(0))
    sub = lm.slice_periods(params["layers"], 0, tiny.n_periods)
    cin, cout = lm.decode_cache_structs(tiny, sub, batch=2, prompt=8, cap=16)
    assert jax.tree.structure(cin) == jax.tree.structure(cout)
    for a, b in zip(jax.tree.leaves(cin), jax.tree.leaves(cout)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_donated_accumulate_matches_tree_map_add():
    """The donated in-place grad accumulate is bitwise-equal to the
    host-driven per-leaf `jax.tree.map(jnp.add, ...)` it replaced, and
    consumes its acc argument."""
    from repro.runtime.pipeline import tree_add_program
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(8,)), jnp.float32)}
    upd = jax.tree.map(lambda l: l * 0.5, tree)
    ref = jax.tree.map(jnp.add, tree, upd)
    acc = jax.tree.map(lambda l: l + 0, tree)      # fresh donatable copy
    old = jax.tree.leaves(acc)
    prog = tree_add_program("t.acc", CompileStats())
    out = prog(acc, upd)
    jax.block_until_ready(out)
    assert all(l.is_deleted() for l in old)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(1, 8), st.integers(1, 8), st.integers(2, 5),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=8, deadline=None)
def test_donated_accumulate_fold_property(rows, cols, folds, seed):
    """Property: folding ``folds`` random updates through the donated
    accumulator equals the eager per-leaf add chain bitwise for arbitrary
    leaf shapes and fold lengths, and every intermediate acc buffer is
    consumed (one live accumulator at any time)."""
    from repro.runtime.pipeline import tree_add_program
    rng = np.random.default_rng(seed)
    updates = [{"a": jnp.asarray(rng.normal(size=(rows, cols)), jnp.float32),
                "b": jnp.asarray(rng.normal(size=(cols,)), jnp.float32)}
               for _ in range(folds)]
    ref = updates[0]
    for u in updates[1:]:
        ref = jax.tree.map(jnp.add, ref, u)
    prog = tree_add_program("p.acc", CompileStats())
    acc = jax.tree.map(lambda l: l + 0, updates[0])
    for u in updates[1:]:
        old = jax.tree.leaves(acc)
        acc = prog(acc, u)
        jax.block_until_ready(acc)
        assert all(l.is_deleted() for l in old)
    for a, b in zip(jax.tree.leaves(acc), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ===========================================================================
# donation changes allocation, not results
# ===========================================================================
def test_donated_decode_tokens_equal_single_device(decode_setup):
    """Pipelined serve (donated caches, AOT programs, async retirement,
    overlap + prefetch on) is token-identical to the non-donated
    single-device `serve_round` — and no op tripped a use-after-donate."""
    plan, stg = decode_setup
    rng = np.random.default_rng(11)
    reqs = [Request(uid=i,
                    prompt=rng.integers(2, tiny.vocab,
                                        rng.integers(4, 20)).tolist(),
                    max_new=8)
            for i in range(8)]
    pipe = DecodePipeline(tiny, stg, plan)
    out_p = LMServer(tiny, max_batch=4, pipeline=pipe).serve(reqs)
    out_r = LMServer(tiny, max_batch=4).serve(reqs)
    for a, b in zip(out_p, out_r):
        assert a.tokens == b.tokens


def test_donated_accumulate_grads_bitwise_equal_sequential(lm_setup):
    """1F1B with the donated accumulator reproduces the sequential eager
    vjp-chain grads BITWISE (same fold order, same adds — donation only
    changed where the sums live)."""
    pipe, mbs = lm_setup
    loss = lambda lg: jnp.sum(lg * lg) / lg.size
    res = pipe.run(mbs, train=True, loss_fn=loss)

    grads = {st.name: None for st in pipe.stages}
    for mb in mbs:
        x = mb
        vjps = []
        for st in pipe.stages:
            x = jax.device_put(x, st.x_target(0))
            y, vjp = jax.vjp(st.fwd, st.params[0], x)
            vjps.append(vjp)
            x = y
        _, y_bar = jax.value_and_grad(loss)(x)
        for st, vjp in reversed(list(zip(pipe.stages, vjps))):
            p_bar, y_bar = vjp(y_bar)
            pb = jax.device_put(p_bar, st.grad_target())
            grads[st.name] = (pb if grads[st.name] is None else
                              jax.tree.map(jnp.add, grads[st.name], pb))
    for st in pipe.stages:
        for a, b in zip(jax.tree.leaves(res.grads[st.name]),
                        jax.tree.leaves(grads[st.name])):
            assert (np.asarray(a) == np.asarray(b)).all(), st.name


def test_interleaved_grads_bitwise_stable_under_donation(lm_setup):
    """Plain vs interleaved 1F1B still agree bitwise with the donated
    accumulator in the loop (per-built-stage fold order is schedule-
    independent)."""
    from repro.runtime.pipeline import interleaved_1f1b, one_f_one_b
    shape = ShapeCfg("donate_ilv", 16, 8, "train")
    stg, _ = lm_graph.build_stg(tiny, shape, max_tp=4)
    pipe = LMPipeline(tiny, stg, Selection.smallest(stg), layers_per_stage=2)
    rng = np.random.default_rng(5)
    mbs = [jnp.asarray(rng.integers(0, tiny.vocab, (1, 16)), jnp.int32)
           for _ in range(4)]
    loss = lambda lg: jnp.mean(lg * lg)
    M = pipe.n_stages
    r_plain = pipe.run(mbs, train=True, loss_fn=loss,
                       schedule=one_f_one_b(M, len(mbs)))
    r_ilv = pipe.run(mbs, train=True, loss_fn=loss,
                     schedule=interleaved_1f1b(M // 2, len(mbs), 2))
    for st in pipe.stages:
        for a, b in zip(jax.tree.leaves(r_plain.grads[st.name]),
                        jax.tree.leaves(r_ilv.grads[st.name])):
            assert (np.asarray(a) == np.asarray(b)).all(), st.name


@pytest.mark.parametrize("group_size,max_new", [(1, 3), (2, 6), (3, 2)])
def test_no_use_after_donate_under_overlap_and_prefetch(decode_setup,
                                                        group_size, max_new):
    """Any grouping/budget under full overlap + prefetch + tight channel
    capacity serves to completion without a use-after-donate (a deleted
    buffer read raises RuntimeError — the engine surfaces it, never
    wedges) and with a drained token stream."""
    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan)
    prompts = [list(range(2, 8)), list(range(3, 12)), list(range(2, 6)),
               list(range(4, 10))]
    run = pipe.serve(prompts, max_new, group_size=group_size,
                     capacity_blocks=1)
    assert all(1 <= len(t) <= max_new for t in run.tokens)


# ===========================================================================
# AOT precompile: no compiles inside timed runs
# ===========================================================================
def test_no_compiles_inside_timed_serve(decode_setup):
    """With warmup on (default), every program is compiled before the
    engine's clock starts: `compile_stats.late == 0` across repeated
    serves and fresh shape classes."""
    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan)
    pipe.serve([list(range(2, 10))] * 4, 5, group_size=2)
    pipe.serve([list(range(2, 30))] * 2, 7, group_size=2)   # new bucket
    assert pipe.compile_stats.late == 0, pipe.compile_stats.summary()
    assert pipe.compile_stats.compiles > 0
    assert pipe.compile_stats.calls > 0


def test_no_compiles_inside_timed_lm_run(lm_setup):
    pipe, mbs = lm_setup
    pipe.run(mbs)
    pipe.run(mbs, train=True,
             loss_fn=lambda lg: jnp.sum(lg * lg) / lg.size)
    assert pipe.compile_stats.late == 0, pipe.compile_stats.summary()


def test_warmup_escape_hatch_counts_late_compiles(decode_setup):
    """``warmup=False`` skips precompile; the compiles that then land
    inside the timed window are counted — the measurement the default
    mode exists to keep at zero."""
    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan, warmup=False)
    pipe.serve([list(range(2, 40))] * 2, 4, group_size=2)
    assert pipe.compile_stats.late > 0


def test_aot_program_is_traceable_and_bitwise_equal_jit():
    """An AotProgram is a drop-in for the jit it wraps: concrete calls
    (compiled path) match the jit bitwise, and `jax.vjp` traces through
    it (the train path's contract)."""
    def fn(p, x):
        return (x @ p["w"]).astype(jnp.float32)

    rng = np.random.default_rng(0)
    p = {"w": jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(2, 8)), jnp.float32)
    prog = AotProgram(fn, name="t")
    jit_out = jax.jit(fn)(p, x)
    np.testing.assert_array_equal(np.asarray(prog(p, x)), np.asarray(jit_out))
    y, vjp = jax.vjp(prog, p, x)
    ref_y, ref_vjp = jax.vjp(jax.jit(fn), p, x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref_y))
    g = vjp(jnp.ones_like(y))
    rg = ref_vjp(jnp.ones_like(ref_y))
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(rg)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_aot_precompile_from_structs_hits_at_runtime():
    """precompile() with ShapeDtypeStructs (sharding attached) builds the
    executable the concrete call then hits — zero cache-miss compiles."""
    from jax.sharding import SingleDeviceSharding
    def fn(p, x):
        return x * p

    stats = CompileStats()
    prog = AotProgram(fn, name="t", stats=stats)
    dev = jax.devices()[0]
    sh = SingleDeviceSharding(dev)
    prog.precompile(jax.ShapeDtypeStruct((4,), jnp.float32, sharding=sh),
                    jax.ShapeDtypeStruct((4,), jnp.float32, sharding=sh))
    assert stats.compiles == 1
    p = jax.device_put(jnp.ones((4,), jnp.float32), dev)
    x = jax.device_put(jnp.arange(4, dtype=jnp.float32), dev)
    out = prog(p, x)
    assert stats.compiles == 1 and stats.misses == 0 and stats.late == 0
    np.testing.assert_array_equal(np.asarray(out), np.arange(4, dtype=np.float32))


def test_shared_embed_program_one_compile_per_aval(decode_setup):
    """The satellite fix: prefill and decode embed share ONE program (the
    old pair of jit instances of the same function paid separate compile
    caches) — identical avals compile once."""
    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan)
    assert not hasattr(pipe, "_embed_prefill") and \
        not hasattr(pipe, "_embed_decode")
    pipe.serve([list(range(2, 10))] * 2, 4, group_size=2)
    n0 = pipe._embed.n_compiled
    # decode embed aval (B, 1) already compiled: a second serve with the
    # same grouping adds no embed executables
    pipe.serve([list(range(2, 10))] * 2, 4, group_size=2)
    assert pipe._embed.n_compiled == n0


# ===========================================================================
# host-overhead accounting
# ===========================================================================
def test_host_overhead_surfaces_in_report(lm_setup):
    from repro.runtime.pipeline import compare_lm
    shape = ShapeCfg("donate_lm", 16, 8, "train")
    plan = planner.plan(tiny, shape, chips=16, max_tp=4)
    stg, _ = lm_graph.build_stg(tiny, shape, max_tp=4)
    pipe, mbs = lm_setup
    res = pipe.run(mbs * 2)
    for st in pipe.stages:
        assert res.stage_host_us(st.name) > 0
    rep = compare_lm(stg, selection_from_plan(plan), res)
    assert any(m.host_v is not None and m.host_v > 0
               for m in rep.stages.values())
    assert "host" in rep.summary()
    # host overhead must be a component of, not exceed, total stage time
    for st in pipe.stages:
        assert (res.stage_dispatch_s[st.name]
                <= res.stage_seconds[st.name] + 1e-6)


def test_serve_run_reports_host_overhead(decode_setup):
    plan, stg = decode_setup
    pipe = DecodePipeline(tiny, stg, plan)
    run = pipe.serve([list(range(2, 12))] * 4, 6, group_size=2)
    for name in pipe.stage_names:
        assert run.stage_host_us(name) > 0


# ===========================================================================
# fused decode kernels keep the donation contract
# ===========================================================================
@pytest.mark.parametrize("impl", ["ref", "fused", "interpret"])
def test_fused_step_cache_out_aval_matches_contract(impl):
    """The fused single-token step must return caches with EXACTLY the
    avals `decode_cache_structs` promises — leaf-for-leaf — under every
    kernel impl, or cache donation would silently stop aliasing."""
    import functools
    params = lm.init_params(tiny, jax.random.PRNGKey(0))
    sub = lm.slice_periods(params["layers"], 0, tiny.n_periods)
    cin, cout = lm.decode_cache_structs(tiny, sub, batch=2, prompt=8, cap=16)
    step = functools.partial(lm.decode_blocks, tiny, impl=impl)
    x = jax.ShapeDtypeStruct((2, 1, tiny.d_model), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    _, got = jax.eval_shape(step, sub, cin, x, pos)
    assert jax.tree.structure(got) == jax.tree.structure(cout)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(cout)):
        assert a.shape == b.shape and a.dtype == b.dtype, impl


def test_single_device_server_decode_is_donated():
    """PR-5 leftover: `LMServer`'s non-pipelined decode loop compiles
    `decode_step` with the cache donated — every leaf aliases in place
    (zero new cache allocations per token) and a stale read is loud."""
    srv = LMServer(tiny, max_batch=2)
    batch = {"tokens": jnp.asarray([[2, 3, 4, 5], [3, 4, 5, 6]], jnp.int32)}
    _, cache = srv._prefill(srv.params, batch, 12)
    old_leaves = [l for l in jax.tree.leaves(cache)
                  if hasattr(l, "unsafe_buffer_pointer")]
    ptrs_in = sorted(l.unsafe_buffer_pointer() for l in old_leaves
                     if l.ndim >= 2)          # cache tensors, not pos scalar
    cur = jnp.asarray([[7], [8]], jnp.int32)
    _, cache2 = srv._decode(srv.params, cache, cur)
    jax.block_until_ready(jax.tree.leaves(cache2))
    assert all(l.is_deleted() for l in old_leaves)
    ptrs_out = sorted(l.unsafe_buffer_pointer()
                      for l in jax.tree.leaves(cache2) if l.ndim >= 2)
    assert ptrs_out == ptrs_in, "cache leaves must alias in place"
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(old_leaves[0])


@pytest.mark.parametrize("impl", ["fused", "interpret"])
def test_single_device_logits_agree_across_impls(impl):
    """The donated fused-step server and the interpret-mode Pallas kernels
    give the logits of the historical (`impl="ref"`) single-device path,
    for the prefill and six decode steps fed the same token history.

    Logits, not sampled tokens: with random weights the largest logit
    changes on rounding.  Bound: 4 bf16 ulps of the largest |logit|.  The
    compute dtype is bfloat16, and the tiers round activations in
    different places (blocked online softmax against one pass), which
    measured at most 2 such ulps here.  A wrong mask, ring slot or
    layer moves the logits by tens of ulps."""
    rng = np.random.default_rng(21)
    B, bucket, steps = 2, 16, 6
    toks = np.zeros((B, bucket), np.int32)
    for i, n in enumerate(rng.integers(4, bucket, B)):
        toks[i, bucket - n:] = rng.integers(2, tiny.vocab, n)
    fed = rng.integers(2, tiny.vocab, (steps, B))
    params = lm.init_params(tiny, jax.random.PRNGKey(0))
    want, got = (
        [np.asarray(l, np.float32) for l in LMServer(
            tiny, params=params, impl=i).forced_logits(toks, fed, bucket + steps)]
        for i in ("ref", impl))
    assert len(got) == steps + 1
    ulp = 2.0 ** -8 * max(np.abs(w).max() for w in want)
    for step, (w, g) in enumerate(zip(want, got)):
        assert g.shape == w.shape == (B, 1, tiny.padded_vocab)
        assert np.abs(g - w).max() <= 4 * ulp, (impl, step)


_TP_DONATE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs.tiny import CONFIG as tiny
from repro.models import lm

assert len(jax.devices()) == 8
mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
params = lm.init_params(tiny, jax.random.PRNGKey(0))
sub = lm.slice_periods(params["layers"], 0, tiny.n_periods)

prefill = jax.jit(functools.partial(lm.prefill_blocks, tiny, impl="fused"),
                  static_argnames=("cap",))
x = jnp.zeros((2, 8, tiny.d_model), jnp.bfloat16)
_, cache = prefill(sub, x, jnp.arange(8), cap=16)

# shard every cache leaf over the kv-head axis of the 2-way tp sub-mesh
def shard(l):
    spec = [None] * l.ndim
    spec[3] = "tp"            # (layers, B, C, KV, hd) stacked leaf
    return jax.device_put(l, NamedSharding(mesh, P(*spec)))
cache = jax.tree.map(shard, cache)

step = jax.jit(functools.partial(lm.decode_blocks, tiny, impl="fused"),
               donate_argnums=(1,))
old = jax.tree.leaves(cache)
shardings_in = [l.sharding for l in old]
ptrs_in = sorted(s.data.unsafe_buffer_pointer()
                 for l in old for s in l.addressable_shards)
xd = jnp.zeros((2, 1, tiny.d_model), jnp.bfloat16)
_, cache2 = step(sub, cache, xd, jnp.asarray(8, jnp.int32))
jax.block_until_ready(jax.tree.leaves(cache2))
assert all(l.is_deleted() for l in old), "tp-sharded donation must consume"
ptrs_out = sorted(s.data.unsafe_buffer_pointer()
                  for l in jax.tree.leaves(cache2)
                  for s in l.addressable_shards)
assert ptrs_out == ptrs_in, "every shard must alias in place"
for l, sh in zip(jax.tree.leaves(cache2), shardings_in):
    assert l.sharding.is_equivalent_to(sh, l.ndim), \
        "donation must preserve the tp sharding"
print("TP_DONATE_OK")
"""


def test_tp_sharded_decode_cache_donation():
    """PR-5 leftover: donation still aliases shard-for-shard when the
    decode cache is tp-sharded over a sub-mesh (8 simulated devices,
    kv-head axis partitioned 2-way) — run in a subprocess so the forced
    device count cannot leak into this process's backend."""
    import subprocess
    import sys
    import os
    r = subprocess.run([sys.executable, "-c", _TP_DONATE],
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-2500:])
    assert "TP_DONATE_OK" in r.stdout
