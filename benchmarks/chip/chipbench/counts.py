"""Operations and bytes the work needs, from shapes alone.

These are the yardstick's own counts: the model's forward FLOPs per
token, the bytes one decode step must move, and each kernel's operations
and bytes per call.  They count what the algorithm requires, not what a
given implementation happens to do, so a faster program cannot change
them.  Element-wise work (norms, activations, rotary, softmax) is left
out of the FLOPs, as is usual for a model's FLOP count; a multiply-add
counts 2.

``m`` is the ``model`` section of a configuration file.  What differs
between model kinds, a layer's FLOPs and a layer's bytes in a decode
step, sits in the kind's own file (``references/<kind>.py``), and each
kernel's operations and bytes per call in its roofline metric's file.
"""
from __future__ import annotations

from . import registry

ACT_BYTES = 2            # bf16 activations and caches
PARAM_BYTES = {"bfloat16": 2, "float32": 4}


def layer_flops(m: dict, ctx: int) -> float:
    """Forward FLOPs of one layer for one token that attends ``ctx``
    positions (itself included)."""
    return registry.reference(m["kind"]).layer_flops(m, ctx)


def head_flops(m: dict) -> float:
    return 2 * m["d_model"] * m["vocab"]


def request_flops(m: dict, prompt: int, generated: int) -> float:
    """Forward FLOPs a request needs: its prompt through every layer, each
    generated token but the last fed back through every layer, and one
    head product per generated token."""
    L = m["n_layers"]
    fed = prompt + max(generated - 1, 0)           # positions 0 .. fed-1
    base = layer_flops(m, 0)
    per_ctx = layer_flops(m, 1) - base
    ctx_sum = fed * (fed + 1) / 2                  # position i attends i+1
    return L * (fed * base + per_ctx * ctx_sum) + generated * head_flops(m)


def decode_step_bytes(m: dict, batch: int, cache_len: int) -> float:
    """Bytes one decode step of the whole model must move: every weight
    read once, the live cache or the recurrent state read and written,
    the embedding rows gathered and the head's logits written.  After
    ``analysis/roofline.decode_stage_bytes`` of the program, kept here so
    that the yardstick does not move with the program."""
    d, L = m["d_model"], m["n_layers"]
    pb = PARAM_BYTES[m["param_dtype"]]
    per = registry.reference(m["kind"]).layer_step_bytes(m, batch, cache_len)
    per += 2 * batch * d * ACT_BYTES                            # activation in/out
    total = L * per
    total += batch * d * pb                                     # embedding rows
    total += d * 4 + d * m["vocab_rows"] * pb + batch * m["vocab_rows"] * ACT_BYTES
    return total


def least_time_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline's least time: the larger of the compute and the
    memory bound."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
