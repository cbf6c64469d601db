"""Work a dense GQA + SwiGLU decoder needs, counted from shapes.

Every count is of the work the model needs, whatever implements it:
real prompt tokens and filled cache positions only (no bucket padding,
no empty cache rows up to ``max_len``), bytes at the configuration's
dtype.  So no change to the program can push a share of a peak or a
roofline computed from these counts past 100%.

``m`` is a configuration file's ``model`` block (published key names).
A kernel call is a pair ``(flops, bytes)``.
"""

from __future__ import annotations

from typing import Iterable, List, Tuple

from bench.references.dense import head_dim

Call = Tuple[float, float]
BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(m: dict):
    d = m["hidden_size"]
    h, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return d, h, hkv, head_dim(m), m["intermediate_size"], m["vocab_size"]


def _elt(m: dict) -> int:
    return BYTES[m["torch_dtype"]]


def swiglu_call(m: dict, tokens: int) -> Call:
    """RMSNorm + gated MLP over ``tokens`` rows: three matmuls; the
    weights, the gain and the rows in and out cross HBM once."""
    d, _, _, _, f, _ = dims(m)
    flops = 6.0 * tokens * d * f
    nbytes = _elt(m) * (3.0 * d * f + d + 2.0 * tokens * d)
    return flops, nbytes


def decode_attention_call(m: dict, contexts: Iterable[int]) -> Call:
    """One-token attention for each sequence over its filled cache: QK
    and PV over ``c`` positions; K and V of those positions, Q and the
    output cross HBM once."""
    _, h, hkv, dh, _, _ = dims(m)
    cs = list(contexts)
    n = float(sum(cs))
    flops = 4.0 * h * dh * n
    nbytes = _elt(m) * (2.0 * hkv * dh * n + 2.0 * h * dh * len(cs))
    return flops, nbytes


def prefill_attention_call(m: dict, p: int) -> Call:
    """Causal attention over a ``p``-token prompt: token t attends to
    t + 1 positions; Q, K, V and the output cross HBM once."""
    _, h, hkv, dh, _, _ = dims(m)
    flops = 4.0 * h * dh * p * (p + 1) / 2.0
    nbytes = _elt(m) * (2.0 * h + 2.0 * hkv) * dh * p
    return flops, nbytes


def decode_kernel_calls(m: dict, contexts: List[int]) -> List[Call]:
    """The attention and SwiGLU kernel calls of one decode step in which
    the active sequences attend over ``contexts`` positions each."""
    per_layer = [decode_attention_call(m, contexts),
                 swiglu_call(m, len(contexts))]
    return per_layer * m["num_hidden_layers"]


def prefill_kernel_calls(m: dict, p: int) -> List[Call]:
    """The attention and SwiGLU kernel calls of one ``p``-token prefill."""
    per_layer = [prefill_attention_call(m, p), swiglu_call(m, p)]
    return per_layer * m["num_hidden_layers"]


def least_time(calls: Iterable[Call], peak: dict) -> float:
    """Seconds the chip needs at best: each call bound by its compute or
    its memory traffic, whichever takes longer."""
    return sum(max(f / peak["bf16_flops_per_s"], b / peak["hbm_bytes_per_s"])
               for f, b in calls)


def _matmul_params(m: dict) -> float:
    d, h, hkv, dh, f, _ = dims(m)
    return d * h * dh + 2.0 * d * hkv * dh + h * dh * d + 3.0 * d * f


def decode_flops(m: dict, context: int) -> float:
    """Model FLOPs of one output token whose step attends over
    ``context`` positions, its head included."""
    d, h, _, dh, _, v = dims(m)
    layer = 2.0 * _matmul_params(m) + 4.0 * h * dh * context
    return m["num_hidden_layers"] * layer + 2.0 * d * v


def prefill_flops(m: dict, p: int) -> float:
    """Model FLOPs of a ``p``-token prompt: every layer at every
    position (causal attention), the head at the last position only,
    since only its logits are needed."""
    d, h, _, dh, _, v = dims(m)
    layer = 2.0 * _matmul_params(m) * p + 4.0 * h * dh * p * (p + 1) / 2.0
    return m["num_hidden_layers"] * layer + 2.0 * d * v
