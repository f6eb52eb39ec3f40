"""Work a call needs, counted from shapes, and the chip's published peaks.

The counts are the algorithm's, not what any implementation happens to do:
a roofline share built on them reads the same whatever runs the call.

* Kron-Matmul ``Y = X (F_1 x ... x F_N)``, X ``(M, prod P)``: the sliced
  multiply algorithm (FastKron, arXiv:2401.10187, section 3) applies one
  factor at a time, ``2 * rows * P_i * Q_i`` FLOPs for a stage whose input
  has ``rows * P_i`` elements.  The backward pass is the same chain on the
  transposed factors for dX, plus one ``(rows, P_i)^T (rows, Q_i)`` product
  per factor gradient, on the forward intermediate entering stage i and the
  cotangent leaving it.
* The least bytes of a call: its inputs read once and its outputs written
  once (forward: X, factors, Y; forward+backward: X, factors, the cotangent,
  Y, dX, dF).
* A language-model step: PaLM's count (Chowdhery et al. 2022, appendix B),
  6 FLOPs per matmul parameter per token, forward and backward, plus
  ``12 * layers * heads * head_dim * seq`` for the attention scores; the
  Kron projections at their algorithmic count, no recompute.
"""
from __future__ import annotations

import json
import math
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time for the work, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def kron_stage_rows(ps, qs) -> list[int]:
    """Per stage (last factor first, as the sliced algorithm runs), the
    number of length-P_i slices of one row of the stage's input."""
    rows, k = [], math.prod(ps)
    for p, q in zip(reversed(ps), reversed(qs)):
        rows.append(k // p)
        k = (k // p) * q
    return rows


def kron_fwd_flops(m: int, ps, qs) -> int:
    """FLOPs of ``Y = X (F_1 x ... x F_N)`` by the sliced multiply algorithm."""
    return sum(2 * m * r * p * q for r, p, q in
               zip(kron_stage_rows(ps, qs), reversed(ps), reversed(qs)))


def kron_fwdbwd_flops(m: int, ps, qs) -> int:
    """Forward, dX (the chain on the transposed factors) and every dF_i."""
    grads = sum(2 * m * r * p * q for r, p, q in
                zip(kron_stage_rows(ps, qs), reversed(ps), reversed(qs)))
    return kron_fwd_flops(m, ps, qs) + kron_fwd_flops(m, qs, ps) + grads


def kron_bytes(m: int, ps, qs, itemsize: int, *, grad: bool) -> int:
    """Least bytes: inputs read once, outputs written once."""
    x, y = m * math.prod(ps), m * math.prod(qs)
    factors = sum(p * q for p, q in zip(ps, qs))
    elems = x + y + factors
    if grad:
        elems += y + x + factors  # the cotangent in, dX and dF out
    return elems * itemsize


def kron_call_work(m: int, ps, qs, itemsize: int, *, grad: bool,
                   chips: int = 1) -> dict:
    """FLOPs and least bytes of one call, per chip when the rows and columns
    of X and Y are split evenly over ``chips``."""
    flops = kron_fwdbwd_flops(m, ps, qs) if grad else kron_fwd_flops(m, ps, qs)
    return {"flops": flops / chips,
            "bytes": kron_bytes(m, ps, qs, itemsize, grad=grad) / chips}


def balanced_factors(d: int, n: int) -> tuple[int, ...]:
    """``d`` split into ``n`` integer factors as evenly as its primes allow,
    largest first (the split a ``kron_ffn`` projection uses)."""
    primes, x, f = [], d, 2
    while f * f <= x:
        while x % f == 0:
            primes.append(f)
            x //= f
        f += 1
    if x > 1:
        primes.append(x)
    out = [1] * n
    for p in sorted(primes, reverse=True):
        out[out.index(min(out))] *= p
    return tuple(sorted(out, reverse=True))


def lm_shapes(cfg: dict) -> dict:
    """Parameter counts of a dense GQA decoder with ``kron_ffn`` projections,
    from the configuration file's keys."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    n = cfg["kron_ffn"]["factors"]
    up = (balanced_factors(d, n), balanced_factors(f, n))
    down = (balanced_factors(f, n), balanced_factors(d, n))
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    kron = 2 * sum(p * q for p, q in zip(*up)) + sum(p * q for p, q in zip(*down))
    norms = 2 * d + (2 * hd if cfg.get("qk_norm") else 0)
    embed = cfg["vocab_size"] * d
    head = 0 if cfg["tie_word_embeddings"] else embed
    layers = cfg["num_hidden_layers"]
    return {
        "attn_matmul": attn, "kron_up": up, "kron_down": down,
        "params": embed + head + d + layers * (attn + kron + norms),
        "head_matmul": embed,
    }


def lm_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs per token of one training step (forward and backward)."""
    s = lm_shapes(cfg)
    layers = cfg["num_hidden_layers"]
    (up_p, up_q), (dn_p, dn_q) = s["kron_up"], s["kron_down"]
    kron = 2 * kron_fwdbwd_flops(1, up_p, up_q) + kron_fwdbwd_flops(1, dn_p, dn_q)
    dense = 6 * (layers * s["attn_matmul"] + s["head_matmul"])
    scores = 12 * layers * cfg["num_attention_heads"] * cfg["head_dim"] * seq
    return dense + layers * kron + scores
