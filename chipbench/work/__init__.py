"""Operations and bytes the algorithm needs, computed from shapes.

Counts are of what the computation needs, whatever implements it: 8-bit
weight and activation codes with their bfloat16 per-tile scales for the
ABFP matmuls, and only the cache that the live lengths cover for decode
attention.  A later kernel that skips padding or reads less than the
whole cache therefore reads higher against the same count.

A pass is described by a :class:`Pass`: its kind (``decode`` or
``prefill``), the real rows it carries, and per live slot the number of
new tokens and the cache length after them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Tuple


@dataclasses.dataclass(frozen=True)
class Work:
    """Operations (multiply and add count as two) and bytes moved."""

    ops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def min_seconds(self, ops_per_s: float, bytes_per_s: float) -> float:
        """The least time the chip could take: the larger of the two
        bounds."""
        return max(self.ops / ops_per_s, self.bytes / bytes_per_s)


@dataclasses.dataclass(frozen=True)
class Pass:
    """One jitted pass: per live slot, (new tokens, cache length after),
    and the prefill bucket (tokens per slot) it was padded to."""

    kind: str
    slots: Tuple[Tuple[int, int], ...]
    bucket: int = 1

    @property
    def rows(self) -> int:
        return sum(n for n, _ in self.slots)


def _tiles(k: int, tile: int) -> int:
    return math.ceil(k / tile)


def packed_matmul(m: int, k: int, n: int, tile: int = 128) -> Work:
    """One ABFP matmul of m rows, (k, n) weight: int8 codes and a bf16
    scale per (tile, column) for the weight, int8 codes and a bf16 scale
    per (row, tile) for the activations, bf16 output."""
    t = _tiles(k, tile)
    return Work(ops=2.0 * m * k * n,
                bytes=k * n + 2 * t * n + m * k + 2 * m * t + 2 * m * n)


def fused_qkv(m: int, k: int, ns: Tuple[int, ...], tile: int = 128) -> Work:
    """The three projections of one activation in one launch: the
    activation codes are read once."""
    t = _tiles(k, tile)
    w = sum(k * n + 2 * t * n for n in ns)
    return Work(ops=2.0 * m * k * sum(ns),
                bytes=w + m * k + 2 * m * t + 2 * m * sum(ns))


def expert_matmuls(rows: int, cfg: dict, tile: int = 128) -> Work:
    """One MoE layer's expert SwiGLU: each row through its top-k experts
    (the operations the routing needs), every expert's weights read once,
    and the activations of each (row, expert) pair."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    pairs = rows * k
    w = e * 3 * (d * f + 2 * _tiles(d, tile) * f)
    act = pairs * (2 * (d + 2 * _tiles(d, tile)) + (f + 2 * _tiles(f, tile))
                   + 2 * (2 * f + d))
    return Work(ops=2.0 * pairs * 3 * d * f, bytes=w + act)


def kv_attention(slots, cfg: dict) -> Work:
    """Decode attention over the int8 KV cache, one new token per slot:
    the codes and bf16 scales of the live positions of K and V, the query
    and the output (bf16)."""
    h, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    ops = sum(4.0 * h * hd * length for _, length in slots)
    cache = sum(length * kh * (2 * hd + 2 * 2) for _, length in slots)
    return Work(ops=ops, bytes=cache + len(slots) * h * hd * 2 * 2)


def matmul_calls(p: Pass, cfg: dict) -> List[Tuple[Work, int]]:
    """Every ABFP matmul of one pass (the packed and the fused-QKV
    kernels) as (work of one call, number of such calls), over all layers
    and the LM head, at the configuration's ABFP tile width."""
    tile = cfg["numerics"]["tile_width"]
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    m = p.rows
    if p.kind == "decode":
        calls = [(fused_qkv(m, d, (h * hd, kh * hd, kh * hd), tile), L)]
    else:
        calls = [(packed_matmul(m, d, h * hd, tile), L),
                 (packed_matmul(m, d, kh * hd, tile), 2 * L)]
    calls.append((packed_matmul(m, h * hd, d, tile), L))
    if cfg.get("num_local_experts"):
        calls.append((expert_matmuls(m, cfg, tile), L))
    else:
        f = cfg["intermediate_size"]
        calls += [(packed_matmul(m, d, f, tile), 2 * L),
                  (packed_matmul(m, f, d, tile), L)]
    calls.append((packed_matmul(len(p.slots), d, cfg["vocab_size"], tile), 1))
    return calls


def min_seconds(calls: List[Tuple[Work, int]], ops_per_s: float,
                bytes_per_s: float) -> float:
    """Least time for a list of (work, count): each call at its own bound."""
    return sum(n * w.min_seconds(ops_per_s, bytes_per_s) for w, n in calls)


def attention_calls(p: Pass, cfg: dict) -> List[Tuple[Work, int]]:
    """The decode attention kernel's calls in one pass, one per layer
    (prefill passes attend in XLA, not in this kernel: none)."""
    if p.kind != "decode":
        return []
    return [(kv_attention(p.slots, cfg), cfg["num_hidden_layers"])]


def model_flops(p: Pass, cfg: dict) -> float:
    """Model FLOPs of the pass's real tokens: two per active matmul
    weight per token (the router and the top-k experts for MoE), plus
    causal attention over each token's own context."""
    d, L, v = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    h, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f = cfg["intermediate_size"]
    attn_w = d * (h + 2 * kh) * hd + h * hd * d
    if cfg.get("num_local_experts"):
        ffn_w = (d * cfg["num_local_experts"]
                 + cfg["num_experts_per_tok"] * 3 * d * f)
    else:
        ffn_w = 3 * d * f
    per_token = 2.0 * L * (attn_w + ffn_w)
    total = 0.0
    for n, length in p.slots:
        # New tokens sit at positions length-n .. length-1; the one at
        # position j attends j + 1 keys.
        ctx = n * (length - n) + n * (n + 1) / 2
        total += n * per_token + 4.0 * L * h * hd * ctx
    # The LM head runs once per slot: on its last new token.
    return total + 2.0 * d * v * len(p.slots)
