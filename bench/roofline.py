"""The yardstick's arithmetic: chip peaks, a kernel call's operations and
bytes, and a model's FLOPs, all from shapes.

Peaks are keyed by the ``device_kind`` JAX reports; a chip that is not
in :data:`PEAKS` is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float           # FLOP/s per chip
    hbm_bytes_per_s: float      # B/s per chip
    source: str


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s'),
}


def peaks_for(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device_kind {device_kind!r}")
    return PEAKS[device_kind]


def lowrank_call(m: int, c: int, r: int, s: int, itemsize: int = 2,
                 in_hbm: tuple[bool, bool, bool, bool] = (True,) * 4
                 ) -> tuple[float, float]:
    """Operations and HBM bytes of one ``y = (x @ w0) @ w1`` call with
    ``x (m, c)``, ``w0 (c, r)``, ``w1 (r, s)`` and ``y (m, s)``: both
    dots, and each of ``x, w0, w1, y`` that ``in_hbm`` says lives in HBM
    moved once (the rank intermediate stays on chip)."""
    ops = 2.0 * m * (c * r + r * s)
    sizes = (m * c, c * r, r * s, m * s)
    moved = float(itemsize) * sum(n for n, h in zip(sizes, in_hbm) if h)
    return ops, moved


def least_time(ops: float, moved: float, peaks: Peaks) -> float:
    """The least time the chip could take: the larger of its compute
    and its bandwidth bound."""
    return max(ops / peaks.flops_bf16, moved / peaks.hbm_bytes_per_s)


@dataclasses.dataclass(frozen=True)
class Linear:
    name: str
    c: int
    r: int                      # 0: dense
    s: int
    count: int                  # calls per token (layers)
    scope: str                  # "layer" (every position) | "head"


def model_linears(config: dict, ranks: dict[str, int]) -> list[Linear]:
    """The linears of a dense GQA decoder from its configuration file's
    sizes and the factor ranks (``{"blocks/attn/q": 768, ...}``)."""
    d = config["hidden_size"]
    h, kh = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    f = config["intermediate_size"]
    layers = config["num_hidden_layers"]
    gated = config["hidden_act"] in ("silu", "swiglu")
    shapes = {"blocks/attn/q": (d, h * hd), "blocks/attn/k": (d, kh * hd),
              "blocks/attn/v": (d, kh * hd), "blocks/attn/o": (h * hd, d),
              "blocks/mlp/up": (d, f), "blocks/mlp/down": (f, d)}
    if gated:
        shapes["blocks/mlp/gate"] = (d, f)
    out = [Linear(k, c, ranks.get(k, 0), s, layers, "layer")
           for k, (c, s) in shapes.items()]
    out.append(Linear("unembed", d, ranks.get("unembed", 0),
                      config["vocab_size"], 1, "head"))
    return out


def linear_flops(lin: Linear) -> float:
    per = (lin.c * lin.r + lin.r * lin.s) if lin.r else lin.c * lin.s
    return 2.0 * per * lin.count


def token_flops(config: dict, linears: list[Linear], context: int,
                head: bool) -> float:
    """Model FLOPs of one token at a position that attends ``context``
    keys: every layer's linears, attention's two products over the
    context, and the head when the token's logits are computed."""
    h, hd = config["num_attention_heads"], config["head_dim"]
    flops = sum(linear_flops(l) for l in linears if l.scope == "layer")
    flops += 4.0 * h * hd * context * config["num_hidden_layers"]
    if head:
        flops += sum(linear_flops(l) for l in linears if l.scope == "head")
    return flops


def prefill_flops(config: dict, linears: list[Linear], start: int,
                  count: int, head: bool) -> float:
    """Model FLOPs of prompt positions ``[start, start + count)`` under
    causal attention, with the head once if ``head``."""
    h, hd = config["num_attention_heads"], config["head_dim"]
    per = sum(linear_flops(l) for l in linears if l.scope == "layer")
    # sum of (p + 1) keys over the positions
    keys = count * start + count * (count + 1) / 2.0
    flops = per * count + 4.0 * h * hd * keys * config["num_hidden_layers"]
    if head:
        flops += sum(linear_flops(l) for l in linears if l.scope == "head")
    return flops
