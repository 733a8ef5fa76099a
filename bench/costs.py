"""Operations and bytes of the decode step and of the paged-decode
kernel, from shapes alone.

``m`` is a model's dimensions (``bench/models/<family>.dims``): d, h, kv,
hd, ff, v, n, tied.  ``ctx`` is a row's attended length at the step: its
cached tokens plus the one it writes.  Bytes are HBM bytes a step cannot
avoid: every weight read once, and the K and V of the valid positions.
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json; add its published peaks")
    return table[device_kind]


def layer_matmul_params(m: dict) -> int:
    q, k = m["h"] * m["hd"], m["kv"] * m["hd"]
    return m["d"] * (q + 2 * k) + q * m["d"] + 3 * m["d"] * m["ff"]


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    q, k = m["h"] * m["hd"], m["kv"] * m["hd"]
    per_layer = layer_matmul_params(m) + q + 2 * k + 2 * m["d"]
    tables = m["v"] * m["d"] * (1 if m["tied"] else 2)
    return (m["n"] * per_layer + tables + m["d"]) * itemsize


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    return m["n"] * 2 * m["kv"] * m["hd"] * itemsize


def decode_step(m: dict, ctx: list[int], itemsize: int = 2
                ) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over rows attending ``ctx``."""
    rows, tokens = len(ctx), sum(ctx)
    flops = rows * (2 * m["n"] * layer_matmul_params(m)
                    + 2 * m["d"] * m["v"]) \
        + m["n"] * 4 * m["h"] * m["hd"] * tokens
    byts = weight_bytes(m, itemsize) + kv_bytes_per_token(m, itemsize) \
        * (tokens + rows)
    return float(flops), float(byts)


def paged_decode_kernel(m: dict, ctx: list[int], block_size: int,
                        itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of the paged-decode kernel over all layers of one
    step: the valid blocks of each row (whole blocks are fetched), its
    query and its output."""
    blocks = sum(-(-c // block_size) for c in ctx) * block_size
    flops = m["n"] * 4 * m["h"] * m["hd"] * sum(ctx)
    byts = m["n"] * itemsize * (2 * m["kv"] * m["hd"] * blocks
                                + 2 * len(ctx) * m["h"] * m["hd"])
    return float(flops), float(byts)


def roofline_s(flops: float, byts: float, pk: dict) -> tuple[float, str]:
    """The least time the chip could take, and the bound that sets it."""
    tf, tb = flops / pk["bf16_flops"], byts / pk["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
