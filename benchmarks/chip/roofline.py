"""Operations and bytes of attention from shapes, the table of peaks, and
shares of them. A model family's own counts (its matrix products, and how
its layers attend) live in its file, ``families/<family>.py``, built on
these.

The attention counts follow the repository's analytic roofline
(``benchmarks/bench_kernels.py:_attn_roofline``), with two changes so that a
share reads the same work whatever implements it: only the causal half of a
prefill or training sequence counts, and a decode row counts only the kv
positions it may attend to. Bytes are the least any implementation moves:
q, k and v read once, the output (and, for the causal kernel, its
log-sum-exp row) written once.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device is not in the table of peaks; no default is assumed."""


def peaks_for(device_kind: str, path: Path = PEAKS) -> Dict[str, float]:
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return {k: float(v) for k, v in table[device_kind].items()}


def decode_attention(kv_len: int, heads: int, kv_heads: int, head_dim: int,
                     dtype_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of one decode row of one layer over ``kv_len`` valid
    positions: q.k and p.v for every query head; k and v read once per kv
    head, q read and the output written once."""
    flops = 4.0 * heads * head_dim * kv_len
    nbytes = (2.0 * kv_heads * kv_len + 2.0 * heads) * head_dim * dtype_bytes
    return flops, nbytes


def causal_attention(seq: int, heads: int, kv_heads: int, head_dim: int,
                     dtype_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of the causal forward of one sequence of one layer:
    seq * (seq + 1) / 2 query-key pairs; q, k, v read and the output
    written once, plus a float32 log-sum-exp per query row and head."""
    pairs = seq * (seq + 1) / 2.0
    flops = 4.0 * heads * head_dim * pairs
    nbytes = ((2.0 * heads + 2.0 * kv_heads) * seq * head_dim * dtype_bytes
              + 4.0 * heads * seq)
    return flops, nbytes


def share(flops: float, nbytes: float, seconds: float,
          peaks: Dict[str, float]) -> Tuple[float, str]:
    """Roofline share in percent: the least time the chip could take (the
    larger of operations over peak and bytes over bandwidth) over the time
    taken, and which of the two bounds it."""
    t_flop = flops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flop >= t_mem else "memory"
    return 100.0 * max(t_flop, t_mem) / seconds, bound
