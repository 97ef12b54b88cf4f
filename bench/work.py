"""Operations and bytes that the served model's work needs, from shapes:
the chip's peaks and the arithmetic every family shares.

Every function counts the logical work of the algorithm, not what a
kernel's tiling happens to touch: a row whose context holds ``n`` tokens
reads ``n`` tokens of K and V, however the pages around them are laid
out.  The model dict is a configuration's ``model`` block
(``bench/configs/<name>.json``).  What a whole token or a whole call
costs depends on the architecture, and its family
(``bench/families/<family>.py``) adds it up from these pieces.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a kind missing from the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}")
    return table[device_kind]


def elem_bytes(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[dtype]


def attention_flops(m: dict, n_ctx: int, t: int = 1) -> float:
    """Score and value products of ``t`` new tokens over ``n_ctx`` cached
    ones plus their own causal chunk, in one layer."""
    h, hd = m["num_heads"], m["head_dim"]
    pairs = t * n_ctx + t * (t + 1) / 2
    return 4.0 * h * hd * pairs


def paged_attention_work(m: dict, rows: Iterable[Tuple[int, int]],
                         kv_dtype: str) -> Tuple[float, float]:
    """(operations, bytes) of one layer's paged-attention call, summed
    over its rows, each ``(n_ctx, t)``: ``t`` query tokens against
    ``n_ctx`` cached tokens plus the chunk itself.

    Bytes: the cached K and V the row reads, the chunk's own K and V,
    the queries read and the output written.
    """
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eb = elem_bytes(kv_dtype)
    ab = elem_bytes(m["dtype"])
    flops = nbytes = 0.0
    for n_ctx, t in rows:
        flops += attention_flops(m, n_ctx, t)
        nbytes += 2.0 * n_ctx * kv * hd * eb       # cached K and V
        nbytes += 2.0 * t * kv * hd * ab           # the chunk's K and V
        nbytes += 2.0 * t * h * hd * ab            # queries in, output out
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, pk: Dict[str, float]
                     ) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    tc = flops / pk["bf16_flops"]
    tm = nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
