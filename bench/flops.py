"""Operations and bytes of the model and of its attention kernels, from
shapes alone.

Everything here reads a configuration file of ``bench/configs`` (the
published ``config.json`` keys), takes its sizes from the configuration's
model family (``bench/families/<reference>.py``: ``dims`` and the
counts of its weights), and counts what the algorithm needs:
matrix products as two operations per multiply-add, causal attention
over the positions each query may see, and each KV row read once per
call.  Work the program does beyond that (padding rows, re-fetched
pages, masked blocks) is not counted, so a share of a roofline or of a
peak computed from these counts cannot pass 100% unless the device time
leaves out part of the work.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from manifest import family_of

BF16 = 2


def dims(cfg: dict) -> dict:
    """The family's sizes; the attention counts below read ``L`` (layers),
    ``D`` (hidden), ``Hq``, ``Hkv`` (query and KV heads), ``hd`` (head
    size) and ``V`` (vocabulary)."""
    return family_of(cfg).dims(cfg)


def layer_matmul_params(cfg: dict, i: int = 0) -> int:
    """Weights one token multiplies in layer ``i`` (active experts
    only)."""
    return family_of(cfg).layer_matmul_params(cfg, i)


def param_count(cfg: dict) -> int:
    """All parameters the served model holds."""
    return family_of(cfg).param_count(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    return family_of(cfg).kv_bytes_per_token(cfg)


def attn_flops_per_layer(cfg: dict, q_len: int, ctx_start: int) -> int:
    """Causal attention of ``q_len`` queries whose first sees
    ``ctx_start + 1`` positions: QK^T and PV, two operations per
    multiply-add each."""
    m = dims(cfg)
    seen = q_len * ctx_start + q_len * (q_len + 1) // 2
    return 4 * m["Hq"] * m["hd"] * seen


def decode_attn_call(cfg: dict, ctx_lens: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) of one paged decode attention call (one layer)
    over sequences holding ``ctx_lens`` positions, the new one included:
    every KV row read once, the query read and the output written."""
    m = dims(cfg)
    flops = nbytes = 0
    for n in ctx_lens:
        flops += attn_flops_per_layer(cfg, 1, n - 1)
        nbytes += n * 2 * m["Hkv"] * m["hd"] * BF16 \
            + 2 * m["Hq"] * m["hd"] * BF16
    return flops, nbytes


def prefill_attn_call(cfg: dict, rows: Iterable[Tuple[int, int]]
                      ) -> Tuple[int, int]:
    """(operations, bytes) of one paged prefill attention call (one
    layer); ``rows`` are ``(pos_start, n_valid)`` of each sequence in the
    launch: the chunk's queries see the cached prefix and themselves,
    causally."""
    m = dims(cfg)
    flops = nbytes = 0
    for start, n in rows:
        if n <= 0:
            continue
        flops += attn_flops_per_layer(cfg, n, start)
        nbytes += (start + n) * 2 * m["Hkv"] * m["hd"] * BF16 \
            + 2 * n * m["Hq"] * m["hd"] * BF16
    return flops, nbytes


def step_flops(cfg: dict, decode_ctx: Iterable[int] = (),
               prefill_rows: Iterable[Tuple[int, int]] = ()) -> int:
    """Model operations of one launch: every token through every layer's
    matrix products and causal attention, and the head for each row
    whose logits are used (one per decoding sequence, the last valid
    token of each prefill row)."""
    m = dims(cfg)
    per_tok = 2 * sum(layer_matmul_params(cfg, i) for i in range(m["L"]))
    head = 2 * m["D"] * m["V"]
    total = 0
    for n in decode_ctx:
        total += per_tok + head + m["L"] * attn_flops_per_layer(cfg, 1, n - 1)
    for start, n in prefill_rows:
        if n <= 0:
            continue
        total += n * per_tok + head \
            + m["L"] * attn_flops_per_layer(cfg, n, start)
    return total


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
