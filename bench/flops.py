"""Operations and bytes, worked out from shapes: the yardstick of the
roofline shares and of the model FLOP utilisation.

``dims`` is a configuration's sizes as its reference module gives them
(``L`` layers, width ``D``, ``H`` heads of ``dh``, FFN width ``F``,
vocabulary ``V``).  A dense block has four D x (H dh) attention matrices
and a D x F / F x D feed-forward pair.
"""
from __future__ import annotations

import re
from typing import Dict, Tuple


def block_matrices(d: Dict) -> Dict[str, Tuple[int, int]]:
    """(K, N) of each weight matrix of one layer."""
    D, A, F = d["D"], d["H"] * d["dh"], d["F"]
    return {"wq": (D, A), "wk": (D, A), "wv": (D, A), "wo": (A, D),
            "w1": (D, F), "w2": (F, D)}


def quant_matmul_call(M: int, K: int, N: int, w_bits: int = 8,
                      x_bytes: int = 2, out_bytes: int = 2
                      ) -> Tuple[float, float]:
    """(operations, bytes) one x (M, K) @ dequant(q (K, N)) call needs:
    2 M K N multiply-adds, and the int weights, their per-column f32
    scales, the activations in and the result out."""
    ops = 2.0 * M * K * N
    nbytes = K * N * w_bits / 8 + 4 * N + M * K * x_bytes + M * N * out_bytes
    return ops, nbytes


_TYPE = re.compile(r"\b(bf16|f16|f32|s8|u8|s32)\[([0-9,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "u8": 1, "s32": 4}


def _shape(dims: str) -> Tuple[int, ...]:
    return tuple(int(x) for x in dims.split(",") if x)


def quant_matmul_from_hlo(op: str) -> Tuple[float, float, bool]:
    """(operations, bytes, int8 activations) of one ``quant_matmul``
    execution, from its HLO text as the trace names it:
    ``%quant_matmul.N = <out>[.., N] custom-call(<x>[.., K], <q>[K', N],
    f32[1, N])``.  The rows M are every leading dimension of x; int8
    weights hold K rows of N, packed int4 ones K / 2."""
    name, _, rest = op.partition(" = ")
    out = _TYPE.search(rest)
    call = rest.find("custom-call(")
    args = _TYPE.findall(rest[call:]) if call >= 0 else []
    if out is None or len(args) < 3:
        raise ValueError(f"not a quant_matmul call: {op[:120]!r}")
    (xt, xd), (qt, qd) = args[0], args[1]
    x, q, y = _shape(xd), _shape(qd), _shape(out.group(2))
    K, N = x[-1], y[-1]
    M = 1
    for m in x[:-1]:
        M *= m
    w_bits = 8 * _BYTES[qt] * q[0] * q[1] // (K * N)
    ops, nbytes = quant_matmul_call(M, K, N, w_bits=w_bits,
                                    x_bytes=_BYTES[xt],
                                    out_bytes=_BYTES[out.group(1)])
    return ops, nbytes, xt == "s8"


def roofline_s(ops: float, nbytes: float, peak_ops: float,
               peak_bytes_s: float) -> float:
    """The least time the chip could take: the larger of the two
    bounds."""
    return max(ops / peak_ops, nbytes / peak_bytes_s)


def token_flops(d: Dict, context: int, logits: bool = True) -> float:
    """Model FLOPs one token needs at ``context`` earlier positions
    (itself included): the block matmuls, attention's scores and values
    over the context, and the tied output head when the token's logits
    are needed."""
    weights = sum(K * N for K, N in block_matrices(d).values())
    attn = 2 * 2 * d["H"] * d["dh"] * context
    head = 2 * d["D"] * d["V"] if logits else 0
    return d["L"] * (2.0 * weights + attn) + head


def _tokens_flops(d: Dict, c0: int, count: int, logits: bool) -> float:
    """Sum of ``token_flops`` over contexts ``c0 .. c0 + count - 1``."""
    if count <= 0:
        return 0.0
    weights = sum(K * N for K, N in block_matrices(d).values())
    ctx = count * c0 + count * (count - 1) / 2
    head = 2 * d["D"] * d["V"] * count if logits else 0
    return d["L"] * (2.0 * weights * count + 4 * d["H"] * d["dh"] * ctx) \
        + head


def prefill_flops(d: Dict, s: int) -> float:
    """Useful FLOPs of one prompt of ``s`` real tokens: causal attention
    over the prompt alone, logits for its last token only."""
    return _tokens_flops(d, 1, s, logits=False) + 2 * d["D"] * d["V"]


def decode_flops(d: Dict, s: int, first: int, count: int) -> float:
    """Useful FLOPs of generating tokens ``first .. first + count - 1``
    of a request with an ``s``-token prompt: token i >= 1 comes from a
    decode step that feeds token i - 1 at context ``s + i`` and yields
    logits.  Token 0 comes from the prefill, so it costs nothing here."""
    lo = max(first, 1)
    return _tokens_flops(d, s + lo, first + count - lo, logits=True)
