"""Operations and bytes that a kernel call needs, computed from its shapes.

These are the numerators of the benchmark's roofline and utilization
metrics; the times they are divided by come from the run. A call's shapes
are read from its HLO instruction, which is the name of its device
operation in the trace.
"""

from __future__ import annotations

import re
from typing import Dict, Tuple

import numpy as np

_DTYPES = {"u8": 1, "s8": 1, "bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8}
_ARRAY = re.compile(r"\b(" + "|".join(_DTYPES) + r")\[([0-9,]*)\]")


def _nbytes(dtype: str, dims: str) -> Tuple[int, int]:
    n = int(np.prod([int(d) for d in dims.split(",") if d])) if dims else 1
    return n, n * _DTYPES[dtype]


def elementwise_cost(hlo: str, ops_per_element: int) -> Tuple[float, float]:
    """(operations, bytes) of one call of an elementwise kernel from its HLO
    instruction ``%name = <out type>[dims]{layout} custom-call(<operands>)``:
    ``ops_per_element`` per output element; every operand read once and the
    output written once. For the u8 dequant (a multiply and an add) that is
    the codes in, the output out, and a float32 scale and bias per lane."""
    out_part, _, operands = hlo.partition("custom-call(")
    out = _ARRAY.search(out_part)
    if out is None:
        raise ValueError(f"no output shape in {hlo[:120]!r}")
    n_out, out_bytes = _nbytes(*out.groups())
    operands = operands.split("custom_call_target")[0]
    in_bytes = sum(_nbytes(*m.groups())[1] for m in _ARRAY.finditer(operands))
    return float(ops_per_element * n_out), float(out_bytes + in_bytes)


def roofline_time(ops: float, nbytes: float, peak: Dict[str, float]) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
