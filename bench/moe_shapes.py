"""Operations and bytes of the grouped expert matmuls of a sparse-expert
layer, from shapes and the program's expert counters alone.

One layer's experts run as three grouped matmuls (gate, up, down) over
the step's picks sorted by expert, each reading only the experts that
some pick chose.  Least work a call cannot avoid: 2 flops a multiply-add
over the picks' rows, and the touched experts' weights read once plus
the rows in and out once.
"""
from __future__ import annotations

# the grouped matmul kernel's custom call in the "XLA Ops" line
# (``gmm.<n>``), and its calls in one layer
GMM = ("gmm",)
CALLS_PER_LAYER = 3


def expert_matmuls(dims: dict, picks: float, touched: float,
                   elem_bytes: int = 2) -> list:
    """[(flops, bytes)] of the gate, up and down calls of one layer for
    ``picks`` token-expert rows touching ``touched`` distinct experts."""
    d, f = dims["d_model"], dims["d_expert"]
    w = touched * d * f * elem_bytes              # each call's weights read
    gate_up = (2.0 * picks * d * f, w + picks * (d + f) * elem_bytes)
    down = (2.0 * picks * f * d, w + picks * (f + d) * elem_bytes)
    return [gate_up, gate_up, down]
