"""Roofline of ``kernels.ops.remove_below``, SOCCER's fused removal sweep.

Its device events are the Pallas custom call that XLA names after the
jitted kernel wrapper (``%remove_below_pallas.N``, or the chunked variant
past 1,024 centers).

The work is counted from the entry point's logical shapes, whatever
implements it: ``x`` ``(m, p, d)`` float32 and ``k`` ``(k, d)`` float32
centers are read, the ``(m, p)`` bool alive mask is read and written,
``(m,)`` int32 live counts and the threshold are moved. The flops are the
distance cross term's ``2 m p k d``, as the algorithm needs them: the
kernel's ``Precision.HIGHEST`` matmul takes about six bf16 MXU passes for
each, and ``|x|^2``, the compare and the count are not counted. No lane
or center padding is counted.
"""
import re

EVENT = re.compile(r"^%remove_below(_chunked)?_pallas(\.\d+)? = ")


def work(m: int, p: int, d: int, k: int):
    """``(flops, bytes)`` of one call on ``(m, p, d)`` points and ``k``
    centers."""
    flops = 2.0 * m * p * k * d
    nbytes = 4.0 * m * p * d + 4.0 * k * d + 2.0 * m * p + 4.0 * m + 4.0
    return flops, nbytes
