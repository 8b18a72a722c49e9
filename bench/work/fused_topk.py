"""Least work of one launch of the fused score -> top-k kernel
(``src/repro/kernels/fused_topk``), from the logical shapes alone.

A launch scores ``rows`` real queries against the ``n_docs`` live rows of
the stored postings, ``width`` columns of ``itemsize`` bytes each.  What the
algorithm needs, whatever implements it:

  * ``gemm`` (classic fake words, bf16): 2 * rows * n_docs * width
    operations on the MXU, against the bf16 peak;
  * ``lsh`` (MinHash collision counts): equality compares on the VPU, for
    which no peak is published, so only the bytes bound counts;
  * bytes: the stored postings read once, n_docs * width * itemsize.  The
    query block, the running top-k and the output are negligible beside it.

Not counted, on purpose: the copy of the postings padded to whole lane tiles,
the second pass for a second query block, padding rows past ``n_docs`` and
the rows of the packed bucket tail.  An implementation that stops doing
them shows as a gain.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

# Device ops of the kernel in a TPU trace.  The op's name is its HLO text;
# the Pallas call is a Mosaic custom call named after the jitted wrapper
# (``%fused_topk.1 = (...) custom-call(...), custom_call_target=
# "tpu_custom_call"``, TPU v5 lite, JAX 0.9.0).  The name is not set by the
# program on purpose, so it is matched as recorded.
TRACE_NAME = r"^%fused_topk[.\d]* = .*tpu_custom_call"


def least_seconds(
    mode: str, rows: float, n_docs: int, width: int, itemsize: int, peaks: Dict[str, Any],
) -> Tuple[float, str]:
    """(least seconds for one launch, which bound sets it)."""
    t_bytes = n_docs * width * itemsize / float(peaks["hbm_bytes_per_s"])
    if mode == "gemm":
        t_ops = 2.0 * rows * n_docs * width / float(peaks["bf16_flops"])
    elif mode == "lsh":
        t_ops = 0.0
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return (t_ops, "compute") if t_ops > t_bytes else (t_bytes, "bytes")
