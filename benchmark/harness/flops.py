"""Operations and bytes of a kernel from its operand shapes, and the least
time the chip could take for them. Nothing here is measured and nothing comes
from XLA's cost analysis (which cannot see inside a Mosaic call), and nothing
here knows a model: per-token model operations are a family's
(``families/<family>/``, README.md there).

A multiply-add counts as two operations. Recomputed operations are never
counted, so a share of the peak built on these numbers errs low, not high.
"""

from __future__ import annotations

from typing import Dict


def attention_kernel(batch_heads: int, q_len: int, k_len: int, head_dim: int,
                     causal: bool, products: int, tensors: int,
                     itemsize: int = 2) -> Dict[str, float]:
    """What one attention kernel call needs, from its operand shapes
    ([batch*heads, T, D] as the kernels fold them).

    ``products``: the [T, T]-sized matrix products the call's results need.
    The forward kernel needs two (q.k and p.v). The backward pass needs four
    (dv, dp, dq, dk), two in each of its two kernels (dq; dk and dv); the
    q.k, and in the second kernel dp, that they compute again are
    recomputation and are not counted. Causal masking halves a product.
    ``tensors``: the [batch*heads, T, D] arrays the call reads or writes,
    each moved once (q, k, v in and o out: four for the forward kernel)."""
    flops = products * 2.0 * batch_heads * q_len * k_len * head_dim
    if causal:
        flops *= 0.5
    bytes_ = float(tensors * batch_heads * max(q_len, k_len) * head_dim
                   * itemsize)
    return {"flops": flops, "bytes": bytes_}


def roofline_seconds(flops: float, bytes_: float, peak: Dict[str, float]
                     ) -> Dict[str, object]:
    """The least time the chip could take and which limit sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
