"""``paddle.incubate`` — fused ops & experimental APIs.

The fused-op python APIs (``python/paddle/incubate/nn/functional``) map to
compositions XLA fuses automatically; they exist for source compatibility
and route to the same Pallas/XLA kernels as the nn.functional ops.
"""
from __future__ import annotations

from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import asp  # noqa: F401
from . import autograd  # noqa: F401
from .nn.functional import (softmax_mask_fuse,  # noqa: F401
                            softmax_mask_fuse_upper_triangle)


def segment_sum(data, segment_ids, name=None):
    import jax
    import numpy as np
    from ..framework.core import apply_jax, as_jax
    n = int(np.asarray(as_jax(segment_ids)).max()) + 1

    def f(d, ids):
        return jax.ops.segment_sum(d, ids.astype(np.int32), n)
    return apply_jax("segment_sum", f, data, segment_ids)


def identity_loss(x, reduction="none"):
    return x
