"""jit'd public wrapper for the fused MoE router."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.moe_router.kernel import moe_router_kernel
from repro.kernels.moe_router.ref import moe_router_ref


@functools.partial(jax.jit, static_argnames=("top_k", "use_kernel",
                                             "interpret"))
def moe_router(logits: jax.Array, top_k: int, use_kernel: bool = True,
               interpret: Optional[bool] = None):
    """logits: (T, E). Returns (gates (T, k) f32, expert idx (T, k) i32)."""
    if use_kernel:
        return moe_router_kernel(logits, top_k, interpret=interpret)
    return moe_router_ref(logits, top_k)
