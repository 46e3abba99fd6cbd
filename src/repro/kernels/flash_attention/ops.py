"""jit'd public wrapper for flash attention.

On TPU: interpret=False executes the Pallas kernel with the BlockSpec VMEM
tiling; on this CPU container interpret=True runs the same body for
validation. The wrapper accepts model-layout tensors (B, S, H, D) and
handles layout transposition.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "use_kernel", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0,
                    use_kernel: bool = True,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, S, H, D); k, v: (B, S, KV, D) — model layout. Returns like q."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if use_kernel:
        out = flash_attention_kernel(qt, kt, vt, causal=causal, window=window,
                                     interpret=interpret)
    else:
        out = attention_ref(qt, kt, vt, causal=causal, window=window)
    return out.transpose(0, 2, 1, 3)
