"""jit'd public wrapper for the RWKV6 WKV scan."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.rwkv6_scan.kernel import rwkv6_scan_kernel
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref


@functools.partial(jax.jit, static_argnames=("chunk", "use_kernel",
                                             "interpret"))
def rwkv6_scan(r, k, v, log_w, u, chunk: int = 64, use_kernel: bool = True,
               interpret: Optional[bool] = None):
    """r,k,log_w: (B,H,T,dk); v: (B,H,T,dv); u: (H,dk)."""
    if use_kernel:
        return rwkv6_scan_kernel(r, k, v, log_w, u, chunk=chunk,
                                 interpret=interpret)
    return rwkv6_scan_ref(r, k, v, log_w, u)
