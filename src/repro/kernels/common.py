"""Shared kernel-routing helpers for the Pallas kernel packages.

Every kernel package in ``repro.kernels`` follows the same three-way
routing convention: the compiled Pallas kernel on TPU, the pure-jnp
oracle as the production CPU path, and the kernel body under the Pallas
interpreter as the CPU debugging/parity path. ``resolve_kernel_mode``
is that convention as a function; callers (``repro.fed.batched``,
``repro.sim.core``, ``repro.policies.solvers``) resolve their
``use_kernel`` knob through it at trace time so a ``None`` default means
"fast path for the current backend" everywhere.
"""
from __future__ import annotations

import time
from typing import Callable, Optional, Sequence, Tuple

import jax
from jax._src import core as jax_core


def resolve_kernel_mode(use_kernel: Optional[bool]) -> Tuple[bool, bool]:
    """(use_kernel, interpret): Pallas compiled on TPU, interpret elsewhere.

    ``use_kernel=None`` auto-selects: the kernel path on TPU, the jnp
    oracle on CPU (interpret mode is a debugging tool, not a fast path).
    """
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu
    return bool(use_kernel), not on_tpu


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The ``interpret`` half of ``resolve_kernel_mode`` for a caller that
    is already on the kernel path: ``None`` -> compiled on TPU,
    interpreted elsewhere. Every Pallas wrapper defaults to ``None`` and
    resolves here, so no caller reaches the interpreter on a TPU unless
    it asks for it by name."""
    if interpret is None:
        return resolve_kernel_mode(True)[1]
    return bool(interpret)


def fastest(candidates: Sequence[int], inputs: Callable[[], tuple],
            run: Callable[..., object]) -> int:
    """The candidate ``c`` for which ``run(c, *inputs())`` is fastest
    here (one compile call each, then the mean of three timed calls).

    The ``best_tile`` autotuners call this at trace time, from inside
    the jit that will hold the kernel. Building the inputs and timing
    run in JAX's eval context, outside the trace in progress, so the
    timed calls execute on the device instead of being staged into that
    trace. (``jax.ensure_compile_time_eval`` will not do: it folds the
    kernel body's own constants while Pallas traces it.)"""
    best_s, pick = None, candidates[0]
    with jax_core.eval_context():
        args = inputs()
        for c in candidates:
            jax.block_until_ready(run(c, *args))      # compile
            t0 = time.perf_counter()
            for _ in range(3):
                jax.block_until_ready(run(c, *args))
            dt = (time.perf_counter() - t0) / 3
            if best_s is None or dt < best_s:
                best_s, pick = dt, c
    return pick
