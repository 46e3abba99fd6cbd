"""Host time to dispatch one fused interval block (trace, compile-cache
lookup and enqueue): the mean ``dispatch_us`` of the ``fused_block*``
spans (milliseconds). The spans sync each block while the tracer is on,
so the figure holds for the traced run only."""


def read(ctx):
    d = [s["dispatch_us"] for s in ctx.spans
         if s.get("ev") == "span" and s["name"].startswith("fused_block")
         and "dispatch_us" in s]
    return sum(d) / len(d) / 1e3 if d else None
