"""Host time of the bandit pre-scan that sizes the slots, per experiment:
the mean ``slots.prescan`` span (milliseconds)."""


def read(ctx):
    d = [s["dur_us"] for s in ctx.spans
         if s.get("ev") == "span" and s["name"] == "slots.prescan"]
    return sum(d) / len(d) / 1e3 if d else None
