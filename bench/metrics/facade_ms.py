"""Host time of the facade's set-up per experiment: the ``repro.obs``
spans ``run.resolve`` + ``env.realize`` + ``train.prepare``, summed and
averaged over the window's experiments (milliseconds)."""

PARTS = ("run.resolve", "env.realize", "train.prepare")


def read(ctx):
    spans = [s for s in ctx.spans if s.get("ev") == "span"]
    runs = sum(1 for s in spans if s["name"] == "run.resolve")
    if not runs:
        return None
    return sum(s["dur_us"] for s in spans if s["name"] in PARTS) / runs / 1e3
