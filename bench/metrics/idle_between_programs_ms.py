"""Device time with no program executing, per traced experiment: the
holes between the device plane's ``XLA Modules`` events inside the
traced stretch (milliseconds; ``bench/stages.py``). The rest of the idle
share lies inside programs."""
from bench import stages


def read(ctx):
    return stages.idle_between_programs_ms(ctx)
