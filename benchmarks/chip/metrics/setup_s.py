"""Set-up seconds: process start to window open (imports, weights, engine,
warm-up and compiles or cache loads, and a closed loop's ramp)."""


def read(run):
    return run.setup_s
