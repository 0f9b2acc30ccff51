"""Process start -> window start: imports, chip start-up, frame pool,
plans, compiles or cache loads, warm-up."""


def read(run):
    return run.setup_s
