"""XLA compiles the worker reported (heartbeat ``compiles``) between the
start and the end of the window; set-up warms every program, so 0."""


def read(run):
    return run.worker["after"]["compiles"] - run.worker["before"]["compiles"]
