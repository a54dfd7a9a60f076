"""Process start -> first due time: runtime start, weight init, loading or
compiling every bucket program, the harness's warm set."""


def reduce(run):
    return run["setup_s"]
