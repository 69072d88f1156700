"""Process start to window start: JAX start-up, compiles or loads from the
persistent cache, and the mix's warm prefix (host clock)."""


def read(run):
    return run.setup_s
