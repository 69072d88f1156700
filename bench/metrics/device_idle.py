"""Device: share of the traced window in which no operation ran on the
chip, 100 * (1 - union of device-op intervals / window), in %."""


def read(run):
    if run.reduced is None or run.reduced.n_devices == 0:
        return None
    return 100.0 * run.reduced.idle_share
