"""Share of the traced window in which no operation ran on the device,
averaged over the chips, in percent."""


def read(run):
    t = run.trace
    if t is None or "tokens" not in run.counters:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
