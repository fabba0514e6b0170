"""Time per train step in which a collective-permute ran on a device with
no other operation running on it, averaged over the chips (trace)."""


def read(run):
    t = run.trace
    if t is None or not t.steps or not t.collective_s:
        return None
    return 1e3 * t.collective_exposed_s / t.steps
