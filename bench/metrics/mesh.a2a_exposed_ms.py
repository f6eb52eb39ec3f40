"""Milliseconds per call of collective time during which no other operation
ran on the chip, on the chip where it is largest (device trace).  Nothing
when no collective ran."""


def read(run):
    t = run.trace
    if not any(d.collective_s > 0 for d in t.devices):
        return None
    return max(d.exposed_collective_s for d in t.devices) / run.calls * 1e3
