"""Scheduler: mean share of the engine's slots that were live, sampled
from its slot table after every pass the window dispatched, in percent."""


def read(run):
    if not run.occupancy:
        return None
    return 100.0 * sum(run.occupancy) / len(run.occupancy)
