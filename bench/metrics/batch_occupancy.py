"""Slots decoded per fleet round, over the slots the cell's engines hold,
mean over rounds, in percent."""


def read(run):
    steps = run.rec.steps
    if not steps:
        return None
    slots = run.deployment["n_slots"] * run.deployment["devices"]
    return 100.0 * sum(s.decoded for s in steps) / len(steps) / slots
