"""Layer `input`: host time a step waits in `next(loader)`, the
runner's own clock round the call, over the whole window."""


def read(run):
    facts = run["facts"]
    if not facts.get("steps"):
        return None
    return 1e3 * facts["loader_wait_s"] / facts["steps"]
