"""Layer `host_sampling`: the `observe` phase `sample` (host-side
sampling and slot bookkeeping) per engine step."""


def read(run):
    facts = run["facts"]
    steps = facts.get("delta", {}).get("steps")
    if not steps:
        return None
    return 1e3 * facts["sample_s"] / steps
