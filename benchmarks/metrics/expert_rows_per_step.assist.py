"""Layer `experts`: rows of the grouped product an expert layer
computes in one engine step (the counter `expert_rows`, which the step
returns with its picks, over `steps` and the layers), sending window
only.  Padding columns are no rows."""


def read(run):
    window = run["facts"].get("window")
    by_expert = (window or {}).get("expert_rows_by_expert")
    if not by_expert or not window.get("steps"):
        return None
    return window["expert_rows"] / window["steps"] / len(by_expert)
