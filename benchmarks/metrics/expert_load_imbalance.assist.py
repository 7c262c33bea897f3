"""Layer `experts`: rows of the busiest expert over the mean of the
experts, per layer, averaged over the layers; sending window only.
1.0 = every expert saw the same load."""


def read(run):
    by_expert = (run["facts"].get("window") or {}) \
        .get("expert_rows_by_expert")
    if not by_expert:
        return None
    shares = [max(layer) * len(layer) / sum(layer)
              for layer in by_expert if sum(layer)]
    return sum(shares) / len(shares) if shares else None
