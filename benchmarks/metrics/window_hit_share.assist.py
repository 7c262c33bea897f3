"""Layer `cache`: of the prompt tokens whose full-group blocks matched
in the prefix cache, the share the window group could serve as well and
so was not computed again: the counters `prefix_hit_tokens` over
`prefix_hit_tokens` + `prefix_tokens_lost_to_window`, window only."""


def read(run):
    delta = run["facts"].get("delta", {})
    hit = delta.get("prefix_hit_tokens")
    lost = delta.get("prefix_tokens_lost_to_window")
    if hit is None or lost is None or not hit + lost:
        return None
    return 100.0 * hit / (hit + lost)
