"""Layer `cache`: prompt tokens served from the prefix cache, the
counters `prefix_hit_tokens` over `prompt_tokens`, window only."""


def read(run):
    delta = run["facts"].get("delta", {})
    if not delta.get("prompt_tokens"):
        return None
    return 100.0 * delta["prefix_hit_tokens"] / delta["prompt_tokens"]
