"""Prompt tokens served from the prefix cache over prompt tokens admitted, in
the window (`engine.metrics()` counters)."""


def read(ctx):
    c = ctx["counters"]
    if not c["prompt_tokens"]:
        return None
    return 100.0 * c["prefix_hit_tokens"] / c["prompt_tokens"]
