"""Share of the window's prompt tokens that the prefix cache served, %:
the change in ``engine.prompt_tokens_cached`` over that in
``engine.prompt_tokens`` (each prompt's pending last token aside)."""


def read(run):
    c0, c1 = run["counters0"]["counters"], run["counters1"]["counters"]
    if "engine.prompt_tokens" not in c1:
        return None
    total = c1["engine.prompt_tokens"] - c0.get("engine.prompt_tokens", 0)
    if not total:
        return None
    cached = (c1.get("engine.prompt_tokens_cached", 0)
              - c0.get("engine.prompt_tokens_cached", 0))
    return 100.0 * cached / total
