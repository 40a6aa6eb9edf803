"""Scheduler: the share of the prompt tokens admitted in the window
whose keys the prefix cache already held, in %: the scheduler's
``n_prefix_tokens`` over its ``n_prompt_tokens``, each the difference
between the window's close and its open."""


def read(run):
    o, c = run.counters_open, run.counters_close
    if not c or "n_prompt_tokens" not in c:
        return None
    prompt = c["n_prompt_tokens"] - o.get("n_prompt_tokens", 0)
    if prompt <= 0:
        return None
    return 100.0 * (c["n_prefix_tokens"] - o.get("n_prefix_tokens", 0)) \
        / prompt
