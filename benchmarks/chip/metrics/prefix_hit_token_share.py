"""Scheduler (prefix index of ``serve/kv_cache.py``): the share of prompt
tokens admitted in the window (before the profiler trace starts) that
came from a cached prefix, from the engine's own counters
``prefix_hit_tokens / prefill_tokens``."""


def read(run):
    st = run["counters"]["stats_before_trace"]
    if not st.get("prefill_tokens"):
        return None
    return 100.0 * st["prefix_hit_tokens"] / st["prefill_tokens"]
