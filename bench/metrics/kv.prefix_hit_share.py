"""Share of the prompt tokens admitted in the window that the prefix cache
served: tokens saved over tokens saved plus tokens prefilled (%)."""


def read(w):
    saved = w.counters.get("prefix_tokens_saved", 0)
    total = saved + w.counters.get("prefill_tokens", 0)
    if not total:
        return None
    return 100.0 * saved / total
