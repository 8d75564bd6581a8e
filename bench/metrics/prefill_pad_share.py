"""prefill_pad_share: share of the token-steps launched in prefill that
were padding, from the window's deltas of the scheduler's `stats()`
counters: 1 - prefill_real_tokens / prefill_token_steps."""


def read(r):
    launched = r.stats.get("prefill_token_steps", 0)
    if launched <= 0:
        return None
    return 100.0 * (1.0 - r.stats["prefill_real_tokens"] / launched)
