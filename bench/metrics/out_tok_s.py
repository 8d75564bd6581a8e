"""out_tok_s: output tokens that became visible in the window, over the
window (host clock; the window ends when its last step returns)."""


def read(r):
    tokens = sum(rec["count"] for rec in r.window.records)
    return tokens / r.elapsed
