"""Device-busy milliseconds per 1000 tokens processed in the traced window:
prompt tokens the traced steps brought, chunk by chunk as the prefills
advanced (``runners/serve.py:Driver.count_prompt``), plus the decode tokens
the steps returned (the same count as serve_tok_s)."""


def read(v):
    tokens = v.counters["traced_prompt_tokens"] + v.counters["traced_pairs"]
    if v.trace is None or not tokens:
        return None
    return 1e3 * v.trace.busy_s() / (tokens / 1e3)
