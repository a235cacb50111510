"""Device-busy milliseconds per 1000 tokens processed in the traced window:
prompt tokens of the requests whose first token appeared there plus the
decode tokens the steps returned (the same count as serve_tok_s)."""


def read(v):
    tokens = v.counters["traced_prompt_tokens"] + v.counters["traced_pairs"]
    if v.trace is None or not tokens:
        return None
    return 1e3 * v.trace.busy_s() / (tokens / 1e3)
