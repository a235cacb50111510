"""The one general traffic generator. A traffic mix is a data file of
parameters (``perfbench/traffic/<name>.json``); this file turns it and a
seed into a schedule of requests. The program sees only the requests.

Parameters a mix may give (all lengths in tokens, times in seconds):

  arrivals   {"process": "poisson", "rate_per_s": r}
             {"process": "poisson", "rate_per_s": r, "count": "fixed"}
                 the same process conditioned on its count: exactly
                 round(r * span) arrivals, placed as sorted uniform draws.
                 A Poisson count over a 45 s window swings by 9 % from seed
                 to seed and the offered load with it; this keeps the
                 burstiness and fixes the amount of work
             {"process": "gamma", "rate_per_s": r, "cv": c}   bursty: gaps
                 Gamma-distributed with coefficient of variation c (>1)
             {"process": "uniform", "rate_per_s": r}          evenly spaced
  classes    [{"weight": w, "prompt_len": D, "output_len": D,
               "shared_prefix": {"pool": k, "len": n}}, ...]
             one class may be given flat as prompt_len/output_len at the top
  D          {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
             any D may add "stratified": true: the n lengths are the
                 distribution's values at n evenly spread quantiles (one
                 uniform draw inside each), in seeded random order, so every
                 seed offers nearly the same total of tokens
             {"dist": "uniform", "min": a, "max": b} | {"dist": "fixed", "value": v}
  shared_prefix   the first ``len`` tokens of each prompt of the class are one
             of ``pool`` seeded prefixes (system prompts, documents asked
             about again), chosen uniformly; the rest is fresh
"""

import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _lengths(rng, d, n):
    # u: where in its distribution each length sits, in (0, 1)
    if d.get("stratified"):
        u = (rng.permutation(n) + rng.uniform(0.0, 1.0, n)) / max(n, 1)
    else:
        u = rng.uniform(0.0, 1.0, n)
    u = np.clip(u, 1e-9, 1.0 - 1e-9)
    if d["dist"] == "fixed":
        out = np.full(n, d["value"])
    elif d["dist"] == "uniform":
        out = np.floor(d["min"] + u * (d["max"] - d["min"] + 1))
    elif d["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        out = np.exp(np.log(d["median"]) + d["sigma"] * z)
        out = np.clip(np.rint(out), d["min"], d["max"])
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return out.astype(np.int64)


def _gaps(rng, a, n):
    mean = 1.0 / a["rate_per_s"]
    if a["process"] == "poisson":
        return rng.exponential(mean, n)
    if a["process"] == "gamma":
        shape = 1.0 / a["cv"] ** 2
        return rng.gamma(shape, mean / shape, n)
    if a["process"] == "uniform":
        return np.full(n, mean)
    raise ValueError(f"unknown arrival process {a['process']!r}")


def schedule(mix, seed, start_s, end_s, vocab_size):
    """Requests due in [start_s, end_s): a list of dicts ``due_s``,
    ``prompt`` (int32 token ids below ``vocab_size``), ``max_new_tokens``,
    ``klass``, in order of ``due_s``. The same seed gives the same list.
    Time 0 is where a measured window opens: the ramp before it and the
    window are drawn apart, so that a fixed count and stratified lengths
    hold for the window by itself."""
    if start_s < 0.0 < end_s:
        return _segment(mix, [seed, 0], start_s, 0.0, vocab_size) \
            + _segment(mix, [seed, 1], 0.0, end_s, vocab_size)
    return _segment(mix, [seed, 1], start_s, end_s, vocab_size)


def _segment(mix, seed, start_s, end_s, vocab_size):
    rng = np.random.default_rng(seed)
    a = mix["arrivals"]
    span = end_s - start_s
    if a.get("count") == "fixed":
        if a["process"] != "poisson":
            raise ValueError("a fixed count is defined for poisson arrivals")
        due = start_s + span * np.sort(rng.uniform(
            0.0, 1.0, int(round(span * a["rate_per_s"]))))
    else:
        n = int(span * a["rate_per_s"] * 1.5) + 64
        due = start_s + np.cumsum(_gaps(rng, a, n))
        while due[-1] < end_s:                # a sparse draw: extend
            due = np.concatenate(
                [due, due[-1] + np.cumsum(_gaps(rng, a, n))])
        due = due[due < end_s]
    n = len(due)

    classes = mix.get("classes") or [
        {"weight": 1.0, "prompt_len": mix["prompt_len"],
         "output_len": mix["output_len"],
         "shared_prefix": mix.get("shared_prefix")}]
    weights = np.array([c["weight"] for c in classes], np.float64)
    klass = rng.choice(len(classes), n, p=weights / weights.sum())
    prompt_len = np.zeros(n, np.int64)
    output_len = np.zeros(n, np.int64)
    for k, c in enumerate(classes):
        pick = klass == k
        prompt_len[pick] = _lengths(rng, c["prompt_len"], int(pick.sum()))
        output_len[pick] = _lengths(rng, c["output_len"], int(pick.sum()))
    prefixes = [
        rng.integers(0, vocab_size, (c["shared_prefix"]["pool"],
                                     c["shared_prefix"]["len"]),
                     dtype=np.int32)
        if c.get("shared_prefix") else None for c in classes]

    # every token of every prompt in one draw, then cut to lengths
    tokens = rng.integers(0, vocab_size, int(prompt_len.sum()),
                          dtype=np.int32)
    cuts = np.concatenate([[0], np.cumsum(prompt_len)])
    out = []
    for i in range(n):
        prompt = tokens[cuts[i]:cuts[i + 1]]
        pool = prefixes[klass[i]]
        if pool is not None:
            shared = pool[rng.integers(len(pool))][:len(prompt)]
            prompt = np.concatenate([shared, prompt[len(shared):]])
        out.append({"due_s": float(due[i]), "prompt": prompt,
                    "max_new_tokens": int(output_len[i]),
                    "klass": int(klass[i])})
    return out
