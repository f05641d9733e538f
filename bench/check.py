"""Decides ``correct``: what the timed window served, against the plain
reference.

From the requests the window finished, a sample drawn from the seed, with
the longest request always in it, is taken until it holds at least
``min_tokens`` served tokens.  The reference runs once over each prompt
followed by its served tokens, and the number compared is the widest gap
by which a served token's logit lies below the reference's best logit at
its position (the traffic is greedy, so a correct program serves the
reference's best token up to rounding).  Every served token is covered:
the first comes from the admission prefill, the others from decode steps
through the paged cache and the Pallas decode-attention kernel.

Each finished request must also have exactly the length it asked for (no
stop tokens are set), and none may be left unfinished.

The limit of the widest gap is the configuration's ``check`` entry; how it
was set, from the program's readings on many seeds and the float8
control's, is in PERF.md.
"""
from __future__ import annotations

import numpy as np


def sample(served: dict, seed: int, min_tokens: int) -> list:
    """uids to compare: the longest request (prompt + output), then others
    in an order drawn from the seed until ``min_tokens`` served tokens."""
    if not served:
        return []
    uids = sorted(served)
    longest = max(uids, key=lambda u: (len(served[u][0]) + len(served[u][1]),
                                       -u))
    rest = [u for u in uids if u != longest]
    order = np.random.default_rng([seed, 7]).permutation(len(rest))
    picked, tokens = [longest], len(served[longest][1])
    for i in order:
        if tokens >= min_tokens:
            break
        picked.append(rest[i])
        tokens += len(served[rest[i]][1])
    return picked


def run(ref, config: dict, params, served: dict, wanted: dict, *,
        unfinished: int, seed: int, min_tokens: int, control: bool = False,
        log=print):
    """Returns ``(checks, details)``.  ``checks`` maps each number compared
    to ``{"value", "limit"}``; ``details`` holds the sample and, with
    ``control``, ``control_checks``: the same checks with the float8
    control's widest gap on the same prompts and tokens."""
    wrong = sum(len(served[u][1]) != wanted[u] for u in served)
    picked = sample(served, seed, min_tokens)
    widest, widest_ctl, n_tok = 0.0, 0.0, 0
    for u in picked:
        prompt, out = served[u]
        g = ref.served_gaps(config, params, prompt, out, control=control)
        widest = max(widest, float(g["served"].max()))
        n_tok += len(out)
        if control:
            widest_ctl = max(widest_ctl, float(g["control"].max()))
    log(f"check: {len(picked)} requests, {n_tok} served tokens compared "
        f"with the reference; widest logit gap {widest!r}"
        + (f"; float8 control's widest gap {widest_ctl!r}" if control
           else ""))
    def checks(gap):
        return {
            "nothing_compared": {"value": int(not picked), "limit": 0},
            "wrong_length": {"value": int(wrong), "limit": 0},
            "unfinished": {"value": int(unfinished), "limit": 0},
            "widest_logit_gap": {"value": gap,
                                 "limit": config["check"]["widest_logit_gap"]},
        }

    details = {"sampled": picked, "tokens_compared": n_tok,
               "widest_gap": widest}
    if control:
        # the control in the program's place: its picks on the same
        # requests, judged by the same checks and limits
        details["control_checks"] = checks(widest_ctl)
    return checks(widest), details


def passed(checks: dict) -> bool:
    """``correct``: every number compared within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())
