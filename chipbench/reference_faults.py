"""Plain reference of the executor's fault semantics, with nothing imported
from the program under test.

``fault_schedule_errors`` holds one run's attempts to what a crash means:
a node that has crashed takes no new attempt and finishes none of the ones
it held, each attempt it held is lost at the crash, no node runs two
attempts at once, no instance is tried more often than its budget allows,
a retry waits out its capped exponential backoff, and every completed
instance gives one observation.

``reliability_factors`` is the Beta-Binomial price of each node's
attempts: with ``s`` successes and ``f`` failures on a node, the success
probability has the posterior Beta(A0 + s, B0 + f), and the node's factor
is ``1 / max(E[p] - k * sd[p], P_FLOOR)``.  While no attempt has been
recorded on any node, every factor is 1.
"""
from __future__ import annotations

import math
from collections import Counter

import numpy as np

A0, B0 = 8.0, 1.0       # the prior: E[p] = 8/9 before any attempt
P_FLOOR = 0.05          # floor of the widened success probability
BACKOFF_SLACK = 1e-9    # s: room for rounding in a retry's time


def reliability_factors(nodes, successes, failures, k) -> np.ndarray:
    """(N,) factors in ``nodes`` order; ``successes`` and ``failures`` map
    a node to its count (absent: 0)."""
    if not any(successes.values()) and not any(failures.values()):
        return np.ones(len(nodes))
    out = []
    for n in nodes:
        a = A0 + successes.get(n, 0)
        b = B0 + failures.get(n, 0)
        mean = a / (a + b)
        sd = math.sqrt(a * b / ((a + b) ** 2 * (a + b + 1.0)))
        out.append(1.0 / max(mean - k * sd, P_FLOOR))
    return np.array(out)


def attempt_counts(records, censored) -> tuple[Counter, Counter]:
    """Successes per node (the winning attempt of each completed instance,
    ``records``) and failures per node (the lost attempts, ``censored``)."""
    return (Counter(r["node"] for r in records),
            Counter(c["node"] for c in censored))


def backoff_errors(attempts, backoff) -> int:
    """Retries of one instance that start before their backoff ends.
    ``attempts``: (start, end, lost) of each attempt, ``lost`` true for a
    censored one.  An attempt is the n-th retry when every earlier attempt
    of the instance was lost by its start (otherwise it is a speculative
    copy beside a live one); it may start no earlier than the last loss
    plus ``min(base * 2**(n-1), cap)``."""
    base, cap = backoff
    errors, n = 0, 0
    attempts = sorted(attempts)
    for j, (start, _, _) in enumerate(attempts[1:], 1):
        earlier = attempts[:j]
        if all(lost and end <= start for _, end, lost in earlier):
            n += 1
            ready = (max(end for _, end, _ in earlier)
                     + min(base * 2.0 ** (n - 1), cap))
            errors += start < ready - BACKOFF_SLACK
    return errors


def fault_schedule_errors(records, censored, crash_at, max_attempts,
                          n_obs, backoff) -> int:
    """Faults of one run against its crashes.  ``records``: dicts with id,
    node, start, end (the completed instances); ``censored``: dicts with
    id, node, start, lost_at, reason (``"node"`` for a crash, else an
    attempt's own failure); ``crash_at``: node -> crash time; ``n_obs``:
    the observations the run absorbed.  Each of these is one error: an
    attempt that starts on a node at or after its crash; a completed
    attempt on a crashed node that ends after the crash; an attempt lost
    to a crash at another time than its node's crash; two attempts that
    overlap on one node; an instance tried more than ``max_attempts``
    times; a retry that starts before its backoff (``backoff``: the
    configuration's (``backoff_base``, ``backoff_cap``)) ends; an
    observation count other than the completed instances."""
    attempts = ([(r["id"], r["node"], r["start"], r["end"], None)
                 for r in records]
                + [(c["id"], c["node"], c["start"], c["lost_at"], c["reason"])
                   for c in censored])
    errors = 0
    spans: dict[str, list] = {}
    tries: dict[str, list] = {}
    for tid, node, start, end, reason in attempts:
        crash = crash_at.get(node, math.inf)
        errors += start >= crash
        if reason is None:
            errors += end > crash
        elif reason == "node":
            errors += end != crash
        spans.setdefault(node, []).append((start, end))
        tries.setdefault(tid, []).append((start, end, reason is not None))
    for s in spans.values():
        s.sort()
        errors += sum(s1 < e0 for (_, e0), (s1, _) in zip(s, s[1:]))
    errors += sum(len(t) > max_attempts for t in tries.values())
    errors += sum(backoff_errors(t, backoff) for t in tries.values())
    errors += n_obs != len(records)
    return int(errors)

