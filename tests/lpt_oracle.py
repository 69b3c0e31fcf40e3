"""Exact makespan oracle for the LPT-bound tests.

Branch and bound over every assignment of the weights to ``r`` bins,
largest weight first. A weight goes only to a bin already in use or to
the first empty one (the bins are interchangeable), and a branch stops
once its makespan reaches the best found so far. Exact, and fast for
the ten or so weights the tests give it.
"""


def exact_makespan(weights, r: int) -> int:
    """The optimal makespan (OPT) of ``weights`` on ``r`` identical bins."""
    w = sorted((int(x) for x in weights), reverse=True)
    loads = [0] * r
    best = sum(w)

    def place(i: int, used: int, cur: int) -> None:
        nonlocal best
        if i == len(w):
            best = min(best, cur)
            return
        if cur >= best:
            return
        for k in range(min(used + 1, r)):
            loads[k] += w[i]
            place(i + 1, max(used, k + 1), max(cur, loads[k]))
            loads[k] -= w[i]

    place(0, 0, 0)
    return best
