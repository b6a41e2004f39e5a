"""What the two check entries share: a request is a list of (resource,
subject) probes of the world's ``PROBE``, the answer one boolean a probe.
Only ``bind`` — which call of the client is timed — differs between them.

Traffic parameters read here: ``request_checks`` (the sizes the pool cycles
through), ``pool_requests``, ``warm_request_checks``.
"""

from __future__ import annotations

import numpy as np

from _judged import record

#: throughput metric -> the key of ``tally`` it is the rate of
RATES = {"checks_per_s": "checks"}


class Request:
    __slots__ = ("index", "res", "subj", "rels")

    def __init__(self, index, res, subj, rels):
        self.index, self.res, self.subj, self.rels = index, res, subj, rels


def to_rels(probe: dict, res, subj) -> list:
    from gochugaru_tpu import rel

    (rt, rp), perm, (st, sp) = probe["resource"], probe["permission"], probe["subject"]
    mk = rel.must_from_triple
    return [mk(f"{rt}:{rp}{r}", perm, f"{st}:{sp}{s}")
            for r, s in zip(res.tolist(), subj.tolist())]


def requests(cell: dict, w: dict, rng) -> list:
    """The distinct requests the callers cycle through.  Every seed gives
    the same multiset of request sizes, in another order, with other
    probes."""
    traffic, mod = cell["traffic"], cell["world"]
    sizes = traffic["request_checks"]
    counts = np.array([sizes[i % len(sizes)]
                       for i in range(traffic["pool_requests"])])
    rng.shuffle(counts)
    res, subj = mod.make_probes(w, cell["sizes"], rng, int(counts.sum()))
    rels = to_rels(mod.PROBE, res, subj)
    ends = np.cumsum(counts)
    return [Request(i, res[e - n:e], subj[e - n:e], rels[e - n:e])
            for i, (n, e) in enumerate(zip(counts.tolist(), ends.tolist()))]


def warm_requests(cell: dict, w: dict, rng) -> list:
    """One request of each size the mix warms, sent once before the warm
    loop."""
    mod = cell["world"]
    return [Request(-1, r, s, to_rels(mod.PROBE, r, s))
            for r, s in (mod.make_probes(w, cell["sizes"], rng, n)
                         for n in cell["traffic"]["warm_request_checks"])]


def tally(answers: list) -> dict:
    """An answer counts for as many operations as it holds verdicts."""
    return {"checks": sum(len(a) for a in answers)}


def judge(cell: dict, w: dict, pool: list, log: list, hung: int) -> dict:
    """Every answer the window returned against the plain reference, which
    runs once over each distinct request that was sent.  Exact: both
    limits are 0."""
    mod = cell["world"]
    used = sorted({e[0] for e in log})
    expected = {}
    if used:
        want = mod.reference(w, cell["sizes"])(
            np.concatenate([pool[i].res for i in used]),
            np.concatenate([pool[i].subj for i in used]))
        at = 0
        for i in used:
            n = pool[i].res.shape[0]
            expected[i] = want[at:at + n]
            at += n
    wrong = compared = 0
    unanswered = hung
    for index, _sent, _answered, out in log:
        if isinstance(out, Exception):
            unanswered += 1
            continue
        exp = expected[index]
        got = np.fromiter(out, bool, len(out))
        if got.shape != exp.shape:
            wrong += exp.shape[0]
        else:
            wrong += int((got != exp).sum())
        compared += exp.shape[0]
    return record(wrong, unanswered, compared)


# -- for control.py: the reference in the program's place, planted faults ------


def reference(cell: dict, w: dict):
    """``answer(request)`` from the world's plain reference over ``w``."""
    check = cell["world"].reference(w, cell["sizes"])
    return lambda req: check(req.res, req.subj).tolist()


def flipped(answer: list) -> list:
    return [not answer[0]] + list(answer[1:])


def short(answer: list) -> list:
    return list(answer[:len(answer) // 2])
