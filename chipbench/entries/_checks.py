"""What the two check entries share: a request is a list of probes of the
world's ``PROBE``, the answer one boolean a probe.  A probe is a row of the
columns ``make_probes`` returns: the first two are (resource, subject) in
index space, a world may add more (a caller's tenant, ...), which its
``reference`` takes and its ``probe_rels`` turns into request context.  Only
``bind`` — which call of the client is timed — differs between the entries.

Traffic parameters read here: ``request_checks`` (the sizes the pool cycles
through), ``pool_requests``, ``warm_request_checks``.
"""

from __future__ import annotations

import numpy as np

from _judged import record

#: throughput metric -> the key of ``tally`` it is the rate of
RATES = {"checks_per_s": "checks"}


class Request:
    """``columns`` is what the reference takes, ``rels`` what the client
    takes: the same probes, row for row."""

    __slots__ = ("index", "columns", "rels")

    def __init__(self, index, columns, rels):
        self.index, self.columns, self.rels = index, columns, rels


def to_rels(probe: dict, res, subj) -> list:
    from gochugaru_tpu import rel

    (rt, rp), perm, (st, sp) = probe["resource"], probe["permission"], probe["subject"]
    mk = rel.must_from_triple
    return [mk(f"{rt}:{rp}{r}", perm, f"{st}:{sp}{s}")
            for r, s in zip(res.tolist(), subj.tolist())]


def probe_rels(mod, columns) -> list:
    """The client's ``Relationship`` of each probe: the world's own
    ``probe_rels(*columns)`` where it has one (the triple plus the request's
    caveat context), else the bare triples of its ``PROBE``."""
    if hasattr(mod, "probe_rels"):
        return mod.probe_rels(*columns)
    return to_rels(mod.PROBE, *columns)


def requests(cell: dict, w: dict, rng) -> list:
    """The distinct requests the callers cycle through.  Every seed gives
    the same multiset of request sizes, in another order, with other
    probes."""
    traffic, mod = cell["traffic"], cell["world"]
    sizes = traffic["request_checks"]
    counts = np.array([sizes[i % len(sizes)]
                       for i in range(traffic["pool_requests"])])
    rng.shuffle(counts)
    columns = mod.make_probes(w, cell["sizes"], rng, int(counts.sum()))
    rels = probe_rels(mod, columns)
    ends = np.cumsum(counts)
    return [Request(i, tuple(c[e - n:e] for c in columns), rels[e - n:e])
            for i, (n, e) in enumerate(zip(counts.tolist(), ends.tolist()))]


def warm_requests(cell: dict, w: dict, rng) -> list:
    """One request of each size the mix warms, sent once before the warm
    loop."""
    mod = cell["world"]
    return [Request(-1, columns, probe_rels(mod, columns))
            for columns in (mod.make_probes(w, cell["sizes"], rng, n)
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
            *(np.concatenate(c) for c in zip(*(pool[i].columns for i in used))))
        at = 0
        for i in used:
            n = pool[i].columns[0].shape[0]
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
    return lambda req: check(*req.columns).tolist()


def flipped(answer: list) -> list:
    return [not answer[0]] + list(answer[1:])


def short(answer: list) -> list:
    return list(answer[:len(answer) // 2])
