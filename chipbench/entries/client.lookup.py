"""Entry ``client.lookup``: one request is one
``Client.lookup_resources(ctx, cs, "<type>#<permission>", "<type>:<id>")`` or
``Client.lookup_subjects(ctx, cs, "<type>:<id>", "<permission>", "<type>")``,
drained to its end inside the timed call; the answer is the list of bare
ids.  The world gives the strata (``make_lookups``) and the plain reference
(``lookup_reference``); an answer is judged as a whole set of ids.

Traffic parameters read here: ``strata`` (stratum -> lookups in the pool),
``warm_strata``.
"""

from __future__ import annotations

import numpy as np

from _judged import record

#: throughput metric -> the key of ``tally`` it is the rate of
RATES = {"lookups_per_s": "lookups"}


class Request:
    """``key`` is the index of the user (resources) or the document
    (subjects); ``args`` what the client's call takes after (ctx, cs)."""

    __slots__ = ("index", "kind", "key", "stratum", "args")

    def __init__(self, index, kind, key, stratum, args):
        self.index, self.kind, self.key = index, kind, key
        self.stratum, self.args = stratum, args


def _requests(cell: dict, w: dict, rng, strata: dict, indexed: bool) -> list:
    mod = cell["world"]
    (rt, rp), perm, (st, sp) = (mod.PROBE[k] for k in
                                ("resource", "permission", "subject"))
    kinds, keys, names = mod.make_lookups(w, cell["sizes"], rng, strata)
    return [Request(i if indexed else -1, kind, key, name,
                    (f"{rt}#{perm}", f"{st}:{sp}{key}") if kind == mod.RESOURCES
                    else (f"{rt}:{rp}{key}", perm, st))
            for i, (kind, key, name) in enumerate(
                zip(kinds.tolist(), keys.tolist(), names.tolist()))]


def requests(cell: dict, w: dict, rng) -> list:
    """The distinct lookups the callers cycle through: the same strata on
    every seed, other keys, shuffled by the seed."""
    return _requests(cell, w, rng, cell["traffic"]["strata"], True)


def warm_requests(cell: dict, w: dict, rng) -> list:
    return _requests(cell, w, rng, cell["traffic"]["warm_strata"], False)


def bind(program):
    """The one call that is timed: the lookup, streamed to its end."""
    from_kind = {program.world.RESOURCES: program.client.lookup_resources,
                 program.world.SUBJECTS: program.client.lookup_subjects}
    return lambda ctx, req: list(from_kind[req.kind](ctx, program.cs, *req.args))


def tally(answers: list) -> dict:
    """A lookup is one operation, whatever it returns."""
    return {"lookups": len(answers), "ids": sum(len(a) for a in answers)}


def counters(before: dict, after: dict) -> dict:
    """What the program's lookup counters gained over the window (an event of
    the run, traced or not; the per-layer readers take their own)."""
    return {k: after[k] - before.get(k, 0) for k in sorted(after)
            if k.startswith(("lookups.", "lookup.", "spmm."))}


def describe(pool: list, done: list) -> dict:
    """Answer sizes and latencies of the window's lookups, by stratum: an
    event of the run, for PERF.md; no metric reads it."""
    by = {}
    for index, sent, answered, out in done:
        by.setdefault(pool[index].stratum, []).append(
            (len(out), 1000.0 * (answered - sent)))
    pct = lambda v, q: np.percentile(v, q).tolist()
    ids = [n for rows in by.values() for n, _ in rows]
    told = {name: {"lookups": len(rows),
                   "ids_p5_p50_p95_max": pct([n for n, _ in rows], [5, 50, 95, 100]),
                   "ms_p50_p95_max": pct([ms for _, ms in rows], [50, 95, 100])}
            for name, rows in sorted(by.items())}
    if ids:
        told["ids_p5_p25_p50_p75_p90_p95_p99"] = pct(ids, [5, 25, 50, 75, 90, 95, 99])
        told["ids_mean"] = float(np.mean(ids))
    return told


def _prefixes(mod) -> dict:
    """Kind of lookup -> the id prefix of what it returns."""
    return {mod.RESOURCES: mod.PROBE["resource"][1],
            mod.SUBJECTS: mod.PROBE["subject"][1]}


def _indices(ids: list, prefix: str) -> np.ndarray:
    """Bare ids back to index space; an id that is not ``<prefix><n>`` as the
    world writes it (no leading zero: ``d00`` is not ``d0``) becomes -1, which
    no reference answer holds."""
    cut = len(prefix)
    return np.array([int(s[cut:]) if s.startswith(prefix) and s[cut:].isdecimal()
                     and s[cut:] == str(int(s[cut:])) else -1 for s in ids], np.int64)


def judge(cell: dict, w: dict, pool: list, log: list, hung: int) -> dict:
    """Every answer the window returned against the plain reference, which
    runs once over each distinct lookup that was sent.  An answer is right
    when its ids, as a set, are the reference's set and none comes twice:
    ``wrong_answers`` counts the ids in the symmetric difference plus the
    repeats, ``answers_compared`` the ids expected.  Exact: limits 0."""
    mod = cell["world"]
    prefix = _prefixes(mod)
    answer = mod.lookup_reference(w, cell["sizes"])
    used = sorted({e[0] for e in log})
    expected = {}
    for kind in prefix:
        mine = [i for i in used if pool[i].kind == kind]
        expected.update(zip(mine, answer(kind, [pool[i].key for i in mine])))
    wrong = compared = 0
    unanswered = hung
    for index, _sent, _answered, out in log:
        if isinstance(out, Exception):
            unanswered += 1
            continue
        exp = expected[index]
        got = _indices(out, prefix[pool[index].kind])
        distinct = np.unique(got)
        wrong += (got.shape[0] - distinct.shape[0]
                  + np.setxor1d(distinct, exp, assume_unique=True).shape[0])
        compared += exp.shape[0]
    return record(int(wrong), unanswered, int(compared))


# -- for control.py: the reference in the program's place, planted faults ------


def reference(cell: dict, w: dict):
    """``answer(request)`` from the world's plain reference over ``w``."""
    answer = cell["world"].lookup_reference(w, cell["sizes"])
    prefix = _prefixes(cell["world"])
    return lambda req: [f"{prefix[req.kind]}{i}"
                        for i in answer(req.kind, [req.key])[0].tolist()]


def flipped(answer: list) -> list:
    """One id altered where it is produced."""
    return [answer[0] + "0"] + list(answer[1:]) if answer else ["0"]


def short(answer: list) -> list:
    return list(answer[:len(answer) // 2])
