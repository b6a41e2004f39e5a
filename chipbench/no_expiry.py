#!/usr/bin/env python3
"""The upper reading of a world whose edges expire (``worlds/rbac_exp.py``):
the same world loaded with no expiry at all (under the world's
``PLAIN_SCHEMA``), so that the edges already expired grant again, judged by
the same reference.  Its result line has to
say ``"correct": false``, and the ``predicted`` event before it gives the
wrong answers the reference predicts for the answers the window returned:
the checks on which the reference over every stored edge and the reference
over the live edges disagree.

    python3 chipbench/no_expiry.py --workload rbac10m_exp.bulk --seed <n> --seconds <s>
"""

from __future__ import annotations

import functools
import sys
import types

import numpy as np

import run


def _module(mod, name: str, **swap):
    """A copy of module ``mod`` with some of its names swapped."""
    out = types.ModuleType(f"{mod.__name__}_{name}")
    out.__dict__.update(vars(mod))
    out.__dict__.update(swap)
    return out


def every_edge(w: dict) -> dict:
    """The world with no edge expired: the offsets of every expiring edge
    list set to 0."""
    return {k: (v[0], v[1], np.zeros_like(v[2])) if isinstance(v, tuple)
            and len(v) == 3 else v for k, v in w.items()}


def predicted_wrong(cell, w, pool, log) -> int:
    """Answers of ``log`` on which the two references disagree."""
    mod, sizes = cell["world"], cell["sizes"]
    live, stored = mod.reference(w, sizes), mod.reference(every_edge(w), sizes)
    differ = {}
    for index, _sent, _answered, out in log:
        if isinstance(out, Exception):
            continue
        if index not in differ:
            columns = pool[index].columns
            differ[index] = int((live(*columns) != stored(*columns)).sum())
    return sum(differ[e[0]] for e in log if not isinstance(e[3], Exception))


def unexpiring(cell, w, say):
    mod, entry = cell["world"], cell["entry"]
    world = _module(mod, "no_expiry", SCHEMA=mod.PLAIN_SCHEMA,
                    load_edges=functools.partial(mod.load_edges, expiring=False))
    program = run.Program({**cell, "world": world}, w, say)

    def judge(cell, w, pool, log, hung):
        say("predicted", wrong_answers=predicted_wrong(cell, w, pool, log))
        return entry.judge(cell, w, pool, log, hung)

    cell["entry"] = _module(entry, "predicting", judge=judge)
    return program


if __name__ == "__main__":
    sys.exit(run.run_cell(run.parse_args(), make_program=unexpiring))
