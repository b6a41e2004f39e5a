#!/usr/bin/env python3
"""What has to come out as NOT correct.

The system runs no model and states no precision, so the control breaks the
guarantee the configurations state (every answer is the answer at the head
revision, never stale): ``stale`` puts the plain reference in the program's
place, reading a world that lacks the newest 1 % of the edges imported last.
``flipped`` and ``short`` are the faults a cell of this benchmark can have,
planted under the harness: one answer of one request altered where it is
produced, and half of a request's answers left out.  All three go through
the cell's entry point (``entries/<entry>.py``): its ``reference`` answers a
request of its type, its ``flipped`` / ``short`` alter an answer of its type.

    python3 chipbench/control.py --control stale --workload <cell> --seed <n> --seconds <s>

drives an ordinary run with that in place of the timed path; its result line
must say ``"correct": false``.  ``stale`` never loads the program.
"""

from __future__ import annotations

import argparse
import sys

import run

STALE_SHARE = 0.01


class StaleReference:
    """The reference, answering from a snapshot that misses the newest
    writes."""

    def __init__(self, cell, w, say) -> None:
        mod = cell["world"]
        newest = w[mod.NEWEST]  # columns of any width: (res, subj, ...)
        n = newest[0].shape[0]
        kept = n - max(int(n * STALE_SHARE), 1)
        stale = {**w, mod.NEWEST: tuple(c[:kept] for c in newest)}
        self.entry = cell["entry"].reference(cell, stale)
        say("control", kind="stale", hidden_edges=n - kept, of=mod.NEWEST)

    def first_answer(self, rels) -> float:
        return 0.0

    def close(self) -> None:
        pass


def broken(fault: str):
    """The program, with the entry's ``fault`` applied to the answer of the
    pool's first request wherever the window returns it."""

    def make(cell, w, say):
        program = run.Program(cell, w, say)
        entry, alter = program.entry, getattr(cell["entry"], fault)
        program.entry = lambda req: (
            alter(entry(req)) if req.index == 0 else entry(req))
        return program

    return make


CONTROLS = {"stale": StaleReference, "flipped": broken("flipped"),
            "short": broken("short")}


if __name__ == "__main__":
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--control", choices=sorted(CONTROLS), required=True)
    mine, rest = ap.parse_known_args()
    sys.exit(run.run_cell(run.parse_args(rest),
                          make_program=CONTROLS[mine.control]))
