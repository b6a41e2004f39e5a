#!/usr/bin/env python3
"""How a configuration's ``hbm_bytes_per_check`` is frozen: one ordinary
run of a cell of that configuration which, once the tables are prepared,
also evaluates the program's gathered-bytes model of that commit on them
and prints it per table (the ``hbm_bytes_model`` line).  The total goes
into the configuration's file as a constant, with the commit and seed;
no run reads the live model afterwards.

    python3 chipbench/freeze_bytes.py --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import sys

import run


def freezing(cell, w, say):
    program = run.Program(cell, w, say)
    first_answer = program.first_answer

    def first_answer_then_model(rels):
        took = first_answer(rels)
        from gochugaru_tpu.utils.perf import gathered_bytes_model

        # the prepared tables of the one revision this world has
        (dsnap,) = program.client._dsnap_cache.values()
        model = gathered_bytes_model(dsnap)
        say("hbm_bytes_model", total=model.total, per_level=list(model.per_level),
            per_table=dict(sorted(model.per_table.items(), key=lambda kv: -kv[1])))
        return took

    program.first_answer = first_answer_then_model
    return program


if __name__ == "__main__":
    sys.exit(run.run_cell(run.parse_args(), make_program=freezing))
