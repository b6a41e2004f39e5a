"""Seconds of the prepare's expiry work that can be told apart (expiry
micros to the snapshot's epoch-relative seconds, the fold's until slices
packed for the device) this process paid before its first answer: the
program's own ``prepare.expiry_s`` timer, cumulative.  None where the
program has no such timer or it saw no expiry."""


def read(before, after, trace, cell):
    total = after.get("prepare.expiry_s.total_s")
    return float(total) if total else None
