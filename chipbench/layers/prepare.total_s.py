"""Seconds of the device prepare (table build on the host, packing, H2D)
this process paid before its first answer: the program's own
``prepare.total_s`` timer, cumulative."""


def read(before, after, trace, cell):
    total = after.get("prepare.total_s.total_s")
    return float(total) if total else None
