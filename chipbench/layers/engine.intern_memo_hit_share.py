"""Share of the window's node look-ups that the engine's 65,536-entry
memo answered without reaching the interner: ``intern.memo_hits`` /
(``intern.memo_hits`` + ``intern.lookups``), counted once a batch."""


def read(before, after, trace, cell):
    if "intern.lookups" not in after:
        return None
    hits = after.get("intern.memo_hits", 0) - before.get("intern.memo_hits", 0)
    asked = hits + after["intern.lookups"] - before.get("intern.lookups", 0)
    return 100.0 * hits / asked if asked > 0 else None
