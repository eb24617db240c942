"""Rows the operators emitted (``EngineStats.rows_emitted``) per match, over
the window's queries: the work the plan makes for each answer."""


def read(run):
    c = run["counters"]
    if not c.get("matches") or "rows_emitted" not in c:
        return None
    return c["rows_emitted"] / c["matches"]
