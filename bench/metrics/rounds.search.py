"""Replay rounds per search: the count of ``search.round`` spans
(``whatif/search.py``, one ``evaluate`` each) over the searches of the
window. Moves ``search_s``."""


def read(rec):
    n = rec["counts"].get("searches", 0)
    rounds = sum(1 for s in rec["spans"] if s.name == "search.round")
    if not n or not rounds:
        return None
    return rounds / n
