"""The comparison that decides ``correct``: every answer the window
collected against the plain reference's answer, number by number.

A query module says how its output columns are held: ``KEYS`` identify a
row, ``EXACT`` columns (integers) must be equal, ``FLOAT`` columns are held
by their worst relative error to ``FLOAT_LIMIT``, and ``ORDERED`` says that
the rows' order is part of the answer."""
from __future__ import annotations

import sys
from typing import Dict, List, Sequence, Tuple

#: name -> (number, limit); a number above its limit is not correct
Compared = Dict[str, Tuple[float, float]]
#: the float error of an answer that holds a null or a NaN: above every
#: limit, and finite, so that the result line stays JSON
NOT_A_NUMBER = sys.float_info.max


def compare_answer(got: Sequence[tuple], want: Sequence[tuple],
                   query) -> Dict[str, float]:
    keys = tuple(query.KEYS)

    def key_of(row):
        return tuple(row[k] for k in keys)

    want_by_key = {key_of(r): r for r in want}
    got_by_key = {key_of(r): r for r in got}
    missing = len(set(want_by_key) ^ set(got_by_key))
    # a duplicated key is a wrong row too
    missing += len(got) - len(got_by_key)
    exact_bad = 0
    worst = 0.0
    for k, w in want_by_key.items():
        g = got_by_key.get(k)
        if g is None:
            continue
        if len(g) != len(w):
            exact_bad += 1
            continue
        for j in query.EXACT:
            if g[j] != w[j]:
                exact_bad += 1
        for j in query.FLOAT:
            if g[j] is None or g[j] != g[j]:  # null or NaN
                worst = NOT_A_NUMBER
                continue
            worst = max(worst, abs(g[j] - w[j]) / max(abs(w[j]), 1e-300))
    out_of_order = 0
    if query.ORDERED and not missing:
        out_of_order = sum(
            1 for g, w in zip(got, want) if key_of(g) != key_of(w))
    return {"rows_wrong": float(missing), "exact_wrong": float(exact_bad),
            "order_wrong": float(out_of_order), "float_rel_err": worst}


def compare_window(answers: List[Tuple[int, Sequence[tuple]]],
                   references: List[Sequence[tuple]],
                   queries: list, query_names: Sequence[str]) -> Compared:
    """Worst of each number over every answer of the window, each query's
    float error against that query's own limit. ``answers`` holds (index
    of the query, its collected rows). With several queries in the mix a
    number's name carries its query's."""
    out: Compared = {}
    for qi in sorted({qi for qi, _ in answers}):
        q = queries[qi]
        worst = {"rows_wrong": 0.0, "exact_wrong": 0.0, "order_wrong": 0.0,
                 "float_rel_err": 0.0}
        for ai, rows in answers:
            if ai != qi:
                continue
            for name, v in compare_answer(rows, references[qi], q).items():
                worst[name] = max(worst[name], v)
        suffix = "" if len(queries) == 1 else "." + query_names[qi]
        for name, v in worst.items():
            limit = float(q.FLOAT_LIMIT) if name == "float_rel_err" else 0.0
            out[name + suffix] = (v, limit)
    return out


def all_within(compared: Compared) -> bool:
    return all(v <= limit for v, limit in compared.values())
