"""Plain semantics of the SQL the analytics mixes send, for the references.

Covers what the mixes use: ``SELECT <cols> FROM dana.predict(...)`` with a
``WHERE`` tree of AND / OR / NOT over ``c<i> <op> <number>`` comparisons, and
``COUNT(*)`` / ``AVG(prediction)``; and the heap pages a projected PREDICT
returns its rows in. Written from the SQL text and the page format alone; it
shares no code with the program's parser or page reader.
"""
from __future__ import annotations

import operator
import re

import numpy as np

_CMP = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
        ">=": operator.ge, "=": operator.eq, "==": operator.eq,
        "!=": operator.ne, "<>": operator.ne}
_TOK = re.compile(r"\s*(<=|>=|==|!=|<>|[<>=()]|[A-Za-z_]\w*|"
                  r"[-+]?(?:\d+\.?\d*|\.\d+)(?:[eE][-+]?\d+)?)")


def _tokens(text: str) -> list[str]:
    out, pos = [], 0
    text = text.strip().rstrip(";")
    while pos < len(text):
        m = _TOK.match(text, pos)
        if not m or m.end() == pos:
            raise ValueError(f"cannot read {text[pos:]!r}")
        out.append(m.group(1))
        pos = m.end()
    return out


def where_mask(where: str, column):
    """Evaluate a WHERE text; ``column(name)`` returns the column's array."""
    toks = _tokens(where)
    pos = 0

    def peek():
        return toks[pos].upper() if pos < len(toks) else None

    def take():
        nonlocal pos
        pos += 1
        return toks[pos - 1]

    def or_expr():
        v = and_expr()
        while peek() == "OR":
            take()
            v = v | and_expr()
        return v

    def and_expr():
        v = not_expr()
        while peek() == "AND":
            take()
            v = v & not_expr()
        return v

    def not_expr():
        if peek() == "NOT":
            take()
            return ~not_expr()
        if peek() == "(":
            take()
            v = or_expr()
            if take() != ")":
                raise ValueError("missing )")
            return v
        col, op, num = take(), take(), take()
        return _CMP[op](column(col), float(num))

    v = or_expr()
    if pos != len(toks):
        raise ValueError(f"trailing tokens {toks[pos:]}")
    return v


def column(x, y, name: str):
    """A selected column of the table: ``label`` or ``c<i>``."""
    return y if name.lower() == "label" else x[:, int(name[1:])]


# the heap page format (a PostgreSQL-style slotted page): a 32-byte header
# whose fifth word counts the tuples, then one line pointer word a tuple
# (offset in 8-byte units << 16 | length); a tuple is an 8-byte header (its
# length, its row id), its float32 columns, then one float32 label
_N_TUPLES_WORD, _LINE_PTR_WORD, _TUPLE_HEADER_WORDS = 4, 8, 2


def read_pages(pages, n_cols: int):
    """(columns (n, n_cols), labels (n,)) of float32 heap pages, in page and
    slot order, read through each page's line pointers."""
    w = np.asarray(pages, np.uint32)
    n_pages, page_words = w.shape
    counts = w[:, _N_TUPLES_WORD].astype(np.int64)
    slot = np.arange(int(counts.max(initial=0)))
    live = slot[None, :] < counts[:, None]
    lp = w[:, _LINE_PTR_WORD:_LINE_PTR_WORD + len(slot)][live].astype(np.int64)
    page = np.broadcast_to(np.arange(n_pages)[:, None], live.shape)[live]
    start = page * page_words + (lp >> 16) * 2  # 8-byte units -> words
    flat = w.reshape(-1)
    tuple_words = _TUPLE_HEADER_WORDS + n_cols + 1
    if np.any(flat[start] != 4 * tuple_words):
        raise ValueError(f"a tuple is not {n_cols} float32 columns and a label")
    body = flat[start[:, None] + _TUPLE_HEADER_WORDS + np.arange(n_cols + 1)]
    body = body.view(np.float32)
    return body[:, :n_cols], body[:, n_cols]


_SELECT = re.compile(
    r"^\s*SELECT\s+(?P<cols>.+?)\s+FROM\s+dana\.(?P<fn>\w+)\((?P<args>[^)]*)\)"
    r"(?:\s+WHERE\s+(?P<where>.+?))?\s*;?\s*$", re.I | re.S)


def parse_select(sql: str) -> dict:
    """``{verb, columns, aggregates, where}`` of one statement."""
    m = _SELECT.match(sql)
    if not m:
        raise ValueError(f"not a statement the reference reads: {sql!r}")
    cols = [c.strip() for c in m.group("cols").split(",")]
    verb = "PREDICT" if m.group("fn").lower() == "predict" else "TRAIN"
    aggs = [c.upper().replace(" ", "") for c in cols
            if c.upper().startswith(("COUNT(", "AVG(", "SUM("))]
    return {"verb": verb, "columns": cols, "aggregates": aggs or None,
            "where": m.group("where")}
