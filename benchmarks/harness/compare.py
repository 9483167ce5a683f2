"""The comparison that decides `correct`: every answer the window returned
against the plain reference's, value by value and in order.

The configurations state exact decimal arithmetic and complete results, so
each number's limit is 0 (PERF.md section 2 gives the readings).  Numbers:

  wrong_values   cells of the answers that differ from the reference's
                 (a missing or extra row counts all its cells)
  max_rel_gap    widest gap of a numeric cell, as a share of the
                 reference's value (says whether a miss is rounding or
                 rubbish)
  answers_missing  queries of the window that raised and so never answered
"""
from __future__ import annotations

import datetime
import decimal
from typing import Dict, List, Tuple

import pyarrow as pa

LIMITS = {"wrong_values": 0, "max_rel_gap": 0.0, "answers_missing": 0}


def _gap(x, y) -> float:
    """Relative gap of two cells; 1.0 where they cannot be subtracted."""
    if x == y:
        return 0.0
    numeric = (int, float, decimal.Decimal)
    if isinstance(x, numeric) and isinstance(y, numeric):
        x, y = decimal.Decimal(x), decimal.Decimal(y)
        return float(abs(x - y) / max(abs(y), decimal.Decimal(1)))
    if isinstance(x, datetime.date) and isinstance(y, datetime.date):
        return float(abs((x - y).days))
    return 1.0


def table_gaps(answer: pa.Table, reference: pa.Table) -> Tuple[int, float]:
    """-> (cells that differ, widest relative gap)."""
    names = reference.schema.names
    cells = max(answer.num_rows, reference.num_rows) * len(names)
    if answer.schema.names != names:
        return cells, 1.0
    wrong = abs(answer.num_rows - reference.num_rows) * len(names)
    widest = 1.0 if wrong else 0.0
    rows = min(answer.num_rows, reference.num_rows)
    for name in names:
        got = answer[name].slice(0, rows).to_pylist()
        want = reference[name].slice(0, rows).to_pylist()
        for x, y in zip(got, want):
            if x != y:
                wrong += 1
                widest = max(widest, _gap(x, y))
    return wrong, widest


def judge(answers: List[Tuple[str, pa.Table]],
          references: Dict[str, pa.Table], missing: int) -> dict:
    """All answers of a run -> {"correct", "numbers": {name: {value,
    limit}}, "compared"}.  Identical answers to one query are compared
    once and counted as often as they came."""
    wrong, widest = 0, 0.0
    seen: Dict[str, List[Tuple[pa.Table, int, float]]] = {}
    for name, table in answers:
        for known, w, g in seen.setdefault(name, []):
            if known.equals(table):
                break
        else:
            w, g = table_gaps(table, references[name])
            seen[name].append((table, w, g))
        wrong += w
        widest = max(widest, g)
    values = {"wrong_values": wrong, "max_rel_gap": widest,
              "answers_missing": missing}
    numbers = {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}
    return {"correct": bool(answers) and all(
                n["value"] <= n["limit"] for n in numbers.values()),
            "compared": len(answers), "numbers": numbers}
