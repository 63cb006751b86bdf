"""Lazy logical-plan layer with a rule-based query optimizer.

The reference executes every relational op eagerly and never optimizes
across ops (SURVEY.md: DistributedHashGroupBy always materializes the join,
then groups — groupby/groupby.cpp:33-91). This package adds the missing
cross-op layer:

- :mod:`nodes` — the logical-plan IR (``Scan``/``Project``/``Filter``/
  ``WithColumns``/``Join``/``GroupBy``/``Sort``/``Shuffle``/``Union``/
  ``Limit``) with schema and partitioning propagation;
- :mod:`expr` — the tiny column-expression language filters and computed
  columns are written in (structured, so the optimizer can see which
  columns an expression touches);
- :mod:`rules` — the rule-based rewriter: filter pushdown, projection
  pushdown, redundant-shuffle elimination, fused join->groupby-SUM pushdown
  (lowers to ``ops.join.join_sum_by_key_pushdown``), a filter under an
  aggregate as the aggregate's row mask;
- :mod:`lower` — lowering of an optimized plan onto the existing eager
  ``Table`` ops;
- :mod:`lazy` — the user-facing ``LazyFrame`` (``Table.lazy()``), with
  ``.explain()`` and ``.collect()`` plus the plan-fingerprint executable
  cache in ``engine.py``.
"""
from .expr import Expr, col, lit
from .lazy import LazyFrame

__all__ = ["Expr", "LazyFrame", "col", "lit"]
