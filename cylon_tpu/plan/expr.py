"""Structured column expressions for the planner.

``Table.select`` takes an opaque Python callable — fine for eager execution,
useless for an optimizer, which must know *which columns a predicate reads*
to push it below a projection, a shuffle, or one side of a join. ``Expr`` is
the minimal structured alternative: column refs, literals, comparisons,
arithmetic and boolean connectives, each knowing its column set, a
structural fingerprint (for the plan cache) and how to evaluate itself over
a dict of :class:`~cylon_tpu.column.Column`.

Null semantics are pandas-flavored: a row where any referenced column is
null evaluates to null, and ``Filter`` drops null rows (the same rows the
eager ``select``/``filter`` pair drops once the mask's validity is folded
in). Dictionary-encoded (string) columns compare against *string literals*
via the sorted dictionary: code order == value order, so every comparison is
two ``searchsorted`` bounds on the host and a code compare on device.
"""
from __future__ import annotations

import functools

import operator
from typing import FrozenSet, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..column import Column

KeyCol = Tuple[jax.Array, Optional[jax.Array]]


def _and_valid(a: Optional[jax.Array], b: Optional[jax.Array]):
    if a is None:
        return b
    if b is None:
        return a
    return a & b


class Expr:
    """Base class; build via :func:`col` / :func:`lit` and operators."""

    def columns(self) -> FrozenSet[str]:
        raise NotImplementedError

    def rename(self, mapping: Mapping[str, str]) -> "Expr":
        """Substitute column names (used when pushing a filter through a
        projection rename or down one side of a join)."""
        raise NotImplementedError

    def key(self) -> tuple:
        """Structural fingerprint (feeds the plan-fingerprint cache)."""
        raise NotImplementedError

    def evaluate(self, cols: Mapping[str, Column], lits=None) -> KeyCol:
        """-> (data, valid|None) arrays over the table's physical rows.
        ``lits`` maps ``id(Lit node)`` to what stands in for the literal's
        value inside a kernel, which takes its literals as arguments so
        that a sweep of a literal compiles nothing
        (:func:`numeric_literals`). A python scalar handed to a program is
        weakly typed there as it is here: either way ``x == 0.1`` compares
        a float32 column in float32."""
        raise NotImplementedError

    def shape_key(self) -> tuple:
        """:meth:`key` without the numeric literals' values: the identity
        of a compiled evaluation kernel."""
        raise NotImplementedError

    def _walk(self):
        """Every node of the expression, depth first, left to right."""
        yield self

    # -- operator sugar ----------------------------------------------------
    def _bin(self, op: str, other) -> "BinOp":
        return BinOp(op, self, other if isinstance(other, Expr) else Lit(other))

    def __eq__(self, other):  # noqa: A003 — expression building, not identity
        return self._bin("==", other)

    def __ne__(self, other):
        return self._bin("!=", other)

    def __lt__(self, other):
        return self._bin("<", other)

    def __le__(self, other):
        return self._bin("<=", other)

    def __gt__(self, other):
        return self._bin(">", other)

    def __ge__(self, other):
        return self._bin(">=", other)

    def __add__(self, other):
        return self._bin("+", other)

    def __sub__(self, other):
        return self._bin("-", other)

    def __mul__(self, other):
        return self._bin("*", other)

    def __truediv__(self, other):
        return self._bin("/", other)

    def __mod__(self, other):
        return self._bin("%", other)

    # a scalar on the left: ``1 - col("d")``
    def __radd__(self, other):
        return Lit(other)._bin("+", self)

    def __rsub__(self, other):
        return Lit(other)._bin("-", self)

    def __rmul__(self, other):
        return Lit(other)._bin("*", self)

    def __rtruediv__(self, other):
        return Lit(other)._bin("/", self)

    def __and__(self, other):
        return self._bin("&", other)

    def __or__(self, other):
        return self._bin("|", other)

    def __invert__(self):
        return UnOp("~", self)

    def __neg__(self):
        return UnOp("-", self)

    def __hash__(self):
        return hash(self.key())


class Col(Expr):
    def __init__(self, name: str):
        self.name = name

    def columns(self) -> FrozenSet[str]:
        return frozenset({self.name})

    def rename(self, mapping) -> "Col":
        return Col(mapping.get(self.name, self.name))

    def key(self) -> tuple:
        return ("col", self.name)

    shape_key = key

    def evaluate(self, cols, lits=None) -> KeyCol:
        c = cols[self.name]
        if c.dtype.is_dictionary:
            # codes only compare meaningfully against an encoded literal;
            # BinOp special-cases that pair before evaluating this side
            raise TypeError(
                f"string column {self.name!r} only supports comparison "
                "against a string literal in plan expressions"
            )
        return c.data, c.valid

    def __repr__(self):
        return f"col({self.name!r})"


class Lit(Expr):
    def __init__(self, value):
        if isinstance(value, Expr) or not isinstance(
            value, (int, float, bool, str, np.integer, np.floating, np.bool_,
                    np.datetime64)
        ):
            # fail at build time with a clear message — an unhashable value
            # would otherwise surface as a bare TypeError from the plan
            # fingerprint inside collect()
            raise TypeError(
                f"plan literals must be scalars (int/float/bool/str/"
                f"datetime64), got {type(value).__name__}"
            )
        self.value = value

    def physical(self):
        """The value as the columns hold it: a date or a time as int64
        nanoseconds (``Column.encode_host``), anything else as it is."""
        if isinstance(self.value, np.datetime64):
            return self.value.astype("datetime64[ns]").astype(np.int64)
        return self.value

    def columns(self) -> FrozenSet[str]:
        return frozenset()

    def rename(self, mapping) -> "Lit":
        return self

    def key(self) -> tuple:
        return ("lit", type(self.value).__name__, self.value)

    def shape_key(self) -> tuple:
        if isinstance(self.value, str):
            return self.key()
        return ("lit", type(self.value).__name__)

    def evaluate(self, cols, lits=None) -> KeyCol:
        if lits is not None and id(self) in lits:
            return lits[id(self)], None
        return jnp.asarray(self.physical()), None

    def __repr__(self):
        return repr(self.value)


_CMP = {"==", "!=", "<", "<=", ">", ">="}
_BOOL = {"&", "|"}


#: ``left op right`` on arrays or scalars, promoting as ``jnp`` does
_OPS = {
    "==": operator.eq, "!=": operator.ne, "<": operator.lt,
    "<=": operator.le, ">": operator.gt, ">=": operator.ge,
    "+": operator.add, "-": operator.sub, "*": operator.mul,
    "/": operator.truediv, "%": operator.mod,
    "&": operator.and_, "|": operator.or_,
}


class BinOp(Expr):
    def __init__(self, op: str, left: Expr, right: Expr):
        self.op = op
        self.left = left
        self.right = right

    def columns(self) -> FrozenSet[str]:
        return self.left.columns() | self.right.columns()

    def rename(self, mapping) -> "BinOp":
        return BinOp(self.op, self.left.rename(mapping), self.right.rename(mapping))

    def key(self) -> tuple:
        return ("bin", self.op, self.left.key(), self.right.key())

    def shape_key(self) -> tuple:
        return ("bin", self.op, self.left.shape_key(), self.right.shape_key())

    def _walk(self):
        yield self
        yield from self.left._walk()
        yield from self.right._walk()

    def _dict_literal_cmp(self, c: Column, value, flip: bool) -> KeyCol:
        """Dictionary-encoded column vs string literal: compare codes
        against the literal's position bounds in the SORTED dictionary."""
        op = self.op
        if flip:  # lit <op> col  ==  col <flipped-op> lit
            op = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}.get(op, op)
        d = c.dictionary
        lo = int(np.searchsorted(d, value, side="left"))
        hi = int(np.searchsorted(d, value, side="right"))
        code = c.data
        if op == "==":
            out = (code >= lo) & (code < hi)
        elif op == "!=":
            out = (code < lo) | (code >= hi)
        elif op == "<":
            out = code < lo
        elif op == "<=":
            out = code < hi
        elif op == ">":
            out = code >= hi
        else:  # ">="
            out = code >= lo
        return out, c.valid

    def evaluate(self, cols, lits=None) -> KeyCol:
        if self.op in _CMP:
            # string-column comparisons route through the dictionary
            l, r = self.left, self.right
            if isinstance(l, Col) and isinstance(r, Lit):
                c = cols[l.name]
                if c.dtype.is_dictionary:
                    return self._dict_literal_cmp(c, r.value, flip=False)
            if isinstance(l, Lit) and isinstance(r, Col):
                c = cols[r.name]
                if c.dtype.is_dictionary:
                    return self._dict_literal_cmp(c, l.value, flip=True)
        ld, lv = self.left.evaluate(cols, lits)
        rd, rv = self.right.evaluate(cols, lits)
        valid = _and_valid(lv, rv)
        return _OPS[self.op](ld, rd), valid

    def __repr__(self):
        return f"({self.left!r} {self.op} {self.right!r})"


class UnOp(Expr):
    def __init__(self, op: str, operand: Expr):
        self.op = op
        self.operand = operand

    def columns(self) -> FrozenSet[str]:
        return self.operand.columns()

    def rename(self, mapping) -> "UnOp":
        return UnOp(self.op, self.operand.rename(mapping))

    def key(self) -> tuple:
        return ("un", self.op, self.operand.key())

    def shape_key(self) -> tuple:
        return ("un", self.op, self.operand.shape_key())

    def _walk(self):
        yield self
        yield from self.operand._walk()

    def evaluate(self, cols, lits=None) -> KeyCol:
        d, v = self.operand.evaluate(cols, lits)
        return (~d if self.op == "~" else -d), v

    def __repr__(self):
        return f"{self.op}{self.operand!r}"


def col(name: str) -> Col:
    """Reference a column by name in a plan expression."""
    return Col(name)


def lit(value) -> Lit:
    """Wrap a Python scalar as a plan-expression literal."""
    return Lit(value)


def numeric_literals(expr: Expr) -> list:
    """The expression's non-string ``Lit`` nodes in walk order: what an
    evaluation kernel takes as arguments (a string literal is compared
    through a column's dictionary on the host and stays in the key)."""
    return [
        e for e in expr._walk()
        if isinstance(e, Lit) and not isinstance(e.value, str)
    ]


def _promotes_as(expr: Expr, dtype_of):
    """What ``expr`` promotes as: a dtype, or the python type of a
    python-scalar literal, which promotes weakly (``x + 1.5`` over a
    float32 column stays float32)."""
    if isinstance(expr, Col):
        return np.dtype(dtype_of(expr.name))
    if isinstance(expr, Lit):
        v = expr.physical()
        return type(v) if type(v) in (bool, int, float) else np.asarray(v).dtype
    if isinstance(expr, UnOp):
        return _promotes_as(expr.operand, dtype_of)
    if expr.op in _CMP:
        return np.dtype(bool)
    return _binop_dtype(
        expr.op, bool(jax.config.jax_enable_x64),
        *(_promotes_as(e, dtype_of) for e in (expr.left, expr.right)),
    )


@functools.lru_cache(maxsize=None)
def _binop_dtype(op: str, x64: bool, left, right) -> np.dtype:
    """What ``left op right`` promotes to (each a dtype, or the python type
    of a weak scalar), asked of JAX once a combination: ``jax.eval_shape``
    traces a jaxpr, and a plan built anew for every query (a report whose
    literal is a parameter) would trace one for every arithmetic node of
    its schema, every time."""
    sides = [
        d() if isinstance(d, type) else jax.ShapeDtypeStruct((), d)
        for d in (left, right)
    ]
    return np.dtype(jax.eval_shape(_OPS[op], *sides).dtype)


def result_dtype(expr: Expr, dtype_of) -> str:
    """Approximate physical dtype of ``expr`` over columns whose physical
    dtype ``dtype_of(name)`` gives (plan schema, display and fingerprint
    only: lowering keeps whatever the evaluation really promotes to)."""
    return str(np.dtype(jnp.result_type(_promotes_as(expr, dtype_of))))


def keep_mask(data: jax.Array, valid: Optional[jax.Array]) -> jax.Array:
    """An evaluated predicate as the boolean KEEP mask ``Table.filter``
    takes: a null predicate row (any referenced column null) is dropped."""
    if data.dtype != jnp.bool_:
        raise TypeError(f"filter predicate must be boolean, got {data.dtype}")
    return data if valid is None else data & valid


def filter_mask(expr: Expr, cols: Mapping[str, Column]) -> jax.Array:
    """Evaluate a predicate eagerly to its :func:`keep_mask`."""
    return keep_mask(*expr.evaluate(cols))
