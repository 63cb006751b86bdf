"""``expr_eval_ms``: device self time a query of the operations whose stage
is ``expr.eval`` (a plan's predicate and computed columns, evaluated in
``jit_expr_eval``), first device. See ``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    return stage_times.stage_ms(obs, "expr.eval")
