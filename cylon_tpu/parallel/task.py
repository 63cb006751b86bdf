"""Task-based all-to-all: more logical partitions than physical workers.

Reference analog: the experimental ``LogicalTaskPlan`` + ``ArrowTaskAllToAll``
(cpp/src/cylon/arrow/arrow_task_all_to_all.h:23-40, .cpp): rows are hashed
into T logical TASKS, each task is owned by one WORKER, and the shuffle
routes by the task->worker map so task-parallel engines can over-decompose
(T >> P) for load balancing / composability.

TPU-native design: the task id is a device column, routing is one gather
through the task->worker map inside the same fused shuffle kernel every
other repartition uses (Table._shuffle_impl kind='task'), and per-task
subtables come from the vectorized filter. No per-task channels or
callbacks — the mesh collective IS the channel.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Union

import numpy as np


class LogicalTaskPlan:
    """Task -> worker ownership map (reference arrow_task_all_to_all.h:23-40:
    task_source_of_operation / worker_for_task tables).

    ``assignments`` may be an explicit {task_id: worker} dict or an int task
    count (tasks then spread round-robin over ``world`` workers).
    """

    def __init__(
        self,
        assignments: Union[int, Dict[int, int]],
        world: int,
    ):
        if isinstance(assignments, int):
            if assignments <= 0:
                raise ValueError("need at least one task")
            self.n_tasks = assignments
            self.worker_for_task = np.arange(self.n_tasks, dtype=np.int32) % world
        else:
            if len(assignments) == 0:
                raise ValueError("need at least one task")
            if sorted(assignments.keys()) != list(range(len(assignments))):
                raise ValueError("task ids must be dense 0..T-1")
            self.n_tasks = len(assignments)
            self.worker_for_task = np.asarray(
                [assignments[t] for t in range(self.n_tasks)], np.int32
            )
        if self.n_tasks and (
            self.worker_for_task.min() < 0 or self.worker_for_task.max() >= world
        ):
            raise ValueError(f"worker ids must be in [0, {world})")
        self.world = world

    def worker_of(self, task: int) -> int:
        return int(self.worker_for_task[task])

    def tasks_of(self, worker: int) -> np.ndarray:
        return np.nonzero(self.worker_for_task == worker)[0]

    def __repr__(self):
        return f"LogicalTaskPlan(tasks={self.n_tasks}, world={self.world})"


def task_partition(
    table,
    hash_columns: Sequence[Union[str, int]],
    plan: LogicalTaskPlan,
) -> Dict[int, "object"]:
    """Hash rows into ``plan.n_tasks`` logical tasks, shuffle each task to
    its owning worker, and return {task_id: Table} — the per-task tables the
    reference's ArrowTaskAllToAll delivers through its receive callback.

    Every returned table's rows physically live on the owning worker's
    shard (verifiable via Table.row_counts).
    """
    import jax
    import jax.numpy as jnp

    from ..column import Column
    from ..dtypes import DataType, Type
    from ..engine import get_kernel, round_cap
    from ..ops import partition as _p

    if plan.world != table.world_size:
        raise ValueError(
            f"plan built for world={plan.world}, table has {table.world_size}"
        )
    T = plan.n_tasks
    names = table._resolve_cols(hash_columns)
    kcols = tuple(table._key_hash_cols(names))
    key = ("task_ids", tuple(names), T)

    def build():
        def kern(dp, rep):
            (kcols, counts) = dp
            return _p.hash_partition_ids(kcols, counts[0], T)

        return kern

    tasks = get_kernel(table.ctx, key, build)((kcols, table.counts_dev), ())
    t2 = table.add_column(
        "__task__", Column(tasks.astype(jnp.int32), DataType(Type.INT32), None, None)
    )
    shuffled = t2._shuffle_impl(
        kind="task", key_names=["__task__"], task_map=plan.worker_for_task
    )

    # split into per-task tables with ONE sort+count kernel (one host sync
    # for all T counts) and one cheap dynamic-slice dispatch per task — not
    # 2T filter dispatches with T syncs
    flat = shuffled._flat_cols()
    ti = shuffled.column_names.index("__task__")
    key2 = ("task_split_sort", ti, len(flat), T)

    def build_sort():
        def kern(dp, rep):
            (cols, counts) = dp
            n = counts[0]
            task_lane, _ = cols[ti]
            cap = task_lane.shape[0]
            live = jnp.arange(cap, dtype=jnp.int32) < n
            lane = jnp.where(live, task_lane, T)
            order = jnp.argsort(lane, stable=True).astype(jnp.int32)
            out = [
                (d[order], None if v is None else v[order]) for d, v in cols
            ]
            cnt = _p.bin_counts(jnp.clip(lane, 0, T), T)
            return out, cnt

        return kern

    sorted_cols, cnts = get_kernel(table.ctx, key2, build_sort)(
        (flat, shuffled.counts_dev), ()
    )
    from ..table import _fetch

    cnts = _fetch(cnts, "task.counts").reshape(table.world_size, T)  # [P, T]
    offs = np.concatenate(
        [np.zeros((table.world_size, 1), np.int64), np.cumsum(cnts, axis=1)],
        axis=1,
    )
    names_out = [n for n in shuffled.column_names if n != "__task__"]
    src = [
        (n, shuffled._columns[n]) for n in shuffled.column_names if n != "__task__"
    ]
    keep = [i for i, n in enumerate(shuffled.column_names) if n != "__task__"]

    def build_slice():
        def kern(dp, rep):
            (cols, start) = dp
            (dummy,) = rep
            cap_t = dummy.shape[0]
            # index gather, not dynamic_slice: XLA clamps a dynamic_slice
            # start so the window stays in bounds, which would silently
            # misalign tasks near the end of the shard; clipped gather rows
            # past the task's live count are dead padding anyway
            idx = start[0] + jnp.arange(cap_t, dtype=jnp.int32)
            out = []
            for i in keep:
                d, v = cols[i]
                safe = jnp.clip(idx, 0, d.shape[0] - 1)
                out.append(
                    (d[safe], None if v is None else v[safe])
                )
            return out

        return kern

    out: Dict[int, "object"] = {}
    for t in range(T):
        t_counts = cnts[:, t].astype(np.int64)
        cap_t = round_cap(int(t_counts.max()))
        start = jax.device_put(
            offs[:, t].astype(np.int32), table.ctx.sharding
        )
        key3 = ("task_split_slice", tuple(keep), len(flat), cap_t)
        cols_t = get_kernel(table.ctx, key3, build_slice)(
            (sorted_cols, start), (jnp.zeros((cap_t,), jnp.int8),)
        )
        out[t] = shuffled._rebuild_cols(src, cols_t, t_counts, cap_t)
    return out
