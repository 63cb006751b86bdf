"""cylon_tpu: a TPU-native distributed data-parallel relational framework.

Brand-new design with the capabilities of the reference library studied in
SURVEY.md (vibhatha/cylon): an Arrow-compatible columnar Table whose columns
live in TPU HBM as XLA device buffers, relational kernels lowered to
jit-compiled XLA computations, and a mesh communicator running the shuffle
over ICI via ``lax.all_to_all`` — no MPI, no per-row C++ loops.
"""
import jax

from .utils import envgate as _envgate

# Dataframe semantics need 64-bit ints/floats (CSV ints are int64, pandas
# float is float64). Opt out with CYLON_TPU_NO_X64=1 for pure-32-bit
# pipelines (TPU int64 is emulated; hot benchmarks should use 32-bit columns).
if not _envgate.NO_X64.raw():
    jax.config.update("jax_enable_x64", True)

# Optional platform pin (e.g. CYLON_TPU_PLATFORM=cpu for the virtual-device
# mesh), applied through jax.config before the first backend touch.
# Embedded/C-ABI consumers rely on this knob.
_platform = _envgate.PLATFORM.raw()
if _platform:
    jax.config.update("jax_platforms", _platform)

# Optional cold-compile/exec-speed tradeoff (XLA:TPU scheduling effort;
# benchmarks/compile_profile.py measures the tradeoff at the headline
# shape). CYLON_TPU_COMPILE_EFFORT=-1.0 compiles fastest; unset keeps
# XLA's default. The reference pays its optimization once at native build
# time — this is the knob for users who'd rather pay less per first-touch
# shape.
_effort = _envgate.COMPILE_EFFORT.raw()
if _effort:
    try:
        _effort_f = float(_effort)
    except ValueError:
        raise ValueError(
            f"CYLON_TPU_COMPILE_EFFORT={_effort!r} is not a float "
            "(expected e.g. -1.0 for fastest compile, 0.0 for default)"
        ) from None
    jax.config.update("jax_exec_time_optimization_effort", _effort_f)
    jax.config.update("jax_memory_fitting_effort", _effort_f)

from . import dtypes  # noqa: E402
from .column import Column  # noqa: E402
from .config import (  # noqa: E402
    CommConfig,
    CommType,
    CPUConfig,
    LocalConfig,
    MPIConfig,
    TPUConfig,
)
from .context import CylonContext  # noqa: E402
from .io import (  # noqa: E402
    CSVReadOptions,
    CSVWriteOptions,
    ParquetOptions,
    read_csv,
    read_parquet,
    write_csv,
    write_parquet,
)
from .frame import CylonEnv, DataFrame  # noqa: E402
from .frame import concat as concat_frames  # noqa: E402
from . import ordering  # noqa: E402
from .ordering import Ordering  # noqa: E402
from .table import Table, concat, merge  # noqa: E402
from . import compute  # noqa: E402
from .series import Series  # noqa: E402
from . import indexing  # noqa: E402
from .join_config import JoinAlgorithm, JoinConfig  # noqa: E402
from . import obs  # noqa: E402
from . import plan  # noqa: E402
from .plan import LazyFrame, col, lit  # noqa: E402
from . import fault  # noqa: E402
from .fault import (  # noqa: E402
    CylonError,
    QueryExecError,
    QueryTimeoutError,
    SchedulerClosedError,
    SpillIOError,
    StreamIngestError,
    WorkerDiedError,
)
from . import serve  # noqa: E402
from .serve import QueryFuture, ServeOverloadError  # noqa: E402
from . import stream  # noqa: E402
from .stream import AppendableTable, IncrementalView, Subscription  # noqa: E402
from .indexing.index import (  # noqa: E402
    CategoricalIndex,
    HashIndex,
    Index,
    IntegerIndex,
    LinearIndex,
    NumericIndex,
    PyRangeIndex,
)

__version__ = "0.1.0"

__all__ = [
    "CategoricalIndex",
    "Column",
    "CommConfig",
    "HashIndex",
    "Index",
    "JoinAlgorithm",
    "JoinConfig",
    "LazyFrame",
    "Ordering",
    "ordering",
    "col",
    "lit",
    "plan",
    "LinearIndex",
    "indexing",
    "IntegerIndex",
    "NumericIndex",
    "PyRangeIndex",
    "Series",
    "compute",
    "CommType",
    "CPUConfig",
    "CSVReadOptions",
    "CSVWriteOptions",
    "ParquetOptions",
    "CylonContext",
    "CylonEnv",
    "DataFrame",
    "concat_frames",
    "LocalConfig",
    "MPIConfig",
    "TPUConfig",
    "CylonError",
    "QueryExecError",
    "QueryFuture",
    "QueryTimeoutError",
    "SchedulerClosedError",
    "ServeOverloadError",
    "SpillIOError",
    "StreamIngestError",
    "WorkerDiedError",
    "fault",
    "serve",
    "stream",
    "AppendableTable",
    "IncrementalView",
    "Subscription",
    "Table",
    "concat",
    "dtypes",
    "merge",
    "obs",
    "read_csv",
    "read_parquet",
    "write_csv",
    "write_parquet",
]
