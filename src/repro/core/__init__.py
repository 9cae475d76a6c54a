"""CCache core: on-demand privatization + flexible commutative merging."""

from repro.core.ccache import (
    CView,
    MergeTopology,
    PendingUpdate,
    c_read,
    c_update,
    c_write,
    commit,
    commit_deferred,
    commit_land,
    commit_launch,
    defer_cascade,
    hierarchical_merge,
    merge,
    overlap_cascade,
    partial_merge,
    privatize,
    reduce_update,
    settle_deferred,
    settle_inflight,
    soft_merge,
    tree_merge,
)
from repro.core.merge_plan import (
    MergeLevel,
    MergePlan,
    compile_plan,
)
from repro.core.blocked import (
    BlockedCache,
    c_read_row,
    cop_scatter,
    flush,
    init_cache,
    stats,
)
from repro.core.grad_merge import (
    merge_gradients,
    microbatched_value_and_grad,
    split_microbatches,
)
from repro.core.merge_functions import (
    ADD,
    BITWISE_AND,
    BITWISE_OR,
    BITWISE_XOR,
    COMPLEX_MUL,
    MAX,
    MIN,
    MUL,
    MergeFn,
    MergeFunctionRegistry,
    default_registry,
    dropping_add,
    int8_compressed_add,
    saturating_add,
)

__all__ = [k for k in dir() if not k.startswith("_")]
