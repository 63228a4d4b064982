"""FractalSortCPU's contribution, adapted to TPU-native JAX (see DESIGN.md §2)."""

from repro.core.fractal_tree import (
    FractalHistogram,
    bit_reverse,
    build_histogram,
    ceil_log2,
    get_index,
    get_item,
    histogram_nbytes,
    merge_histograms,
    taper_levels,
    tapered_bits,
    tapered_dtype,
    trie_depth,
)
from repro.core.sort_plan import (
    DEFAULT_MAX_BINS_LOG2,
    DigitPass,
    ONEHOT_MAX_BITS,
    SortPlan,
    default_engine,
    make_sort_plan,
    rank_chunk_len,
    scatter_tile_len,
)
from repro.core.executor import (
    DistributedBackend,
    JnpBackend,
    PallasBackend,
    PassBackend,
    PlanExecutor,
)
from repro.core.fractal_sort import (
    PassStats,
    SortStats,
    fractal_argsort,
    fractal_rank,
    fractal_rank_scatter,
    fractal_rank_serial,
    fractal_sort,
    fractal_sort_batched,
    fractal_sort_pairs,
    fractal_sort_stats,
    rank_engine,
    reconstruct,
)
from repro.core.baselines import (
    bitonic_sort,
    bitonic_sort_stats,
    comparison_sort_stats,
    lsd_radix_sort,
    radix_sort_stats,
    xla_sort,
)
from repro.core.distributed import (
    distributed_fractal_argsort,
    distributed_fractal_sort,
    make_distributed_argsort,
    make_distributed_sort,
    make_distributed_sort_pairs,
    make_fragment_placer,
)
from repro.core.faults import (
    CorruptFragmentError,
    FaultPlan,
    FaultSpec,
    StoreError,
    StorePermanentError,
    TransientStoreError,
)
