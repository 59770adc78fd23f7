from .ops import (  # noqa: F401
    coerce_dense_sets,
    coerce_packed_sets,
    compact_row_words,
    pack_bitmask,
    pack_bitmask_csr,
    pack_bitmask_csr_compact,
    pack_row_words,
    packed_delta,
    packed_intersect_counts,
    packed_union,
    packed_union_delta,
    parsa_cost,
    parsa_cost_select,
    refine_sweep_chunk,
    sketch_cost_select,
    unpack_bitmask,
)
from .ref import (  # noqa: F401
    BIG,
    parsa_cost_ref,
    parsa_select_greedy_ref,
    parsa_select_ref,
    refine_sweep_ref,
    select_from_cost,
    select_greedy_from_cost,
    sketch_select_ref,
)
from .select import (  # noqa: F401
    SKETCH_KERNEL_MAX_WORDS,
    refine_sweep_kernel,
    sketch_select_kernel,
)
