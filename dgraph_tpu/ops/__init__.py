"""Batched set-algebra kernels over sorted uid sets.

TPU-native equivalent of the reference's algo/ package
(/root/reference/algo/uidlist.go:42-300): intersection, union (k-way merge
with dedup), difference, binary membership and CSR posting-list expansion —
re-designed as fixed-shape, mask-padded JAX programs instead of pointer
chasing over variable-length slices.
"""

from dgraph_tpu.ops.sets import (  # noqa: F401
    CHUNK,
    INLINE,
    SENT,
    bucket,
    bucket_fine,
    expand_inline_seg,
    sort_desc_free,
    pad_to,
    pad_rows,
    compact,
    sort_unique,
    intersect,
    difference,
    union,
    intersect_many,
    union_many,
    member_mask,
    mask_to_set,
    expand_csr,
    count_valid,
    rows_of,
    range_rows,
    frontier_rows,
)
from dgraph_tpu.ops.pallas_gather import (  # noqa: F401
    gather_pallas,
    gather_pallas_packed,
    gather_reference,
)
from dgraph_tpu.ops.order import (  # noqa: F401
    gather_ranks,
    segmented_sort_perm,
)
from dgraph_tpu.ops.batch import (  # noqa: F401
    difference_batch,
    expand_ascending,
    expand_filter_compact,
    expand_filter_compact_batch,
    intersect_batch,
    member_mask_batch,
    multi_hop,
    sort_unique_batch,
    union_many_batch,
)
from dgraph_tpu.ops.spgemm import (  # noqa: F401
    PredTiles,
    build_tiles,
    count_tile_blocks,
    est_tile_bytes,
    expand_counts,
    expand_mask,
    expand_mask_batch,
    intersect_masks,
    intersect_stack,
    intersect_stack_batch,
    mask_lanes,
    mask_to_uids,
    run_mask_chain,
    tile_budget,
    tile_size,
    triangle_mask,
    triangle_mask_batch,
    uids_to_mask,
)
from dgraph_tpu.ops import ref  # noqa: F401
