from repro.kernels.block_fp.ops import (  # noqa: F401
    block_fingerprint,
    fingerprint_tree,
    gather_blocks,
    kernel_path,
    leaves_match,
    tree_to_host,
)
from repro.kernels.block_fp.ref import (  # noqa: F401
    DEFAULT_BLOCK_BYTES,
    LeafFP,
    dirty_block_indices,
    fingerprint_array,
    fingerprint_bytes,
)
