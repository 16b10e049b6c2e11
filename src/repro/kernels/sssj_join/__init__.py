from .compact import (  # noqa: F401
    PairBuffer,
    PairCandidates,
    compact_pairs,
    merge_candidates,
    tile_candidates,
    tile_emit_counts,
)
from .gate import (  # noqa: F401
    GATE_KERNEL,
    StripSummary,
    init_strip_summary,
    refresh_strip_summary,
    strip_gate,
    summarize_strips,
)
from .kernel import CANDIDATE_KERNEL, tpu_kernels  # noqa: F401
from .ops import (  # noqa: F401
    JoinCandidates,
    NEG_UID,
    sssj_join_candidates,
    sssj_join_scores,
    sssj_join_tiles,
    suffix_chunk_norms,
)
from .ref import sssj_join_ref  # noqa: F401
