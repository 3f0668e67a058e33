"""Pallas TPU kernels for the PPAC operation modes.

engine        — the unified dispatch surface: ``ppac_matmul(x, a, mode=...)``
                over a registry of every Table-I operation mode, with
                bit-identical 'pallas' / 'ref' / 'mxu' backends
tiling        — shared machinery: pad-to-tile planning on the TPU block
                rule, lane-tile streaming, the per-row popcount loop
binary_mvp    — packed 1-bit XNOR/AND popcount matmul (modes III-A/B/D/E)
bitserial_mvp — fused multi-bitplane MVP (mode III-C, all Table-I formats;
                ``ppac_matmul_planes`` serves pre-packed resident weights,
                ``ppac_matmul_resident`` is the zero-repack serving fast
                path: in-kernel activation slicing, MXU contraction)
hamming_topk  — fused streaming Hamming top-k / CAM δ-match (mode III-A
                associative retrieval at scale; never materializes [B, M])
gf2_tiled     — tiled GF(2) matmul with XOR-parity accumulation across
                lane tiles (mode III-D at n ≫ 256; operands stay packed)
"""
from .binary_mvp.ops import (  # noqa: F401
    and_dot,
    cam_match,
    gf2_matmul,
    hamming_similarity,
    inner_product_pm1,
    pla_eval,
)
from .bitserial_mvp.ops import (  # noqa: F401
    ppac_cycles,
    ppac_matmul_planes,
    ppac_matmul_resident,
)
from .bitserial_mvp.ops import ppac_matmul as multibit_matmul  # noqa: F401
from .engine import MODES, modes, ppac_matmul  # noqa: F401
from .gf2_tiled.ops import gf2_matmul_tiled  # noqa: F401
from .hamming_topk.ops import (  # noqa: F401
    hamming_threshold_match,
    hamming_topk,
)
